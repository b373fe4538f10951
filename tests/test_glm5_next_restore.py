"""GLM-5.3-Flash's engine checkpointed and restored in mid-decode: every
slot-state leaf (the delta-rule state, the convolution tail, the
indexer's accumulator) travels with the pages, at the tiny widths of
``tests/glm5_next_tiny.py``. The other engine-level cases are
``tests/test_glm5_next_engine.py``, the programs' own
``tests/test_glm5_next.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.glm5_next_tiny import (  # noqa: F401  (ckpt, model: fixtures)
    TOL, ckpt, make_engine, model, prompt_ids, reference_logits, run, run_one,
)


@pytest.mark.parametrize("lost", [None, "s", "conv"])
def test_checkpoint_restore_round_trips_a_stream_in_mid_decode(model, tmp_path,
                                                               lost):
    """With every leaf carried (each read back bit for bit, the
    accumulator of an unfinished block among them) the stream goes on as
    if nothing happened; with the states or the tails blanked it goes
    elsewhere."""
    cfg, params, _ = model
    prompt = prompt_ids(42, seed=61)  # ends two rows into a pooled block
    want = run_one(make_engine(cfg, params), prompt, 18)
    engine = make_engine(cfg, params)
    engine.submit("r", prompt, 18)
    head = []
    while len(head) < 5:
        head += [tok for _r, tok, _d in engine.step()]
    snap = engine.checkpoint_state()
    assert snap["slot_state"] is True
    engine.save_pools(tmp_path / "pools")
    fresh = make_engine(cfg, params)
    fresh.restore_pools(tmp_path / "pools")
    saved, back = (jax.tree.map(np.asarray, e.slot_state) for e in (engine, fresh))
    assert jax.tree.all(jax.tree.map(lambda a, b: (a == b).all(), saved, back))
    assert all(np.abs(leaf).max() > 0 for leaf in jax.tree.leaves(saved))
    if lost is not None:
        fresh.slot_state = {
            key: {name: jnp.zeros_like(leaf) if name == lost else leaf
                  for name, leaf in leaves.items()}
            for key, leaves in fresh.slot_state.items()}
    fresh.restore_state(snap)
    rest = run(fresh, "r")
    if lost is None:
        assert head + rest == want
    else:
        logits = reference_logits(model, prompt + head + rest)
        chosen = logits[np.arange(len(prompt) - 1, len(logits) - 1), head + rest]
        top = logits[len(prompt) - 1 : -1].max(-1)
        assert head + rest != want or (top - chosen).max() > TOL
