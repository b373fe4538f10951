"""GLM-5.3-Flash through ``PagedBatchEngine`` itself (scheduler,
allocator, K-tick window, preemption, the chunk ahead) at the tiny widths of ``tests/glm5_next_tiny.py``, against the
plain float32 reference. The programs' own cases are
``tests/test_glm5_next.py``, checkpoint and restore
``tests/test_glm5_next_restore.py``: three files are three workers' under
``--dist loadfile``, and none of them is the run.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dora_tpu.models.hf import glm5_next as G
from dora_tpu.models.hf import glm5_next_reference as R
from tests.glm5_next_tiny import (  # noqa: F401  (ckpt, model: fixtures)
    CHUNK, K_TICKS, KPOOL, PAGE, SLOTS, TOL, TOPK, Served, ckpt, held_of,
    make_engine, model, prompt_ids, reference_logits, run, run_one,
)


def test_a_short_and_a_long_stream_decode_in_one_window(model):
    """Rows of one tick below ``index_topk`` and several times past it:
    one row attends ``0..t``, the other its picked blocks and its tail,
    and the frozen slot nothing; the counters say so."""
    cfg, params, _ = model
    short, long_ = prompt_ids(4, seed=21), prompt_ids(70, seed=22)
    follow = {0: prompt_ids(10, seed=23), 2: prompt_ids(10, seed=24)}
    served = Served(cfg, params)
    served.prefill(0, short)
    served.prefill(2, long_)
    got = {0: [], 2: []}
    for k in range(10):
        rows = served.tick({b: follow[b][k] for b in follow})
        for b in follow:
            got[b].append(rows[b])
    for b, prompt in ((0, short), (2, long_)):
        want = reference_logits(model, prompt + follow[b])[len(prompt):]
        assert np.abs(np.stack(got[b]) - want).max() < TOL
    kda = {k: int(v) for k, v in served.stats["kda"].items()}
    pos = [4 + k for k in range(10)] + [70 + k for k in range(10)]

    def picked(t):
        return TOPK + t % KPOOL + 1 if t >= TOPK else t + 1

    assert kda["kda_decode_ticks"] == 10 and kda["kda_row_ticks"] == 4 * 20
    assert kda["dsa_rows_in_context"] == sum(p + 1 for p in pos)
    assert kda["dsa_rows_picked"] == sum(picked(p) for p in pos)
    assert kda["dsa_rows_fetched"] == 20 * (TOPK + KPOOL)
    assert kda["dsa_row_ticks_selecting"] == 10
    assert kda["dsa_index_rows_scored"] == sum(p // KPOOL for p in pos if p >= TOPK)
    assert kda["kda_chunks"] == 1 + 3 and kda["kda_chunk_rows"] == 74
    chunk_pos = list(range(4)) + list(range(70))
    assert kda["dsa_chunk_rows_in_context"] == sum(p + 1 for p in chunk_pos)
    assert kda["dsa_chunk_rows_picked"] == sum(picked(p) for p in chunk_pos)
    assert kda["dsa_chunk_rows_selecting"] == 70 - TOPK
    # the dense product under the mask sweeps blocks of 16 cached rows to the
    # chunk's last row (padding included), for every valid row
    assert kda["dsa_chunk_rows_fetched"] == 4 * 32 + 32 * 32 + 32 * 64 + 6 * 96
    assert kda["dsa_rows_picked"] / kda["dsa_rows_in_context"] < 0.5


def test_engine_tokens_are_the_references_argmax(model):
    """Through ``PagedBatchEngine`` itself (scheduler, allocator, K-tick
    window, greedy head): every emitted token is the top of the
    reference's teacher-forced logits, or within TOL of it."""
    cfg, params, _ = model
    engine = make_engine(cfg, params)
    prompts = {"a": prompt_ids(6, 31), "b": prompt_ids(50, 32),
               "c": prompt_ids(33, 33)}
    for rid, prompt in prompts.items():
        engine.submit(rid, prompt, 13)
    out = {rid: [] for rid in prompts}
    for _ in range(200):
        for rid, tok, _done in engine.step():
            out[rid].append(tok)
        if not engine.active:
            break
    for rid, prompt in prompts.items():
        assert len(out[rid]) == 13
        want = reference_logits(model, prompt + out[rid])[len(prompt) - 1 : -1]
        chosen = want[np.arange(13), out[rid]]
        assert (want.max(-1) - chosen).max() < TOL
    report = engine.model_counters()
    assert report["kv_bytes_per_token"] == (16 + 8 // 4) * 4  # one layer, f32
    assert report["kda_state_bytes"] == SLOTS * (
        4 * (4 * 16 * 16 * 4 + 3 * 3 * 64 * 4) + 8 * 4)
    assert report["moe_tokens"] > 0 and len(report["moe_expert_tokens"]) == 2
    assert report["kda_row_ticks"] > 0 and report["dsa_row_ticks_selecting"] > 0
    # three slots: groups of one row, so a group a live row a tick
    assert G.decode_group(SLOTS) == 1
    assert report["dsa_decode_groups"] == report["kda_row_ticks"] // 4
    assert report["dsa_rows_fetched"] == (
        report["dsa_decode_groups"] * (TOPK + KPOOL))
    assert set(engine.pools) == {"4"} and set(engine.pools["4"]) == {"kv", "ik"}
    assert engine.pools["4"]["ik"].shape[1:] == (PAGE // KPOOL, 8)
    assert {k: set(v) for k, v in engine.slot_state.items()} == {
        "0": {"s", "conv"}, "1": {"s", "conv"}, "2": {"s", "conv"},
        "3": {"s", "conv"}, "4": {"acc"}}


def test_an_audited_engine_hands_out_its_selection_and_serves_the_same(model):
    """``make_paged_engine(picks=True)`` (a cache audit's, through
    ``llm_server.make_engine``'s keywords): the tokens of a served engine,
    and behind every chunk and window each sparse-latent layer's picked
    blocks and output rows; a served engine keeps nothing. A window's tick
    ``j`` of a row that came in at position ``p`` is the row at ``p + j``:
    its picks are the reference's at that position."""
    from dora_tpu.nodehub import llm_server

    cfg, params, rp = model
    prompt = prompt_ids(41, 71)
    tokens, engines = {}, {}
    for picks in (False, True):
        engine = engines[picks] = make_engine(cfg, params, picks=picks)
        seen = []
        if picks:
            window = engine.window_step

            def window_step(tokens, pools, positions, *rest, window=window):
                first = int(np.asarray(positions)[0])
                out = window(tokens, pools, positions, *rest)
                seen.append((first, np.asarray(engine.selection["window"][0]["picked"])[:, 0]))
                return out

            engine.window_step = window_step
        engine.submit("a", prompt, 9)
        tokens[picks] = [tok for _ in range(40) for _, tok, _d in engine.step()]
    assert tokens[True] == tokens[False] and len(tokens[True]) == 9
    assert engines[False].selection == {"chunk": [], "window": []}
    look = engines[True].selection
    assert look["chunk"][0]["picked"].shape == (CHUNK, TOPK // KPOOL)
    assert look["chunk"][0]["attended"].shape == (CHUNK, 64)
    assert look["window"][0]["picked"].shape == (K_TICKS, SLOTS, TOPK // KPOOL)
    assert look["window"][0]["attended"].shape == (K_TICKS, SLOTS, 64)
    _, kept = R.forward(rp, cfg, jnp.asarray(prompt + tokens[True]),
                        held=held_of(cfg), rows=True)
    own = np.asarray(kept[4]["picked"])
    assert seen[0][0] == len(prompt)
    for first, picked in seen:
        for j in range(K_TICKS):
            if first + j < len(prompt) + 8:  # the ticks that fed a token
                assert set(picked[j]) == set(own[first + j]), (first, j)
    # the server's way in: keywords go to the module's engine as they stand
    with pytest.raises(TypeError, match="no_such_keyword"):
        llm_server.make_engine(params, cfg, module=G, no_such_keyword=1)


# -- preemption and the chunk ahead keep every slot-state leaf right ------------


def test_preempt_and_resume_give_the_first_streams_tokens(model):
    cfg, params, _ = model
    prompt = prompt_ids(29, seed=51)
    want = run_one(make_engine(cfg, params), prompt, 14)
    engine = make_engine(cfg, params)
    engine.submit("r", prompt, 14)
    head = []
    while len(head) < 5:
        head += [tok for _r, tok, _d in engine.step()]
    engine.preempt("r")
    assert engine.active == 0
    # another stream dirties the slot's state, then the first comes back
    assert len(run_one(engine, prompt_ids(40, seed=52), 9, "other")) == 9
    assert run_one(engine, prompt, 14) == want


def test_chunks_ahead_of_their_period_give_the_tokens_of_step(model):
    """``dispatch → ahead → collect`` against ``step()`` where a chunk
    is NOT idempotent (the delta-rule layers' state a slot): a chunk
    that goes ahead reads the state the running window leaves and is
    adopted once, so three- and six-chunk prompts beside streams that
    decode give the tokens they give in line."""
    cfg, params, _ = model
    prompts = {"a": prompt_ids(21, seed=61), "long": prompt_ids(45, seed=62),
               "b": prompt_ids(9, seed=63)}
    caps = {"a": 9, "long": 14, "b": 6}

    engine = make_engine(cfg, params)

    def serve(halves: bool):
        ran, ahead = engine.chunks_run, engine.chunks_ahead
        for rid, prompt in prompts.items():
            engine.submit(rid, prompt, caps[rid])
        got = {rid: [] for rid in prompts}
        for _ in range(300):
            if not engine.active:
                break
            if halves:
                out = engine.dispatch()
                engine.ahead()
                out += engine.collect()
            else:
                out = engine.step()
            for rid, tok, _done in out:
                got[rid].append(tok)
        engine.check_invariants()
        return got, engine.chunks_run - ran, engine.chunks_ahead - ahead

    # the same engine, so the same two programs: in line, then ahead
    # (every slot is taken again from zeros)
    want, line_chunks, line_ahead = serve(False)
    got, chunks, ahead = serve(True)
    assert got == want and [len(got[r]) for r in caps] == list(caps.values())
    assert line_ahead == 0 and ahead >= 3 and chunks == line_chunks
