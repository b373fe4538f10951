"""The engine's state snapshot (models/batch_engine.py +
models/prefix_cache.py): the prefix cache beside a slot state, on the tiny
Olmo-Hybrid (tests/olmo_hybrid_tiny.py: page 8, chunk 32, 3 slots).

(a) a conversation served with the cache on emits the tokens it emits with
    the cache off, and by hand the rows past a grant have the logits of a
    prefill from row 0, bit for bit;
(b) a grant lands on a snapshot's depth and never between two; a prompt
    under one chunk saves none and is granted none;
(c) custody: rows are counted as pages are, through eviction under a full
    snapshot pool and under a short free list, through ``preempt`` and
    through ``save_pools`` / ``restore_state``;
(d) a chunk that went ``ahead()`` still saves the right state;
(e) the four other slot-state models' engines still refuse a prefix cache,
    each by the message that names the snapshot;
(f) the second snapshot, where a prompt branches: two prompts that share
    2.5 chunks and then differ leave a row at the last chunk edge inside
    what they share, a third is granted to it; a session-shaped pair saves
    none; the rows are counted and evicted as the others are.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dora_tpu.models import paged_model as PM
from dora_tpu.models.batch_engine import PagedBatchEngine
from tests.olmo_hybrid_tiny import (  # noqa: F401  (fixtures)
    CHUNK, MAX_SEQ, PAGE, SLOTS, Served, ckpt, make_engine, model, prompt_ids,
    run)


def conversation(turns: int, first: int, seed: int, answer: int = 6,
                 message: int = 11):
    """``turns`` prompts, each the one before + ``answer`` tokens (stand-ins
    for what the model said: the test does not need them to be) + a new
    message: turn n's prompt begins with turn n-1's, token for token."""
    rng = np.random.default_rng(seed)
    draw = lambda n: rng.integers(1, 256, size=n).tolist()  # noqa: E731
    prompts = [draw(first)]
    for _ in range(turns - 1):
        prompts.append(prompts[-1] + draw(answer) + draw(message))
    return prompts


def serve_all(engine, prompts, max_new=5, halves=False, prefix="t"):
    """The prompts one after another; -> [tokens of each]."""
    out = []
    for n, prompt in enumerate(prompts):
        rid = f"{prefix}{n}"
        engine.submit(rid, prompt, max_new)
        got = []
        for _ in range(400):
            if halves:
                step = engine.dispatch()
                engine.ahead()
                step += engine.collect()
            else:
                step = engine.step()
            got += [tok for r, tok, _d in step if r == rid]
            if not engine.active:
                break
        out.append(got)
        engine.check_invariants()
    return out


# -- (a) cache on = cache off ------------------------------------------------------


def test_a_conversation_emits_the_same_tokens_with_the_cache_on_and_off(model):
    cfg, params, _ = model
    prompts = conversation(5, first=75, seed=1)
    off = make_engine(cfg, params, prefix_cache=False)
    want = serve_all(off, prompts)
    on = make_engine(cfg, params, prefix_cache=True)
    got = serve_all(on, prompts)
    assert got == want and all(len(t) == 5 for t in got)
    cache = on.prefix_cache
    # every turn after the first was granted a prefix from a snapshot
    assert cache.hits == 4 and on.snapshots_restored == 4
    assert on.chunks_run < off.chunks_run
    stats = on.model_counters()
    assert stats["state_snapshots_saved"] == on.snapshots_saved >= 3
    assert stats["state_snapshot_bytes_copied"] == on.snapshot_bytes * (
        on.snapshots_saved + 4)
    assert stats["state_snapshots_held"] == sum(
        1 for _ in cache.snapshot_rows())
    assert stats["state_snapshot_pool_bytes"] == on.snapshot_bytes * 2 * SLOTS
    assert "state_snapshots_saved" not in off.model_counters()


def test_the_rows_past_a_grant_have_the_logits_of_a_prefill_from_row_0(model):
    """By hand, logits: the state after a prompt's last full chunk, copied
    out and into ANOTHER slot whose first pages are the first slot's; the
    chunks from there on are the same program over the same operands, so
    every row's logits are those of the prefill from row 0, bit for bit,
    the first token's among them."""
    cfg, params, _ = model
    first, second = conversation(2, first=75, seed=2)
    depth = len(first) // CHUNK * CHUNK  # 64
    whole = Served(cfg, params)
    want = whole.prefill(0, second)[depth:]
    served = Served(cfg, params)
    served.prefill(0, first[:depth])  # the prompt's full chunks
    snapshot = jax.tree.map(lambda a: a[0], served.state)
    served.prefill(0, first)  # the slot moves on; the copy does not
    served.state = jax.tree.map(lambda a, s: a.at[2].set(s), served.state,
                                snapshot)
    served.bts[2, : depth // PAGE] = served.bts[0, : depth // PAGE]
    got = served.prefill(2, second, base0=depth)
    assert got.shape == want.shape and (got == want).all()
    # and a state that is not the snapshot's does not do
    served.state = jax.tree.map(lambda a: a.at[2].set(0), served.state)
    assert not (served.prefill(2, second, base0=depth) == want).all()


def test_a_snapshot_is_a_copy_in_the_states_own_dtype(model):
    cfg, params, _ = model
    engine = make_engine(cfg, params, prefix_cache=True)
    pool, state = engine.snapshot_pool, engine.slot_state
    assert jax.tree.structure(pool) == jax.tree.structure(state)
    for a, b in zip(jax.tree.leaves(pool), jax.tree.leaves(state)):
        assert a.dtype == b.dtype and a.shape[1:] == b.shape[1:]
        assert a.shape[0] == 2 * SLOTS
    assert engine.snapshot_pool["0"]["s"].dtype == jnp.float32
    prompt = prompt_ids(70, seed=5)
    engine.submit("r", prompt, 1)
    run(engine, "r")
    (row,) = engine.prefix_cache.snapshot_rows()
    # the row holds the state after the prompt's first 64 rows
    served = Served(cfg, params, dirty=False)
    served.prefill(0, prompt[:64])
    for key, leaves in served.state.items():
        for name, leaf in leaves.items():
            assert (np.asarray(engine.snapshot_pool[key][name][row])
                    == np.asarray(leaf[0])).all(), (key, name)


# -- (b) where a grant may end -----------------------------------------------------


def test_a_grant_lands_on_a_snapshots_depth_and_never_between(model):
    cfg, params, _ = model
    engine = make_engine(cfg, params, prefix_cache=True)
    first = prompt_ids(75, seed=7)  # full chunks end at 64; 72 rows in pages
    engine.submit("a", first, 2)
    run(engine, "a")
    cache = engine.prefix_cache
    assert [d for d, _ in cache.snapshots_on_path(first)] == [64]
    # the cache holds the pages down to the snapshot and none past it
    assert cache.size == 64 // PAGE
    # a prompt that shares all 75 rows is granted 64, not 72
    engine.submit("b", first + prompt_ids(30, seed=8), 2)
    slot = next(s for s in engine.slots if s is not None)
    assert slot.chunk_base == 64 and slot.shared == 64 // PAGE
    assert slot.snap_from is not None and slot.snap_from.snap_pins == 1
    run(engine, "b")
    # one that shares 40 rows (5 pages, none with a snapshot) is granted none
    hits = cache.hits
    engine.submit("c", first[:40] + prompt_ids(30, seed=9), 2)
    slot = next(s for s in engine.slots if s is not None)
    assert slot.chunk_base == 0 and slot.shared == 0 and slot.snap_from is None
    assert cache.hits == hits
    run(engine, "c")
    engine.check_invariants()
    # the longer prompt left a deeper snapshot on the same path, and "c",
    # which left the tree's path after 40 rows, one at its branch edge (32)
    assert [d for d, _ in cache.snapshots_on_path(
        first + prompt_ids(30, seed=8))] == [32, 64, 96]


def test_a_prompt_under_one_chunk_saves_none_and_is_granted_none(model):
    cfg, params, _ = model
    engine = make_engine(cfg, params, prefix_cache=True)
    short = prompt_ids(CHUNK - 1, seed=11)
    for rid in ("a", "b"):
        engine.submit(rid, short, 3)
        slot = next(s for s in engine.slots if s is not None)
        assert slot.chunk_base == 0 and slot.snap_from is None
        run(engine, rid)
    assert engine.snapshots_saved == 0 and engine.prefix_cache.size == 0
    assert engine.prefix_cache.hits == 0
    engine.check_invariants()


def test_a_prompt_that_ends_on_a_chunk_edge_saves_there_for_a_longer_one(model):
    cfg, params, _ = model
    engine = make_engine(cfg, params, prefix_cache=True)
    first = prompt_ids(2 * CHUNK, seed=12)
    engine.submit("a", first, 2)
    run(engine, "a")
    assert [d for d, _ in engine.prefix_cache.snapshots_on_path(first)] == [64]
    # the same prompt again: its last row must be prefilled, so no grant
    engine.submit("b", first, 2)
    assert next(s for s in engine.slots if s is not None).chunk_base == 0
    run(engine, "b")
    engine.submit("c", first + [7, 8, 9], 2)
    assert next(s for s in engine.slots if s is not None).chunk_base == 64
    run(engine, "c")
    engine.check_invariants()


# -- (c) custody ---------------------------------------------------------------------


def test_a_full_snapshot_pool_evicts_the_least_recently_used_unpromised_row(model):
    cfg, params, _ = model
    engine = make_engine(cfg, params, prefix_cache=True, state_snapshots=2)
    cache = engine.prefix_cache
    a, b, c = (prompt_ids(70, seed=s) for s in (21, 22, 23))
    for rid, prompt in (("a", a), ("b", b)):
        engine.submit(rid, prompt, 1)
        run(engine, rid)
    assert cache.snapshots_free == 0
    # a grant from a's snapshot, promised and not yet copied: its row stays
    engine.submit("a2", a + prompt_ids(20, seed=24), 1)
    engine.check_invariants()
    engine.submit("c", c, 1)
    run(engine, "c")  # (a2 runs first: FIFO), c's save takes b's row
    engine.check_invariants()
    assert cache.snapshots_evicted >= 1
    assert not cache.snapshots_on_path(b)
    # b's pages went with its snapshot: nobody could be granted them
    assert cache.lookup(b)[0] == 0
    assert cache.snapshots_on_path(c)
    assert engine.model_counters()["state_snapshots_evicted"] == (
        cache.snapshots_evicted)


def test_every_row_promised_means_no_save_and_no_harm(model):
    cfg, params, _ = model
    engine = make_engine(cfg, params, prefix_cache=True, state_snapshots=1)
    a = prompt_ids(70, seed=31)
    engine.submit("a", a, 1)
    run(engine, "a")
    want = serve_all(make_engine(cfg, params, prefix_cache=False),
                     [a + prompt_ids(40, seed=32)], prefix="w")
    # the one row is promised to a2 while b's last full chunk runs: b saves
    # none, and is served as it would be without
    engine.submit("b", prompt_ids(70, seed=33), 2)
    engine.submit("a2", a + prompt_ids(40, seed=32), 5)
    saved = engine.snapshots_saved
    got = []
    for _ in range(300):
        got += [t for r, t, _d in engine.step() if r == "a2"]
        if not engine.active:
            break
    engine.check_invariants()
    assert [got] == want
    assert engine.snapshots_saved >= saved


def test_eviction_under_a_short_free_list_frees_the_snapshot_with_its_node(model):
    cfg, params, _ = model
    # 33 pages: the null page and two streams' worth
    engine = make_engine(cfg, params, prefix_cache=True, num_pages=33)
    cache = engine.prefix_cache
    for n in range(6):
        prompt = prompt_ids(100, seed=40 + n)
        engine.submit(f"r{n}", prompt, 4)
        run(engine, f"r{n}")
        engine.check_invariants()
        held = sum(1 for _ in cache.snapshot_rows())
        assert held + cache.snapshots_free == cache.snapshots
    assert cache.evicted_pages > 0 and cache.snapshots_evicted > 0
    # held pages == granted + cached, with nothing live: the cache's alone
    assert engine.allocator.in_use == cache.size


def test_preempt_gives_back_what_its_stream_had_not_handed_over(model):
    cfg, params, _ = model
    engine = make_engine(cfg, params, prefix_cache=True)
    cache = engine.prefix_cache
    a = prompt_ids(75, seed=51)
    engine.submit("a", a, 1)
    run(engine, "a")
    follow = a + prompt_ids(60, seed=52)  # 135 rows: full chunks end at 128
    engine.submit("b", follow, 6)
    engine.preempt("b")  # promised a's snapshot, no chunk run yet
    engine.check_invariants()
    assert cache.snapshots_on_path(a)[0][1].snap_pins == 0
    engine.submit("b", follow, 6)
    for _ in range(2):  # chunks at 64 and 96: the second saves at 128
        engine.step()
    slot = next(s for s in engine.slots if s is not None)
    assert slot.snap_saved is not None and slot.snap_saved[0] == 128
    free = cache.snapshots_free
    engine.preempt("b")  # a row saved, not yet the cache's: given back
    engine.check_invariants()
    assert cache.snapshots_free == free + 1
    # the cache's own rows stay: the resumed stream is granted a's again
    want = serve_all(make_engine(cfg, params, prefix_cache=False), [follow],
                     max_new=6, prefix="w")
    hits = cache.hits
    assert serve_all(engine, [follow], max_new=6, prefix="again") == want
    assert cache.hits == hits + 1


def test_save_pools_and_restore_state_drop_the_snapshots(model, tmp_path):
    """A checkpoint carries the slots' state beside the pages, not the
    radix tree and not the snapshot pool: the restored engine decodes on
    from the slots' own state and starts with no snapshot."""
    cfg, params, _ = model
    first, second = conversation(2, first=75, seed=61)
    want = serve_all(make_engine(cfg, params, prefix_cache=False), [second],
                     max_new=12, prefix="w")[0]
    engine = make_engine(cfg, params, prefix_cache=True)
    serve_all(engine, [first])
    engine.submit("r", second, 12)
    got = []
    while len(got) < 5:
        got += [t for _r, t, _d in engine.step()]
    state = engine.checkpoint_state()
    engine.save_pools(tmp_path / "pools")
    fresh = make_engine(cfg, params, prefix_cache=True)
    fresh.restore_pools(tmp_path / "pools")
    assert fresh.restore_state(state) == ["r"]
    fresh.check_invariants()
    assert fresh.prefix_cache.size == 0
    assert fresh.prefix_cache.snapshots_free == fresh.prefix_cache.snapshots
    got += run(fresh, "r")
    assert got == want
    fresh.check_invariants()


# -- (d) a chunk that went ahead ------------------------------------------------------


def test_a_chunk_that_went_ahead_still_saves_the_right_state(model):
    """``dispatch → ahead → collect`` beside a stream that decodes: the
    last full chunk goes ahead behind a running window, its snapshot is
    enqueued behind it there and then, and the follow-up turn that is
    granted it emits what it emits with the cache off."""
    cfg, params, _ = model
    prompts = conversation(3, first=110, seed=71)
    want = serve_all(make_engine(cfg, params, prefix_cache=False), prompts)
    engine = make_engine(cfg, params, prefix_cache=True)
    engine.submit("bg", prompt_ids(20, seed=72), 200)
    for _ in range(3):
        engine.step()
    got = []
    for n, prompt in enumerate(prompts):
        engine.submit(f"t{n}", prompt, 5)
        toks = []
        while len(toks) < 5:
            out = engine.dispatch()
            engine.ahead()
            out += engine.collect()
            toks += [t for r, t, _d in out if r == f"t{n}"]
        got.append(toks)
        engine.check_invariants()
    assert got == want
    assert engine.chunks_ahead >= 3 and engine.snapshots_restored == 2
    assert engine.snapshots_saved >= 2


# -- (e) who may not ------------------------------------------------------------------


def test_a_slot_state_engine_without_snapshots_refuses_by_the_new_message():
    def pool(n):
        return {"0": {"k": jnp.zeros((n, 8, 4))}}

    def state(rows):
        return {"0": jnp.zeros((rows, 4))}

    kw = dict(init_pool=pool, chunk_prefill=None, window_step=None, max_slots=2,
              max_seq=64, page_size=8, chunk=16, num_pages=9,
              init_slot_state=state, chunk_valid_rows=True)
    with pytest.raises(NotImplementedError, match="state snapshots"):
        PagedBatchEngine(**kw, prefix_cache=True)
    assert PagedBatchEngine(**kw).snapshot_pool is None
    assert PagedBatchEngine(**kw, state_snapshots=4).snapshot_pool is None
    engine = PagedBatchEngine(**kw, prefix_cache=True, state_snapshots=4)
    assert engine.snapshot_pool["0"].shape == (4, 4)


@pytest.mark.parametrize("name", ["falcon_h1", "exaone_moe", "glm5_next", "zaya"])
def test_the_other_slot_state_models_still_refuse_a_prefix_cache(
        name, tmp_path, monkeypatch, caplog):
    import importlib

    from tests import program_text

    program_text.write_checkpoint(name, tmp_path / "ckpt")
    module = importlib.import_module(f"dora_tpu.models.hf.{name}")
    cfg, params = module.load(tmp_path / "ckpt", max_seq=128)
    params = module.quantize_decode(params, cfg)
    sizes = dict(max_slots=3, page_size=8, chunk=32, window=4)
    # asked for one, the model builds its engine without (and says so)
    with caplog.at_level("WARNING"):
        engine = module.make_paged_engine(params, cfg, prefix_cache=True, **sizes)
    assert engine.prefix_cache is None and engine.snapshot_pool is None
    assert engine.snapshot_stats() == {}
    # and were the request to reach the engine, the engine would refuse it
    real = PM.build_engine

    def forced(*a, **kw):
        return real(*a, **{**kw, "prefix_cache": True})

    monkeypatch.setattr(PM, "build_engine", forced)
    with pytest.raises(NotImplementedError,
                       match="unless its model keeps state snapshots"):
        module.make_paged_engine(params, cfg, **sizes)


# -- (f) the second snapshot: where a prompt branches ---------------------------------


def branching(n: int, shared: int, tail: int, seed: int):
    """``n`` prompts that share their first ``shared`` rows (a system
    prompt) and differ after them (``tail`` rows each)."""
    prefix = prompt_ids(shared, seed=seed)
    return [prefix + prompt_ids(tail, seed=seed + 1 + i) for i in range(n)]


def test_f_a_prompt_that_branches_saves_at_the_edge_and_the_third_is_granted(model):
    cfg, params, _ = model
    # 80 shared rows = 2.5 chunks = 10 pages; tails of 21: 101 rows, whose
    # last full chunk ends at 96, past the shared part
    prompts = branching(4, shared=80, tail=21, seed=40)
    off = make_engine(cfg, params, prefix_cache=False)
    want = serve_all(off, prompts)
    on = make_engine(cfg, params, prefix_cache=True)
    cache = on.prefix_cache
    on.submit("t0", prompts[0], 5)
    slot = next(s for s in on.slots if s is not None)
    assert slot.chunk_base == 0 and slot.branch_edge == 0  # an empty tree
    got = [run(on, "t0")]
    assert on.snapshots_branch_saved == 0
    assert [d for d, _ in cache.snapshots_on_path(prompts[0])] == [96]
    # the second matches 80 rows, none of them under a snapshot: it starts
    # at row 0 and saves where it leaves the tree, at the chunk edge 64
    on.submit("t1", prompts[1], 5)
    slot = next(s for s in on.slots if s is not None)
    assert slot.chunk_base == 0 and slot.branch_edge == 64
    assert slot.snap_from is None
    got.append(run(on, "t1"))
    on.check_invariants()
    assert on.snapshots_branch_saved == 1 and on.snapshots_restored == 0
    assert [d for d, _ in cache.snapshots_on_path(prompts[1])] == [64, 96]
    # the row holds the state after the shared prompt's first 64 rows
    row = cache.snapshots_on_path(prompts[1])[0][1].snap
    served = Served(cfg, params, dirty=False)
    served.prefill(0, prompts[1][:64])
    for key, leaves in served.state.items():
        for name, leaf in leaves.items():
            assert (np.asarray(on.snapshot_pool[key][name][row])
                    == np.asarray(leaf[0])).all(), (key, name)
    # the third and the fourth are granted to it: one chunk and a page short
    # of the shared depth at most, and no further branch row (64 is taken)
    for n in (2, 3):
        chunks = on.chunks_run
        on.submit(f"t{n}", prompts[n], 5)
        slot = next(s for s in on.slots if s is not None)
        assert slot.chunk_base == 64 and slot.shared == 64 // PAGE
        assert slot.branch_edge == 0
        got.append(run(on, f"t{n}"))
        assert on.chunks_run - chunks == 2  # rows 64..100, not 0..100
        on.check_invariants()
    assert got == want
    assert on.snapshots_branch_saved == 1 and on.snapshots_restored == 2
    stats = on.model_counters()
    assert stats["state_snapshots_branch_saved"] == 1
    assert stats["state_snapshots_saved"] == on.snapshots_saved == 5


def test_f_a_session_shaped_pair_saves_no_branch_row(model):
    """A turn that resends its history matches down to the last turn's
    snapshot and no further (the pages past it were never inserted): no
    chunk edge lies between the grant and the match."""
    cfg, params, _ = model
    engine = make_engine(cfg, params, prefix_cache=True)
    serve_all(engine, conversation(5, first=75, seed=41))
    assert engine.snapshots_restored == 4
    assert engine.snapshots_branch_saved == 0
    assert engine.model_counters()["state_snapshots_branch_saved"] == 0


def test_f_branch_rows_are_counted_and_evicted_as_the_others(model):
    cfg, params, _ = model
    engine = make_engine(cfg, params, prefix_cache=True, state_snapshots=3)
    cache = engine.prefix_cache
    # four system prompts, three requests each: every second request saves
    # two rows (its branch edge, its last full chunk) into a pool of three
    for group in range(4):
        prompts = branching(3, shared=80, tail=21, seed=50 + 10 * group)
        serve_all(engine, prompts, max_new=2, prefix=f"g{group}_")
        engine.check_invariants()
        assert [d for d, _ in cache.snapshots_on_path(prompts[2])][:1] == [64]
    assert engine.snapshots_branch_saved == 4
    assert cache.snapshots_evicted > 0
    assert cache.snapshots_held + cache.snapshots_free == 3
    # a stream preempted between its branch edge and its final chunk gives
    # the row back
    first, second = branching(2, shared=80, tail=21, seed=90)
    serve_all(engine, [first], max_new=2, prefix="p")
    engine.submit("q", second, 2)
    for _ in range(3):  # chunks 0, 32, 64: the branch row is the stream's
        engine.step()
    slot = next(s for s in engine.slots if s is not None)
    assert slot.snap_branch is not None and slot.snap_branch[0] == 64
    engine.check_invariants()
    engine.preempt("q")
    engine.check_invariants()
    assert cache.snapshots_held + cache.snapshots_free == 3
