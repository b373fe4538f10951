"""Shared-prefix KV subsystem (models/prefix_cache + refcounted pages).

The load-bearing properties:

* TOKEN IDENTITY: a stream admitted onto cached prefix pages emits
  exactly the tokens a cold run emits, across fused-window K and
  speculative configs — sharing changes WHICH pages the block table
  maps and WHERE prefill starts, never the math. Shared pages are
  immutable; the copy-on-write boundary page is re-materialized by the
  divergence chunk, not written in place.
* CUSTODY: pages are refcounted, never copied — double frees and
  frees of shared pages raise, and after any sequence of admissions,
  evictions and preemptions every allocated page's refcount equals the
  number of holders that can name it (engine.check_invariants()).
* PRESSURE: eviction yields to admission — cached pages are
  free-in-waiting, and sharing never turns an admissible request
  inadmissible (the chunk-overhang backoff).
* COMPILES: cache hits add ZERO steady-state XLA compiles — the
  divergence base is a traced operand, so chunked prefill keeps its
  single compiled shape.
"""

from __future__ import annotations

import numpy as np
import pytest
import torch

#: every XLA backend compile observed in this process (same listener as
#: test_paged_engine — registered at import so warmup is counted too)
_COMPILE_EVENTS: list[str] = []


def _register_compile_listener() -> None:
    from jax._src import monitoring

    def _on_duration(event: str, duration: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILE_EVENTS.append(event)

    monitoring.register_event_duration_secs_listener(_on_duration)


_register_compile_listener()


@pytest.fixture(scope="module")
def tiny_qwen2(tmp_path_factory):
    from transformers import Qwen2Config, Qwen2ForCausalLM

    config = Qwen2Config(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rope_theta=10000.0,
        rms_norm_eps=1e-6, tie_word_embeddings=False,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = Qwen2ForCausalLM(config).eval()
    path = tmp_path_factory.mktemp("qwen2-prefix")
    model.save_pretrained(path, safe_serialization=True)
    return path


@pytest.fixture(scope="module")
def quantized(tiny_qwen2):
    import os

    from dora_tpu.models.hf import qwen2

    cfg, params = qwen2.load(tiny_qwen2, max_seq=64)
    os.environ["DORA_INT8_DECODE"] = "1"
    try:
        qparams = qwen2.quantize_decode(params, cfg)
    finally:
        os.environ.pop("DORA_INT8_DECODE", None)
    return cfg, qparams


def _run_sequential(engine, prompts, max_new):
    """Submit one stream at a time, drain to completion. Sequential on
    purpose: the cache inserts a prompt's pages when its final prefill
    chunk lands, so stream N+1 can hit what stream N computed. Returns
    (tokens per rid, prefill chunks per stream)."""
    out: dict[str, list[int]] = {}
    chunks: list[int] = []
    for i, p in enumerate(prompts):
        c0 = engine.chunks_run
        engine.submit(f"r{i}", p, max_new)
        while engine.active or engine.prefilling:
            for rid, tok, _done in engine.step():
                out.setdefault(rid, []).append(tok)
        chunks.append(engine.chunks_run - c0)
    return out, chunks


# ---------------------------------------------------------------------------
# allocator hardening: refcounts, double free, free-while-shared
# ---------------------------------------------------------------------------


def test_allocator_ref_unref_share_and_release():
    from dora_tpu.models.batch_engine import PageAllocator

    a = PageAllocator(8)
    grant = a.alloc(3)
    assert all(a.refcount(p) == 1 for p in grant)
    a.ref(grant[:2])
    assert a.refcount(grant[0]) == 2 and a.refcount(grant[2]) == 1
    assert a.free_pages == 4  # sharing does not consume pages
    a.unref(grant)  # first holder lets go
    assert a.free_pages == 5  # only the unshared page returned
    assert a.refcount(grant[0]) == 1
    a.unref(grant[:2])
    assert a.free_pages == 7
    a.check_invariants()


def test_allocator_double_free_raises():
    from dora_tpu.models.batch_engine import PageAllocator

    a = PageAllocator(8)
    grant = a.alloc(2)
    a.free(grant)
    with pytest.raises(RuntimeError, match="double free"):
        a.free(grant)
    with pytest.raises(RuntimeError, match="double free"):
        a.unref([grant[0]])
    a.check_invariants()


def test_allocator_free_while_shared_raises():
    from dora_tpu.models.batch_engine import PageAllocator

    a = PageAllocator(8)
    grant = a.alloc(2)
    a.ref(grant)
    with pytest.raises(RuntimeError, match="shared page"):
        a.free(grant)
    a.unref(grant)
    a.free(grant)  # last holder may free
    a.check_invariants()


def test_allocator_ref_of_free_page_raises():
    from dora_tpu.models.batch_engine import PageAllocator

    a = PageAllocator(8)
    (page,) = a.alloc(1)
    a.free([page])
    with pytest.raises(RuntimeError, match="not allocated"):
        a.ref([page])
    a.check_invariants()


# ---------------------------------------------------------------------------
# radix tree unit: lookup / insert / pin / evict
# ---------------------------------------------------------------------------


def _cache(num_pages=32, page_size=4, **kw):
    from dora_tpu.models.batch_engine import PageAllocator
    from dora_tpu.models.prefix_cache import PrefixCache

    a = PageAllocator(num_pages)
    return a, PrefixCache(a, page_size, **kw)


def test_radix_longest_prefix_and_mid_page_flag():
    a, c = _cache()
    ids = list(range(1, 13))  # 3 full pages of 4
    pages = a.alloc(3)
    assert c.insert(ids, pages) == 3
    m, got, mid = c.lookup(ids)
    assert (m, got, mid) == (12, pages, False)
    # diverge at token 6 — inside the second cached page
    m, got, mid = c.lookup(ids[:5] + [99, 99, 99])
    assert (m, got) == (4, pages[:1]) and mid
    # diverge exactly at a page boundary — no boundary copy needed
    m, got, mid = c.lookup(ids[:8] + [99, 99])
    assert (m, got) == (8, pages[:2]) and not mid
    assert c.lookup([77, 78, 79, 80])[0] == 0


def test_radix_insert_dedup_first_writer_wins():
    a, c = _cache()
    ids = list(range(1, 9))
    first = a.alloc(2)
    c.insert(ids, first)
    dup = a.alloc(2)
    assert c.insert(ids, dup) == 0  # nodes exist: no pages adopted
    assert c.lookup(ids)[1] == first
    assert c.size == 2
    # the duplicate stays in its stream's custody, not the cache's
    a.free(dup)
    a.check_invariants()


def test_radix_lru_eviction_leaf_first_skips_pinned_and_shared():
    a, c = _cache()
    base = list(range(1, 9))  # 2 shared pages
    pa = a.alloc(3)
    pb = a.alloc(3)
    c.insert(base + [11, 12, 13, 14], pa)
    c.insert(base + [21, 22, 23, 24], pb)
    assert c.size == 4  # base deduped: 2 + two distinct leaves
    c.lookup(base + [21, 22, 23, 24])  # touch branch B: A's leaf is LRU
    # the streams released their grants; cache custody only now
    a.unref(pa)
    a.unref(pb)
    assert c.evictable_pages() == 4
    assert c.evict(1) == 1
    assert c.lookup(base + [11, 12, 13, 14])[0] == 8  # A's leaf gone
    assert c.lookup(base + [21, 22, 23, 24])[0] == 12  # B intact
    # pin B's path: nothing evictable below it, the base pages are held
    # up by B's pinned leaf
    c.pin(base + [21, 22, 23, 24])
    assert c.evictable_pages() == 0
    assert c.evict(10) == 0
    c.unpin(base + [21, 22, 23, 24])
    # share the base with a "live stream": rc 2 pages never evict
    shared = c.lookup(base)[1]
    a.ref(shared)
    assert c.evictable_pages() == 1  # only B's unshared leaf
    assert c.evict(10) == 1
    a.unref(shared)
    assert c.flush() == 2
    assert c.size == 0 and a.free_pages == a.num_pages - 1
    a.check_invariants()


def test_evictable_pages_of_a_chain_deeper_than_the_recursion_limit():
    """A 16k-token prompt at page 16 is a chain of 1,024 nodes (the
    kimi_k2 cell's max_seq): the count must not recurse a level a page.
    A live stream's hold on the LAST page keeps the whole chain."""
    import sys

    depth = sys.getrecursionlimit() + 24
    a, c = _cache(num_pages=depth + 2, page_size=1)
    pages = a.alloc(depth)
    c.insert(list(range(1, depth + 1)), pages)
    assert c.evictable_pages() == 0  # the stream still holds every page
    a.unref(pages[:-1])
    assert c.evictable_pages() == 0  # its last page holds up the ancestors
    a.unref(pages[-1:])
    assert c.evictable_pages() == depth
    assert c.evict(depth) == depth
    a.check_invariants()


@pytest.mark.parametrize("free_enough", [True, False])
def test_admission_counts_evictable_pages_only_when_the_free_list_is_short(
    free_enough,
):
    """can_admit and the prefix grant walk every cached page to count the
    evictable ones: with 10k cached pages that is milliseconds of host
    time an admission, beside a decode window. The answer is the same
    without the walk whenever the free list alone covers the need."""
    from types import SimpleNamespace

    from dora_tpu.models.batch_engine import PagedBatchEngine

    a, c = _cache(num_pages=32, page_size=4)
    held = a.alloc(4)
    c.insert(list(range(1, 17)), held)
    a.unref(held)  # cache custody only: 4 evictable pages
    if not free_enough:
        a.alloc(a.free_pages - 1)  # leave one free page
    walks = []
    count = c.evictable_pages
    c.evictable_pages = lambda: walks.append(1) or count()
    eng = SimpleNamespace(
        allocator=a, prefix_cache=c, page_size=4, chunk=4, max_seq=64,
        lora=None, free_slots=1, _spec_cfg=0, max_slots=1,
        snapshot_pool=None,
    )
    for name in ("can_admit", "fits", "pages_needed", "spec_headroom",
                 "_prefix_grant"):
        setattr(eng, name, getattr(PagedBatchEngine, name).__get__(eng))
    eng.free_pages = a.free_pages
    ids = list(range(1, 17)) + [99, 98]  # 4 cached pages + a tail
    assert eng.can_admit(len(ids), 2)  # needs 5 pages
    base, shared, _node, _branch = eng._prefix_grant(ids, 2)
    assert (base, len(shared)) == (16, 4)
    assert bool(walks) == (not free_enough)
    a.unref(shared)


def test_radix_max_pages_cap_evicts_on_insert():
    a, c = _cache(max_pages=2)
    ids = list(range(1, 13))
    pages = a.alloc(3)
    c.insert(ids, pages)
    # over cap, but the inserting stream still shares the pages — the
    # cap cannot evict in-use pages, so it bites on the NEXT insert
    assert c.size == 3
    a.unref(pages)
    other = a.alloc(1)
    c.insert([50, 51, 52, 53], other)
    a.unref(other)
    assert c.size == 2 and c.evicted_pages == 2
    a.check_invariants()


# ---------------------------------------------------------------------------
# evict: one walk and a heap, against the walk a page it replaced
# ---------------------------------------------------------------------------


def _evict_a_walk_a_page(c, need: int) -> int:
    """``PrefixCache.evict`` as it stood before the heap, the plain
    reference: every page freed costs a walk of every node of every
    tenant's tree to find the least recently used leaf that may go."""
    freed = 0
    while freed < need:
        best = None
        stack = [
            n for root in c._roots.values() for n in root.children.values()
        ]
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            if n.children or n.pins:
                continue
            if c.allocator.refcount(n.page) != 1:
                continue
            if best is None or n.last_used < best.last_used:
                best = n
        if best is None:
            break
        c._drop(best)
        freed += 1
    c.evicted_pages += freed
    return freed


def _random_forest(seed: int, ties: bool):
    """A cache as traffic leaves it, the same for the same seed: three
    tenants, prompts that branch off one another at a random page, one
    chain deeper than the recursion limit with a branch off its middle,
    pinned paths, pages a "live stream" still holds, nodes with snapshot
    rows, lookups that restamp paths. ``ties`` then writes a few stamps
    over many nodes, as no clock would: the tie rule's case. Returns the
    allocator, the cache and what a later step lets go of."""
    import random
    import sys

    rng = random.Random(seed)
    ps = 2
    a, c = _cache(num_pages=4096, page_size=ps, snapshots=64)
    tenants = [None, "ann", "bob"]
    prompts: list[tuple[str | None, list[int]]] = []

    def adopt(adapter, ids):
        pages = a.alloc(len(ids) // ps)
        c.insert(ids, pages, adapter)
        a.unref(pages)  # the stream ends: what the cache took stays
        prompts.append((adapter, ids))

    deep = (sys.getrecursionlimit() + 40) * ps
    chain = [rng.randrange(1, 50) for _ in range(deep)]
    adopt("ann", chain)
    adopt("ann", chain[: deep // 2] + [77] * 12)
    for _ in range(60):
        adapter = rng.choice(tenants)
        own = [ids for t, ids in prompts if t == adapter and len(ids) < 200]
        head = []
        if own and rng.random() < 0.7:
            stem = rng.choice(own)
            head = stem[: rng.randrange(0, len(stem) // ps + 1) * ps]
        tail = [rng.randrange(1, 6) for _ in range(rng.randrange(1, 9) * ps)]
        adopt(adapter, head + tail)
        if rng.random() < 0.5:
            t, ids = rng.choice(prompts)
            c.lookup(ids[: rng.randrange(1, len(ids) + 1)], t)
    pinned = rng.sample(prompts, 6)
    for t, ids in pinned:
        c.pin(ids, t)
    live = []
    for t, ids in rng.sample(prompts, 8):
        pages = c.lookup(ids, t)[1]
        held = pages[: rng.randrange(1, len(pages) + 1)]
        a.ref(held)
        live.append(held)
    for t, ids in rng.sample(prompts, 20):
        row = c.snapshot_take()
        depth = rng.randrange(1, len(ids) // ps + 1) * ps
        if not c.snapshot_attach(ids, depth, row, t):
            c.snapshot_give_back(row)
    if ties:
        for n in c._nodes():
            if rng.random() < 0.6:
                n.last_used = rng.randrange(1, 6)
    return a, c, (pinned, live)


def _evict_and_note(a, c, evict, need: int) -> tuple[int, list[int]]:
    """What ``evict(need)`` returns and the pages it let go, in order."""
    gone: list[int] = []
    unref = a.unref
    a.unref = lambda pages: (gone.extend(pages), unref(pages))[1]
    try:
        return evict(need), gone
    finally:
        del a.unref


def _custody(a, c) -> dict:
    return {
        "size": c.size, "evicted_pages": c.evicted_pages,
        "snapshots_evicted": c.snapshots_evicted,
        "snapshots_free": list(c._snap_free),
        "snapshot_rows": sorted(c.snapshot_rows()),
        "refcounts": dict(a._ref), "free_list": list(a._free),
        "cached": sorted(c.pages()), "evictable": c.evictable_pages(),
    }


@pytest.mark.parametrize("ties", [False, True], ids=["clock", "ties"])
@pytest.mark.parametrize("need", [1, 7, 60, 400, 10_000])
@pytest.mark.parametrize("seed", [3, 11, 29])
def test_evict_frees_the_pages_the_walk_a_page_freed_in_its_order(
    seed, need, ties,
):
    """Page for page: the heap pops what the repeated walk picked, under
    pins, shared pages, snapshot rows, several tenants and a chain deeper
    than the recursion limit; with stamps that tie, the first in the
    walk's order goes first. A second call, after a pinned path and a
    live stream let go, finds the same pages again: nothing was kept."""
    a, c, (pinned, live) = _random_forest(seed, ties)
    ra, rc, (rpinned, rlive) = _random_forest(seed, ties)
    assert _custody(a, c) == _custody(ra, rc) and c.size > 1_200
    for _ in range(2):
        got = _evict_and_note(a, c, c.evict, need)
        want = _evict_and_note(
            ra, rc, lambda k: _evict_a_walk_a_page(rc, k), need)
        assert got == want
        assert got[0] == len(got[1]) <= need
        assert _custody(a, c) == _custody(ra, rc)
        for cache, alloc, pins, held in (
            (c, a, pinned, live), (rc, ra, rpinned, rlive),
        ):
            for t, ids in pins[::2]:
                cache.unpin(ids, t)
            for pages in held[::2]:
                alloc.unref(pages)
            del pins[::2], held[::2]
    a.check_invariants()
    assert set(c.pages()) <= set(a._ref)


def _chains_and_branches(nodes: int, branches: int):
    """A trunk with ``branches`` chains off its end, ``nodes`` in all,
    page size 1, every page in the cache's custody alone."""
    # twice the nodes: a later arm's pages for the trunk go home again
    a, c = _cache(num_pages=2 * nodes, page_size=1)
    trunk = list(range(1, nodes // (branches + 1) + 1))
    arm = (nodes - len(trunk)) // branches
    for b in range(branches):
        ids = trunk + [1_000_000 * (b + 1) + i for i in range(arm)]
        pages = a.alloc(len(ids))
        c.insert(ids, pages)
        a.unref(pages)
    assert c.size == len(trunk) + branches * arm
    return a, c


@pytest.mark.parametrize("case", ["600_of_8000", "need_0", "empty_tree",
                                  "every_leaf_pinned"])
def test_evict_visits_a_node_once_whatever_it_frees(case):
    """The count, no clock: ``evict_visits`` is the nodes the call walked
    plus the leaves it popped. The walk a page read ``need`` x nodes."""
    a, c = _chains_and_branches(8_000 if case == "600_of_8000" else 90, 15)
    nodes = c.size
    if case == "600_of_8000":
        assert c.evict(600) == 600
        assert c.evict_calls == 1
        assert nodes + 600 <= c.evict_visits <= 3 * nodes
        assert c.evict_visits / c.evicted_pages < 20
        return
    if case == "empty_tree":
        assert c.flush() == nodes and c.size == 0
        nodes = 0
    if case == "every_leaf_pinned":
        for b in range(15):
            c.pin(list(range(1, 6)) + [1_000_000 * (b + 1) + i for i in range(5)])
    calls, visits, freed = c.evict_calls, c.evict_visits, c.evicted_pages
    assert c.evict(0 if case == "need_0" else 600) == 0
    assert c.evict_calls == calls and c.evicted_pages == freed
    assert c.evict_visits - visits <= (0 if case == "need_0" else nodes)
    assert c.size == nodes
    a.check_invariants()


def test_the_serving_snapshot_carries_what_eviction_cost():
    """``prefix_evict_calls`` and ``prefix_evict_visits`` beside
    ``prefix_evictions``, from the cache's own counts at the loop's last
    report: a pool of 8 pages under prompts that never repeat."""
    from dora_tpu.metrics import ServingMetrics
    from tests.test_serving_trace import _serve_once

    assert ServingMetrics().snapshot()["prefix_evict_visits"] == 0
    eng = _stub(num_pages=9, max_slots=1, prefix_cache=True)
    metrics = ServingMetrics()
    events = [
        {"type": "INPUT", "value": (chr(65 + i) * 40).encode(),
         "metadata": {"request_id": f"r{i}", "max_new_tokens": 4}}
        for i in range(4)
    ]
    _serve_once(eng, metrics, events)
    snap, pc = metrics.snapshot(), eng.prefix_cache
    assert snap["prefix_evictions"] == pc.evicted_pages >= 4
    assert snap["prefix_evict_calls"] == pc.evict_calls >= 2
    assert snap["prefix_evict_visits"] == pc.evict_visits >= pc.evicted_pages
    eng.check_invariants()


# ---------------------------------------------------------------------------
# stub-engine scheduler: sharing, COW, eviction, backoff
# ---------------------------------------------------------------------------


def _stub(**kw):
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("chunk", 16)
    kw.setdefault("window", 2)
    return make_stub_paged_engine(**kw)


def test_stub_factory_defaults_cache_off():
    # Raw factories build cache-less engines unless asked: existing
    # pool-accounting assertions (free == total after drain) stay true.
    assert _stub().prefix_cache is None
    assert _stub(prefix_cache=True).prefix_cache is not None


def test_stub_shared_vs_cold_identity_and_chunk_savings():
    tmpl = list(range(1, 33))  # 4 pages, 2 chunks
    prompts = [tmpl + [50, 51], tmpl + [60, 61, 62], tmpl[:20] + [70, 71]]
    cold, cc = _run_sequential(_stub(), prompts, 6)
    eng = _stub(prefix_cache=True)
    warm, wc = _run_sequential(eng, prompts, 6)
    assert cold == warm
    # stream 1 re-prefills only its unshared tail; stream 2 diverges
    # mid-template and still skips its shared whole pages
    assert wc[1] < cc[1] and wc[2] < cc[2]
    pc = eng.prefix_cache
    assert pc.hits == 2 and pc.misses == 1
    assert pc.cow_copies >= 1  # stream 2 diverges mid-page
    eng.check_invariants()
    # every non-cached page went home
    assert eng.free_pages + pc.size == eng.allocator.num_pages - 1


def test_stub_pool_pressure_evicts_cache_then_readmits():
    # 8 usable pages: the cached template (4 pages) must partially make
    # way for an unrelated 6-page admission, then the template
    # re-admits — cold again, same tokens, custody intact.
    tmpl = list(range(1, 33))
    other = [90 - i for i in range(40)]
    prompts = [tmpl, other, tmpl]
    cold, _ = _run_sequential(_stub(num_pages=9, max_slots=2), prompts, 8)
    eng = _stub(num_pages=9, max_slots=2, prefix_cache=True)
    warm, _ = _run_sequential(eng, prompts, 8)
    assert cold == warm
    pc = eng.prefix_cache
    assert pc.evicted_pages >= 2  # admission pressure trimmed the cache
    eng.check_invariants()
    assert eng.free_pages + pc.size == eng.allocator.num_pages - 1


def test_stub_sharing_never_blocks_admission_backoff():
    # Chunk-overhang geometry: sharing the full 3-page template would
    # need 5 total pages (3 shared + 2 fresh) where the no-cache grant
    # is 4 — with only 4 usable pages the grant backs off one shared
    # page instead of failing an admission can_admit promised.
    tmpl = list(range(1, 25))  # 3 pages cached after the first stream
    eng = _stub(num_pages=5, max_slots=1, prefix_cache=True)
    out, _ = _run_sequential(eng, [tmpl, tmpl + [50, 51]], 2)
    pc = eng.prefix_cache
    assert pc.hits == 1 and pc.hit_tokens == 16  # trimmed from 24
    assert pc.cow_copies >= 1  # the trimmed boundary page re-prefills
    cold, _ = _run_sequential(
        _stub(num_pages=5, max_slots=1), [tmpl, tmpl + [50, 51]], 2
    )
    assert out == cold
    eng.check_invariants()


def test_stub_spec_identity_on_shared_pages():
    # Speculative verification writes rows past true_len — those land
    # in the stream's own pages, never the shared prefix, so tokens
    # stay identical to the spec-off cold run at every (K, spec_k).
    tmpl = list(range(1, 33))
    prompts = [tmpl + [50, 51], tmpl + [60, 61, 62]]
    ref, _ = _run_sequential(_stub(), prompts, 6)
    for spec_k in (0, 2):
        for window in (1, 8):
            eng = _stub(window=window, spec_k=spec_k, prefix_cache=True)
            got, _ = _run_sequential(eng, prompts, 6)
            assert got == ref, f"K={window} spec_k={spec_k}"
            assert eng.prefix_cache.hits == 1
            eng.check_invariants()


def test_preempt_pin_protects_victim_prefix_until_resume():
    # The server-side resume contract at engine level: pin the victim's
    # path, preempt, fill the pool with competing work, then resume —
    # the pinned pages survived eviction pressure and the resume maps
    # them (satellite of KNOWN_ISSUES round 14: preemption no longer
    # re-pays the whole prefill on a cache hit).
    tmpl = list(range(1, 33))
    eng = _stub(num_pages=17, max_slots=2, prefix_cache=True)
    _run_sequential(eng, [tmpl + [50, 51]], 4)  # template now cached
    eng.submit("victim", tmpl + [60, 61], 8)
    while eng.prefilling:
        eng.step()
    assert eng.prefix_pin(tmpl + [60, 61]) > 0
    assert eng.preempt("victim") is not None
    # competing admissions squeeze the pool while the victim waits
    _run_sequential(eng, [[80 + i for i in range(24)]], 8)
    c0 = eng.chunks_run
    h0 = eng.prefix_cache.hits
    eng.submit("victim", tmpl + [60, 61], 8)
    eng.prefix_unpin(tmpl + [60, 61])  # after submit, like serve()
    while eng.active or eng.prefilling:
        eng.step()
    assert eng.prefix_cache.hits == h0 + 1  # resume hit the pinned path
    assert eng.chunks_run - c0 < -(-len(tmpl + [60, 61]) // eng.chunk)
    eng.check_invariants()


# ---------------------------------------------------------------------------
# real model: shared-vs-cold identity across K x spec_k, zero compiles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window", [1, 8])
@pytest.mark.parametrize("spec_k", [0, 2])
def test_real_shared_vs_cold_identity(quantized, window, spec_k):
    """Cache-on serving is byte-identical to cache-off on the real
    (tiny) model: attention actually reads the shared KV rows here, so
    a wrong page mapping or a clobbered shared row changes tokens.
    After the first stream's warmup, cache-hit admissions at new
    prompt lengths add ZERO XLA compiles and the chunk jit holds its
    single shape — the divergence base is a traced operand."""
    from dora_tpu.models.hf import qwen2

    cfg, qparams = quantized
    rng = np.random.default_rng(5)
    tmpl = rng.integers(0, cfg.vocab, size=24).tolist()
    tails = [rng.integers(0, cfg.vocab, size=n).tolist() for n in (2, 3, 2)]
    prompts = [tmpl + tails[0], tmpl + tails[1], tmpl[:20] + tails[2]]

    def build(cache: bool):
        return qwen2.make_paged_engine(
            qparams, cfg, max_slots=4, page_size=8, chunk=16,
            window=window, spec_k=spec_k, prefix_cache=cache,
        )

    cold, cc = _run_sequential(build(False), prompts, 6)
    eng = build(True)
    warm0, _ = _run_sequential(eng, prompts[:1], 6)  # warmup + insert
    compiled = len(_COMPILE_EVENTS)
    warm1, wc = _run_sequential(eng, prompts[1:], 6)
    assert {**warm0, **{f"r{i + 1}": v for i, v in
                        enumerate(warm1.values())}} == cold
    assert len(_COMPILE_EVENTS) == compiled, (
        f"cache-hit admissions compiled "
        f"{len(_COMPILE_EVENTS) - compiled} new XLA program(s)"
    )
    assert eng.chunk_prefill.func._cache_size() == 1
    pc = eng.prefix_cache
    assert pc.hits == 2 and pc.misses == 1 and pc.cow_copies >= 1
    assert wc[0] < cc[1]  # the hit skipped the shared chunks
    eng.check_invariants()
    assert eng.free_pages + pc.size == eng.allocator.num_pages - 1


def test_real_eviction_then_readmission_identity(quantized):
    """Pool pressure evicts cached pages mid-sequence; the evicted
    template re-admits cold and the KV it recomputes is exact — reuse
    is an optimization with no correctness surface."""
    from dora_tpu.models.hf import qwen2

    cfg, qparams = quantized
    rng = np.random.default_rng(9)
    tmpl = rng.integers(0, cfg.vocab, size=32).tolist()
    other = rng.integers(0, cfg.vocab, size=40).tolist()
    prompts = [tmpl, other, tmpl]

    def build(cache: bool):
        return qwen2.make_paged_engine(
            qparams, cfg, max_slots=2, page_size=8, chunk=16, window=8,
            num_pages=9, prefix_cache=cache,
        )

    cold, _ = _run_sequential(build(False), prompts, 8)
    eng = build(True)
    warm, _ = _run_sequential(eng, prompts, 8)
    assert cold == warm
    assert eng.prefix_cache.evicted_pages >= 2
    eng.check_invariants()


def test_factory_env_default(quantized, monkeypatch):
    """DORA_PREFIX_CACHE gates the factory default: raw engines stay
    cache-off unless the env opts in (the serving entry points default
    it on; DORA_PREFIX_CACHE=0 is byte-identical to the pre-cache
    program because no cache object is ever built)."""
    from dora_tpu.models.hf import qwen2

    cfg, qparams = quantized

    def build():
        return qwen2.make_paged_engine(
            qparams, cfg, max_slots=2, page_size=8, chunk=16,
        )

    monkeypatch.delenv("DORA_PREFIX_CACHE", raising=False)
    assert build().prefix_cache is None
    monkeypatch.setenv("DORA_PREFIX_CACHE", "1")
    monkeypatch.setenv("DORA_PREFIX_CACHE_PAGES", "8")
    eng = build()
    assert eng.prefix_cache is not None
    assert eng.prefix_cache.max_pages == 8
    monkeypatch.setenv("DORA_PREFIX_CACHE", "0")
    assert build().prefix_cache is None
