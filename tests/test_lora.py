"""Multi-tenant LoRA serving (ops/lora + models/lora_pool + engine).

The load-bearing properties:

* KERNEL PARITY: the grouped gather-matmul Pallas kernel (CPU
  interpret mode) matches the eager per-stream reference exactly, and
  slot 0 (the all-zeros base adapter) contributes an exactly-zero
  delta — base streams in a mixed batch are bitwise-unaffected.
* POOL CUSTODY: adapter slots are refcounted; eviction is LRU over
  refcount-zero slots only; ``fits()`` accounts resident bytes; the
  invariants (slot bijection, free-list disjointness) hold through
  arbitrary acquire/release/eviction sequences.
* PER-TENANT TOKEN IDENTITY: every tenant's stream from one N-adapter
  engine is byte-identical to a single-adapter engine with the same
  weights, across K x spec_k, on the stub and the real tiny model.
* ZERO STEADY-STATE COMPILES: adapter ids are traced data and the
  stacked pool has a fixed shape, so admission/eviction churn across
  more tenants than resident slots triggers no XLA compiles after
  warmup, and chunked prefill still holds exactly one cached shape.
* TENANCY ISOLATION: the prefix cache keys on (tenant, tokens) — two
  tenants submitting the identical prompt never share KV pages; a
  pre-LoRA (adapter-less) checkpoint restores token-identically into
  a LoRA-enabled engine.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

#: every XLA backend compile observed in this process (registered at
#: import so warmup compiles are counted too)
_COMPILE_EVENTS: list[str] = []


def _register_compile_listener() -> None:
    from jax._src import monitoring

    def _on_duration(event: str, duration: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILE_EVENTS.append(event)

    monitoring.register_event_duration_secs_listener(_on_duration)


_register_compile_listener()


# -- kernel ----------------------------------------------------------------


def test_gather_matmul_matches_reference_and_base_slot_is_zero():
    import jax.numpy as jnp

    from dora_tpu.ops.lora import lora_gather_matmul, lora_gather_matmul_ref

    rng = np.random.default_rng(0)
    rows, dim, rank, slots = 6, 48, 8, 3
    x = jnp.asarray(rng.normal(size=(rows, dim)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(slots, dim, rank)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.normal(size=(slots, rank, dim)) * 0.3, jnp.float32)
    a = a.at[0].set(0.0)
    b = b.at[0].set(0.0)
    groups = jnp.asarray([0, 1, 2, 1, 0, 2], jnp.int32)

    got = lora_gather_matmul(x, groups, a, b)
    want = lora_gather_matmul_ref(x, groups, a, b)
    # Same two matmuls in a different accumulation order: equal to float
    # rounding, which moves with the installed JAX ...
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
    )
    # ... but slot 0 rows are exactly zero, not merely small.
    assert np.all(np.asarray(got)[np.asarray(groups) == 0] == 0.0)


# -- adapter pool ----------------------------------------------------------


def _pool(max_resident=2, known=None):
    import jax.numpy as jnp

    from dora_tpu.models.lora_pool import AdapterPool

    def loader(name):
        return jnp.asarray(sum(ord(c) for c in name) % 97, jnp.int32)

    return AdapterPool(
        loader, jnp.asarray(0, jnp.int32), max_resident=max_resident,
        known=known,
    )


def test_pool_refcount_lru_eviction_and_invariants():
    pool = _pool(max_resident=2)
    ia = pool.acquire("a")
    ib = pool.acquire("b")
    assert {ia, ib} == {1, 2} and pool.resident == 2
    # Both refcounted: a third tenant cannot displace either.
    assert pool.acquire("c") is None
    pool.check_invariants()
    # Release "a": it becomes the LRU refcount-zero victim.
    pool.release("a")
    ic = pool.acquire("c")
    assert ic == ia and pool.evictions == 1
    assert pool.slot_of("b") == ib and pool.slot_of("a") is None
    # Re-acquiring a resident tenant is free (no load).
    loads = pool.loads
    assert pool.acquire("b") == ib and pool.loads == loads
    pool.check_invariants()


def test_pool_fits_counts_resident_bytes_and_known_rejects():
    pool = _pool(max_resident=2, known={"a", "b"})
    assert pool.has("a") and not pool.has("nope")
    assert pool.has(None)  # base is always servable
    pool.acquire("a")
    assert pool.resident_bytes() == pool.adapter_bytes() * 1
    assert pool.fits("b")
    pool.acquire("b")
    assert not pool.fits("c") or pool.max_resident > 2


# -- per-tenant token identity (stub engine) -------------------------------


def _serve_all(engine, work, max_new=12):
    """work: (key, ids, adapter) triples. Returns key -> token list."""
    out: dict[str, list[int]] = {k: [] for k, _, _ in work}
    backlog = list(work)
    active: set[str] = set()
    while backlog or active:
        while backlog and engine.can_admit(
            len(backlog[0][1]), max_new, backlog[0][2]
        ):
            key, ids, ad = backlog.pop(0)
            active.add(key)
            engine.submit(key, ids, max_new, adapter=ad)
        for key, tok, done in engine.step():
            out[key].append(int(tok))
            if done:
                active.discard(key)
    return out


@pytest.mark.parametrize("window", [1, 8])
@pytest.mark.parametrize("spec_k", [0, 2])
def test_stub_multi_tenant_identity_across_k_and_spec(window, spec_k):
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    tenants = ["ta", "tb", "tc"]
    prompts = {"ta": [3, 5], "tb": [7], "tc": [11, 2, 4]}

    shared = make_stub_paged_engine(
        max_slots=4, vocab=53, window=window, spec_k=spec_k,
        lora_max_resident=4,
    )
    mixed = _serve_all(
        shared,
        [(n, prompts[n], n) for n in tenants] + [("base", [9], None)],
    )
    for n in tenants:
        solo = make_stub_paged_engine(
            max_slots=4, vocab=53, window=window, spec_k=spec_k,
            lora_max_resident=4,
        )
        want = _serve_all(solo, [(n, prompts[n], n)])
        assert mixed[n] == want[n], (n, window, spec_k)
    # The base stream is bitwise what a LoRA-free engine emits.
    plain = make_stub_paged_engine(
        max_slots=4, vocab=53, window=window, spec_k=spec_k,
    )
    want_base = _serve_all(plain, [("base", [9], None)])
    assert mixed["base"] == want_base["base"]


def test_stub_adapter_changes_tokens():
    """The identity test above is vacuous if adapters are no-ops."""
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    engine = make_stub_paged_engine(
        max_slots=2, vocab=53, lora_max_resident=2
    )
    got = _serve_all(engine, [("t", [3], "ta"), ("b", [3], None)])
    assert got["t"] != got["b"]


# -- zero steady-state compiles across churn -------------------------------


def test_adapter_churn_holds_zero_compiles_and_one_chunk_shape():
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    engine = make_stub_paged_engine(
        max_slots=2, vocab=53, window=4, lora_max_resident=2,
    )
    names = [f"t{i}" for i in range(6)]
    # Warmup: compile the lora window + chunk shapes once.
    _serve_all(engine, [(f"w/{n}", [5], n) for n in names[:2]])
    assert engine.lora.resident == 2
    n0 = len(_COMPILE_EVENTS)
    for cycle in range(2):
        for n in names:
            _serve_all(engine, [(f"{cycle}/{n}", [7], n)])
    # 6 tenants through 2 resident slots: plenty of eviction traffic...
    assert engine.lora.evictions > 0
    # ...and not one new executable: adapter ids are data, the stacked
    # pool's shape never changes.
    assert len(_COMPILE_EVENTS) == n0, _COMPILE_EVENTS[n0:]
    assert engine.chunk_prefill._cache_size() == 1


# -- prefix-cache tenancy isolation ----------------------------------------


def test_prefix_cache_never_shares_pages_across_tenants():
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    engine = make_stub_paged_engine(
        max_slots=4, vocab=53, page_size=8, chunk=8,
        prefix_cache=True, lora_max_resident=4,
    )
    prompt = list(range(3, 19))  # two full pages
    _serve_all(engine, [("a0", prompt, "ta")], max_new=4)
    hits0 = engine.prefix_cache.hit_tokens
    # Same tenant, same prompt: the cached pages ARE shared.
    _serve_all(engine, [("a1", prompt, "ta")], max_new=4)
    assert engine.prefix_cache.hit_tokens > hits0
    # Different tenant, identical prompt: zero hits — KV written under
    # one adapter must never serve another.
    hits1 = engine.prefix_cache.hit_tokens
    _serve_all(engine, [("b0", prompt, "tb")], max_new=4)
    assert engine.prefix_cache.hit_tokens == hits1
    # And the base (adapter-less) namespace is separate from both.
    _serve_all(engine, [("c0", prompt, None)], max_new=4)
    assert engine.prefix_cache.hit_tokens == hits1


def test_prefix_cache_lookup_keys_on_adapter():
    from dora_tpu.models.batch_engine import PageAllocator
    from dora_tpu.models.prefix_cache import PrefixCache

    a = PageAllocator(16)
    c = PrefixCache(a, 4)
    ids = list(range(1, 9))
    pages = a.alloc(2)
    c.insert(ids, pages, "ta")
    m, got, _mid = c.lookup(ids, "ta")
    assert (m, got) == (8, pages)
    m, got, _mid = c.lookup(ids, "tb")
    assert (m, got) == (0, [])
    m, got, _mid = c.lookup(ids, None)
    assert (m, got) == (0, [])


# -- checkpoint custody ----------------------------------------------------


def test_pre_lora_checkpoint_restores_identically_into_lora_engine():
    """An adapter-less snapshot (the pre-LoRA wire format: no
    ``adapter`` key in stream metas) restores into a LoRA-enabled
    engine and finishes byte-identically to an uninterrupted run."""
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    def build(lora):
        return make_stub_paged_engine(
            max_slots=2, vocab=53, window=1,
            lora_max_resident=2 if lora else 0,
        )

    # Uninterrupted reference on a plain engine.
    ref_engine = build(lora=False)
    want = _serve_all(ref_engine, [("r", [3, 5], None)], max_new=10)

    a = build(lora=False)
    a.submit("r", [3, 5], 10)
    got: dict[str, list[int]] = {"r": []}
    for _ in range(4):
        for key, tok, done in a.step():
            got[key].append(int(tok))
    snap = json.loads(json.dumps(a.checkpoint_state()))
    assert all("adapter" not in m for m in snap["slots"])

    b = build(lora=True)
    assert set(b.restore_state(snap)) == {"r"}
    active = {"r"}
    while active:
        for key, tok, done in b.step():
            got[key].append(int(tok))
            if done:
                active.discard(key)
    assert got == want


def test_checkpoint_carries_adapter_and_restores_per_tenant():
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    def build():
        return make_stub_paged_engine(
            max_slots=2, vocab=53, window=1, lora_max_resident=2,
        )

    want = _serve_all(build(), [("t", [3, 5], "ta")], max_new=10)

    a = build()
    a.submit("t", [3, 5], 10, adapter="ta")
    got: dict[str, list[int]] = {"t": []}
    for _ in range(4):
        for key, tok, done in a.step():
            got[key].append(int(tok))
    snap = json.loads(json.dumps(a.checkpoint_state()))
    assert [m.get("adapter") for m in snap["slots"]] == ["ta"]

    b = build()
    assert set(b.restore_state(snap)) == {"t"}
    active = {"t"}
    while active:
        for key, tok, done in b.step():
            got[key].append(int(tok))
            if done:
                active.discard(key)
    assert got == want
    assert b.lora.slot_of("ta") is not None


def test_restore_with_adapter_into_plain_engine_refuses():
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    a = make_stub_paged_engine(
        max_slots=2, vocab=53, window=1, lora_max_resident=2,
    )
    a.submit("t", [3, 5], 10, adapter="ta")
    for _ in range(2):
        list(a.step())
    snap = json.loads(json.dumps(a.checkpoint_state()))
    plain = make_stub_paged_engine(max_slots=2, vocab=53, window=1)
    with pytest.raises(RuntimeError):
        plain.restore_state(snap)


# -- serving-layer routing -------------------------------------------------


def test_admission_queue_parks_and_admits_with_adapter():
    from dora_tpu.models.batch_engine import make_stub_paged_engine
    from dora_tpu.nodehub.llm_server import AdmissionQueue

    engine = make_stub_paged_engine(
        max_slots=2, vocab=53, lora_max_resident=2,
    )
    started: list[tuple[str, str | None]] = []
    q = AdmissionQueue(
        engine, lambda k, ids, mn, ad=None: started.append((k, ad))
    )
    engine.submit("s0", [1, 2], 2)
    engine.submit("s1", [1, 2], 2)
    assert q.push("parked", [3, 4], 4, adapter="ta")
    (key, _ids, _mn, _cls, adapter), = q.pending()
    assert (key, adapter) == ("parked", "ta")
    for _ in range(20):
        list(engine.step())
        q.drain()
        if started:
            break
    assert started == [("parked", "ta")]


def test_base_model_names_and_unknown_tenant_gate():
    from dora_tpu.models.batch_engine import make_stub_paged_engine
    from dora_tpu.nodehub.llm_server import BASE_MODEL_NAMES

    engine = make_stub_paged_engine(
        max_slots=2, vocab=53, lora_max_resident=2,
    )
    # The server resolves any non-base `model` against the catalog;
    # the stub pool is open (known=None) so every name is servable,
    # while a catalog-backed pool rejects strangers.
    for name in BASE_MODEL_NAMES:
        assert (name or None) is None or name in ("dora-tpu", "base")
    assert engine.lora.has("any-tenant")
    engine.lora.known = {"ta"}
    assert engine.lora.has("ta") and not engine.lora.has("tb")
    assert engine.lora.has(None)


# -- real tiny model -------------------------------------------------------


@pytest.fixture(scope="module")
def lora_qwen2(tmp_path_factory):
    """Tiny random Qwen2 checkpoint + an adapter catalog of two
    tenants whose deltas are large enough to flip greedy tokens."""
    import torch
    from transformers import Qwen2Config, Qwen2ForCausalLM

    config = Qwen2Config(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rope_theta=10000.0,
        rms_norm_eps=1e-6, tie_word_embeddings=False,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    path = tmp_path_factory.mktemp("qwen2-lora")
    Qwen2ForCausalLM(config).eval().save_pretrained(
        path, safe_serialization=True
    )
    lora_dir = tmp_path_factory.mktemp("adapters")
    rng = np.random.default_rng(7)
    for name, scale, rank in (("ta", 0.3, 4), ("tb", 0.5, 8)):
        np.savez(
            lora_dir / f"{name}.npz",
            **{
                f"a_{i}": rng.normal(size=(64, rank)).astype(np.float32)
                * scale
                for i in range(2)
            },
            **{
                f"b_{i}": rng.normal(size=(rank, 64)).astype(np.float32)
                * scale
                for i in range(2)
            },
        )
    return path, lora_dir


@pytest.mark.parametrize("window,spec_k", [(1, 0), (8, 0), (1, 2), (8, 2)])
def test_qwen2_per_tenant_identity(lora_qwen2, window, spec_k):
    import os

    from dora_tpu.models.hf import qwen2

    path, lora_dir = lora_qwen2
    cfg, params = qwen2.load(str(path), max_seq=64)
    os.environ["DORA_INT8_DECODE"] = "1"
    try:
        params = qwen2.quantize_decode(params, cfg)
    finally:
        os.environ.pop("DORA_INT8_DECODE", None)

    def engine():
        return qwen2.make_paged_engine(
            params, cfg, max_slots=4, page_size=8, chunk=8,
            window=window, spec_k=spec_k, lora_dir=str(lora_dir),
        )

    prompts = {"ta": [3, 5, 7], "tb": [11, 2], None: [9, 4]}
    mixed = _serve_all(
        engine(),
        [("ta", prompts["ta"], "ta"), ("tb", prompts["tb"], "tb"),
         ("base", prompts[None], None)],
        max_new=8,
    )
    for tenant in ("ta", "tb"):
        solo = _serve_all(
            engine(), [(tenant, prompts[tenant], tenant)], max_new=8
        )
        assert mixed[tenant] == solo[tenant], (tenant, window, spec_k)
    # Base stream: byte-identical to an engine with no catalog at all.
    plain = qwen2.make_paged_engine(
        params, cfg, max_slots=4, page_size=8, chunk=8,
        window=window, spec_k=spec_k,
    )
    want = _serve_all(plain, [("base", prompts[None], None)], max_new=8)
    assert mixed["base"] == want["base"]
    # And the adapters genuinely steer: tenants disagree with base.
    assert mixed["ta"] != want["base"] or mixed["tb"] != want["base"]
