"""Paged KV engine (models/batch_engine.PagedBatchEngine).

The load-bearing properties:

* TOKEN IDENTITY: the paged + chunked-prefill engine emits exactly the
  greedy tokens the serial batch-1 path (qwen2.generate) emits,
  across staggered multi-slot admissions including prompts longer than
  one prefill chunk — block-table indirection and chunk interleaving
  change WHERE the KV rows live and WHEN prefill work runs, never the
  math.
* CAPACITY: 16 concurrent slots run inside the HBM four contiguous
  [max_seq] cache planes take (pages are granted for actual context).
* COMPILE COUNT: steady-state serving (admissions at varied prompt
  lengths + decode steps) triggers ZERO new XLA compiles after warmup,
  and chunked prefill compiles exactly one chunk shape.
"""

from __future__ import annotations

import numpy as np
import pytest
import torch

#: every XLA backend compile observed in this process (the jax-internal
#: monitoring event fires once per backend_compile; registered at import
#: so warmup compiles are counted too)
_COMPILE_EVENTS: list[str] = []
#: every trace of a jitted function and every backend compile, as
#: ``(kind, fun_name)``: what JAX itself times inside a jit call
_JIT_EVENTS: list[tuple[str, str]] = []


def _register_compile_listener() -> None:
    from jax._src import monitoring

    def _on_duration(event: str, duration: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILE_EVENTS.append(event)
            _JIT_EVENTS.append(("compile", str(kwargs.get("fun_name", ""))))
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            _JIT_EVENTS.append(("trace", str(kwargs.get("fun_name", ""))))

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
    path = tmp_path_factory.mktemp("qwen2-paged")
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


@pytest.fixture(scope="module")
def serial_ref(quantized):
    """Serial batch-1 greedy reference, cached per prompt tuple."""
    import jax.numpy as jnp

    from dora_tpu.models.hf import qwen2

    cfg, qparams = quantized
    cache: dict[tuple, list[int]] = {}

    def ref(prompt: list[int], max_new: int) -> list[int]:
        key = (tuple(prompt), max_new)
        if key not in cache:
            cache[key] = np.asarray(
                qwen2.generate(
                    qparams, cfg, jnp.asarray([prompt], jnp.int32), max_new
                )
            )[0].tolist()
        return cache[key]

    return ref


def _drain(streams: dict, events) -> None:
    for rid, token, _done in events:
        streams[rid].append(token)


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------


def test_allocator_reserves_null_page_and_is_all_or_nothing():
    from dora_tpu.models.batch_engine import PageAllocator

    a = PageAllocator(8)
    assert a.free_pages == 7  # page 0 reserved
    grant = a.alloc(7)
    assert grant is not None and 0 not in grant
    assert sorted(grant) == list(range(1, 8))
    assert a.alloc(1) is None  # empty pool refuses
    a.free(grant[:3])
    assert a.free_pages == 3
    assert a.alloc(4) is None  # all-or-nothing: no partial grant
    assert a.free_pages == 3  # refused alloc takes nothing
    assert sorted(a.alloc(3)) == sorted(grant[:3])


def test_pages_needed_covers_chunk_padding():
    from dora_tpu.models.batch_engine import PagedBatchEngine

    e = PagedBatchEngine(
        init_pool=lambda n: {}, chunk_prefill=None, window_step=None,
        max_slots=2, max_seq=64, page_size=8, chunk=16, num_pages=9,
    )
    # chunked prefill writes WHOLE pages: a 3-token prompt still burns a
    # full 16-row chunk = 2 pages, even though 3+4 decode rows fit in 1
    assert e.pages_needed(3, 4) == 2
    # decode reach past the chunk padding is what sizes the grant
    assert e.pages_needed(3, 30) == 5  # 33 rows -> ceil(33/8)
    assert e.pages_needed(16, 4) == 3  # 20 rows beats the 16-row chunk
    # fits() rejects never-admissible requests up front
    assert not e.fits(60, 8)  # 68 rows > max_seq
    assert e.fits(62, 2)  # 64 rows = 8 pages = the whole usable pool
    # a second stream can't co-reside with a pool-filling one: admission
    # is page-aware, not just slot-aware
    e2 = PagedBatchEngine(
        init_pool=lambda n: {}, chunk_prefill=None, window_step=None,
        max_slots=2, max_seq=64, page_size=8, chunk=16, num_pages=9,
    )
    e2.allocator.alloc(8)
    assert e2.fits(3, 4) and not e2.can_admit(3, 4)


# ---------------------------------------------------------------------------
# token identity vs the serial reference
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def staggered(quantized):
    """``run(window)``: staggered multi-slot admissions, including a
    37-token prompt that spans FIVE 8-token chunks admitted while other
    streams decode; (engine, prompts, max_new, streams) after the drain,
    cached per window."""
    cache: dict[int, tuple] = {}

    def run(window: int) -> tuple:
        if window not in cache:
            cache[window] = _staggered(quantized, window)
        return cache[window]

    return run


def _staggered(quantized, window: int) -> tuple:
    from dora_tpu.models.hf import qwen2

    cfg, qparams = quantized
    rng = np.random.default_rng(5)
    plens = (3, 7, 12, 37, 5)
    prompts = [rng.integers(0, cfg.vocab, size=n).tolist() for n in plens]
    max_new = 10
    paged = qwen2.make_paged_engine(
        qparams, cfg, max_slots=5, page_size=8, chunk=8, window=window
    )
    streams: dict[str, list[int]] = {f"r{i}": [] for i in range(len(plens))}
    paged.submit("r0", prompts[0], max_new)
    for _ in range(3):
        _drain(streams, paged.step())
    paged.submit("r1", prompts[1], max_new)
    paged.submit("r2", prompts[2], max_new)
    _drain(streams, paged.step())
    paged.submit("r3", prompts[3], max_new)  # 5-chunk prompt mid-run
    _drain(streams, paged.step())
    paged.submit("r4", prompts[4], max_new)
    for _ in range(300):
        if not paged.active:
            break
        _drain(streams, paged.step())
    assert paged.active == 0
    return paged, prompts, max_new, streams


@pytest.mark.parametrize("window", (1, 8))
def test_paged_matches_serial_across_staggered_admissions(
    staggered, serial_ref, window
):
    """Admissions staggered mid-decode, at per-token dispatch (K=1) and
    with the fused 8-tick decode window: the serial streams either
    way."""
    paged, prompts, max_new, streams = staggered(window)
    for i, prompt in enumerate(prompts):
        assert streams[f"r{i}"] == serial_ref(prompt, max_new), (
            f"K={window} stream r{i} diverged from the serial ref"
        )
    # Every page returned to the allocator (no leaks across the run).
    assert paged.free_pages == paged.allocator.num_pages - 1


def test_window_amortizes_host_round_trips(staggered):
    """The window amortizes host round-trips even on this short
    workload."""
    rt = {}
    for k in (1, 8):
        engine = staggered(k)[0]
        rt[k] = engine.dispatches + engine.fetches
    assert rt[8] < rt[1], rt


@pytest.mark.parametrize("window", (1, 8))
def test_window_freezes_streams_mid_window(quantized, serial_ref, window):
    """Device-side completion INSIDE a K=8 window: one stream hits EOS
    mid-window, another's max_new expires mid-window. The window must
    freeze each the very tick it finishes (KV writes rerouted to the
    null page), the host unpack must truncate at the done offset, and
    the emitted streams must be the serial reference cut at the same
    eos — at K=1 too."""
    from dora_tpu.models.hf import qwen2

    cfg, qparams = quantized
    rng = np.random.default_rng(31)
    prompts = [rng.integers(0, cfg.vocab, size=n).tolist() for n in (4, 6)]
    max_new = (12, 5)  # r1's cap expires at tick 4 of its first window

    # Pick eos = r0's 6th greedy token: with K=8 the EOS lands at tick 5
    # of r0's first full window — strictly inside it.
    ref0 = serial_ref(prompts[0], max_new[0])
    eos = ref0[5]

    def expect(i: int) -> list[int]:
        out = []
        for t in serial_ref(prompts[i], max_new[i])[: max_new[i]]:
            out.append(t)
            if t == eos:
                break
        return out

    engine = qwen2.make_paged_engine(
        qparams, cfg, max_slots=2, page_size=8, chunk=8, eos=eos,
        window=window,
    )
    streams: dict[str, list[int]] = {"r0": [], "r1": []}
    engine.submit("r0", prompts[0], max_new[0])
    engine.submit("r1", prompts[1], max_new[1])
    for _ in range(100):
        if not engine.active:
            break
        _drain(streams, engine.step())
    assert engine.active == 0
    for rid, i in (("r0", 0), ("r1", 1)):
        assert streams[rid] == expect(i), f"paged K={window} {rid}"
    # EOS actually cut r0 short and the cap cut r1 short (mid-window).
    assert len(streams["r0"]) == 6 and len(streams["r1"]) == 5


def test_16_slots_inside_a_4_plane_footprint(quantized, serial_ref):
    """16 slots in EXACTLY the KV HBM of four contiguous [max_seq]
    cache planes (qwen2.init_cache, the serial reference's): the
    default pool is 4 * max_seq rows per layer (null page included),
    and 16 short streams decode concurrently inside it."""
    import jax

    from dora_tpu.models.hf import qwen2

    cfg, qparams = quantized
    paged = qwen2.make_paged_engine(
        qparams, cfg, max_slots=16, page_size=8, chunk=8, window=8
    )
    plane_caches = qwen2.init_cache(cfg, 4)
    pool_bytes = sum(
        leaf.nbytes for leaf in jax.tree.leaves(paged.pools)
    )
    plane_bytes = sum(
        leaf.nbytes for leaf in jax.tree.leaves(plane_caches)
    )
    assert pool_bytes <= plane_bytes
    assert paged.max_slots == 16

    rng = np.random.default_rng(11)
    base_prompts = [
        rng.integers(0, cfg.vocab, size=n).tolist() for n in (3, 4, 2, 4)
    ]
    max_new = 4
    streams: dict[str, list[int]] = {}
    for i in range(16):
        rid = f"s{i}"
        streams[rid] = []
        assert paged.can_admit(len(base_prompts[i % 4]), max_new)
        paged.submit(rid, base_prompts[i % 4], max_new)
    assert paged.active == 16  # all concurrent
    for _ in range(200):
        if not paged.active:
            break
        _drain(streams, paged.step())
    assert paged.active == 0
    for i in range(16):
        want = serial_ref(base_prompts[i % 4], max_new)
        assert streams[f"s{i}"] == want, f"stream s{i} diverged"
    assert paged.free_pages == paged.allocator.num_pages - 1


# ---------------------------------------------------------------------------
# speculative decoding inside the window
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec_k", (2, 4))
@pytest.mark.parametrize("window", (1, 8))
def test_spec_window_token_identity(quantized, serial_ref, spec_k, window):
    """Prompt-lookup speculation folded into the paged window emits
    EXACTLY the spec-off / serial greedy streams at every
    (K, k): drafts only ever propose, the batched verification pass
    decides — including multi-chunk prompts admitted mid-decode."""
    from dora_tpu.models.hf import qwen2

    cfg, qparams = quantized
    rng = np.random.default_rng(5)
    plens = (3, 7, 12, 5)
    prompts = [rng.integers(0, cfg.vocab, size=n).tolist() for n in plens]
    max_new = 10

    engine = qwen2.make_paged_engine(
        qparams, cfg, max_slots=4, page_size=8, chunk=8, window=window,
        spec_k=spec_k,
    )
    assert engine.spec_k == spec_k
    streams: dict[str, list[int]] = {f"r{i}": [] for i in range(len(plens))}
    engine.submit("r0", prompts[0], max_new)
    for _ in range(3):
        _drain(streams, engine.step())
    engine.submit("r1", prompts[1], max_new)
    engine.submit("r2", prompts[2], max_new)
    _drain(streams, engine.step())
    engine.submit("r3", prompts[3], max_new)
    for _ in range(300):
        if not engine.active:
            break
        _drain(streams, engine.step())
    assert engine.active == 0
    for i in range(len(plens)):
        assert streams[f"r{i}"] == serial_ref(prompts[i], max_new), (
            f"spec k={spec_k} K={window} stream r{i} diverged"
        )
    assert engine.free_pages == engine.allocator.num_pages - 1


@pytest.mark.parametrize("window", (1, 8))
def test_spec_window_freezes_streams_mid_chunk(quantized, serial_ref, window):
    """Completion INSIDE a verified chunk: one stream hits EOS at a
    draft position, another's max_new expires mid-chunk. The spec
    window must truncate the tick's emission AT the completing token
    (later accepted candidates discarded), freeze the stream
    (null-page KV routing), and the host replay must agree — emitted
    streams identical to the spec-off engine with the same eos."""
    from dora_tpu.models.hf import qwen2

    cfg, qparams = quantized
    rng = np.random.default_rng(31)
    prompts = [rng.integers(0, cfg.vocab, size=n).tolist() for n in (4, 6)]
    max_new = (12, 5)
    eos = serial_ref(prompts[0], max_new[0])[5]

    def expect(i: int) -> list[int]:
        out = []
        for t in serial_ref(prompts[i], max_new[i])[: max_new[i]]:
            out.append(t)
            if t == eos:
                break
        return out

    def run(spec_k: int):
        engine = qwen2.make_paged_engine(
            qparams, cfg, max_slots=2, page_size=8, chunk=8, eos=eos,
            window=window, spec_k=spec_k,
        )
        streams: dict[str, list[int]] = {"r0": [], "r1": []}
        engine.submit("r0", prompts[0], max_new[0])
        engine.submit("r1", prompts[1], max_new[1])
        for _ in range(100):
            if not engine.active:
                break
            _drain(streams, engine.step())
        assert engine.active == 0
        assert engine.free_pages == engine.allocator.num_pages - 1
        return streams

    off = run(0)
    for spec_k in (2, 4):
        got = run(spec_k)
        for rid, i in (("r0", 0), ("r1", 1)):
            want = expect(i)
            assert off[rid] == want, f"spec-off {rid}"
            assert got[rid] == want, f"spec k={spec_k} K={window} {rid}"
    assert len(off["r0"]) == 6 and len(off["r1"]) == 5


def test_spec_headroom_shapes_admission(quantized):
    """fits()/pages_needed() reserve the verification tail (spec_k + 1
    rows): a request that fills max_seq exactly is admissible spec-off
    but must be rejected spec-on — the last verify would write past the
    sequence end mid-owed-tokens otherwise (the serial gate's contract,
    in page units)."""
    from dora_tpu.models.hf import qwen2

    cfg, qparams = quantized
    off = qwen2.make_paged_engine(
        qparams, cfg, max_slots=2, page_size=8, chunk=8, window=1,
    )
    on = qwen2.make_paged_engine(
        qparams, cfg, max_slots=2, page_size=8, chunk=8, window=1, spec_k=4,
    )
    assert on.spec_headroom() == 5 and off.spec_headroom() == 0
    assert off.fits(56, 8)  # 64 rows = max_seq exactly
    assert not on.fits(56, 8)  # + 5 tail rows would cross max_seq
    assert on.fits(51, 8)
    # the tail also costs pages when it crosses a page boundary
    assert on.pages_needed(3, 30) == off.pages_needed(3, 35)


def test_steady_state_adds_zero_compiles_and_one_chunk_shape(quantized):
    """After warmup, admissions at NEW prompt lengths plus decode
    drains must not trigger a single XLA compile — at K=8 AND at K=1
    (positions, block tables, chunk offsets, the active mask and the
    emitted/max_new vectors are all traced operands of fixed shape).
    The chunked-prefill jit and the K-window jit each hold exactly ONE
    compiled shape."""
    from dora_tpu.models.hf import qwen2

    cfg, qparams = quantized
    engines = {
        k: qwen2.make_paged_engine(
            qparams, cfg, max_slots=4, page_size=8, chunk=16, window=k
        )
        for k in (8, 1)
    }
    rng = np.random.default_rng(7)

    def run(engine, lengths: tuple[int, ...]) -> None:
        streams: dict[str, list[int]] = {}
        for i, n in enumerate(lengths):
            rid = f"w{n}-{i}"
            streams[rid] = []
            while not engine.can_admit(n, 6):
                _drain(streams, engine.step())
            engine.submit(rid, rng.integers(0, cfg.vocab, size=n).tolist(), 6)
            _drain(streams, engine.step())
        for _ in range(200):
            if not engine.active:
                return
            _drain(streams, engine.step())

    for engine in engines.values():
        run(engine, (3, 12, 20))  # warmup: single- and multi-chunk
    warm = len(_COMPILE_EVENTS)

    for engine in engines.values():
        run(engine, (5, 9, 17, 33, 2))  # five NEW lengths, both K
    assert len(_COMPILE_EVENTS) == warm, (
        f"steady-state serving compiled "
        f"{len(_COMPILE_EVENTS) - warm} new XLA program(s)"
    )
    for k, engine in engines.items():
        # Exactly one chunk shape and one window shape ever: each jit's
        # cache holds one entry after prompt lengths from 2 to 33 and
        # every slot-membership pattern the drains walked through.
        assert engine.chunk_prefill.func._cache_size() == 1, f"K={k}"
        assert engine.window_step.func._cache_size() == 1, f"K={k}"


def test_spec_steady_state_adds_zero_compiles(quantized):
    """The compile discipline holds with speculation ON: drafts,
    verification chunks, acceptance lengths and history updates are all
    traced fixed-shape operands, so steady-state serving (new prompt
    lengths + ragged acceptance + drains) adds ZERO XLA compiles and
    the spec window jit holds exactly ONE compiled shape."""
    from dora_tpu.models.hf import qwen2

    cfg, qparams = quantized
    engine = qwen2.make_paged_engine(
        qparams, cfg, max_slots=4, page_size=8, chunk=16, window=8,
        spec_k=4,
    )
    rng = np.random.default_rng(7)

    def run(lengths: tuple[int, ...]) -> None:
        streams: dict[str, list[int]] = {}
        for i, n in enumerate(lengths):
            rid = f"w{n}-{i}"
            streams[rid] = []
            while not engine.can_admit(n, 6):
                _drain(streams, engine.step())
            engine.submit(rid, rng.integers(0, cfg.vocab, size=n).tolist(), 6)
            _drain(streams, engine.step())
        for _ in range(200):
            if not engine.active:
                return
            _drain(streams, engine.step())

    run((3, 12, 20))  # warmup
    warm = len(_COMPILE_EVENTS)
    run((5, 9, 17, 33, 2))  # five NEW lengths
    assert len(_COMPILE_EVENTS) == warm, (
        f"spec-on steady state compiled "
        f"{len(_COMPILE_EVENTS) - warm} new XLA program(s)"
    )
    assert engine.chunk_prefill.func._cache_size() == 1
    assert engine.window_step.func._cache_size() == 1


def test_window_operands_cached_while_membership_is_unchanged(quantized):
    """With membership unchanged between two ``dispatch()`` calls the
    engine rebuilds no block-table / max_new operand (host work inside
    the dispatch gap); freeing a slot invalidates both."""
    from dora_tpu.models.hf import qwen2

    cfg, qparams = quantized
    engine = qwen2.make_paged_engine(
        qparams, cfg, max_slots=2, page_size=8, chunk=8, window=1
    )
    engine.submit("a", [1, 2, 3], 8)
    assert engine._members_dirty
    engine.step()  # prefill lands, the row joins the window: rebuilds
    assert not engine._members_dirty and not engine._bt_dirty
    bt, maxnew = engine._bt_dec, engine._maxnew_dev
    for _ in range(2):
        engine.dispatch()
        assert engine._bt_dec is bt and engine._maxnew_dev is maxnew
        engine.collect()
    while engine.active:
        engine.step()
    # freeing a slot invalidates the cached operands
    assert engine._members_dirty and engine._bt_dirty


# -- a first token goes to its slot on the device (PR 40) --------------------
#
# The stub engine: the real scheduler over the affine rule
# next = (7 t + 3) % 97, so every stream is known without a model.

_MIXED = [("a", 5, 9), ("b", 20, 6), ("c", 40, 1), ("d", 16, 12), ("e", 33, 7),
          ("f", 5, 9), ("g", 47, 3), ("h", 1, 2), ("i", 31, 5), ("j", 18, 1)]
#: sha256(repr(emitted))[:16] of the run below as the PARENT of PR 40
#: emitted it (the read of a first token before the window's launch),
#: by ``eos``: 39 is e's first token and a's fourth, 54 h's first and
#: d's second, 87 the first of a and of its twin f
_MIXED_AS_BEFORE = {None: (55, "eea7e09dd9be0edb"), 39: (39, "e93fad7339262e64"),
                    54: (44, "2d8aaea50243cf98"), 87: (38, "a6b8742a262bcdb1")}


def _mixed_prompt(rid: str, n: int) -> list[int]:
    rid = "a" if rid == "f" else rid  # f repeats a: a prefix-cache hit
    return [(ord(rid) * 5 + 3 * i) % 97 for i in range(n)]


def _mixed_run(engine, halves: bool = True, after=None) -> list:
    """One-chunk, three-chunk and one-token requests, a twin, three
    slots for ten streams; ``after(engine, first)`` runs between the
    halves, where the loop sends."""
    out, pending = [], list(_MIXED)
    while pending or engine.active:
        while pending and engine.can_admit(pending[0][1], pending[0][2]):
            rid, n, max_new = pending.pop(0)
            engine.submit(rid, _mixed_prompt(rid, n), max_new)
        if halves:
            first = engine.dispatch()
            if after is not None:
                after(engine, first)
            out += first + engine.collect()
        else:
            out += engine.step()
        engine.check_invariants()
    return out


def _stub(**kw):
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    return make_stub_paged_engine(
        max_slots=3, window=4, chunk=16, max_seq=64, prefix_cache=True, **kw
    )


@pytest.mark.parametrize("eos", list(_MIXED_AS_BEFORE))
def test_the_emitted_sequence_is_the_one_before_the_read_moved(eos):
    import hashlib

    from dora_tpu.metrics import ServingMetrics

    engine = _stub(eos=eos)
    phases = engine.tracer.histograms = ServingMetrics().phases
    out = _mixed_run(engine)
    engine.tracer.close()
    # token for token and in the same order, step() and the halves alike
    assert out == _mixed_run(_stub(eos=eos), halves=False)
    count, digest = _MIXED_AS_BEFORE[eos]
    assert len(out) == count
    assert hashlib.sha256(repr(out).encode()).hexdigest()[:16] == digest
    # and each stream is the rule's, cut at its cap or at eos
    streams: dict[str, list] = {}
    for rid, token, done in out:
        streams.setdefault(rid, []).append((token, done))
    for rid, n, max_new in _MIXED:
        want, t = [], _mixed_prompt(rid, n)[-1]
        while len(want) < max_new and (not want or want[-1] != eos):
            t = (7 * t + 3) % 97
            want.append(t)
        assert [tok for tok, _ in streams[rid]] == want, rid
        assert [d for _, d in streams[rid]] == [False] * (len(want) - 1) + [True]
    # the order that gave them: every first token whose dispatch also
    # launched a window was read beside it
    reads, waits = phases["first_token_read"].count, phases["first_token_wait"].count
    assert reads + waits == len(_MIXED) and reads >= 8


@pytest.mark.parametrize("eos,stream", [(39, "e"), (54, "h"), (87, "a")])
def test_a_first_token_that_is_eos_ends_its_stream_with_that_token(eos, stream):
    seen = []

    def after(engine, first):
        # between dispatch() and collect(): the stream that ended on its
        # first token holds no slot any more, and nothing took its place
        for rid, token, done in first:
            if token == eos:
                assert done and engine.in_flight
                assert all(s is None or s.request_id != rid for s in engine.slots)
                seen.append((rid, engine.free_slots, engine.free_pages))

    engine = _stub(eos=eos)
    out = _mixed_run(engine, after=after)
    assert [(t, d) for r, t, d in out if r == stream] == [(eos, True)]
    assert seen and seen[0][0] == stream and seen[0][1] >= 1
    # the window it sat in gave it nothing, and its pages came back
    assert engine.active == 0 and engine.free_slots == 3
    engine.prefix_cache.evict(engine.allocator.num_pages)
    assert engine.free_pages == engine.allocator.num_pages - 1
    engine.check_invariants()


def test_a_one_token_stream_keeps_its_slot_until_its_token_is_read():
    """``max_new`` 1 ends a stream without the token's value, but not
    before the chunk is done: the chunk was handed a view of the slot's
    row of the block table, and freeing the slot zeroes that row."""
    engine = _stub()
    seen = []
    read = engine._first_token

    def spy(s, b, *rest):
        # at the read: the slot and its row are as the chunk met them
        seen.append((s.request_id, engine.slots[b] is s,
                     engine._bt[b, : len(s.pages)].tolist() == s.pages))
        return read(s, b, *rest)

    engine._first_token = spy
    engine.submit("long", [4, 5], 30)
    engine.step()
    engine.submit("one", list(range(20)), 1)  # two chunks, beside a window
    first = engine.dispatch()
    assert first == [] and engine.collect()
    first = engine.dispatch()
    assert [(r, d) for r, _, d in first] == [("one", True)] and engine.in_flight
    assert seen == [("long", True, True), ("one", True, True)]
    # read, it is gone: one slot of three is taken, by the other stream
    assert engine.free_slots == 2
    engine.collect()
    engine.check_invariants()


def test_speculation_keeps_the_read_before_the_launch():
    from dora_tpu.metrics import ServingMetrics

    plain, spec = _stub(), _stub(spec_k=2)
    phases = spec.tracer.histograms = ServingMetrics().phases
    out = _mixed_run(spec)
    spec.tracer.close()
    assert phases["first_token_wait"].count == len(_MIXED)
    assert phases["first_token_read"].count == 0 and spec.launched_at is None
    # the streams are the plain engine's; only the windows' packing differs
    def by_stream(rows):
        return {rid: [t for r, t, _ in rows if r == rid] for rid, _, _ in _MIXED}

    assert by_stream(out) == by_stream(_mixed_run(plain))
    # paused, the window needs no history before its launch: the read moves
    spec.set_window(4, spec_on=False)
    _mixed_run(spec)
    spec.tracer.close()
    assert phases["first_token_read"].count >= 8
    assert spec._hist == [[] for _ in spec.slots]


def test_set_slot_is_one_program_for_every_remainder_of_a_prompt():
    engine = _stub()
    for n in range(1, 34):  # every remainder modulo the chunk, twice
        engine.submit(f"p{n}", list(range(n)), 3)
        while engine.active:
            engine.step()
    assert engine._set_slot._cache_size() == 1
    assert engine.chunk_prefill._cache_size() == 1
    # a restored stream is seated by the same program
    engine.submit("q", [1, 2, 3], 8)
    engine.step()
    state = engine.checkpoint_state()
    other = _stub()
    assert other.restore_state(state) == ["q"]
    other.submit("r", [4, 5], 3)
    while other.active:
        other.step()
    assert other._set_slot._cache_size() == 1


def test_the_window_a_stream_joins_counts_its_first_token_as_emitted():
    """The device's completion counter is rebuilt before the first token
    is read: a stream of two tokens decodes ONE tick of the window it
    joins and is frozen for the rest, as when the read came first."""
    engine = _stub()
    engine.submit("two", [1, 2, 3], 2)
    engine.submit("long", [4, 5], 9)
    first = engine.dispatch()
    assert [(r, d) for r, _, d in first] == [("two", False)]
    # as the window returns it: the first token, and the one tick
    assert np.asarray(engine._emitted_dev).tolist() == [2, 0, 0]
    row = np.asarray(engine._flight[0])[0]
    assert (row[: engine.window] >= 0).tolist() == [True, False, False, False]
    assert [(r, d) for r, _, d in engine.collect()] == [("two", True)]


# -- a chunk ahead: the next period's chunk behind the running window (PR 45) -
#
# ``dispatch()`` then ``collect()`` is every chunk in line; ``ahead()``
# between them (``step()`` calls it, and the serving loop after its
# flush) hands the next period's chunk to the device early. Same
# programs in the same order, same tokens.


def _go_ahead(engine, first):
    engine.ahead()


def _logged(engine) -> list:
    """Every program the engine hands the device, in the order it does:
    a chunk with its base and block-table row, a window with its rows."""
    log = []
    chunk, window, dispatch = (
        engine.chunk_prefill, engine.window_step, engine.dispatch)

    def counted_dispatch():
        log.append(("dispatch",))
        return dispatch()

    def chunk_prefill(ids, pools, position, bt, *rest):
        log.append(("chunk", int(position), np.asarray(bt).tolist()))
        return chunk(ids, pools, position, bt, *rest)

    def window_step(tokens, pools, positions, bts, active, *rest):
        log.append(("window", np.asarray(active).tolist()))
        return window(tokens, pools, positions, bts, active, *rest)

    engine.chunk_prefill, engine.window_step = chunk_prefill, window_step
    engine.dispatch = counted_dispatch
    engine._window_cache = {(engine.window, engine.spec_k): window_step}
    return log


@pytest.mark.parametrize("eos", list(_MIXED_AS_BEFORE))
def test_chunks_ahead_emit_what_chunks_in_line_emit(eos):
    """Multi-chunk and one-chunk prompts, one-token requests, a twin
    (a prefix-cache hit), first tokens that are ``eos``: token for token
    and in the same order, from the same programs in the same order."""
    import hashlib

    from dora_tpu.metrics import ServingMetrics

    line, went = _stub(eos=eos), _stub(eos=eos)
    programs = _logged(line), _logged(went)
    phases = went.tracer.histograms = ServingMetrics().phases
    out = _mixed_run(went, after=_go_ahead)
    went.tracer.close()
    assert out == _mixed_run(line)
    count, digest = _MIXED_AS_BEFORE[eos]
    assert len(out) == count
    assert hashlib.sha256(repr(out).encode()).hexdigest()[:16] == digest
    assert line.chunks_ahead == 0 and line.chunks_run == went.chunks_run
    # (b) the device's order is chunk, window, chunk, window as in line,
    # program for program: never two chunks between two windows
    assert ([p for p in programs[0] if p[0] != "dispatch"]
            == [p for p in programs[1] if p[0] != "dispatch"])
    kinds = "".join(p[0][0] for p in programs[1])
    assert "wcd" in kinds and "dcw" in kinds  # ahead of, and in, its period
    for between in kinds.split("w")[1:-1]:
        # a dispatch brings at most one chunk, whichever side of it
        assert between.count("c") <= between.count("d"), kinds
    assert "wcdcw" not in kinds and "wcdw" in kinds
    # (c) a chunk is launched in the gap or goes ahead, never both
    assert went.chunks_ahead >= went.chunks_run // 2
    assert phases["chunk_ahead"].count == went.chunks_ahead
    assert (phases["chunk_launch"].count + phases["chunk_ahead"].count
            == went.chunks_run)
    # every first token behind a window was still read beside it
    reads, waits = phases["first_token_read"].count, phases["first_token_wait"].count
    assert reads + waits == len(_MIXED) and reads >= 8
    went.check_invariants()


def test_a_chunk_goes_ahead_only_behind_a_window_and_one_a_period():
    engine = _stub()
    engine.submit("p", list(range(40)), 20)  # three chunks, nothing decodes
    for want in (1, 2):
        assert engine.dispatch() == [] and not engine.in_flight
        engine.ahead()  # no window was launched: nothing goes ahead
        assert engine._ahead is None and engine.chunks_run == want
        assert engine.collect() == []
    engine.submit("q", list(range(20)), 4)
    assert [r for r, _, _ in engine.dispatch()] == ["p"] and engine.in_flight
    engine.ahead()
    engine.ahead()  # once a period
    assert engine.chunks_run == 4 and engine.chunks_ahead == 1
    s, b, _greedy = engine._ahead
    assert (s.request_id, s.chunk_base) == ("q", 0)  # the host's side waits
    engine.collect()
    # the dispatch that finds it launches none of its own
    assert engine.dispatch() == [] and engine.chunks_run == 4
    assert engine._ahead is None and s.chunk_base == 16
    engine.ahead()  # q's final chunk
    assert engine.chunks_ahead == 2 and engine._prefillq[0] == b
    assert s.prompt is not None and s.emitted == 0 and not engine._decode[b]
    engine.collect()
    # adopted where an in-line chunk's bookkeeping runs: in dispatch()
    assert [r for r, _, _ in engine.dispatch()] == ["q"]
    assert s.prompt is None and s.emitted == 1 and engine._decode[b]
    assert not engine._prefillq and engine.chunks_run == 5
    engine.collect()
    engine.check_invariants()


@pytest.mark.parametrize(
    "between", ["checkpoint", "drain", "preempt", "preempt_refill"])
def test_what_lands_between_a_final_chunk_ahead_and_its_adoption(between):
    """A reader of slots between ``collect()`` and the next ``dispatch()``
    sees the stream whose final chunk went ahead as it would with the
    chunk still to come: the two engines answer alike and go on alike."""
    runs = []
    for go in (False, True):
        engine = _stub()
        out = []

        def turn():
            out.extend(engine.dispatch())
            if go:
                engine.ahead()
            out.extend(engine.collect())

        engine.submit("long", [4, 5], 30)
        turn()
        engine.submit("x", list(range(7)), 5)
        engine.submit("y", list(range(9, 20)), 6)
        turn()  # x in line; y's one chunk, its final, goes ahead
        assert (engine._ahead is not None) == go
        if between == "checkpoint":
            state = engine.checkpoint_state()
            out.append(state)
            y = next(m for m in state["slots"] if m["request_id"] == "y")
            assert not y["decode"] and y["emitted"] == 0 and y["chunk_base"] == 0
        elif between == "drain":
            # migrated out and back in: y is prefilled again from scratch
            state = engine.drain_streams()
            out.append(state)
            assert engine._ahead is None and not engine.active
            assert sorted(engine.admit_streams(state)) == ["long", "y"]
        else:
            out.append(engine.preempt("y"))
            assert out[-1]["emitted"] == 0 and not out[-1]["was_decoding"]
            engine.check_invariants()
            if between == "preempt_refill":
                # its slot goes to another stream before the next dispatch
                engine.submit("z", list(range(30, 50)), 4)
            turn()
            engine.submit("y", list(range(9, 20)), 6)  # resumed from scratch
        while engine.active:
            turn()
        engine.check_invariants()
        assert engine.chunks_ahead > 0 if go else engine.chunks_ahead == 0
        runs.append(out)
    def by_stream(out):
        rows = [e for e in out if isinstance(e, tuple)]
        return {rid: [e[1:] for e in rows if e[0] == rid] for rid, _, _ in rows}

    assert by_stream(runs[0]) == by_stream(runs[1])
    assert set(by_stream(runs[0])) >= {"long", "x", "y"}
    if between != "preempt_refill":
        # there the chunk whose stream went was the period's chunk all
        # the same, so z's begins a period later than in line
        assert runs[0] == runs[1]


def test_a_slot_freed_after_the_enqueue_leaves_the_chunks_block_table_row():
    """The chunk's row of the block table is handed over as a copy: on
    this backend ``jnp.asarray`` of a numpy view may alias it, and
    freeing the slot zeroes the row in place (debt 20)."""
    engine = _stub()
    handed = []
    chunk = engine.chunk_prefill

    def chunk_prefill(ids, pools, position, bt, *rest):
        handed.append(bt)
        return chunk(ids, pools, position, bt, *rest)

    engine.chunk_prefill = chunk_prefill
    engine.submit("long", [4, 5], 30)
    engine.step()
    engine.submit("y", list(range(20)), 6)
    engine.dispatch()
    engine.ahead()
    b = engine._ahead[1]
    row = engine._bt[b].copy()
    assert row.any() and np.asarray(handed[-1]).tolist() == row.tolist()
    engine.collect()
    assert engine.preempt("y") is not None and not engine._bt[b].any()
    assert np.asarray(handed[-1]).tolist() == row.tolist()
    # the in-line chunk's operand too
    engine.submit("w", list(range(5)), 1)
    engine.dispatch()  # the chunk that went ahead lost its stream: no chunk
    engine.collect()
    n = len(handed)
    first = engine.dispatch()
    assert len(handed) == n + 1 and [(r, d) for r, _, d in first] == [("w", True)]
    assert engine.slots[b] is None or engine.slots[b].request_id != "w"
    assert np.asarray(handed[-1]).any()
    engine.collect()
    engine.check_invariants()


def test_a_programs_first_call_and_no_other_runs_in_a_roomy_frame(monkeypatch):
    """The call that traces, lowers and compiles a chunk or window
    program goes through ``backend.roomy`` (where the stack below cannot
    slow it: the start-up PR 45 lost); every later call is direct."""
    from dora_tpu import backend

    roomy, real = [], backend.roomy

    def counted(program, *operands):
        roomy.append(program)
        return real(program, *operands)

    monkeypatch.setattr(backend, "roomy", counted)
    engine = _stub()
    out = _mixed_run(engine, after=_go_ahead)
    assert len(out) == _MIXED_AS_BEFORE[None][0]
    assert engine.chunks_run > 5 and engine.dispatches > 10
    assert roomy == [engine.chunk_prefill, engine.window_step]
    # a window program the autotuner brings is new: its first call too
    assert engine.set_window(2)
    engine.submit("again", list(range(5)), 4)
    while engine.active:
        engine.step()
    assert roomy == [engine.chunk_prefill, engine._window_cache[(4, 0)],
                     engine.window_step]


def test_under_speculation_a_final_chunk_never_goes_ahead():
    """With ``spec_k`` the host needs a first token before the launch it
    would precede (the history mirror goes into the window's operands):
    a prompt's other chunks go ahead, its final one is launched in line
    and read before the window, and the tokens are those of ``step()``."""
    from dora_tpu.metrics import ServingMetrics

    def serve(halves: bool):
        engine = _stub(spec_k=2)
        phases = engine.tracer.histograms = ServingMetrics().phases
        engine.submit("decodes", [4, 5], 24)
        out = engine.step()
        engine.submit("p", list(range(40)), 6)  # three chunks
        engine.submit("q", list(range(7, 12)), 5)  # one, final
        finals_ahead = 0
        while engine.active:
            if halves:
                out += engine.dispatch()
                engine.ahead()
                if engine._ahead is not None:
                    s = engine._ahead[0]
                    finals_ahead += engine._final_chunk(s, s.chunk_base)
                out += engine.collect()
            else:
                out += engine.step()
            engine.check_invariants()
        engine.tracer.close()
        return out, engine, phases, finals_ahead

    want, line, _, _ = serve(False)
    got, went, phases, finals_ahead = serve(True)
    assert got == want and line.chunks_ahead == 0
    assert went.chunks_run == 1 + 3 + 1 and went.chunks_ahead == 1
    assert finals_ahead == 0
    # every first token was read before its window's launch, blocking
    assert phases["first_token_wait"].count == 3
    assert phases["first_token_read"].count == 0
    assert phases["chunk_launch"].count + phases["chunk_ahead"].count == 5


@pytest.mark.parametrize("ahead", [False, True])
def test_the_stubs_modelled_period_is_window_plus_chunk_behind_a_chunk_ahead(
    monkeypatch, ahead
):
    """The stub's modelled device is one queue and a wait is for one
    piece of work: with a prompt queued at every launch and the host
    busy ``GAP`` between a ``collect()`` and the next ``dispatch()``,
    the period is window + chunk where the chunk went ahead (the gap
    runs beside it), window + chunk + gap where it is launched in line.
    On a clock of its own: no second of the wall's is read."""
    from types import SimpleNamespace

    from dora_tpu.models import batch_engine

    now = [100.0]

    def sleep(seconds: float) -> None:
        now[0] += seconds

    monkeypatch.setattr(batch_engine, "time", SimpleNamespace(
        perf_counter=lambda: now[0], sleep=sleep, monotonic=lambda: now[0]))
    TICK, CHUNK, GAP, K = 0.010, 0.030, 0.020, 4
    engine = batch_engine.make_stub_paged_engine(
        max_slots=3, window=K, chunk=16, max_seq=64,
        tick_sleep_s=TICK, chunk_sleep_s=CHUNK,
    )
    engine.submit("decodes", [4, 5], 60)
    engine.step()
    ends = []
    for turn in range(12):
        if engine.can_admit(20, 2):
            # two chunks a prompt: the prefill queue is never empty
            engine.submit(f"p{turn}", list(range(20)), 2)
        engine.dispatch()
        if ahead:
            engine.ahead()
        engine.collect()
        ends.append(now[0])
        sleep(GAP)  # the host between collect() and the next dispatch()
    periods = [round(b - a, 6) for a, b in zip(ends[2:], ends[3:])]
    want = K * TICK + CHUNK + (0.0 if ahead else GAP)
    assert periods == [pytest.approx(want)] * len(periods), periods
    assert (engine.chunks_ahead > 0) == ahead


@pytest.mark.parametrize("prefix_cache", [False, True])
def test_chunks_ahead_give_the_serial_streams_on_a_model_that_reads_its_cache(
    quantized, serial_ref, prefix_cache
):
    """The real chunk and window programs (tiny Qwen2): five- and
    two-chunk prompts whose chunks go ahead of their periods behind the
    windows of streams that decode, then attend what those chunks wrote;
    the tokens are those of ``step()`` and of the serial reference. With
    the prefix cache on, a twin of the five-chunk prompt maps the pages
    a chunk that went ahead wrote and its stream's adoption inserted."""
    from dora_tpu.models.hf import qwen2

    cfg, qparams = quantized
    rng = np.random.default_rng(11)
    plens = (3, 37, 12, 6, 21)
    prompts = [rng.integers(0, cfg.vocab, size=n).tolist() for n in plens]
    prompts.append(list(prompts[1]))  # the twin, submitted once r1 decodes

    def serve(halves: bool):
        paged = qwen2.make_paged_engine(
            qparams, cfg, max_slots=5, page_size=8, chunk=8, window=4,
            prefix_cache=prefix_cache,
        )
        streams: dict[str, list[int]] = {f"r{i}": [] for i in range(len(prompts))}
        for i, prompt in enumerate(prompts[:-1]):
            paged.submit(f"r{i}", prompt, 10)
        twin = False
        for _ in range(300):
            if twin and not paged.active:
                break
            if halves:
                first = paged.dispatch()
                paged.ahead()
                _drain(streams, first + paged.collect())
            else:
                _drain(streams, paged.step())
            if not twin and streams["r1"] and paged.can_admit(37, 10):
                paged.submit("r5", prompts[-1], 10)
                twin = True
        assert twin and paged.active == 0
        paged.check_invariants()
        return streams, paged

    want, line = serve(False)
    got, went = serve(True)
    assert got == want and line.chunks_ahead == 0
    # the twin re-prefills five chunks, or the one its cached pages leave
    assert went.chunks_run == line.chunks_run == 12 + (1 if prefix_cache else 5)
    assert went.chunks_ahead >= 8  # all but those no window ran before
    for i, prompt in enumerate(prompts):
        assert got[f"r{i}"] == serial_ref(prompt, 10), f"r{i}"
    if prefix_cache:
        assert went.prefix_cache.hits == line.prefix_cache.hits == 1
    else:
        assert went.free_pages == went.allocator.num_pages - 1


def test_a_warm_wave_traces_and_compiles_both_programs_in_its_first_dispatch(
    quantized,
):
    """The guard for what PR 45 was refused for (a start-up that grew):
    a warm wave through the serving loop — one request, then fifteen
    behind it, ``dispatch → emit → ahead → collect`` — traces and
    compiles the chunk program once and the window program once, both
    inside the first ``dispatch()``; no later ``dispatch()``, ``ahead()``
    or ``collect()`` traces or compiles anything. Counts, not seconds."""
    from dora_tpu.metrics import ServingMetrics
    from dora_tpu.models.hf import qwen2
    from dora_tpu.nodehub.llm_server import AdmissionQueue, _run_loop

    cfg, qparams = quantized
    # shapes no other test of this module compiles: this engine's
    # programs are traced and compiled here, whatever ran before
    engine = qwen2.make_paged_engine(
        qparams, cfg, max_slots=16, page_size=8, chunk=32, window=3
    )
    rng = np.random.default_rng(3)
    prompts = {f"w{i}": rng.integers(0, cfg.vocab, size=20).tolist()
               for i in range(16)}
    calls: list[tuple[str, list]] = []

    def counted(name):
        call = getattr(engine, name)

        def run():
            before = len(_JIT_EVENTS)
            out = call()
            calls.append((name, _JIT_EVENTS[before:]))
            return out

        setattr(engine, name, run)

    for name in ("dispatch", "ahead", "collect"):
        counted(name)
    sent: list[str] = []

    class Node:
        stream_ended = False
        script = list(prompts)

        def recv(self, timeout=None):
            # one request; the other fifteen once its first token left
            if self.script and (len(self.script) == 16 or sent):
                return {"type": "INPUT", "rid": self.script.pop(0)}
            self.stream_ended = not self.script
            return None

    metrics = ServingMetrics(engine="paged")
    backlog = AdmissionQueue(
        engine, lambda k, ids, mn, adapter: engine.submit(k, ids, mn)
    )
    _run_loop(
        Node(), engine, backlog, metrics,
        lambda event: backlog.push(event["rid"], prompts[event["rid"]],
                                   2 * (1 + int(event["rid"][1:]))),
        lambda key, tokens, done: sent.append(key),
        lambda now: None,
    )
    assert engine.chunks_run == 16 and engine.chunks_ahead >= 6
    assert [name for name, _ in calls[:3]] == ["dispatch", "ahead", "collect"]
    first = calls[0][1]
    chunk_name = engine.chunk_prefill.func.__name__
    window_name = engine.window_step.func.__name__
    for program in (chunk_name, window_name):
        assert first.count(("trace", program)) == 1, (program, first)
        assert sum(kind == "compile" and program in fun
                   for kind, fun in first) == 1, (program, first)
    later = [(name, events) for name, events in calls[1:] if events]
    assert later == [], later[:3]
    assert engine.chunk_prefill.func._cache_size() == 1
    assert engine.window_step.func._cache_size() == 1
