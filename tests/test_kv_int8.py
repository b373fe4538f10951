"""Quantized serving: int8 KV pages with per-page scales.

The divergence metric is layered so every gate is checkable on CPU:

* NUMBER FORMAT: ``ops.decode_block.kv_quant_rows`` is the single
  definition of the page format — the kernels' quantize-on-write sites
  and this file's references call the same function, so the int8
  payloads are compared BITWISE (scales get a 1-ulp band for XLA's
  division strength-reduction; see ``_assert_pool_parity``).
* KERNEL PARITY: each quantized paged kernel (batch / chunk / spec)
  must be bitwise-equal to its fp twin run on a ``kv_dequant``'d
  snapshot of the same pool — the quantized kernel IS the fp kernel
  over dequantized context, plus int8 writes. On CPU the kernels run
  under the Pallas interpreter as plain jnp ops, so f32 arithmetic is
  deterministic and "bitwise" means bitwise.
* E2E: the int8-KV engine emits exactly the fp engine's greedy tokens
  on the tiny CI model across K x spec_k, with zero steady-state
  compiles and ONE compiled window shape — quantization is a trace
  constant, not a shape. (Real models with near-tie continuations may
  legitimately flip argmaxes — KNOWN_ISSUES round 18; the tiny-model
  identity is the CI regression gate, not a product guarantee.)
* CUSTODY: capacity in the same byte budget, fp<->int8 snapshot
  rejection, prefix-cache sharing identity, and the quant-error gauge.
"""

from __future__ import annotations

import numpy as np
import pytest
import torch

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from dora_tpu.models import layers as L

#: every XLA backend compile observed in this process (same listener as
#: test_paged_engine — registered at import so warmups are counted)
_COMPILE_EVENTS: list[str] = []


def _register_compile_listener() -> None:
    from jax._src import monitoring

    def _on_duration(event: str, duration: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILE_EVENTS.append(event)

    monitoring.register_event_duration_secs_listener(_on_duration)


_register_compile_listener()


# ---------------------------------------------------------------------------
# number format
# ---------------------------------------------------------------------------


def test_kv_quant_rows_format():
    from dora_tpu.ops.decode_block import kv_dequant, kv_quant_rows

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((3, 2, 8, 16)), jnp.float32)
    q, s = kv_quant_rows(x)
    assert q.dtype == jnp.int8 and q.shape == x.shape
    assert s.dtype == jnp.float32 and s.shape == x.shape[:-1]
    # symmetric: the row amax lands on +-127 exactly
    assert int(jnp.max(jnp.abs(q.astype(jnp.int32)))) == 127
    # worst-case per-element error is scale/2
    deq = kv_dequant(q, s, jnp.float32)
    err = np.asarray(jnp.abs(deq - x))
    bound = np.asarray(s)[..., None] * 0.5 + 1e-6
    assert (err <= bound).all()
    # all-zero rows hit the scale floor instead of dividing by zero
    qz, sz = kv_quant_rows(jnp.zeros((2, 4), jnp.float32))
    assert not np.asarray(qz).any()
    assert np.allclose(np.asarray(sz), 1e-8)


# ---------------------------------------------------------------------------
# kernel parity: quant kernel == fp kernel over the dequantized pool
# ---------------------------------------------------------------------------

_D, _H, _KV, _HD, _S, _PAGE = 64, 4, 2, 16, 64, 8


def _weights(rng):
    from dora_tpu.ops.int8_matmul import quantize_int8

    nw = jnp.asarray(rng.standard_normal(_D), jnp.float32)
    wqkv = quantize_int8(jnp.asarray(
        rng.standard_normal((_D, (_H + 2 * _KV) * _HD)), jnp.float32))
    wo = quantize_int8(jnp.asarray(
        rng.standard_normal((_H * _HD, _D)), jnp.float32))
    bqkv = jnp.asarray(rng.standard_normal((_H + 2 * _KV) * _HD), jnp.float32)
    return nw, wqkv, bqkv, wo


def _quant_pools(rng, pages):
    """Random int8 pools + scale planes, quantized through the shared
    format, and the fp snapshot the parity run reads."""
    from dora_tpu.ops.decode_block import kv_dequant, kv_quant_rows

    kf = jnp.asarray(
        rng.standard_normal((pages, _KV, _PAGE, _HD)), jnp.float32) * 0.1
    vf = jnp.asarray(
        rng.standard_normal((pages, _KV, _PAGE, _HD)), jnp.float32) * 0.1
    kq, ks = kv_quant_rows(kf)
    vq, vs = kv_quant_rows(vf)
    snap_k = kv_dequant(kq, ks, jnp.float32)
    snap_v = kv_dequant(vq, vs, jnp.float32)
    return (kq, vq, ks, vs), (snap_k, snap_v)


def _assert_pool_parity(quant_out, quant_in, fp_out, written):
    """The quant kernel's pool writes: every WRITTEN (page, row) must be
    bitwise kv_quant_rows of the fp kernel's written row; every other
    entry must be bit-preserved from the input pool."""
    from dora_tpu.ops.decode_block import kv_quant_rows

    (kpq, vpq, ksq, vsq) = [np.asarray(a) for a in quant_out]
    (kq0, vq0, ks0, vs0) = [np.asarray(a) for a in quant_in]
    kpf, vpf = np.asarray(fp_out[0]), np.asarray(fp_out[1])
    exp_k, exp_ks = kq0.copy(), ks0.copy()
    exp_v, exp_vs = vq0.copy(), vs0.copy()
    for pg, off in written:
        qk, sk = kv_quant_rows(jnp.asarray(kpf[pg, :, off, :]))
        qv, sv = kv_quant_rows(jnp.asarray(vpf[pg, :, off, :]))
        exp_k[pg, :, off, :], exp_ks[pg, :, off] = np.asarray(qk), np.asarray(sk)
        exp_v[pg, :, off, :], exp_vs[pg, :, off] = np.asarray(qv), np.asarray(sv)
    np.testing.assert_array_equal(kpq, exp_k)
    np.testing.assert_array_equal(vpq, exp_v)
    # Scales: the kernel's compiled ``amax / 127`` may differ from the
    # eager reference by one ulp (XLA strength-reduces the division to
    # a reciprocal multiply inside the fused kernel). The QUANTIZATION
    # DECISIONS (the int8 payloads above) are still bitwise — a 1-ulp
    # scale never moves round(x/scale) on these magnitudes — so scales
    # get a 1-ulp band and untouched entries still compare exactly
    # (they round-trip as stored bits).
    np.testing.assert_allclose(ksq, exp_ks, rtol=2e-7, atol=0)
    np.testing.assert_allclose(vsq, exp_vs, rtol=2e-7, atol=0)


def test_paged_batch_step_quant_bitwise_parity():
    """One decode row per stream, positions covering both halves of an
    8-row scale group and a page boundary."""
    from dora_tpu.ops.decode_block import (
        attention_paged_batch_step, rope_rows_at,
    )

    rng = np.random.default_rng(1)
    B = 4
    positions = [9, 30, 7, 16]
    npages = _S // _PAGE
    nw, wqkv, bqkv, wo = _weights(rng)
    (kq, vq, ks, vs), (snap_k, snap_v) = _quant_pools(rng, 1 + B * npages)
    bt = np.zeros((B, npages), np.int32)
    for b in range(B):
        bt[b] = 1 + b * npages + np.arange(npages)
    x = jnp.asarray(rng.standard_normal((B, _D)), jnp.float32)
    cos_t, sin_t = L.rope_table(_S, _HD)
    pos_arr = jnp.asarray(positions, jnp.int32)
    cosr, sinr = rope_rows_at(cos_t, sin_t, pos_arr)

    xo_q, kp, vp, ksp, vsp = attention_paged_batch_step(
        x, nw, wqkv["int8"], wqkv["scale"], bqkv, cosr, sinr, kq, vq,
        wo["int8"], wo["scale"], pos_arr, jnp.asarray(bt), ks, vs,
        heads=_H, kv_heads=_KV, head_dim=_HD,
    )
    xo_f, kpf, vpf = attention_paged_batch_step(
        x, nw, wqkv["int8"], wqkv["scale"], bqkv, cosr, sinr,
        snap_k, snap_v, wo["int8"], wo["scale"], pos_arr, jnp.asarray(bt),
        heads=_H, kv_heads=_KV, head_dim=_HD,
    )
    np.testing.assert_array_equal(np.asarray(xo_q), np.asarray(xo_f))
    written = [
        (int(bt[b, positions[b] // _PAGE]), positions[b] % _PAGE)
        for b in range(B)
    ]
    _assert_pool_parity((kp, vp, ksp, vsp), (kq, vq, ks, vs),
                        (kpf, vpf), written)


def test_paged_chunk_step_quant_bitwise_parity():
    """A 16-row prefill chunk (2 whole pages) with 16 rows of prior
    context streaming through the table."""
    from dora_tpu.ops.decode_block import (
        attention_paged_chunk_step, rope_rows,
    )

    rng = np.random.default_rng(2)
    M, pos = 16, 16
    npages = _S // _PAGE
    nw, wqkv, bqkv, wo = _weights(rng)
    (kq, vq, ks, vs), (snap_k, snap_v) = _quant_pools(rng, 1 + npages)
    bt = np.arange(1, 1 + npages, dtype=np.int32)
    x = jnp.asarray(rng.standard_normal((M, _D)), jnp.float32)
    cos_t, sin_t = L.rope_table(_S, _HD)
    cosr, sinr = rope_rows(cos_t, sin_t, pos, M)

    xo_q, kp, vp, ksp, vsp = attention_paged_chunk_step(
        x, nw, wqkv["int8"], wqkv["scale"], bqkv, cosr, sinr, kq, vq,
        wo["int8"], wo["scale"], pos, jnp.asarray(bt), ks, vs,
        heads=_H, kv_heads=_KV, head_dim=_HD,
    )
    xo_f, kpf, vpf = attention_paged_chunk_step(
        x, nw, wqkv["int8"], wqkv["scale"], bqkv, cosr, sinr,
        snap_k, snap_v, wo["int8"], wo["scale"], pos, jnp.asarray(bt),
        heads=_H, kv_heads=_KV, head_dim=_HD,
    )
    np.testing.assert_array_equal(np.asarray(xo_q), np.asarray(xo_f))
    written = [
        (int(bt[r // _PAGE]), r % _PAGE) for r in range(pos, pos + M)
    ]
    _assert_pool_parity((kp, vp, ksp, vsp), (kq, vq, ks, vs),
                        (kpf, vpf), written)


def test_paged_spec_step_quant_bitwise_parity():
    """B speculative-verify chunks, positions exercising the straddle
    window (pos=6, m=5 crosses a page AND a scale-group boundary)."""
    from dora_tpu.ops.decode_block import (
        attention_paged_spec_step, rope_rows_at,
    )

    rng = np.random.default_rng(3)
    B, M = 4, 5
    positions = [9, 30, 6, 16]
    npages = _S // _PAGE
    nw, wqkv, bqkv, wo = _weights(rng)
    (kq, vq, ks, vs), (snap_k, snap_v) = _quant_pools(rng, 1 + B * npages)
    bt = np.zeros((B, npages), np.int32)
    for b in range(B):
        bt[b] = 1 + b * npages + np.arange(npages)
    x = jnp.asarray(rng.standard_normal((B * M, _D)), jnp.float32)
    cos_t, sin_t = L.rope_table(_S, _HD)
    pos_arr = jnp.asarray(positions, jnp.int32)
    flat = (pos_arr[:, None] + jnp.arange(M)[None, :]).reshape(B * M)
    cosr, sinr = rope_rows_at(cos_t, sin_t, flat)

    xo_q, kp, vp, ksp, vsp = attention_paged_spec_step(
        x, nw, wqkv["int8"], wqkv["scale"], bqkv, cosr, sinr, kq, vq,
        wo["int8"], wo["scale"], pos_arr, jnp.asarray(bt), ks, vs,
        heads=_H, kv_heads=_KV, head_dim=_HD, m=M,
    )
    xo_f, kpf, vpf = attention_paged_spec_step(
        x, nw, wqkv["int8"], wqkv["scale"], bqkv, cosr, sinr,
        snap_k, snap_v, wo["int8"], wo["scale"], pos_arr, jnp.asarray(bt),
        heads=_H, kv_heads=_KV, head_dim=_HD, m=M,
    )
    np.testing.assert_array_equal(np.asarray(xo_q), np.asarray(xo_f))
    written = [
        (int(bt[b, r // _PAGE]), r % _PAGE)
        for b in range(B) for r in range(positions[b], positions[b] + M)
    ]
    _assert_pool_parity((kp, vp, ksp, vsp), (kq, vq, ks, vs),
                        (kpf, vpf), written)


# ---------------------------------------------------------------------------
# e2e: fp vs int8 engines on the tiny model
# ---------------------------------------------------------------------------


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
    path = tmp_path_factory.mktemp("qwen2-kvint8")
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
    out: dict[str, list[int]] = {}
    for i, p in enumerate(prompts):
        engine.submit(f"r{i}", p, max_new)
        while engine.active or engine.prefilling:
            for rid, tok, _done in engine.step():
                out.setdefault(rid, []).append(tok)
    return out


@pytest.mark.parametrize("window", [1, 8])
@pytest.mark.parametrize("spec_k", [0, 2])
def test_greedy_identity_and_compile_discipline(quantized, window, spec_k):
    """The int8-KV engine emits exactly the fp engine's greedy tokens
    (multi-chunk prompts included), steady-state admissions at NEW
    prompt lengths add zero XLA compiles, and the chunk/window jits
    each hold exactly ONE compiled shape — the fp engine's compile
    discipline survives quantization at every (K, spec_k)."""
    from dora_tpu.models.hf import qwen2

    cfg, qparams = quantized
    rng = np.random.default_rng(11)
    warm = [rng.integers(0, cfg.vocab, size=n).tolist() for n in (3, 20)]
    fresh = [rng.integers(0, cfg.vocab, size=n).tolist() for n in (5, 33, 2)]

    def build(kv8: bool):
        return qwen2.make_paged_engine(
            qparams, cfg, max_slots=4, page_size=8, chunk=16,
            window=window, spec_k=spec_k, kv_int8=kv8,
        )

    fp, q8 = build(False), build(True)
    assert fp.kv_dtype == "fp" and q8.kv_dtype == "int8"
    fp_tokens = _run_sequential(fp, warm, 6)
    fp_tokens.update(_run_sequential(fp, fresh, 6))
    q8_warm = _run_sequential(q8, warm, 6)
    compiled = len(_COMPILE_EVENTS)
    q8_fresh = _run_sequential(q8, fresh, 6)
    assert len(_COMPILE_EVENTS) == compiled, (
        f"int8 steady state compiled "
        f"{len(_COMPILE_EVENTS) - compiled} new XLA program(s)"
    )
    assert {**q8_warm, **q8_fresh} == fp_tokens
    assert q8.chunk_prefill.func._cache_size() == 1
    assert q8.window_step.func._cache_size() == 1


# ---------------------------------------------------------------------------
# capacity: more streams in the SAME pool byte budget
# ---------------------------------------------------------------------------


def test_capacity_in_same_byte_budget(quantized):
    """The int8 pool auto-resizes its page count into the fp pool's
    byte budget (scale planes included) and admits >= 1.8x the
    concurrent streams through the real can_admit/submit path."""
    from dora_tpu.models.hf import qwen2

    cfg, qparams = quantized
    plen, max_new = 4, 24

    def admitted(kv8: bool):
        eng = qwen2.make_paged_engine(
            qparams, cfg, max_slots=512, page_size=8, chunk=8, kv_int8=kv8,
        )
        prompt = list(range(plen))
        n = 0
        while n < 512 and eng.can_admit(plen, max_new):
            eng.submit(f"c{n}", prompt, max_new)
            n += 1
        return n, eng.kv_pool_bytes()

    n_fp, bytes_fp = admitted(False)
    n_q8, bytes_q8 = admitted(True)
    assert bytes_q8 <= bytes_fp  # never exceeds the fp budget
    assert bytes_q8 >= 0.9 * bytes_fp  # and actually fills it
    assert n_q8 >= 1.8 * n_fp, (n_q8, n_fp)
    # page_pool_bytes is the math the auto-sizing used
    assert qwen2.page_pool_bytes(cfg, 8, kv_int8=True) < \
        qwen2.page_pool_bytes(cfg, 8)


# ---------------------------------------------------------------------------
# custody: checkpoint dtype gate, prefix sharing, quant-error gauge
# ---------------------------------------------------------------------------


def test_checkpoint_kv_dtype_mismatch_rejected(quantized):
    from dora_tpu.models.hf import qwen2

    cfg, qparams = quantized

    def build(kv8: bool):
        # window=1 keeps steps granular so the stream is still LIVE
        # when the snapshot is taken (a wide window would finish it)
        return qwen2.make_paged_engine(
            qparams, cfg, max_slots=2, page_size=8, chunk=16, window=1,
            kv_int8=kv8,
        )

    q8 = build(True)
    q8.submit("a", [1, 2, 3], 6)
    while q8.prefilling:
        q8.step()
    assert q8.active  # still decoding: the snapshot carries the stream
    snap = q8.checkpoint_state()
    assert snap["kv_dtype"] == "int8"
    with pytest.raises(ValueError, match="kv_dtype"):
        build(False).restore_state(snap)
    # round-trip onto a matching engine restores the stream
    assert build(True).restore_state(snap) == ["a"]
    # pre-quantization snapshots (no kv_dtype key) default to fp:
    # accepted by fp engines, rejected by int8 engines
    fp_snap = build(False).checkpoint_state()
    del fp_snap["kv_dtype"]
    assert build(False).restore_state(fp_snap) == []
    with pytest.raises(ValueError, match="kv_dtype"):
        build(True).restore_state(fp_snap)


def test_prefix_cache_shares_quantized_pages(quantized):
    """Shared-vs-cold identity with int8 pages: cache-hit admissions
    ref the QUANTIZED pages (values + scale planes move together), so
    warm tokens match the cold run exactly."""
    from dora_tpu.models.hf import qwen2

    cfg, qparams = quantized
    rng = np.random.default_rng(5)
    tmpl = rng.integers(0, cfg.vocab, size=24).tolist()
    tails = [rng.integers(0, cfg.vocab, size=n).tolist() for n in (2, 3)]
    prompts = [tmpl + tails[0], tmpl + tails[1]]

    def build(cache: bool):
        return qwen2.make_paged_engine(
            qparams, cfg, max_slots=4, page_size=8, chunk=16, window=8,
            prefix_cache=cache, kv_int8=True,
        )

    cold = _run_sequential(build(False), prompts, 6)
    eng = build(True)
    warm = _run_sequential(eng, prompts, 6)
    assert cold == warm
    assert eng.prefix_cache.hits == 1 and eng.prefix_cache.misses == 1
    eng.check_invariants()


def test_kv_quant_error_gauge(quantized):
    """The gauge is None on fp pools, and a small positive relative
    step on an int8 pool that actually holds context."""
    from dora_tpu.models.hf import qwen2

    cfg, qparams = quantized

    def build(kv8: bool):
        return qwen2.make_paged_engine(
            qparams, cfg, max_slots=2, page_size=8, chunk=16, window=1,
            kv_int8=kv8,
        )

    fp = build(False)
    assert fp.kv_quant_error() is None
    q8 = build(True)
    assert q8.kv_quant_error() == 0.0  # nothing allocated yet
    # keep the stream LIVE (window=1: one token per step): completed
    # streams free their pages and the gauge samples held pages only
    q8.submit("g", [1, 2, 3, 4], 16)
    for _ in range(6):
        q8.step()
    assert q8.active
    err = q8.kv_quant_error()
    assert err is not None and 0.0 < err < 0.05, err
    assert q8.kv_pool_bytes() > 0

    from dora_tpu.metrics import ServingMetrics

    m = ServingMetrics("paged")
    m.kv_dtype = q8.kv_dtype
    m.kv_pool_bytes = q8.kv_pool_bytes()
    m.kv_quant_err = err
    snap = m.snapshot()
    assert snap["kv_dtype"] == "int8"
    assert snap["kv_pool_bytes"] == q8.kv_pool_bytes()
    assert snap["kv_quant_err"] == err
