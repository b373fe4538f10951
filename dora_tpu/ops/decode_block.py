"""Fused batch-1 decode blocks as Pallas TPU kernels.

Why: int8 vanilla decode sits at ~70% of its own HBM-bandwidth bound.
The residue is not the weight stream — it is the other
~10 XLA ops per layer (norms, rope, cache update, attention, residuals)
plus 4 Pallas launches per layer, each a fixed ~2.4 us entry and a break
in DMA overlap. These kernels collapse one decode step to TWO Pallas
calls per layer plus one for the lm_head:

  ``attention_step``  — RMSNorm → fused int8 qkv matvec → RoPE →
      in-place KV-cache row write (HBM, no full-cache copy-back) →
      flash-decode over the *live* context (online softmax, streamed
      from the HBM cache in blocks, trip count = position/BS + 1) →
      int8 output projection → residual.
  ``mlp_step``        — RMSNorm → fused int8 gate/up matvec (streamed
      by ffn tile) → SiLU·mul → int8 down accumulation → residual,
      one grid sweep, VMEM flat in ffn width.
  ``lm_head_argmax``  — RMSNorm → int8 lm_head streamed by vocab tile
      with a running argmax in SMEM — the [1, 152k] f32 logits round
      trip to HBM and the XLA argmax disappear; the kernel returns the
      token id.

Quantization layout comes from ops.int8_matmul.quantize_tree(fuse=True):
``wqkv``/``w_gateup`` fused dicts — int8 with per-output-channel scales
(which commute with the matmul, so applying them on the f32 accumulator
is exact), or int4 group-packed nibbles with group scales (ops.int4,
DORA_INT4_DECODE=1 — half the decode bytes; every kernel dispatches on
the weight dtype).

Reference parity: the reference's decode path is torch/CUDA eager
(node-hub/dora-qwenvl/dora_qwenvl/main.py) with no fused-kernel tier;
this is the beat-on-perf axis on TPU. Non-TPU backends run the Pallas
interpreter (tests assert parity against the plain-JAX path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dora_tpu.backend import interpret as _interpret

_LANE = 128

#: Scoped-VMEM budget of the M-row chunk kernel. It keeps the whole chunk
#: resident (fused qkv/o weights cast to bf16, [M*H, hd] f32 q/k/v and the
#: [KV*M*group, M] in-chunk score tile): 17.2 MB at M=256 and the 1.5B
#: widths, over the compiler's 16 MiB default scope. The v5e has 128 MiB
#: of VMEM; half of it leaves the surrounding XLA fusions their room.
_CHUNK_VMEM_LIMIT = 64 * 1024 * 1024

#: The batched decode kernel takes ``wqkv`` and ``wo`` from HBM by column
#: tiles of about this many bytes, through a ring of ``_WEIGHT_SLOTS``
#: buffers. Kernel alone on the v5e at Ouro's widths (16.8 MB of int8,
#: 34 groups a call; PERF.md section 6, PR 55): 0.5, 0.75 and 1 MiB with
#: 3 and 4 buffers measured 75.1-77.5 us a call, 2 MiB 80.9; 1 MiB and 3
#: leave the call's scope inside the compiler's default 16 MiB at every
#: width served and at Falcon-H1's (no limit of the kernel's own).
_WEIGHT_TILE_BYTES = 1024 * 1024
_WEIGHT_SLOTS = 3


def _weight_tile_cols(rows: int) -> int:
    """Columns of a streamed tile of a weight of ``rows`` rows, a byte a
    value: whole lane tiles, one at least."""
    return max(_LANE, _WEIGHT_TILE_BYTES // rows // _LANE * _LANE)


def _rms(x_ref, w_ref, eps: float):
    """f32 RMSNorm of a [M, D] ref block against weight [1, D]."""
    x = x_ref[...].astype(jnp.float32)
    x = x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps
    )
    return x * w_ref[...].astype(jnp.float32)


def _wdot(x, w_ref, s, *, int4: bool):
    """``x @ W`` for a quantized weight block, f32 accumulator.

    int8 layout: w_ref [K, BN] int8, s [1, BN] per-column scale applied
    on the accumulator (commutes exactly). int4 layout: w_ref [K/2, BN]
    group-packed nibbles (ops.int4), s [K/GROUP, BN] group scales
    applied per-group via a batched dot — HBM streams half the bytes of
    int8. ``s`` is the loaded scale ARRAY (callers pass ``s_ref[...]``
    or a gathered tile).
    """
    dtype = x.dtype
    if not int4:
        return jax.lax.dot(
            x, w_ref[...].astype(dtype), preferred_element_type=jnp.float32
        ) * s.astype(jnp.float32)
    from dora_tpu.ops.int4 import unpack_grouped

    k = x.shape[-1]
    ng = s.shape[0]  # group count; group size = K // ng
    m = x.shape[0]
    gsz = k // ng
    # BIASED unpack (values q+8 in 0..15): the bias folds out of the
    # accumulator instead — ``x @ (q'-8) = x @ q' - 8*sum(x)`` per
    # group — deleting one VPU subtract per nibble from the unpack,
    # which KNOWN_ISSUES measured as the int4 bottleneck (the correction
    # term costs O(ng*M) flops against O(K*N) saved subtracts).
    q3 = unpack_grouped(w_ref[...], ng, dtype, biased=True)  # [ng, G, BN]
    # Grouped batched dot with f32 scale application on the partials.
    # Measured on v5e this beats folding scales into the weights
    # (307 tok/s) — the fold pays a VPU multiply on every weight value;
    # here the scale rides on the [ng, M, BN] partials instead. Numeric
    # note: q is integer-exact in bf16 and scales apply in f32, so this
    # is mathematically x @ dequantize, but its rounding differs from
    # the bf16(q*s) weights the unfused fallback uses — exact token
    # equality between the two is asserted on the f32 interpret path
    # (tests), and on TPU they may differ by final-ulp logit ties.
    x3 = x.reshape(m, ng, gsz).transpose(1, 0, 2)  # [ng, M, G]
    parts = jax.lax.dot_general(
        x3, q3, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # [ng, M, BN]
    xsum = jnp.sum(x3.astype(jnp.float32), axis=2)  # [ng, M]
    scaled = (parts - 8.0 * xsum[:, :, None]) * s.astype(jnp.float32)[:, None, :]
    return jnp.sum(scaled, axis=0)


def _rotate(x, cos_full, sin_signed, half: int):
    """NeoX rotary on [H, hd] rows given full-width tables:
    ``cos_full = [cos, cos]``, ``sin_signed = [-sin, sin]`` — then
    ``x*cos_full + swap_halves(x)*sin_signed`` is exactly
    ``[x1*cos - x2*sin, x2*cos + x1*sin]``."""
    swapped = jnp.concatenate([x[:, half:], x[:, :half]], axis=1)
    return x * cos_full + swapped * sin_signed


def kv_quant_rows(x):
    """Symmetric int8 row quantization over the last axis.

    The single definition of the KV-page number format: every
    quantize-on-write site in the paged kernels AND the plain-JAX
    reference in tests/test_kv_int8.py call THIS function, so kernel
    pool bytes are bitwise-checkable against the reference. Returns
    ``(q int8, scale f32)`` with ``scale`` shaped like ``x`` minus the
    last axis — one scale per (row, kv-head) so a page carries a
    [KV, page] scale plane parallel to its [KV, page, hd] values.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(
        jnp.round(xf / scale[..., None]), -127.0, 127.0
    ).astype(jnp.int8)
    return q, scale


def kv_dequant(q, scale, dtype):
    """Inverse of :func:`kv_quant_rows` at the sweep's read edge —
    dequantize int8 page values back to the compute dtype in-register.
    Shared with the test reference for the same bitwise reason."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


# ---------------------------------------------------------------------------
# attention block
# ---------------------------------------------------------------------------


def _attn_kernel(
    pos_ref,  # SMEM (1,) int32 — scalar prefetch
    x_ref, nw_ref, wqkv_ref, sqkv_ref, bqkv_ref, cos_ref, sin_ref,
    kc_in, vc_in, wo_ref, swo_ref,
    out_ref, kc_out, vc_out,
    kv_row, kblk, vblk, sem,
    *, heads: int, kv_heads: int, head_dim: int, bs: int, eps: float,
    residual: bool,
):
    pos = pos_ref[0]
    half = head_dim // 2
    dtype = x_ref.dtype
    int4 = wqkv_ref.dtype == jnp.uint8

    # --- projections --------------------------------------------------------
    h = _rms(x_ref, nw_ref, eps).astype(dtype)  # [1, D]
    qkv = _wdot(h, wqkv_ref, sqkv_ref[...], int4=int4) + bqkv_ref[...].astype(
        jnp.float32
    )
    qkv = qkv.reshape(heads + 2 * kv_heads, head_dim)
    q = qkv[:heads]
    k = qkv[heads : heads + kv_heads]
    v = qkv[heads + kv_heads :]

    cos_full = cos_ref[...].astype(jnp.float32)  # [1, hd]
    sin_signed = sin_ref[...].astype(jnp.float32)
    q = _rotate(q, cos_full, sin_signed, half)
    k = _rotate(k, cos_full, sin_signed, half)

    # --- in-place cache row write (overlapped) ------------------------------
    # DMA slices must be sublane-aligned (8), so the write is an aligned
    # 8-row read-modify-write: pull the row group, select-insert the new
    # row (no sub-tile dynamic indexing anywhere), push it back. The
    # attention below never reads position ``pos`` from the cache — the
    # fresh k/v fold in from registers — so only the RMW *read* gates
    # the insert; the write-back overlaps the whole attention sweep and
    # is awaited at kernel end.
    aligned = pl.multiple_of(pos // 8 * 8, 8)
    row_sel = (
        jax.lax.broadcasted_iota(jnp.int32, (kv_heads, 8, head_dim), 1)
        == pos - aligned
    )
    krd = pltpu.make_async_copy(
        kc_out.at[:, pl.ds(aligned, 8), :], kv_row.at[0], sem.at[0]
    )
    vrd = pltpu.make_async_copy(
        vc_out.at[:, pl.ds(aligned, 8), :], kv_row.at[1], sem.at[1]
    )
    krd.start()
    vrd.start()
    krd.wait()
    vrd.wait()
    kv_row[0] = jnp.where(
        row_sel, k[:, None, :].astype(kv_row.dtype), kv_row[0]
    )
    kv_row[1] = jnp.where(
        row_sel, v[:, None, :].astype(kv_row.dtype), kv_row[1]
    )
    kwr = pltpu.make_async_copy(
        kv_row.at[0], kc_out.at[:, pl.ds(aligned, 8), :], sem.at[0]
    )
    vwr = pltpu.make_async_copy(
        kv_row.at[1], vc_out.at[:, pl.ds(aligned, 8), :], sem.at[1]
    )
    kwr.start()
    vwr.start()

    # --- flash-decode over the PRIOR context (idx < pos) --------------------
    # Streams K/V HBM blocks; online softmax so VMEM is flat in context.
    # The row being written this step is excluded from the sweep (its
    # contribution folds in from registers below), which is what lets
    # the write-back stay off the critical path. NOTE: blocks past
    # ``aligned`` may transiently hold the half-written row group, but
    # that row is masked out by ``live``.
    group = heads // kv_heads
    scale = 1.0 / (head_dim ** 0.5)
    nblocks = (pos + bs - 1) // bs  # ceil(pos / bs): prior context only

    def body(b, carry):
        m_run, l_run, acc = carry
        kcp = pltpu.make_async_copy(
            kc_out.at[:, pl.ds(b * bs, bs), :], kblk, sem.at[2]
        )
        vcp = pltpu.make_async_copy(
            vc_out.at[:, pl.ds(b * bs, bs), :], vblk, sem.at[3]
        )
        kcp.start()
        vcp.start()
        kcp.wait()
        vcp.wait()
        live = (
            jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1) + b * bs
        ) < pos  # [1, bs] — strictly prior positions
        scores = []
        for g in range(kv_heads):
            s_g = jax.lax.dot_general(
                q[g * group : (g + 1) * group].astype(dtype),
                kblk[g].astype(dtype),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [group, bs]
            scores.append(s_g)
        s = jnp.concatenate(scores, axis=0) * scale  # [H, bs]
        s = jnp.where(live, s, -jnp.inf)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new)  # [H, bs]
        l_new = l_run * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = []
        for g in range(kv_heads):
            pv_g = jax.lax.dot(
                p[g * group : (g + 1) * group].astype(dtype),
                vblk[g].astype(dtype),
                preferred_element_type=jnp.float32,
            )  # [group, hd]
            pv.append(pv_g)
        acc_new = acc * alpha + jnp.concatenate(pv, axis=0)
        return m_new, l_new, acc_new

    m0 = jnp.full((heads, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((heads, 1), jnp.float32)
    a0 = jnp.zeros((heads, head_dim), jnp.float32)
    m_fin, l_fin, acc = jax.lax.fori_loop(0, nblocks, body, (m0, l0, a0))

    # Fold in the current position from registers (exact: one more
    # online-softmax merge; when nblocks == 0 the exp(-inf - s) terms
    # vanish and attention degenerates to v, as it must at pos == 0).
    q3 = q.reshape(kv_heads, group, head_dim)
    s_new = (
        jnp.sum(q3 * k[:, None, :], axis=-1).reshape(heads, 1) * scale
    )  # [H, 1], f32
    m2 = jnp.maximum(m_fin, s_new)
    alpha = jnp.exp(m_fin - m2)
    w_new = jnp.exp(s_new - m2)  # [H, 1]
    l2 = l_fin * alpha + w_new
    v_full = jnp.broadcast_to(
        v[:, None, :], (kv_heads, group, head_dim)
    ).reshape(heads, head_dim)
    attn = (acc * alpha + w_new * v_full) / l2  # [H, hd]

    # --- output projection + residual ---------------------------------------
    o = _wdot(
        attn.reshape(1, heads * head_dim).astype(dtype), wo_ref,
        swo_ref[...], int4=int4,
    )
    if residual:
        o = x_ref[...].astype(jnp.float32) + o
    # residual=False: emit the raw f32 sublayer delta — the tensor-
    # parallel pass (parallel/fused_tp.py) psums per-rank partials in f32
    # and adds the residual outside, so sharded math stays exact.
    out_ref[...] = o.astype(out_ref.dtype)
    kwr.wait()
    vwr.wait()


@functools.partial(
    jax.jit,
    static_argnames=("heads", "kv_heads", "head_dim", "eps", "residual"),
)
def attention_step(
    x, norm_w, wqkv, sqkv, bqkv, cos_full, sin_signed, k_cache, v_cache,
    wo, swo, position, *, heads: int, kv_heads: int, head_dim: int,
    eps: float = 1e-6, residual: bool = True,
):
    """One fused decode attention sublayer.

    x: [1, D]; wqkv int8 [D, (H+2KV)*hd] with scale [1, ...] or int4
    [D/2, ...] uint8 with group scales; caches [KV, S, hd] (updated in
    place at ``position`` — the returned caches alias the inputs);
    cos_full/sin_signed: [1, hd] position-gathered rope rows (see vlm
    rope prep). Returns (x_out, k_cache, v_cache). With
    ``residual=False`` the output is the raw f32 sublayer delta
    (``attn @ wo`` only) for the tensor-parallel partial-sum path.
    """
    seq = k_cache.shape[1]
    bs = min(512, seq)
    assert seq % bs == 0, (seq, bs)
    d = x.shape[-1]
    n_qkv = wqkv.shape[1]
    kernel = functools.partial(
        _attn_kernel, heads=heads, kv_heads=kv_heads, head_dim=head_dim,
        bs=bs, eps=eps, residual=residual,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),  # x
            pl.BlockSpec(memory_space=pltpu.VMEM),  # norm_w
            pl.BlockSpec(memory_space=pltpu.VMEM),  # wqkv
            pl.BlockSpec(memory_space=pltpu.VMEM),  # sqkv
            pl.BlockSpec(memory_space=pltpu.VMEM),  # bqkv
            pl.BlockSpec(memory_space=pltpu.VMEM),  # cos
            pl.BlockSpec(memory_space=pltpu.VMEM),  # sin
            pl.BlockSpec(memory_space=pl.ANY),   # k_cache (HBM)
            pl.BlockSpec(memory_space=pl.ANY),   # v_cache (HBM)
            pl.BlockSpec(memory_space=pltpu.VMEM),  # wo
            pl.BlockSpec(memory_space=pltpu.VMEM),  # swo
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),  # x_out
            pl.BlockSpec(memory_space=pl.ANY),   # k_cache
            pl.BlockSpec(memory_space=pl.ANY),   # v_cache
        ],
        scratch_shapes=[
            pltpu.VMEM((2, kv_heads, 8, head_dim), k_cache.dtype),  # kv_row
            pltpu.VMEM((kv_heads, bs, head_dim), k_cache.dtype),  # kblk
            pltpu.VMEM((kv_heads, bs, head_dim), v_cache.dtype),  # vblk
            pltpu.SemaphoreType.DMA((4,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(
                (1, d), x.dtype if residual else jnp.float32
            ),
            jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
            jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
        ],
        # positional arg i (0-based, INCLUDING the scalar prefetch) ->
        # output j: the caches update in place, no copy-back.
        input_output_aliases={8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=_interpret(),
    )(
        jnp.asarray([position], jnp.int32).reshape(1),
        x, norm_w.reshape(1, d), wqkv, sqkv, bqkv.reshape(1, n_qkv),
        cos_full, sin_signed, k_cache, v_cache, wo, swo,
    )


# ---------------------------------------------------------------------------
# chunk attention (speculative verify: M rows in one pass)
# ---------------------------------------------------------------------------


def _attn_chunk_kernel(
    pos_ref,
    x_ref, nw_ref, wqkv_ref, sqkv_ref, bqkv_ref, cos_ref, sin_ref,
    kc_in, vc_in, wo_ref, swo_ref,
    out_ref, kc_out, vc_out,
    kv_win, kblk, vblk, sem,
    *, heads: int, kv_heads: int, head_dim: int, bs: int, eps: float,
    m: int, win: int, seq: int, residual: bool,
):
    """M-row decode step: rows occupy positions pos..pos+m-1, attend the
    prior cache (idx < pos) plus each other causally (from registers).
    The speculative-verify workhorse — one weight stream serves all M
    rows, same as the reference insight that makes drafts nearly free."""
    pos = pos_ref[0]
    half = head_dim // 2
    dtype = x_ref.dtype
    group = heads // kv_heads
    scale = 1.0 / (head_dim ** 0.5)

    int4 = wqkv_ref.dtype == jnp.uint8

    # --- projections --------------------------------------------------------
    h = _rms(x_ref, nw_ref, eps).astype(dtype)  # [M, D]
    qkv = _wdot(h, wqkv_ref, sqkv_ref[...], int4=int4) + bqkv_ref[...].astype(
        jnp.float32
    )
    qf = qkv[:, : heads * head_dim].reshape(m * heads, head_dim)
    kf = qkv[:, heads * head_dim : (heads + kv_heads) * head_dim].reshape(
        m * kv_heads, head_dim
    )
    vf = qkv[:, (heads + kv_heads) * head_dim :].reshape(
        m * kv_heads, head_dim
    )

    cos_m = cos_ref[...].astype(jnp.float32)  # [M, hd] per-row tables
    sin_m = sin_ref[...].astype(jnp.float32)

    def _expand(t, reps):  # [M, hd] -> [M*reps, hd], row-major per chunk row
        return jnp.broadcast_to(
            t[:, None, :], (m, reps, head_dim)
        ).reshape(m * reps, head_dim)

    q = _rotate(qf, _expand(cos_m, heads), _expand(sin_m, heads), half)
    k = _rotate(kf, _expand(cos_m, kv_heads), _expand(sin_m, kv_heads), half)
    k_m = k.reshape(m, kv_heads, head_dim)
    v_m = vf.reshape(m, kv_heads, head_dim)

    # --- cache window write (rows pos..pos+m-1, overlapped) -----------------
    start = pl.multiple_of(
        jnp.minimum(pos // 8 * 8, seq - win), 8
    )
    offs = pos - start
    win_iota = jax.lax.broadcasted_iota(
        jnp.int32, (kv_heads, win, head_dim), 1
    )
    krd = pltpu.make_async_copy(
        kc_out.at[:, pl.ds(start, win), :], kv_win.at[0], sem.at[0]
    )
    vrd = pltpu.make_async_copy(
        vc_out.at[:, pl.ds(start, win), :], kv_win.at[1], sem.at[1]
    )
    krd.start()
    vrd.start()
    krd.wait()
    vrd.wait()
    for i in range(m):
        sel = win_iota == offs + i
        kv_win[0] = jnp.where(
            sel, k_m[i][:, None, :].astype(kv_win.dtype), kv_win[0]
        )
        kv_win[1] = jnp.where(
            sel, v_m[i][:, None, :].astype(kv_win.dtype), kv_win[1]
        )
    kwr = pltpu.make_async_copy(
        kv_win.at[0], kc_out.at[:, pl.ds(start, win), :], sem.at[0]
    )
    vwr = pltpu.make_async_copy(
        kv_win.at[1], vc_out.at[:, pl.ds(start, win), :], sem.at[1]
    )
    kwr.start()
    vwr.start()

    # --- flash sweep over the prior cache (idx < pos, all rows) -------------
    nblocks = (pos + bs - 1) // bs
    rows = m * group  # per kv head

    def body(b, carry):
        m_run, l_run, acc = carry  # [KV*rows, 1], [KV*rows, 1], [KV*rows, hd]
        kcp = pltpu.make_async_copy(
            kc_out.at[:, pl.ds(b * bs, bs), :], kblk, sem.at[2]
        )
        vcp = pltpu.make_async_copy(
            vc_out.at[:, pl.ds(b * bs, bs), :], vblk, sem.at[3]
        )
        kcp.start()
        vcp.start()
        kcp.wait()
        vcp.wait()
        live = (
            jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1) + b * bs
        ) < pos
        q4 = q.reshape(m, heads, head_dim)
        outs = []
        for g in range(kv_heads):
            q_g = q4[:, g * group : (g + 1) * group, :].reshape(
                rows, head_dim
            )
            s_g = jax.lax.dot_general(
                q_g.astype(dtype), kblk[g].astype(dtype),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [rows, bs]
            outs.append(jnp.where(live, s_g, -jnp.inf))
        s = jnp.concatenate(outs, axis=0)  # [KV*rows, bs]
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_run * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = []
        for g in range(kv_heads):
            pv.append(
                jax.lax.dot(
                    p[g * rows : (g + 1) * rows].astype(dtype),
                    vblk[g].astype(dtype),
                    preferred_element_type=jnp.float32,
                )
            )
        acc_new = acc * alpha + jnp.concatenate(pv, axis=0)
        return m_new, l_new, acc_new

    m0 = jnp.full((kv_heads * rows, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((kv_heads * rows, 1), jnp.float32)
    a0 = jnp.zeros((kv_heads * rows, head_dim), jnp.float32)
    m_fin, l_fin, acc = jax.lax.fori_loop(0, nblocks, body, (m0, l0, a0))

    # --- within-chunk causal attention from registers -----------------------
    q4 = q.reshape(m, heads, head_dim)
    causal = (
        jax.lax.broadcasted_iota(jnp.int32, (rows, m), 0) // group
        >= jax.lax.broadcasted_iota(jnp.int32, (rows, m), 1)
    )
    s_parts = []
    for g in range(kv_heads):
        q_g = q4[:, g * group : (g + 1) * group, :].reshape(rows, head_dim)
        s_cc = jax.lax.dot_general(
            q_g.astype(dtype), k_m[:, g, :].astype(dtype),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [rows, m]
        s_parts.append(jnp.where(causal, s_cc, -jnp.inf))
    s_cc = jnp.concatenate(s_parts, axis=0)  # [KV*rows, m]
    m2 = jnp.maximum(m_fin, jnp.max(s_cc, axis=-1, keepdims=True))
    alpha = jnp.exp(m_fin - m2)
    p_cc = jnp.exp(s_cc - m2)
    l2 = l_fin * alpha + jnp.sum(p_cc, axis=-1, keepdims=True)
    pv = []
    for g in range(kv_heads):
        pv.append(
            jax.lax.dot(
                p_cc[g * rows : (g + 1) * rows].astype(dtype),
                v_m[:, g, :].astype(dtype),
                preferred_element_type=jnp.float32,
            )
        )
    acc = acc * alpha + jnp.concatenate(pv, axis=0)
    attn = acc / l2  # [KV*rows, hd], rows ordered (g, i, gg)

    attn = (
        attn.reshape(kv_heads, m, group, head_dim)
        .transpose(1, 0, 2, 3)
        .reshape(m, heads * head_dim)
    )
    o = _wdot(attn.astype(dtype), wo_ref, swo_ref[...], int4=int4)
    if residual:
        o = x_ref[...].astype(jnp.float32) + o
    out_ref[...] = o.astype(out_ref.dtype)
    kwr.wait()
    vwr.wait()


@functools.partial(
    jax.jit,
    static_argnames=("heads", "kv_heads", "head_dim", "eps", "residual"),
)
def attention_chunk_step(
    x, norm_w, wqkv, sqkv, bqkv, cos_rows, sin_rows, k_cache, v_cache,
    wo, swo, position, *, heads: int, kv_heads: int, head_dim: int,
    eps: float = 1e-6, residual: bool = True,
):
    """M-row fused attention sublayer (speculative verify).

    x: [M, D] — rows are the chunk tokens at positions
    ``position..position+M-1``; cos_rows/sin_rows: [M, hd] per-row rope
    tables (rope_rows with a length). Caller must guarantee
    ``position + M <= seq`` (the speculation headroom contract).
    Returns (x_out [M, D], k_cache, v_cache) with the caches updated in
    place at all M rows.
    """
    m, d = x.shape
    seq = k_cache.shape[1]
    bs = min(512, seq)
    assert seq % bs == 0, (seq, bs)
    win = (7 + m + 7) // 8 * 8  # aligned row window covering all M rows
    assert win <= seq, (win, seq)
    n_qkv = wqkv.shape[1]
    kernel = functools.partial(
        _attn_chunk_kernel, heads=heads, kv_heads=kv_heads,
        head_dim=head_dim, bs=bs, eps=eps, m=m, win=win, seq=seq,
        residual=residual,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),  # x
            pl.BlockSpec(memory_space=pltpu.VMEM),  # norm_w
            pl.BlockSpec(memory_space=pltpu.VMEM),  # wqkv
            pl.BlockSpec(memory_space=pltpu.VMEM),  # sqkv
            pl.BlockSpec(memory_space=pltpu.VMEM),  # bqkv
            pl.BlockSpec(memory_space=pltpu.VMEM),  # cos rows
            pl.BlockSpec(memory_space=pltpu.VMEM),  # sin rows
            pl.BlockSpec(memory_space=pl.ANY),      # k_cache (HBM)
            pl.BlockSpec(memory_space=pl.ANY),      # v_cache (HBM)
            pl.BlockSpec(memory_space=pltpu.VMEM),  # wo
            pl.BlockSpec(memory_space=pltpu.VMEM),  # swo
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, kv_heads, win, head_dim), k_cache.dtype),
            pltpu.VMEM((kv_heads, bs, head_dim), k_cache.dtype),
            pltpu.VMEM((kv_heads, bs, head_dim), v_cache.dtype),
            pltpu.SemaphoreType.DMA((4,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(
                (m, d), x.dtype if residual else jnp.float32
            ),
            jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
            jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
        ],
        input_output_aliases={8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=_interpret(),
    )(
        jnp.asarray([position], jnp.int32).reshape(1),
        x, norm_w.reshape(1, d), wqkv, sqkv, bqkv.reshape(1, n_qkv),
        cos_rows, sin_rows, k_cache, v_cache, wo, swo,
    )


# ---------------------------------------------------------------------------
# paged attention (block-table KV: concurrency decoupled from max_seq)
# ---------------------------------------------------------------------------
#
# A private contiguous [slot, max_seq] cache plane per row would cost
# max_slots * max_seq rows of HBM whether a slot holds 40 tokens or
# 2000. The paged tier keeps ONE fixed pool of page-size blocks shared
# by every slot; a per-slot block table maps logical page j to a
# physical pool page, so HBM scales with tokens actually held (vLLM's
# PagedAttention insight). Physical page 0 is reserved as the idle
# dump: inactive rows point at it and their position-0 writes land
# there harmlessly.
#
# The decode kernel's flash sweep over those pages is a software
# pipeline (:func:`_paged_sweep`): a page is 16 rows, far too little for
# one DMA round trip and one matmul each, so the sweep moves in GROUPS
# of ``_SWEEP_COLS // page`` pages (8 at page 16: one 128-column MXU
# tile), walks every live row's groups as ONE flat (row, group)
# schedule built from ``positions``, and keeps the next group's page
# copies in flight while it multiplies the current one — across row
# boundaries, and from kernel entry, before this tick's q exists. The
# chunk and spec kernels below still fetch one page, wait, and multiply
# (ROADMAP design debt 3).
#
# What a step does with its group has two forms, chosen from the shape
# the kernel is traced with and nothing else: ``rows``, the query rows a
# K/V head serves (``heads // kv_heads`` x m). With several rows (Qwen,
# 12/2 heads: 6) it is two MXU products a K/V head, ``[rows, hd]`` x
# ``[hd, 128]`` and ``[rows, 128]`` x ``[128, hd]``. With ONE row (no
# head grouping: Ouro, 16/16) those products would each load a 128 x 128
# stationary tile to push a single row through it, 2 x KV of them a
# group, so the step is ONE vector pass over all K/V heads of the group
# instead: scores as the keys times q broadcast over the group's rows,
# summed along ``hd`` — which leaves them one per cache row, the
# orientation the value mix wants (weights broadcast along ``hd``,
# summed over the rows) — and the softmax state of the whole row loaded
# and stored once. bf16 x bf16 is exact in float32, so this form only
# reorders float32 sums. Kernel alone on the v5e at Ouro's widths (16
# heads of 128, 1 MB of K/V a group; PERF.md section 6, PR 38): 2.60 us
# a group as products, 1.51 as the vector pass, 1.40 for the group's
# copies with no arithmetic at all.
#
# What a step FETCHES has two forms as well, chosen from the pool
# operand's rank: K and V pools ``[P, KV, page, hd]`` (two copies a
# page, a buffer each), or one pool ``[P, page, 2 * KV * hd]`` whose row
# is a position's keys, then its values (one copy a page into one
# buffer, a head's keys and values its lane slices): the layout of the
# models that project in XLA and enter through
# :func:`attention_paged_rows_step`, the sweep and nothing else.

#: Cache rows (columns of the score tile) one sweep step covers.
_SWEEP_COLS = 128
#: Buffer slots of the sweep. Step t lives in slot t % SLOTS; while it
#: is multiplied, steps t+1 .. t+SLOTS-1 have their copies started. One
#: group ahead is enough on the v5e: 2, 3 and 4 slots measured the same
#: to 1 % at 4, 15 and 16 live rows (PERF.md section 6, PR 28) — a step
#: is bound by issuing its copies and by its own products, not by their
#: latency. At 16 K/V heads a group is 1 MB and the step is bound by its
#: bytes: 3 and 4 slots measured 1.45 and 1.44 us a group against 1.51
#: with 2, where the copies alone take 1.40 (PR 38): 4 % of a step, not
#: taken.
_SWEEP_SLOTS = 2
#: A caller's chores (``run(chores)``: work that needs no step's result,
#: done under the groups' copies) go into every third step from the
#: second. The decode kernel's are products of a 1 MiB weight tile whose
#: copy then shares the bus with three groups of 1 MB at 16 K/V heads:
#: one in every step stalled the steps on their pages (79.2 us a call),
#: every second 77.1, every third 73.6-75.1, spread evenly over the
#: whole schedule 76.6 (kernel alone, Ouro's widths, PR 55).
_SWEEP_CHORE_EVERY = 3


def _sweep_pages(page: int, max_pages: int) -> int:
    """Pages per sweep group: one ``_SWEEP_COLS``-wide tile's worth, never
    more than a block table holds."""
    return max(1, min(_SWEEP_COLS // page, max_pages))


def sweep_group_rows(page: int, max_pages: int) -> int:
    """Cache rows one (row, group) step of the sweep covers."""
    return _sweep_pages(page, max_pages) * page


def _sweep_scratch(batch, max_pages, kv_heads, rows, head_dim, page,
                   pool_dtype, q_dtype, kv_quant, joined=False):
    """Scratch of :func:`_paged_sweep`, in the order it unpacks them:
    group buffers, their DMA semaphores, the SMEM schedule, the query
    rows and the online-softmax state. ``joined``: the pool keeps K and
    V of a position as ONE row (``[P, page, 2 * KV * hd]``), so a group
    is one buffer of such rows."""
    gp = _sweep_pages(page, max_pages)
    steps = batch * pl.cdiv(max_pages, gp)
    if joined:
        assert not kv_quant, "no int8 pages in the joined row layout"
        bufs = [pltpu.VMEM(
            (_SWEEP_SLOTS, gp * page, 2 * kv_heads * head_dim), pool_dtype
        )]                                                    # kvbuf
    else:
        group_buf = pltpu.VMEM(
            (_SWEEP_SLOTS, kv_heads, gp * page, head_dim), pool_dtype
        )
        bufs = [
            group_buf,                                        # kbuf
            group_buf,                                        # vbuf
            *(
                [pltpu.VMEM(
                    (_SWEEP_SLOTS, 2, kv_heads, gp * page), jnp.float32)]
                if kv_quant else []                           # sbuf
            ),
        ]
    state = (batch, kv_heads, rows)
    copies = 1 if joined else 4 if kv_quant else 2  # DMAs a page
    return [
        *bufs,
        pltpu.SemaphoreType.DMA((_SWEEP_SLOTS, copies)),
        pltpu.SMEM((steps,), jnp.int32),                      # step -> row
        pltpu.SMEM((steps,), jnp.int32),                      # step -> group
        pltpu.VMEM((*state, head_dim), q_dtype),              # q
        pltpu.VMEM((*state, 1), jnp.float32),                 # running max
        pltpu.VMEM((*state, 1), jnp.float32),                 # running sum
        pltpu.VMEM((*state, head_dim), jnp.float32),          # accumulator
    ]


def _paged_sweep(pos_ref, bt_ref, pools, scratch, *, batch: int, page: int,
                 scale: float, dtype):
    """Pipelined flash sweep of B paged contexts; call at kernel entry.

    ``pools``: (k, v) HBM pools [P, KV, page, hd], plus the (k, v) scale
    pools [P, KV, page] on the int8-KV path; or ONE pool [P, page,
    2 * KV * hd] whose row is a position's keys, then its values (the
    joined layout, told by the pool's rank alone): a page is then one
    copy, not two, and head ``h``'s keys and values are the lane slices
    ``[:, h * hd : (h + 1) * hd]`` and ``[:, (KV + h) * hd : ...]`` of
    the group's buffer; schedule, slots, masking and arithmetic are the
    same. ``scratch``: what
    :func:`_sweep_scratch` declared. Row b attends pool rows
    ``idx < pos_ref[b]`` through ``bt_ref[b]``, with ``rows`` query rows
    per kv head (group size x m; m = 1 in the decode kernel) that the
    caller stores in ``q[b, kv]`` before it calls the returned function.

    On entry this zeroes the group buffers (a group's tail beyond the
    context is never fetched, and what it holds must be finite for the
    masked product to ignore it), resets the state to an empty softmax
    (max -inf, sum 0), writes the flat schedule — every (row, group)
    with at least one page below the row's position, rows in order, so a
    row at position 0 contributes no step — and starts the copies of the
    first SLOTS-1 steps. They need no q: they land under the caller's
    RMSNorm and qkv product.

    The returned ``run()`` walks the schedule. Step t starts the copies
    of step t+SLOTS-1 (into the slot step t-1 just released: the next
    group of this row or the first of the next live row), waits for its
    own, then does one score product, one online-softmax update and one
    value product per kv head over the whole group — or, where a kv
    head serves ONE query row (``rows == 1``, a traced shape), one
    vector pass over all kv heads of the group: scores ``[KV, cols, 1]``
    from keys x q summed along ``hd``, one softmax update of the row's
    whole ``[KV, 1, 1]`` / ``[KV, 1, hd]`` state, the mix summed over
    the group's rows; same operands (bf16, products exact in float32),
    same float32 sums in another order. Columns at or past
    the position are masked in ``s`` and again in ``p``. ``run()``
    leaves (max, sum, accumulator) of every row in the state refs, which
    it returns; the caller folds in what it holds in registers.
    ``run(chores)`` also calls each of the caller's thunks once, in
    order: chore j in step ``1 + _SWEEP_CHORE_EVERY * j``, between the
    start of the next group's copies and the wait for this group's, or
    after the last step where the schedule is shorter than that.
    """
    kv_quant = len(pools) == 4
    joined = len(pools[0].shape) == 3
    *bufs, sem, row_ref, grp_ref, q_ref, m_ref, l_ref, acc_ref = scratch
    _, kv_heads, rows, hd = q_ref.shape
    if joined:
        (kvbuf,) = bufs
        slots, cols, _ = kvbuf.shape
        assert rows > 1, "the joined layout has no one-row form"
    else:
        kbuf, vbuf = bufs[:2]
        sbuf = bufs[2] if kv_quant else None
        slots, _, cols, _ = kbuf.shape
    gp = cols // page

    def nblocks(b):  # prior context in pages, the partial one included
        return (pos_ref[b] + page - 1) // page

    for buf in bufs:
        buf[...] = jnp.zeros(buf.shape, buf.dtype)
    m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    total = jnp.int32(0)
    for b in range(batch):
        ngroups = (nblocks(b) + gp - 1) // gp

        def fill(g, carry, b=b, base=total):
            row_ref[base + g] = jnp.int32(b)
            grp_ref[base + g] = g
            return carry

        jax.lax.fori_loop(0, ngroups, fill, 0)
        total = total + ngroups

    def copies(t, go: str):
        """``start`` or ``wait`` for every page copy of step t."""
        b, first = row_ref[t], grp_ref[t] * gp
        slot = jax.lax.rem(t, slots)

        def one(j, carry):
            pg = bt_ref[b, first + j]
            at = pl.ds(pl.multiple_of(j * page, page), page)
            if joined:
                dsts = [kvbuf.at[slot, at, :]]
            else:
                dsts = [kbuf.at[slot, :, at, :], vbuf.at[slot, :, at, :]]
            if kv_quant:
                dsts += [sbuf.at[slot, 0, :, at], sbuf.at[slot, 1, :, at]]
            for i, (pool, dst) in enumerate(zip(pools, dsts)):
                copy = pltpu.make_async_copy(pool.at[pg], dst, sem.at[slot, i])
                getattr(copy, go)()
            return carry

        jax.lax.fori_loop(0, jnp.minimum(nblocks(b) - first, gp), one, 0)

    for t in range(slots - 1):
        pl.when(t < total)(functools.partial(copies, t, "start"))

    def one_row_heads(b, g, slot):
        """The step's arithmetic where a kv head serves one query row:
        every array is ``[KV, cols, .]``, cache rows along sublanes."""
        f32 = jnp.float32
        if kv_quant:
            k = kv_dequant(kbuf[slot], sbuf[slot, 0], dtype)
            v = kv_dequant(vbuf[slot], sbuf[slot, 1], dtype)
        else:
            k, v = kbuf[slot].astype(dtype), vbuf[slot].astype(dtype)
        live = (
            jax.lax.broadcasted_iota(jnp.int32, (1, cols, 1), 1) + g * cols
        ) < pos_ref[b]
        s = jnp.sum(
            k.astype(f32) * q_ref[b].astype(f32), axis=-1, keepdims=True
        ) * scale  # [KV, cols, 1]
        s = jnp.where(live, s, -jnp.inf)
        m_old = m_ref[b]  # [KV, 1, 1]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        m_ref[b] = m_new
        l_ref[b] = l_ref[b] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[b] = acc_ref[b] * alpha + jnp.sum(
            p.astype(dtype).astype(f32) * v.astype(f32), axis=1, keepdims=True
        )  # [KV, 1, hd]

    def step(t, carry, chores=()):
        ahead = t + slots - 1
        pl.when(ahead < total)(functools.partial(copies, ahead, "start"))
        for at, chore in chores:
            pl.when(t == at)(chore)
        copies(t, "wait")
        b, g = row_ref[t], grp_ref[t]
        slot = jax.lax.rem(t, slots)
        if rows == 1:
            one_row_heads(b, g, slot)
            return carry
        live = (
            jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1) + g * cols
        ) < pos_ref[b]
        for h in range(kv_heads):
            if joined:
                at_k, at_v = (pl.ds(i * hd, hd) for i in (h, kv_heads + h))
                k_h = kvbuf[slot, :, at_k].astype(dtype)
                v_h = kvbuf[slot, :, at_v].astype(dtype)
            elif kv_quant:
                k_h = kv_dequant(kbuf[slot, h], sbuf[slot, 0, h], dtype)
                v_h = kv_dequant(vbuf[slot, h], sbuf[slot, 1, h], dtype)
            else:
                k_h = kbuf[slot, h].astype(dtype)
                v_h = vbuf[slot, h].astype(dtype)
            s = jax.lax.dot_general(
                q_ref[b, h], k_h, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [rows, cols]
            s = jnp.where(live, s, -jnp.inf)
            m_old = m_ref[b, h]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_old - m_new)
            p = jnp.where(live, jnp.exp(s - m_new), 0.0)
            m_ref[b, h] = m_new
            l_ref[b, h] = l_ref[b, h] * alpha + jnp.sum(
                p, axis=-1, keepdims=True
            )
            acc_ref[b, h] = acc_ref[b, h] * alpha + jax.lax.dot(
                p.astype(dtype), v_h, preferred_element_type=jnp.float32
            )
        return carry

    def run(chores=()):
        due = [
            (1 + _SWEEP_CHORE_EVERY * j, chore) for j, chore in enumerate(chores)
        ]
        jax.lax.fori_loop(0, total, functools.partial(step, chores=due), 0)
        for at, chore in due:  # the schedule ended before its step
            pl.when(total <= at)(chore)
        return m_ref, l_ref, acc_ref

    return q_ref, run


def _attn_paged_batch_kernel(
    pos_ref,  # SMEM (B,) int32 — per-row positions
    bt_ref,   # SMEM (B, max_pages) int32 — per-row block tables
    *refs,
    heads: int, kv_heads: int, head_dim: int, page: int, eps: float,
    batch: int, residual: bool, kv_quant: bool = False,
):
    """B-row decode over B independent sequences whose K/V live in a
    shared page pool [P, KV, page, hd]. Per row, identical math to
    :func:`_attn_kernel`; only the HBM addressing changes — the flash
    sweep walks pool pages through the row's block table, and the
    in-place row write targets the row's CURRENT page.

    ``wqkv`` and ``wo`` stay in HBM; the body copies them itself, a
    column tile at a time, as it copies pages. Order of events, so that
    weight bytes and page bytes move together and the arithmetic runs
    under them: (1) at entry the first tiles of ``wqkv`` are requested,
    then every row's 8-row window of its current page (one semaphore
    per row) and, by :func:`_paged_sweep`, the first group of the flat
    (row, group) schedule; nothing is waited for yet; (2) RMSNorm, then
    the fused projection by column tiles, THE QUERY COLUMNS FIRST: a
    tile's product runs as its copy lands while the next tiles' copies
    are in flight; RoPE on q, and q stands; (3) the sweep starts: groups
    of 8 pages (128 cache rows at page 16) in two slots, one group
    ahead, one score and one value product per kv head per group, or one
    vector pass over all kv heads of the group where ``heads ==
    kv_heads`` leaves a kv head one query row; the K and V columns'
    tiles are projected BETWEEN its steps, one every third step
    (``run(chores)``), and the copies of ``wo``'s first tiles follow
    them into the ring; (4) RoPE on the current token's k, one wait for
    the windows, the token's K/V inserted in registers, the write-backs
    started (waited for at the very end); (5) each row's current token
    folded in from registers (it never round-trips the pool within its
    own step), the output projection by ``wo``'s column tiles, the
    residual. Column tiles leave every output column's sum over ``D``
    as it was, so the result is the whole-weight product's to the bit.

    The sweep reads a row's current page before its window is written
    back, and the next call's sweep after: the window's seven other rows
    are rewritten with the bytes they held, and the eighth is column
    ``pos``, which this call's sweep masks.

    ``kv_quant`` adds the int8-KV pools: values are
    :func:`kv_quant_rows`-quantized in-register right before the RMW
    insert, per-(row, kv-head) f32 scales ride parallel [P, KV, page]
    scale pools through the SAME page ids, and the flash sweep
    dequantizes each streamed group in-register — HBM traffic per page
    is the int8 bytes plus a [KV, page] scale plane."""
    if kv_quant:
        (x_ref, nw_ref, wqkv_hbm, sqkv_ref, bqkv_ref, cos_ref, sin_ref,
         kp_in, vp_in, ks_in, vs_in, wo_hbm, swo_ref,
         out_ref, kp_out, vp_out, ks_out, vs_out,
         kv_row, s_row, wsem, wbuf, tsem, qkv_ref, *sweep_scratch) = refs
        pools = (kp_out, vp_out, ks_out, vs_out)
    else:
        (x_ref, nw_ref, wqkv_hbm, sqkv_ref, bqkv_ref, cos_ref, sin_ref,
         kp_in, vp_in, wo_hbm, swo_ref,
         out_ref, kp_out, vp_out,
         kv_row, wsem, wbuf, tsem, qkv_ref, *sweep_scratch) = refs
        pools = (kp_out, vp_out)
    half = head_dim // 2
    dtype = x_ref.dtype
    int4 = wqkv_hbm.dtype == jnp.uint8
    group = heads // kv_heads
    scale = 1.0 / (head_dim ** 0.5)

    # --- the weights' column tiles, the first ones requested ----------------
    # ``wqkv``'s query columns, its K and V columns, then ``wo``, as ONE
    # static list of column tiles through the ring ``wbuf``: tile i
    # lives in slot i % ring, and taking it starts the copy of tile
    # i + ring - 1 into the slot tile i - 1 just left, as the sweep does
    # with its groups. A tile takes the same columns of its scales (int8:
    # one a column; int4: a group's row of them), which stay VMEM operands.
    ring, _, tile_cols = wbuf.shape
    n_q = heads * head_dim

    def column_tiles(w, start, end):
        return [
            (w, c0, min(tile_cols, end - c0))
            for c0 in range(start, end, tile_cols)
        ]

    tiles = column_tiles(wqkv_hbm, 0, n_q)
    n_q_tiles = len(tiles)
    tiles += column_tiles(wqkv_hbm, n_q, wqkv_hbm.shape[1])
    n_qkv_tiles = len(tiles)
    tiles += column_tiles(wo_hbm, 0, wo_hbm.shape[1])

    def tile_slot(i):
        w, _, width = tiles[i]
        return wbuf.at[i % ring, pl.ds(0, w.shape[0]), pl.ds(0, width)]

    def tile_copy(i):
        w, c0, width = tiles[i]
        return pltpu.make_async_copy(
            w.at[:, pl.ds(c0, width)], tile_slot(i), tsem.at[i % ring]
        )

    def take_tile(i):
        """Tile i, landed in its slot, and the columns it holds."""
        if i + ring - 1 < len(tiles):
            tile_copy(i + ring - 1).start()
        tile_copy(i).wait()
        return tile_slot(i), pl.ds(*tiles[i][1:])

    for i in range(min(ring - 1, len(tiles))):
        tile_copy(i).start()

    # --- every row's current 8-row window, requested together ---------------
    # The aligned 8-row read-modify-write of :func:`_attn_kernel`, but the
    # window lives inside pool page bt[b, pos // page] at in-page offset
    # pos % page (page is a multiple of 8, so the window never crosses a
    # page boundary). ``windows(b)`` pairs each pool window with its
    # VMEM twin.
    def windows(b):
        pos = pos_ref[b]
        cur = bt_ref[b, pos // page]
        inpage = pos - pos // page * page
        at = pl.ds(pl.multiple_of(inpage // 8 * 8, 8), 8)
        pairs = [
            (kp_out.at[cur, :, at, :], kv_row.at[0, b]),
            (vp_out.at[cur, :, at, :], kv_row.at[1, b]),
        ]
        if kv_quant:
            # The 8-row scale windows RMW alongside the value windows:
            # old rows keep their scales (written once, never
            # requantized), only the current row's slot is replaced.
            pairs += [
                (ks_out.at[cur, :, at], s_row.at[0, b]),
                (vs_out.at[cur, :, at], s_row.at[1, b]),
            ]
        return inpage - inpage // 8 * 8, pairs

    # (wsem[i, b] serves row b's read, then, once every read has been
    # waited for, its write-back.)
    wins = [windows(b) for b in range(batch)]
    reads = [
        pltpu.make_async_copy(hbm, vmem, wsem.at[i, b])
        for b, (_, pairs) in enumerate(wins)
        for i, (hbm, vmem) in enumerate(pairs)
    ]
    for rd in reads:
        rd.start()

    q_ref, sweep = _paged_sweep(
        pos_ref, bt_ref, pools, sweep_scratch,
        batch=batch, page=page, scale=scale, dtype=dtype,
    )

    # --- the query columns' tiles, RoPE, and q stands -----------------------
    h = _rms(x_ref, nw_ref, eps).astype(dtype)  # [B, D]

    def project(i):
        w_tile, at = take_tile(i)
        qkv_ref[:, at] = _wdot(
            h, w_tile, sqkv_ref[:, at], int4=int4
        ) + bqkv_ref[:, at].astype(jnp.float32)

    for i in range(n_q_tiles):
        project(i)
    cos_b = cos_ref[...].astype(jnp.float32)
    sin_b = sin_ref[...].astype(jnp.float32)

    def _expand(t, reps):
        return jnp.broadcast_to(
            t[:, None, :], (batch, reps, head_dim)
        ).reshape(batch * reps, head_dim)

    qf = qkv_ref[:, :n_q].reshape(batch * heads, head_dim)
    q = _rotate(qf, _expand(cos_b, heads), _expand(sin_b, heads), half)
    q_b = q.reshape(batch, kv_heads, group, head_dim)

    # --- the pipelined sweep over every row's prior context, the K and V ----
    # --- columns' tiles projected between its steps -------------------------
    q_ref[...] = q_b.astype(q_ref.dtype)
    m_ref, l_ref, acc_ref = sweep([
        functools.partial(project, i) for i in range(n_q_tiles, n_qkv_tiles)
    ])

    kf = qkv_ref[:, n_q : n_q + kv_heads * head_dim].reshape(
        batch * kv_heads, head_dim
    )
    vf = qkv_ref[:, n_q + kv_heads * head_dim :].reshape(
        batch * kv_heads, head_dim
    )
    k = _rotate(kf, _expand(cos_b, kv_heads), _expand(sin_b, kv_heads), half)
    k_b = k.reshape(batch, kv_heads, head_dim)
    v_b = vf.reshape(batch, kv_heads, head_dim)

    # --- insert the current token, start the write-backs --------------------
    for rd in reads:
        rd.wait()
    pending = []
    for b, (inwin, pairs) in enumerate(wins):
        row_sel = (
            jax.lax.broadcasted_iota(jnp.int32, (kv_heads, 8, head_dim), 1)
            == inwin
        )
        if kv_quant:
            kq, ksc = kv_quant_rows(k_b[b])
            vq, vsc = kv_quant_rows(v_b[b])
            kv_row[0, b] = jnp.where(row_sel, kq[:, None, :], kv_row[0, b])
            kv_row[1, b] = jnp.where(row_sel, vq[:, None, :], kv_row[1, b])
            s_sel = (
                jax.lax.broadcasted_iota(jnp.int32, (kv_heads, 8), 1) == inwin
            )
            s_row[0, b] = jnp.where(s_sel, ksc[:, None], s_row[0, b])
            s_row[1, b] = jnp.where(s_sel, vsc[:, None], s_row[1, b])
        else:
            kv_row[0, b] = jnp.where(
                row_sel, k_b[b][:, None, :].astype(kv_row.dtype), kv_row[0, b]
            )
            kv_row[1, b] = jnp.where(
                row_sel, v_b[b][:, None, :].astype(kv_row.dtype), kv_row[1, b]
            )
        writes = [
            pltpu.make_async_copy(vmem, hbm, wsem.at[i, b])
            for i, (hbm, vmem) in enumerate(pairs)
        ]
        for wr in writes:
            wr.start()
        pending += writes

    # --- fold in each row's current position from registers (exact merge) ---
    attn_rows = []
    for b in range(batch):
        m_fin, l_fin, acc = m_ref[b], l_ref[b], acc_ref[b]
        s_new = jnp.sum(
            q_b[b] * k_b[b][:, None, :], axis=-1, keepdims=True
        ) * scale  # [KV, group, 1]
        m2 = jnp.maximum(m_fin, s_new)
        alpha = jnp.exp(m_fin - m2)
        w_new = jnp.exp(s_new - m2)
        l2 = l_fin * alpha + w_new
        attn_rows.append(
            ((acc * alpha + w_new * v_b[b][:, None, :]) / l2).reshape(
                heads, head_dim
            )
        )

    attn = jnp.stack(attn_rows, axis=0).reshape(batch, heads * head_dim)

    # --- output projection + residual, a column tile of ``wo`` at a time ----
    attn = attn.astype(dtype)
    for i in range(n_qkv_tiles, len(tiles)):
        w_tile, at = take_tile(i)
        o = _wdot(attn, w_tile, swo_ref[:, at], int4=int4)
        if residual:
            o = x_ref[:, at].astype(jnp.float32) + o
        out_ref[:, at] = o.astype(out_ref.dtype)
    for copy in pending:
        copy.wait()


@functools.partial(
    jax.jit,
    static_argnames=("heads", "kv_heads", "head_dim", "eps", "residual"),
)
def attention_paged_batch_step(
    x, norm_w, wqkv, sqkv, bqkv, cos_rows, sin_rows, k_pool, v_pool,
    wo, swo, positions, block_tables, k_scale=None, v_scale=None,
    *, heads: int, kv_heads: int, head_dim: int, eps: float = 1e-6,
    residual: bool = True,
):
    """Fused paged decode attention for B independent sequences.

    x: [B, D]; pools: [P, KV, page, hd] shared blocks (updated in place
    at each row's ``positions[b]`` inside page
    ``block_tables[b, positions[b] // page]``); block_tables:
    [B, max_pages] int32 physical page ids (0 = the reserved idle page).
    Weight layout matches :func:`attention_step` (wqkv int8
    [D, (H+2KV)*hd] with scale [1, ...], or int4 [D/2, ...] uint8 with
    group scales); cos_rows/sin_rows: [B, hd] per-row rope rows gathered
    at each row's position (rope_rows_at). Returns
    (x_out [B, D], k_pool, v_pool).

    One kernel call, ``grid=(1,)``. The rows' contexts are swept by
    :func:`_paged_sweep`: groups of ``128 // page`` pages (8 pages, 128
    cache rows, at page 16) in ``_SWEEP_SLOTS`` = 2 buffers of
    [KV, 128, hd] each for K and V; only (row, group) pairs below a
    row's position are scheduled, so a frozen row (position 0) costs no
    step and a 20-token row one. A step multiplies its group on the
    MXU, two products a kv head, unless a kv head serves one query row
    (``heads == kv_heads``): then all heads of the group go through one
    vector pass. ``wqkv`` and ``wo`` are HBM operands: the kernel
    copies them by column tiles of ``_WEIGHT_TILE_BYTES`` through
    ``_WEIGHT_SLOTS`` buffers of ``[max(rows of wqkv, rows of wo), tile
    columns]``, whatever their size, so the call's scope is the sweep's
    buffers and three tiles. In flight at any time: from kernel entry,
    two weight tiles, every row's 8-row write window and the first
    group; during the projection of q, the two tiles after the one
    being multiplied; during the sweep, the group after the one being
    multiplied (the next row's first when a row ends) and two tiles of
    K, V or ``wo`` columns; from the insert of the current token, after
    the sweep, to the end, the 2B window write-backs.

    ``k_scale``/``v_scale`` (both or neither) switch on the int8-KV
    path: pools must be int8 and the scales are parallel [P, KV, page]
    f32 pools indexed by the SAME physical page ids — sharing,
    copy-on-write and migration stay block-table tricks because a page
    id resolves values and scales together. Quantization happens
    in-register before the row write (:func:`kv_quant_rows`),
    dequantization in-register during the sweep (:func:`kv_dequant`).
    Returns (x_out, k_pool, v_pool, k_scale, v_scale) in that mode.
    The None/array distinction changes the jit pytree, so fp callers
    trace the exact pre-quant program — byte-identical specs.
    """
    kv_quant = k_scale is not None
    assert kv_quant == (v_scale is not None)
    batch = x.shape[0]
    page = k_pool.shape[2]
    assert page % 8 == 0, page
    if kv_quant:
        assert k_pool.dtype == jnp.int8 and v_pool.dtype == jnp.int8, (
            "int8-KV path needs int8 pools", k_pool.dtype
        )
    d = x.shape[-1]
    n_qkv = wqkv.shape[1]
    tile_rows = max(wqkv.shape[0], wo.shape[0])
    tile_cols = _weight_tile_cols(tile_rows)
    kernel = functools.partial(
        _attn_paged_batch_kernel, heads=heads, kv_heads=kv_heads,
        head_dim=head_dim, page=page, eps=eps, batch=batch,
        residual=residual, kv_quant=kv_quant,
    )
    pool_specs = [
        pl.BlockSpec(memory_space=pl.ANY),      # k_pool (HBM)
        pl.BlockSpec(memory_space=pl.ANY),      # v_pool (HBM)
    ]
    pool_outs = [
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    pool_shapes = [
        jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
        jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
    ]
    scale_scratch = []
    if kv_quant:
        pool_specs += [
            pl.BlockSpec(memory_space=pl.ANY),  # k_scale (HBM)
            pl.BlockSpec(memory_space=pl.ANY),  # v_scale (HBM)
        ]
        pool_outs += [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ]
        pool_shapes += [
            jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype),
            jax.ShapeDtypeStruct(v_scale.shape, v_scale.dtype),
        ]
        scale_scratch = [
            pltpu.VMEM((2, batch, kv_heads, 8), jnp.float32),  # s_row
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),  # x
            pl.BlockSpec(memory_space=pltpu.VMEM),  # norm_w
            pl.BlockSpec(memory_space=pl.ANY),      # wqkv (HBM)
            pl.BlockSpec(memory_space=pltpu.VMEM),  # sqkv
            pl.BlockSpec(memory_space=pltpu.VMEM),  # bqkv
            pl.BlockSpec(memory_space=pltpu.VMEM),  # cos rows
            pl.BlockSpec(memory_space=pltpu.VMEM),  # sin rows
            *pool_specs,
            pl.BlockSpec(memory_space=pl.ANY),      # wo (HBM)
            pl.BlockSpec(memory_space=pltpu.VMEM),  # swo
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            *pool_outs,
        ],
        scratch_shapes=[
            pltpu.VMEM((2, batch, kv_heads, 8, head_dim), k_pool.dtype),
            *scale_scratch,
            pltpu.SemaphoreType.DMA((len(pool_specs), batch)),  # wsem
            pltpu.VMEM((_WEIGHT_SLOTS, tile_rows, tile_cols), wqkv.dtype),
            pltpu.SemaphoreType.DMA((_WEIGHT_SLOTS,)),            # tsem
            pltpu.VMEM((batch, n_qkv), jnp.float32),              # qkv
            *_sweep_scratch(
                batch, block_tables.shape[1], kv_heads, heads // kv_heads,
                head_dim, page, k_pool.dtype, x.dtype, kv_quant,
            ),
        ],
    )
    operands = [k_pool, v_pool]
    if kv_quant:
        operands += [k_scale, v_scale]
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(
                (batch, d), x.dtype if residual else jnp.float32
            ),
            *pool_shapes,
        ],
        # positional arg i (0-based, INCLUDING the 2 scalar prefetches)
        # -> output j: pools (and scale pools) update in place.
        input_output_aliases=(
            {9: 1, 10: 2, 11: 3, 12: 4} if kv_quant else {9: 1, 10: 2}
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=_interpret(),
    )(
        jnp.asarray(positions, jnp.int32).reshape(batch),
        jnp.asarray(block_tables, jnp.int32),
        x, norm_w.reshape(1, d), wqkv, sqkv, bqkv.reshape(1, n_qkv),
        cos_rows, sin_rows, *operands, wo, swo,
    )


def _attn_paged_rows_kernel(
    cnt_ref,  # SMEM (B,) int32 — cache rows each batch row attends
    bt_ref,   # SMEM (B, max_pages) int32 — per-row block tables
    q_in, pool, out_ref, *sweep_scratch, page: int, batch: int,
):
    """:func:`_paged_sweep` and nothing else: the queries come projected,
    every attended row (the tick's own among them) is in the pool. A row
    with no step keeps an empty softmax (sum 0, accumulator 0) and
    leaves as zeros."""
    q_ref, sweep = _paged_sweep(
        cnt_ref, bt_ref, (pool,), sweep_scratch, batch=batch, page=page,
        scale=1.0 / (q_in.shape[-1] ** 0.5), dtype=q_in.dtype,
    )
    q_ref[...] = q_in[...]
    _, l_ref, acc_ref = sweep()
    total = l_ref[...]
    out_ref[...] = acc_ref[...] / jnp.where(total > 0.0, total, 1.0)


@jax.jit
def attention_paged_rows_step(q, pool, counts, block_tables):
    """Paged decode attention of B independent sequences WITHOUT the
    projections, for a model that projects outside the kernel (its
    callers' ``wqkv`` and ``wo`` did not fit VMEM while the fused kernel
    held them whole, and they norm each head between the projection and
    the sweep): the caller projects (and norms, ropes) in XLA,
    writes the tick's K/V row to its page, and hands over

    q: [B, KV, G, hd] queries (``G`` = query rows a K/V head serves, at
    least 2); pool: [P, page, 2 * KV * hd], a position's keys then its
    values as one row, left in HBM, read only; counts: [B] int32, the
    cache rows each batch row attends (positions ``0 .. counts[b] - 1``
    through ``block_tables[b]``; 0 for a frozen row); block_tables:
    [B, max_pages] int32. Returns the normalised context [B, KV, G, hd]
    float32; a row with ``counts`` 0 gets zeros.

    One kernel call, ``grid=(1,)``, the flat (row, group) schedule of
    :func:`_paged_sweep` over the live rows' pages only: a group is 8
    pages = 128 cache rows at page 16, one copy a page, two buffer
    slots, the next group in flight while this one is multiplied (two
    MXU products a K/V head); probabilities go to ``q``'s dtype before
    the value product, sums are float32."""
    batch, kv_heads, rows, head_dim = q.shape
    page = pool.shape[1]
    assert pool.shape[2] == 2 * kv_heads * head_dim, (pool.shape, q.shape)
    assert page % 8 == 0, page
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),  # q
            pl.BlockSpec(memory_space=pl.ANY),      # pool (HBM)
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=_sweep_scratch(
            batch, block_tables.shape[1], kv_heads, rows, head_dim, page,
            pool.dtype, q.dtype, False, joined=True,
        ),
    )
    return pl.pallas_call(
        functools.partial(_attn_paged_rows_kernel, page=page, batch=batch),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=_interpret(),
    )(
        jnp.asarray(counts, jnp.int32).reshape(batch),
        jnp.asarray(block_tables, jnp.int32),
        q, pool,
    )


def _attn_paged_chunk_kernel(
    pos_ref,  # SMEM (1,) int32 — chunk start (multiple of page)
    bt_ref,   # SMEM (max_pages,) int32 — this slot's block table
    *refs,
    heads: int, kv_heads: int, head_dim: int, page: int, eps: float,
    m: int, residual: bool, kv_quant: bool = False,
):
    """M-row chunked-prefill step for ONE slot: rows occupy positions
    pos..pos+m-1, attend the prior paged context (idx < pos, streamed
    through the block table) plus each other causally from registers.
    ``pos`` and ``m`` are multiples of ``page``, so the chunk's K/V
    write covers m/page WHOLE pool pages — no read-modify-write.

    ``kv_quant``: the whole chunk quantizes in-register before the page
    writes (:func:`kv_quant_rows` — whole pages, so no scale RMW
    either) and the prior-context sweep dequantizes each streamed page;
    the within-chunk causal fold uses the exact fp registers."""
    if kv_quant:
        (x_ref, nw_ref, wqkv_ref, sqkv_ref, bqkv_ref, cos_ref, sin_ref,
         kp_in, vp_in, ks_in, vs_in, wo_ref, swo_ref,
         out_ref, kp_out, vp_out, ks_out, vs_out,
         kv_win, s_win, kblk, vblk, sblk, sem, wsem) = refs
    else:
        (x_ref, nw_ref, wqkv_ref, sqkv_ref, bqkv_ref, cos_ref, sin_ref,
         kp_in, vp_in, wo_ref, swo_ref,
         out_ref, kp_out, vp_out,
         kv_win, kblk, vblk, sem, wsem) = refs
    pos = pos_ref[0]
    half = head_dim // 2
    dtype = x_ref.dtype
    group = heads // kv_heads
    scale = 1.0 / (head_dim ** 0.5)
    int4 = wqkv_ref.dtype == jnp.uint8

    # --- projections --------------------------------------------------------
    h = _rms(x_ref, nw_ref, eps).astype(dtype)  # [M, D]
    qkv = _wdot(h, wqkv_ref, sqkv_ref[...], int4=int4) + bqkv_ref[...].astype(
        jnp.float32
    )
    qf = qkv[:, : heads * head_dim].reshape(m * heads, head_dim)
    kf = qkv[:, heads * head_dim : (heads + kv_heads) * head_dim].reshape(
        m * kv_heads, head_dim
    )
    vf = qkv[:, (heads + kv_heads) * head_dim :].reshape(
        m * kv_heads, head_dim
    )

    cos_m = cos_ref[...].astype(jnp.float32)  # [M, hd] per-row tables
    sin_m = sin_ref[...].astype(jnp.float32)

    def _expand(t, reps):
        return jnp.broadcast_to(
            t[:, None, :], (m, reps, head_dim)
        ).reshape(m * reps, head_dim)

    q = _rotate(qf, _expand(cos_m, heads), _expand(sin_m, heads), half)
    k = _rotate(kf, _expand(cos_m, kv_heads), _expand(sin_m, kv_heads), half)
    k_m = k.reshape(m, kv_heads, head_dim)
    v_m = vf.reshape(m, kv_heads, head_dim)

    # --- whole-page chunk write (overlapped with the sweep) -----------------
    if kv_quant:
        kq, ksc = kv_quant_rows(k_m)  # [M, KV, hd] int8, [M, KV] f32
        vq, vsc = kv_quant_rows(v_m)
        kv_win[0] = kq.transpose(1, 0, 2)  # [KV, M, hd]
        kv_win[1] = vq.transpose(1, 0, 2)
        s_win[0] = ksc.transpose(1, 0)  # [KV, M]
        s_win[1] = vsc.transpose(1, 0)
    else:
        kv_win[0] = k_m.transpose(1, 0, 2).astype(kv_win.dtype)  # [KV, M, hd]
        kv_win[1] = v_m.transpose(1, 0, 2).astype(kv_win.dtype)
    pending = []
    for j in range(m // page):
        pg = bt_ref[pos // page + j]
        writes = [
            pltpu.make_async_copy(
                kv_win.at[0, :, pl.ds(j * page, page), :], kp_out.at[pg],
                wsem.at[0, j],
            ),
            pltpu.make_async_copy(
                kv_win.at[1, :, pl.ds(j * page, page), :], vp_out.at[pg],
                wsem.at[1, j],
            ),
        ]
        if kv_quant:
            writes += [
                pltpu.make_async_copy(
                    s_win.at[0, :, pl.ds(j * page, page)], ks_out.at[pg],
                    wsem.at[2, j],
                ),
                pltpu.make_async_copy(
                    s_win.at[1, :, pl.ds(j * page, page)], vs_out.at[pg],
                    wsem.at[3, j],
                ),
            ]
        for wr in writes:
            wr.start()
        pending += writes

    # --- flash sweep over the prior paged context (idx < pos) ---------------
    nblocks = pos // page  # pos is page-aligned: all prior pages are full
    rows = m * group  # per kv head

    def body(blk, carry):
        m_run, l_run, acc = carry
        pg = bt_ref[blk]
        copies = [
            pltpu.make_async_copy(kp_out.at[pg], kblk, sem.at[2]),
            pltpu.make_async_copy(vp_out.at[pg], vblk, sem.at[3]),
        ]
        if kv_quant:
            copies += [
                pltpu.make_async_copy(ks_out.at[pg], sblk.at[0], sem.at[4]),
                pltpu.make_async_copy(vs_out.at[pg], sblk.at[1], sem.at[5]),
            ]
        for cp in copies:
            cp.start()
        for cp in copies:
            cp.wait()
        q4 = q.reshape(m, heads, head_dim)
        outs = []
        for g in range(kv_heads):
            q_g = q4[:, g * group : (g + 1) * group, :].reshape(
                rows, head_dim
            )
            if kv_quant:
                k_g = kv_dequant(kblk[g], sblk[0, g], dtype)
            else:
                k_g = kblk[g].astype(dtype)
            s_g = jax.lax.dot_general(
                q_g.astype(dtype), k_g,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [rows, page]
            outs.append(s_g)
        s = jnp.concatenate(outs, axis=0)  # [KV*rows, page]
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_run * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = []
        for g in range(kv_heads):
            if kv_quant:
                v_g = kv_dequant(vblk[g], sblk[1, g], dtype)
            else:
                v_g = vblk[g].astype(dtype)
            pv.append(
                jax.lax.dot(
                    p[g * rows : (g + 1) * rows].astype(dtype),
                    v_g,
                    preferred_element_type=jnp.float32,
                )
            )
        acc_new = acc * alpha + jnp.concatenate(pv, axis=0)
        return m_new, l_new, acc_new

    m0 = jnp.full((kv_heads * rows, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((kv_heads * rows, 1), jnp.float32)
    a0 = jnp.zeros((kv_heads * rows, head_dim), jnp.float32)
    m_fin, l_fin, acc = jax.lax.fori_loop(0, nblocks, body, (m0, l0, a0))

    # --- within-chunk causal attention from registers -----------------------
    q4 = q.reshape(m, heads, head_dim)
    causal = (
        jax.lax.broadcasted_iota(jnp.int32, (rows, m), 0) // group
        >= jax.lax.broadcasted_iota(jnp.int32, (rows, m), 1)
    )
    s_parts = []
    for g in range(kv_heads):
        q_g = q4[:, g * group : (g + 1) * group, :].reshape(rows, head_dim)
        s_cc = jax.lax.dot_general(
            q_g.astype(dtype), k_m[:, g, :].astype(dtype),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [rows, m]
        s_parts.append(jnp.where(causal, s_cc, -jnp.inf))
    s_cc = jnp.concatenate(s_parts, axis=0)  # [KV*rows, m]
    m2 = jnp.maximum(m_fin, jnp.max(s_cc, axis=-1, keepdims=True))
    alpha = jnp.exp(m_fin - m2)
    p_cc = jnp.exp(s_cc - m2)
    l2 = l_fin * alpha + jnp.sum(p_cc, axis=-1, keepdims=True)
    pv = []
    for g in range(kv_heads):
        pv.append(
            jax.lax.dot(
                p_cc[g * rows : (g + 1) * rows].astype(dtype),
                v_m[:, g, :].astype(dtype),
                preferred_element_type=jnp.float32,
            )
        )
    acc = acc * alpha + jnp.concatenate(pv, axis=0)
    attn = acc / l2  # [KV*rows, hd], rows ordered (g, i, gg)

    attn = (
        attn.reshape(kv_heads, m, group, head_dim)
        .transpose(1, 0, 2, 3)
        .reshape(m, heads * head_dim)
    )
    o = _wdot(attn.astype(dtype), wo_ref, swo_ref[...], int4=int4)
    if residual:
        o = x_ref[...].astype(jnp.float32) + o
    out_ref[...] = o.astype(out_ref.dtype)
    for copy in pending:
        copy.wait()


@functools.partial(
    jax.jit,
    static_argnames=("heads", "kv_heads", "head_dim", "eps", "residual"),
)
def attention_paged_chunk_step(
    x, norm_w, wqkv, sqkv, bqkv, cos_rows, sin_rows, k_pool, v_pool,
    wo, swo, position, block_table, k_scale=None, v_scale=None,
    *, heads: int, kv_heads: int, head_dim: int, eps: float = 1e-6,
    residual: bool = True,
):
    """M-row paged attention sublayer (chunked prefill).

    x: [M, D] — the chunk's tokens at positions ``position..position+M-1``
    where ``position`` and M are multiples of the pool page size;
    block_table: [max_pages] int32 for THIS slot. The chunk's K/V land as
    whole pool pages; prior context streams through the table. Returns
    (x_out [M, D], k_pool, v_pool).

    ``k_scale``/``v_scale`` switch on the int8-KV path (see
    :func:`attention_paged_batch_step`) and the return grows to
    (x_out, k_pool, v_pool, k_scale, v_scale).
    """
    kv_quant = k_scale is not None
    assert kv_quant == (v_scale is not None)
    m, d = x.shape
    page = k_pool.shape[2]
    assert page % 8 == 0 and m % page == 0, (m, page)
    if kv_quant:
        assert k_pool.dtype == jnp.int8 and v_pool.dtype == jnp.int8, (
            "int8-KV path needs int8 pools", k_pool.dtype
        )
    n_qkv = wqkv.shape[1]
    kernel = functools.partial(
        _attn_paged_chunk_kernel, heads=heads, kv_heads=kv_heads,
        head_dim=head_dim, page=page, eps=eps, m=m, residual=residual,
        kv_quant=kv_quant,
    )
    pool_specs = [
        pl.BlockSpec(memory_space=pl.ANY),      # k_pool (HBM)
        pl.BlockSpec(memory_space=pl.ANY),      # v_pool (HBM)
    ]
    pool_outs = [
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    pool_shapes = [
        jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
        jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
    ]
    scale_scratch = []
    if kv_quant:
        pool_specs += [
            pl.BlockSpec(memory_space=pl.ANY),  # k_scale (HBM)
            pl.BlockSpec(memory_space=pl.ANY),  # v_scale (HBM)
        ]
        pool_outs += [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ]
        pool_shapes += [
            jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype),
            jax.ShapeDtypeStruct(v_scale.shape, v_scale.dtype),
        ]
        scale_scratch = [
            pltpu.VMEM((2, kv_heads, m), jnp.float32),  # s_win
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),  # x
            pl.BlockSpec(memory_space=pltpu.VMEM),  # norm_w
            pl.BlockSpec(memory_space=pltpu.VMEM),  # wqkv
            pl.BlockSpec(memory_space=pltpu.VMEM),  # sqkv
            pl.BlockSpec(memory_space=pltpu.VMEM),  # bqkv
            pl.BlockSpec(memory_space=pltpu.VMEM),  # cos rows
            pl.BlockSpec(memory_space=pltpu.VMEM),  # sin rows
            *pool_specs,
            pl.BlockSpec(memory_space=pltpu.VMEM),  # wo
            pl.BlockSpec(memory_space=pltpu.VMEM),  # swo
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            *pool_outs,
        ],
        scratch_shapes=[
            pltpu.VMEM((2, kv_heads, m, head_dim), k_pool.dtype),  # kv_win
            *scale_scratch,
            pltpu.VMEM((kv_heads, page, head_dim), k_pool.dtype),
            pltpu.VMEM((kv_heads, page, head_dim), v_pool.dtype),
            *(
                [pltpu.VMEM((2, kv_heads, page), jnp.float32)]  # sblk
                if kv_quant else []
            ),
            pltpu.SemaphoreType.DMA((6 if kv_quant else 4,)),
            pltpu.SemaphoreType.DMA((4 if kv_quant else 2, m // page)),
        ],
    )
    operands = [k_pool, v_pool]
    if kv_quant:
        operands += [k_scale, v_scale]
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(
                (m, d), x.dtype if residual else jnp.float32
            ),
            *pool_shapes,
        ],
        input_output_aliases=(
            {9: 1, 10: 2, 11: 3, 12: 4} if kv_quant else {9: 1, 10: 2}
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_CHUNK_VMEM_LIMIT,
        ),
        interpret=_interpret(),
    )(
        jnp.asarray([position], jnp.int32).reshape(1),
        jnp.asarray(block_table, jnp.int32),
        x, norm_w.reshape(1, d), wqkv, sqkv, bqkv.reshape(1, n_qkv),
        cos_rows, sin_rows, *operands, wo, swo,
    )


def _attn_paged_spec_kernel(
    pos_ref,  # SMEM (B,) int32 — per-stream chunk START positions
    bt_ref,   # SMEM (B, max_pages) int32 — per-stream block tables
    *refs,
    heads: int, kv_heads: int, head_dim: int, page: int, eps: float,
    batch: int, m: int, win: int, seq: int, residual: bool,
    kv_quant: bool = False,
):
    """B independent speculative-verify chunks over paged KV: stream b's
    m rows (rows b*m..(b+1)*m-1 of x) occupy positions
    pos[b]..pos[b]+m-1 of ITS paged context. Math is the chunk kernel's
    (prior-context flash sweep + within-chunk causal fold from
    registers), addressing is the paged batch kernel's (every cache
    touch routes through the stream's block table). The m-row cache
    write is the one genuinely new piece: unlike the single-row paged
    RMW, an aligned window covering m consecutive rows can straddle a
    page boundary, so the window is read, modified and written back in
    8-row groups — page size is a multiple of 8 and the groups are
    8-aligned, so each group lives wholly inside ONE pool page and maps
    through the block table independently. A frozen stream (pos 0,
    zeroed table row) dumps all m rows into the reserved null page.

    ``kv_quant``: each stream's m rows quantize in-register before the
    group inserts (:func:`kv_quant_rows`; the [KV, win] scale window
    RMWs in the same page-safe 8-row groups) and the prior-context
    sweep dequantizes each streamed page; the within-chunk causal fold
    stays exact fp from registers."""
    if kv_quant:
        (x_ref, nw_ref, wqkv_ref, sqkv_ref, bqkv_ref, cos_ref, sin_ref,
         kp_in, vp_in, ks_in, vs_in, wo_ref, swo_ref,
         out_ref, kp_out, vp_out, ks_out, vs_out,
         kv_win, s_win, kblk, vblk, sblk, sem, wsem) = refs
    else:
        (x_ref, nw_ref, wqkv_ref, sqkv_ref, bqkv_ref, cos_ref, sin_ref,
         kp_in, vp_in, wo_ref, swo_ref,
         out_ref, kp_out, vp_out,
         kv_win, kblk, vblk, sem, wsem) = refs
    half = head_dim // 2
    dtype = x_ref.dtype
    int4 = wqkv_ref.dtype == jnp.uint8
    group = heads // kv_heads
    scale = 1.0 / (head_dim ** 0.5)
    rows = m * group  # per kv head, per stream
    ngroups = win // 8

    # --- projections (all B*m rows at once: one weight pass) ----------------
    h = _rms(x_ref, nw_ref, eps).astype(dtype)  # [B*m, D]
    qkv = _wdot(h, wqkv_ref, sqkv_ref[...], int4=int4) + bqkv_ref[...].astype(
        jnp.float32
    )
    bm = batch * m
    qf = qkv[:, : heads * head_dim].reshape(bm * heads, head_dim)
    kf = qkv[:, heads * head_dim : (heads + kv_heads) * head_dim].reshape(
        bm * kv_heads, head_dim
    )
    vf = qkv[:, (heads + kv_heads) * head_dim :].reshape(
        bm * kv_heads, head_dim
    )

    cos_r = cos_ref[...].astype(jnp.float32)  # [B*m, hd] per-row tables
    sin_r = sin_ref[...].astype(jnp.float32)

    def _expand(t, reps):
        return jnp.broadcast_to(
            t[:, None, :], (bm, reps, head_dim)
        ).reshape(bm * reps, head_dim)

    q = _rotate(qf, _expand(cos_r, heads), _expand(sin_r, heads), half)
    k = _rotate(kf, _expand(cos_r, kv_heads), _expand(sin_r, kv_heads), half)
    q_s = q.reshape(batch, m, heads, head_dim)
    k_s = k.reshape(batch, m, kv_heads, head_dim)
    v_s = vf.reshape(batch, m, kv_heads, head_dim)

    # --- per-stream m-row cache RMW in page-safe 8-row groups ---------------
    # The aligned window [aligned, aligned+win) covers all m rows (same
    # clamp as the dense chunk kernel, so it never walks past seq). Rows
    # the window drags in beyond the chunk — up to 7 before pos and the
    # alignment tail after pos+m-1 — are read and written back
    # unchanged, so a tail group resolving to an ungranted table entry
    # (physical page 0) only round-trips null-page bytes. The flash
    # sweep below never reads rows >= pos from the pool (``live`` masks
    # them; the chunk rows fold in from registers), so only the group
    # READS gate the inserts and the write-backs overlap the sweep.
    pending = []
    for b in range(batch):
        pos = pos_ref[b]
        aligned = pl.multiple_of(
            jnp.minimum(pos // 8 * 8, seq - win), 8
        )
        reads = []
        for g in range(ngroups):
            gs = aligned + g * 8
            pg = bt_ref[b, gs // page]
            off = pl.multiple_of(gs - gs // page * page, 8)
            reads += [
                pltpu.make_async_copy(
                    kp_out.at[pg, :, pl.ds(off, 8), :],
                    kv_win.at[0, b, :, pl.ds(g * 8, 8), :], sem.at[0],
                ),
                pltpu.make_async_copy(
                    vp_out.at[pg, :, pl.ds(off, 8), :],
                    kv_win.at[1, b, :, pl.ds(g * 8, 8), :], sem.at[1],
                ),
            ]
            if kv_quant:
                # Scale windows RMW in the same page-safe groups, on
                # the same counting semaphores as the value reads.
                reads += [
                    pltpu.make_async_copy(
                        ks_out.at[pg, :, pl.ds(off, 8)],
                        s_win.at[0, b, :, pl.ds(g * 8, 8)], sem.at[0],
                    ),
                    pltpu.make_async_copy(
                        vs_out.at[pg, :, pl.ds(off, 8)],
                        s_win.at[1, b, :, pl.ds(g * 8, 8)], sem.at[1],
                    ),
                ]
        for rd in reads:
            rd.start()
        for rd in reads:
            rd.wait()
        offs = pos - aligned
        win_iota = jax.lax.broadcasted_iota(
            jnp.int32, (kv_heads, win, head_dim), 1
        )
        if kv_quant:
            kq, ksc = kv_quant_rows(k_s[b])  # [m, KV, hd] int8, [m, KV]
            vq, vsc = kv_quant_rows(v_s[b])
            s_iota = jax.lax.broadcasted_iota(jnp.int32, (kv_heads, win), 1)
            for i in range(m):
                sel = win_iota == offs + i
                kv_win[0, b] = jnp.where(sel, kq[i][:, None, :], kv_win[0, b])
                kv_win[1, b] = jnp.where(sel, vq[i][:, None, :], kv_win[1, b])
                s_sel = s_iota == offs + i
                s_win[0, b] = jnp.where(s_sel, ksc[i][:, None], s_win[0, b])
                s_win[1, b] = jnp.where(s_sel, vsc[i][:, None], s_win[1, b])
        else:
            for i in range(m):
                sel = win_iota == offs + i
                kv_win[0, b] = jnp.where(
                    sel, k_s[b, i][:, None, :].astype(kv_win.dtype),
                    kv_win[0, b]
                )
                kv_win[1, b] = jnp.where(
                    sel, v_s[b, i][:, None, :].astype(kv_win.dtype),
                    kv_win[1, b]
                )
        for g in range(ngroups):
            gs = aligned + g * 8
            pg = bt_ref[b, gs // page]
            off = pl.multiple_of(gs - gs // page * page, 8)
            writes = [
                pltpu.make_async_copy(
                    kv_win.at[0, b, :, pl.ds(g * 8, 8), :],
                    kp_out.at[pg, :, pl.ds(off, 8), :], wsem.at[0, b, g],
                ),
                pltpu.make_async_copy(
                    kv_win.at[1, b, :, pl.ds(g * 8, 8), :],
                    vp_out.at[pg, :, pl.ds(off, 8), :], wsem.at[1, b, g],
                ),
            ]
            if kv_quant:
                writes += [
                    pltpu.make_async_copy(
                        s_win.at[0, b, :, pl.ds(g * 8, 8)],
                        ks_out.at[pg, :, pl.ds(off, 8)], wsem.at[2, b, g],
                    ),
                    pltpu.make_async_copy(
                        s_win.at[1, b, :, pl.ds(g * 8, 8)],
                        vs_out.at[pg, :, pl.ds(off, 8)], wsem.at[3, b, g],
                    ),
                ]
            for wr in writes:
                wr.start()
            pending += writes

    # --- per-stream flash sweep + within-chunk causal fold ------------------
    attn_rows = []
    for b in range(batch):
        pos = pos_ref[b]
        nblocks = (pos + page - 1) // page  # prior context only

        def body(blk, carry, pos=pos, b=b):
            m_run, l_run, acc = carry
            pg = bt_ref[b, blk]
            copies = [
                pltpu.make_async_copy(kp_out.at[pg], kblk, sem.at[2]),
                pltpu.make_async_copy(vp_out.at[pg], vblk, sem.at[3]),
            ]
            if kv_quant:
                copies += [
                    pltpu.make_async_copy(
                        ks_out.at[pg], sblk.at[0], sem.at[4]
                    ),
                    pltpu.make_async_copy(
                        vs_out.at[pg], sblk.at[1], sem.at[5]
                    ),
                ]
            for cp in copies:
                cp.start()
            for cp in copies:
                cp.wait()
            live = (
                jax.lax.broadcasted_iota(jnp.int32, (1, page), 1) + blk * page
            ) < pos
            outs = []
            for g in range(kv_heads):
                q_g = q_s[b, :, g * group : (g + 1) * group, :].reshape(
                    rows, head_dim
                )
                if kv_quant:
                    k_g = kv_dequant(kblk[g], sblk[0, g], dtype)
                else:
                    k_g = kblk[g].astype(dtype)
                s_g = jax.lax.dot_general(
                    q_g.astype(dtype), k_g,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale  # [rows, page]
                outs.append(jnp.where(live, s_g, -jnp.inf))
            s = jnp.concatenate(outs, axis=0)  # [KV*rows, page]
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_run - m_new)
            p = jnp.exp(s - m_new)
            l_new = l_run * alpha + jnp.sum(p, axis=-1, keepdims=True)
            pv = []
            for g in range(kv_heads):
                if kv_quant:
                    v_g = kv_dequant(vblk[g], sblk[1, g], dtype)
                else:
                    v_g = vblk[g].astype(dtype)
                pv.append(
                    jax.lax.dot(
                        p[g * rows : (g + 1) * rows].astype(dtype),
                        v_g,
                        preferred_element_type=jnp.float32,
                    )
                )
            acc_new = acc * alpha + jnp.concatenate(pv, axis=0)
            return m_new, l_new, acc_new

        m0 = jnp.full((kv_heads * rows, 1), -1e30, jnp.float32)
        l0 = jnp.zeros((kv_heads * rows, 1), jnp.float32)
        a0 = jnp.zeros((kv_heads * rows, head_dim), jnp.float32)
        m_fin, l_fin, acc = jax.lax.fori_loop(0, nblocks, body, (m0, l0, a0))

        # within-chunk causal attention from registers — stream-local:
        # rows of stream b attend ONLY their own chunk, never another
        # stream's (the sequences are independent).
        causal = (
            jax.lax.broadcasted_iota(jnp.int32, (rows, m), 0) // group
            >= jax.lax.broadcasted_iota(jnp.int32, (rows, m), 1)
        )
        s_parts = []
        for g in range(kv_heads):
            q_g = q_s[b, :, g * group : (g + 1) * group, :].reshape(
                rows, head_dim
            )
            s_cc = jax.lax.dot_general(
                q_g.astype(dtype), k_s[b, :, g, :].astype(dtype),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [rows, m]
            s_parts.append(jnp.where(causal, s_cc, -jnp.inf))
        s_cc = jnp.concatenate(s_parts, axis=0)  # [KV*rows, m]
        m2 = jnp.maximum(m_fin, jnp.max(s_cc, axis=-1, keepdims=True))
        alpha = jnp.exp(m_fin - m2)
        p_cc = jnp.exp(s_cc - m2)
        l2 = l_fin * alpha + jnp.sum(p_cc, axis=-1, keepdims=True)
        pv = []
        for g in range(kv_heads):
            pv.append(
                jax.lax.dot(
                    p_cc[g * rows : (g + 1) * rows].astype(dtype),
                    v_s[b, :, g, :].astype(dtype),
                    preferred_element_type=jnp.float32,
                )
            )
        acc = acc * alpha + jnp.concatenate(pv, axis=0)
        attn_b = acc / l2  # [KV*rows, hd], rows ordered (g, i, gg)
        attn_rows.append(
            attn_b.reshape(kv_heads, m, group, head_dim)
            .transpose(1, 0, 2, 3)
            .reshape(m, heads * head_dim)
        )

    attn = jnp.concatenate(attn_rows, axis=0)  # [B*m, H*hd]

    # --- output projection + residual ---------------------------------------
    o = _wdot(attn.astype(dtype), wo_ref, swo_ref[...], int4=int4)
    if residual:
        o = x_ref[...].astype(jnp.float32) + o
    out_ref[...] = o.astype(out_ref.dtype)
    for copy in pending:
        copy.wait()


@functools.partial(
    jax.jit,
    static_argnames=("heads", "kv_heads", "head_dim", "m", "eps", "residual"),
)
def attention_paged_spec_step(
    x, norm_w, wqkv, sqkv, bqkv, cos_rows, sin_rows, k_pool, v_pool,
    wo, swo, positions, block_tables, k_scale=None, v_scale=None,
    *, heads: int, kv_heads: int, head_dim: int, m: int,
    eps: float = 1e-6, residual: bool = True,
):
    """Fused paged attention for B speculative-verify chunks.

    x: [B*m, D] — stream b's m candidate rows (last emitted token + its
    m-1 drafts) at positions ``positions[b]..positions[b]+m-1``, rows
    flattened stream-major; cos_rows/sin_rows: [B*m, hd] rope rows
    gathered at every flattened position; block_tables: [B, max_pages]
    int32 (0 = the reserved null page). Rejected tail rows the write
    leaves behind are overwritten by the next chunk before any sweep
    can attend them (the spec_decode invariant: the next chunk starts
    at the first rejected position). Callers must keep
    ``positions[b] + m <= max_seq`` (the spec headroom contract, in the
    engine enforced by ``pages_needed``/``fits``). Returns
    (x_out [B*m, D], k_pool, v_pool).

    ``k_scale``/``v_scale`` switch on the int8-KV path (see
    :func:`attention_paged_batch_step`) and the return grows to
    (x_out, k_pool, v_pool, k_scale, v_scale).
    """
    kv_quant = k_scale is not None
    assert kv_quant == (v_scale is not None)
    bm, d = x.shape
    assert bm % m == 0, (bm, m)
    batch = bm // m
    page = k_pool.shape[2]
    assert page % 8 == 0, page
    if kv_quant:
        assert k_pool.dtype == jnp.int8 and v_pool.dtype == jnp.int8, (
            "int8-KV path needs int8 pools", k_pool.dtype
        )
    seq = block_tables.shape[1] * page
    win = (7 + m + 7) // 8 * 8  # aligned row window covering all m rows
    assert win <= seq, (win, seq)
    n_qkv = wqkv.shape[1]
    kernel = functools.partial(
        _attn_paged_spec_kernel, heads=heads, kv_heads=kv_heads,
        head_dim=head_dim, page=page, eps=eps, batch=batch, m=m, win=win,
        seq=seq, residual=residual, kv_quant=kv_quant,
    )
    pool_specs = [
        pl.BlockSpec(memory_space=pl.ANY),      # k_pool (HBM)
        pl.BlockSpec(memory_space=pl.ANY),      # v_pool (HBM)
    ]
    pool_outs = [
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    pool_shapes = [
        jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
        jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
    ]
    scale_scratch = []
    if kv_quant:
        pool_specs += [
            pl.BlockSpec(memory_space=pl.ANY),  # k_scale (HBM)
            pl.BlockSpec(memory_space=pl.ANY),  # v_scale (HBM)
        ]
        pool_outs += [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ]
        pool_shapes += [
            jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype),
            jax.ShapeDtypeStruct(v_scale.shape, v_scale.dtype),
        ]
        scale_scratch = [
            pltpu.VMEM((2, batch, kv_heads, win), jnp.float32),  # s_win
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),  # x
            pl.BlockSpec(memory_space=pltpu.VMEM),  # norm_w
            pl.BlockSpec(memory_space=pltpu.VMEM),  # wqkv
            pl.BlockSpec(memory_space=pltpu.VMEM),  # sqkv
            pl.BlockSpec(memory_space=pltpu.VMEM),  # bqkv
            pl.BlockSpec(memory_space=pltpu.VMEM),  # cos rows
            pl.BlockSpec(memory_space=pltpu.VMEM),  # sin rows
            *pool_specs,
            pl.BlockSpec(memory_space=pltpu.VMEM),  # wo
            pl.BlockSpec(memory_space=pltpu.VMEM),  # swo
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            *pool_outs,
        ],
        scratch_shapes=[
            pltpu.VMEM((2, batch, kv_heads, win, head_dim), k_pool.dtype),
            *scale_scratch,
            pltpu.VMEM((kv_heads, page, head_dim), k_pool.dtype),
            pltpu.VMEM((kv_heads, page, head_dim), v_pool.dtype),
            *(
                [pltpu.VMEM((2, kv_heads, page), jnp.float32)]  # sblk
                if kv_quant else []
            ),
            pltpu.SemaphoreType.DMA((6 if kv_quant else 4,)),
            pltpu.SemaphoreType.DMA(
                (4 if kv_quant else 2, batch, win // 8)
            ),
        ],
    )
    operands = [k_pool, v_pool]
    if kv_quant:
        operands += [k_scale, v_scale]
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(
                (bm, d), x.dtype if residual else jnp.float32
            ),
            *pool_shapes,
        ],
        input_output_aliases=(
            {9: 1, 10: 2, 11: 3, 12: 4} if kv_quant else {9: 1, 10: 2}
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=_interpret(),
    )(
        jnp.asarray(positions, jnp.int32).reshape(batch),
        jnp.asarray(block_tables, jnp.int32),
        x, norm_w.reshape(1, d), wqkv, sqkv, bqkv.reshape(1, n_qkv),
        cos_rows, sin_rows, *operands, wo, swo,
    )


# ---------------------------------------------------------------------------
# MLP block
# ---------------------------------------------------------------------------


def _mlp_kernel(
    x_ref, nw_ref, gate_ref, up_ref, sg_ref, su_ref, bg_ref, bu_ref,
    down_ref, sd_ref, out_ref, acc_ref, *, nf: int, eps: float, int4: bool,
    residual: bool,
):
    fi = pl.program_id(0)
    dtype = x_ref.dtype

    @pl.when(fi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    h = _rms(x_ref, nw_ref, eps).astype(dtype)  # recomputed per tile: O(D)
    g = _wdot(h, gate_ref, sg_ref[...], int4=int4) + bg_ref[...].astype(
        jnp.float32
    )
    u = _wdot(h, up_ref, su_ref[...], int4=int4) + bu_ref[...].astype(jnp.float32)
    a = (jax.nn.silu(g) * u).astype(dtype)  # [M, BF]
    if int4:
        # The down group scales ride in FULL (their per-tile row count
        # is not sublane-aligned, which Mosaic block specs require);
        # gather this tile's rows with a one-hot matmul — the only
        # Mosaic-safe dynamic row gather.
        sd = sd_ref[...].astype(jnp.float32)          # [F/G, D]
        rows = sd.shape[0] // nf
        sel = (
            jax.lax.broadcasted_iota(jnp.int32, (rows, sd.shape[0]), 1)
            == fi * rows
            + jax.lax.broadcasted_iota(jnp.int32, (rows, sd.shape[0]), 0)
        ).astype(jnp.float32)
        sd_tile = jax.lax.dot(sel, sd, preferred_element_type=jnp.float32)
        acc_ref[...] += _wdot(a, down_ref, sd_tile, int4=True)
    else:
        acc_ref[...] += jax.lax.dot(
            a, down_ref[...].astype(dtype), preferred_element_type=jnp.float32
        )

    @pl.when(fi == nf - 1)
    def _finalize():
        acc = acc_ref[...]
        if not int4:
            # Per-column down scale commutes with the ffn sweep: apply
            # once on the final accumulator.
            acc = acc * sd_ref[...].astype(jnp.float32)
        if residual:
            acc = x_ref[...].astype(jnp.float32) + acc
        out_ref[...] = acc.astype(out_ref.dtype)


#: int8 bytes of weight one grid step of :func:`mlp_step` (gate, up and
#: down panels: 3 * D * BF) and of :func:`lm_head_argmax` (D * BV) may
#: stream: what the 1.5B widths take at their measured tiles (BF 1024,
#: BV 2048 at D = 1536). Twice that, double-buffered, plus the panels'
#: bf16 copies, stays inside the compiler's 16 MiB scope at any D.
_MLP_STEP_BYTES = 3 * 1536 * 1024
_HEAD_STEP_BYTES = 1536 * 2048


def _pick_bf(ffn: int, d: int) -> int:
    """Largest lane-multiple tile dividing ffn, at most 1024 and at most
    what keeps the three per-step int8 panels (gate + up + down = 3*D*BF
    bytes) within ``_MLP_STEP_BYTES``, so Mosaic can double-buffer the
    stream at any hidden size: 1024 at D = 1536, 256 at D = 5120 — a
    bigger tile serializes the DMAs and shows up directly as decode
    latency (measured: 1792 -> 896 on the 2B shape was worth ~5%)."""
    if ffn % _LANE:
        return ffn
    cap = max(min(1024, _MLP_STEP_BYTES // (3 * d) // _LANE * _LANE), _LANE)
    for bf in range(min(ffn, cap), 0, -_LANE):
        if ffn % bf == 0:
            return bf
    return ffn


@functools.partial(jax.jit, static_argnames=("eps", "residual"))
def mlp_step(x, norm_w, w_gateup, s_gateup, b_gateup, w_down, s_down,
             *, eps: float = 1e-6, residual: bool = True):
    """Fused SwiGLU decode sublayer: one grid sweep over ffn tiles.

    w_gateup: int8 [D, 2F] (gate | up concatenated — quantize_tree
    layout) with per-column scales [1, 2F], or int4-packed [D/2, 2F]
    uint8 with group scales [D/GROUP, 2F] (ops.int4); w_down likewise
    [F, D] / [F/2, D]. x: [M, D] — M = 1 for vanilla decode, k+1 for
    speculative verify (the weight stream serves all rows).
    Returns x + down(silu(gate)·up).
    """
    mrows, d = x.shape
    int4 = w_gateup.dtype == jnp.uint8
    f = w_down.shape[0] * (2 if int4 else 1)
    bf = _pick_bf(f, d)
    nf = f // bf
    kernel = functools.partial(
        _mlp_kernel, nf=nf, eps=eps, int4=int4, residual=residual
    )
    if int4:
        wrows, drows = d // 2, bf // 2  # packed row counts
        srows = s_gateup.shape[0]       # groups over D (gate/up K dim)
        sdrows = s_down.shape[0]        # down scales ride in full
        assert bf % (f // s_down.shape[0]) == 0, (bf, f, s_down.shape)
    else:
        wrows, drows, srows, sdrows = d, bf, 1, 1
    return pl.pallas_call(
        kernel,
        grid=(nf,),
        in_specs=[
            pl.BlockSpec((mrows, d), lambda i: (0, 0)),       # x
            pl.BlockSpec((1, d), lambda i: (0, 0)),          # norm_w
            pl.BlockSpec((wrows, bf), lambda i: (0, i)),      # gate tile
            pl.BlockSpec((wrows, bf), lambda i, _nf=nf: (0, _nf + i)),  # up
            pl.BlockSpec((srows, bf), lambda i: (0, i)),      # gate scale
            pl.BlockSpec((srows, bf), lambda i, _nf=nf: (0, _nf + i)),
            pl.BlockSpec((1, bf), lambda i: (0, i)),          # gate bias
            pl.BlockSpec((1, bf), lambda i, _nf=nf: (0, _nf + i)),  # up bias
            pl.BlockSpec((drows, d), lambda i: (i, 0)),       # down tile
            pl.BlockSpec((sdrows, d), lambda i: (0, 0)),  # down scale
        ],
        out_specs=pl.BlockSpec((mrows, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (mrows, d), x.dtype if residual else jnp.float32
        ),
        scratch_shapes=[pltpu.VMEM((mrows, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=_interpret(),
    )(
        # gate and up tiles index into the same fused arrays (two specs
        # with different index maps), so each rides in twice.
        x, norm_w.reshape(1, d), w_gateup, w_gateup, s_gateup, s_gateup,
        b_gateup.reshape(1, 2 * f), b_gateup.reshape(1, 2 * f),
        w_down, s_down,
    )


# ---------------------------------------------------------------------------
# lm_head + argmax
# ---------------------------------------------------------------------------


def _head_kernel(
    x_ref, nw_ref, w_ref, s_ref, out_ref, val_ref, best_ref, besti_ref,
    *, nv: int, bv: int, vocab: int, eps: float,
):
    vi = pl.program_id(0)
    dtype = x_ref.dtype
    m = x_ref.shape[0]

    h = _rms(x_ref, nw_ref, eps).astype(dtype)
    logits = _wdot(h, w_ref, s_ref[...], int4=w_ref.dtype == jnp.uint8)  # [M, BV]
    # The ragged last tile's lanes past the vocab hold anything, NaN
    # included: select, never multiply, so they cannot win.
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) + vi * bv
    logits = jnp.where(col < vocab, logits, -jnp.inf)
    blk_max = jnp.max(logits, axis=-1)  # [M]
    blk_arg = jnp.argmax(logits, axis=-1).astype(jnp.int32) + vi * bv

    @pl.when(vi == 0)
    def _init():
        best_ref[...] = jnp.full_like(best_ref, -jnp.inf)
        besti_ref[...] = jnp.zeros_like(besti_ref)

    # Strict > keeps the first-index tie-break of jnp.argmax across
    # blocks; within a block argmax already takes the first maximum.
    better = blk_max > best_ref[...][:, 0]
    best_ref[...] = jnp.where(better, blk_max, best_ref[...][:, 0])[:, None]
    besti_ref[...] = jnp.where(better, blk_arg, besti_ref[...][:, 0])[:, None]

    @pl.when(vi == nv - 1)
    def _finalize():
        out_ref[...] = besti_ref[...]
        val_ref[...] = best_ref[...]


@functools.partial(jax.jit, static_argnames=("eps", "return_val"))
def lm_head_argmax(x, norm_w, w, s, *, eps: float = 1e-6,
                   return_val: bool = False):
    """Greedy next-token ids straight from the kernel.

    x: [M, D] (M = 1 vanilla decode, k+1 speculative verify); w: int8
    [D, V] or int4-packed [D/2, V] uint8 with group scales. Streams the
    head by vocab tile with a running per-row
    argmax — no [M, V] f32 logits materialize anywhere. Returns [M]
    int32; with ``return_val`` additionally the winning logit value
    [M] f32 (the tensor-parallel pass combines per-rank winners with a
    pmax/pmin pair — see parallel/fused_tp.py).
    """
    m, d = x.shape
    int4 = w.dtype == jnp.uint8
    vocab = w.shape[1]
    # Tile sweep note (v5e, 152k vocab): 2048 keeps the int8 panel +
    # its in-register bf16 conversion inside the double-buffer budget;
    # 4096 measured ~2x slower end-to-end (VMEM pressure serializes the
    # stream). The head and its scales go in as stored: a vocab that is
    # no multiple of the tile ends in a ragged block whose lanes past
    # ``vocab`` hold whatever the buffer held, and the kernel's
    # ``col < vocab`` select drops them (a column of the product reads
    # only its own column of the tile and of the scales).
    # At a wider D the tile shrinks with it (``_HEAD_STEP_BYTES``: 512
    # columns at D = 5120).
    bv = max(min(2048, _HEAD_STEP_BYTES // d // _LANE * _LANE), _LANE)
    bv = min(bv, pl.cdiv(vocab, 128) * 128)
    nv = pl.cdiv(vocab, bv)
    kernel = functools.partial(
        _head_kernel, nv=nv, bv=bv, vocab=vocab, eps=eps
    )
    wrows = d // 2 if int4 else d
    srows = s.shape[0] if int4 else 1
    out, val = pl.pallas_call(
        kernel,
        grid=(nv,),
        in_specs=[
            pl.BlockSpec((m, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((wrows, bv), lambda i: (0, i)),
            pl.BlockSpec((srows, bv), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((m, 1), lambda i: (0, 0)),
            pl.BlockSpec((m, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, 1), jnp.int32),
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((m, 1), jnp.float32),
            pltpu.VMEM((m, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=_interpret(),
    )(x, norm_w.reshape(1, d), w, s)
    if return_val:
        return out[:, 0], val[:, 0]
    return out[:, 0]


# ---------------------------------------------------------------------------
# rope row prep (shared by the fused step)
# ---------------------------------------------------------------------------


def freeze_inactive(positions, block_tables, active):
    """Mask-adjusted operands for one paged decode tick: inactive rows
    pin to position 0 and get an all-zero block-table row, so their KV
    writes land in the reserved null page and their attention sweep
    degenerates to one harmless row — the same discipline the paged
    engine applies between steps, made reusable INSIDE a scan body so a
    multi-step window can freeze a stream the very tick it finishes.
    positions [B] i32, block_tables [B, P] i32, active [B] bool."""
    a = active.astype(jnp.int32)
    return jnp.where(active, positions, 0), block_tables * a[:, None]


def rope_rows_at(cos_table, sin_table, positions):
    """Per-row rope rows at INDEPENDENT positions [B] (the batched
    decode shape — each sequence sits at its own position). Returns two
    [B, hd] f32 arrays in the kernel's full-width layout."""
    cos = jnp.take(cos_table, positions, axis=0)
    sin = jnp.take(sin_table, positions, axis=0)
    return (
        jnp.concatenate([cos, cos], axis=-1).astype(jnp.float32),
        jnp.concatenate([-sin, sin], axis=-1).astype(jnp.float32),
    )


def rope_rows(cos_table, sin_table, position, length: int = 1):
    """Gather ``length`` rope rows starting at ``position`` and expand
    to the kernel's full-width layout: cos_full = [cos, cos],
    sin_signed = [-sin, sin] (see _rotate). Tables: [S, hd/2]. Returns
    two [length, hd] f32 arrays."""
    cos = jax.lax.dynamic_slice_in_dim(cos_table, position, length, 0)
    sin = jax.lax.dynamic_slice_in_dim(sin_table, position, length, 0)
    return (
        jnp.concatenate([cos, cos], axis=-1).astype(jnp.float32),
        jnp.concatenate([-sin, sin], axis=-1).astype(jnp.float32),
    )
