"""Flash attention (online softmax) as a Pallas TPU kernel.

Dense attention (`dora_tpu.models.layers.attention`) materializes the
[B, H, T, T] float32 score tensor in HBM. The round-2 kernel streamed
q-blocks but still held full [T, D] K/V tiles and a [BQ, T] score row in
VMEM — VMEM-linear in T, overflowing somewhere past T≈8k. This version
is true flash attention: K/V are streamed through VMEM one [BK, D]
block at a time along an inner (sequential) grid dimension, and the
softmax is computed online — a running row-max ``m``, running
denominator ``l``, and an [BQ, D] accumulator live in VMEM scratch
across the K sweep. VMEM use is flat in T, so T=16k and beyond compile
and run with the same footprint as T=2k.

Scope: the no-KV-cache paths — training loss, VLM prefill, the ViT
tower (non-causal). Decode attends against a cache one token at a time
and has no score-matrix problem. This is the default attention path on
TPU (see ``models.layers.use_flash``); DORA_FLASH_ATTENTION=0 opts out.

Causal runs skip fully-masked K blocks (above the diagonal) entirely —
half the FLOPs of the non-causal sweep at large T.

Unaligned shapes are handled by padding T up to the 128-row block and D
up to the 128-lane tile (zero-padded D contributes nothing to scores or
outputs; padded key rows are masked to -inf before softmax), so the
bench_2b ViT (head_dim 80, 256 patches + cls rows) works unchanged.

On non-TPU backends the kernel runs through the Pallas interpreter —
tests assert numeric parity with the dense reference on CPU.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dora_tpu.backend import interpret as _interpret

BLOCK_Q = 128
BLOCK_K = 256
LANE = 128

_NEG_INF = float(jnp.finfo(jnp.float32).min)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  t_real: int, nk: int, causal: bool, scale: float):
    """One (batch*head, q-block, k-block) program step.

    Block shapes: q [1, BQ, D], k/v [1, BK, D], o [1, BQ, D]. Scratch
    (persistent across the sequential k dimension): m/l [BQ, LANE] f32,
    acc [BQ, D] f32.
    """
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Causal: K blocks strictly above the diagonal contribute nothing.
    # (q row r attends to k cols <= qi*BQ + r; the block is live iff its
    # first col <= the q-block's last row.)
    live = (ki * BLOCK_K <= qi * BLOCK_Q + BLOCK_Q - 1) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)  # [BQ, D]
        k = k_ref[0].astype(jnp.float32)  # [BK, D]
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [BQ, BK]

        col = ki * BLOCK_K + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1
        )
        valid = col < t_real
        if causal:
            row = qi * BLOCK_Q + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 0
            )
            valid = valid & (col <= row)
        scores = jnp.where(valid, scores, _NEG_INF)

        m_prev = m_ref[:, :1]  # [BQ, 1]
        m_cur = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # alpha rescales the running state; exp(-inf - -inf) is guarded by
        # m_new >= m_prev and the first-block init (m_prev = min-float, and
        # min-float - min-float = 0 -> alpha = 1 with l = 0, harmless).
        alpha = jnp.exp(m_prev - m_new)  # [BQ, 1]
        p = jnp.exp(scores - m_new)  # [BQ, BK]
        p = jnp.where(valid, p, 0.0)

        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)  # [BK, D]
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        # Fully-masked rows (t padding) have l = 0: emit 0, not NaN.
        l = l_ref[:, :1]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(jax.jit, static_argnames=("causal",))
def flash_attention(q, k, v, causal: bool = False):
    """Attention over [B, H, T, D]; VMEM footprint independent of T.

    Drop-in for ``layers.attention(q, k, v, causal_mask(T, T))`` /
    ``layers.attention(q, k, v, None)`` (self-attention, same q/k
    length). Softmax in float32; returns q.dtype.
    """
    b, h, t, d = q.shape
    assert k.shape == v.shape == (b, h, t, d), (q.shape, k.shape)
    scale = 1.0 / math.sqrt(d)

    t_pad = _round_up(t, max(BLOCK_Q, BLOCK_K))
    d_pad = _round_up(d, LANE)
    if (t_pad, d_pad) != (t, d):
        pad = ((0, 0), (0, 0), (0, t_pad - t), (0, d_pad - d))
        q, k, v = (jnp.pad(x, pad) for x in (q, k, v))

    bh = b * h
    q, k, v = (x.reshape(bh, t_pad, d_pad) for x in (q, k, v))
    nq = t_pad // BLOCK_Q
    nk = t_pad // BLOCK_K

    kernel = functools.partial(
        _flash_kernel, t_real=t, nk=nk, causal=causal, scale=scale
    )
    out = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, BLOCK_Q, d_pad), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, BLOCK_K, d_pad), lambda i, j, kk: (i, kk, 0)),
            pl.BlockSpec((1, BLOCK_K, d_pad), lambda i, j, kk: (i, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_Q, d_pad), lambda i, j, kk: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t_pad, d_pad), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((BLOCK_Q, LANE), jnp.float32),   # running max m
            pltpu.VMEM((BLOCK_Q, LANE), jnp.float32),   # running denom l
            pltpu.VMEM((BLOCK_Q, d_pad), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
    )(q, k, v)

    out = out.reshape(b, h, t_pad, d_pad)
    return out[:, :, :t, :d]
