"""Int4 weight quantization for the fused decode tier.

Batch-1 decode is HBM-bandwidth-bound; int8 weights reach 84% of their
own bound (round 4, chip), so the next factor-of-two lives in the
weight bytes themselves. Here weights pack two 4-bit values per byte
with **group-wise scales** (one f32 scale per 128 input rows per output
column — per-channel scales are too coarse at 4 bits to serve real
checkpoints).

Packing layout (kernel-friendly): nibbles pair WITHIN each scale group
— for group g of G rows, the packed block's byte ``[j, n]`` holds
``q[g*G + j, n]`` (low nibble) and ``q[g*G + G/2 + j, n]`` (high
nibble). Unpacking a group block therefore yields its two contiguous
half-planes, the grouped matmul consumes them directly, and — the
load-bearing property — any K-tile that is a whole number of groups
(the ffn down sweep, the vocab-tiled head) maps to a contiguous packed
row range. Values are stored biased (q+8 in [0, 15]); group scales
fold in on the f32 accumulator per group.

Reference parity: none — the reference serves torch/CUDA fp16. This is
the beat-on-perf axis (ops/decode_block.py consumes these weights when
DORA_INT4_DECODE=1).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: Preferred input rows per scale group. 128 = one MXU pass per group
#: dot; shapes not divisible by 128 fall back to gcd(K, 128) so tiny
#: test configs quantize too. Kernels derive the actual group size from
#: the scale shape (K // gscale.shape[0]).
GROUP = 128


def group_for(k: int) -> int:
    import math

    return math.gcd(k, GROUP)


def quantize_int4(w, keep_bf16: bool = False) -> dict:
    """[K, N] float -> {"int4": [K/2, N] uint8, "gscale": [K/G, N] f32}.

    Symmetric per-(group, column): q = round(w / s) in [-8, 7],
    s = max|w_group| / 7. K must be even and a multiple of GROUP.
    ``keep_bf16`` rides the original weight along for the MXU-bound
    large-M paths (prefill), like the int8 sidecar.
    """
    k, n = w.shape
    g = group_for(k)
    assert g % 2 == 0 and k % g == 0, (k, g)
    wf = jnp.asarray(w, jnp.float32)
    groups = wf.reshape(k // g, g, n)
    scale = jnp.max(jnp.abs(groups), axis=1) / 7.0  # [K/G, N]
    scale = jnp.maximum(scale, jnp.float32(1e-12))
    q = jnp.clip(jnp.round(groups / scale[:, None, :]), -8, 7)
    biased = (q + 8).astype(jnp.uint8)  # [K/G, G, N]
    lo = biased[:, : g // 2]
    hi = biased[:, g // 2 :]
    out = {
        "int4": (lo | (hi << 4)).astype(jnp.uint8).reshape(k // 2, n),
        "gscale": scale,
    }
    if keep_bf16:
        out["bf16"] = jnp.asarray(w).astype(jnp.bfloat16)
    return out


def unpack_grouped(packed, n_groups: int, dtype, biased: bool = False):
    """Packed [K/2, N] u8 -> q [n_groups, G, N] in ``dtype``, ready for
    the grouped matmul. Works on any slice that is a whole number of
    groups.

    With ``biased`` the stored q+8 values (0..15) come back as-is — the
    caller folds the bias out of the ACCUMULATOR instead
    (``x @ (q'-8) == x @ q' - 8*sum(x)`` per group), which deletes one
    VPU subtract per nibble from the bandwidth-critical unpack (round-5
    shaving of the KNOWN_ISSUES int4 VPU bound). Otherwise the bias
    subtraction happens in the float compute dtype (exact for
    |q| <= 8): Mosaic does not legalize i8 vector subtraction."""
    k2, n = packed.shape
    half = k2 // n_groups  # G/2 packed rows per group
    blocks = packed.reshape(n_groups, half, n).astype(jnp.int32)
    # Mosaic legalizes neither i8 vector subtraction nor u8->bf16 casts;
    # widen to i32, then cast to the compute dtype.
    if biased:
        lo = (blocks & 0xF).astype(dtype)
        hi = (blocks >> 4).astype(dtype)
    else:
        lo = ((blocks & 0xF) - 8).astype(dtype)
        hi = ((blocks >> 4) - 8).astype(dtype)
    return jnp.concatenate([lo, hi], axis=1)  # [ng, G, N]


def dequantize_int4(wq: dict, dtype=jnp.float32):
    """Reference dequantization (tests + non-kernel paths)."""
    packed = wq["int4"]
    scale = wq["gscale"]  # [K/G, N]
    k2, n = packed.shape
    k = 2 * k2
    q = unpack_grouped(packed, scale.shape[0], jnp.float32)
    deq = q * scale[:, None, :]
    return deq.reshape(k, n).astype(dtype)


def quantize_tree_int4(params, names=None, fuse: bool = True,
                       keep_bf16: bool = True):
    """quantize_tree with the int4 quantizer (shared fusion/recursion
    machinery lives in ops.int8_matmul.quantize_tree)."""
    from dora_tpu.ops.int8_matmul import DECODE_WEIGHTS, quantize_tree

    return quantize_tree(
        params, names if names is not None else DECODE_WEIGHTS,
        keep_bf16, fuse, quantizer=quantize_int4,
    )
