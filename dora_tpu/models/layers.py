"""Shared pure-JAX transformer building blocks.

Design: parameters are nested dicts of arrays (full sharding control, no
framework indirection); compute in bfloat16 on accelerators (MXU-native),
accumulate norms/softmax in float32; tensor-parallel layouts follow the
Megatron pattern (qkv/up column-split, out/down row-split) so each block
needs exactly one psum pair, inserted by XLA from sharding annotations.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from dora_tpu import backend


def compute_dtype():
    return backend.compute_dtype()


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32):
    scale = 1.0 / math.sqrt(d_in)
    return jax.random.uniform(key, (d_in, d_out), dtype, -scale, scale)


def embed_init(key, vocab: int, dim: int, dtype=jnp.float32):
    return jax.random.normal(key, (vocab, dim), dtype) * 0.02


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------


def rms_norm(x, weight, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * weight).astype(dtype)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    return (x * weight + bias).astype(dtype)


def _norm(x, params, prefix: str, kind: str, eps: float):
    """Apply the block's pre-norm: RMSNorm (weight only) or LayerNorm
    (weight + ``<prefix>_b`` bias) — the two conventions pretrained
    checkpoints use (Qwen2/Llama vs ViT/Whisper)."""
    if kind == "ln":
        return layer_norm(x, params[prefix], params[prefix + "_b"], eps)
    return rms_norm(x, params[prefix], eps)


def matmul(x, w):
    """``x @ w`` where ``w`` is a float array or a quantized dict
    ({"int8", "scale"[, "bf16"]} — ops.int8_matmul — or
    {"int4", "gscale"[, "bf16"]} — ops.int4). Matvec-shaped int8 calls
    (decode) run the Pallas dequant-at-MXU-edge kernel so HBM reads the
    int8 bytes only; larger-M calls (prefill/training, MXU-bound)
    prefer the bf16 sidecar when the quantizer kept one. Int4 decode
    normally rides the fused kernel tier (ops.decode_block); this
    path dequantizes on the fly for any call that lands here."""
    if isinstance(w, dict):
        m = math.prod(x.shape[:-1])
        if m > 32 and "bf16" in w:
            return x @ w["bf16"].astype(x.dtype)
        if "int4" in w:
            from dora_tpu.ops.int4 import dequantize_int4

            return x @ dequantize_int4(w, x.dtype)
        if backend.partitioned_by_xla():
            # Inside a program XLA partitions over a mesh the Pallas
            # kernel is not an option (it cannot be partitioned
            # automatically): the same int8 weights through plain XLA
            # (per-output-channel scales commute with the matmul).
            return (x @ w["int8"].astype(x.dtype)) * w["scale"].astype(x.dtype)
        from dora_tpu.ops.int8_matmul import int8_matmul

        return int8_matmul(x, w["int8"], w["scale"])
    return x @ w.astype(x.dtype)


def dense(x, params, w: str, b: str):
    """x @ params[w] (+ params[b] when the checkpoint has the bias)."""
    out = matmul(x, params[w])
    bias = params.get(b)
    if bias is not None:
        out = out + bias.astype(x.dtype)
    return out


def rope_table(max_len: int, head_dim: int, base: float = 10000.0):
    """(cos, sin) tables [max_len, head_dim/2] in float32."""
    inv_freq = 1.0 / base ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    )
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs), jnp.sin(freqs)


def yarn_inv_freq(head_dim: int, base: float, factor: float,
                  original_max: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0):
    """YaRN's blended inverse frequencies [head_dim/2] (numpy float64;
    HF ``DeepseekV3YarnRotaryEmbedding``): pair ``i`` keeps its plain
    frequency ``base^(-2i/d)`` where it turns more than ``beta_fast``
    times over the original context, is divided by ``factor`` where it
    turns fewer than ``beta_slow`` times, and is blended linearly over
    the pairs between the two (the ramp's ends are ``floor``/``ceil``
    of the correction dimensions, clipped to the pair range)."""
    import numpy as np

    def correction_dim(rotations: float) -> float:
        return (
            head_dim * math.log(original_max / (rotations * 2 * math.pi))
        ) / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001  # HF: no division by zero
    exponent = np.arange(0, head_dim, 2, dtype=np.float64) / head_dim
    plain = 1.0 / base ** exponent
    ramp = np.clip(
        (np.arange(head_dim // 2, dtype=np.float64) - low) / (high - low),
        0.0, 1.0,
    )
    return plain / factor * ramp + plain * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature term: ``0.1 * mscale * ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_rope_table(max_len: int, head_dim: int, base: float, factor: float,
                    original_max: int, beta_fast: float = 32.0,
                    beta_slow: float = 1.0, mscale: float = 1.0,
                    mscale_all_dim: float = 0.0):
    """(cos, sin) tables [max_len, head_dim/2] in float32 under YaRN
    scaling, beside :func:`rope_table`. Both are multiplied by
    ``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``
    as HF does (1 where the two are equal, as Kimi-K2 has them)."""
    inv_freq = jnp.asarray(
        yarn_inv_freq(head_dim, base, factor, original_max, beta_fast,
                      beta_slow),
        jnp.float32,
    )
    ratio = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)
    freqs = jnp.outer(jnp.arange(max_len, dtype=jnp.float32), inv_freq)
    return jnp.cos(freqs) * ratio, jnp.sin(freqs) * ratio


def apply_rope(x, cos, sin, positions):
    """x: [B, H, T, D]; positions: [B, T] absolute token positions."""
    return apply_rope_tables(x, cos[positions], sin[positions])


def apply_rope_tables(x, cos, sin):
    """Rotary with per-token half-dim tables ([T, D/2] or [B, T, D/2]),
    NeoX split convention. x: [B, H, T, D]."""
    if cos.ndim == 2:
        cos = cos[None]
        sin = sin[None]
    cos = cos[:, None].astype(jnp.float32)  # [B,1,T,D/2]
    sin = sin[:, None].astype(jnp.float32)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def attention(q, k, v, mask=None):
    """Dense attention, [B,H,T,D]; softmax in float32."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(q.shape[-1])
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def attend_blocks(q, fetch, visible, n_blocks, score, mix, *, scale: float,
                  width: int):
    """Running-softmax attention of queries ``q [..., dims]`` over cached
    rows fetched a block at a time, in plain XLA (the paged attention of
    the models whose cache the fused kernels do not read): ``fetch(j)``
    gives block ``j`` of the cache (whatever the model keeps there),
    ``visible(j)`` which of its rows each query may see (broadcastable
    to the scores), ``score(q, block)`` the float32 scores ``[..., T]``
    and ``mix(p, block)`` the float32 sum of the block's values under
    probabilities ``p``, ``[..., width]``. Returns ``[..., width]``
    float32. ``n_blocks`` is traced: work follows the longest live
    context."""
    lead = q.shape[:-1]
    neg = jnp.float32(-1e30)

    def body(j, carry):
        m, l, acc = carry
        kv, seen = fetch(j), visible(j)
        s = jnp.where(seen, score(q, kv) * scale, neg)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(seen, jnp.exp(s - m_new[..., None]), 0.0)
        acc = acc * alpha[..., None] + mix(p, kv)
        return m_new, l * alpha + p.sum(-1), acc

    m0 = jnp.full(lead, neg, jnp.float32)
    l0 = jnp.zeros(lead, jnp.float32)
    a0 = jnp.zeros((*lead, width), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, a0))
    return acc / l[..., None]


def rotate_half(x, cos, sin):
    """Rotate-half rotary over the whole head: ``x [..., hd]``, ``cos``
    / ``sin`` ``[..., hd/2]`` broadcastable to the halves."""
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def kv_rows(cfg, k, v):
    """Keys and values ``[N, KV, hd]`` -> the rows as a ``[P, page, 2 * KV
    * hd]`` pool caches them: a position's keys, then its values
    (``cfg.kv_width`` = KV * hd)."""
    n = k.shape[0]
    return jnp.concatenate(
        [k.reshape(n, cfg.kv_width), v.reshape(n, cfg.kv_width)], axis=-1)


def attend_kv_blocks(cfg, q, kv_of, visible, n_blocks, score: str, mix: str):
    """:func:`attend_blocks` of ``q [..., KV, G, hd]`` over pool rows:
    ``kv_of(j)`` gives block ``j``'s keys and values (``[..., block, KV,
    hd]`` each); ``score`` and ``mix`` are the einsums of queries with
    keys and of probabilities with values. Returns ``[..., KV, G, hd]``
    float32."""
    f32 = {"preferred_element_type": jnp.float32}
    return attend_blocks(
        q, kv_of, visible, n_blocks,
        lambda q, kv: jnp.einsum(score, q, kv[0], **f32),
        lambda p, kv: jnp.einsum(mix, p.astype(kv[1].dtype), kv[1], **f32),
        scale=cfg.head_dim ** -0.5, width=cfg.head_dim,
    )


def attend_latent_blocks(cfg, q, rows_of, visible, n_blocks, score: str,
                         mix: str):
    """:func:`attend_blocks` of absorbed MLA queries ``q [..., row]`` over
    latent rows: ``rows_of(j)`` gives block ``j``'s rows (``[..., block,
    row]``, the first ``cfg.kv_rank`` of each its ``c_kv``); ``score``
    and ``mix`` are the einsums of queries with rows and of probabilities
    with rows. Returns the softmax-weighted ``c_kv`` ``[..., kv_rank]``
    in float32, under ``cfg.softmax_scale``."""
    f32 = {"preferred_element_type": jnp.float32}
    return attend_blocks(
        q, rows_of, visible, n_blocks,
        lambda q, kv: jnp.einsum(score, q, kv, **f32),
        lambda p, kv: jnp.einsum(
            mix, p.astype(kv.dtype), kv[..., : cfg.kv_rank], **f32),
        scale=cfg.softmax_scale, width=cfg.kv_rank,
    )


def mla_kv_b_weights(kvb: dict, cfg) -> dict:
    """``W_kvb`` (int8 ``[kv_rank, H * (nope + v)]`` with per-column
    scales) per head, split for the absorbed form: the key part ``"k8"
    [H, nope, kv_rank]`` folds into the query, the value part ``"v8" [H,
    kv_rank, v]`` into the output (:func:`mla_output`); the scales
    ``"ks"`` / ``"vs"`` are per (head, column)."""
    h, nope = cfg.heads, cfg.nope
    kvb8 = kvb["int8"].reshape(cfg.kv_rank, h, nope + cfg.v_dim)
    kvbs = kvb["scale"].reshape(h, nope + cfg.v_dim)
    return {
        "k8": jnp.transpose(kvb8[:, :, :nope], (1, 2, 0)),
        "ks": kvbs[:, :nope],
        "v8": jnp.transpose(kvb8[:, :, nope:], (1, 0, 2)),
        "vs": kvbs[:, nope:],
    }


def mla_output(blk, cfg, ctx):
    """``ctx [N, H, kv_rank]`` (softmax-weighted latent rows, float32)
    -> an absorbed MLA sublayer's output ``[N, dim]``: the value half of
    ``W_kvb`` per head (``blk["w_kv_b"]``: int8 ``"v8" [H, kv_rank, v]``,
    scales ``"vs"``), then ``blk["wo"]``."""
    kb = blk["w_kv_b"]
    dtype = compute_dtype()
    o = jnp.einsum(
        "nhc,hcj->nhj", ctx.astype(dtype), kb["v8"].astype(dtype),
        preferred_element_type=jnp.float32,
    ) * kb["vs"]
    return matmul(
        o.astype(dtype).reshape(ctx.shape[0], cfg.heads * cfg.v_dim),
        blk["wo"],
    )


def use_flash() -> bool:
    """Flash attention for the no-cache self-attention paths (see
    dora_tpu.ops.flash_attention). Default ON on TPU (the kernel's VMEM
    use is flat in T, so it is safe at any length); elsewhere the Pallas
    interpreter would be slower than dense, so default OFF. Override
    either way with DORA_FLASH_ATTENTION=1/0. Never inside a program
    XLA partitions over a mesh: the kernel cannot be partitioned
    automatically, and the sharded paths (DORA_MESH) take dense
    attention, which XLA shards by heads."""
    import os

    if backend.partitioned_by_xla():
        return False
    v = os.environ.get("DORA_FLASH_ATTENTION")
    if v is not None:
        return v not in ("", "0")
    return backend.on_tpu()


def causal_mask(tq: int, tk: int, offset: int = 0):
    """[1,1,tq,tk] boolean mask; offset = number of cached tokens before q."""
    qi = jnp.arange(tq)[:, None] + offset
    ki = jnp.arange(tk)[None, :]
    return (qi >= ki)[None, None, :, :]


# ---------------------------------------------------------------------------
# transformer block (pre-norm, SwiGLU)
# ---------------------------------------------------------------------------


def init_block(key, dim: int, n_heads: int, ffn_dim: int, n_kv_heads: int | None = None):
    n_kv_heads = n_kv_heads or n_heads
    head_dim = dim // n_heads
    keys = jax.random.split(key, 7)
    return {
        "attn_norm": jnp.ones((dim,), jnp.float32),
        "wq": dense_init(keys[0], dim, n_heads * head_dim),
        "wk": dense_init(keys[1], dim, n_kv_heads * head_dim),
        "wv": dense_init(keys[2], dim, n_kv_heads * head_dim),
        "wo": dense_init(keys[3], n_heads * head_dim, dim),
        "ffn_norm": jnp.ones((dim,), jnp.float32),
        "w_gate": dense_init(keys[4], dim, ffn_dim),
        "w_up": dense_init(keys[5], dim, ffn_dim),
        "w_down": dense_init(keys[6], ffn_dim, dim),
    }


def block_forward(
    params: dict,
    x,
    n_heads: int,
    *,
    n_kv_heads: int | None = None,
    rope: tuple | None = None,
    positions=None,
    rope_tables: tuple | None = None,
    mask=None,
    cache: dict | None = None,
    cache_index=None,
    mesh=None,
    ring_axis: str | None = None,
    norm: str = "rms",
    mlp: str = "swiglu",
    norm_eps: float = 1e-6,
    head_dim: int | None = None,
    flash: str | None = None,
    sp_impl: str | None = None,
):
    """One pre-norm block. Returns (y, new_cache).

    With ``cache`` (decode): k/v are written at ``cache_index`` and attention
    runs against the full cache. With ``ring_axis``: attention runs as ring
    attention over that mesh axis (training/prefill long-context path).

    ``norm`` ("rms" | "ln"), ``mlp`` ("swiglu" | "gelu") and the optional
    projection biases (``bq``/``bk``/``bv``/``bo``/``b_up``/``b_down``/
    ``b_gate`` keys, applied when present) select between the layouts
    pretrained checkpoints use: Qwen2/Llama = rms+swiglu (+qkv bias for
    Qwen2), ViT/Whisper = ln+gelu with full biases.
    """
    x, new_cache = attention_sublayer(
        params, x, n_heads, n_kv_heads=n_kv_heads, rope=rope,
        positions=positions, rope_tables=rope_tables, mask=mask, cache=cache,
        cache_index=cache_index, mesh=mesh, ring_axis=ring_axis, norm=norm,
        norm_eps=norm_eps, head_dim=head_dim, flash=flash, sp_impl=sp_impl,
    )
    x = mlp_sublayer(params, x, norm=norm, mlp=mlp, norm_eps=norm_eps)
    return x, new_cache


def attention_sublayer(
    params, x, n_heads, *, n_kv_heads=None, rope=None, positions=None,
    rope_tables=None, mask=None, cache=None, cache_index=None, mesh=None,
    ring_axis=None, norm="rms", norm_eps=1e-6, head_dim=None, flash=None,
    sp_impl=None,
):
    """Pre-norm self-attention with residual. Returns (y, new_cache).

    Rotary comes either as ``rope=(cos, sin)`` position-indexed tables (+
    ``positions``), or as ``rope_tables=(cos, sin)`` per-token tables
    ([B, T, D/2] — the M-RoPE / 2-D vision case).

    ``flash`` ("causal" | "full") routes the no-cache path through the
    Pallas block-streamed kernel instead of dense+``mask`` — only valid
    when the mask the caller would pass is exactly that pattern.
    """
    b, t, dim = x.shape
    n_kv = n_kv_heads or n_heads
    head_dim = head_dim or dim // n_heads
    dtype = x.dtype

    h = _norm(x, params, "attn_norm", norm, norm_eps)
    if "wqkv" in params:
        # Decode-fused projection (ops.int8_matmul.quantize_tree fuses
        # q/k/v into one weight sweep): one kernel call, then split.
        qkv = dense(h, params, "wqkv", "bqkv")
        q, k, v = jnp.split(
            qkv,
            [n_heads * head_dim, (n_heads + n_kv) * head_dim],
            axis=-1,
        )
    else:
        q = dense(h, params, "wq", "bq")
        k = dense(h, params, "wk", "bk")
        v = dense(h, params, "wv", "bv")
    q = q.reshape(b, t, n_heads, head_dim)
    k = k.reshape(b, t, n_kv, head_dim)
    v = v.reshape(b, t, n_kv, head_dim)
    q, k, v = (z.transpose(0, 2, 1, 3) for z in (q, k, v))  # [B,H,T,D]

    if rope is not None:
        cos, sin = rope
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(t), (b, t))
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
    elif rope_tables is not None:
        cos, sin = rope_tables
        q = apply_rope_tables(q, cos, sin)
        k = apply_rope_tables(k, cos, sin)

    new_cache = None
    if cache is not None:
        k = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, cache_index, 0)
        )
        v = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, cache_index, 0)
        )
        new_cache = {"k": k, "v": v}

    if n_kv != n_heads:  # grouped-query: repeat kv heads
        rep = n_heads // n_kv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)

    if ring_axis is not None and mesh is not None:
        causal = mask is not None
        if sp_impl == "ulysses":
            from dora_tpu.parallel.ulysses import ulysses_attention

            out = ulysses_attention(q, k, v, mesh, causal=causal, axis=ring_axis)
        elif sp_impl in (None, "ring"):
            from dora_tpu.parallel.ring import ring_attention

            out = ring_attention(q, k, v, mesh, causal=causal, axis=ring_axis)
        else:
            raise ValueError(f"unknown sp_impl {sp_impl!r} (ring | ulysses)")
    elif flash is not None and cache is None:
        from dora_tpu.ops import flash_attention

        out = flash_attention(
            q, k.astype(dtype), v.astype(dtype), causal=flash == "causal"
        )
    else:
        out = attention(q, k.astype(dtype), v.astype(dtype), mask)

    out = out.transpose(0, 2, 1, 3).reshape(b, t, n_heads * head_dim)
    return x + dense(out, params, "wo", "bo"), new_cache


def mlp_sublayer(params, x, *, norm="rms", mlp="swiglu", norm_eps=1e-6):
    """Pre-norm feed-forward with residual."""
    h = _norm(x, params, "ffn_norm", norm, norm_eps)
    if mlp == "gelu":
        up = jax.nn.gelu(dense(h, params, "w_up", "b_up"), approximate=False)
        return x + dense(up, params, "w_down", "b_down")
    if "w_gateup" in params:  # decode-fused (see quantize_tree)
        fused = dense(h, params, "w_gateup", "b_gateup")
        gate, up = jnp.split(fused, 2, axis=-1)
        gate = jax.nn.silu(gate)
    else:
        gate = jax.nn.silu(dense(h, params, "w_gate", "b_gate"))
        up = dense(h, params, "w_up", "b_up")
    return x + dense(gate * up, params, "w_down", "b_down")


#: Tensor-parallel sharding rules for block parameters (Megatron layout):
#: column-parallel for q/k/v/gate/up, row-parallel for o/down. Names are
#: exact leaf names (see parallel.mesh.shard_params) — cross-attention
#: projections get their own entries, and position tables / norms fall to
#: the replicated default.
def tp_rules():
    from jax.sharding import PartitionSpec as P

    return [
        ("wq", P(None, "tp")),
        ("wk", P(None, "tp")),
        ("wv", P(None, "tp")),
        ("wo", P("tp", None)),
        ("x_wq", P(None, "tp")),
        ("x_wk", P(None, "tp")),
        ("x_wv", P(None, "tp")),
        ("x_wo", P("tp", None)),
        ("w_gate", P(None, "tp")),
        ("w_up", P(None, "tp")),
        ("w_down", P("tp", None)),
        ("embed", P("tp", None)),
        ("lm_head", P(None, "tp")),
        ("patch_proj", P(None, "tp")),
    ]
