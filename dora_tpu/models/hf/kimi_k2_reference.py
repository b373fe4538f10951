"""The plain reference of the Kimi-K2 / DeepSeek-V3 block: float32
``jax.numpy`` at ``jax.default_matmul_precision("highest")``, EXPANDED
multi-head latent attention (``W_kvb`` applied to every cached latent:
64 heads of keys and values, nothing folded), a Python loop over the
experts, no cache, no paging, no batching, no kernels. It follows HF's
``DeepseekV3`` forward pass; the one departure is the argument ``held``:
the experts whose part of the routed sum is computed (``None`` = all of
them, the uncut model). The weights of the routed sum are normalised
over every chosen expert either way, so the parts that disjoint shares
give add up to the whole.

It is given the serving parameters' own int8 weights, dequantized
(:func:`reference_params`), so a comparison with the serving path
measures its bf16 activations, its cache and its arithmetic, not the
quantization.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dora_tpu.models.hf import kimi_k2 as K
from dora_tpu.models.moe import unstack_experts
from dora_tpu.ops.int8_matmul import dequantize


def reference_params(params, cfg: K.KimiK2Config) -> dict:
    """Serving parameters (:func:`kimi_k2.load`) -> float32 matrices in
    ``[in, out]`` layout under the HF module names' last parts."""
    f32 = jnp.float32
    out = {
        "embed": params["embed"].astype(f32),
        "out_norm": params["out_norm"].astype(f32),
        "lm_head": dequantize(params["lm_head"]),
        "blocks": {},
    }

    def swiglu(w):
        gate, up = jnp.split(dequantize(w["w_gateup"]), 2, axis=1)
        return {"gate": gate, "up": up, "down": dequantize(w["w_down"])}

    for i, blk in params["blocks"].items():
        qkv_a = dequantize(blk["w_qkv_a"])
        kb = blk["w_kv_b"]
        # back to HF's one matrix [kv_rank, H, nope + v]
        k = jnp.transpose(kb["k8"].astype(f32) * kb["ks"][:, :, None], (2, 0, 1))
        v = jnp.transpose(kb["v8"].astype(f32) * kb["vs"][:, None, :], (1, 0, 2))
        r = {
            "attn_norm": blk["attn_norm"].astype(f32),
            "q_a": qkv_a[:, : cfg.q_rank],
            "q_norm": blk["q_norm"].astype(f32),
            "q_b": dequantize(blk["w_q_b"]),
            "kv_a": qkv_a[:, cfg.q_rank : cfg.q_rank + cfg.latent],
            "kv_norm": blk["kv_norm"].astype(f32),
            "kv_b": jnp.concatenate([k, v], axis=-1),
            "o": dequantize(blk["wo"]),
            "ffn_norm": blk["ffn_norm"].astype(f32),
        }
        if "dense" in blk:
            r["dense"] = swiglu(blk["dense"])
        else:
            r["router"] = blk["router"].astype(f32)
            r["router_bias"] = blk["router_bias"].astype(f32)
            if "shared" in blk:
                r["shared"] = swiglu(blk["shared"])
            r["experts"] = {
                cfg.expert_first + e: swiglu(w)
                for e, w in enumerate(unstack_experts(blk["experts"]))
            }
        out["blocks"][i] = r
    return out


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def rotate(x, cos, sin):
    """HF: de-interleave the pairs, then rotate_half."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(w, x):
    return (jax.nn.silu(x @ w["gate"]) * (x @ w["up"])) @ w["down"]


def attention(r, cfg: K.KimiK2Config, x, cos, sin, q_block: int | None = None):
    """Expanded MLA over the whole sequence ``x [T, dim]`` (normed).
    ``q_block`` computes the scores a block of queries at a time (the
    same numbers; the benchmark's long samples need it to fit)."""
    t = x.shape[0]
    h, nope, rope, v = cfg.heads, cfg.nope, cfg.rope, cfg.v_dim
    c_q = rms_norm(x @ r["q_a"], r["q_norm"], cfg.norm_eps)
    q = (c_q @ r["q_b"]).reshape(t, h, nope + rope)
    kv_a = x @ r["kv_a"]
    c_kv = rms_norm(kv_a[:, : cfg.kv_rank], r["kv_norm"], cfg.norm_eps)
    k_pe = rotate(kv_a[:, cfg.kv_rank :], cos, sin)  # one key for all heads
    kv = jnp.einsum("tc,chj->thj", c_kv, r["kv_b"])  # [T, H, nope + v]
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe[:, None], (t, h, rope))], -1
    )
    q = jnp.concatenate(
        [q[..., :nope], rotate(q[..., nope:], cos[:, None], sin[:, None])], -1
    )
    out = []
    step = q_block or t
    for a in range(0, t, step):
        qa = q[a : a + step]
        s = jnp.einsum("qhd,khd->hqk", qa, k) * cfg.softmax_scale
        causal = (a + jnp.arange(qa.shape[0]))[:, None] >= jnp.arange(t)[None]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p, kv[..., nope:]))
    return jnp.concatenate(out).reshape(t, h * v) @ r["o"]


def route(r, cfg: K.KimiK2Config, x):
    """Chosen experts [T, k] and their weights [T, k]: the bias enters
    the choice only; the weights are the unbiased scores, normalised
    over the chosen, times ``routed_scaling_factor``."""
    scores = jax.nn.sigmoid(x @ r["router"])
    _, ids = jax.lax.top_k(scores + r["router_bias"], cfg.top_k)
    w = jnp.take_along_axis(scores, ids, -1)
    if cfg.norm_topk:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return ids, w * cfg.routed_scale


def moe(r, cfg: K.KimiK2Config, x, held=None, shared: bool = True):
    """The expert layer on normed rows ``x [T, dim]``: the routed sum
    over ``chosen ∩ held`` (every expert in ``r["experts"]`` when
    ``held`` is None) and, with ``shared``, the shared expert."""
    ids, w = route(r, cfg, x)
    y = jnp.zeros_like(x)
    for e in (r["experts"] if held is None else held):
        w_e = (w * (ids == e)).sum(-1)  # 0 where e was not chosen
        y = y + swiglu(r["experts"][e], x) * w_e[:, None]
    if shared and "shared" in r:
        y = y + swiglu(r["shared"], x)
    return y


def forward(rparams, cfg: K.KimiK2Config, tokens, held=None,
            q_block: int | None = None):
    """Logits ``[T, vocab]`` of the whole sequence ``tokens [T]``."""
    with jax.default_matmul_precision("highest"):
        t = tokens.shape[0]
        cos_t, sin_t = K.rope_tables(cfg)
        cos, sin = cos_t[:t], sin_t[:t]
        x = rparams["embed"][tokens]
        for i in range(cfg.layers):
            r = rparams["blocks"][str(i)]
            x = x + attention(
                r, cfg, rms_norm(x, r["attn_norm"], cfg.norm_eps), cos, sin,
                q_block,
            )
            h = rms_norm(x, r["ffn_norm"], cfg.norm_eps)
            x = x + (swiglu(r["dense"], h) if "dense" in r
                     else moe(r, cfg, h, held))
        x = rms_norm(x, rparams["out_norm"], cfg.norm_eps)
        return x @ rparams["lm_head"]
