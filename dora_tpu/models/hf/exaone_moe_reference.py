"""The plain reference of the K-EXAONE block: float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``, the whole sequence at once
under dense masks (a band for the sliding layers, the causal triangle
for the global ones), a Python loop over the experts. No cache, no ring,
no paging, no batching, no kernels. The one departure from the uncut
model is the argument ``held``: the experts whose part of the routed sum
is computed (``None`` = all of them). The weights of the routed sum are
normalised over every chosen expert either way, so the parts that
disjoint shares give add up to the whole.

The published ``config.json`` does not settle four things (the † lines
of ``exaone_moe.py``'s docstring; ``KNOWN_ISSUES.md`` "PR 41"). Each is a
switch HERE AND ONLY HERE, at the program's choice by default, so that a
test can show that the program's choice and no other matches it:

* ``post_norm``: EXAONE-4's placement, ``x = x + RMSNorm(Attn(x))`` and
  ``x = x + RMSNorm(MLP(x))``, in place of the pre-norm;
* ``qkv_bias``: add ``blk["qkv_bias"]`` (a vector the caller supplies)
  to the projections;
* ``qk_norm``: off = no RMSNorm of q and k over the head;
* ``rope_on_global``: rotary on the global layers too.

``full_everywhere`` is a control, not a † line: no band mask, every
layer attends causally over everything (what a program that ignored
``sliding_window`` would compute).

It is given the serving parameters' own int8 weights, dequantized
(:func:`reference_params`), so a comparison with the serving path
measures its activations, its caches and its arithmetic, not the
quantization.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dora_tpu.models.hf import exaone_moe as E
from dora_tpu.models.moe import unstack_experts
from dora_tpu.ops.int8_matmul import dequantize

SWITCHES = ("post_norm", "qkv_bias", "qk_norm", "rope_on_global",
            "full_everywhere")
#: the program's choice of each
AS_SERVED = {"post_norm": False, "qkv_bias": False, "qk_norm": True,
             "rope_on_global": False, "full_everywhere": False}


def reference_params(params, cfg: E.ExaoneMoeConfig) -> dict:
    """Serving parameters (:func:`exaone_moe.load`) -> float32 matrices
    in ``[in, out]`` layout."""
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
        r = {
            "attn_norm": blk["attn_norm"].astype(f32),
            "qkv": dequantize(blk["wqkv"]),
            "q_norm": blk["q_norm"].astype(f32),
            "k_norm": blk["k_norm"].astype(f32),
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
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(w, x):
    return (jax.nn.silu(x @ w["gate"]) * (x @ w["up"])) @ w["down"]


def attention(r, cfg: E.ExaoneMoeConfig, x, sliding: bool, sw: dict):
    """``x [T, dim]`` (normed, or raw under ``post_norm``) -> (the
    sublayer's output [T, dim], keys [T, KV, hd], values [T, KV, hd])."""
    t = x.shape[0]
    h, kv, hd = cfg.heads, cfg.kv_heads, cfg.head_dim
    p = x @ r["qkv"]
    if sw["qkv_bias"]:
        p = p + r["qkv_bias"]
    q = p[:, : cfg.q_width].reshape(t, h, hd)
    k = p[:, cfg.q_width : cfg.q_width + cfg.kv_width].reshape(t, kv, hd)
    v = p[:, cfg.q_width + cfg.kv_width :].reshape(t, kv, hd)
    if sw["qk_norm"]:
        q = rms_norm(q, r["q_norm"], cfg.norm_eps)
        k = rms_norm(k, r["k_norm"], cfg.norm_eps)
    if sliding or sw["rope_on_global"]:
        inv = 1.0 / cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
        cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
        q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    back = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]  # i - j
    seen = back >= 0
    if sliding and not sw["full_everywhere"]:
        seen = seen & (back < cfg.window)
    kr, vr = (jnp.repeat(a, h // kv, axis=1) for a in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, kr) / hd ** 0.5
    pr = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    ctx = jnp.einsum("hqk,khd->qhd", pr, vr).reshape(t, h * hd)
    return ctx @ r["o"], k, v


def route(r, cfg: E.ExaoneMoeConfig, x):
    """Chosen experts [T, k] and their weights [T, k]: the bias enters
    the choice only; the weights are the unbiased scores, normalised
    over the chosen, times ``routed_scaling_factor``."""
    scores = jax.nn.sigmoid(x @ r["router"])
    _, ids = jax.lax.top_k(scores + r["router_bias"], cfg.top_k)
    w = jnp.take_along_axis(scores, ids, -1)
    if cfg.norm_topk:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return ids, w * cfg.routed_scale


def moe(r, cfg: E.ExaoneMoeConfig, x, held=None, shared: bool = True):
    """The expert layer on rows ``x [T, dim]``: the routed sum over
    ``chosen ∩ held`` (every expert in ``r["experts"]`` when ``held`` is
    None) and, with ``shared``, the shared expert."""
    ids, w = route(r, cfg, x)
    y = jnp.zeros_like(x)
    for e in (r["experts"] if held is None else held):
        w_e = (w * (ids == e)).sum(-1)  # 0 where e was not chosen
        y = y + swiglu(r["experts"][e], x) * w_e[:, None]
    if shared and "shared" in r:
        y = y + swiglu(r["shared"], x)
    return y


def forward(rparams, cfg: E.ExaoneMoeConfig, tokens, held=None, rows=False,
            **switches):
    """Logits ``[T, vocab]`` of the whole sequence ``tokens [T]``; with
    ``rows`` also every layer's ``(keys, values)``, each ``[T, KV, hd]``
    (roped where the layer ropes them: what the program caches)."""
    unknown = set(switches) - set(SWITCHES)
    if unknown:
        raise TypeError(f"unknown switches {sorted(unknown)}")
    sw = {**AS_SERVED, **switches}
    kept = []
    with jax.default_matmul_precision("highest"):
        x = rparams["embed"][tokens]
        for i in range(cfg.layers):
            r = rparams["blocks"][str(i)]

            def mlp(h, r=r):
                return swiglu(r["dense"], h) if "dense" in r else moe(
                    r, cfg, h, held)

            if sw["post_norm"]:
                a, k, v = attention(r, cfg, x, cfg.sliding[i], sw)
                x = x + rms_norm(a, r["attn_norm"], cfg.norm_eps)
                x = x + rms_norm(mlp(x), r["ffn_norm"], cfg.norm_eps)
            else:
                a, k, v = attention(
                    r, cfg, rms_norm(x, r["attn_norm"], cfg.norm_eps),
                    cfg.sliding[i], sw)
                x = x + a
                x = x + mlp(rms_norm(x, r["ffn_norm"], cfg.norm_eps))
            kept.append((k, v))
        x = rms_norm(x, rparams["out_norm"], cfg.norm_eps)
        logits = x @ rparams["lm_head"]
    return (logits, kept) if rows else logits
