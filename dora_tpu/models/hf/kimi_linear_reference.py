"""The plain reference of the Kimi Linear block: float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``, the whole sequence at once,
the delta rule a token at a time (``lax.scan``), the latent layer as full
(unabsorbed) multi-head attention under a dense causal ``[T, T]`` mask, a
Python loop over the experts. No cache, no state kept between calls, no
paging, no batching, no kernels. The one departure from the uncut model is
the argument ``held``: the experts whose part of the routed sum is
computed (``None`` = all of them). The weights of the routed sum are
normalised over every chosen expert either way, so the parts that disjoint
shares give add up to the whole.

The published ``config.json`` leaves details open (the † lines of
``kimi_linear.py``'s docstring; ``KNOWN_ISSUES.md`` "PR 58"). Those that
are a CHOICE are a switch here and only here, at the program's choice by
default, so that a test can show that the program's choice and no other
matches it:

* ``bounded_gate`` (†2): GLM-5.3-Flash's ``g = -5 sigmoid(exp(A_log) (r +
  dt_bias))`` in place of the published ``-exp(A_log) softplus(r +
  dt_bias)``;
* ``drop_shared_columns`` (†3): ``mla_use_nope`` read as "the
  ``qk_rope_head_dim`` columns go": scores over the ``nope`` columns
  alone, scale ``nope^-0.5``.

Controls, not † lines: ``state_bf16`` (the delta-rule state rounded to
bfloat16 after every token) and ``router_bf16`` (the router's scores
computed from bfloat16 operands): what a program that kept either in the
precision below the stated one would compute.

It is given the serving parameters' own int8 weights, dequantized
(:func:`reference_params`), so a comparison with the serving path measures
its activations, its caches and its arithmetic, not the quantization.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dora_tpu.models.hf import kimi_linear as K
from dora_tpu.models.moe import unstack_experts
from dora_tpu.ops.int8_matmul import dequantize

SWITCHES = ("bounded_gate", "drop_shared_columns", "state_bf16", "router_bf16")
AS_SERVED = dict.fromkeys(SWITCHES, False)
#: GLM-5.3-Flash's ``gate_lower_bound``: what ``bounded_gate`` multiplies
GATE_LOWER = -5.0


def reference_params(params, cfg: K.KimiLinearConfig) -> dict:
    """Serving parameters (:func:`kimi_linear.load`) -> float32 matrices in
    ``[in, out]`` layout, the fused ones taken apart."""
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

    def cut(w, *widths):
        parts, at = [], 0
        for n in widths:
            parts.append(w[:, at : at + n])
            at += n
        return parts

    hk, r = cfg.kda_width, cfg.kda_dim
    for i, blk in params["blocks"].items():
        p = {"attn_norm": blk["attn_norm"].astype(f32),
             "ffn_norm": blk["ffn_norm"].astype(f32)}
        if cfg.linear[int(i)]:
            q, k, v, fa, ga, b = cut(dequantize(blk["w_in"]), hk, hk, hk, r, r,
                                     cfg.kda_heads)
            p.update(
                wq=q, wk=k, wv=v, wfa=fa, wga=ga, wb=b,
                conv=blk["conv_w"].astype(f32),  # [taps, 3 H d_k]
                wfb=dequantize(blk["w_fb"]), wgb=dequantize(blk["w_gb"]),
                a=blk["a"], dt_bias=blk["dt_bias"],
                o_norm=blk["o_norm"].astype(f32), wo=dequantize(blk["wo"]))
        else:
            wq, wc, ws = cut(dequantize(blk["w_in"]),
                             cfg.heads * (cfg.nope + cfg.shared), cfg.kv_rank,
                             cfg.shared)
            kb = blk["w_kv_b"]
            p.update(
                wq=wq, wc=wc, ws=ws, kv_norm=blk["kv_norm"].astype(f32),
                wkb=kb["k8"].astype(f32) * kb["ks"][:, :, None],  # [H, nope, c]
                wvb=kb["v8"].astype(f32) * kb["vs"][:, None, :],  # [H, c, v]
                wo=dequantize(blk["wo"]))
        if "dense" in blk:
            p["dense"] = swiglu(blk["dense"])
        else:
            p["router"] = blk["router"].astype(f32)
            p["router_bias"] = blk["router_bias"].astype(f32)
            if "shared" in blk:
                p["shared"] = swiglu(blk["shared"])
            p["experts"] = {
                cfg.expert_first + e: swiglu(w)
                for e, w in enumerate(unstack_experts(blk["experts"]))
            }
        out["blocks"][i] = p
    return out


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def swiglu(w, x):
    return (jax.nn.silu(x @ w["gate"]) * (x @ w["up"])) @ w["down"]


def kda(p, cfg: K.KimiLinearConfig, x, sw: dict):
    """``x [T, dim]`` normed -> (the mixer's output [T, dim], the state
    after the last row [H, d_k, d_v], the convolution's inputs [T, 3 H
    d_k])."""
    t = x.shape[0]
    h, d = cfg.kda_heads, cfg.kda_dim
    pre = jnp.concatenate([x @ p["wq"], x @ p["wk"], x @ p["wv"]], -1)
    padded = jnp.concatenate(
        [jnp.zeros((cfg.conv - 1, pre.shape[1]), pre.dtype), pre], 0)
    conv = sum(padded[j : j + t] * p["conv"][j] for j in range(cfg.conv))
    q, k, v = (a.reshape(t, h, d)
               for a in jnp.split(jax.nn.silu(conv), 3, axis=-1))

    def l2(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + K.L2_EPS)

    q, k = l2(q) * d ** -0.5, l2(k)
    r = ((x @ p["wfa"]) @ p["wfb"]).reshape(t, h, d) + p["dt_bias"]
    if sw["bounded_gate"]:
        g = GATE_LOWER * jax.nn.sigmoid(p["a"][:, None] * r)
    else:
        g = -p["a"][:, None] * jax.nn.softplus(r)
    beta = jax.nn.sigmoid(x @ p["wb"])

    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        s = s * jnp.exp(g_t)[..., None]
        pred = jnp.einsum("hkv,hk->hv", s, k_t)
        s = s + (b_t[:, None] * k_t)[..., None] * (v_t - pred)[:, None, :]
        if sw["state_bf16"]:
            s = jax.lax.reduce_precision(s, 8, 7)
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    s, o = jax.lax.scan(step, jnp.zeros((h, d, d), x.dtype), (q, k, v, g, beta))
    gate = jax.nn.sigmoid((x @ p["wga"]) @ p["wgb"]).reshape(t, h, d)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.norm_eps)
    o = o * p["o_norm"] * gate
    return o.reshape(t, h * d) @ p["wo"], s, pre


def mla(p, cfg: K.KimiLinearConfig, x, sw: dict):
    """``x [T, dim]`` normed -> (the sublayer's output [T, dim], the rows a
    cache would hold ``[T, kv_rank + shared]``: ``c`` then ``k_s``)."""
    t, h, nope = x.shape[0], cfg.heads, cfg.nope
    q = (x @ p["wq"]).reshape(t, h, nope + cfg.shared)
    c = rms_norm(x @ p["wc"], p["kv_norm"], cfg.norm_eps)
    k_s = x @ p["ws"]  # [T, shared]: every head's, not rotated
    k = jnp.einsum("tc,hjc->thj", c, p["wkb"])
    v = jnp.einsum("tc,hcj->thj", c, p["wvb"])
    s = jnp.einsum("qhj,khj->hqk", q[..., :nope], k)
    if sw["drop_shared_columns"]:
        s = s * nope ** -0.5
    else:
        s = (s + jnp.einsum("qhj,kj->hqk", q[..., nope:], k_s)) * (
            nope + cfg.shared) ** -0.5
    pos = jnp.arange(t)
    seen = pos[None, :] <= pos[:, None]
    pr = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    ctx = jnp.einsum("hqk,khj->qhj", pr, v).reshape(t, h * cfg.v_dim)
    return ctx @ p["wo"], jnp.concatenate([c, k_s], -1)


def route(p, cfg: K.KimiLinearConfig, x, sw: dict):
    if sw["router_bf16"]:
        bf16 = jnp.bfloat16
        logits = jnp.dot(x.astype(bf16), p["router"].astype(bf16),
                         preferred_element_type=jnp.float32)
    else:
        logits = x @ p["router"]
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(scores + p["router_bias"], cfg.top_k)
    w = jnp.take_along_axis(scores, ids, -1)
    if cfg.norm_topk:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return ids, w * cfg.routed_scale


def moe(p, cfg: K.KimiLinearConfig, x, held=None, shared: bool = True,
        sw: dict = AS_SERVED):
    """The expert layer on rows ``x [T, dim]``: the routed sum over
    ``chosen ∩ held`` (every expert in ``p["experts"]`` when ``held`` is
    None) and, with ``shared``, the shared expert."""
    ids, w = route(p, cfg, x, sw)
    y = jnp.zeros_like(x)
    for e in (p["experts"] if held is None else held):
        w_e = (w * (ids == e)).sum(-1)  # 0 where e was not chosen
        y = y + swiglu(p["experts"][e], x) * w_e[:, None]
    if shared and "shared" in p:
        y = y + swiglu(p["shared"], x)
    return y


def forward(rparams, cfg: K.KimiLinearConfig, tokens, held=None, rows=False,
            **switches):
    """Logits ``[T, vocab]`` of the whole sequence ``tokens [T]``; with
    ``rows`` also what every layer would cache: a delta-rule layer's
    ``{"s": the state after the last row, "pre": the convolution's
    inputs}``, a latent layer's ``{"kv": [T, kv_rank + shared]}``."""
    unknown = set(switches) - set(SWITCHES)
    if unknown:
        raise TypeError(f"unknown switches {sorted(unknown)}")
    sw = {**AS_SERVED, **switches}
    kept = []
    with jax.default_matmul_precision("highest"):
        x = rparams["embed"][tokens]
        for i in range(cfg.layers):
            p = rparams["blocks"][str(i)]
            h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
            if cfg.linear[i]:
                out, s, pre = kda(p, cfg, h, sw)
                kept.append({"s": s, "pre": pre})
            else:
                out, cached = mla(p, cfg, h, sw)
                kept.append({"kv": cached})
            x = x + out
            h = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
            x = x + (swiglu(p["dense"], h) if "dense" in p
                     else moe(p, cfg, h, held, sw=sw))
        x = rms_norm(x, rparams["out_norm"], cfg.norm_eps)
        logits = x @ rparams["lm_head"]
    return (logits, kept) if rows else logits
