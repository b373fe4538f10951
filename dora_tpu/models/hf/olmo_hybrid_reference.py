"""The plain reference of the Olmo-Hybrid block: float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``, the whole sequence at once
and the delta rule ONE TOKEN AT A TIME as it is written down (a
``lax.scan`` over the rows that carries ``S``): no blocks, no kernel, no
cache, no pages, no slot state, no batching.

The published ``config.json`` leaves five points open (the † lines of
``olmo_hybrid.py``'s docstring; ``KNOWN_ISSUES.md`` "PR 56"). Each is a
switch HERE AND ONLY HERE, at the program's choice by default, so that a
test can show that the program's choice and no other matches it:

* ``pre_norm`` (†1): ``x + F(norm(x))`` in place of ``x + norm(F(x))``;
* ``qk_norm_per_head`` (†2): the RMSNorm of q and of k runs over each head
  of 128 (with the first 128 entries of the weight), not over the whole
  projection;
* ``rope_theta`` (†3): a number = rotate-half rotary with that base on the
  full layers' q and k (after the norm), None = no rotary;
* ``conv_newest_first`` (†5): tap 0 of the convolution's weight multiplies
  the NEWEST row (a flipped kernel), not the oldest.

(†4, the tensor names, is the loader's and has no arithmetic to switch.)

Controls, not † lines: ``beta_not_doubled`` (``linear_allow_neg_eigval``
ignored), ``gate_heads_reversed`` (head ``h``'s state decays by head ``H - 1 -
h``'s gate: a gate laid over its axis the wrong way), ``drop_oldest_tap``
(the convolution sums three taps), ``state_bf16`` (``S`` is rounded to
bfloat16 after every token), and ``zero_state_at`` (an int: ``S`` and the
convolution's memory are zeroed before that row, what a grant without its
snapshot would compute).

It is given the serving parameters' own int8 weights, dequantized
(:func:`reference_params`), so a comparison with the serving path
measures its activations, its caches and its arithmetic, not the
quantization.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dora_tpu.models.hf import olmo_hybrid as O
from dora_tpu.ops.int8_matmul import dequantize

SWITCHES = ("pre_norm", "qk_norm_per_head", "rope_theta", "conv_newest_first",
            "beta_not_doubled", "gate_heads_reversed",
            "drop_oldest_tap", "state_bf16", "zero_state_at")
#: the program's choice of each
AS_SERVED = {**dict.fromkeys(SWITCHES, False), "rope_theta": None,
             "zero_state_at": None}


def reference_params(params, cfg: O.OlmoHybridConfig) -> dict:
    """Serving parameters (:func:`olmo_hybrid.load`) -> float32 matrices in
    ``[in, out]`` layout, the fused ones taken apart."""
    f32 = jnp.float32
    out = {
        "embed": params["embed"].astype(f32),
        "out_norm": params["out_norm"].astype(f32),
        "lm_head": dequantize(params["lm_head"]),
        "blocks": {},
    }

    def apart(fused, widths):
        parts, at = [], 0
        for n in widths:
            parts.append(fused[:, at : at + n])
            at += n
        return parts

    kw, vw, lanes = cfg.gdn_key_width, cfg.gdn_value_width, O.gate_lanes(cfg)
    for i, blk in params["blocks"].items():
        gate, up = jnp.split(dequantize(blk["dense"]["w_gateup"]), 2, axis=1)
        r = {
            "attn_norm": blk["attn_norm"].astype(f32),
            "ffn_norm": blk["ffn_norm"].astype(f32),
            "gate": gate, "up": up,
            "down": dequantize(blk["dense"]["w_down"]),
            "o": dequantize(blk["wo"]),
        }
        if cfg.linear[int(i)]:
            q, k, v, g, a, b = apart(
                dequantize(blk["w_in"]), (kw, kw, vw, vw, lanes, lanes))
            r.update(
                q=q, k=k, v=v, g=g, a=a[:, : cfg.gdn_heads],
                b=b[:, : cfg.gdn_heads],
                conv_w=blk["conv_w"].astype(f32), a_exp=blk["a"],
                dt_bias=blk["dt_bias"], o_norm=blk["o_norm"].astype(f32))
        else:
            q, k, v = apart(dequantize(blk["wqkv"]),
                            (cfg.q_width, cfg.kv_width, cfg.kv_width))
            r.update(q=q, k=k, v=v, q_norm=blk["q_norm"].astype(f32),
                     k_norm=blk["k_norm"].astype(f32))
        out["blocks"][i] = r
    return out


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def rotate(x, cos, sin):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def convolution(r, cfg: O.OlmoHybridConfig, c, sw: dict):
    """``c [T, channels]`` -> the causal depthwise convolution of
    ``cfg.conv`` taps, zeros before position 0 (or before
    ``zero_state_at``): a sum over shifted copies of the sequence."""
    t, taps = c.shape[0], cfg.conv
    w = r["conv_w"][::-1] if sw["conv_newest_first"] else r["conv_w"]
    at = jnp.arange(t)[:, None]
    out = 0.0
    for j in range(1 if sw["drop_oldest_tap"] else 0, taps):
        back = taps - 1 - j  # tap j reads the row ``back`` positions earlier
        rows = jnp.pad(c, ((back, 0), (0, 0)))[:t]
        if sw["zero_state_at"] is not None:
            cut = sw["zero_state_at"]
            rows = jnp.where((at >= cut) & (at - back < cut), 0.0, rows)
        out = out + w[j] * rows
    return out


def delta_rule(cfg: O.OlmoHybridConfig, q, k, v, g, beta, sw: dict):
    """The recurrence as written: q, k ``[T, H, d_k]``, v ``[T, H, d_v]``,
    g (log alpha), beta ``[T, H]`` -> (o ``[T, H, d_v]``, every row's state
    ``[T, H, d_k, d_v]`` where asked, the last state)."""
    t = q.shape[0]
    cut = -1 if sw["zero_state_at"] is None else sw["zero_state_at"]

    def step(s, inp):
        q_, k_, v_, g_, b_, at = inp
        s = jnp.where(at == cut, 0.0, s)
        if sw["gate_heads_reversed"]:
            g_ = g_[::-1]
        s = s * jnp.exp(g_)[:, None, None]
        pred = jnp.einsum("hkv,hk->hv", s, k_)
        s = s + (b_[:, None] * k_)[:, :, None] * (v_ - pred)[:, None, :]
        if sw["state_bf16"]:
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.einsum("hkv,hk->hv", s, q_)

    s0 = jnp.zeros((cfg.gdn_heads, cfg.gdn_dk, cfg.gdn_dv), jnp.float32)
    s, o = jax.lax.scan(step, s0, (q, k, v, g, beta, jnp.arange(t)))
    return o, s


def linear_attention(r, cfg: O.OlmoHybridConfig, x, sw: dict):
    """Rows ``x [T, dim]`` -> (the mixer's output [T, dim], the state after
    the last row [H, d_k, d_v], the pre-convolution rows [T, channels])."""
    t, h = x.shape[0], cfg.gdn_heads
    c = jnp.concatenate([x @ r["q"], x @ r["k"], x @ r["v"]], -1)
    act = jax.nn.silu(convolution(r, cfg, c, sw))
    kw = cfg.gdn_key_width
    q = act[:, :kw].reshape(t, h, cfg.gdn_dk)
    k = act[:, kw : 2 * kw].reshape(t, h, cfg.gdn_dk)
    v = act[:, 2 * kw :].reshape(t, h, cfg.gdn_dv)

    def l2(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + O.L2_EPS)

    q, k = l2(q) * cfg.gdn_dk ** -0.5, l2(k)
    beta = jax.nn.sigmoid(x @ r["b"])
    if cfg.neg_eigval and not sw["beta_not_doubled"]:
        beta = beta * 2.0
    g = -r["a_exp"] * jax.nn.softplus(x @ r["a"] + r["dt_bias"])
    o, s = delta_rule(cfg, q, k, v, g, beta, sw)
    o = rms_norm(o, r["o_norm"], cfg.norm_eps)
    o = o * jax.nn.silu(x @ r["g"]).reshape(o.shape)
    return o.reshape(t, -1) @ r["o"], s, c


def full_attention(r, cfg: O.OlmoHybridConfig, x, sw: dict):
    """Rows ``x [T, dim]`` -> (the sublayer's output [T, dim], the keys
    attention read [T, KV, hd], the values [T, KV, hd])."""
    t, heads, kv, hd = x.shape[0], cfg.heads, cfg.kv_heads, cfg.head_dim
    q, k, v = x @ r["q"], x @ r["k"], x @ r["v"]
    if sw["qk_norm_per_head"]:
        q = rms_norm(q.reshape(t, heads, hd), r["q_norm"][:hd], cfg.norm_eps)
        k = rms_norm(k.reshape(t, kv, hd), r["k_norm"][:hd], cfg.norm_eps)
    else:
        q = rms_norm(q, r["q_norm"], cfg.norm_eps).reshape(t, heads, hd)
        k = rms_norm(k, r["k_norm"], cfg.norm_eps).reshape(t, kv, hd)
    if sw["rope_theta"] is not None:
        inv = 1.0 / sw["rope_theta"] ** (
            jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
        cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
        q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    v = v.reshape(t, kv, hd)
    g = heads // kv
    s = jnp.einsum("qkgd,tkd->kgqt", q.reshape(t, kv, g, hd), k) / hd ** 0.5
    seen = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    ctx = jnp.einsum("kgqt,tkd->qkgd", p, v).reshape(t, heads * hd)
    return ctx @ r["o"], k, v


def forward(rparams, cfg: O.OlmoHybridConfig, tokens, rows=False, **switches):
    """Logits ``[T, vocab]`` of the whole sequence ``tokens [T]``; with
    ``rows`` also every layer's cache as the program would hold it after
    the last row: a linear layer's ``{"s" [H, d_k, d_v], "c" [T,
    channels]}`` (its tail is ``c``'s last three rows), a full layer's
    ``{"k", "v"}`` ``[T, KV, hd]``."""
    unknown = set(switches) - set(SWITCHES)
    if unknown:
        raise TypeError(f"unknown switches {sorted(unknown)}")
    sw = {**AS_SERVED, **switches}
    kept = []

    def sublayer(x, f, norm_w):
        if sw["pre_norm"]:
            y, *rest = f(rms_norm(x, norm_w, cfg.norm_eps))
            return x + y, rest
        y, *rest = f(x)
        return x + rms_norm(y, norm_w, cfg.norm_eps), rest

    with jax.default_matmul_precision("highest"):
        x = rparams["embed"][tokens]
        for i in range(cfg.layers):
            r = rparams["blocks"][str(i)]
            if cfg.linear[i]:
                x, (s, c) = sublayer(
                    x, lambda u, r=r: linear_attention(r, cfg, u, sw),
                    r["attn_norm"])
                kept.append({"s": s, "c": c})
            else:
                x, (k, v) = sublayer(
                    x, lambda u, r=r: full_attention(r, cfg, u, sw),
                    r["attn_norm"])
                kept.append({"k": k, "v": v})
            x, _ = sublayer(
                x, lambda u, r=r: ((jax.nn.silu(u @ r["gate"]) * (u @ r["up"]))
                                   @ r["down"],),
                r["ffn_norm"])
        x = rms_norm(x, rparams["out_norm"], cfg.norm_eps)
        logits = x @ rparams["lm_head"]
    return (logits, kept) if rows else logits
