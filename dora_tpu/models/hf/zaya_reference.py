"""The plain reference of the ZAYA1 block: float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``, the whole sequence at once:
the two convolutions as padded sums over the sequence, the value shift as
a padded slice, attention under the dense causal triangle, the router
state carried from layer to layer, a Python loop over the experts. No
cache, no tails, no pages, no batching, no kernels. The one departure
from the uncut model is the argument ``held``: the experts whose part of
the routed sum is computed (``None`` = all of them); a token whose one
expert is not among them gets nothing from the layer.

The published ``config.json`` names the mechanisms (``cca_time0``,
``cca_time1``, ``router_hidden_size``) without settling eight points (the
† lines of ``zaya.py``'s docstring; ``KNOWN_ISSUES.md`` "PR 52"). Each is
a switch HERE AND ONLY HERE, at the program's choice by default, so that
a test can show that the program's choice and no other matches it:

* ``tau_linear`` (†1): the key's gain is ``1 + tau``, not ``exp(tau)``;
* ``value_heads_swapped`` (†2): K/V head 0 takes the PREVIOUS position's
  projection and head 1 this position's;
* ``no_residual_scaling`` (†3): ``x + y`` in place of ``(x + rb) rs + (y +
  hb) hs``;
* ``router_reads_residual`` (†4): the router's down projection reads the
  residual row itself, not the normed one;
* ``carry_normed_state`` (†5): what goes to the next layer's router is
  the RMSNorm of the state, not the state;
* ``router_one_hidden`` (†6): one hidden layer (``W2``, ``b2`` unused);
* ``skip_output`` (†7): the router's LAST output is a skip
  ("mixture-of-depths"): a token that chooses it gets nothing from the
  layer;
* ``pad_each_conv`` (†8): each convolution pads its own input with a zero
  row, so ``a_{-1} = 0`` and not ``b0``.

Controls, not † lines: ``no_conv`` (``d_t = c_t``: what a program that
left the convolutions out would compute), ``no_value_shift`` (both value
heads from this position), ``no_router_carry`` (every layer's router
starts from zeros), and the two of a lower precision, ``conv_bf16`` (the
convolutions' inputs, weights and sums through bfloat16) and
``router_bf16`` (the router from ``Wd`` on in bfloat16).

It is given the serving parameters' own int8 weights, dequantized
(:func:`reference_params`), so a comparison with the serving path
measures its activations, its caches and its arithmetic, not the
quantization.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dora_tpu.models.hf import zaya as Z
from dora_tpu.models.moe import unstack_experts
from dora_tpu.ops.int8_matmul import dequantize

SWITCHES = ("tau_linear", "value_heads_swapped", "no_residual_scaling",
            "router_reads_residual", "carry_normed_state",
            "router_one_hidden", "skip_output", "pad_each_conv",
            "no_conv", "no_value_shift", "no_router_carry",
            "conv_bf16", "router_bf16")
#: the program's choice of each
AS_SERVED = dict.fromkeys(SWITCHES, False)


def reference_params(params, cfg: Z.ZayaConfig) -> dict:
    """Serving parameters (:func:`zaya.load`) -> float32 matrices in
    ``[in, out]`` layout, the fused one taken apart."""
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

    def f32s(tree):
        return jax.tree.map(lambda a: a.astype(f32), tree)

    widths = (cfg.q_width, cfg.kv_width, cfg.head_dim, cfg.head_dim)
    for i, blk in params["blocks"].items():
        fused, parts, at = dequantize(blk["wqkv"]), [], 0
        for n in widths:
            parts.append(fused[:, at : at + n])
            at += n
        out["blocks"][i] = {
            **dict(zip(("q", "k", "v1", "v2"), parts)),
            "o": dequantize(blk["wo"]),
            **f32s({k: blk[k] for k in (
                "attn_norm", "ffn_norm", "conv0_w", "conv0_b", "conv1_w",
                "conv1_b", "tau", "attn_res", "ffn_res", "router")}),
            "experts": {
                cfg.expert_first + e: swiglu(w)
                for e, w in enumerate(unstack_experts(blk["experts"]))
            },
        }
    return out


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def rotate(x, cos, sin):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(w, x):
    return (jax.nn.silu(x @ w["gate"]) * (x @ w["up"])) @ w["down"]


def shift(x, by: int = 1):
    """Rows ``by`` positions later, zeros in front: ``out[t] = x[t - by]``."""
    return jnp.pad(x, ((by, 0), (0, 0)))[: x.shape[0]]


def convolutions(r, cfg: Z.ZayaConfig, c, sw: dict):
    """``c [T, 1280]`` -> ``d [T, 1280]``: the depthwise convolution of two
    taps, then the grouped one, a head of 128 channels at a time, as sums
    over shifted copies of the sequence."""
    if sw["no_conv"]:
        return c
    t = c.shape[0]
    g, hd = cfg.heads + cfg.kv_heads, cfg.head_dim
    w0, b0, w1, b1 = r["conv0_w"], r["conv0_b"], r["conv1_w"], r["conv1_b"]
    if sw["conv_bf16"]:
        c, w0, b0, w1, b1 = (a.astype(jnp.bfloat16) for a in (c, w0, b0, w1, b1))
    a = w0[0] * shift(c) + w0[1] * c + b0
    # a_{-1}: the depthwise convolution of the padded input's first rows
    # (both zeros) is its bias; a convolution that pads for itself sees 0
    before = jnp.zeros_like(b0) if sw["pad_each_conv"] else b0
    a_prev = jnp.concatenate([before[None], a[:-1]], 0)
    d = (jnp.einsum("tgi,gio->tgo", a_prev.reshape(t, g, hd), w1[0])
         + jnp.einsum("tgi,gio->tgo", a.reshape(t, g, hd), w1[1]))
    return (d.reshape(t, -1) + b1).astype(jnp.float32)


def attention(r, cfg: Z.ZayaConfig, h, sw: dict):
    """Normed rows ``h [T, dim]`` -> (the sublayer's output [T, dim], the
    keys attention read [T, KV, hd], the values [T, KV, hd], the
    pre-convolution rows ``c`` [T, 1280], ``Wv2 h`` [T, hd])."""
    t = h.shape[0]
    heads, kv, hd = cfg.heads, cfg.kv_heads, cfg.head_dim
    g = heads // kv
    c = jnp.concatenate([h @ r["q"], h @ r["k"]], -1)
    d = convolutions(r, cfg, c, sw)

    def split(x):
        return (x[:, : cfg.q_width].reshape(t, kv, g, hd),
                x[:, cfg.q_width :].reshape(t, kv, hd))

    (qt, kt), (dq, dk) = split(c), split(d)
    m = (qt + kt[:, :, None]) / 2
    q, k = dq + m, dk + m.mean(2)

    def l2(x):
        return hd ** 0.5 * x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    gain = 1.0 + r["tau"] if sw["tau_linear"] else jnp.exp(r["tau"])
    q, k = l2(q), l2(k) * gain[None, :, None]
    rd = cfg.rotary_dim
    inv = 1.0 / cfg.rope_theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    q = jnp.concatenate([rotate(q[..., :rd], cos[:, None, None], sin[:, None, None]),
                         q[..., rd:]], -1)
    k = jnp.concatenate([rotate(k[..., :rd], cos[:, None], sin[:, None]),
                         k[..., rd:]], -1)
    v1, v2 = h @ r["v1"], h @ r["v2"]
    late = v2 if sw["no_value_shift"] else shift(v2)
    v = jnp.stack([late, v1] if sw["value_heads_swapped"] else [v1, late], 1)
    s = jnp.einsum("qkgd,tkd->kgqt", q, k) / hd ** 0.5
    seen = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    ctx = jnp.einsum("kgqt,tkd->qkgd", p, v).reshape(t, heads * hd)
    return ctx @ r["o"], k, v, c, v2


def route(r, cfg: Z.ZayaConfig, x, s, sw: dict):
    """Rows ``x [T, dim]`` and the carried state ``s [T, R]`` -> (the
    chosen expert [T], its probability [T], what the next layer's router
    is given [T, R], the biased probabilities [T, experts])."""
    w = r["router"]
    dtype = jnp.bfloat16 if sw["router_bf16"] else jnp.float32
    w = jax.tree.map(lambda a: a.astype(dtype), w)
    s = x.astype(dtype) @ w["down"] + w["down_b"] + w["gamma"] * s.astype(dtype)
    u = rms_norm(s, w["norm"], cfg.norm_eps)
    z = jax.nn.gelu(u @ w["w1"] + w["b1"], approximate=False)
    if not sw["router_one_hidden"]:
        z = jax.nn.gelu(z @ w["w2"] + w["b2"], approximate=False)
    p = jax.nn.softmax((z @ w["w3"]).astype(jnp.float32), -1)
    biased = p + r["router"]["bias"]
    e = jnp.argmax(biased, -1)
    carried = u if sw["carry_normed_state"] else s
    return (e, jnp.take_along_axis(p, e[:, None], -1)[:, 0],
            carried.astype(jnp.float32), biased)


def moe(r, cfg: Z.ZayaConfig, x, s, sw: dict, held=None, reads=None):
    """The expert sublayer on normed rows ``x [T, dim]``: the one chosen
    expert's SwiGLU times its probability, where it is among ``held``
    (every expert in ``r["experts"]`` when None). ``reads`` is what the
    router reads (``x`` unless †4 is flipped). Returns (y, the state for
    the next layer, the chosen expert, the biased probabilities)."""
    e, w, s, biased = route(r, cfg, x if reads is None else reads, s, sw)
    y = jnp.zeros_like(x)
    for n in (r["experts"] if held is None else held):
        if sw["skip_output"] and n == cfg.n_experts - 1:
            continue
        y = y + swiglu(r["experts"][n], x) * (w * (e == n))[:, None]
    return y, s, e, biased


def scaled(res, x, y, sw: dict):
    if sw["no_residual_scaling"]:
        return x + y
    return (x + res["rb"]) * res["rs"] + (y + res["hb"]) * res["hs"]


def forward(rparams, cfg: Z.ZayaConfig, tokens, held=None, rows=False,
            **switches):
    """Logits ``[T, vocab]`` of the whole sequence ``tokens [T]``; with
    ``rows`` also every layer's ``{"k", "v"`` (what the program caches,
    ``[T, KV, hd]`` each), ``"c"`` (the pre-convolution rows ``[T,
    1280]``), ``"v2"`` (``Wv2 h`` ``[T, hd]``: a tail after position ``t``
    is ``c[t-1], c[t]`` and ``v2[t]``), ``"expert"`` ``[T]`` and
    ``"biased"`` ``[T, experts]}``."""
    unknown = set(switches) - set(SWITCHES)
    if unknown:
        raise TypeError(f"unknown switches {sorted(unknown)}")
    sw = {**AS_SERVED, **switches}
    kept = []
    with jax.default_matmul_precision("highest"):
        x = rparams["embed"][tokens]
        s = jnp.zeros((x.shape[0], cfg.router_hidden), jnp.float32)
        for i in range(cfg.layers):
            r = rparams["blocks"][str(i)]
            a, k, v, c, v2 = attention(
                r, cfg, rms_norm(x, r["attn_norm"], cfg.norm_eps), sw)
            x = scaled(r["attn_res"], x, a, sw)
            if sw["no_router_carry"]:
                s = jnp.zeros_like(s)
            y, s, e, biased = moe(
                r, cfg, rms_norm(x, r["ffn_norm"], cfg.norm_eps), s, sw, held,
                reads=x if sw["router_reads_residual"] else None)
            x = scaled(r["ffn_res"], x, y, sw)
            kept.append({"k": k, "v": v, "c": c, "v2": v2, "expert": e,
                         "biased": biased})
        x = rms_norm(x, rparams["out_norm"], cfg.norm_eps)
        logits = x @ rparams["lm_head"]
    return (logits, kept) if rows else logits
