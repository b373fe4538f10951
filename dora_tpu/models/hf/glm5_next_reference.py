"""The plain reference of the GLM-5.3-Flash block: float32 ``jax.numpy``
at ``jax.default_matmul_precision("highest")``, the whole sequence at
once, the delta rule a token at a time (``lax.scan``), the sparse-latent
layer as full (unabsorbed) multi-head attention under a dense ``[T, T]``
picked mask, a Python loop over the experts. No cache, no pooled-row
cache, no accumulator, no paging, no batching, no kernels. The one
departure from the uncut model is the argument ``held``: the experts
whose part of the routed sum is computed (``None`` = all of them). The
weights of the routed sum are normalised over every chosen expert either
way, so the parts that disjoint shares give add up to the whole.

The published ``config.json`` names seven mechanisms whose detail it does
not settle (the † lines of ``glm5_next.py``'s docstring;
``KNOWN_ISSUES.md`` "PR 43"). Six are a switch HERE AND ONLY HERE, at the
program's choice by default, so that a test can show that the program's
choice and no other matches it (†4, the gates' low rank, is a size: the
checkpoint's tensors carry it):

* ``hc_eps_inside`` (†1): Sinkhorn divides by the sum of ``(entry +
  hc_eps)`` in place of ``(sum + hc_eps)``;
* ``first_in_mean_out`` (†2): the embedding enters the first stream
  alone (zeros in the others) and the exit is the streams' mean, in place
  of copy-in / sum-out;
* ``softplus_gate`` (†3): Kimi Linear's published ``g = -exp(A_log)
  softplus(r + dt_bias)`` in place of the bounded ``gate_lower_bound *
  sigmoid(exp(A_log) (r + dt_bias))``;
* ``max_pool`` (†5): a block's pooled indexer key is the maximum of its
  keys in place of their mean;
* ``topk_blocks`` (†6): ``index_topk`` counts pooled BLOCKS in place of
  positions (a row picks ``index_topk`` blocks, ``index_kpool`` times the
  rows);
* ``clamp_routed_only`` (†7): ``swiglu_limit`` clamps the routed experts
  alone, in place of every SwiGLU.

Controls, not † lines: ``no_selection`` (every row attends all of
``0..t``: what a program that ignored the indexer would compute),
``one_stream`` (``hc_mult`` 1: a plain residual, ``x + F(rmsnorm(x))``),
and ``picks`` (the sparse-latent layers' picked blocks given from
outside, e.g. the program's, in place of this file's own top-k).

It is given the serving parameters' own int8 weights, dequantized
(:func:`reference_params`), so a comparison with the serving path
measures its activations, its caches and its arithmetic, not the
quantization.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dora_tpu.models.hf import glm5_next as G
from dora_tpu.models.moe import unstack_experts
from dora_tpu.ops.int8_matmul import dequantize

SWITCHES = ("hc_eps_inside", "first_in_mean_out", "softplus_gate", "max_pool",
            "topk_blocks", "clamp_routed_only", "no_selection", "one_stream")
#: the program's choice of each
AS_SERVED = dict.fromkeys(SWITCHES, False)


def reference_params(params, cfg: G.Glm5NextConfig) -> dict:
    """Serving parameters (:func:`glm5_next.load`) -> float32 matrices in
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
        for sub in ("hc_attn", "hc_ffn"):
            p[sub] = {k: v.astype(f32) for k, v in blk[sub].items()}
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
            qa, kva, ik, iw = cut(dequantize(blk["w_a"]), cfg.q_rank,
                                  cfg.kv_rank, cfg.idx_dim, cfg.idx_heads)
            qb, iq = cut(dequantize(blk["w_q_b"]), cfg.heads * cfg.nope,
                         cfg.idx_heads * cfg.idx_dim)
            kb = blk["w_kv_b"]
            p.update(
                wqa=qa, wkva=kva, wik=ik, wiw=iw, wqb=qb, wiq=iq,
                q_norm=blk["q_norm"].astype(f32),
                kv_norm=blk["kv_norm"].astype(f32),
                idx_norm_w=blk["idx_norm_w"], idx_norm_b=blk["idx_norm_b"],
                # [H, nope, kv_rank] and [H, kv_rank, v]
                wkb=kb["k8"].astype(f32) * kb["ks"][:, :, None],
                wvb=kb["v8"].astype(f32) * kb["vs"][:, None, :],
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


def swiglu(w, x, limit=None):
    gate, up = x @ w["gate"], x @ w["up"]
    if limit is not None:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return (jax.nn.silu(gate) * up) @ w["down"]


# -- the residual path -----------------------------------------------------------


def sinkhorn(m, iters: int, eps: float, eps_inside: bool):
    for _ in range(iters):
        for axis in (-1, -2):
            if eps_inside:
                m = m / (m + eps).sum(axis, keepdims=True)
            else:
                m = m / (m.sum(axis, keepdims=True) + eps)
    return m


def mhc_maps(hc, cfg: G.Glm5NextConfig, streams, sw: dict):
    """``streams [T, n, dim]`` -> Hpre [T, n], Hpost [T, n], Hres [T, n, n]."""
    n = cfg.hc
    z = streams.reshape(streams.shape[0], n * cfg.dim)
    z = z * jax.lax.rsqrt(jnp.mean(z * z, -1, keepdims=True) + cfg.hc_eps)
    m = z @ hc["fn"]
    a, b = hc["scale"], hc["base"]
    pre = jax.nn.sigmoid(a[0] * m[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * m[:, n : 2 * n] + b[n : 2 * n])
    res = jnp.exp(a[2] * m[:, 2 * n :].reshape(-1, n, n)
                  + b[2 * n :].reshape(n, n))
    return pre, post, sinkhorn(res, cfg.hc_iters, cfg.hc_eps,
                               sw["hc_eps_inside"])


def mhc_sublayer(hc, cfg: G.Glm5NextConfig, streams, norm_w, sublayer, sw):
    pre, post, res = mhc_maps(hc, cfg, streams, sw)
    u = jnp.einsum("ti,tid->td", pre, streams)
    y = sublayer(rms_norm(u, norm_w, cfg.norm_eps))
    return jnp.einsum("tij,tjd->tid", res, streams) + post[:, :, None] * y[:, None]


# -- the delta-rule mixer ----------------------------------------------------------


def kda(p, cfg: G.Glm5NextConfig, x, sw: dict):
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
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + G.L2_EPS)

    q, k = l2(q) * d ** -0.5, l2(k)
    r = ((x @ p["wfa"]) @ p["wfb"]).reshape(t, h, d) + p["dt_bias"]
    if sw["softplus_gate"]:
        g = -p["a"][:, None] * jax.nn.softplus(r)
    else:
        g = cfg.gate_lower * jax.nn.sigmoid(p["a"][:, None] * r)
    beta = jax.nn.sigmoid(x @ p["wb"])

    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        s = s * jnp.exp(g_t)[..., None]
        pred = jnp.einsum("hkv,hk->hv", s, k_t)
        s = s + (b_t[:, None] * k_t)[..., None] * (v_t - pred)[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    s, o = jax.lax.scan(step, jnp.zeros((h, d, d), x.dtype), (q, k, v, g, beta))
    gate = jax.nn.sigmoid((x @ p["wga"]) @ p["wgb"]).reshape(t, h, d)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.norm_eps)
    o = o * p["o_norm"] * gate
    return o.reshape(t, h * d) @ p["wo"], s, pre


# -- the sparse-latent layer ---------------------------------------------------------


def index_scores(p, cfg: G.Glm5NextConfig, x, c_q, sw: dict):
    """-> (score [T, N] float32 of row t against pooled block b, -inf
    where ``b >= floor(t / index_kpool)``; the pooled keys [N, d_I])."""
    t, kp = x.shape[0], cfg.idx_pool
    ki = x @ p["wik"]
    ki = ki - ki.mean(-1, keepdims=True)
    ki = ki * jax.lax.rsqrt(jnp.mean(ki * ki, -1, keepdims=True)
                            + G.INDEX_NORM_EPS)
    ki = ki * p["idx_norm_w"] + p["idx_norm_b"]
    n = t // kp  # complete blocks
    blocks = ki[: n * kp].reshape(n, kp, cfg.idx_dim)
    pooled = blocks.max(1) if sw["max_pool"] else blocks.mean(1)
    qi = (c_q @ p["wiq"]).reshape(t, cfg.idx_heads, cfg.idx_dim)
    w = (x @ p["wiw"]) * (cfg.idx_heads ** -0.5 * cfg.idx_dim ** -0.5)
    s = (jax.nn.relu(jnp.einsum("tjd,nd->tjn", qi, pooled)) * w[..., None]).sum(1)
    complete = jnp.arange(t) // kp
    return jnp.where(jnp.arange(n)[None] < complete[:, None], s, -jnp.inf), pooled


def picked_rows(cfg: G.Glm5NextConfig, t: int, ids, sw: dict):
    """The dense mask ``[T, T]``: which positions each row attends, from
    its picked blocks ``ids [T, n_picked]`` (used where the row
    selects)."""
    kp = cfg.idx_pool
    pos = jnp.arange(t)
    causal = pos[None, :] <= pos[:, None]
    if sw["no_selection"]:
        return causal
    count = cfg.idx_topk if sw["topk_blocks"] else cfg.picked_blocks
    selects = pos >= count * kp
    n = max(t // kp, 1)
    sel = jnp.zeros((t, n), bool).at[pos[:, None], jnp.minimum(ids, n - 1)].set(True)
    mine = jnp.repeat(sel, kp, axis=1)
    mine = jnp.pad(mine, ((0, 0), (0, t - mine.shape[1])))[:, :t]
    tail = pos[None, :] >= (pos // kp * kp)[:, None]
    return causal & (~selects[:, None] | mine | tail)


def dsa(p, cfg: G.Glm5NextConfig, x, sw: dict, picks=None):
    """``x [T, dim]`` normed -> (the sublayer's output [T, dim], a dict of
    what a cache would hold and what was picked: ``c`` [T, kv_rank],
    ``pooled`` [N, d_I], ``scores`` [T, N], ``picked`` [T, n_picked])."""
    t, h = x.shape[0], cfg.heads
    c_q = rms_norm(x @ p["wqa"], p["q_norm"], cfg.norm_eps)
    c = rms_norm(x @ p["wkva"], p["kv_norm"], cfg.norm_eps)
    q = (c_q @ p["wqb"]).reshape(t, h, cfg.nope)
    scores, pooled = index_scores(p, cfg, x, c_q, sw)
    count = cfg.idx_topk if sw["topk_blocks"] else cfg.picked_blocks
    n = scores.shape[1]
    if n >= count:
        _, own = jax.lax.top_k(scores, count)
    else:  # no row can select yet
        own = jnp.zeros((t, count), jnp.int32)
    ids = own if picks is None else picks
    seen = picked_rows(cfg, t, ids, sw)
    k = jnp.einsum("tc,hjc->thj", c, p["wkb"])
    v = jnp.einsum("tc,hcj->thj", c, p["wvb"])
    s = jnp.einsum("qhj,khj->hqk", q, k) * cfg.nope ** -0.5
    pr = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    ctx = jnp.einsum("hqk,khj->qhj", pr, v).reshape(t, h * cfg.v_dim)
    return ctx @ p["wo"], {"c": c, "pooled": pooled, "scores": scores,
                           "picked": own}


# -- the expert layer -----------------------------------------------------------------


def route(p, cfg: G.Glm5NextConfig, x):
    scores = jax.nn.sigmoid(x @ p["router"])
    _, ids = jax.lax.top_k(scores + p["router_bias"], cfg.top_k)
    w = jnp.take_along_axis(scores, ids, -1)
    if cfg.norm_topk:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return ids, w * cfg.routed_scale


def moe(p, cfg: G.Glm5NextConfig, x, held=None, shared: bool = True,
        routed_only: bool = False):
    """The expert layer on rows ``x [T, dim]``: the routed sum over
    ``chosen ∩ held`` (every expert in ``p["experts"]`` when ``held`` is
    None) and, with ``shared``, the shared expert."""
    ids, w = route(p, cfg, x)
    y = jnp.zeros_like(x)
    for e in (p["experts"] if held is None else held):
        w_e = (w * (ids == e)).sum(-1)  # 0 where e was not chosen
        y = y + swiglu(p["experts"][e], x, cfg.swiglu_limit) * w_e[:, None]
    if shared and "shared" in p:
        y = y + swiglu(p["shared"], x,
                       None if routed_only else cfg.swiglu_limit)
    return y


def forward(rparams, cfg: G.Glm5NextConfig, tokens, held=None, rows=False,
            picks=None, **switches):
    """Logits ``[T, vocab]`` of the whole sequence ``tokens [T]``; with
    ``rows`` also what every layer would cache: a delta-rule layer's
    ``{"s": the state after the last row, "pre": the convolution's
    inputs}``, a sparse-latent layer's dict of :func:`dsa`. ``picks``:
    ``{layer: [T, n_picked]}`` picked blocks from outside."""
    unknown = set(switches) - set(SWITCHES)
    if unknown:
        raise TypeError(f"unknown switches {sorted(unknown)}")
    sw = {**AS_SERVED, **switches}
    kept = []
    with jax.default_matmul_precision("highest"):
        x = rparams["embed"][tokens]
        t = x.shape[0]
        if sw["one_stream"]:
            streams = None
        elif sw["first_in_mean_out"]:
            streams = jnp.zeros((t, cfg.hc, cfg.dim), x.dtype).at[:, 0].set(x)
        else:
            streams = jnp.broadcast_to(x[:, None], (t, cfg.hc, cfg.dim))
        for i in range(cfg.layers):
            p = rparams["blocks"][str(i)]

            def mixer(h, p=p, i=i):
                if cfg.linear[i]:
                    out, s, pre = kda(p, cfg, h, sw)
                    kept.append({"s": s, "pre": pre})
                else:
                    out, cached = dsa(p, cfg, h, sw,
                                      None if picks is None else picks[i])
                    kept.append(cached)
                return out

            def ffn(h, p=p):
                if "dense" in p:
                    return swiglu(p["dense"], h, None if sw["clamp_routed_only"]
                                  else cfg.swiglu_limit)
                return moe(p, cfg, h, held,
                           routed_only=sw["clamp_routed_only"])

            if sw["one_stream"]:
                x = x + mixer(rms_norm(x, p["attn_norm"], cfg.norm_eps))
                x = x + ffn(rms_norm(x, p["ffn_norm"], cfg.norm_eps))
            else:
                streams = mhc_sublayer(p["hc_attn"], cfg, streams,
                                       p["attn_norm"], mixer, sw)
                streams = mhc_sublayer(p["hc_ffn"], cfg, streams,
                                       p["ffn_norm"], ffn, sw)
        if not sw["one_stream"]:
            x = streams.mean(1) if sw["first_in_mean_out"] else streams.sum(1)
        x = rms_norm(x, rparams["out_norm"], cfg.norm_eps)
        logits = x @ rparams["lm_head"]
    return (logits, kept) if rows else logits
