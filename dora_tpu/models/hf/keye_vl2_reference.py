"""The plain reference of the Keye-VL-2.0 language block: float32
``jax.numpy`` at ``jax.default_matmul_precision("highest")``, the whole
sequence at once, the full ``[T, T]`` index scores and a ``top_k`` a row,
attention under the dense picked mask, a Python loop over the experts.
No cache, no pages, no batching, no kernels. The one departure from the
uncut model is the argument ``held``: the experts whose part of the routed
sum is computed (``None`` = all of them). The weights of the routed sum
are normalised over every chosen expert either way, so the parts that
disjoint shares give add up to the whole.

The published ``config.json`` names an indexer (``sa_config``) whose
detail it does not settle (the † lines of ``keye_vl2.py``'s docstring;
``KNOWN_ISSUES.md`` "PR 49"). Each is a switch HERE AND ONLY HERE, at the
program's choice by default, so that a test can show that the program's
choice and no other matches it:

* ``index_reads_residual`` (†1): the indexer's three projections read
  the residual row itself, not the normed one (DeepSeek-V3.2's read the
  query's latent; there is no ``q_lora`` here to read);
* ``index_plain_key`` (†2): no LayerNorm on the indexer's key and no
  rotary on the indexer (GLM-5.3-Flash's indexer has no rotary part);
* ``index_unscaled`` (†3): ``w = u Ww`` without ``J^-1/2 dI^-1/2``. A
  positive factor leaves every ranking where it is: the switch exists so
  that a test can show that it moves no pick, and it does not;
* ``block_picks`` (†4): ``q_chunk_size`` / ``kv_chunk_size`` are part of
  the mathematics: the rows of a query chunk share one pick of
  ``topk / kv_chunk_size`` K/V blocks by the largest score any of them
  gives any position of a block, and every row also sees the block it
  stands in (``chunks=(q, kv)`` gives the sizes);
* ``forced_tail_and_sink`` (†5): position 0 and the row's own position
  are always among the picks;
* ``post_norm`` (†6): ``x + RMSNorm(F(x))`` in place of the pre-norm.

Controls, not † lines: ``no_selection`` (every row attends all of
``0..t``: what a program that ignored the indexer would compute),
``no_qk_norm`` (no RMSNorm of q and k over the head), ``picks`` (each
layer's picked mask given from outside) and ``positions3`` (the three
M-RoPE components of every position, ``[3, T]``; all equal to the
position by default, as for ids without a tower; the indexer turns by the
temporal one).

It is given the serving parameters' own int8 weights, dequantized
(:func:`reference_params`), so a comparison with the serving path
measures its activations, its caches and its arithmetic, not the
quantization.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dora_tpu.models.hf import keye_vl2 as K
from dora_tpu.models.moe import unstack_experts
from dora_tpu.ops.int8_matmul import dequantize

SWITCHES = ("index_reads_residual", "index_plain_key", "index_unscaled",
            "block_picks", "forced_tail_and_sink", "post_norm",
            "no_selection", "no_qk_norm")
#: the program's choice of each
AS_SERVED = dict.fromkeys(SWITCHES, False)


def reference_params(params, cfg: K.KeyeVL2Config) -> dict:
    """Serving parameters (:func:`keye_vl2.load`) -> float32 matrices in
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

    widths = (cfg.q_width, cfg.kv_width, cfg.kv_width,
              cfg.idx_heads * cfg.idx_dim, cfg.idx_dim, cfg.idx_heads)
    for i, blk in params["blocks"].items():
        fused, parts, at = dequantize(blk["wqkv"]), [], 0
        for n in widths:
            parts.append(fused[:, at : at + n])
            at += n
        out["blocks"][i] = {
            "attn_norm": blk["attn_norm"].astype(f32),
            **dict(zip(("q", "k", "v", "iq", "ik", "iw"), parts)),
            "q_norm": blk["q_norm"].astype(f32),
            "k_norm": blk["k_norm"].astype(f32),
            "idx_norm_w": blk["idx_norm_w"].astype(f32),
            "idx_norm_b": blk["idx_norm_b"].astype(f32),
            "o": dequantize(blk["wo"]),
            "ffn_norm": blk["ffn_norm"].astype(f32),
            "router": blk["router"].astype(f32),
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


def mrope_angles(cfg: K.KeyeVL2Config, positions3):
    """``positions3 [3, T]`` -> the attention heads' rotary angles ``[T,
    hd / 2]``: frequency ``i`` turns by the component its section names
    (the first ``mrope_section[0]`` by the temporal one, the next by the
    height, the rest by the width)."""
    hd = cfg.head_dim
    inv = 1.0 / cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    component = jnp.repeat(jnp.arange(len(cfg.mrope_section)),
                           jnp.asarray(cfg.mrope_section),
                           total_repeat_length=hd // 2)
    return positions3.astype(jnp.float32)[component].T * inv[None, :]


def index_scores(r, cfg: K.KeyeVL2Config, h, positions, sw: dict):
    """The indexer's ``I(t, s)`` over the whole sequence, ``[T, T]``
    float32, ``-inf`` above the diagonal; and its keys ``[T, dI]``."""
    t = h.shape[0]
    qi = (h @ r["iq"]).reshape(t, cfg.idx_heads, cfg.idx_dim)
    ki = h @ r["ik"]
    wi = h @ r["iw"]
    if not sw["index_unscaled"]:
        wi = wi * (cfg.idx_heads ** -0.5 * cfg.idx_dim ** -0.5)
    if not sw["index_plain_key"]:
        ki = ki - ki.mean(-1, keepdims=True)
        ki = ki * jax.lax.rsqrt(jnp.mean(ki * ki, -1, keepdims=True)
                                + K.INDEX_NORM_EPS)
        ki = ki * r["idx_norm_w"] + r["idx_norm_b"]
        d = cfg.idx_dim
        inv = 1.0 / cfg.rope_theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        angles = positions.astype(jnp.float32)[:, None] * inv[None]
        cos, sin = jnp.cos(angles), jnp.sin(angles)
        qi, ki = rotate(qi, cos[:, None], sin[:, None]), rotate(ki, cos, sin)
    s = (jax.nn.relu(jnp.einsum("tjd,sd->tjs", qi, ki)) * wi[..., None]).sum(1)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    return jnp.where(causal, s, -jnp.inf), ki


def picked_mask(cfg: K.KeyeVL2Config, scores, sw: dict, chunks=None):
    """``[T, T]`` bool: the positions each row attends. Rows at ``t <
    topk`` attend ``0..t``; above, the ``topk`` positions of largest
    score (``lax.top_k``: ties to the lower position)."""
    t = scores.shape[0]
    rows = jnp.arange(t)
    causal = rows[:, None] >= rows[None, :]
    if sw["no_selection"] or t <= cfg.idx_topk:
        return causal
    if sw["block_picks"]:
        qc, kc = chunks
        nq, nk = -(-t // qc), -(-t // kc)
        padded = jnp.pad(scores, ((0, nq * qc - t), (0, nk * kc - t)),
                         constant_values=-jnp.inf)
        blocks = padded.reshape(nq, qc, nk, kc).max((1, 3))  # [nq, nk]
        _, top = jax.lax.top_k(blocks, min(cfg.idx_topk // kc, nk))
        chosen = jnp.zeros((nq, nk), bool).at[
            jnp.arange(nq)[:, None], top].set(True)
        sel = chosen[rows // qc][:, rows // kc]
        own = (rows // kc)[:, None] == (rows // kc)[None, :]
        return causal & (sel | own | (rows < cfg.idx_topk)[:, None])
    if sw["forced_tail_and_sink"]:
        forced = (rows[None, :] == 0) | (rows[:, None] == rows[None, :])
        scores = jnp.where(forced, jnp.inf, scores)
    _, top = jax.lax.top_k(scores, cfg.idx_topk)
    sel = jnp.zeros((t, t), bool).at[rows[:, None], top].set(True)
    return causal & (sel | (rows < cfg.idx_topk)[:, None])


def attention(r, cfg: K.KeyeVL2Config, x, raw, positions3, sw: dict,
              picks=None, chunks=None):
    """``x [T, dim]`` (normed; the raw rows under ``post_norm``), ``raw``
    the residual rows the indexer reads under ``index_reads_residual``.
    Returns (the sublayer's output ``[T, dim]``, a look: keys, values
    ``[T, KV, hd]`` as cached, the indexer's keys ``[T, dI]``, the index
    scores and the picked mask ``[T, T]``)."""
    t = x.shape[0]
    h, kv, hd = cfg.heads, cfg.kv_heads, cfg.head_dim
    q = (x @ r["q"]).reshape(t, h, hd)
    k = (x @ r["k"]).reshape(t, kv, hd)
    v = (x @ r["v"]).reshape(t, kv, hd)
    if not sw["no_qk_norm"]:
        q = rms_norm(q, r["q_norm"], cfg.norm_eps)
        k = rms_norm(k, r["k_norm"], cfg.norm_eps)
    angles = mrope_angles(cfg, positions3)
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    scores, ki = index_scores(
        r, cfg, raw if sw["index_reads_residual"] else x, positions3[0], sw)
    seen = picked_mask(cfg, scores, sw, chunks) if picks is None else picks
    kr, vr = (jnp.repeat(a, h // kv, axis=1) for a in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, kr) / hd ** 0.5
    pr = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    ctx = jnp.einsum("hqk,khd->qhd", pr, vr).reshape(t, h * hd)
    return ctx @ r["o"], {"k": k, "v": v, "ik": ki, "scores": scores,
                          "picked": seen}


def route(r, cfg: K.KeyeVL2Config, x):
    """Chosen experts [T, k] and their weights [T, k]: softmax over every
    expert, the ``top_k`` largest, renormalised over the chosen."""
    w, ids = jax.lax.top_k(jax.nn.softmax(x @ r["router"], -1), cfg.top_k)
    if cfg.norm_topk:
        w = w / w.sum(-1, keepdims=True)
    return ids, w


def moe(r, cfg: K.KeyeVL2Config, x, held=None):
    """The expert layer on rows ``x [T, dim]``: the routed sum over
    ``chosen ∩ held`` (every expert in ``r["experts"]`` when ``held`` is
    None)."""
    ids, w = route(r, cfg, x)
    y = jnp.zeros_like(x)
    for e in (r["experts"] if held is None else held):
        w_e = (w * (ids == e)).sum(-1)  # 0 where e was not chosen
        y = y + swiglu(r["experts"][e], x) * w_e[:, None]
    return y


def forward(rparams, cfg: K.KeyeVL2Config, tokens, held=None, rows=False,
            picks=None, positions3=None, chunks=None, **switches):
    """Logits ``[T, vocab]`` of the whole sequence ``tokens [T]``; with
    ``rows`` also every layer's look (:func:`attention`'s, with the
    sublayer's output rows under ``"attended"``). ``picks``: a picked mask
    a layer, in place of this file's own."""
    unknown = set(switches) - set(SWITCHES)
    if unknown:
        raise TypeError(f"unknown switches {sorted(unknown)}")
    sw = {**AS_SERVED, **switches}
    t = len(tokens)
    if positions3 is None:
        positions3 = jnp.broadcast_to(jnp.arange(t), (3, t))
    kept = []
    with jax.default_matmul_precision("highest"):
        x = rparams["embed"][jnp.asarray(tokens)]
        for i in range(cfg.layers):
            r = rparams["blocks"][str(i)]
            given = None if picks is None else picks[i]
            if sw["post_norm"]:
                a, look = attention(r, cfg, x, x, positions3, sw, given, chunks)
                x = x + rms_norm(a, r["attn_norm"], cfg.norm_eps)
                x = x + rms_norm(moe(r, cfg, x, held), r["ffn_norm"],
                                 cfg.norm_eps)
            else:
                a, look = attention(
                    r, cfg, rms_norm(x, r["attn_norm"], cfg.norm_eps), x,
                    positions3, sw, given, chunks)
                x = x + a
                x = x + moe(r, cfg, rms_norm(x, r["ffn_norm"], cfg.norm_eps),
                            held)
            kept.append({**look, "attended": a})
        x = rms_norm(x, rparams["out_norm"], cfg.norm_eps)
        logits = x @ rparams["lm_head"]
    return (logits, kept) if rows else logits
