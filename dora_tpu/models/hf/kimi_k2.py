"""Kimi-K2 / DeepSeek-V3 causal LM (``model_type`` ``kimi_k2`` or
``deepseek_v3``) on the paged serving path, as ONE RANK of an expert
group.

The block (HF ``DeepseekV3`` semantics): multi-head latent attention
(MLA) — queries through a rank-``q_lora_rank`` bottleneck, keys and
values through one shared ``kv_lora_rank`` latent ``c_kv`` plus one
roped key ``k_pe`` that all heads share — then either a dense SwiGLU
(the first ``first_k_dense_replace`` layers) or an expert layer: a
sigmoid router over ALL ``n_routed_experts``, top-k of the biased
scores, unbiased normalised weights times ``routed_scaling_factor``,
plus ``n_shared_experts`` that every token takes. YaRN rotary on the
``qk_rope_head_dim`` part only.

What this module adds to the serving path:

* **a latent page pool** — per layer one ``kv`` array ``[P, page, row]``
  holding the normalised ``c_kv`` and the roped ``k_pe`` of each cached
  position, 576 values padded to 640 (a lane multiple; see
  ``KimiK2Config.row``): 1,280 B a row a layer at bf16 for Kimi-K2,
  against 32,768 B for the expanded 64-head K/V. The engine (models/batch_engine.PagedBatchEngine) carries it as
  an opaque pytree like every other pool.
* **absorbed attention** — ``W_kvb`` folded into the query
  (``q' = W_kvb^K^T q_nope``) and into the output
  (``o = W_kvb^V (P c_kv)``), so decode and the prefill chunk are
  multi-QUERY attention over the latent rows: the 64 heads share one
  ``[T, 576]`` key/value stream read straight from the pool's pages, in
  blocks, with a running softmax, for as many blocks as the longest
  live context needs.
* **an expert layer that is told which experts it holds** — the router
  keeps its published width; this rank computes the part of the result
  that its own ``experts_held`` experts (``expert_first`` onward) give,
  for the pairs that land on them, and leaves out what the absent
  experts would add. The weights are normalised over all chosen
  experts. One chip runs the layer without its exchange.

The share: ``ep_size`` is the checkpoint's (``config.json``, HF's key),
the rank is the process's (``DORA_EP_RANK``); see :func:`expert_share`.

Plain ``jax.numpy`` + ``ops/int8_matmul``: no fused kernel is written
here. The float32 reference of the same mathematics (expanded MLA, a
Python loop over the experts, no cache) is ``kimi_k2_reference.py``.
Text path only: K2.5's vision tower is not part of this module.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from pathlib import Path

import jax
import jax.numpy as jnp

from dora_tpu import profiling
from dora_tpu.models import layers as L
from dora_tpu.models.hf.loader import TensorFiles, read_config
from dora_tpu.ops.int8_matmul import quantize_int8_t as _quantize_t

MODEL_TYPES = ("kimi_k2", "deepseek_v3")

#: rows of one attention block (a multiple of the page): the pool is read
#: this many positions at a time, up to the longest live context.
ATTN_BLOCK = 512
#: rows one expert computes at a time in a prefill chunk. A decode batch
#: of at most this many rows goes to a touched expert whole.
EXPERT_BLOCK = 32
#: share of the device memory left after the weights that the default
#: latent pool may take (the rest is the programs' temporaries).
POOL_SHARE_OF_FREE = 0.5

#: serving knobs of the Qwen path that this model refuses (KNOWN_ISSUES.md)
NOT_OFFERED = {
    "DORA_KV_INT8": "the int8 page kernels are written for per-head K/V "
                    "planes, not latent pages",
    "DORA_SPEC_K": "the speculative window's verify pass is the Qwen path's",
    "DORA_LORA_DIR": "the grouped LoRA matmul is fused into the Qwen kernels",
}


@dataclass(frozen=True)
class KimiK2Config:
    vocab: int
    dim: int
    layers: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    ffn: int
    moe_ffn: int
    n_experts: int  # the router's width: every expert of the model
    top_k: int
    n_shared: int
    first_dense: int
    routed_scale: float
    norm_topk: bool
    norm_eps: float
    rope_theta: float
    #: (factor, original_max, beta_fast, beta_slow, mscale, mscale_all_dim)
    yarn: tuple | None
    max_seq: int
    #: this rank's share: experts ``expert_first .. +experts_held``
    expert_first: int
    experts_held: int

    @property
    def latent(self) -> int:
        """Width of one cached row: ``c_kv`` then ``k_pe``."""
        return self.kv_rank + self.rope

    @property
    def row(self) -> int:
        """Width of one row AS STORED: ``latent`` padded with zeros to a
        multiple of 128 lanes (640 for Kimi-K2's 576). XLA:TPU keeps an
        array whose minor dimension is no lane multiple transposed
        (``{0,2,1}``) and then copies the whole pool into and out of
        every program that scatters into it (compiled for a described
        v5e: 2 copies of 302 MB a layer); a lane multiple keeps it
        row-major and updated in place."""
        return -(-self.latent // 128) * 128

    @property
    def moe_layers(self) -> int:
        return self.layers - self.first_dense

    @property
    def softmax_scale(self) -> float:
        scale = (self.nope + self.rope) ** -0.5
        if self.yarn is not None and self.yarn[5]:
            m = L.yarn_mscale(self.yarn[0], self.yarn[5])
            scale *= m * m
        return scale

    @classmethod
    def from_hf(cls, config: dict, max_seq: int | None = None,
                ep_rank: int | None = None) -> "KimiK2Config":
        if config.get("model_type") not in MODEL_TYPES:
            raise ValueError(
                f"model_type {config.get('model_type')!r} is not one of "
                f"{MODEL_TYPES}"
            )
        if not config.get("q_lora_rank"):
            raise NotImplementedError(
                "kimi_k2: a checkpoint without q_lora_rank (DeepSeek-V2-"
                "Lite's plain q_proj) is not supported"
            )
        if config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
            raise NotImplementedError(
                "kimi_k2: group-limited routing (n_group/topk_group > 1, "
                "DeepSeek-V3's own setting) is not written; Kimi-K2 has 1"
            )
        if config.get("scoring_func", "sigmoid") != "sigmoid":
            raise NotImplementedError("kimi_k2: only sigmoid routing")
        first, held = expert_share(config, ep_rank)
        rs = config.get("rope_scaling")
        yarn = None
        if rs:
            if rs.get("type", rs.get("rope_type")) != "yarn":
                raise NotImplementedError(f"kimi_k2: rope_scaling {rs!r}")
            yarn = (
                float(rs["factor"]),
                int(rs["original_max_position_embeddings"]),
                float(rs.get("beta_fast", 32)), float(rs.get("beta_slow", 1)),
                float(rs.get("mscale", 1)), float(rs.get("mscale_all_dim", 0)),
            )
        return cls(
            vocab=config["vocab_size"],
            dim=config["hidden_size"],
            layers=config["num_hidden_layers"],
            heads=config["num_attention_heads"],
            q_rank=config["q_lora_rank"],
            kv_rank=config["kv_lora_rank"],
            nope=config["qk_nope_head_dim"],
            rope=config["qk_rope_head_dim"],
            v_dim=config["v_head_dim"],
            ffn=config["intermediate_size"],
            moe_ffn=config["moe_intermediate_size"],
            n_experts=config["n_routed_experts"],
            top_k=config["num_experts_per_tok"],
            n_shared=config.get("n_shared_experts") or 0,
            first_dense=config.get("first_k_dense_replace", 0),
            routed_scale=float(config.get("routed_scaling_factor", 1.0)),
            norm_topk=bool(config.get("norm_topk_prob", True)),
            norm_eps=config.get("rms_norm_eps", 1e-6),
            rope_theta=float(config.get("rope_theta", 10000.0)),
            yarn=yarn,
            max_seq=max_seq
            or min(config.get("max_position_embeddings", 2048), 2048),
            expert_first=first,
            experts_held=held,
        )


def expert_share(config: dict, ep_rank: int | None = None) -> tuple[int, int]:
    """``(first, held)``: the experts of every layer that this rank
    computes. HF's meaning of the keys: ``n_routed_experts`` counts the
    model's experts and ``ep_size`` the ranks that divide them, each
    holding ``n_routed_experts // ep_size`` consecutive ones. The ranks
    of a group share one checkpoint directory, so ``ep_size`` is its
    ``config.json``'s and nothing else's; which share is this process's
    is the launcher's to say: ``ep_rank``, else ``DORA_EP_RANK``, else 0."""
    total = config["n_routed_experts"]
    ep_size = int(config.get("ep_size") or 1)
    if ep_rank is None:
        ep_rank = int(os.environ.get("DORA_EP_RANK") or 0)
    if total % ep_size or not 0 <= ep_rank < ep_size:
        raise ValueError(
            f"kimi_k2: {total} experts do not divide over ep_size "
            f"{ep_size} (rank {ep_rank})"
        )
    held = total // ep_size
    return ep_rank * held, held


# ---------------------------------------------------------------------------
# loading: one layer at a time, only the held experts, int8 on the device
# ---------------------------------------------------------------------------


def _pad_outputs(w, to: int):
    """Zero output channels up to ``to`` (HF layout: rows are outputs)."""
    return jnp.pad(w, ((0, to - w.shape[0]), (0, 0)))


def _swiglu(get, prefix: str) -> dict:
    return {
        "w_gateup": _quantize_t(
            get(prefix + "gate_proj.weight"), get(prefix + "up_proj.weight")
        ),
        "w_down": _quantize_t(get(prefix + "down_proj.weight")),
    }


def load_layer(get, cfg: KimiK2Config, i: int, prefix: str = "model.") -> dict:
    """Layer ``i``'s serving parameters from ``get(name) -> device
    array`` under the HF tensor names. Reads the held experts only."""
    lp = f"{prefix}layers.{i}."
    a = lp + "self_attn."
    h, nope, v = cfg.heads, cfg.nope, cfg.v_dim
    # q_a and kv_a read the same row: one matrix, padded to a lane multiple
    kv_a = get(a + "kv_a_proj_with_mqa.weight")
    width = cfg.q_rank + cfg.latent
    kv_a = _pad_outputs(kv_a, kv_a.shape[0] + (-width) % 128)
    kvb = _quantize_t(get(a + "kv_b_proj.weight"))  # [kv_rank, H*(nope+v)]
    kvb8 = kvb["int8"].reshape(cfg.kv_rank, h, nope + v)
    kvbs = kvb["scale"].reshape(h, nope + v)
    block = {
        "attn_norm": get(lp + "input_layernorm.weight"),
        "w_qkv_a": _quantize_t(get(a + "q_a_proj.weight"), kv_a),
        "q_norm": get(a + "q_a_layernorm.weight"),
        "w_q_b": _quantize_t(get(a + "q_b_proj.weight")),
        "kv_norm": get(a + "kv_a_layernorm.weight"),
        # W_kvb per head, split for the absorbed form: the key part
        # [H, nope, kv_rank] folds into the query, the value part
        # [H, kv_rank, v] into the output; the scales are per (head, column)
        "w_kv_b": {
            "k8": jnp.transpose(kvb8[:, :, :nope], (1, 2, 0)),
            "ks": kvbs[:, :nope],
            "v8": jnp.transpose(kvb8[:, :, nope:], (1, 0, 2)),
            "vs": kvbs[:, nope:],
        },
        "wo": _quantize_t(get(a + "o_proj.weight")),
        "ffn_norm": get(lp + "post_attention_layernorm.weight"),
    }
    m = lp + "mlp."
    if i < cfg.first_dense:
        block["dense"] = _swiglu(get, m)
        return block
    block["router"] = get(m + "gate.weight").T.astype(L.compute_dtype())
    block["router_bias"] = get(m + "gate.e_score_correction_bias").astype(
        jnp.float32
    )
    if cfg.n_shared:
        block["shared"] = _swiglu(get, m + "shared_experts.")
    block["experts"] = [
        _swiglu(get, f"{m}experts.{e}.")
        for e in range(cfg.expert_first, cfg.expert_first + cfg.experts_held)
    ]
    return block


def load(model_dir: str | Path, max_seq: int | None = None,
         ep_rank: int | None = None):
    """(config, serving params) from a HF checkpoint directory. Tensors
    go from the file to the device one at a time and are quantized there
    (``ops/int8_matmul.quantize_int8``, per output channel), so at most
    one matrix exists in a float format at any moment; the embedding,
    the router and the norms stay in the compute dtype. Experts this
    rank does not hold are never read."""
    cfg = KimiK2Config.from_hf(read_config(model_dir), max_seq, ep_rank)
    files = TensorFiles(model_dir)
    prefix = "model." if "model.embed_tokens.weight" in files else ""
    dtype = L.compute_dtype()

    def get(name: str):
        return jnp.asarray(files.get(name)).astype(dtype)

    params = {
        "embed": get(f"{prefix}embed_tokens.weight"),
        "out_norm": get(f"{prefix}norm.weight"),
        "lm_head": _quantize_t(get("lm_head.weight")),
        "blocks": {
            str(i): load_layer(get, cfg, i, prefix) for i in range(cfg.layers)
        },
    }
    return cfg, params


def quantize_decode(params, cfg=None):
    """The serving layout IS what :func:`load` returns (int8 from the
    start); kept so that ``llm_server`` treats every model module alike."""
    return params


# ---------------------------------------------------------------------------
# the layer, in plain jax.numpy over int8 weights
# ---------------------------------------------------------------------------


def rope_tables(cfg: KimiK2Config):
    if cfg.yarn is None:
        return L.rope_table(cfg.max_seq, cfg.rope, base=cfg.rope_theta)
    factor, original, fast, slow, mscale, all_dim = cfg.yarn
    return L.yarn_rope_table(cfg.max_seq, cfg.rope, cfg.rope_theta, factor,
                             original, fast, slow, mscale, all_dim)


def rotate(x, cos, sin):
    """HF DeepseekV3 rotary on the last axis: de-interleave the pairs
    (even lanes, then odd lanes), then ``rotate_half``. ``cos``/``sin``
    are the positions' half-width rows, broadcastable to ``x[..., :d/2]``."""
    xf = x.astype(jnp.float32)
    pairs = xf.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def mla_project(blk, cfg: KimiK2Config, x, cos, sin):
    """Rows ``x [N, dim]`` (already normed) at rotary rows ``cos/sin
    [N, rope/2]`` -> absorbed queries ``[N, H, row]`` and the cache
    rows ``[N, row]`` (normalised ``c_kv``, roped ``k_pe``, zeros up to
    the stored width)."""
    n = x.shape[0]
    h, nope = cfg.heads, cfg.nope
    a = L.matmul(x, blk["w_qkv_a"])
    c_q = L.rms_norm(a[:, : cfg.q_rank], blk["q_norm"], cfg.norm_eps)
    c_kv = L.rms_norm(
        a[:, cfg.q_rank : cfg.q_rank + cfg.kv_rank], blk["kv_norm"],
        cfg.norm_eps,
    )
    k_pe = a[:, cfg.q_rank + cfg.kv_rank : cfg.q_rank + cfg.latent]
    k_pe = rotate(k_pe, cos, sin)
    q = L.matmul(c_q, blk["w_q_b"]).reshape(n, h, nope + cfg.rope)
    q_pe = rotate(q[..., nope:], cos[:, None], sin[:, None])
    kb = blk["w_kv_b"]
    # q' = W_kvb^K^T q_nope, per head; the per-column scale rides the query
    q_nope = (q[..., :nope].astype(jnp.float32) * kb["ks"]).astype(x.dtype)
    q_abs = jnp.einsum(
        "nhj,hjc->nhc", q_nope, kb["k8"].astype(x.dtype),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    pad = cfg.row - cfg.latent
    return (
        jnp.concatenate(
            [q_abs, q_pe, jnp.zeros((n, h, pad), x.dtype)], axis=-1),
        jnp.concatenate([c_kv, k_pe, jnp.zeros((n, pad), x.dtype)], axis=-1),
    )


def mla_output(blk, cfg: KimiK2Config, ctx):
    """``ctx [N, H, kv_rank]`` (softmax-weighted latent rows, float32)
    -> the attention sublayer's output ``[N, dim]``."""
    kb = blk["w_kv_b"]
    dtype = L.compute_dtype()
    o = jnp.einsum(
        "nhc,hcj->nhj", ctx.astype(dtype), kb["v8"].astype(dtype),
        preferred_element_type=jnp.float32,
    ) * kb["vs"]
    return L.matmul(
        o.astype(dtype).reshape(ctx.shape[0], cfg.heads * cfg.v_dim),
        blk["wo"],
    )


def _attend_blocks(cfg: KimiK2Config, q, rows_of, visible, n_blocks,
                   score: str, mix: str):
    """:func:`layers.attend_blocks` of absorbed queries ``q [..., row]``
    over latent rows: ``rows_of(j)`` gives block ``j``'s rows
    (``[..., block, row]``); ``score`` and ``mix`` are the einsums of
    queries with rows and of probabilities with rows. Returns the
    softmax-weighted ``c_kv`` ``[..., kv_rank]`` in float32."""
    f32 = {"preferred_element_type": jnp.float32}
    return L.attend_blocks(
        q, rows_of, visible, n_blocks,
        lambda q, kv: jnp.einsum(score, q, kv, **f32),
        lambda p, kv: jnp.einsum(
            mix, p.astype(kv.dtype), kv[..., : cfg.kv_rank], **f32),
        scale=cfg.softmax_scale, width=cfg.kv_rank,
    )


def mla_absorbed(blk, cfg: KimiK2Config, x, pool, positions, block_tables,
                 cos, sin, block: int):
    """Decode: ``x [B, dim]`` (normed), one new position a row. Writes
    each row's latent into its page, then attends over positions
    ``0..positions[b]`` through the row's block table. Returns
    (attention output [B, dim], pool)."""
    with jax.named_scope("mla_absorbed"):
        page = pool.shape[1]
        q, rows = mla_project(blk, cfg, x, cos, sin)
        b = x.shape[0]
        pool = pool.at[
            block_tables[jnp.arange(b), positions // page], positions % page
        ].set(rows.astype(pool.dtype))
        per = block // page

        def rows_of(j):
            ids = jax.lax.dynamic_slice_in_dim(block_tables, j * per, per, 1)
            return pool[ids].reshape(b, block, cfg.row)

        def visible(j):
            t = j * block + jnp.arange(block)
            return (t[None, :] <= positions[:, None])[:, None, :]

        ctx = _attend_blocks(
            cfg, q, rows_of, visible, positions.max() // block + 1,
            "bhc,btc->bht", "bht,btc->bhc",
        )
        return mla_output(blk, cfg, ctx), pool


def mla_chunk(blk, cfg: KimiK2Config, x, pool, position, block_table,
              cos, sin, block: int):
    """Prefill chunk, absorbed form: ``x [C, dim]`` (normed) at
    positions ``position..position+C-1`` (page-aligned), one block
    table. Writes the chunk's latents as whole pages, then every row
    attends causally over ``0..its own position``: all heads of all
    rows against one stream of latent rows."""
    with jax.named_scope("mla_chunk"):
        page = pool.shape[1]
        c = x.shape[0]
        q, rows = mla_project(blk, cfg, x, cos, sin)
        ids = jax.lax.dynamic_slice_in_dim(block_table, position // page,
                                           c // page)
        pool = pool.at[ids].set(
            rows.astype(pool.dtype).reshape(c // page, page, cfg.row)
        )
        per = block // page
        q_pos = position + jnp.arange(c)

        def rows_of(j):
            ids = jax.lax.dynamic_slice_in_dim(block_table, j * per, per)
            return pool[ids].reshape(block, cfg.row)

        def visible(j):
            t = j * block + jnp.arange(block)
            return (t[None, :] <= q_pos[:, None])[:, None, :]

        ctx = _attend_blocks(
            cfg, q, rows_of, visible, (position + c - 1) // block + 1,
            "qhc,tc->qht", "qht,tc->qhc",
        )
        return mla_output(blk, cfg, ctx), pool


def swiglu(w: dict, x):
    """``w["limit"]``, where a loader put one beside the matrices (a
    checkpoint's ``swiglu_limit``; Kimi-K2 has none): the gate held to
    ``(-inf, limit]`` and the up part to ``[-limit, limit]`` before
    ``silu(gate) * up``."""
    gate, up = jnp.split(L.matmul(x, w["w_gateup"]), 2, axis=-1)
    if "limit" in w:
        gate = jnp.minimum(gate, w["limit"].astype(gate.dtype))
        up = jnp.clip(up, -w["limit"].astype(up.dtype), w["limit"].astype(up.dtype))
    return L.matmul(jax.nn.silu(gate) * up, w["w_down"])


def route(blk, cfg: KimiK2Config, x):
    """``noaux_tc`` routing with one group: sigmoid scores in float32
    over all experts; the top-k of ``score + bias`` are chosen; the
    weights are the UNBIASED scores of the chosen, normalised over all
    of them, times ``routed_scaling_factor``. Returns (ids [N, k] —
    global expert numbers — and weights [N, k], float32)."""
    with jax.named_scope("moe_router"):
        logits = jnp.dot(
            x.astype(jnp.float32), blk["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        scores = jax.nn.sigmoid(logits)
        _, ids = jax.lax.top_k(scores + blk["router_bias"], cfg.top_k)
        w = jnp.take_along_axis(scores, ids, axis=-1)
        if cfg.norm_topk:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        return ids, w * cfg.routed_scale


def held_experts(blk, cfg: KimiK2Config, x, local, weights, live):
    """This rank's part of the routed sum: ``sum over chosen ∩ held of
    w_i E_i(x)`` for rows ``x [N, dim]``; ``local [N, k]`` numbers the
    chosen experts from this rank's first (outside ``0..held`` = absent). Work follows the pairs that
    land here: an expert no live row chose is skipped (its weights are
    not read), and in a chunk an expert computes only its own rows,
    ``EXPERT_BLOCK`` at a time, gathered and scattered by one-hot
    products. ``live [N]`` masks rows whose result nobody reads (frozen
    decode rows). Returns y [N, dim] in float32."""
    n = x.shape[0]
    y = jnp.zeros((n, cfg.dim), jnp.float32)
    with jax.named_scope("moe_experts"):
        for e, w in enumerate(blk["experts"]):
            hit = (local == e) & live[:, None]  # [N, k]
            mine = hit.any(-1)
            w_e = (weights * hit).sum(-1)  # [N] float32, 0 where not chosen
            n_e = mine.sum().astype(jnp.int32)
            if n <= EXPERT_BLOCK:
                y = jax.lax.cond(
                    n_e > 0,
                    lambda y, w=w, w_e=w_e: y
                    + swiglu(w, x).astype(jnp.float32) * w_e[:, None],
                    lambda y: y,
                    y,
                )
                continue
            # rank of each of the expert's rows among them, in order
            rank = jnp.cumsum(mine) - 1

            def body(j, y, w=w, w_e=w_e, mine=mine, rank=rank):
                slot = j * EXPERT_BLOCK + jnp.arange(EXPERT_BLOCK)
                pick = (mine[None, :] & (rank[None, :] == slot[:, None]))
                pick = pick.astype(x.dtype)  # [block, N] one-hot rows
                out = swiglu(w, pick @ x)  # this block's rows, in order
                back = jnp.dot(pick.T, out, preferred_element_type=jnp.float32)
                return y + back * w_e[:, None]

            blocks = (n_e + EXPERT_BLOCK - 1) // EXPERT_BLOCK
            y = jax.lax.fori_loop(0, blocks, body, y)
    return y


def mlp(blk, cfg: KimiK2Config, x, live, counted):
    """The feed-forward sublayer on normed rows ``x``. Returns (output
    [N, dim], counters or None): for an expert layer ``(rows routed,
    pairs that landed on held experts, rows per held expert [held])``
    over the rows ``counted`` marks."""
    if "dense" in blk:
        with jax.named_scope("dense_mlp"):
            return swiglu(blk["dense"], x), None
    ids, weights = route(blk, cfg, x)
    local = ids - cfg.expert_first
    y = held_experts(blk, cfg, x, local, weights, live)
    if "shared" in blk:
        with jax.named_scope("moe_shared"):
            y = y + swiglu(blk["shared"], x).astype(jnp.float32)
    landed = (local >= 0) & (local < cfg.experts_held) & counted[:, None]
    per_expert = (
        (local[..., None] == jnp.arange(cfg.experts_held)) & landed[..., None]
    ).sum((0, 1)).astype(jnp.int32)
    return y.astype(x.dtype), (
        counted.sum().astype(jnp.int32), landed.sum().astype(jnp.int32),
        per_expert,
    )


def init_counters(cfg: KimiK2Config) -> dict:
    """Routing counters on the device: an operand and a result of their
    own of the window and the chunk program, donated like the pools but
    no part of them (the cache's snapshot, restore and byte count never
    see them). int32 that wraps; :class:`MoeCounters` adds up the
    differences on the host."""
    names = ("tokens", "local_pairs", "decode_ticks", "touched")
    return {
        # a buffer each: the programs donate them one by one
        **{name: jnp.zeros((), jnp.int32) for name in names},
        "expert_tokens": jnp.zeros((cfg.moe_layers, cfg.experts_held),
                                   jnp.int32),
    }


def _layers(params, cfg: KimiK2Config, x, pools, stats, attend, live,
            counted, decode: bool):
    """The stack: ``attend(blk, normed rows, pool) -> (out, pool)``.
    Returns (rows, pools, stats)."""
    pools = dict(pools)
    stats = dict(stats)
    per_layer = []
    for i in range(cfg.layers):
        blk = params["blocks"][str(i)]
        lp = pools[str(i)]
        a, kv = attend(blk, L.rms_norm(x, blk["attn_norm"], cfg.norm_eps),
                       lp["kv"])
        pools[str(i)] = {**lp, "kv": kv}
        x = x + a.astype(x.dtype)
        y, counters = mlp(
            blk, cfg, L.rms_norm(x, blk["ffn_norm"], cfg.norm_eps), live,
            counted,
        )
        x = x + y
        if counters is not None:
            tokens, pairs, per_expert = counters
            stats["tokens"] = stats["tokens"] + tokens
            stats["local_pairs"] = stats["local_pairs"] + pairs
            per_layer.append(per_expert)
            if decode:
                stats["touched"] = stats["touched"] + (per_expert > 0).sum(
                    dtype=jnp.int32
                )
    if per_layer:
        stats["expert_tokens"] = stats["expert_tokens"] + jnp.stack(per_layer)
        if decode:
            stats["decode_ticks"] = stats["decode_ticks"] + counted.any(
            ).astype(jnp.int32)
    return x, pools, stats


def head_logits(params, cfg: KimiK2Config, x):
    h = L.rms_norm(x, params["out_norm"], cfg.norm_eps)
    return L.matmul(h, params["lm_head"]).astype(jnp.float32)


def paged_batch_logits(params, cfg: KimiK2Config, tokens, pools, stats,
                       positions, block_tables, block: int = ATTN_BLOCK):
    """One decode step for B independent sequences over the latent
    pools: tokens/positions [B], block_tables [B, max_pages] (0 = the
    null page; a frozen row comes with position 0 and a zeroed table
    row, which is also how this step knows it: its routing is neither
    computed on nor counted). ``stats`` are the routing counters
    (:func:`init_counters`). Returns (logits [B, vocab] f32, pools,
    stats)."""
    cos_t, sin_t = rope_tables(cfg)
    cos, sin = cos_t[positions], sin_t[positions]
    x = params["embed"].astype(L.compute_dtype())[tokens]
    live = block_tables[:, 0] != 0

    def attend(blk, h, pool):
        return mla_absorbed(blk, cfg, h, pool, positions, block_tables,
                            cos, sin, block)

    x, pools, stats = _layers(params, cfg, x, pools, stats, attend, live,
                              live, True)
    return head_logits(params, cfg, x), pools, stats


def paged_chunk_logits(params, cfg: KimiK2Config, chunk_ids, pools, stats,
                       position, block_table, valid,
                       block: int = ATTN_BLOCK):
    """One prefill chunk into the latent pools: ``chunk_ids [C]`` at
    positions ``position..position+C-1`` (page-aligned), of which the
    first ``valid`` are the prompt's (the engine right-pads the tail
    chunk; pad rows land beyond the prompt where decode overwrites them
    before they are attendable). ``position`` and ``valid`` are traced:
    one program for every chunk. Every row is computed; the routing
    counters ``stats`` count the ``valid`` ones. Returns (logits
    [C, vocab] f32, pools, stats)."""
    c = chunk_ids.shape[0]
    cos_t, sin_t = rope_tables(cfg)
    cos = jax.lax.dynamic_slice_in_dim(cos_t, position, c)
    sin = jax.lax.dynamic_slice_in_dim(sin_t, position, c)
    x = params["embed"].astype(L.compute_dtype())[chunk_ids]
    live = jnp.ones((c,), bool)
    counted = jnp.arange(c) < valid

    def attend(blk, h, pool):
        return mla_chunk(blk, cfg, h, pool, position, block_table, cos, sin,
                         block)

    x, pools, stats = _layers(params, cfg, x, pools, stats, attend, live,
                              counted, False)
    return head_logits(params, cfg, x), pools, stats


def fused_paged_batch_step(params, cfg, tokens, pools, stats, positions,
                           block_tables, block: int = ATTN_BLOCK):
    logits, pools, stats = paged_batch_logits(
        params, cfg, tokens, pools, stats, positions, block_tables, block)
    return jnp.argmax(logits, -1).astype(jnp.int32), pools, stats


def fused_paged_chunk_step(params, cfg, chunk_ids, pools, stats, position,
                           block_table, valid, block: int = ATTN_BLOCK):
    logits, pools, stats = paged_chunk_logits(
        params, cfg, chunk_ids, pools, stats, position, block_table, valid,
        block)
    return jnp.argmax(logits, -1).astype(jnp.int32), pools, stats


def window_program(params, cfg, k: int, eos, block: int, tokens, pools,
                   stats, *rest):
    """The K-tick decode window (models/vlm.make_paged_window) over
    :func:`fused_paged_batch_step`: the pools and the counters ride the
    window's carry together and come back apart. Returns (the window's
    own results, pools last; stats)."""
    from dora_tpu.models import vlm as _vlm

    def batch(tokens, carried, positions, bts):
        nxt, pools, stats = fused_paged_batch_step(
            params, cfg, tokens, *carried, positions, bts, block=block)
        return nxt, (pools, stats)

    *out, (pools, stats) = _vlm.make_paged_window(batch, k=k, eos=eos)(
        tokens, (pools, stats), *rest)
    return (*out, pools), stats


# ---------------------------------------------------------------------------
# the pool and the engine
# ---------------------------------------------------------------------------


def init_page_pool(cfg: KimiK2Config, num_pages: int, page_size: int,
                   dtype=None) -> dict:
    """Latent page pools ``{layer: {"kv": [P, page, row]}}`` (see
    :attr:`KimiK2Config.row`). Page 0 is the null page."""
    dtype = dtype or L.compute_dtype()
    return {
        str(i): {"kv": jnp.zeros((num_pages, page_size, cfg.row), dtype)}
        for i in range(cfg.layers)
    }


def page_pool_bytes(cfg: KimiK2Config, page_size: int) -> int:
    """Bytes one page takes over all layers."""
    return (
        cfg.layers * page_size * cfg.row
        * jnp.dtype(L.compute_dtype()).itemsize
    )


def default_num_pages(cfg: KimiK2Config, max_slots: int,
                      page_size: int) -> int:
    """The latent pool's default size. The rule: every slot may reach
    ``max_seq`` (``max_slots * max_seq`` rows; what is not granted to a
    stream is the prefix cache's), but no more than
    ``POOL_SHARE_OF_FREE`` of the device memory that is free now, after
    the weights; where the device reports no memory figures (the CPU)
    the Qwen engine's ``4 * max_seq`` rows."""
    stats = jax.devices()[0].memory_stats() or {}
    limit, used = stats.get("bytes_limit"), stats.get("bytes_in_use")
    if not limit or used is None:
        return 4 * cfg.max_seq // page_size
    fits = int(
        POOL_SHARE_OF_FREE * (limit - used) // page_pool_bytes(cfg, page_size)
    )
    return max(min(max_slots * cfg.max_seq // page_size, fits),
               2 * cfg.max_seq // page_size)


class MoeCounters:
    """The routing counters of one engine: the device arrays the two
    programs take and give back (``device``), and their host side, which
    adds up the int32 differences. :meth:`read` fetches a few hundred
    bytes; ``llm_server``'s 1 Hz report calls it at a window boundary,
    after ``collect()``, when the arrays are ready and nothing waits."""

    def __init__(self, cfg: KimiK2Config, page_size: int):
        self.device = init_counters(cfg)
        #: set by :func:`make_paged_engine`: whose pages ``read`` counts
        self.allocator = None
        self._rows_per_page = page_size
        self._row_bytes = page_pool_bytes(cfg, page_size) // page_size
        self._last: dict | None = None
        self.totals = {
            "moe_tokens": 0, "moe_local_pairs": 0, "moe_decode_ticks": 0,
            "moe_touched": 0,
            "moe_expert_tokens": [0] * cfg.experts_held,
        }
        self._layers = max(cfg.moe_layers, 1)

    def read(self) -> dict:
        import numpy as np

        now = {
            k: np.asarray(v).astype(np.int64) for k, v in self.device.items()
        }
        last = self._last or {k: np.zeros_like(v) for k, v in now.items()}
        self._last = now
        d = {k: (now[k] - last[k]) & 0xFFFFFFFF for k in now}
        t = self.totals
        t["moe_tokens"] += int(d["tokens"])
        t["moe_local_pairs"] += int(d["local_pairs"])
        t["moe_decode_ticks"] += int(d["decode_ticks"])
        t["moe_touched"] += int(d["touched"])
        t["moe_expert_tokens"] = [
            a + int(b)
            for a, b in zip(t["moe_expert_tokens"], d["expert_tokens"].sum(0))
        ]
        ticks = t["moe_decode_ticks"] * self._layers
        alloc = self.allocator
        return {
            "moe_tokens": t["moe_tokens"],
            "moe_local_pairs": t["moe_local_pairs"],
            "moe_expert_tokens": list(t["moe_expert_tokens"]),
            "moe_experts_touched": (
                round(t["moe_touched"] / ticks, 4) if ticks else None
            ),
            "latent_rows_in_use": alloc.in_use * self._rows_per_page,
            "latent_pool_bytes": alloc.num_pages * self._rows_per_page
            * self._row_bytes,
        }


def flops_per_token(cfg: KimiK2Config) -> float:
    """Weight-matmul FLOPs of one token on this rank (no score term):
    attention, the shared expert, the router, the expected
    ``top_k * held / n_experts`` routed pairs a layer, the dense
    layers and the head."""
    h = cfg.heads
    attn = (
        cfg.dim * (cfg.q_rank + cfg.latent)
        + cfg.q_rank * h * (cfg.nope + cfg.rope)
        + cfg.kv_rank * h * (cfg.nope + cfg.v_dim)
        + h * cfg.v_dim * cfg.dim
    )
    expert = 3 * cfg.dim * cfg.moe_ffn
    moe = (
        cfg.dim * cfg.n_experts + cfg.n_shared * expert
        + cfg.top_k * cfg.experts_held / cfg.n_experts * expert
    )
    dense = 3 * cfg.dim * cfg.ffn
    return 2.0 * (
        cfg.layers * attn + cfg.moe_layers * moe + cfg.first_dense * dense
        + cfg.dim * cfg.vocab
    )


def make_paged_engine(params, cfg: KimiK2Config, *, max_slots: int = 16,
                      eos: int | None = None, page_size: int = 16,
                      chunk: int | None = None,
                      num_pages: int | None = None,
                      window: int | None = None,
                      prefix_cache: bool | None = None,
                      prefix_cache_pages: int | None = None,
                      attn_block: int | None = None):
    """The paged continuous-batching engine
    (models/batch_engine.PagedBatchEngine) over the latent pool: the
    same scheduler, allocator, prefix cache and K-tick window
    (models/vlm.make_paged_window) as the Qwen engine, with this
    module's three closures. ``num_pages`` defaults to
    :func:`default_num_pages`. Speculation, LoRA and int8 pages are not
    offered for this model (KNOWN_ISSUES.md)."""
    from dora_tpu.models.batch_engine import PagedBatchEngine

    for knob, why in NOT_OFFERED.items():
        if os.environ.get(knob, "0") not in ("", "0"):
            raise NotImplementedError(f"kimi_k2: {knob} is not offered: {why}")
    chunk = chunk or min(256, cfg.max_seq)
    if attn_block is None:
        attn_block = ATTN_BLOCK if cfg.max_seq % ATTN_BLOCK == 0 else chunk
    assert attn_block % page_size == 0 and cfg.max_seq % attn_block == 0, (
        attn_block, page_size, cfg.max_seq,
    )
    if num_pages is None:
        num_pages = default_num_pages(cfg, max_slots, page_size)
    if window is None:
        window = int(os.environ.get("DORA_MULTISTEP_K", "8"))
    if prefix_cache is None:
        prefix_cache = os.environ.get("DORA_PREFIX_CACHE", "0") != "0"
    if prefix_cache_pages is None:
        prefix_cache_pages = int(os.environ.get("DORA_PREFIX_CACHE_PAGES", "0"))

    counters = MoeCounters(cfg, page_size)

    # params ride as an argument, never a closed-over constant (see
    # qwen2.make_paged_engine); the pools and the routing counters are
    # arguments 2 and 3, hence the donation. The engine sees the pools
    # alone: the counters stay with ``counters``.
    def window_factory(k, sk):
        assert not sk, "kimi_k2: no speculative window"

        def program(p, *args):
            return window_program(p, cfg, k, eos, attn_block, *args)

        jitted = jax.jit(program, donate_argnums=(2, 3))

        def window_step(tokens, pools, *rest):
            out, counters.device = jitted(params, tokens, pools,
                                          counters.device, *rest)
            return out

        return window_step

    def step(p, ids, pools, stats, position, bt, valid):
        return fused_paged_chunk_step(p, cfg, ids, pools, stats, position,
                                      bt, valid, block=attn_block)

    chunk_jitted = jax.jit(step, donate_argnums=(2, 3))

    def chunk_prefill(ids, pools, position, bt, valid):
        greedy, pools, counters.device = chunk_jitted(
            params, ids, pools, counters.device, position, bt, valid)
        return greedy, pools

    engine = PagedBatchEngine(
        init_pool=lambda n: init_page_pool(cfg, n, page_size),
        chunk_prefill=chunk_prefill,
        chunk_valid_rows=True,
        window_step=window_factory(window, 0),
        window_factory=window_factory,
        window=window,
        max_slots=max_slots,
        max_seq=cfg.max_seq,
        page_size=page_size,
        chunk=chunk,
        num_pages=num_pages,
        eos=eos,
        prefix_cache=prefix_cache,
        prefix_cache_pages=prefix_cache_pages,
    )
    engine.flops_per_token = flops_per_token(cfg)
    engine.device_peak_flops = profiling.detect_peak_flops()
    counters.allocator = engine.allocator
    engine.model_counters = counters.read
    return engine
