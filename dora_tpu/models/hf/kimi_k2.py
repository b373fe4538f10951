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
the rank is the process's (``DORA_EP_RANK``); see ``models/moe.expert_share``,
where the layer itself lives (K-EXAONE and GLM-5.3-Flash run it too).

Plain ``jax.numpy`` + ``ops/int8_matmul``: no fused kernel is written
here. The float32 reference of the same mathematics (expanded MLA, a
Python loop over the experts, no cache) is ``kimi_k2_reference.py``.
Text path only: K2.5's vision tower is not part of this module.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp

from dora_tpu.models import layers as L
from dora_tpu.models import moe
from dora_tpu.models import paged_model as PM
from dora_tpu.models.hf.loader import TensorFiles, read_config
from dora_tpu.models.paged_window import make_paged_window
from dora_tpu.ops.int8_matmul import quantize_int8_t as _quantize_t

MODEL_TYPES = ("kimi_k2", "deepseek_v3")

#: rows of one attention block (a multiple of the page): the pool is read
#: this many positions at a time, up to the longest live context.
ATTN_BLOCK = 512
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
        first, held = moe.expert_share(config, ep_rank)
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


# ---------------------------------------------------------------------------
# loading: one layer at a time, only the held experts, int8 on the device
# ---------------------------------------------------------------------------


def load_layer(get, cfg: KimiK2Config, i: int, prefix: str = "model.") -> dict:
    """Layer ``i``'s serving parameters from ``get(name) -> device
    array`` under the HF tensor names. Reads the held experts only."""
    lp = f"{prefix}layers.{i}."
    a = lp + "self_attn."
    # q_a and kv_a read the same row: one matrix, padded to a lane multiple
    kv_a = get(a + "kv_a_proj_with_mqa.weight")
    width = cfg.q_rank + cfg.latent
    kv_a = moe.pad_outputs(kv_a, kv_a.shape[0] + (-width) % 128)
    kvb = _quantize_t(get(a + "kv_b_proj.weight"))  # [kv_rank, H*(nope+v)]
    block = {
        "attn_norm": get(lp + "input_layernorm.weight"),
        "w_qkv_a": _quantize_t(get(a + "q_a_proj.weight"), kv_a),
        "q_norm": get(a + "q_a_layernorm.weight"),
        "w_q_b": _quantize_t(get(a + "q_b_proj.weight")),
        "kv_norm": get(a + "kv_a_layernorm.weight"),
        "w_kv_b": L.mla_kv_b_weights(kvb, cfg),
        "wo": _quantize_t(get(a + "o_proj.weight")),
        "ffn_norm": get(lp + "post_attention_layernorm.weight"),
    }
    m = lp + "mlp."
    if i < cfg.first_dense:
        block["dense"] = moe.swiglu_weights(get, m)
        return block
    return {**block, **moe.expert_layer_weights(get, cfg, m)}


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

        ctx = L.attend_latent_blocks(
            cfg, q, rows_of, visible, positions.max() // block + 1,
            "bhc,btc->bht", "bht,btc->bhc",
        )
        return L.mla_output(blk, cfg, ctx), pool


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

        ctx = L.attend_latent_blocks(
            cfg, q, rows_of, visible, (position + c - 1) // block + 1,
            "qhc,tc->qht", "qht,tc->qhc",
        )
        return L.mla_output(blk, cfg, ctx), pool


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
        y, counters = moe.mlp(
            blk, cfg, L.rms_norm(x, blk["ffn_norm"], cfg.norm_eps), live,
            counted,
        )
        x = x + y
        moe.add_layer(stats, per_layer, counters, decode)
    moe.add_stack(stats, per_layer, counted, decode)
    return x, pools, stats


def paged_batch_logits(params, cfg: KimiK2Config, tokens, pools, stats,
                       positions, block_tables, block: int = ATTN_BLOCK):
    """One decode step for B independent sequences over the latent
    pools: tokens/positions [B], block_tables [B, max_pages] (0 = the
    null page; a frozen row comes with position 0 and a zeroed table
    row, which is also how this step knows it: its routing is neither
    computed on nor counted). ``stats`` are the routing counters
    (``moe.init_counters``). Returns (logits [B, vocab] f32, pools,
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
    return PM.head_logits(params, cfg, x), pools, stats


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
    return PM.head_logits(params, cfg, x), pools, stats


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
    """The K-tick decode window (models/paged_window.make_paged_window)
    over :func:`fused_paged_batch_step`: the pools and the counters ride
    the window's carry together and come back apart. Returns (the
    window's own results, pools last; stats)."""
    def batch(tokens, carried, positions, bts):
        nxt, pools, stats = fused_paged_batch_step(
            params, cfg, tokens, *carried, positions, bts, block=block)
        return nxt, (pools, stats)

    *out, (pools, stats) = make_paged_window(batch, k=k, eos=eos)(
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


def report(cfg: KimiK2Config, page_size: int, totals: dict, engine) -> dict:
    """The gauges of one engine (``paged_model.build_engine``'s
    ``report``): the routing counters' sums and the latent pool."""
    alloc = engine.allocator
    row_bytes = page_pool_bytes(cfg, page_size) // page_size
    return {
        **moe.report(totals, cfg.moe_layers),
        "latent_rows_in_use": alloc.in_use * page_size,
        "latent_pool_bytes": alloc.num_pages * page_size * row_bytes,
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
    (models/paged_window.make_paged_window) as the Qwen engine, with this
    module's two programs (``paged_model.build_engine``; the pools and
    the routing counters are arguments 2 and 3 of both, hence the
    donation). ``num_pages`` defaults to :func:`default_num_pages`.
    Speculation, LoRA and int8 pages are not offered for this model
    (KNOWN_ISSUES.md)."""
    chunk = PM.default_chunk(chunk, cfg.max_seq)
    attn_block = PM.default_attn_block(attn_block, ATTN_BLOCK, chunk,
                                       cfg.max_seq, page_size)
    if num_pages is None:
        num_pages = default_num_pages(cfg, max_slots, page_size)

    def step(p, ids, pools, stats, position, bt, valid):
        return fused_paged_chunk_step(p, cfg, ids, pools, stats, position,
                                      bt, valid, block=attn_block)

    return PM.build_engine(
        "kimi_k2", cfg, params,
        window_program=lambda p, k, *args: window_program(
            p, cfg, k, eos, attn_block, *args),
        chunk_step=step, donate_window=(2, 3), donate_chunk=(2, 3),
        init_page_pool=lambda n: init_page_pool(cfg, n, page_size),
        counters=moe.init_counters(cfg), report=partial(report, cfg, page_size),
        not_offered=NOT_OFFERED, flops_per_token=flops_per_token(cfg),
        max_slots=max_slots, eos=eos, page_size=page_size, chunk=chunk,
        num_pages=num_pages, window=window, prefix_cache=prefix_cache,
        prefix_cache_pages=prefix_cache_pages)
