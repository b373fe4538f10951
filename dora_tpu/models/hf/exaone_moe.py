"""K-EXAONE causal LM (``model_type`` ``exaone_moe``) on the paged serving
path, as ONE RANK of an expert group: sliding-window layers beside
global ones (``layer_types``, three of 128 rows for every global one in
K-EXAONE-236B-A23B), over a dense SwiGLU in the first layer(s) and a
sigmoid-routed expert layer in the rest.

The layer, as the published ``config.json`` gives it (``†`` = not
settled by the config, an assumption written down in
``KNOWN_ISSUES.md`` "PR 41"; the float32 reference of the same
mathematics, whole sequence, is ``exaone_moe_reference.py``, where each
† is a switch):

    h = RMSNorm(x; input_layernorm)                              † pre-norm
    q, k, v = h Wq, h Wk, h Wv                                   † no bias
    q, k = RMSNorm_head(q; q_norm), RMSNorm_head(k; k_norm)      † one weight for all heads
    sliding_attention: rotate-half rotary on q, k; key j visible to row i iff 0 <= i - j < window
    full_attention:    no rotary †;                 key j visible iff j <= i
    x = x + softmax(q k^T / sqrt(hd)) v Wo
    x = x + MLP(RMSNorm(x; post_attention_layernorm))    dense, or the expert layer

The expert layer is Kimi-K2's letter for letter (``models/moe.py``:
``route``, ``held_experts``, ``mlp``, ``swiglu``, ``expert_share``): the
router keeps its published width, this rank computes what its own
``experts_held`` experts give, nothing stands in for the absent ranks.

What this module adds to the serving path: **layers that differ in
cache kind by layer type.**

* a *window* layer never needs more than its last ``sliding_window``
  rows, so it keeps exactly those, as a **ring a slot**: ``[slots,
  window, 2 * KV * hd]`` a layer, position ``p`` in row ``p % window``,
  roped keys then values. The rings are the engine's slot state
  (``PagedBatchEngine(init_slot_state=...)``), never leaves of the
  pools. A ring row is visible by position alone, so a new stream in a
  used slot needs no zero-start: what an earlier stream left is masked
  until this one has overwritten it. A decode tick writes row ``p %
  window`` and attends the ring in one einsum; a chunk attends ``[the
  ring as it stood] ++ [its own rows]`` under the band mask and then
  writes its last ``min(valid, window)`` valid rows. Padding rows and
  frozen rows leave the ring as it was. A window layer costs the same
  at row 16,000 as at row 200, in both programs.
* a *global* layer has pages under the engine's one block table;
  ``pools`` holds leaves for those layers only, so a cached token costs
  ``global layers x 2 x KV x hd`` values (8,192 B for two global layers
  at bf16) and admission counts pages that a quarter of the layers use.
  A decode tick reads them through the decode kernels' sweep without
  its projections (``ops/decode_block.attention_paged_rows_step``: the
  live rows' own pages, 128 rows a step, nothing for a frozen row); a
  chunk, one stream, goes through ``layers.attend_blocks`` (plain XLA,
  a block of ``ATTN_BLOCK`` rows at a time up to its own position).

Every matrix goes through ``ops/int8_matmul`` (the fused attention
kernels hold ``wqkv`` and ``wo`` whole in VMEM: 113 MB of int8 here), the
head through ``lm_head_argmax``. Text only; the multi-token-prediction
layer is not served (``num_nextn_predict_layers`` must be 0 or is
ignored with its weights unread).
"""

from __future__ import annotations

import logging
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
from dora_tpu.ops import decode_block as DB
from dora_tpu.ops.int8_matmul import quantize_int8_t as _quantize_t

MODEL_TYPES = ("exaone_moe",)

#: rows of one attention block of a global layer's CHUNK (a multiple of
#: the page): its pool is read this many positions at a time, up to the
#: chunk's last position.
ATTN_BLOCK = 256

#: serving knobs of the Qwen path that this model refuses (KNOWN_ISSUES.md)
NOT_OFFERED = {
    "DORA_KV_INT8": "the int8 page kernels are fused into the Qwen "
                    "attention kernels, which this model does not run, and "
                    "a window layer's ring is no page",
    "DORA_SPEC_K": "a rejected draft would have overwritten ring rows that "
                   "the accepted prefix still needs; no snapshot is kept",
    "DORA_LORA_DIR": "the grouped LoRA matmul is fused into the Qwen kernels",
}

#: the window layers' and the global layers' counters on the device
SWA_COUNTERS = (
    "swa_decode_ticks", "swa_row_ticks", "swa_ring_rows_read",
    "global_kv_rows_read", "global_kv_rows_swept", "global_sweep_groups",
    "swa_chunks", "swa_chunk_rows", "swa_chunk_positions",
)

_log = logging.getLogger(__name__)


@dataclass(frozen=True)
class ExaoneMoeConfig:
    vocab: int
    dim: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    moe_ffn: int
    n_experts: int  # the router's width: every expert of the model
    top_k: int
    n_shared: int
    routed_scale: float
    norm_topk: bool
    norm_eps: float
    rope_theta: float
    max_seq: int
    window: int
    #: per layer: True = sliding-window attention, False = global
    sliding: tuple
    #: per layer: True = expert layer, False = dense MLP
    sparse: tuple
    #: this rank's share: experts ``expert_first .. +experts_held``
    expert_first: int
    experts_held: int

    @property
    def q_width(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def window_layers(self) -> tuple:
        return tuple(i for i, s in enumerate(self.sliding) if s)

    @property
    def global_layers(self) -> tuple:
        return tuple(i for i, s in enumerate(self.sliding) if not s)

    @property
    def moe_layers(self) -> int:
        return sum(self.sparse)

    @property
    def kv_bytes_per_token(self) -> int:
        """What a cached position holds in the paged pool: K and V of the
        GLOBAL layers alone (8,192 B for K-EXAONE's two of eight)."""
        return (len(self.global_layers) * 2 * self.kv_width
                * jnp.dtype(L.compute_dtype()).itemsize)

    @property
    def ring_bytes_per_slot(self) -> int:
        """The window layers' rings of one slot."""
        return (len(self.window_layers) * self.window * 2 * self.kv_width
                * jnp.dtype(L.compute_dtype()).itemsize)

    @classmethod
    def from_hf(cls, config: dict, max_seq: int | None = None,
                ep_rank: int | None = None) -> "ExaoneMoeConfig":
        if config.get("model_type") not in MODEL_TYPES:
            raise ValueError(
                f"model_type {config.get('model_type')!r} is not one of "
                f"{MODEL_TYPES}"
            )
        n = config["num_hidden_layers"]
        kinds = config.get("layer_types")
        if kinds is None or len(kinds) != n:
            raise ValueError(
                f"exaone_moe: layer_types must name all {n} layers, got "
                f"{kinds!r}")
        unknown = set(kinds) - {"sliding_attention", "full_attention"}
        if unknown:
            raise NotImplementedError(
                f"exaone_moe: layer_types {sorted(unknown)} is not written")
        sliding = tuple(k == "sliding_attention" for k in kinds)
        window = config.get("sliding_window")
        if any(sliding) and not window:
            raise ValueError(
                "exaone_moe: sliding_attention layers need sliding_window")
        mlp_kinds = config.get("mlp_layer_types")
        if mlp_kinds is None:
            dense = config.get("first_k_dense_replace", 0)
            mlp_kinds = ["dense"] * dense + ["sparse"] * (n - dense)
        if len(mlp_kinds) != n or set(mlp_kinds) - {"dense", "sparse"}:
            raise ValueError(
                f"exaone_moe: mlp_layer_types must name all {n} layers as "
                f"'dense' or 'sparse', got {mlp_kinds!r}")
        if config.get("scoring_func", "sigmoid") != "sigmoid":
            raise NotImplementedError(
                f"exaone_moe: scoring_func {config['scoring_func']!r} is not "
                f"written (only sigmoid)")
        if config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
            raise NotImplementedError(
                "exaone_moe: group-limited routing (n_group/topk_group > 1) "
                "is not written; K-EXAONE has 1")
        for key in ("attention_bias", "mlp_bias"):
            if config.get(key):
                raise NotImplementedError(f"exaone_moe: {key} is not written")
        rope = config.get("rope_parameters") or {}
        if rope.get("rope_type", "default") != "default" or config.get(
                "rope_scaling"):
            raise NotImplementedError(
                f"exaone_moe: scaled rotary "
                f"{config.get('rope_scaling') or rope!r} is not written")
        first, held = moe.expert_share(
            {"n_routed_experts": config["num_experts"],
             "ep_size": config.get("ep_size")}, ep_rank)
        return cls(
            vocab=config["vocab_size"],
            dim=config["hidden_size"],
            layers=n,
            heads=config["num_attention_heads"],
            kv_heads=config["num_key_value_heads"],
            head_dim=config.get("head_dim")
            or config["hidden_size"] // config["num_attention_heads"],
            ffn=config["intermediate_size"],
            moe_ffn=config["moe_intermediate_size"],
            n_experts=config["num_experts"],
            top_k=config["num_experts_per_tok"],
            n_shared=config.get("num_shared_experts") or 0,
            routed_scale=float(config.get("routed_scaling_factor", 1.0)),
            norm_topk=bool(config.get("norm_topk_prob", True)),
            norm_eps=config.get("rms_norm_eps", 1e-5),
            rope_theta=float(
                rope.get("rope_theta", config.get("rope_theta", 1e6))),
            max_seq=max_seq
            or min(config.get("max_position_embeddings", 2048), 2048),
            window=int(window or 0),
            sliding=sliding,
            sparse=tuple(k == "sparse" for k in mlp_kinds),
            expert_first=first,
            experts_held=held,
        )


# ---------------------------------------------------------------------------
# loading: one layer at a time, only the held experts, int8 on the device
# ---------------------------------------------------------------------------


def load_layer(get, cfg: ExaoneMoeConfig, i: int, prefix: str = "model.") -> dict:
    """Layer ``i``'s serving parameters from ``get(name) -> device
    array`` under the HF tensor names (EXAONE-4's, with DeepSeek-V3's for
    the expert layer: †). Reads the held experts only."""
    lp = f"{prefix}layers.{i}."
    a, m = lp + "self_attn.", lp + "mlp."
    block = {
        "attn_norm": get(lp + "input_layernorm.weight"),
        "wqkv": _quantize_t(get(a + "q_proj.weight"), get(a + "k_proj.weight"),
                            get(a + "v_proj.weight")),
        "q_norm": get(a + "q_norm.weight"),
        "k_norm": get(a + "k_norm.weight"),
        "wo": _quantize_t(get(a + "o_proj.weight")),
        "ffn_norm": get(lp + "post_attention_layernorm.weight"),
    }
    if not cfg.sparse[i]:
        block["dense"] = moe.swiglu_weights(get, m)
        return block
    return {**block, **moe.expert_layer_weights(get, cfg, m)}


def load(model_dir: str | Path, max_seq: int | None = None,
         ep_rank: int | None = None):
    """(config, serving params) from a HF checkpoint directory, as
    ``kimi_k2.load``: tensors go from the file to the device one at a
    time and are quantized there, the embedding, the routers and the
    norms stay in the compute dtype, absent experts are never read."""
    cfg = ExaoneMoeConfig.from_hf(read_config(model_dir), max_seq, ep_rank)
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
# attention: a ring for the window layers, pages for the global ones
# ---------------------------------------------------------------------------


def _qkv(blk, cfg: ExaoneMoeConfig, u, rope):
    """Normed rows ``u [N, dim]`` -> q ``[N, KV, G, hd]``, k and v
    ``[N, KV, hd]``: projected, q and k normed over the head, and roped
    where ``rope`` (``(cos, sin) [N, hd/2]``) is given."""
    n = u.shape[0]
    kv, hd = cfg.kv_heads, cfg.head_dim
    p = L.matmul(u, blk["wqkv"])
    q = p[:, : cfg.q_width].reshape(n, cfg.heads, hd)
    k = p[:, cfg.q_width : cfg.q_width + cfg.kv_width].reshape(n, kv, hd)
    v = p[:, cfg.q_width + cfg.kv_width :].reshape(n, kv, hd)
    with jax.named_scope("qk_norm"):
        q = L.rms_norm(q, blk["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, blk["k_norm"], cfg.norm_eps)
    if rope is not None:
        cos, sin = rope
        q = L.rotate_half(q, cos[:, None], sin[:, None])
        k = L.rotate_half(k, cos[:, None], sin[:, None])
    return q.reshape(n, kv, cfg.heads // kv, hd), k, v


def _split_rows(cfg: ExaoneMoeConfig, rows):
    """Cached rows ``[..., 2 * KV * hd]`` -> keys, values ``[..., KV, hd]``."""
    rows = rows.reshape(*rows.shape[:-1], 2, cfg.kv_heads, cfg.head_dim)
    return rows[..., 0, :, :], rows[..., 1, :, :]


def _softmax_mix(cfg: ExaoneMoeConfig, s, seen, v, mix: str):
    """Whole softmax of float32 scores ``s`` under ``seen``, then the
    values' sum (``mix`` einsum). float32 out."""
    s = jnp.where(seen, s * cfg.head_dim ** -0.5, -1e30)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = jnp.where(seen, p, 0.0)
    out = jnp.einsum(mix, p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out / p.sum(-1)[..., None]


def _out(blk, cfg: ExaoneMoeConfig, ctx, dtype):
    return L.matmul(ctx.astype(dtype).reshape(-1, cfg.q_width), blk["wo"])


def window_decode(blk, cfg: ExaoneMoeConfig, u, ring, positions, active, rope):
    """A window layer's decode tick: ``u [B, dim]`` (normed), row = slot,
    ``ring [B, W, 2 * KV * hd]``. An active row writes its K/V into ring
    row ``p % W`` and attends the ring rows that hold positions ``p - W +
    1 .. p`` (all of them once ``p >= W - 1``; before that the rows past
    ``p`` are an earlier stream's and masked). Returns (output [B, dim],
    ring)."""
    with jax.named_scope("attn_window"):
        b, w = u.shape[0], cfg.window
        q, k, v = _qkv(blk, cfg, u, rope)
        rows, at = jnp.arange(b), positions % w
        new = L.kv_rows(cfg, k, v).astype(ring.dtype)
        ring = ring.at[rows, at].set(
            jnp.where(active[:, None], new, ring[rows, at]))
        keys, values = _split_rows(cfg, ring)  # [B, W, KV, hd]
        j = jnp.arange(w)
        seen = (j[None, :] <= positions[:, None]) | (positions[:, None] >= w)
        s = jnp.einsum("bkgd,bwkd->bkgw", q, keys,
                       preferred_element_type=jnp.float32)
        ctx = _softmax_mix(cfg, s, seen[:, None, None, :], values,
                           "bkgw,bwkd->bkgd")
        return _out(blk, cfg, ctx, u.dtype), ring


def window_chunk(blk, cfg: ExaoneMoeConfig, u, ring, slot, position, valid,
                 rope):
    """A window layer's prefill chunk: ``u [C, dim]`` (normed) at
    positions ``position..position+C-1``, of which the first ``valid``
    are the prompt's; ``ring [slots, W, ...]``, row ``slot`` this
    stream's. Every row attends ``[the ring as it stood] ++ [the chunk's
    own rows]`` under the band mask (a ring row counts by the position it
    holds: none at position 0), then the last ``min(valid, W)`` valid
    rows go into the ring. Returns (output [C, dim], ring)."""
    with jax.named_scope("attn_window"):
        c, w = u.shape[0], cfg.window
        q, k, v = _qkv(blk, cfg, u, rope)
        mine = ring[slot]  # [W, 2 * KV * hd]
        j = jnp.arange(w)
        # the position ring row j holds once position - 1 was written
        last = position - 1
        held = last - (last - j) % w  # < 0: not this stream's
        new = L.kv_rows(cfg, k, v).astype(ring.dtype)
        keys, values = _split_rows(cfg, jnp.concatenate([mine, new], 0))
        key_pos = jnp.concatenate([held, position + jnp.arange(c)])
        q_pos = position + jnp.arange(c)
        back = q_pos[:, None] - key_pos[None, :]
        seen = (back >= 0) & (back < w) & (key_pos[None, :] >= 0)
        s = jnp.einsum("qkgd,tkd->qkgt", q, keys,
                       preferred_element_type=jnp.float32)
        ctx = _softmax_mix(cfg, s, seen[:, None, None, :], values,
                           "qkgt,tkd->qkgd")
        # ring row j <- the last valid chunk row whose position is j mod W
        end = position + valid - 1
        src = end - (end - j) % w - position
        mine = jnp.where((src >= 0)[:, None], new[jnp.maximum(src, 0)], mine)
        ring = jax.lax.dynamic_update_index_in_dim(ring, mine, slot, 0)
        return _out(blk, cfg, ctx, u.dtype), ring


def global_decode(blk, cfg: ExaoneMoeConfig, u, pool, positions, block_tables,
                  counts):
    """A global layer's decode tick: writes each row's K/V into its page
    (a frozen row's, at position 0 of a zeroed table row, into the null
    page), then row ``b`` attends its first ``counts[b]`` positions
    (``positions[b] + 1`` of an active row, 0 of a frozen one, which
    gets zeros) through the block table, its own pages and no others
    (``attention_paged_rows_step``). No rotary. Returns (output [B,
    dim], pool)."""
    with jax.named_scope("attn_global"):
        page = pool.shape[1]
        b = u.shape[0]
        q, k, v = _qkv(blk, cfg, u, None)
        pool = pool.at[
            block_tables[jnp.arange(b), positions // page], positions % page
        ].set(L.kv_rows(cfg, k, v).astype(pool.dtype))
        ctx = DB.attention_paged_rows_step(q, pool, counts, block_tables)
        return _out(blk, cfg, ctx, u.dtype), pool


def global_chunk(blk, cfg: ExaoneMoeConfig, u, pool, position, block_table,
                 block: int):
    """A global layer's prefill chunk: writes the chunk's K/V as whole
    pages, then every row attends causally over ``0..its own position``."""
    with jax.named_scope("attn_global"):
        page = pool.shape[1]
        c = u.shape[0]
        q, k, v = _qkv(blk, cfg, u, None)
        ids = jax.lax.dynamic_slice_in_dim(block_table, position // page,
                                           c // page)
        pool = pool.at[ids].set(
            L.kv_rows(cfg, k, v).astype(pool.dtype).reshape(
                c // page, page, 2 * cfg.kv_width))
        per = block // page
        q_pos = position + jnp.arange(c)

        def kv_of(j):
            ids = jax.lax.dynamic_slice_in_dim(block_table, j * per, per)
            return _split_rows(cfg, pool[ids].reshape(block, -1))

        def visible(j):
            t = j * block + jnp.arange(block)
            return (t[None, :] <= q_pos[:, None])[:, None, None, :]

        ctx = L.attend_kv_blocks(
            cfg, q, kv_of, visible, (position + c - 1) // block + 1,
            "qkgd,tkd->qkgt", "qkgt,tkd->qkgd")
        return _out(blk, cfg, ctx, u.dtype), pool


# ---------------------------------------------------------------------------
# the stack, the two programs
# ---------------------------------------------------------------------------


def init_counters(cfg: ExaoneMoeConfig) -> dict:
    """The counters on the device, an operand and a result of their own
    of both programs (a buffer each: donated one by one), int32 that
    wraps: ``moe`` are the expert layer's routing counters
    (``moe.init_counters``), ``swa`` this module's (:data:`SWA_COUNTERS`)."""
    return {
        "moe": moe.init_counters(cfg),
        "swa": {name: jnp.zeros((), jnp.int32) for name in SWA_COUNTERS},
    }


def _layers(params, cfg: ExaoneMoeConfig, x, pools, state, stats, window,
            attend, live, counted, decode: bool):
    """The stack: ``window(blk, normed rows, ring) -> (out, ring)`` for a
    sliding layer, ``attend(blk, normed rows, pool) -> (out, pool)`` for
    a global one, then ``moe.mlp``. Returns (rows, pools, state, the
    routing counters)."""
    pools, state = dict(pools), dict(state)
    routed = dict(stats)
    per_layer = []
    for i in range(cfg.layers):
        blk, key = params["blocks"][str(i)], str(i)
        u = L.rms_norm(x, blk["attn_norm"], cfg.norm_eps)
        if cfg.sliding[i]:
            a, ring = window(blk, u, state[key]["kv"])
            state[key] = {"kv": ring}
        else:
            a, kv = attend(blk, u, pools[key]["kv"])
            pools[key] = {"kv": kv}
        x = x + a.astype(x.dtype)
        y, counters = moe.mlp(
            blk, cfg, L.rms_norm(x, blk["ffn_norm"], cfg.norm_eps), live,
            counted)
        x = x + y
        moe.add_layer(routed, per_layer, counters, decode)
    moe.add_stack(routed, per_layer, counted, decode)
    return x, pools, state, routed


def _rope_rows(cfg: ExaoneMoeConfig, positions):
    cos, sin = L.rope_table(cfg.max_seq, cfg.head_dim, base=cfg.rope_theta)
    return cos[positions], sin[positions]


def paged_batch_rows(params, cfg: ExaoneMoeConfig, tokens, pools, state, stats,
                     positions, block_tables, active):
    """One decode step for B = slots independent sequences: tokens,
    positions, active ``[B]``, block_tables ``[B, max_pages]`` (a frozen
    row comes with position 0 and a zeroed table row, so its global K/V
    write lands in the null page; its rings have no null row and are
    kept by its ``active`` bit; its routing is neither computed on nor
    counted). Returns (the final rows [B, dim], pools, state, stats)."""
    rope = _rope_rows(cfg, positions)
    x = params["embed"].astype(L.compute_dtype())[tokens]
    seen = jnp.where(active, positions + 1, 0)  # rows each row attends

    def window(blk, u, ring):
        return window_decode(blk, cfg, u, ring, positions, active, rope)

    def attend(blk, u, pool):
        return global_decode(blk, cfg, u, pool, positions, block_tables, seen)

    x, pools, state, routed = _layers(
        params, cfg, x, pools, state, stats["moe"], window, attend, active,
        active, True)
    i32 = jnp.int32
    live = active.sum(dtype=i32)
    # the (row, group) steps one global layer's sweep holds: a group is
    # DB's page group of cache rows, fetched whole for its last row
    group = DB.sweep_group_rows(
        next(iter(pools.values()))["kv"].shape[1], block_tables.shape[1])
    groups = ((seen + group - 1) // group).sum(dtype=i32)
    swa = PM.add_counts(
        stats["swa"],
        swa_decode_ticks=(live > 0).astype(i32), swa_row_ticks=live,
        swa_ring_rows_read=len(cfg.window_layers) * jnp.minimum(
            seen, cfg.window).sum(dtype=i32),
        global_kv_rows_read=len(cfg.global_layers) * seen.sum(dtype=i32),
        global_kv_rows_swept=len(cfg.global_layers) * group * groups,
        global_sweep_groups=groups,
    )
    return x, pools, state, {"moe": routed, "swa": swa}


def paged_chunk_rows(params, cfg: ExaoneMoeConfig, chunk_ids, pools, state,
                     stats, position, block_table, valid, slot,
                     block: int = ATTN_BLOCK):
    """One prefill chunk of the stream in ``slot``: ``chunk_ids [C]`` at
    positions ``position..position+C-1`` (page-aligned), of which the
    first ``valid`` are the prompt's. ``position``, ``valid`` and
    ``slot`` are traced: one program for every chunk. Every row is
    computed; the routing counters count the ``valid`` ones."""
    c = chunk_ids.shape[0]
    rope = _rope_rows(cfg, position + jnp.arange(c))
    x = params["embed"].astype(L.compute_dtype())[chunk_ids]
    counted = jnp.arange(c) < valid

    def window(blk, u, ring):
        return window_chunk(blk, cfg, u, ring, slot, position, valid, rope)

    def attend(blk, u, pool):
        return global_chunk(blk, cfg, u, pool, position, block_table, block)

    x, pools, state, routed = _layers(
        params, cfg, x, pools, state, stats["moe"], window, attend,
        jnp.ones((c,), bool), counted, False)
    i32 = jnp.int32
    swa = PM.add_counts(
        stats["swa"], swa_chunks=jnp.ones((), i32),
        swa_chunk_rows=valid.astype(i32),
        swa_chunk_positions=position.astype(i32))
    return x, pools, state, {"moe": routed, "swa": swa}


paged_batch_logits, fused_paged_batch_step = PM.under_the_head(paged_batch_rows)
paged_chunk_logits, fused_paged_chunk_step = PM.under_the_head(paged_chunk_rows)


def window_program(params, cfg, k: int, eos, tokens, pools, stats,
                   positions, bts, active, emitted, max_new, state):
    """The K-tick decode window (models/paged_window.make_paged_window with a
    slot state) over :func:`fused_paged_batch_step`: the counters ride
    the window's carry beside the rings and come back apart. Returns
    (the window's own results — pools, then state, last — and stats)."""
    def batch(tokens, pools, positions, bts, active, carried):
        nxt, pools, state, stats = fused_paged_batch_step(
            params, cfg, tokens, pools, *carried, positions, bts, active)
        return nxt, pools, (state, stats)

    *out, (state, stats) = make_paged_window(
        batch, k=k, eos=eos, slot_state=True)(
        tokens, pools, positions, bts, active, emitted, max_new,
        (state, stats))
    return (*out, state), stats


# ---------------------------------------------------------------------------
# the pool, the rings and the engine
# ---------------------------------------------------------------------------


def init_page_pool(cfg: ExaoneMoeConfig, num_pages: int, page_size: int,
                   dtype=None) -> dict:
    """K/V page pools of the GLOBAL layers alone, ``{layer: {"kv": [P,
    page, 2 * KV * hd]}}``: a cached position is one row a layer, its
    keys then its values (row-major with a lane multiple as the minor
    dimension, so XLA:TPU scatters into it in place). Page 0 is the null
    page."""
    dtype = dtype or L.compute_dtype()
    shape = (num_pages, page_size, 2 * cfg.kv_width)
    return {str(i): {"kv": jnp.zeros(shape, dtype)} for i in cfg.global_layers}


def init_slot_state(cfg: ExaoneMoeConfig, max_slots: int) -> dict:
    """The rings of every slot, for the WINDOW layers alone: ``{layer:
    {"kv": [slots, window, 2 * KV * hd]}}``."""
    shape = (max_slots, cfg.window, 2 * cfg.kv_width)
    return {str(i): {"kv": jnp.zeros(shape, L.compute_dtype())}
            for i in cfg.window_layers}


def page_pool_bytes(cfg: ExaoneMoeConfig, page_size: int) -> int:
    """Bytes one page takes over the layers that have pages."""
    return page_size * cfg.kv_bytes_per_token


def default_num_pages(cfg: ExaoneMoeConfig, max_slots: int,
                      page_size: int) -> int:
    """The pool's default size, ``paged_model.default_num_pages``' rule in
    bytes. At K-EXAONE's cut on a 16 GB v5e the cap does not bind: 16 x
    16,384 rows x 8,192 B = 2.15 GB, every slot may reach ``max_seq``."""
    return PM.default_num_pages(
        page_pool_bytes(cfg, page_size), max_slots, cfg.max_seq, page_size)


def report(cfg: ExaoneMoeConfig, page_size: int, totals: dict, engine) -> dict:
    """The gauges of one engine (``paged_model.build_engine``'s
    ``report``): the routing counters under the names every expert-layer
    model gives them (``moe.report``), this module's own, the pool and
    the rings."""
    return {
        **moe.report(totals["moe"], cfg.moe_layers),
        # raw, for a reader that takes it over a capture's ticks
        "moe_touched": int(totals["moe"]["touched"]),
        **{name: int(totals["swa"][name]) for name in SWA_COUNTERS},
        "kv_bytes_per_token": cfg.kv_bytes_per_token,
        "kv_pool_bytes": engine.allocator.num_pages * page_pool_bytes(
            cfg, page_size),
        "kv_pages_free": engine.allocator.free_pages,
        "swa_ring_bytes": cfg.ring_bytes_per_slot * engine.max_slots,
    }


def flops_per_token(cfg: ExaoneMoeConfig) -> float:
    """Weight-matmul FLOPs of one token on this rank (no score term):
    attention, the dense layers, the shared expert, the router, the
    expected ``top_k * held / n_experts`` routed pairs a layer, the head."""
    attn = cfg.dim * (cfg.q_width + 2 * cfg.kv_width) + cfg.q_width * cfg.dim
    expert = 3 * cfg.dim * cfg.moe_ffn
    moe = (cfg.dim * cfg.n_experts + cfg.n_shared * expert
           + cfg.top_k * cfg.experts_held / cfg.n_experts * expert)
    dense = 3 * cfg.dim * cfg.ffn
    return 2.0 * (
        cfg.layers * attn + cfg.moe_layers * moe
        + (cfg.layers - cfg.moe_layers) * dense + cfg.dim * cfg.vocab)


def make_paged_engine(params, cfg: ExaoneMoeConfig, *, max_slots: int = 16,
                      eos: int | None = None, page_size: int = 16,
                      chunk: int | None = None,
                      num_pages: int | None = None,
                      window: int | None = None,
                      prefix_cache: bool | None = None,
                      prefix_cache_pages: int | None = None,
                      attn_block: int | None = None):
    """The paged continuous-batching engine
    (models/batch_engine.PagedBatchEngine) with the window layers' rings
    as its slot state and pages for the global layers alone: the same
    scheduler, allocator and K-tick window as the other families
    (``paged_model.build_engine``; the pools, the counters and the rings
    are arguments 2, 3 and 9 of the window and 2, 3 and 6 of the chunk,
    hence the donation). ``num_pages`` defaults to
    :func:`default_num_pages`. **No prefix cache, whatever is asked**: a
    granted prefix would need the window layers' last ``sliding_window``
    rows at its end, and none are kept at a page boundary. Speculation,
    LoRA and int8 pages are not offered (KNOWN_ISSUES.md, PR 41)."""
    if cfg.window % page_size:
        raise NotImplementedError(
            f"exaone_moe: sliding_window {cfg.window} is no multiple of the "
            f"page ({page_size} rows)")
    if prefix_cache or prefix_cache_pages:
        _log.warning(
            "exaone_moe: the prefix cache is off for this model: a granted "
            "prefix needs the window layers' rows at its end, and none are "
            "kept")
    chunk = PM.default_chunk(chunk, cfg.max_seq)
    attn_block = PM.default_attn_block(attn_block, ATTN_BLOCK, chunk,
                                       cfg.max_seq, page_size)
    if num_pages is None:
        num_pages = default_num_pages(cfg, max_slots, page_size)

    def step(p, ids, pools, stats, position, bt, state, valid, slot):
        return fused_paged_chunk_step(p, cfg, ids, pools, state, stats,
                                      position, bt, valid, slot,
                                      block=attn_block)

    return PM.build_engine(
        "exaone_moe", cfg, params,
        window_program=lambda p, k, *args: window_program(
            p, cfg, k, eos, *args),
        chunk_step=step, donate_window=(2, 3, 9), donate_chunk=(2, 3, 6),
        init_page_pool=lambda n: init_page_pool(cfg, n, page_size),
        init_slot_state=lambda slots: init_slot_state(cfg, slots),
        counters=init_counters(cfg), report=partial(report, cfg, page_size),
        not_offered=NOT_OFFERED, flops_per_token=flops_per_token(cfg),
        max_slots=max_slots, eos=eos, page_size=page_size, chunk=chunk,
        num_pages=num_pages, window=window, prefix_cache=False,
        prefix_cache_pages=0)
