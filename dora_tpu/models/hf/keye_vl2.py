"""Keye-VL-2.0's language model (``model_type`` ``KeyeVL2``) on the paged
serving path, as ONE RANK of an expert group: grouped-query attention
whose cached rows a learned indexer picks (DeepSeek-V3.2's lightning
indexer set on GQA pages: every earlier POSITION is scored, the ``topk``
best are attended), over a softmax-routed expert layer, in every layer.

The layer, as the published ``config.json`` names it (``†`` = a detail
the config does not settle, an assumption written down in
``KNOWN_ISSUES.md`` "PR 49"; the float32 reference of the same
mathematics, whole sequence, is ``keye_vl2_reference.py``, where each †
is a switch). Rows ``h [T, dim]``, ``t`` a row's position:

    u = rmsnorm(h)                                                  †6 pre-norm
    q = u Wq [H, hd];  k = u Wk, v = u Wv [KV, hd]                  no bias
    q, k = rmsnorm_head(q; q_norm), rmsnorm_head(k; k_norm)         †6 one weight for all heads
    rotary (rotate-half, rope_theta; mrope_section with three equal
      components IS plain rotary: ids only, no tower) on q, k
    indexer: qI = u WqI [J, dI];  kI = layernorm(u WkI) [dI]        †1 †2
             rotary over all dI dims with the model's theta on both  †2
             w = (u Ww) J^-1/2 dI^-1/2 [J]                           †3
             I(t, s) = sum_j w[t, j] relu(qI[t, j] . kI[s]),  s <= t   float32
    S_t = {0..t} while t + 1 <= topk, else the topk positions of
          largest I(t, .), ties to the lower position                †4 †5
    o[t, h] = sum_{s in S_t} softmax_s(q[t, h] . k[s, g(h)] / sqrt(hd)) v[s, g(h)]
    h = h + concat(o) Wo
    u' = rmsnorm(h);  p = softmax(u' Wr) over every expert, float32
    the top_k largest, w_e = p_e / sum_chosen p (norm_topk_prob)
    h = h + sum_{e in chosen and held} w_e down_e(silu(gate_e u') * up_e u')

What this module adds to the serving path: **K/V pages read through a
per-row, per-tick selection.**

* two leaves a layer in ``pools`` under the one block table, at the same
  row rate: ``"kv" [P, page, 2 * KV * hd]`` (a position's roped keys then
  its values: K-EXAONE's row) and ``"ik"``, the indexer's key of that
  position: ``[P, page, dI]`` in memory, kept as ``[P, page / 2, 2 dI]``
  so that a row is 128 lanes (:attr:`KeyeVL2Config.idx_pack`). No slot
  state: a page holds all a position left behind, so the prefix cache
  stays on, over two-leaf pages.
* a decode tick writes the row's ``kv`` and ``ik``, then takes the LIVE
  rows ``DECODE_ROWS`` at a time (a frozen row scores nothing): scores
  their own pages a block of ``INDEX_BLOCK`` positions at a time up to
  their longest context, names the ``topk`` best without a sort
  (``ops/picked_ids``: the ``topk``-th largest by counts, the ids by
  compare-and-sum, in ascending position: ``lax.top_k``'s set, ties to
  the lower position), finds the picked rows in the pool, gathers them in
  one XLA gather and attends those alone (``ops/picked_rows``:
  ``pool_rows``, ``attend_rows``).
  ``dsa_rows_fetched`` = what that gather reads, fixed by its shapes:
  ``topk`` a row of every whole group (no kernel copies rows by count:
  Mosaic names no single row of such a leaf, ``KNOWN_ISSUES.md`` "PR 50").
* a chunk writes whole pages, every row scores and picks as a tick at
  its position would, and attention runs over cached blocks of
  ``ATTN_BLOCK`` rows under the picked mask (``layers.attend_kv_blocks``;
  the counters say which was done: ``dsa_chunk_rows_fetched`` against
  ``dsa_chunk_rows_picked``). The mask is the scores held to each row's
  ``topk``-th largest, equal scores to the lower position: no scatter.

The expert layer's loop and counters are ``models/moe.py``'s
(``held_experts``, ``swiglu_weights``, the counters); its router here is
the softmax form (:func:`route`: ``moe.route`` is sigmoid + bias), with
the dozen lines of ``moe.mlp`` around it (:func:`mlp`) and the loader of
Qwen3-MoE's tensor names. Every matrix goes through ``ops/int8_matmul``,
the head through ``lm_head_argmax``. **The vision tower is not here**:
the catalog's ``config`` carries no ``vision_config``, so there is no
published width to build it from; ids come from the vocabulary.
"""

from __future__ import annotations

import math
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
from dora_tpu.ops.picked_ids import picked_ids
from dora_tpu.ops.picked_rows import attend_rows, pool_rows

MODEL_TYPES = ("KeyeVL2",)

#: rows of one block of cached K/V rows in a CHUNK's product under the
#: picked mask (a multiple of the page)
ATTN_BLOCK = 256
#: positions of one block of cached indexer keys in both programs'
#: scoring loop (a multiple of the page): work follows the longest context
INDEX_BLOCK = 2048
#: live rows a decode tick scores, picks for (one grid step of
#: ``ops/picked_ids``), gathers and attends at a time: its selection
#: follows the rows that are live, not the slots
DECODE_ROWS = 4
#: eps of the indexer's LayerNorm (DeepSeek-V3.2's; no key of the config)
INDEX_NORM_EPS = 1e-6

#: serving knobs of the Qwen path that this model refuses (KNOWN_ISSUES.md)
NOT_OFFERED = {
    "DORA_KV_INT8": "the int8 page kernels are fused into the Qwen "
                    "attention kernels, which read no picked rows and no "
                    "indexer keys",
    "DORA_SPEC_K": "the speculative window verifies a draft through the "
                   "Qwen kernels' dense sweep, not through a selection",
    "DORA_LORA_DIR": "the grouped LoRA matmul is fused into the Qwen kernels",
}

#: the selection's counters on the device: GLM-5.3-Flash's names and
#: meanings (one reader serves both), and three of this module's that its
#: shares divide by (``dsa_decode_ticks``, ``dsa_row_ticks`` = live rows
#: summed over ticks, ``dsa_chunk_rows`` = prompt rows prefilled);
#: ``dsa_rows_fetched`` = the rows the tick's gather reads (its shapes')
DSA_COUNTERS = (
    "dsa_decode_ticks", "dsa_row_ticks", "dsa_chunk_rows",
    "dsa_rows_in_context", "dsa_rows_picked", "dsa_rows_fetched",
    "dsa_index_rows_scored", "dsa_row_ticks_selecting",
    "dsa_chunk_rows_in_context", "dsa_chunk_rows_picked",
    "dsa_chunk_rows_fetched", "dsa_chunk_index_rows_scored",
    "dsa_chunk_rows_selecting",
)

_HIGHEST = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class KeyeVL2Config:
    vocab: int
    dim: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    moe_ffn: int
    n_experts: int  # the router's width: every expert of the model
    top_k: int
    norm_topk: bool
    norm_eps: float
    rope_theta: float
    #: rotary frequencies a position component: (temporal, height, width)
    mrope_section: tuple
    max_seq: int
    idx_heads: int
    idx_dim: int
    idx_topk: int
    #: this rank's share: experts ``expert_first .. +experts_held``
    expert_first: int
    experts_held: int
    # what ``moe.ExpertLayerConfig`` names and this model has none of
    n_shared: int = 0
    routed_scale: float = 1.0

    @property
    def q_width(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def moe_layers(self) -> int:
        return self.layers

    @property
    def idx_pack(self) -> int:
        """Positions whose indexer keys share one cached row of 128 lanes
        (2 at Keye-VL-2.0's 64): XLA:TPU copies a whole pool leaf into and
        out of every program that scatters into it unless its minor
        dimension is a lane multiple (``tests/test_chip_compile.py``)."""
        return max(1, 128 // self.idx_dim)

    @property
    def kv_bytes_per_token(self) -> int:
        """What a cached position holds in the paged pool: K, V and the
        indexer's key of every layer (2,176 B a layer at bf16: 26,112 B
        for twelve)."""
        return (self.layers * (2 * self.kv_width + self.idx_dim)
                * jnp.dtype(L.compute_dtype()).itemsize)

    @classmethod
    def from_hf(cls, config: dict, max_seq: int | None = None,
                ep_rank: int | None = None) -> "KeyeVL2Config":
        if config.get("model_type") not in MODEL_TYPES:
            raise ValueError(
                f"model_type {config.get('model_type')!r} is not one of "
                f"{MODEL_TYPES}"
            )
        if config.get("sliding_window") or config.get("use_sliding_window"):
            raise NotImplementedError(
                f"keye_vl2: sliding_window {config.get('sliding_window')!r} "
                f"is not written (Keye-VL-2.0 has none: the indexer picks)")
        if config.get("mlp_only_layers"):
            raise NotImplementedError(
                f"keye_vl2: mlp_only_layers {config['mlp_only_layers']!r} is "
                f"not written (every layer is an expert layer)")
        if config.get("decoder_sparse_step", 1) != 1:
            raise NotImplementedError(
                f"keye_vl2: decoder_sparse_step "
                f"{config['decoder_sparse_step']!r} is not written (only 1)")
        if config.get("tie_word_embeddings"):
            raise NotImplementedError(
                "keye_vl2: tied embeddings are not written (the head is a "
                "matrix of its own)")
        if config.get("attention_bias"):
            raise NotImplementedError("keye_vl2: attention_bias is not written")
        rope = config.get("rope_scaling") or {}
        kind = rope.get("rope_type", rope.get("type", "default"))
        if kind != "default":
            raise NotImplementedError(
                f"keye_vl2: scaled rotary {rope!r} is not written")
        head_dim = config.get("head_dim") or (
            config["hidden_size"] // config["num_attention_heads"])
        section = tuple(rope.get("mrope_section") or (head_dim // 2,))
        if sum(section) != head_dim // 2:
            raise ValueError(
                f"keye_vl2: mrope_section {list(section)} does not add up to "
                f"head_dim / 2 = {head_dim // 2}")
        sa = config.get("sa_config")
        if not sa:
            raise ValueError(
                "keye_vl2: no sa_config: the indexer's sizes are not given")
        if sa.get("indexer_num_kv_heads", 1) != 1:
            raise NotImplementedError(
                f"keye_vl2: indexer_num_kv_heads "
                f"{sa['indexer_num_kv_heads']!r} is not written (one key a "
                f"position)")
        first, held = moe.expert_share(
            {"n_routed_experts": config["num_experts"],
             "ep_size": config.get("ep_size")}, ep_rank)
        return cls(
            vocab=config["vocab_size"],
            dim=config["hidden_size"],
            layers=config["num_hidden_layers"],
            heads=config["num_attention_heads"],
            kv_heads=config["num_key_value_heads"],
            head_dim=head_dim,
            moe_ffn=config["moe_intermediate_size"],
            n_experts=config["num_experts"],
            top_k=config["num_experts_per_tok"],
            norm_topk=bool(config.get("norm_topk_prob", True)),
            norm_eps=config.get("rms_norm_eps", 1e-6),
            rope_theta=float(config.get("rope_theta", 1e6)),
            mrope_section=section,
            max_seq=max_seq
            or min(config.get("max_position_embeddings", 2048), 2048),
            idx_heads=sa["indexer_num_heads"],
            idx_dim=sa["indexer_head_dim"],
            idx_topk=sa["topk"],
            expert_first=first,
            experts_held=held,
        )


# ---------------------------------------------------------------------------
# loading: one layer at a time, only the held experts, int8 on the device
# ---------------------------------------------------------------------------


def load_layer(get, cfg: KeyeVL2Config, i: int, prefix: str = "model.") -> dict:
    """Layer ``i``'s serving parameters from ``get(name) -> device
    array`` under the HF tensor names (Qwen3-MoE's for attention, norms
    and the expert layer, which has no ``e_score_correction_bias``;
    DeepSeek-V3.2's for the indexer, with ``wq`` in place of ``wq_b``:
    †1). Reads the held experts only."""
    lp = f"{prefix}layers.{i}."
    a, m = lp + "self_attn.", lp + "mlp."
    heads = get(a + "indexer.weights_proj.weight")
    return {
        "attn_norm": get(lp + "input_layernorm.weight"),
        # the attention's and the indexer's projections read the same row:
        # one matrix (the indexer's head weights padded to a lane multiple)
        "wqkv": _quantize_t(
            get(a + "q_proj.weight"), get(a + "k_proj.weight"),
            get(a + "v_proj.weight"), get(a + "indexer.wq.weight"),
            get(a + "indexer.wk.weight"),
            moe.pad_outputs(heads, heads.shape[0] + (-heads.shape[0]) % 128)),
        "q_norm": get(a + "q_norm.weight"),
        "k_norm": get(a + "k_norm.weight"),
        "idx_norm_w": get(a + "indexer.k_norm.weight").astype(jnp.float32),
        "idx_norm_b": get(a + "indexer.k_norm.bias").astype(jnp.float32),
        "wo": _quantize_t(get(a + "o_proj.weight")),
        "ffn_norm": get(lp + "post_attention_layernorm.weight"),
        "router": get(m + "gate.weight").T.astype(L.compute_dtype()),
        "experts": moe.stack_experts(get, cfg, m),
    }


def load(model_dir: str | Path, max_seq: int | None = None,
         ep_rank: int | None = None):
    """(config, serving params) from a HF checkpoint directory, as
    ``kimi_k2.load``: tensors go from the file to the device one at a
    time and are quantized there, the embedding, the routers and the
    norms stay in the compute dtype, absent experts are never read. The
    language model's tensors alone (``visual.*`` is never asked for)."""
    cfg = KeyeVL2Config.from_hf(read_config(model_dir), max_seq, ep_rank)
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
# attention under the indexer's picks
# ---------------------------------------------------------------------------


def rope_rows(cfg: KeyeVL2Config, positions):
    """``(cos, sin)`` of the attention heads and of the indexer at
    ``positions [N]``, each ``[N, width / 2]``. With ids alone the three
    M-RoPE components of a position are equal, so ``mrope_section`` picks
    every frequency from the same angle: plain rotary (the reference
    rotates by sections, and a test gives it unequal components)."""
    def rows(width):
        cos, sin = L.rope_table(cfg.max_seq, width, base=cfg.rope_theta)
        return cos[positions], sin[positions]

    return rows(cfg.head_dim), rows(cfg.idx_dim)


def project(blk, cfg: KeyeVL2Config, u, rope):
    """Normed rows ``u [N, dim]`` -> (q ``[N, KV, G, hd]``, k and v ``[N,
    KV, hd]``: q and k normed over the head and roped; the indexer's
    queries ``[N, J, dI]`` and its key ``[N, dI]`` float32 — LayerNorm,
    then rotary — and its head weights ``[N, J]`` float32)."""
    f32 = jnp.float32
    n = u.shape[0]
    kv, hd = cfg.kv_heads, cfg.head_dim
    (cos, sin), (icos, isin) = rope
    with jax.named_scope("attn_qkv"):
        p = L.matmul(u, blk["wqkv"])
    o1 = cfg.q_width
    o2 = o1 + cfg.kv_width
    o3 = o2 + cfg.kv_width
    o4 = o3 + cfg.idx_heads * cfg.idx_dim
    o5 = o4 + cfg.idx_dim
    q = p[:, :o1].reshape(n, cfg.heads, hd)
    k = p[:, o1:o2].reshape(n, kv, hd)
    v = p[:, o2:o3].reshape(n, kv, hd)
    with jax.named_scope("qk_norm"):
        q = L.rms_norm(q, blk["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, blk["k_norm"], cfg.norm_eps)
    q = L.rotate_half(q, cos[:, None], sin[:, None])
    k = L.rotate_half(k, cos[:, None], sin[:, None])
    with jax.named_scope("dsa_index"):
        qi = L.rotate_half(p[:, o3:o4].reshape(n, cfg.idx_heads, cfg.idx_dim),
                           icos[:, None], isin[:, None])
        ki = p[:, o4:o5].astype(f32)
        ki = ki - ki.mean(-1, keepdims=True)
        ki = ki * jax.lax.rsqrt(jnp.mean(ki * ki, -1, keepdims=True)
                                + INDEX_NORM_EPS)
        ki = L.rotate_half(ki * blk["idx_norm_w"] + blk["idx_norm_b"],
                           icos, isin)
        wi = p[:, o5 : o5 + cfg.idx_heads].astype(f32) * (
            cfg.idx_heads ** -0.5 * cfg.idx_dim ** -0.5)
    return q.reshape(n, kv, cfg.heads // kv, hd), k, v, qi, ki, wi


def index_scores(cfg: KeyeVL2Config, qi, wi, keys_of, seen, n_blocks,
                 block: int):
    """``I(t, s) = sum_j w[t, j] relu(qI[t, j] . kI[s])`` in float32 over
    the cached keys a block at a time: qi ``[..., J, dI]``, wi ``[...,
    J]``, ``keys_of(j)`` block ``j``'s keys ``[..., block, dI]`` or
    ``[block, dI]``, ``seen [...]`` = how many positions each row may
    score. Returns ``[..., max_seq]``, ``-inf`` from ``seen`` on and past
    block ``n_blocks`` (traced: work follows the longest context)."""
    lead = qi.shape[:-2]
    s0 = jnp.full((*lead, cfg.max_seq), -jnp.inf, jnp.float32)

    def body(j, s):
        keys = keys_of(j)
        dots = jnp.einsum(
            "...jd,...nd->...jn" if keys.ndim > 2 else "...jd,nd->...jn",
            qi, keys.astype(qi.dtype), preferred_element_type=jnp.float32)
        part = (jax.nn.relu(dots) * wi[..., None]).sum(-2)
        at = j * block + jnp.arange(block)
        part = jnp.where(at < seen[..., None], part, -jnp.inf)
        return jax.lax.dynamic_update_slice_in_dim(
            s, part, j * block, s.ndim - 1)

    return jax.lax.fori_loop(0, n_blocks, body, s0)


def _split_rows(cfg: KeyeVL2Config, rows):
    """Cached rows ``[..., 2 * KV * hd]`` -> keys, values ``[..., KV, hd]``."""
    rows = rows.reshape(*rows.shape[:-1], 2, cfg.kv_heads, cfg.head_dim)
    return rows[..., 0, :, :], rows[..., 1, :, :]


def _out(blk, cfg: KeyeVL2Config, ctx, dtype):
    return L.matmul(ctx.astype(dtype).reshape(-1, cfg.q_width), blk["wo"])


def decode_group(slots: int) -> int:
    """The rows of one group of a decode tick's selection: ``DECODE_ROWS``,
    or what of it divides the slots."""
    return math.gcd(DECODE_ROWS, slots)


def dsa_decode(blk, cfg: KeyeVL2Config, u, pool, positions, block_tables,
               live, rope, block: int):
    """A layer's decode tick: ``u [B, dim]`` (normed), row = slot. Each
    row's K|V and indexer key go to its page (a frozen row's, at position
    0 of a zeroed table row, to the null page). Then the LIVE rows alone
    (``live`` = the slots with the live ones first, and how many they
    are), ``DECODE_ROWS`` at a time: a row at ``t >= topk`` scores
    positions ``0..t`` of its own pages and attends the ``topk`` best;
    below that it attends ``0..t``. Either way ``topk`` rows a live row
    are gathered through the block table; a frozen row scores, picks and
    gathers nothing and puts out zeros. Returns (output [B, dim], pool, a
    look at the selection: the rows attended ``"rows" [B]``, the picked
    positions ``"picked" [B, topk]``, ascending, and the output rows)."""
    f32 = jnp.float32
    kvp, ikp = pool["kv"], pool["ik"]
    page, k_ = kvp.shape[1], cfg.idx_topk
    b = u.shape[0]
    rows, t = jnp.arange(b), positions
    q, k, v, qi, ki, wi = project(blk, cfg, u, rope)
    pages = block_tables[rows, t // page]
    kvp = kvp.at[pages, t % page].set(L.kv_rows(cfg, k, v).astype(kvp.dtype))
    # the key shares its cached row with its neighbours': read, place, write
    pack = ikp.shape[2] // cfg.idx_dim
    at = (t % page) // pack
    lane = jnp.arange(pack * cfg.idx_dim) // cfg.idx_dim
    ikp = ikp.at[pages, at].set(jnp.where(
        lane[None, :] == (t % pack)[:, None],
        jnp.tile(ki.astype(ikp.dtype), (1, pack)), ikp[pages, at]))
    order, n_live = live
    r = decode_group(b)
    first = jnp.broadcast_to(jnp.arange(k_), (r, k_))
    per = block // page
    flat = kvp.reshape(-1, kvp.shape[-1])  # a cached row a position

    def group(g, carry):
        ctx, seen_rows, picked = carry
        mine = jax.lax.dynamic_slice_in_dim(order, g * r, r)  # slots
        ok = g * r + jnp.arange(r) < n_live
        t_g, bt = t[mine], block_tables[mine]
        selecting = ok & (t_g >= k_)

        def scored(_):
            def keys_of(j):
                ids = jax.lax.dynamic_slice_in_dim(bt, j * per, per, 1)
                return ikp[ids].reshape(r, block, cfg.idx_dim)

            with jax.named_scope("dsa_index"):
                s = index_scores(
                    cfg, qi[mine], wi[mine], keys_of,
                    jnp.where(selecting, t_g + 1, 0),
                    jnp.where(selecting, t_g, 0).max() // block + 1, block)
            with jax.named_scope("dsa_select"):
                return picked_ids(s, k_)

        ids = jax.lax.cond(selecting.any(), scored, lambda _: first, None)
        with jax.named_scope("dsa_select"):
            ids = jnp.where(selecting[:, None], ids, first)
            seen = (selecting[:, None] | (ids <= t_g[:, None])) & ok[:, None]
            held = flat[pool_rows(bt, ids, page)]
        with jax.named_scope("dsa_attend"):
            mix = attend_rows(q[mine], held, seen)
        # a short last group's spare entries are frozen slots: zeros there
        return (ctx.at[mine].set(mix),
                seen_rows.at[mine].set(seen.sum(-1, dtype=jnp.int32)),
                picked.at[mine].set(ids))

    ctx, seen_rows, picked = jax.lax.fori_loop(
        0, (n_live + r - 1) // r, group,
        (jnp.zeros(q.shape, f32), jnp.zeros((b,), jnp.int32),
         jnp.broadcast_to(jnp.arange(k_), (b, k_))))
    with jax.named_scope("dsa_attend"):
        out = _out(blk, cfg, ctx, u.dtype)
    return out, {"kv": kvp, "ik": ikp}, {
        "rows": seen_rows, "picked": picked, "attended": out}


def kth_largest(s, k: int):
    """The ``k``-th largest of each row of float32 ``s [..., N]``, exactly
    and without a sort: the floats' bit patterns put in their order as
    unsigned keys, and the key found a bit at a time, 32 counts of the
    row. Returns (the keys ``[..., N]``, the ``k``-th largest ``[...]``)."""
    u32 = jnp.uint32
    bits = jax.lax.bitcast_convert_type(jnp.where(s == 0, 0.0, s), u32)
    keys = jnp.where(bits >> 31 == 1, ~bits, bits | u32(1 << 31))

    def body(i, found):
        trial = found | (u32(1 << 31) >> i.astype(u32))
        enough = (keys >= trial[..., None]).sum(-1) >= k
        return jnp.where(enough, trial, found)

    return keys, jax.lax.fori_loop(0, 32, body, jnp.zeros(s.shape[:-1], u32))


def picked_mask(cfg: KeyeVL2Config, s, q_pos, ids: bool = False):
    """Index scores ``s [C, max_seq]`` (``-inf`` where a row may not
    look) -> (which positions each row picked ``[C, max_seq]`` bool, and
    with ``ids`` the picked positions themselves ``[C, topk]``, else
    None): the scores held to the row's ``topk``-th largest
    (:func:`kth_largest`), equal scores going to the lower positions as
    ``lax.top_k`` breaks its ties; the ids are the mask's own positions
    (an audit's look, which a served chunk does not pay: the one way from
    scores to ids, ``ops/picked_ids``, over the mask as its scores). Rows
    below ``topk`` pick by position, not here."""
    k_ = cfg.idx_topk
    keys, kth = kth_largest(s, k_)
    above = keys > kth[:, None]
    level = keys == kth[:, None]
    room = k_ - above.sum(-1, keepdims=True)
    # nearly always every score at the level has room: no running count
    sel = above | jax.lax.cond(
        (level.sum(-1, keepdims=True) > room).any(),
        lambda: level & (jnp.cumsum(level, -1) <= room), lambda: level)
    selecting = (q_pos >= k_)[:, None]
    if not ids:
        return sel & selecting, None
    return sel & selecting, jnp.where(
        selecting, picked_ids(sel.astype(jnp.float32), k_), jnp.arange(k_))


def dsa_chunk(blk, cfg: KeyeVL2Config, u, pool, position, block_table, rope,
              block: int, idx_block: int, picks: bool = False):
    """A layer's prefill chunk: ``u [C, dim]`` (normed) at positions
    ``position..position+C-1`` (page-aligned). The chunk's K|V rows and
    indexer keys go to whole pages (padding rows land beyond the prompt,
    where a decode tick rewrites them before anything may score or attend
    them). Every row picks as a decode tick at its position would, and
    attention runs over the cached rows a block at a time under the picked
    mask. Returns (output [C, dim], pool, a look at the selection: the
    output rows and, with ``picks``, the picked positions ``[C, topk]``)."""
    kvp, ikp = pool["kv"], pool["ik"]
    page, k_ = kvp.shape[1], cfg.idx_topk
    c = u.shape[0]
    q_pos = position + jnp.arange(c)
    q, k, v, qi, ki, wi = project(blk, cfg, u, rope)
    ids = jax.lax.dynamic_slice_in_dim(block_table, position // page, c // page)
    kvp = kvp.at[ids].set(L.kv_rows(cfg, k, v).astype(kvp.dtype).reshape(
        c // page, page, 2 * cfg.kv_width))
    ikp = ikp.at[ids].set(ki.astype(ikp.dtype).reshape(c // page, *ikp.shape[1:]))
    last = position + c - 1

    def scored(_):
        per = idx_block // page

        def keys_of(j):
            at = jax.lax.dynamic_slice_in_dim(block_table, j * per, per)
            return ikp[at].reshape(idx_block, cfg.idx_dim)

        with jax.named_scope("dsa_index"):
            s = index_scores(cfg, qi, wi, keys_of, q_pos + 1,
                             last // idx_block + 1, idx_block)
        with jax.named_scope("dsa_select"):
            return picked_mask(cfg, s, q_pos, ids=picks)

    sel, top = jax.lax.cond(
        last >= k_, scored,
        lambda _: (jnp.zeros((c, cfg.max_seq), bool),  # below topk: 0..t
                   jnp.broadcast_to(jnp.arange(k_), (c, k_)) if picks else None),
        None)
    with jax.named_scope("dsa_attend"):
        per = block // page

        def kv_of(j):
            at = jax.lax.dynamic_slice_in_dim(block_table, j * per, per)
            return _split_rows(cfg, kvp[at].reshape(block, -1))

        def visible(j):
            at = j * block + jnp.arange(block)
            mine = jax.lax.dynamic_slice_in_dim(sel, j * block, block, 1)
            causal = at[None, :] <= q_pos[:, None]
            dense = (q_pos < k_)[:, None]
            return (causal & (dense | mine))[:, None, None, :]

        ctx = L.attend_kv_blocks(
            cfg, q, kv_of, visible, last // block + 1,
            "qkgd,tkd->qkgt", "qkgt,tkd->qkgd")
        out = _out(blk, cfg, ctx, u.dtype)
    return out, {"kv": kvp, "ik": ikp}, {"picked": top, "attended": out}


# ---------------------------------------------------------------------------
# the expert layer: softmax scores over models/moe.py's routed sum and counters
# ---------------------------------------------------------------------------


def route(blk, cfg: KeyeVL2Config, x):
    """Qwen3-MoE's routing: softmax in float32 over every expert of the
    model, the ``top_k`` largest, their probabilities renormalised over
    the chosen (``norm_topk_prob``); no bias, no scale. Returns (ids [N,
    k] — global expert numbers — and weights [N, k], float32)."""
    with jax.named_scope("moe_router"):
        logits = jnp.dot(
            x.astype(jnp.float32), blk["router"].astype(jnp.float32),
            precision=_HIGHEST,
        )
        w, ids = jax.lax.top_k(jax.nn.softmax(logits, -1), cfg.top_k)
        if cfg.norm_topk:
            w = w / w.sum(-1, keepdims=True)
        return ids, w


def mlp(blk, cfg: KeyeVL2Config, x, live, counted):
    """The expert layer on normed rows ``x``: ``moe.mlp`` with
    :func:`route` in ``moe.route``'s place (which it calls by name) and
    neither a dense layer nor a shared expert to look for. Returns
    (output [N, dim], ``moe.add_layer``'s counters)."""
    ids, weights = route(blk, cfg, x)
    local = ids - cfg.expert_first
    y = moe.held_experts(blk, cfg, x, local, weights, live)
    landed = (local >= 0) & (local < cfg.experts_held) & counted[:, None]
    per_expert = (
        (local[..., None] == jnp.arange(cfg.experts_held)) & landed[..., None]
    ).sum((0, 1)).astype(jnp.int32)
    return y.astype(x.dtype), (
        counted.sum().astype(jnp.int32), landed.sum().astype(jnp.int32),
        per_expert,
    )


# ---------------------------------------------------------------------------
# the stack, the two programs
# ---------------------------------------------------------------------------


def init_counters(cfg: KeyeVL2Config) -> dict:
    """The counters on the device, an operand and a result of their own
    of both programs (a buffer each: donated one by one), int32 that
    wraps: ``moe`` are the expert layer's routing counters
    (``moe.init_counters``), ``dsa`` this module's (:data:`DSA_COUNTERS`)."""
    return {
        "moe": moe.init_counters(cfg),
        "dsa": {name: jnp.zeros((), jnp.int32) for name in DSA_COUNTERS},
    }


def _layers(params, cfg: KeyeVL2Config, x, pools, stats, attend, live,
            counted, decode: bool):
    """The stack: ``attend(blk, normed rows, layer pool) -> (out, pool,
    look)``, then :func:`mlp`. Returns (rows, pools, the routing
    counters, every layer's look)."""
    pools = dict(pools)
    routed = dict(stats)
    per_layer, looks = [], []
    for i in range(cfg.layers):
        blk, key = params["blocks"][str(i)], str(i)
        a, pools[key], look = attend(
            blk, L.rms_norm(x, blk["attn_norm"], cfg.norm_eps), pools[key])
        looks.append(look)
        x = x + a.astype(x.dtype)
        y, counters = mlp(
            blk, cfg, L.rms_norm(x, blk["ffn_norm"], cfg.norm_eps), live,
            counted)
        x = x + y
        moe.add_layer(routed, per_layer, counters, decode)
    moe.add_stack(routed, per_layer, counted, decode)
    return x, pools, routed, looks


def _looks(looks):
    return [{k: a[k] for k in ("picked", "attended")} for a in looks]


def paged_batch_rows(params, cfg: KeyeVL2Config, tokens, pools, stats,
                     positions, block_tables, block: int = INDEX_BLOCK,
                     picks: bool = False):
    """One decode step for B = slots independent sequences: tokens,
    positions ``[B]``, block_tables ``[B, max_pages]`` (a frozen row
    comes with position 0 and a zeroed table row, which is also how this
    step knows it: its rows land in the null page, it scores nothing and
    its routing is neither computed on nor counted). Returns (the final
    rows [B, dim], pools, stats), and with ``picks`` each layer's look
    last (:func:`dsa_decode`'s ``"picked"`` and ``"attended"``)."""
    active = block_tables[:, 0] != 0
    i32 = jnp.int32
    n_live, r = active.sum(dtype=i32), decode_group(active.shape[0])
    # the slots with the live ones first (in slot order), and how many
    live = jnp.argsort(~active, stable=True), n_live
    rope = rope_rows(cfg, positions)
    x = params["embed"].astype(L.compute_dtype())[tokens]

    def attend(blk, u, pool):
        return dsa_decode(blk, cfg, u, pool, positions, block_tables, live,
                          rope, block)

    x, pools, routed, looks = _layers(
        params, cfg, x, pools, stats["moe"], attend, active, active, True)
    selecting = active & (positions >= cfg.idx_topk)
    in_context = jnp.where(active, positions + 1, 0)
    dsa = PM.add_counts(
        stats["dsa"],
        dsa_decode_ticks=(n_live > 0).astype(i32), dsa_row_ticks=n_live,
        dsa_rows_in_context=cfg.layers * in_context.sum(dtype=i32),
        dsa_rows_picked=sum(a["rows"].sum(dtype=i32) for a in looks),
        # a short last group gathers for its spare entries too
        dsa_rows_fetched=cfg.layers * cfg.idx_topk
        * ((n_live + r - 1) // r * r),
        dsa_index_rows_scored=cfg.layers * jnp.where(
            selecting, in_context, 0).sum(dtype=i32),
        dsa_row_ticks_selecting=selecting.sum(dtype=i32),
    )
    out = (x, pools, {"moe": routed, "dsa": dsa})
    return (*out, _looks(looks)) if picks else out


def paged_chunk_rows(params, cfg: KeyeVL2Config, chunk_ids, pools, stats,
                     position, block_table, valid, block: int = ATTN_BLOCK,
                     idx_block: int = INDEX_BLOCK, picks: bool = False):
    """One prefill chunk of one stream: ``chunk_ids [C]`` at positions
    ``position..position+C-1`` (page-aligned), of which the first
    ``valid`` are the prompt's. ``position`` and ``valid`` are traced:
    one program for every chunk. Every row is computed; the counters
    count the ``valid`` ones. With ``picks`` each layer's look comes back
    last: its picked positions ``"picked" [C, topk]`` and its output rows
    ``"attended" [C, dim]`` (an engine built with ``picks`` keeps them
    for a cache audit: :func:`make_paged_engine`)."""
    c = chunk_ids.shape[0]
    q_pos = position + jnp.arange(c)
    rope = rope_rows(cfg, q_pos)
    x = params["embed"].astype(L.compute_dtype())[chunk_ids]
    counted = jnp.arange(c) < valid

    def attend(blk, u, pool):
        return dsa_chunk(blk, cfg, u, pool, position, block_table, rope,
                         block, idx_block, picks)

    x, pools, routed, looks = _layers(
        params, cfg, x, pools, stats["moe"], attend, jnp.ones((c,), bool),
        counted, False)
    i32 = jnp.int32
    selecting = counted & (q_pos >= cfg.idx_topk)
    swept = ((position + c - 1) // block + 1) * block

    def over_valid(values):
        return jnp.where(counted, values, 0).sum(dtype=i32)

    dsa = PM.add_counts(
        stats["dsa"],
        dsa_chunk_rows=valid.astype(i32),
        dsa_chunk_rows_in_context=cfg.layers * over_valid(q_pos + 1),
        dsa_chunk_rows_picked=cfg.layers * over_valid(
            jnp.minimum(q_pos + 1, cfg.idx_topk)),
        dsa_chunk_rows_fetched=cfg.layers * valid.astype(i32)
        * swept.astype(i32),
        dsa_chunk_index_rows_scored=cfg.layers * jnp.where(
            selecting, q_pos + 1, 0).sum(dtype=i32),
        dsa_chunk_rows_selecting=selecting.sum(dtype=i32),
    )
    out = (x, pools, {"moe": routed, "dsa": dsa})
    return (*out, _looks(looks)) if picks else out


paged_batch_logits, fused_paged_batch_step = PM.under_the_head(paged_batch_rows)
paged_chunk_logits, fused_paged_chunk_step = PM.under_the_head(paged_chunk_rows)


def window_program(params, cfg, k: int, eos, block: int, tokens, pools,
                   stats, *rest, picks: bool = False):
    """The K-tick decode window (models/paged_window.make_paged_window)
    over :func:`fused_paged_batch_step`: the pools and the counters ride
    the window's carry together and come back apart. Returns (the
    window's own results, pools last; stats). With ``picks`` the window is
    the slot-state one over :func:`audit_state` (``rest`` ends with it,
    and it comes back after the pools), and each layer's look at every
    tick is the last result: ``"picked" [K, B, topk]``, ``"attended" [K,
    B, dim]`` float32 (tick ``j`` of a row that came in at position ``p``
    is the row at ``p + j``)."""
    def batch(tokens, carried, positions, bts, *audit):
        nxt, pools, stats, *look = fused_paged_batch_step(
            params, cfg, tokens, *carried, positions, bts, block=block,
            picks=picks)
        if not picks:
            return nxt, (pools, stats)
        _active, (state, tick, kept) = audit
        kept = jax.tree.map(
            lambda every, one: jax.lax.dynamic_update_index_in_dim(
                every, one.astype(every.dtype), tick, 0), kept, look[0])
        return nxt, (pools, stats), (state, tick + 1, kept)

    if picks:
        *rest, state = rest
        b = tokens.shape[0]
        rest.append((state, jnp.zeros((), jnp.int32), [{
            "picked": jnp.zeros((k, b, cfg.idx_topk), jnp.int32),
            "attended": jnp.zeros((k, b, cfg.dim), jnp.float32),
        } for _ in range(cfg.layers)]))
    *out, last = make_paged_window(batch, k=k, eos=eos, slot_state=picks)(
        tokens, (pools, stats), *rest)
    if not picks:
        pools, stats = last
        return (*out, pools), stats
    (pools, stats), (state, _, kept) = out.pop(), last
    return (*out, pools, state), stats, kept


# ---------------------------------------------------------------------------
# the pool and the engine
# ---------------------------------------------------------------------------


def init_page_pool(cfg: KeyeVL2Config, num_pages: int, page_size: int,
                   dtype=None) -> dict:
    """Two leaves a layer under the one block table, a row a position
    each: ``"kv" [P, page, 2 * KV * hd]`` (keys then values) and ``"ik"
    [P, page / pack, pack * dI]`` (the indexer's keys, ``idx_pack``
    positions a row of 128 lanes, or as many as a page has; ``[P, page,
    dI]`` in memory). Page 0 is the null page."""
    dtype = dtype or L.compute_dtype()
    pack = math.gcd(cfg.idx_pack, page_size)
    return {str(i): {
        "kv": jnp.zeros((num_pages, page_size, 2 * cfg.kv_width), dtype),
        "ik": jnp.zeros((num_pages, page_size // pack, pack * cfg.idx_dim),
                        dtype),
    } for i in range(cfg.layers)}


def audit_state(max_slots: int) -> dict:
    """The slot state of an audit's engine: nothing, a word a slot.
    ``paged_model.build_engine`` hands a program's results past its
    counters to ``looks`` on its slot-state path alone; an engine built
    with ``picks`` carries this to be on it (and so has no prefix cache)."""
    return {"none": jnp.zeros((max_slots,), jnp.int32)}


def default_num_pages(cfg: KeyeVL2Config, max_slots: int,
                      page_size: int) -> int:
    """The pool's default size, ``paged_model.default_num_pages``' rule in
    bytes. At the cell's cut (26,112 B a token) the cap does not bind on
    a 16 GB v5e: 16 x 16,384 rows are 6.85 GB, every slot may reach
    ``max_seq``."""
    return PM.default_num_pages(
        page_size * cfg.kv_bytes_per_token, max_slots, cfg.max_seq, page_size)


def report(cfg: KeyeVL2Config, page_size: int, totals: dict, engine) -> dict:
    """The gauges of one engine (``paged_model.build_engine``'s
    ``report``): the routing counters under the names every expert-layer
    model gives them (``moe.report``), the selection's, the pool."""
    return {
        **moe.report(totals["moe"], cfg.moe_layers),
        # raw, for a reader that takes it over a capture's ticks
        "moe_touched": int(totals["moe"]["touched"]),
        **{name: int(totals["dsa"][name]) for name in DSA_COUNTERS},
        "kv_bytes_per_token": cfg.kv_bytes_per_token,
        "kv_pool_bytes": (engine.allocator.num_pages * page_size
                          * cfg.kv_bytes_per_token),
        "kv_pages_free": engine.allocator.free_pages,
    }


def flops_per_token(cfg: KeyeVL2Config) -> float:
    """Weight-matmul FLOPs of one token on this rank (no score or index
    term): attention and the indexer's projections, the router, the
    expected ``top_k * held / n_experts`` routed pairs a layer, the head."""
    attn = (cfg.dim * (cfg.q_width + 2 * cfg.kv_width
                       + (cfg.idx_heads + 1) * cfg.idx_dim + cfg.idx_heads)
            + cfg.q_width * cfg.dim)
    expert = 3 * cfg.dim * cfg.moe_ffn
    routed = (cfg.dim * cfg.n_experts
              + cfg.top_k * cfg.experts_held / cfg.n_experts * expert)
    return 2.0 * (cfg.layers * (attn + routed) + cfg.dim * cfg.vocab)


def make_paged_engine(params, cfg: KeyeVL2Config, *, max_slots: int = 16,
                      eos: int | None = None, page_size: int = 16,
                      chunk: int | None = None,
                      num_pages: int | None = None,
                      window: int | None = None,
                      prefix_cache: bool | None = None,
                      prefix_cache_pages: int | None = None,
                      attn_block: int | None = None, picks: bool = False):
    """The paged continuous-batching engine
    (models/batch_engine.PagedBatchEngine) over the two-leaf pages: the
    same scheduler, allocator, prefix cache and K-tick window as the
    other families (``paged_model.build_engine``; the pools and the
    counters are arguments 2 and 3 of both programs, hence the donation).
    ``num_pages`` defaults to :func:`default_num_pages`. Speculation,
    LoRA and int8 pages are not offered (KNOWN_ISSUES.md, PR 49). With
    ``picks`` (a cache audit's engine, never the server's: ``llm_server``
    has no knob for it) ``engine.selection`` holds the looks, a layer
    each, of the last chunk and of the last window; such an engine has no
    prefix cache (:func:`audit_state`) and asking for both is refused."""
    if picks and (prefix_cache or prefix_cache_pages):
        raise NotImplementedError(
            "keye_vl2: picks on an engine with a prefix cache (a served "
            "engine's) is not built: an audit's looks ride the slot-state "
            "path, which has none")
    if cfg.max_seq < cfg.idx_topk:
        raise ValueError(
            f"keye_vl2: max_seq {cfg.max_seq} is under sa_config.topk "
            f"{cfg.idx_topk}: no row would ever select")
    chunk = PM.default_chunk(chunk, cfg.max_seq)
    attn_block = PM.default_attn_block(attn_block, ATTN_BLOCK, chunk,
                                       cfg.max_seq, page_size)
    idx_block = PM.default_attn_block(None, INDEX_BLOCK, chunk, cfg.max_seq,
                                      page_size)
    if num_pages is None:
        num_pages = default_num_pages(cfg, max_slots, page_size)

    def step(p, ids, pools, stats, position, bt, valid):
        return fused_paged_chunk_step(p, cfg, ids, pools, stats, position,
                                      bt, valid, block=attn_block,
                                      idx_block=idx_block)

    def audit_step(p, ids, pools, stats, position, bt, state, valid, slot):
        greedy, pools, stats, look = fused_paged_chunk_step(
            p, cfg, ids, pools, stats, position, bt, valid, block=attn_block,
            idx_block=idx_block, picks=True)
        return greedy, pools, state, stats, look

    selection = {"chunk": [], "window": []}
    engine = PM.build_engine(
        "keye_vl2", cfg, params,
        window_program=lambda p, k, *args: window_program(
            p, cfg, k, eos, idx_block, *args, picks=picks),
        chunk_step=audit_step if picks else step,
        donate_window=(2, 3), donate_chunk=(2, 3),
        init_page_pool=lambda n: init_page_pool(cfg, n, page_size),
        init_slot_state=audit_state if picks else None,
        counters=init_counters(cfg), report=partial(report, cfg, page_size),
        not_offered=NOT_OFFERED, flops_per_token=flops_per_token(cfg),
        looks=selection if picks else None,
        max_slots=max_slots, eos=eos, page_size=page_size, chunk=chunk,
        num_pages=num_pages, window=window,
        prefix_cache=prefix_cache, prefix_cache_pages=prefix_cache_pages)
    if picks:
        engine.selection = selection
    return engine
