"""GLM-5.3-Flash's text model (``model_type`` ``glm5_next_text``) on the
paged serving path, as ONE RANK of an expert group: delta-rule (KDA)
layers of per-slot state, three for every latent-attention layer whose
rows a learned indexer picks (DeepSeek sparse attention over MLA without
a rotary part), ``hc_mult`` residual streams mixed by Sinkhorn-normalised
matrices around every sublayer (mHC), over a dense SwiGLU in the first
layer(s) and the sigmoid-routed expert layer (``models/moe.py``) in the rest.

The layer, as the published ``config.json`` names it (``†`` = a detail
the config does not settle, an assumption written down in
``KNOWN_ISSUES.md`` "PR 43"; the float32 reference of the same
mathematics, whole sequence, is ``glm5_next_reference.py``, where each †
is a switch). Rows ``x [T, dim]``, ``n = hc_mult`` streams ``X [T, n,
dim]``:

    residual path, around EACH sublayer F (mixer, mlp) of each layer:
      z     = rmsnorm_noweight(vec(X), hc_eps)                      [T, n * dim]
      Hpre  = sigmoid(a_pre * (z P_pre) + b_pre)                    [T, n]
      Hpost = 2 sigmoid(a_post * (z P_post) + b_post)               [T, n]
      Hres  = sinkhorn(exp(a_res * mat(z P_res) + b_res))           [T, n, n]
              rows then columns to sum 1, hc_sinkhorn_iters times,
              each division by (sum + hc_eps)                       †1
      u = sum_i Hpre[i] X[i];  y = F(rmsnorm(u, w));  X'[i] = sum_j Hres[i, j] X[j] + Hpost[i] y
      entry: the embedding row copied to all n streams; exit: their sum   †2

    linear_attention (KDA; H heads, d_k = d_v = head_dim; state S [H, d_k, d_v] float32):
      q, k, v = silu(conv(h Wq)), silu(conv(h Wk)), silu(conv(h Wv))   causal depthwise, kernel 4
      q, k    = l2norm(q) d_k^-0.5, l2norm(k)
      g       = gate_lower_bound * sigmoid(exp(A_log) ((h Wfa) Wfb + dt_bias))   †3   per channel, in (lower, 0)
      beta    = sigmoid(h Wb)
      S~ = diag(exp(g_t)) S_{t-1};  S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T;  o_t = S_t^T q_t
      out     = (rmsnorm(o, o_norm) * sigmoid((h Wga) Wgb)) Wo          low rank = head_dim †4

    deepseek_sparse_attention (MLA, qk_rope_head_dim 0: no rotary anywhere):
      cq = rmsnorm(h Wqa);  q = cq Wqb;  c = rmsnorm(h Wkva)       <- the cached row, kv_lora_rank wide
      indexer: qI = cq WIq [index_n_heads, index_head_dim];  kI = layernorm(h WIk)
               w = (h WIw) index_n_heads^-0.5 index_head_dim^-0.5
               block b = positions index_kpool b .. +index_kpool-1;  kI~_b = their mean   †5
               score(t, b) = sum_j w[t, j] relu(qI[t, j] . kI~_b)   for b < floor(t / index_kpool)
               picked(t) = the positions of the top index_topk / index_kpool blocks        †6
                           + positions index_kpool floor(t / index_kpool) .. t; all of 0..t while t < index_topk
      attend: absorbed MLA (Kimi-K2's) over picked(t) alone, scale qk_head_dim^-0.5
    mlp: moe.mlp (route / held_experts / the shared expert), every SwiGLU
         clamped by swiglu_limit (kept beside its matrices by the loader)   †7

What this module adds to the serving path: **three cache kinds in one
model.**

* a KDA layer keeps slot state only (``PagedBatchEngine``'s
  ``init_slot_state``): the float32 state ``"s" [slots, H, d_k, d_v]``
  and the convolution's last three rows ``"conv" [slots, 3, 3 H d_k]``.
  A decode tick steps live rows only, in one pass over their state
  (``ops/kda_state_step``: the state stays in HBM, rows that are not
  live move none of it); a chunk runs the blocked delta rule
  (``models/delta_rule.delta_rule_blocks``) from the slot's state, zeros at position
  0, and writes back the state after its last VALID row.
* a sparse-latent layer keeps two leaves of different row rates in
  ``pools`` under the one block table: ``"kv" [P, page, kv_lora_rank]``
  (a row a position) and ``"ik" [P, page / index_kpool,
  index_head_dim]`` (a pooled indexer row a block of ``index_kpool``
  positions), plus the slot state ``"acc" [slots, index_head_dim]``
  float32, the sum of the block that is still filling. The pooled row
  is written when the block's last position is (decode: from the
  accumulator; chunk: the blocks that complete inside it, the rest left
  in the accumulator; a chunk starts on a page and so on a block).
* decode takes the LIVE rows alone, ``DECODE_ROWS`` at a time (a frozen
  slot scores, sorts and gathers nothing): scores a row's ``floor(t /
  index_kpool)`` pooled rows, ``lax.top_k`` (ties to the lower block),
  finds the picked BLOCKS in the pool by a compare and a sum over the
  row's block table (``ops/picked_rows.pool_rows``: no gather of
  scalars), gathers their ``index_topk + index_kpool`` latent rows and
  attends those alone; a chunk
  scores each of its rows, and runs the dense absorbed product a block
  of cached rows at a time UNDER the picked mask (the counters say which
  was done: ``dsa_rows_fetched`` against ``dsa_rows_picked``).

Every matrix goes through ``ops/int8_matmul``; the head through
``lm_head_argmax``. Text only; the multi-token-prediction layer and the
tower are not served.
"""

from __future__ import annotations

import logging
import math
from dataclasses import dataclass
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp

from dora_tpu.models import layers as L
from dora_tpu.models import moe
from dora_tpu.models.delta_rule import delta_rule_blocks, delta_rule_step
from dora_tpu.models import paged_model as PM
from dora_tpu.models.hf.loader import TensorFiles, read_config
from dora_tpu.models.paged_window import make_paged_window
from dora_tpu.ops.int8_matmul import quantize_int8_t as _quantize_t
from dora_tpu.ops.picked_rows import pool_rows

MODEL_TYPES = ("glm5_next_text",)

#: rows of one block of cached latent rows in a CHUNK's dense product
#: under the picked mask (a multiple of the page)
ATTN_BLOCK = 256
#: rows of one block of the delta rule's blocked form. Inside a block the
#: decays enter pairwise, ``exp(G_t - G_s)`` with ``s <= t`` (never above
#: 1, whatever ``gate_lower_bound``), so a block costs ``block^2 * d_k`` a
#: head; between blocks the state is carried.
KDA_BLOCK = 16
#: positions of one block of pooled indexer keys in a decode tick's scoring
#: loop (a multiple of the page): work follows a group's longest context
INDEX_BLOCK = 2048
#: live rows a sparse-latent layer's decode tick scores, sorts, gathers
#: and attends at a time: its selection follows the rows that are live,
#: not the slots
DECODE_ROWS = 4
#: eps of the indexer's LayerNorm and of the l2 norms (DeepSeek-V3.2's and
#: Kimi Linear's; neither is a key of the config)
INDEX_NORM_EPS = 1e-6
L2_EPS = 1e-6

#: serving knobs of the Qwen path that this model refuses (KNOWN_ISSUES.md)
NOT_OFFERED = {
    "DORA_KV_INT8": "the int8 page kernels are fused into the Qwen "
                    "attention kernels; latent rows and pooled index rows "
                    "are no per-head K/V planes",
    "DORA_SPEC_K": "a rejected draft would have stepped the delta-rule "
                   "state and the indexer's accumulator; no snapshot is kept",
    "DORA_LORA_DIR": "the grouped LoRA matmul is fused into the Qwen kernels",
}

#: the delta-rule layers' and the sparse-latent layers' counters on the
#: device; ``dsa_rows_fetched`` = the rows a tick's gathers name (their
#: shapes': a group's, for every group that ran)
KDA_COUNTERS = (
    "kda_decode_ticks", "kda_row_ticks", "kda_chunks", "kda_chunk_rows",
    "dsa_rows_in_context", "dsa_rows_picked", "dsa_rows_fetched",
    "dsa_index_rows_scored", "dsa_row_ticks_selecting",
    "dsa_chunk_rows_in_context", "dsa_chunk_rows_picked",
    "dsa_chunk_rows_fetched", "dsa_chunk_index_rows_scored",
    "dsa_chunk_rows_selecting",
)

_HIGHEST = jax.lax.Precision.HIGHEST
_log = logging.getLogger(__name__)


@dataclass(frozen=True)
class Glm5NextConfig:
    vocab: int
    dim: int
    layers: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    v_dim: int
    ffn: int
    moe_ffn: int
    n_experts: int  # the router's width: every expert of the model
    top_k: int
    n_shared: int
    routed_scale: float
    norm_topk: bool
    norm_eps: float
    swiglu_limit: float | None
    max_seq: int
    #: per layer: True = delta-rule (KDA) mixer, False = sparse latent attention
    linear: tuple
    #: per layer: True = expert layer, False = dense MLP
    sparse: tuple
    kda_heads: int
    kda_dim: int
    conv: int
    gate_lower: float
    idx_heads: int
    idx_dim: int
    idx_topk: int
    idx_pool: int
    hc: int
    hc_iters: int
    hc_eps: float
    #: this rank's share: experts ``expert_first .. +experts_held``
    expert_first: int
    experts_held: int

    @property
    def kda_width(self) -> int:
        return self.kda_heads * self.kda_dim

    @property
    def kda_layers(self) -> tuple:
        return tuple(i for i, s in enumerate(self.linear) if s)

    @property
    def dsa_layers(self) -> tuple:
        return tuple(i for i, s in enumerate(self.linear) if not s)

    @property
    def moe_layers(self) -> int:
        return sum(self.sparse)

    @property
    def softmax_scale(self) -> float:
        return self.nope ** -0.5

    @property
    def picked_blocks(self) -> int:
        """Pooled blocks a selecting row picks: ``index_topk`` POSITIONS."""
        return self.idx_topk // self.idx_pool

    @property
    def kv_bytes_per_token(self) -> int:
        """What a cached position holds in the paged pool: the latent row
        and its share of a pooled indexer row, of the SPARSE-LATENT layers
        alone (1,088 B for one layer of 512 + 128 / 4 values at bf16)."""
        width = self.kv_rank + self.idx_dim // self.idx_pool
        return (len(self.dsa_layers) * width
                * jnp.dtype(L.compute_dtype()).itemsize)

    @property
    def state_bytes_per_slot(self) -> int:
        """Every slot-state leaf of one slot: float32 delta-rule states,
        convolution tails, the indexer's accumulators."""
        kda = (self.kda_heads * self.kda_dim * self.kda_dim * 4
               + (self.conv - 1) * 3 * self.kda_width
               * jnp.dtype(L.compute_dtype()).itemsize)
        return len(self.kda_layers) * kda + len(self.dsa_layers) * self.idx_dim * 4

    @classmethod
    def from_hf(cls, config: dict, max_seq: int | None = None,
                ep_rank: int | None = None) -> "Glm5NextConfig":
        if config.get("model_type") not in MODEL_TYPES:
            raise ValueError(
                f"model_type {config.get('model_type')!r} is not one of "
                f"{MODEL_TYPES}"
            )
        n = config["num_hidden_layers"]
        kinds = config.get("layer_types")
        if kinds is None or len(kinds) != n:
            raise ValueError(
                f"glm5_next: layer_types must name all {n} layers, got "
                f"{kinds!r}")
        unknown = set(kinds) - {"linear_attention", "deepseek_sparse_attention"}
        if unknown:
            raise NotImplementedError(
                f"glm5_next: layer_types {sorted(unknown)} is not written")
        linear = tuple(k == "linear_attention" for k in kinds)
        lin = config.get("linear_attn_config") or {}
        if any(linear) and not lin:
            raise ValueError(
                "glm5_next: linear_attention layers need linear_attn_config")
        for key, want in (("kda_layers", [i for i in range(n) if linear[i]]),
                          ("full_attn_layers",
                           [i for i in range(n) if not linear[i]])):
            if lin.get(key) is not None and list(lin[key]) != want:
                raise ValueError(
                    f"glm5_next: linear_attn_config.{key} {lin[key]!r} and "
                    f"layer_types disagree (expected {want})")
        mlp_kinds = config.get("mlp_layer_types")
        if mlp_kinds is None:
            dense = config.get("first_k_dense_replace", 0)
            mlp_kinds = ["dense"] * dense + ["sparse"] * (n - dense)
        if len(mlp_kinds) != n or set(mlp_kinds) - {"dense", "sparse"}:
            raise ValueError(
                f"glm5_next: mlp_layer_types must name all {n} layers as "
                f"'dense' or 'sparse', got {mlp_kinds!r}")
        rope = config.get("qk_rope_head_dim", 0)
        if rope and config.get("mla_use_nope"):
            raise NotImplementedError(
                f"glm5_next: qk_rope_head_dim {rope} > 0 with mla_use_nope: "
                f"a latent layer without position encoding has no rotary part")
        if rope:
            raise NotImplementedError(
                f"glm5_next: qk_rope_head_dim {rope}: rotary latent attention "
                f"under the indexer is not written (GLM-5.3-Flash has 0)")
        not_full = sorted(set(config.get("indexer_types") or ["full"]) - {"full"})
        if not_full:
            raise NotImplementedError(
                f"glm5_next: indexer_types {not_full} is not written (only "
                f"'full': every sparse-latent layer scores for itself)")
        if config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
            raise NotImplementedError(
                "glm5_next: group-limited routing (n_group/topk_group > 1) "
                "is not written; GLM-5.3-Flash has n_group 1")
        if config.get("scoring_func", "sigmoid") != "sigmoid":
            raise NotImplementedError(
                f"glm5_next: scoring_func {config['scoring_func']!r} is not "
                f"written (only sigmoid)")
        hc = int(config.get("hc_mult", 1))
        if hc > 1 and not config.get("mhc", False):
            raise NotImplementedError(
                f"glm5_next: hc_mult {hc} with mhc false: plain hyper-"
                f"connections (no Sinkhorn) are not written")
        for key in ("index_kpool_compress", "index_kpool_always_select_tail"):
            if not config.get(key, True):
                raise NotImplementedError(
                    f"glm5_next: {key} false is not written")
        if config.get("attention_bias"):
            raise NotImplementedError("glm5_next: attention_bias is not written")
        if not config.get("q_lora_rank"):
            raise NotImplementedError(
                "glm5_next: a checkpoint without q_lora_rank is not written "
                "(the indexer reads the query's latent)")
        pool, topk = int(config.get("index_kpool", 1)), config["index_topk"]
        if topk % pool:
            raise ValueError(
                f"glm5_next: index_topk {topk} is no multiple of index_kpool "
                f"{pool}")
        first, held = moe.expert_share(config, ep_rank)
        limit = config.get("swiglu_limit")
        return cls(
            vocab=config["vocab_size"],
            dim=config["hidden_size"],
            layers=n,
            heads=config["num_attention_heads"],
            q_rank=config["q_lora_rank"],
            kv_rank=config["kv_lora_rank"],
            nope=config["qk_nope_head_dim"],
            v_dim=config["v_head_dim"],
            ffn=config["intermediate_size"],
            moe_ffn=config["moe_intermediate_size"],
            n_experts=config["n_routed_experts"],
            top_k=config["num_experts_per_tok"],
            n_shared=config.get("n_shared_experts") or 0,
            routed_scale=float(config.get("routed_scaling_factor", 1.0)),
            norm_topk=bool(config.get("norm_topk_prob", True)),
            norm_eps=config.get("rms_norm_eps", 1e-5),
            swiglu_limit=float(limit) if limit else None,
            max_seq=max_seq
            or min(config.get("max_position_embeddings", 2048), 2048),
            linear=linear,
            sparse=tuple(k == "sparse" for k in mlp_kinds),
            kda_heads=int(lin.get("num_heads", 0)),
            kda_dim=int(lin.get("head_dim", 0)),
            conv=int(lin.get("short_conv_kernel_size", 4)),
            gate_lower=float(lin.get("gate_lower_bound", -5.0)),
            idx_heads=config["index_n_heads"],
            idx_dim=config["index_head_dim"],
            idx_topk=topk,
            idx_pool=pool,
            hc=hc,
            hc_iters=int(config.get("hc_sinkhorn_iters", 20)),
            hc_eps=float(config.get("hc_eps", 1e-6)),
            expert_first=first,
            experts_held=held,
        )


# ---------------------------------------------------------------------------
# loading: one layer at a time, only the held experts, int8 on the device
# ---------------------------------------------------------------------------


def _pad_to_lanes(w):
    """Zero output channels (HF layout: rows) up to a multiple of 128."""
    return moe.pad_outputs(w, w.shape[0] + (-w.shape[0]) % 128)


def _load_kda(get, cfg: Glm5NextConfig, a: str) -> dict:
    f32 = jnp.float32
    taps = [get(a + f"{n}_conv1d.weight").reshape(cfg.kda_width, cfg.conv)
            for n in "qkv"]
    return {
        # q, k, v, the two low-rank gates' first halves and beta read the
        # same row: one matrix
        "w_in": _quantize_t(
            get(a + "q_proj.weight"), get(a + "k_proj.weight"),
            get(a + "v_proj.weight"), get(a + "f_a_proj.weight"),
            get(a + "g_a_proj.weight"), _pad_to_lanes(get(a + "b_proj.weight"))),
        "conv_w": jnp.concatenate(taps, 0).T,  # [taps, 3 H d_k], oldest first
        "w_fb": _quantize_t(get(a + "f_b_proj.weight")),
        "w_gb": _quantize_t(get(a + "g_b_proj.weight")),
        "a": jnp.exp(get(a + "A_log").astype(f32)).reshape(cfg.kda_heads),
        "dt_bias": get(a + "dt_bias").astype(f32).reshape(
            cfg.kda_heads, cfg.kda_dim),
        "o_norm": get(a + "o_norm.weight"),
        "wo": _quantize_t(get(a + "o_proj.weight")),
    }


def _load_dsa(get, cfg: Glm5NextConfig, a: str) -> dict:
    kvb = _quantize_t(get(a + "kv_b_proj.weight"))  # [kv_rank, H*(nope+v)]
    return {
        # the query's and the cache's latents, the indexer's key and its
        # head weights read the same row: one matrix
        "w_a": _quantize_t(
            get(a + "q_a_proj.weight"), get(a + "kv_a_proj_with_mqa.weight"),
            get(a + "indexer.wk.weight"),
            _pad_to_lanes(get(a + "indexer.weights_proj.weight"))),
        "q_norm": get(a + "q_a_layernorm.weight"),
        "kv_norm": get(a + "kv_a_layernorm.weight"),
        # the query's heads and the indexer's read the same latent
        "w_q_b": _quantize_t(get(a + "q_b_proj.weight"),
                             get(a + "indexer.wq_b.weight")),
        "idx_norm_w": get(a + "indexer.k_norm.weight").astype(jnp.float32),
        "idx_norm_b": get(a + "indexer.k_norm.bias").astype(jnp.float32),
        # Kimi-K2's absorbed layout (layers.mla_output reads it)
        "w_kv_b": L.mla_kv_b_weights(kvb, cfg),
        "wo": _quantize_t(get(a + "o_proj.weight")),
    }


def _swiglu(get, cfg: Glm5NextConfig, prefix: str) -> dict:
    """The SwiGLU's matrices with the checkpoint's clamp beside them
    (``moe.swiglu`` applies a ``"limit"`` where it finds one: a static
    argument at its call sites would be plainer, and moves the source
    columns inside Kimi-K2's and K-EXAONE's serialized kernels)."""
    w = moe.swiglu_weights(get, prefix)
    if cfg.swiglu_limit is not None:
        w["limit"] = jnp.asarray(cfg.swiglu_limit, jnp.float32)
    return w


def load_layer(get, cfg: Glm5NextConfig, i: int, prefix: str = "model.") -> dict:
    """Layer ``i``'s serving parameters from ``get(name) -> device
    array`` under the HF tensor names (Kimi Linear's for the KDA mixer,
    DeepSeek-V3.2's for the latent layer and its indexer, DeepSeek-V3's
    for the expert layer, ``hc_{attn,ffn}_{fn,base,scale}`` for the
    residual maps: †). Reads the held experts only."""
    lp = f"{prefix}layers.{i}."
    a, m = lp + "self_attn.", lp + "mlp."
    block = {
        "attn_norm": get(lp + "input_layernorm.weight"),
        "ffn_norm": get(lp + "post_attention_layernorm.weight"),
        **(_load_kda(get, cfg, a) if cfg.linear[i] else _load_dsa(get, cfg, a)),
    }
    for sub in ("attn", "ffn"):
        block[f"hc_{sub}"] = {
            # [n * dim, 2 n + n * n]: pre, post, res
            "fn": get(lp + f"hc_{sub}_fn").T,
            "base": get(lp + f"hc_{sub}_base").astype(jnp.float32),
            "scale": get(lp + f"hc_{sub}_scale").astype(jnp.float32),
        }
    if not cfg.sparse[i]:
        block["dense"] = _swiglu(get, cfg, m)
        return block
    return {**block, **moe.expert_layer_weights(
        get, cfg, m, lambda get, prefix: _swiglu(get, cfg, prefix))}


def load(model_dir: str | Path, max_seq: int | None = None,
         ep_rank: int | None = None):
    """(config, serving params) from a HF checkpoint directory, as
    ``kimi_k2.load``: tensors go from the file to the device one at a
    time and are quantized there; the embedding, the routers, the norms
    and the residual maps stay in the compute dtype; absent experts are
    never read."""
    cfg = Glm5NextConfig.from_hf(read_config(model_dir), max_seq, ep_rank)
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
# the residual path: hc_mult streams under Sinkhorn-normalised maps
# ---------------------------------------------------------------------------


def sinkhorn(m, iters: int, eps: float):
    """``m [..., n, n]`` positive -> rows then columns brought to sum 1,
    ``iters`` times, each division by ``(sum + eps)``. float32."""
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


def mhc_maps(hc, cfg: Glm5NextConfig, streams):
    """``streams [N, n, dim]`` -> (Hpre [N, n], Hpost [N, n], Hres [N, n,
    n]) in float32."""
    f32 = jnp.float32
    n = cfg.hc
    with jax.named_scope("mhc_maps"):
        z = streams.astype(f32).reshape(streams.shape[0], n * cfg.dim)
        z = z * jax.lax.rsqrt(jnp.mean(z * z, -1, keepdims=True) + cfg.hc_eps)
        m = jnp.dot(z, hc["fn"].astype(f32), precision=_HIGHEST)
        scale, base = hc["scale"], hc["base"]
        pre = jax.nn.sigmoid(scale[0] * m[:, :n] + base[:n])
        post = 2.0 * jax.nn.sigmoid(scale[1] * m[:, n : 2 * n] + base[n : 2 * n])
        res = jnp.exp(scale[2] * m[:, 2 * n :].reshape(-1, n, n)
                      + base[2 * n :].reshape(n, n))
    with jax.named_scope("mhc_sinkhorn"):
        res = sinkhorn(res, cfg.hc_iters, cfg.hc_eps)
    return pre, post, res


def mhc_sublayer(hc, cfg: Glm5NextConfig, streams, norm_w, sublayer):
    """One sublayer on the residual streams ``[N, n, dim]``:
    ``sublayer(normed rows [N, dim]) -> [N, dim]``. The maps and the
    mixing are float32 (sums over the ``n`` streams on the vector unit,
    exact); the streams go back to the compute dtype."""
    f32 = jnp.float32
    pre, post, res = mhc_maps(hc, cfg, streams)
    xs = streams.astype(f32)
    u = (pre[:, :, None] * xs).sum(1).astype(streams.dtype)
    y = sublayer(L.rms_norm(u, norm_w, cfg.norm_eps)).astype(f32)
    mixed = (res[:, :, :, None] * xs[:, None, :, :]).sum(2)
    return (mixed + post[:, :, None] * y[:, None, :]).astype(streams.dtype)


# ---------------------------------------------------------------------------
# the delta-rule mixer: one-token step (decode) and blocked form (prefill)
# ---------------------------------------------------------------------------


def _kda_in(blk, cfg: Glm5NextConfig, u):
    """Normed rows -> (q|k|v before the convolution [N, 3 H d_k], the
    decay gate's and the output gate's low-rank halves [N, d_k] each,
    beta's logits [N, H])."""
    p = L.matmul(u, blk["w_in"])
    w, r = 3 * cfg.kda_width, cfg.kda_dim
    return (p[:, :w], p[:, w : w + r], p[:, w + r : w + 2 * r],
            p[:, w + 2 * r : w + 2 * r + cfg.kda_heads])


def _kda_heads(cfg: Glm5NextConfig, conv):
    """Convolved rows ``[N, 3 H d_k]`` float32 -> silu, then q (l2-normed,
    scaled), k (l2-normed), v, each ``[N, H, d_k]``."""
    n = conv.shape[0]
    q, k, v = (t.reshape(n, cfg.kda_heads, cfg.kda_dim)
               for t in jnp.split(jax.nn.silu(conv), 3, axis=-1))

    def l2(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + L2_EPS)

    return l2(q) * cfg.kda_dim ** -0.5, l2(k), v


def _kda_gates(blk, cfg: Glm5NextConfig, fa, ga, b):
    """-> (g [N, H, d_k] in (gate_lower, 0), beta [N, H], the output gate
    [N, H, d_k]), float32."""
    f32 = jnp.float32
    n = fa.shape[0]
    shape = (n, cfg.kda_heads, cfg.kda_dim)
    r = L.matmul(fa, blk["w_fb"]).astype(f32).reshape(shape)
    g = cfg.gate_lower * jax.nn.sigmoid(
        blk["a"][:, None] * (r + blk["dt_bias"]))
    gate = jax.nn.sigmoid(L.matmul(ga, blk["w_gb"]).astype(f32)).reshape(shape)
    return g, jax.nn.sigmoid(b.astype(f32)), gate


def _kda_out(blk, cfg: Glm5NextConfig, o, gate):
    """``o [N, H, d_v]`` float32, normed over the head, gated, through
    ``Wo``."""
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.norm_eps)
    o = o * blk["o_norm"].astype(jnp.float32) * gate
    return L.matmul(
        o.astype(L.compute_dtype()).reshape(o.shape[0], cfg.kda_width),
        blk["wo"])


def kda_step(blk, cfg: Glm5NextConfig, u, st, active):
    """Decode: one token a row, ``u [B, dim]`` normed; ``st`` is the
    layer's ``{"s": [B, H, d_k, d_v] f32, "conv": [B, taps-1, 3 H d_k]}``
    (row = slot). Rows with ``active`` off leave both as they were.
    Returns (the mixer's output [B, dim], state)."""
    f32 = jnp.float32
    with jax.named_scope("kda_proj"):
        qkv, fa, ga, b = _kda_in(blk, cfg, u)
        tail = st["conv"]
        taps = jnp.concatenate([tail, qkv[:, None].astype(tail.dtype)], 1)
        conv = jnp.sum(taps.astype(f32) * blk["conv_w"].astype(f32)[None], 1)
        tail = jnp.where(active[:, None, None], taps[:, 1:], tail)
        q, k, v = _kda_heads(cfg, conv)
        g, beta, gate = _kda_gates(blk, cfg, fa, ga, b)
    with jax.named_scope("kda_step"):
        # one pass over the live rows' state; products and sums on the
        # vector unit: exact in float32
        o, s = delta_rule_step(st["s"], g, k, q, v, beta, active)
    return _kda_out(blk, cfg, o, gate), {"s": s, "conv": tail}


def kda_chunk(blk, cfg: Glm5NextConfig, u, st, slot, position, valid):
    """Prefill chunk of one stream: ``u [C, dim]`` normed; ``st`` the
    layer's slot arrays, of which row ``slot`` is this stream's. State
    and tail come in from the slot (zeros when ``position`` is 0: no
    reset call from the host) and go back as they stand after row
    ``valid`` (rows past it are padding: their ``g`` and ``beta`` are 0,
    so they neither decay nor write). Returns (output [C, dim], state)."""
    f32 = jnp.float32
    c = u.shape[0]
    fresh = position == 0
    with jax.named_scope("kda_proj"):
        qkv, fa, ga, b = _kda_in(blk, cfg, u)
        tail = jnp.where(fresh, 0, st["conv"][slot])  # [taps-1, 3 H d_k]
        rows = jnp.concatenate([tail, qkv.astype(tail.dtype)], 0)
        w = blk["conv_w"].astype(f32)
        conv = sum(
            jax.lax.dynamic_slice_in_dim(rows, j, c).astype(f32) * w[j]
            for j in range(cfg.conv))
        # the last taps-1 rows that are the prompt's: rows valid-3..valid-1
        tail = jax.lax.dynamic_slice_in_dim(rows, valid, cfg.conv - 1)
        q, k, v = _kda_heads(cfg, conv)
        g, beta, gate = _kda_gates(blk, cfg, fa, ga, b)
        live = jnp.arange(c) < valid
        g = jnp.where(live[:, None, None], g, 0.0)
        beta = jnp.where(live[:, None], beta, 0.0)
    with jax.named_scope("kda_scan"):
        s0 = jnp.where(fresh, 0.0, st["s"][slot])
        o, s = delta_rule_blocks(q, k, v, g, beta, s0, KDA_BLOCK)
    return _kda_out(blk, cfg, o, gate), {
        "s": jax.lax.dynamic_update_index_in_dim(st["s"], s, slot, 0),
        "conv": jax.lax.dynamic_update_index_in_dim(
            st["conv"], tail, slot, 0),
    }


# ---------------------------------------------------------------------------
# the sparse-latent layer: latent pages, pooled indexer rows, picked rows
# ---------------------------------------------------------------------------


def latent_project(blk, cfg: Glm5NextConfig, u):
    """Normed rows ``u [N, dim]`` -> (absorbed queries [N, H, kv_rank],
    the cache rows [N, kv_rank], the indexer's queries [N, J, d_I], its
    key [N, d_I] float32, its head weights [N, J] float32).
    Kimi-K2's ``mla_project`` arithmetic with no rotary part, and the
    query's latent kept for the indexer."""
    f32 = jnp.float32
    n, h, nope = u.shape[0], cfg.heads, cfg.nope
    o1 = cfg.q_rank
    o2 = o1 + cfg.kv_rank
    o3 = o2 + cfg.idx_dim
    a = L.matmul(u, blk["w_a"])
    c_q = L.rms_norm(a[:, :o1], blk["q_norm"], cfg.norm_eps)
    c_kv = L.rms_norm(a[:, o1:o2], blk["kv_norm"], cfg.norm_eps)
    ki = a[:, o2:o3].astype(f32)
    ki = ki - ki.mean(-1, keepdims=True)
    ki = ki * jax.lax.rsqrt(jnp.mean(ki * ki, -1, keepdims=True)
                            + INDEX_NORM_EPS)
    ki = ki * blk["idx_norm_w"] + blk["idx_norm_b"]
    wi = a[:, o3 : o3 + cfg.idx_heads].astype(f32) * (
        cfg.idx_heads ** -0.5 * cfg.idx_dim ** -0.5)
    qq = L.matmul(c_q, blk["w_q_b"])
    q = qq[:, : h * nope].reshape(n, h, nope)
    qi = qq[:, h * nope :].reshape(n, cfg.idx_heads, cfg.idx_dim)
    kb = blk["w_kv_b"]
    # q' = W_kvb^K^T q, per head; the per-column scale rides the query
    q = (q.astype(f32) * kb["ks"]).astype(u.dtype)
    q_abs = jnp.einsum(
        "nhj,hjc->nhc", q, kb["k8"].astype(u.dtype),
        preferred_element_type=f32,
    ).astype(u.dtype)
    return q_abs, c_kv, qi, ki, wi


def index_scores(cfg: Glm5NextConfig, qi, wi, pooled, complete):
    """``score(t, b) = sum_j w[t, j] relu(qI[t, j] . kI~_b)`` in float32:
    qi ``[..., J, d_I]``, wi ``[..., J]``, pooled ``[..., N, d_I]``,
    ``complete [...]`` = how many blocks each row may score (the rest
    read -inf)."""
    s = jnp.einsum("...jd,...nd->...jn", qi, pooled,
                   preferred_element_type=jnp.float32)
    s = (jax.nn.relu(s) * wi[..., None]).sum(-2)
    n = jnp.arange(pooled.shape[-2])
    return jnp.where(n < complete[..., None], s, -jnp.inf)


def _masked_softmax(s, seen):
    """float32 scores under ``seen`` -> probabilities (zeros where
    nothing is seen)."""
    s = jnp.where(seen, s, -1e30)
    p = jnp.where(seen, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    return p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)


def decode_group(slots: int) -> int:
    """The rows of one group of a decode tick's selection: ``DECODE_ROWS``,
    or what of it divides the slots."""
    return math.gcd(DECODE_ROWS, slots)


def decode_groups(cfg: Glm5NextConfig, slots: int, rows_fetched: int) -> int:
    """The groups of ``DECODE_ROWS`` live rows that ONE sparse-latent
    layer's decode ticks ran (1 a tick up to four live rows, 4 with 16),
    from the rows their gathers named: every group names
    ``decode_group(slots) x (index_topk + index_kpool)`` a layer. Not a
    counter of its own on the device: the counters are operands of the
    chunk program too."""
    return rows_fetched // (len(cfg.dsa_layers) * decode_group(slots)
                            * (cfg.idx_topk + cfg.idx_pool))


def dsa_decode(blk, cfg: Glm5NextConfig, u, pool, st, positions, block_tables,
               active, live, index_block: int = INDEX_BLOCK):
    """A sparse-latent layer's decode tick: ``u [B, dim]`` (normed), row
    = slot. Each row's latent goes to its page (a frozen row's, at
    position 0 of a zeroed table row, to the null page); its indexer key
    joins the slot's accumulator, and where the row's position closes a
    block of ``index_kpool`` the block's mean goes to ``"ik"``. Then the
    LIVE rows alone (``live`` = the slots with the live ones first, and
    how many they are), ``DECODE_ROWS`` at a time: a row at ``t >=
    index_topk`` scores its ``floor(t / index_kpool)`` pooled rows (its
    own pages' a block of ``index_block`` positions at a time, to the
    group's longest context) and attends the positions of the top blocks
    and its own unfinished block; below that it attends ``0..t``. Either
    way ``index_topk + index_kpool`` latent rows a live row are gathered:
    the ``picked_blocks + 1`` BLOCKS are found in the pool by a compare
    and a sum over the row's block table, and a block's rows, which follow
    one another in its page, are named one by one (on the chip a block is
    no contiguous 4 KB: XLA relays the whole leaf to fetch it whole,
    KNOWN_ISSUES.md "PR 53"). A frozen row scores, sorts and gathers
    nothing and puts out zeros. Returns (output [B, dim], pool, state, a
    look at the selection: the rows attended ``"rows" [B]``, the picked
    blocks ``"picked" [B, picked_blocks]`` and the output rows
    themselves)."""
    f32 = jnp.float32
    kvp, ikp, acc = pool["kv"], pool["ik"], st["acc"]
    page, kp, n_picked = kvp.shape[1], cfg.idx_pool, cfg.picked_blocks
    b = u.shape[0]
    rows = jnp.arange(b)
    t = positions
    with jax.named_scope("dsa_index"):
        q_abs, c_kv, qi, ki, wi = latent_project(blk, cfg, u)
        pages = block_tables[rows, t // page]
        kvp = kvp.at[pages, t % page].set(c_kv.astype(kvp.dtype))
        summed = acc + ki
        closes = active & (t % kp == kp - 1)
        # a row that closes no block writes the null page's first row
        ikp = ikp.at[jnp.where(closes, pages, 0),
                     jnp.where(closes, (t % page) // kp, 0)].set(
            (summed / kp).astype(ikp.dtype))
        acc = jnp.where(active[:, None],
                        jnp.where(closes[:, None], 0.0, summed), acc)
    order, n_live = live
    r = decode_group(b)
    first = jnp.broadcast_to(jnp.arange(n_picked), (r, n_picked))
    flat = kvp.reshape(-1, cfg.kv_rank)  # a cached row a position
    # pages, and their pooled rows, of one block of the scoring loop
    per = math.gcd(index_block // page, block_tables.shape[1])
    n_block = per * (page // kp)

    def group(g, carry):
        ctx, seen_rows, picked = carry
        mine = jax.lax.dynamic_slice_in_dim(order, g * r, r)  # slots
        ok = g * r + jnp.arange(r) < n_live
        t_g, bt = t[mine], block_tables[mine]
        selecting = ok & (t_g >= cfg.idx_topk)

        def scored(_):
            complete = jnp.where(selecting, t_g // kp, 0)

            def block(j, s):
                ids = jax.lax.dynamic_slice_in_dim(bt, j * per, per, 1)
                part = index_scores(
                    cfg, qi[mine], wi[mine],
                    ikp[ids].reshape(r, n_block, cfg.idx_dim),
                    complete - j * n_block)
                return jax.lax.dynamic_update_slice_in_dim(
                    s, part, j * n_block, 1)

            with jax.named_scope("dsa_index"):
                s = jax.lax.fori_loop(
                    0, (complete.max() + n_block - 1) // n_block, block,
                    jnp.full((r, bt.shape[1] * (page // kp)), -jnp.inf, f32))
            return jax.lax.top_k(s, n_picked)[1]

        with jax.named_scope("dsa_select"):
            ids = jax.lax.cond(selecting.any(), scored, lambda _: first, None)
            ids = jnp.where(selecting[:, None], ids, first)
            picked_at = (ids[:, :, None] * kp + jnp.arange(kp)).reshape(r, -1)
            tail = (t_g // kp * kp)[:, None] + jnp.arange(kp)[None, :]
            seen = jnp.concatenate([
                selecting[:, None] | (picked_at <= t_g[:, None]),
                selecting[:, None] & (tail <= t_g[:, None])], 1) & ok[:, None]
            # the picked blocks, then the unfinished one; a block's rows
            # follow one another in its page: [r, topk + kpool, kv_rank]
            held = jnp.concatenate([ids, (t_g // kp)[:, None]], 1)
            at = pool_rows(bt, held, page // kp)[:, :, None] * kp + jnp.arange(kp)
            latent = flat[at.reshape(r, -1)]
        with jax.named_scope("dsa_attend"):
            s = jnp.einsum("bhc,bnc->bhn", q_abs[mine], latent,
                           preferred_element_type=f32) * cfg.softmax_scale
            p = _masked_softmax(s, seen[:, None, :])
            mix = jnp.einsum("bhn,bnc->bhc", p.astype(latent.dtype), latent,
                             preferred_element_type=f32)
        # a short last group's spare entries are frozen slots: zeros there
        return (ctx.at[mine].set(mix),
                seen_rows.at[mine].set(seen.sum(-1, dtype=jnp.int32)),
                picked.at[mine].set(ids))

    ctx, seen_rows, picked = jax.lax.fori_loop(
        0, (n_live + r - 1) // r, group,
        (jnp.zeros((b, cfg.heads, cfg.kv_rank), f32),
         jnp.zeros((b,), jnp.int32),
         jnp.broadcast_to(jnp.arange(n_picked), (b, n_picked))))
    with jax.named_scope("dsa_attend"):
        out = L.mla_output(blk, cfg, ctx)
    return out, {"kv": kvp, "ik": ikp}, {"acc": acc}, {
        "rows": seen_rows, "picked": picked, "attended": out}


def picked_mask(cfg: Glm5NextConfig, ids, q_pos, n_blocks: int):
    """``ids [C, picked_blocks]`` -> which pooled blocks each row picked,
    ``[C, n_blocks]`` bool (rows below ``index_topk`` pick by position,
    not here)."""
    c = ids.shape[0]
    sel = jnp.zeros((c, n_blocks), bool).at[
        jnp.arange(c)[:, None], ids].set(True)
    return sel & (q_pos >= cfg.idx_topk)[:, None]


def dsa_chunk(blk, cfg: Glm5NextConfig, u, pool, st, slot, position,
              block_table, valid, block: int):
    """A sparse-latent layer's prefill chunk: ``u [C, dim]`` (normed) at
    positions ``position..position+C-1`` (page-aligned, so block-aligned),
    of which the first ``valid`` are the prompt's. The chunk's latents
    and the means of its blocks go to whole pages (a block that holds a
    padding row is not complete: nothing scores it before a decode tick
    has rewritten it), the valid rows of the unfinished block to the
    slot's accumulator. Every row picks as a decode tick at its position
    would, and the absorbed product runs over the cached rows a block at
    a time under the picked mask. Returns (output [C, dim], pool, state,
    a look at the selection: the picked blocks ``[C, picked_blocks]`` and
    the output rows themselves)."""
    f32 = jnp.float32
    kvp, ikp = pool["kv"], pool["ik"]
    page, kp = kvp.shape[1], cfg.idx_pool
    c = u.shape[0]
    r = jnp.arange(c)
    q_pos = position + r
    with jax.named_scope("dsa_index"):
        q_abs, c_kv, qi, ki, wi = latent_project(blk, cfg, u)
        ids = jax.lax.dynamic_slice_in_dim(block_table, position // page,
                                           c // page)
        kvp = kvp.at[ids].set(
            c_kv.astype(kvp.dtype).reshape(c // page, page, cfg.kv_rank))
        means = ki.reshape(c // kp, kp, cfg.idx_dim).sum(1) / kp
        ikp = ikp.at[ids].set(
            means.astype(ikp.dtype).reshape(c // page, page // kp, cfg.idx_dim))
        unfinished = (r >= valid // kp * kp) & (r < valid)
        acc = jax.lax.dynamic_update_index_in_dim(
            st["acc"], (ki * unfinished[:, None]).sum(0), slot, 0)
    with jax.named_scope("dsa_select"):
        n_pooled = block_table.shape[0] * (page // kp)
        first = jnp.broadcast_to(jnp.arange(cfg.picked_blocks), (c, cfg.picked_blocks))

        def scored(_):
            pooled = ikp[block_table].reshape(n_pooled, cfg.idx_dim)
            _, top = jax.lax.top_k(
                index_scores(cfg, qi, wi, pooled, q_pos // kp),
                cfg.picked_blocks)
            return top

        top = jax.lax.cond(position + c > cfg.idx_topk, scored,
                           lambda _: first, None)
        sel = picked_mask(cfg, top, q_pos, n_pooled)
    with jax.named_scope("dsa_attend"):
        per = block // page

        def rows_of(j):
            pages = jax.lax.dynamic_slice_in_dim(block_table, j * per, per)
            return kvp[pages].reshape(block, cfg.kv_rank)

        def visible(j):
            at = j * block + jnp.arange(block)
            mine = jnp.repeat(jax.lax.dynamic_slice_in_dim(
                sel, j * (block // kp), block // kp, 1), kp, axis=1)
            causal = at[None, :] <= q_pos[:, None]
            tail = at[None, :] >= (q_pos // kp * kp)[:, None]
            dense = (q_pos < cfg.idx_topk)[:, None]
            return (causal & (dense | mine | tail))[:, None, :]

        ctx = L.attend_latent_blocks(
            cfg, q_abs, rows_of, visible, (position + c - 1) // block + 1,
            "qhc,tc->qht", "qht,tc->qhc")
        out = L.mla_output(blk, cfg, ctx)
    return out, {"kv": kvp, "ik": ikp}, {"acc": acc}, {
        "picked": top, "attended": out}


def rows_picked(cfg: Glm5NextConfig, t):
    """How many rows a row at position ``t`` attends."""
    return jnp.where(t >= cfg.idx_topk,
                     cfg.idx_topk + t % cfg.idx_pool + 1, t + 1)


# ---------------------------------------------------------------------------
# the stack, the two programs
# ---------------------------------------------------------------------------


def init_counters(cfg: Glm5NextConfig) -> dict:
    """The counters on the device, an operand and a result of their own
    of both programs (a buffer each: donated one by one), int32 that
    wraps: ``moe`` are the expert layer's routing counters
    (``moe.init_counters``), ``kda`` this module's (:data:`KDA_COUNTERS`)."""
    return {
        "moe": moe.init_counters(cfg),
        "kda": {name: jnp.zeros((), jnp.int32) for name in KDA_COUNTERS},
    }


def _layers(params, cfg: Glm5NextConfig, x, pools, state, stats, mix, attend,
            live, counted, decode: bool):
    """The stack over the residual streams: ``mix(blk, normed rows, layer
    state) -> (out, layer state)`` for a delta-rule layer, ``attend(blk,
    normed rows, layer pool, layer state) -> (out, pool, state, extra)``
    for a sparse-latent one, then ``moe.mlp`` (the clamp rides the
    SwiGLU weights: :func:`_swiglu`); each
    inside :func:`mhc_sublayer`. ``x [N, dim]`` is copied to every stream
    at the entry and the streams are summed at the exit. Returns (rows,
    pools, state, the routing counters, the sparse-latent layers'
    extras)."""
    pools, state = dict(pools), dict(state)
    routed = dict(stats)
    per_layer, extras = [], []
    streams = jnp.broadcast_to(x[:, None, :], (x.shape[0], cfg.hc, cfg.dim))
    for i in range(cfg.layers):
        blk, key = params["blocks"][str(i)], str(i)

        def mixer(h, blk=blk, key=key, i=i):
            if cfg.linear[i]:
                out, state[key] = mix(blk, h, state[key])
            else:
                out, pools[key], state[key], extra = attend(
                    blk, h, pools[key], state[key])
                extras.append(extra)
            return out

        def ffn(h, blk=blk):
            y, counters = moe.mlp(blk, cfg, h, live, counted)
            moe.add_layer(routed, per_layer, counters, decode)
            return y

        streams = mhc_sublayer(blk["hc_attn"], cfg, streams, blk["attn_norm"],
                               mixer)
        streams = mhc_sublayer(blk["hc_ffn"], cfg, streams, blk["ffn_norm"],
                               ffn)
    moe.add_stack(routed, per_layer, counted, decode)
    x = streams.astype(jnp.float32).sum(1).astype(streams.dtype)
    return x, pools, state, routed, extras


def paged_batch_rows(params, cfg: Glm5NextConfig, tokens, pools, state, stats,
                     positions, block_tables, active, picks: bool = False,
                     index_block: int = INDEX_BLOCK):
    """One decode step for B = slots independent sequences: tokens,
    positions, active ``[B]``, block_tables ``[B, max_pages]`` (a frozen
    row comes with position 0 and a zeroed table row, so its latent lands
    in the null page; its delta-rule state, tail and accumulator have no
    null row and are kept by its ``active`` bit; its routing is neither
    computed on nor counted). Returns (the final rows [B, dim], pools,
    state, stats), and with ``picks`` each sparse-latent layer's look
    last (:func:`dsa_decode`'s ``"picked"`` and ``"attended"``)."""
    x = params["embed"].astype(L.compute_dtype())[tokens]
    i32 = jnp.int32
    live = active.sum(dtype=i32)
    # the slots with the live ones first (in slot order), and how many
    ordered = jnp.argsort(~active, stable=True), live

    def mix(blk, u, st):
        return kda_step(blk, cfg, u, st, active)

    def attend(blk, u, pool, st):
        return dsa_decode(blk, cfg, u, pool, st, positions, block_tables,
                          active, ordered, index_block)

    x, pools, state, routed, looks = _layers(
        params, cfg, x, pools, state, stats["moe"], mix, attend, active,
        active, True)
    n_dsa = len(cfg.dsa_layers)
    r = decode_group(active.shape[0])
    groups = (live + r - 1) // r
    selecting = active & (positions >= cfg.idx_topk)
    kda = PM.add_counts(
        stats["kda"],
        kda_decode_ticks=(live > 0).astype(i32),
        kda_row_ticks=len(cfg.kda_layers) * live,
        dsa_rows_in_context=n_dsa * jnp.where(active, positions + 1, 0).sum(
            dtype=i32),
        dsa_rows_picked=sum(a["rows"].sum(dtype=i32) for a in looks),
        # a short last group gathers for its spare entries too
        dsa_rows_fetched=n_dsa * (cfg.idx_topk + cfg.idx_pool) * groups * r,
        dsa_index_rows_scored=n_dsa * jnp.where(
            selecting, positions // cfg.idx_pool, 0).sum(dtype=i32),
        dsa_row_ticks_selecting=selecting.sum(dtype=i32),
    )
    out = (x, pools, state, {"moe": routed, "kda": kda})
    if picks:
        return (*out, [{k: a[k] for k in ("picked", "attended")} for a in looks])
    return out


def paged_chunk_rows(params, cfg: Glm5NextConfig, chunk_ids, pools, state,
                     stats, position, block_table, valid, slot,
                     block: int = ATTN_BLOCK, picks: bool = False):
    """One prefill chunk of the stream in ``slot``: ``chunk_ids [C]`` at
    positions ``position..position+C-1`` (page-aligned), of which the
    first ``valid`` are the prompt's. ``position``, ``valid`` and
    ``slot`` are traced: one program for every chunk. Every row is
    computed; the counters count the ``valid`` ones. With ``picks`` a
    look at each sparse-latent layer's selection comes back last: its
    picked blocks ``"picked" [C, picked_blocks]`` and its output rows
    ``"attended" [C, dim]`` (an engine built with ``picks`` keeps them
    for a cache audit: :func:`make_paged_engine`)."""
    c = chunk_ids.shape[0]
    x = params["embed"].astype(L.compute_dtype())[chunk_ids]
    counted = jnp.arange(c) < valid

    def mix(blk, u, st):
        return kda_chunk(blk, cfg, u, st, slot, position, valid)

    def attend(blk, u, pool, st):
        return dsa_chunk(blk, cfg, u, pool, st, slot, position, block_table,
                         valid, block)

    x, pools, state, routed, picked = _layers(
        params, cfg, x, pools, state, stats["moe"], mix, attend,
        jnp.ones((c,), bool), counted, False)
    i32 = jnp.int32
    n_dsa = len(cfg.dsa_layers)
    q_pos = position + jnp.arange(c)
    selecting = counted & (q_pos >= cfg.idx_topk)
    swept = ((position + c - 1) // block + 1) * block

    def over_valid(values):
        return jnp.where(counted, values, 0).sum(dtype=i32)

    kda = PM.add_counts(
        stats["kda"],
        kda_chunks=jnp.ones((), i32), kda_chunk_rows=valid.astype(i32),
        dsa_chunk_rows_in_context=n_dsa * over_valid(q_pos + 1),
        dsa_chunk_rows_picked=n_dsa * over_valid(rows_picked(cfg, q_pos)),
        dsa_chunk_rows_fetched=n_dsa * valid.astype(i32) * swept.astype(i32),
        dsa_chunk_index_rows_scored=n_dsa * jnp.where(
            selecting, q_pos // cfg.idx_pool, 0).sum(dtype=i32),
        dsa_chunk_rows_selecting=selecting.sum(dtype=i32),
    )
    out = (x, pools, state, {"moe": routed, "kda": kda})
    return (*out, picked) if picks else out


paged_batch_logits, fused_paged_batch_step = PM.under_the_head(paged_batch_rows)
paged_chunk_logits, fused_paged_chunk_step = PM.under_the_head(paged_chunk_rows)


def window_program(params, cfg, k: int, eos, tokens, pools, stats,
                   positions, bts, active, emitted, max_new, state,
                   picks: bool = False):
    """The K-tick decode window (models/paged_window.make_paged_window with a
    slot state) over :func:`fused_paged_batch_step`: the counters ride
    the window's carry beside the slot state and come back apart.
    Returns (the window's own results — pools, then state, last — and
    stats), and with ``picks`` each sparse-latent layer's look at every
    tick last: ``"picked" [K, B, picked_blocks]``, ``"attended" [K, B,
    dim]`` float32 (tick ``j`` of a row that came in at position ``p`` is
    the row at ``p + j``)."""
    def batch(tokens, pools, positions, bts, active, carried):
        state, stats, *seen = carried
        nxt, pools, state, stats, *look = fused_paged_batch_step(
            params, cfg, tokens, pools, state, stats, positions, bts, active,
            picks=picks)
        if picks:
            tick, kept = seen
            kept = jax.tree.map(
                lambda every, one: jax.lax.dynamic_update_index_in_dim(
                    every, one.astype(every.dtype), tick, 0), kept, look[0])
            seen = [tick + 1, kept]
        return nxt, pools, (state, stats, *seen)

    carried = (state, stats)
    if picks:
        b = tokens.shape[0]
        carried += (jnp.zeros((), jnp.int32), [{
            "picked": jnp.zeros((k, b, cfg.picked_blocks), jnp.int32),
            "attended": jnp.zeros((k, b, cfg.dim), jnp.float32),
        } for _ in cfg.dsa_layers])
    *out, (state, stats, *seen) = make_paged_window(
        batch, k=k, eos=eos, slot_state=True)(
        tokens, pools, positions, bts, active, emitted, max_new, carried)
    result = ((*out, state), stats)
    return (*result, seen[1]) if picks else result


# ---------------------------------------------------------------------------
# the pools, the slot state and the engine
# ---------------------------------------------------------------------------


def init_page_pool(cfg: Glm5NextConfig, num_pages: int, page_size: int,
                   dtype=None) -> dict:
    """The SPARSE-LATENT layers' leaves alone, two a layer under the one
    block table: ``"kv" [P, page, kv_rank]``, a latent row a position,
    and ``"ik" [P, page / index_kpool, index_head_dim]``, a pooled
    indexer row a block. Page 0 is the null page."""
    dtype = dtype or L.compute_dtype()
    return {str(i): {
        "kv": jnp.zeros((num_pages, page_size, cfg.kv_rank), dtype),
        "ik": jnp.zeros((num_pages, page_size // cfg.idx_pool, cfg.idx_dim),
                        dtype),
    } for i in cfg.dsa_layers}


def init_slot_state(cfg: Glm5NextConfig, max_slots: int) -> dict:
    """Every slot's state: a delta-rule layer's float32 state and its
    convolution tail, a sparse-latent layer's accumulator of the indexer
    keys of its unfinished block."""
    state = {}
    for i in range(cfg.layers):
        if cfg.linear[i]:
            state[str(i)] = {
                "s": jnp.zeros((max_slots, cfg.kda_heads, cfg.kda_dim,
                                cfg.kda_dim), jnp.float32),
                "conv": jnp.zeros((max_slots, cfg.conv - 1, 3 * cfg.kda_width),
                                  L.compute_dtype()),
            }
        else:
            state[str(i)] = {
                "acc": jnp.zeros((max_slots, cfg.idx_dim), jnp.float32)}
    return state


def default_num_pages(cfg: Glm5NextConfig, max_slots: int,
                      page_size: int) -> int:
    """The pool's default size, ``paged_model.default_num_pages``' rule in
    bytes. At this model's 1,088 B a token the cap does not bind: 16 x
    16,384 rows are 285 MB, every slot may reach ``max_seq``."""
    return PM.default_num_pages(
        page_size * cfg.kv_bytes_per_token, max_slots, cfg.max_seq, page_size)


def report(cfg: Glm5NextConfig, page_size: int, totals: dict, engine) -> dict:
    """The gauges of one engine (``paged_model.build_engine``'s
    ``report``): the routing counters under the names every expert-layer
    model gives them (``moe.report``), this module's own, the pool and
    the slots' state."""
    return {
        **moe.report(totals["moe"], cfg.moe_layers),
        # raw, for a reader that takes it over a capture's ticks
        "moe_touched": int(totals["moe"]["touched"]),
        **{name: int(totals["kda"][name]) for name in KDA_COUNTERS},
        "dsa_decode_groups": decode_groups(
            cfg, engine.max_slots, int(totals["kda"]["dsa_rows_fetched"])),
        "kv_bytes_per_token": cfg.kv_bytes_per_token,
        "kv_pool_bytes": (engine.allocator.num_pages * page_size
                          * cfg.kv_bytes_per_token),
        "kv_pages_free": engine.allocator.free_pages,
        "kda_state_bytes": cfg.state_bytes_per_slot * engine.max_slots,
    }


def flops_per_token(cfg: Glm5NextConfig) -> float:
    """Weight-matmul FLOPs of one token on this rank (no score, state or
    index term): the mixers, the dense layers, the shared expert, the
    router, the expected ``top_k * held / n_experts`` routed pairs a
    layer, the head."""
    hk, r = cfg.kda_width, cfg.kda_dim
    kda = cfg.dim * (3 * hk + 2 * r + cfg.kda_heads) + 2 * r * hk + hk * cfg.dim
    dsa = (cfg.dim * (cfg.q_rank + cfg.kv_rank + cfg.idx_dim + cfg.idx_heads)
           + cfg.q_rank * (cfg.heads * cfg.nope + cfg.idx_heads * cfg.idx_dim)
           + cfg.heads * cfg.kv_rank * (cfg.nope + cfg.v_dim)
           + cfg.heads * cfg.v_dim * cfg.dim)
    expert = 3 * cfg.dim * cfg.moe_ffn
    moe = (cfg.dim * cfg.n_experts + cfg.n_shared * expert
           + cfg.top_k * cfg.experts_held / cfg.n_experts * expert)
    dense = 3 * cfg.dim * cfg.ffn
    maps = 2 * cfg.hc * cfg.dim * (2 * cfg.hc + cfg.hc * cfg.hc)
    return 2.0 * (
        len(cfg.kda_layers) * kda + len(cfg.dsa_layers) * dsa
        + cfg.layers * maps + cfg.moe_layers * moe
        + (cfg.layers - cfg.moe_layers) * dense + cfg.dim * cfg.vocab)


def make_paged_engine(params, cfg: Glm5NextConfig, *, max_slots: int = 16,
                      eos: int | None = None, page_size: int = 16,
                      chunk: int | None = None,
                      num_pages: int | None = None,
                      window: int | None = None,
                      prefix_cache: bool | None = None,
                      prefix_cache_pages: int | None = None,
                      attn_block: int | None = None, picks: bool = False):
    """The paged continuous-batching engine
    (models/batch_engine.PagedBatchEngine) with the delta-rule layers'
    states, tails and the indexer's accumulators as its slot state and
    pages (latent rows and pooled indexer rows) for the sparse-latent
    layers alone: the same scheduler, allocator and K-tick window as the
    other families (``paged_model.build_engine``; the pools, the counters
    and the slot state are arguments 2, 3 and 9 of the window and 2, 3
    and 6 of the chunk, hence the donation). ``num_pages`` defaults to
    :func:`default_num_pages`. **No prefix cache, whatever is asked**: a
    granted prefix would need the delta-rule state at its boundary, and
    none is kept. Speculation, LoRA and int8 pages are not offered
    (KNOWN_ISSUES.md, PR 43). With ``picks`` (a cache audit's engine,
    never the server's) ``engine.selection`` holds the looks, a
    sparse-latent layer each, of the last chunk and of the last window."""
    if page_size % cfg.idx_pool:
        raise NotImplementedError(
            f"glm5_next: index_kpool {cfg.idx_pool} does not divide the page "
            f"({page_size} rows): a page must hold whole pooled blocks")
    if cfg.max_seq < cfg.idx_topk:
        raise ValueError(
            f"glm5_next: max_seq {cfg.max_seq} is under index_topk "
            f"{cfg.idx_topk}: no row would ever select")
    if prefix_cache or prefix_cache_pages:
        _log.warning(
            "glm5_next: the prefix cache is off for this model: a granted "
            "prefix needs the delta-rule state at its boundary, and none "
            "is kept")
    chunk = PM.default_chunk(chunk, cfg.max_seq)
    attn_block = PM.default_attn_block(attn_block, ATTN_BLOCK, chunk,
                                       cfg.max_seq, page_size)
    if num_pages is None:
        num_pages = default_num_pages(cfg, max_slots, page_size)

    def step(p, ids, pools, stats, position, bt, state, valid, slot):
        return fused_paged_chunk_step(p, cfg, ids, pools, state, stats,
                                      position, bt, valid, slot,
                                      block=attn_block, picks=picks)

    selection = {"chunk": [], "window": []}
    engine = PM.build_engine(
        "glm5_next", cfg, params,
        window_program=lambda p, k, *args: window_program(
            p, cfg, k, eos, *args, picks=picks),
        chunk_step=step, donate_window=(2, 3, 9), donate_chunk=(2, 3, 6),
        init_page_pool=lambda n: init_page_pool(cfg, n, page_size),
        init_slot_state=lambda slots: init_slot_state(cfg, slots),
        counters=init_counters(cfg), report=partial(report, cfg, page_size),
        not_offered=NOT_OFFERED, flops_per_token=flops_per_token(cfg),
        looks=selection,
        max_slots=max_slots, eos=eos, page_size=page_size, chunk=chunk,
        num_pages=num_pages, window=window, prefix_cache=False,
        prefix_cache_pages=0)
    engine.selection = selection
    return engine
