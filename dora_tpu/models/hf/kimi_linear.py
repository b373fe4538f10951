"""Kimi Linear (``model_type`` ``kimi_linear``) on the paged serving path,
as ONE RANK of an expert group: three delta-rule (KDA) layers of per-slot
float32 state for every latent-attention layer (MLA without a query rank
and without a rotary part) that attends EVERY earlier row of its context,
a plain residual, a dense SwiGLU in the first layer(s) and the
sigmoid-routed expert layer (``models/moe.py``) in the rest.

The layer, as the published ``config.json`` names it (``†`` = a detail the
config does not settle, an assumption written down in ``KNOWN_ISSUES.md``
"PR 58"; the float32 reference of the same mathematics, whole sequence, is
``kimi_linear_reference.py``, where each † that is a choice is a switch).
Rows ``x [T, dim]``; every layer is ``x += mixer(rmsnorm(x)); x +=
mlp(rmsnorm(x))``, ``u`` the normed rows:

    KDA (H = linear_attn_config.num_heads heads, d_k = d_v = its head_dim;
         state S [H, d_k, d_v] float32):
      q, k, v = silu(conv(u Wq)), silu(conv(u Wk)), silu(conv(u Wv))   causal depthwise, 4 taps, no bias  †1
      q, k    = l2norm(q) d_k^-0.5, l2norm(k)
      g       = -exp(A_log) softplus((u Wfa) Wfb + dt_bias)   a key channel, <= 0; low rank = head_dim  †2
      beta    = sigmoid(u Wb)
      S~ = diag(exp(g_t)) S_{t-1};  S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T;  o_t = S_t^T q_t
      out     = (rmsnorm_head(o, o_norm) * sigmoid((u Wga) Wgb)) Wo
    latent attention (MLA, q_lora_rank null, mla_use_nope):
      q = u Wq  [H, nope + shared];  [c | k_s] = u Wkva;  c = rmsnorm(c)  (kv_lora_rank)
      k_s: qk_rope_head_dim columns that every head shares, NOT rotated  †3
      k_h = [Wkvb^K c | k_s];  v_h = Wkvb^V c;  softmax scale (nope + shared)^-0.5,
      causal over every earlier row; served absorbed over the stored row [c | k_s]
    mlp: moe.mlp (route / held_experts / the shared expert): sigmoid scores
         in float32, top-k of score + bias, the chosen scores renormalised
         x routed_scaling_factor

What this module adds to the serving path: nothing of a new KIND, and the
first model that has all of these at once, at 64 slots:

* a KDA layer keeps slot state only: the float32 state ``"s" [slots, H,
  d_k, d_v]`` and the convolution's last three rows ``"conv" [slots, 3, 3
  H d_k]``. A decode tick steps live rows only (``ops/kda_state_step``);
  a chunk runs the blocked delta rule (``models/delta_rule``) from the
  slot's state and leaves the state after its last VALID row, which the
  engine snapshots at a prompt's last full chunk edge AND where the prompt
  leaves what the radix tree knew (``state_snapshots``: the prefix cache
  stands beside the slot state).
* a latent layer keeps pages alone: ``"kv" [P, page, row]``, a position's
  ``c`` then its ``k_s`` then zeros to a lane multiple (576 values stored
  as 640). Decode and chunk sweep a row's own pages a block at a time in
  plain XLA (``layers.attend_latent_blocks``), to the LONGEST live
  context for every row: ``mla_rows_swept`` against
  ``mla_rows_in_context`` says what that costs.

Every matrix goes through ``ops/int8_matmul``; the head through
``lm_head_argmax``.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp

from dora_tpu.models import layers as L
from dora_tpu.models import moe
from dora_tpu.models import paged_model as PM
from dora_tpu.models.delta_rule import delta_rule_blocks, delta_rule_step
from dora_tpu.models.hf.loader import TensorFiles, read_config
from dora_tpu.models.paged_window import make_paged_window
from dora_tpu.ops.int8_matmul import quantize_int8_t as _quantize_t

MODEL_TYPES = ("kimi_linear",)

#: rows of one block of cached latent rows (a multiple of the page): the
#: pool is read this many positions at a time, to the longest live context
ATTN_BLOCK = 512
#: rows of one block of the delta rule's blocked form: inside a block the
#: decays enter pairwise, ``block^2 * d_k`` a head on the vector unit
KDA_BLOCK = 16
#: eps of the l2 norms (Kimi Linear's kernels'; no key of the config)
L2_EPS = 1e-6

#: serving knobs of the Qwen path that this model refuses (KNOWN_ISSUES.md)
NOT_OFFERED = {
    "DORA_KV_INT8": "the int8 page kernels are written for per-head K/V "
                    "planes, not latent pages",
    "DORA_SPEC_K": "a rejected draft would have stepped the delta-rule "
                   "state; a snapshot is kept at a prompt's chunk edges, "
                   "none a draft",
    "DORA_LORA_DIR": "the grouped LoRA matmul is fused into the Qwen kernels",
}

#: this module's counters on the device (the expert layer's are moe's)
KDA_COUNTERS = (
    "kda_decode_ticks", "kda_row_ticks", "kda_chunks", "kda_chunk_rows",
    "mla_rows_in_context", "mla_rows_swept", "mla_chunk_rows_in_context",
)


@dataclass(frozen=True)
class KimiLinearConfig:
    vocab: int
    dim: int
    layers: int
    heads: int
    kv_rank: int
    nope: int
    #: ``qk_rope_head_dim``: key columns every head shares, never rotated
    shared: int
    v_dim: int
    ffn: int
    moe_ffn: int
    n_experts: int  # the router's width: every expert of the model
    top_k: int
    n_shared: int
    routed_scale: float
    norm_topk: bool
    norm_eps: float
    max_seq: int
    #: per layer: True = delta-rule (KDA) mixer, False = latent attention
    linear: tuple
    #: per layer: True = expert layer, False = dense MLP
    sparse: tuple
    kda_heads: int
    kda_dim: int
    conv: int
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
    def mla_layers(self) -> tuple:
        return tuple(i for i, s in enumerate(self.linear) if not s)

    @property
    def moe_layers(self) -> int:
        return sum(self.sparse)

    @property
    def latent(self) -> int:
        """Width of one cached row: ``c`` then ``k_s``."""
        return self.kv_rank + self.shared

    @property
    def row(self) -> int:
        """Width of one row AS STORED: ``latent`` padded with zeros to a
        multiple of 128 lanes (640 for 576; ``KimiK2Config.row`` says why)."""
        return -(-self.latent // 128) * 128

    @property
    def softmax_scale(self) -> float:
        return (self.nope + self.shared) ** -0.5

    @property
    def kv_bytes_per_token(self) -> int:
        """What a cached position holds in the paged pool: a stored row a
        LATENT layer (2,560 B for two layers of 640 bf16 values)."""
        return (len(self.mla_layers) * self.row
                * jnp.dtype(L.compute_dtype()).itemsize)

    @property
    def state_bytes_per_slot(self) -> int:
        """Every slot-state leaf of one slot, which is also one snapshot:
        the float32 states and the convolution tails."""
        one = (self.kda_heads * self.kda_dim * self.kda_dim * 4
               + (self.conv - 1) * 3 * self.kda_width
               * jnp.dtype(L.compute_dtype()).itemsize)
        return len(self.kda_layers) * one

    @classmethod
    def from_hf(cls, config: dict, max_seq: int | None = None,
                ep_rank: int | None = None) -> "KimiLinearConfig":
        if config.get("model_type") not in MODEL_TYPES:
            raise ValueError(
                f"model_type {config.get('model_type')!r} is not one of "
                f"{MODEL_TYPES}")
        n = config["num_hidden_layers"]
        lin = config.get("linear_attn_config") or {}
        # the published lists number the layers from 1
        kda = sorted(lin.get("kda_layers") or [])
        full = sorted(lin.get("full_attn_layers") or [])
        if sorted(kda + full) != list(range(1, n + 1)):
            raise ValueError(
                f"kimi_linear: linear_attn_config.kda_layers {kda} and "
                f"full_attn_layers {full} must name layers 1..{n} once each")
        if config.get("q_lora_rank"):
            raise NotImplementedError(
                f"kimi_linear: q_lora_rank {config['q_lora_rank']}: a query "
                f"rank is not written (Kimi Linear has null)")
        if not config.get("mla_use_nope", False):
            raise NotImplementedError(
                "kimi_linear: mla_use_nope false: a rotated latent layer is "
                "not written (Kimi Linear's carries no position)")
        if config.get("rope_scaling"):
            raise NotImplementedError(
                f"kimi_linear: rope_scaling {config['rope_scaling']!r}")
        if (config.get("num_expert_group", 1) != 1
                or config.get("topk_group", 1) != 1):
            raise NotImplementedError(
                "kimi_linear: group-limited routing (num_expert_group / "
                "topk_group > 1) is not written; Kimi Linear has 1")
        if config.get("moe_router_activation_func", "sigmoid") != "sigmoid":
            raise NotImplementedError(
                f"kimi_linear: moe_router_activation_func "
                f"{config['moe_router_activation_func']!r} is not written")
        if config.get("tie_word_embeddings"):
            raise NotImplementedError(
                "kimi_linear: tied embeddings are not written")
        dense, freq = (config.get("first_k_dense_replace", 0),
                       config.get("moe_layer_freq", 1))
        first, held = moe.expert_share(
            {"n_routed_experts": config["num_experts"],
             "ep_size": config.get("ep_size")}, ep_rank)
        return cls(
            vocab=config["vocab_size"],
            dim=config["hidden_size"],
            layers=n,
            heads=config["num_attention_heads"],
            kv_rank=config["kv_lora_rank"],
            nope=config["qk_nope_head_dim"],
            shared=config["qk_rope_head_dim"],
            v_dim=config["v_head_dim"],
            ffn=config["intermediate_size"],
            moe_ffn=config["moe_intermediate_size"],
            n_experts=config["num_experts"],
            top_k=config["num_experts_per_token"],
            n_shared=config.get("num_shared_experts") or 0,
            routed_scale=float(config.get("routed_scaling_factor", 1.0)),
            norm_topk=bool(config.get("moe_renormalize", True)),
            norm_eps=config.get("rms_norm_eps", 1e-5),
            max_seq=max_seq or min(config.get("model_max_length", 2048), 2048),
            linear=tuple(i + 1 in kda for i in range(n)),
            sparse=tuple(i >= dense and i % freq == 0 for i in range(n)),
            kda_heads=int(lin["num_heads"]),
            kda_dim=int(lin["head_dim"]),
            conv=int(lin.get("short_conv_kernel_size", 4)),
            expert_first=first,
            experts_held=held,
        )


# ---------------------------------------------------------------------------
# loading: one layer at a time, only the held experts, int8 on the device
# ---------------------------------------------------------------------------


def _pad_to_lanes(w):
    """Zero output channels (HF layout: rows) up to a multiple of 128."""
    return moe.pad_outputs(w, w.shape[0] + (-w.shape[0]) % 128)


def _load_kda(get, cfg: KimiLinearConfig, a: str) -> dict:
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


def _load_mla(get, cfg: KimiLinearConfig, a: str) -> dict:
    kvb = _quantize_t(get(a + "kv_b_proj.weight"))  # [kv_rank, H*(nope+v)]
    return {
        # the queries and the cached row read the same row: one matrix, the
        # cached row's part padded to its stored width
        "w_in": _quantize_t(
            get(a + "q_proj.weight"),
            moe.pad_outputs(get(a + "kv_a_proj_with_mqa.weight"), cfg.row)),
        "kv_norm": get(a + "kv_a_layernorm.weight"),
        # Kimi-K2's absorbed layout (layers.mla_output reads it)
        "w_kv_b": L.mla_kv_b_weights(kvb, cfg),
        "wo": _quantize_t(get(a + "o_proj.weight")),
    }


def _swiglu_weights(get, prefix: str) -> dict:
    """``moe.swiglu_weights`` under Kimi Linear's names: a routed expert's
    matrices are ``w1`` (gate), ``w3`` (up) and ``w2`` (down); the shared
    expert's and the dense layer's are Llama's."""
    if "shared_experts." in prefix:
        return moe.swiglu_weights(get, prefix)
    return {
        "w_gateup": _quantize_t(get(prefix + "w1.weight"),
                                get(prefix + "w3.weight")),
        "w_down": _quantize_t(get(prefix + "w2.weight")),
    }


def load_layer(get, cfg: KimiLinearConfig, i: int,
               prefix: str = "model.") -> dict:
    """Layer ``i``'s serving parameters from ``get(name) -> device array``
    under Kimi Linear's tensor names (``self_attn.`` for both mixers,
    ``mlp.`` for a dense layer, ``block_sparse_moe.`` for an expert layer:
    †4). Reads the held experts only."""
    lp = f"{prefix}layers.{i}."
    a = lp + "self_attn."
    block = {
        "attn_norm": get(lp + "input_layernorm.weight"),
        "ffn_norm": get(lp + "post_attention_layernorm.weight"),
        **(_load_kda(get, cfg, a) if cfg.linear[i] else _load_mla(get, cfg, a)),
    }
    if not cfg.sparse[i]:
        block["dense"] = moe.swiglu_weights(get, lp + "mlp.")
        return block
    return {**block, **moe.expert_layer_weights(
        get, cfg, lp + "block_sparse_moe.", _swiglu_weights)}


def load(model_dir: str | Path, max_seq: int | None = None,
         ep_rank: int | None = None):
    """(config, serving params) from a HF checkpoint directory, as
    ``kimi_k2.load``: tensors go from the file to the device one at a time
    and are quantized there; the embedding, the routers, the norms and the
    convolution stay in the compute dtype; absent experts are never read."""
    cfg = KimiLinearConfig.from_hf(read_config(model_dir), max_seq, ep_rank)
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
# the delta-rule mixer: one-token step (decode) and blocked form (prefill).
# GLM-5.3-Flash's wrappers over models/delta_rule.py with the published
# gate: the third copy of them (ROADMAP debt 24; no model file imports
# another, tests/test_model_seam.py)
# ---------------------------------------------------------------------------


def _kda_in(blk, cfg: KimiLinearConfig, u):
    """Normed rows -> (q|k|v before the convolution [N, 3 H d_k], the
    decay gate's and the output gate's low-rank halves [N, d_k] each,
    beta's logits [N, H])."""
    p = L.matmul(u, blk["w_in"])
    w, r = 3 * cfg.kda_width, cfg.kda_dim
    return (p[:, :w], p[:, w : w + r], p[:, w + r : w + 2 * r],
            p[:, w + 2 * r : w + 2 * r + cfg.kda_heads])


def _kda_heads(cfg: KimiLinearConfig, conv):
    """Convolved rows ``[N, 3 H d_k]`` float32 -> silu, then q (l2-normed,
    scaled), k (l2-normed), v, each ``[N, H, d_k]``."""
    n = conv.shape[0]
    q, k, v = (t.reshape(n, cfg.kda_heads, cfg.kda_dim)
               for t in jnp.split(jax.nn.silu(conv), 3, axis=-1))

    def l2(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + L2_EPS)

    return l2(q) * cfg.kda_dim ** -0.5, l2(k), v


def _kda_gates(blk, cfg: KimiLinearConfig, fa, ga, b):
    """-> (g [N, H, d_k] <= 0, beta [N, H], the output gate [N, H, d_k]),
    float32. The published gate: ``-exp(A_log) softplus(r + dt_bias)``."""
    f32 = jnp.float32
    shape = (fa.shape[0], cfg.kda_heads, cfg.kda_dim)
    r = L.matmul(fa, blk["w_fb"]).astype(f32).reshape(shape)
    g = -blk["a"][:, None] * jax.nn.softplus(r + blk["dt_bias"])
    gate = jax.nn.sigmoid(L.matmul(ga, blk["w_gb"]).astype(f32)).reshape(shape)
    return g, jax.nn.sigmoid(b.astype(f32)), gate


def _kda_out(blk, cfg: KimiLinearConfig, o, gate):
    """``o [N, H, d_v]`` float32, normed over the head, gated, through
    ``Wo``."""
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.norm_eps)
    o = o * blk["o_norm"].astype(jnp.float32) * gate
    return L.matmul(
        o.astype(L.compute_dtype()).reshape(o.shape[0], cfg.kda_width),
        blk["wo"])


def kda_step(blk, cfg: KimiLinearConfig, u, st, active):
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
    with jax.named_scope("kda_out"):
        return _kda_out(blk, cfg, o, gate), {"s": s, "conv": tail}


def kda_chunk(blk, cfg: KimiLinearConfig, u, st, slot, position, valid):
    """Prefill chunk of one stream: ``u [C, dim]`` normed; ``st`` the
    layer's slot arrays, of which row ``slot`` is this stream's. State and
    tail come in from the slot (zeros when ``position`` is 0: no reset
    call from the host; at any other position what the slot holds, a
    snapshot the engine copied there or an earlier chunk's result) and go
    back as they stand after row ``valid`` (rows past it are padding:
    their ``g`` and ``beta`` are 0, so they neither decay nor write).
    Returns (output [C, dim], state)."""
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
    with jax.named_scope("kda_chunk"):
        s0 = jnp.where(fresh, 0.0, st["s"][slot])
        o, s = delta_rule_blocks(q, k, v, g, beta, s0, KDA_BLOCK)
    with jax.named_scope("kda_out"):
        return _kda_out(blk, cfg, o, gate), {
            "s": jax.lax.dynamic_update_index_in_dim(st["s"], s, slot, 0),
            "conv": jax.lax.dynamic_update_index_in_dim(
                st["conv"], tail, slot, 0),
        }


# ---------------------------------------------------------------------------
# latent attention without a query rank and without a rotary part
# ---------------------------------------------------------------------------


def mla_project(blk, cfg: KimiLinearConfig, u):
    """Normed rows ``u [N, dim]`` -> absorbed queries ``[N, H, row]`` (the
    query's ``nope`` part through ``Wkvb^K``, its ``shared`` columns as
    they are, zeros to the stored width) and the cache rows ``[N, row]``
    (normalised ``c``, ``k_s`` as projected, zeros). Kimi-K2's
    ``mla_project`` arithmetic with ``q = u Wq`` and nothing rotated."""
    n, h, nope = u.shape[0], cfg.heads, cfg.nope
    a = L.matmul(u, blk["w_in"])
    width = h * (nope + cfg.shared)
    q = a[:, :width].reshape(n, h, nope + cfg.shared)
    c = L.rms_norm(a[:, width : width + cfg.kv_rank], blk["kv_norm"],
                   cfg.norm_eps)
    # k_s and the projection's zero columns up to the stored width
    rest = a[:, width + cfg.kv_rank : width + cfg.row]
    kb = blk["w_kv_b"]
    # q' = W_kvb^K^T q_nope, per head; the per-column scale rides the query
    q_nope = (q[..., :nope].astype(jnp.float32) * kb["ks"]).astype(u.dtype)
    q_abs = jnp.einsum(
        "nhj,hjc->nhc", q_nope, kb["k8"].astype(u.dtype),
        preferred_element_type=jnp.float32,
    ).astype(u.dtype)
    pad = cfg.row - cfg.latent
    return (
        jnp.concatenate(
            [q_abs, q[..., nope:], jnp.zeros((n, h, pad), u.dtype)], axis=-1),
        jnp.concatenate([c, rest], axis=-1),
    )


def mla_decode(blk, cfg: KimiLinearConfig, u, pool, positions, block_tables,
               block: int):
    """Decode: ``u [B, dim]`` (normed), one new position a row. Writes
    each row's latent into its page (a frozen row's, at position 0 of a
    zeroed table row, into the null page), then every row attends
    ``0..positions[b]`` through its block table, a block at a time to the
    LONGEST row's context. Returns (attention output [B, dim], pool)."""
    with jax.named_scope("mla_nope_decode"):
        page = pool.shape[1]
        q, rows = mla_project(blk, cfg, u)
        b = u.shape[0]
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


def mla_chunk(blk, cfg: KimiLinearConfig, u, pool, position, block_table,
              block: int):
    """Prefill chunk, absorbed form: ``u [C, dim]`` (normed) at positions
    ``position..position+C-1`` (page-aligned), one block table. Writes the
    chunk's latents as whole pages, then every row attends causally over
    ``0..its own position``."""
    with jax.named_scope("mla_nope_chunk"):
        page = pool.shape[1]
        c = u.shape[0]
        q, rows = mla_project(blk, cfg, u)
        ids = jax.lax.dynamic_slice_in_dim(block_table, position // page,
                                           c // page)
        pool = pool.at[ids].set(
            rows.astype(pool.dtype).reshape(c // page, page, cfg.row))
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


# ---------------------------------------------------------------------------
# the stack, the two programs
# ---------------------------------------------------------------------------


def init_counters(cfg: KimiLinearConfig) -> dict:
    """The counters on the device, an operand and a result of their own of
    both programs (a buffer each: donated one by one), int32 that wraps:
    ``moe`` are the expert layer's routing counters
    (``moe.init_counters``), ``kda`` this module's (:data:`KDA_COUNTERS`)."""
    return {
        "moe": moe.init_counters(cfg),
        "kda": {name: jnp.zeros((), jnp.int32) for name in KDA_COUNTERS},
    }


def _layers(params, cfg: KimiLinearConfig, x, pools, state, stats, mix,
            attend, live, counted, decode: bool):
    """The stack: ``mix(blk, normed rows, layer state) -> (out, layer
    state)`` for a delta-rule layer, ``attend(blk, normed rows, pool) ->
    (out, pool)`` for a latent one, then ``moe.mlp``; a plain pre-norm
    residual around each. Returns (rows, pools, state, the routing
    counters)."""
    pools, state = dict(pools), dict(state)
    routed = dict(stats)
    per_layer = []
    for i in range(cfg.layers):
        blk, key = params["blocks"][str(i)], str(i)
        h = L.rms_norm(x, blk["attn_norm"], cfg.norm_eps)
        if cfg.linear[i]:
            a, state[key] = mix(blk, h, state[key])
        else:
            a, kv = attend(blk, h, pools[key]["kv"])
            pools[key] = {"kv": kv}
        x = x + a.astype(x.dtype)
        y, counters = moe.mlp(
            blk, cfg, L.rms_norm(x, blk["ffn_norm"], cfg.norm_eps), live,
            counted)
        moe.add_layer(routed, per_layer, counters, decode)
        x = x + y
    moe.add_stack(routed, per_layer, counted, decode)
    return x, pools, state, routed


def paged_batch_rows(params, cfg: KimiLinearConfig, tokens, pools, state,
                     stats, positions, block_tables, active,
                     block: int = ATTN_BLOCK):
    """One decode step for B = slots independent sequences: tokens,
    positions, active ``[B]``, block_tables ``[B, max_pages]`` (a frozen
    row comes with position 0 and a zeroed table row, so its latent lands
    in the null page; its delta-rule state and tail have no null row and
    are kept by its ``active`` bit; its routing is neither computed on nor
    counted). Returns (the final rows [B, dim], pools, state, stats)."""
    x = params["embed"].astype(L.compute_dtype())[tokens]
    i32 = jnp.int32

    def mix(blk, u, st):
        return kda_step(blk, cfg, u, st, active)

    def attend(blk, u, pool):
        return mla_decode(blk, cfg, u, pool, positions, block_tables, block)

    x, pools, state, routed = _layers(
        params, cfg, x, pools, state, stats["moe"], mix, attend, active,
        active, True)
    live = active.sum(dtype=i32)
    n_mla = len(cfg.mla_layers)
    kda = PM.add_counts(
        stats["kda"],
        kda_decode_ticks=(live > 0).astype(i32),
        kda_row_ticks=len(cfg.kda_layers) * live,
        mla_rows_in_context=n_mla * jnp.where(active, positions + 1, 0).sum(
            dtype=i32),
        # every row's sweep runs to the longest row's last block
        mla_rows_swept=n_mla * active.shape[0] * block * (
            positions.max().astype(i32) // block + 1),
    )
    return x, pools, state, {"moe": routed, "kda": kda}


def paged_chunk_rows(params, cfg: KimiLinearConfig, chunk_ids, pools, state,
                     stats, position, block_table, valid, slot,
                     block: int = ATTN_BLOCK):
    """One prefill chunk of the stream in ``slot``: ``chunk_ids [C]`` at
    positions ``position..position+C-1`` (page-aligned), of which the
    first ``valid`` are the prompt's. ``position``, ``valid`` and ``slot``
    are traced: one program for every chunk. Every row is computed; the
    counters count the ``valid`` ones."""
    c = chunk_ids.shape[0]
    x = params["embed"].astype(L.compute_dtype())[chunk_ids]
    counted = jnp.arange(c) < valid

    def mix(blk, u, st):
        return kda_chunk(blk, cfg, u, st, slot, position, valid)

    def attend(blk, u, pool):
        return mla_chunk(blk, cfg, u, pool, position, block_table, block)

    x, pools, state, routed = _layers(
        params, cfg, x, pools, state, stats["moe"], mix, attend,
        jnp.ones((c,), bool), counted, False)
    i32 = jnp.int32
    kda = PM.add_counts(
        stats["kda"],
        kda_chunks=jnp.ones((), i32), kda_chunk_rows=valid.astype(i32),
        # rows in context over the prompt's rows: position + 1 of each
        mla_chunk_rows_in_context=len(cfg.mla_layers) * jnp.where(
            counted, position + 1 + jnp.arange(c), 0).sum(dtype=i32),
    )
    return x, pools, state, {"moe": routed, "kda": kda}


paged_batch_logits, fused_paged_batch_step = PM.under_the_head(paged_batch_rows)
paged_chunk_logits, fused_paged_chunk_step = PM.under_the_head(paged_chunk_rows)


def window_program(params, cfg, k: int, eos, block: int, tokens, pools, stats,
                   positions, bts, active, emitted, max_new, state):
    """The K-tick decode window (models/paged_window.make_paged_window with
    a slot state) over :func:`fused_paged_batch_step`: the counters ride
    the window's carry beside the slot state and come back apart. Returns
    (the window's own results — pools, then state, last — and stats)."""
    def batch(tokens, pools, positions, bts, active, carried):
        state, stats = carried
        nxt, pools, state, stats = fused_paged_batch_step(
            params, cfg, tokens, pools, state, stats, positions, bts, active,
            block=block)
        return nxt, pools, (state, stats)

    *out, (state, stats) = make_paged_window(
        batch, k=k, eos=eos, slot_state=True)(
        tokens, pools, positions, bts, active, emitted, max_new,
        (state, stats))
    return (*out, state), stats


# ---------------------------------------------------------------------------
# the pools, the slot state and the engine
# ---------------------------------------------------------------------------


def init_page_pool(cfg: KimiLinearConfig, num_pages: int, page_size: int,
                   dtype=None) -> dict:
    """The LATENT layers' leaves alone: ``"kv" [P, page, row]`` (see
    :attr:`KimiLinearConfig.row`). Page 0 is the null page."""
    dtype = dtype or L.compute_dtype()
    return {str(i): {"kv": jnp.zeros((num_pages, page_size, cfg.row), dtype)}
            for i in cfg.mla_layers}


def init_slot_state(cfg: KimiLinearConfig, rows: int) -> dict:
    """``rows`` rows of state, a delta-rule layer each: the float32 state
    and the convolution tail. The engine asks for ``max_slots`` rows (the
    slots' state) and for its snapshot pool's (the same leaves)."""
    return {str(i): {
        "s": jnp.zeros((rows, cfg.kda_heads, cfg.kda_dim, cfg.kda_dim),
                       jnp.float32),
        "conv": jnp.zeros((rows, cfg.conv - 1, 3 * cfg.kda_width),
                          L.compute_dtype()),
    } for i in cfg.kda_layers}


def default_sizes(cfg: KimiLinearConfig, max_slots: int, page_size: int,
                  snapshots: bool) -> tuple[int, int]:
    """(pages, snapshot rows) by the rules in bytes of ``paged_model``:
    what the device has, less what is in use (the weights), less
    ``POOL_HEADROOM_BYTES``, less the slots' own state; of that the
    snapshot pool takes ``PM.snapshots_that_fit`` rows (none without a
    prefix cache) and the pages the rest (``PM.pages_that_fit``)."""
    page_bytes = page_size * cfg.kv_bytes_per_token
    stats = jax.devices()[0].memory_stats() or {}
    limit, used = stats.get("bytes_limit"), stats.get("bytes_in_use")
    if not limit or used is None:
        return (4 * cfg.max_seq // page_size,
                2 * max_slots if snapshots else 0)
    used += max_slots * cfg.state_bytes_per_slot
    rows = PM.snapshots_that_fit(
        cfg.state_bytes_per_slot, limit - used - PM.POOL_HEADROOM_BYTES,
        max_slots) if snapshots else 0
    used += rows * cfg.state_bytes_per_slot
    return PM.pages_that_fit(page_bytes, limit, used, max_slots, cfg.max_seq,
                             page_size), rows


def report(cfg: KimiLinearConfig, page_size: int, totals: dict,
           engine) -> dict:
    """The gauges of one engine (``paged_model.build_engine``'s
    ``report``): the routing counters under the names every expert-layer
    model gives them (``moe.report``), this module's own, the pool and the
    slots' state. The snapshot pool's are the engine's own."""
    kda = totals["kda"]
    return {
        **moe.report(totals["moe"], cfg.moe_layers),
        # raw, for a reader that takes it over a capture's ticks
        "moe_touched": int(totals["moe"]["touched"]),
        **{name: int(kda[name]) for name in KDA_COUNTERS},
        # the rows a delta-rule chunk program prefilled, under the name the
        # benchmark's prefix_hit_tokens_pct.serve reads them by (Olmo's)
        "gdn_chunk_rows": int(kda["kda_chunk_rows"]),
        "kv_bytes_per_token": cfg.kv_bytes_per_token,
        "kv_pool_bytes": (engine.allocator.num_pages * page_size
                          * cfg.kv_bytes_per_token),
        "kv_pages_free": engine.allocator.free_pages,
        "kda_state_bytes": cfg.state_bytes_per_slot * engine.max_slots,
    }


def flops_per_token(cfg: KimiLinearConfig) -> float:
    """Weight-matmul FLOPs of one token on this rank (no score or state
    term): the mixers, the dense layers, the shared expert, the router,
    the expected ``top_k * held / n_experts`` routed pairs a layer, the
    head."""
    hk, r = cfg.kda_width, cfg.kda_dim
    kda = cfg.dim * (3 * hk + 2 * r + cfg.kda_heads) + 2 * r * hk + hk * cfg.dim
    mla = (cfg.dim * (cfg.heads * (cfg.nope + cfg.shared) + cfg.latent)
           + cfg.heads * cfg.kv_rank * (cfg.nope + cfg.v_dim)
           + cfg.heads * cfg.v_dim * cfg.dim)
    expert = 3 * cfg.dim * cfg.moe_ffn
    routed = (cfg.dim * cfg.n_experts + cfg.n_shared * expert
              + cfg.top_k * cfg.experts_held / cfg.n_experts * expert)
    dense = 3 * cfg.dim * cfg.ffn
    return 2.0 * (
        len(cfg.kda_layers) * kda + len(cfg.mla_layers) * mla
        + cfg.moe_layers * routed + (cfg.layers - cfg.moe_layers) * dense
        + cfg.dim * cfg.vocab)


def make_paged_engine(params, cfg: KimiLinearConfig, *, max_slots: int = 16,
                      eos: int | None = None, page_size: int = 16,
                      chunk: int | None = None,
                      num_pages: int | None = None,
                      window: int | None = None,
                      prefix_cache: bool | None = None,
                      prefix_cache_pages: int | None = None,
                      attn_block: int | None = None,
                      state_snapshots: int | None = None):
    """The paged continuous-batching engine
    (models/batch_engine.PagedBatchEngine) with the delta-rule layers'
    states and tails as its slot state and pages for the latent layers
    alone (``paged_model.build_engine``; the pools, the counters and the
    slot state are arguments 2, 3 and 9 of the window and 2, 3 and 6 of
    the chunk, hence the donation). **The prefix cache works**: the engine
    keeps ``state_snapshots`` rows shaped like one slot's state, copies a
    prompt's state there after its last full chunk and where it leaves
    what the radix tree knew, and grants a later prompt the pages up to a
    snapshot's depth with the snapshot. ``num_pages`` and
    ``state_snapshots`` default to :func:`default_sizes`. Speculation,
    LoRA and int8 pages are not offered (KNOWN_ISSUES.md, PR 58)."""
    if prefix_cache is None:
        prefix_cache = os.environ.get("DORA_PREFIX_CACHE", "0") != "0"
    chunk = PM.default_chunk(chunk, cfg.max_seq)
    attn_block = PM.default_attn_block(attn_block, ATTN_BLOCK, chunk,
                                       cfg.max_seq, page_size)
    pages, rows = default_sizes(cfg, max_slots, page_size, prefix_cache)
    if num_pages is None:
        num_pages = pages
    if state_snapshots is None:
        state_snapshots = rows

    def step(p, ids, pools, stats, position, bt, state, valid, slot):
        return fused_paged_chunk_step(p, cfg, ids, pools, state, stats,
                                      position, bt, valid, slot,
                                      block=attn_block)

    return PM.build_engine(
        "kimi_linear", cfg, params,
        window_program=lambda p, k, *args: window_program(
            p, cfg, k, eos, attn_block, *args),
        chunk_step=step, donate_window=(2, 3, 9), donate_chunk=(2, 3, 6),
        init_page_pool=lambda n: init_page_pool(cfg, n, page_size),
        init_slot_state=lambda rows: init_slot_state(cfg, rows),
        counters=init_counters(cfg), report=partial(report, cfg, page_size),
        not_offered=NOT_OFFERED, flops_per_token=flops_per_token(cfg),
        max_slots=max_slots, eos=eos, page_size=page_size, chunk=chunk,
        num_pages=num_pages, window=window, prefix_cache=prefix_cache,
        prefix_cache_pages=prefix_cache_pages,
        state_snapshots=state_snapshots if prefix_cache else 0)
