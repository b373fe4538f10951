"""Olmo-Hybrid (``model_type`` ``olmo_hybrid``) on the paged serving path:
three gated-delta-rule layers (the Gated DeltaNet: ONE decay a head) for
every full-attention layer, a dense SwiGLU in every layer.

The layer, as the published ``config.json`` names it (``†`` = a detail the
config does not settle, an assumption written down in ``KNOWN_ISSUES.md``
"PR 56"; the float32 reference of the same mathematics, one token at a
time, is ``olmo_hybrid_reference.py``, where each † is a switch). Rows
``x [T, hidden]``; every layer is ``x += norm(mixer(x)); x += norm(mlp(x))``
(†1: Olmo 2 and 3 norm a sublayer's OUTPUT):

    linear_attention (H = linear_num_key_heads = linear_num_value_heads,
                      d_k = linear_key_head_dim, d_v = linear_value_head_dim;
                      state S [H, d_k, d_v] float32):
      [q; k; v] = silu(conv(x Wq | x Wk | x Wv))     causal depthwise, 4 taps, zeros before position 0  †5
      q, k      = l2norm(q) d_k^-0.5, l2norm(k)       over the head
      beta      = sigmoid(x Wb) (x 2: linear_allow_neg_eigval)   a head, in (0, 2)
      g         = -exp(A_log) softplus(x Wa + dt_bias)           a head: log alpha <= 0
      S~ = exp(g_t) S_{t-1};  S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T;  o_t = S_t^T q_t
      out       = (rmsnorm_{d_v}(o, o_norm) * silu(x Wg)) Wo
    full_attention (num_attention_heads = num_key_value_heads heads of hidden / heads):
      q, k, v = x Wq, x Wk, x Wv;  q, k = rmsnorm(q, q_norm), rmsnorm(k, k_norm)
                over the WHOLE projection (†2: Olmo 2 and 3's);  no rotary
                (†3: ``rope_theta`` null; the delta-rule layers carry the order)
      softmax attention, scale head_dim^-0.5, causal
    mlp: Wd (silu(x Wg) * x Wu)

What this module adds to the serving path: **a slot state that the prefix
cache can stand beside.**

* a linear layer keeps slot state only: the float32 state ``"s" [slots,
  H, d_k, d_v]`` and the convolution's last three rows ``"conv" [slots,
  3, 2 H d_k + H d_v]``. A decode tick steps live rows only
  (``models/delta_rule.delta_rule_step``: the state stays in HBM); a chunk
  runs the blocked delta rule with the triangular system SOLVED
  (``head_gated_delta_rule_blocks``: ``beta`` reaches 2) from the slot's
  state, zeros at position 0, and leaves the state after its last VALID
  row. After a chunk whose every row is the prompt's, that is the state
  at a page-aligned boundary, and the engine copies it (bit for bit,
  float32) into a row of its snapshot pool, which a later prompt with the
  same first rows is granted with the pages (``make_paged_engine`` opts
  in: ``state_snapshots``).
* a full layer keeps pages alone (``"kv" [P, page, 2 * KV * hd]``): a
  position's 30 key heads then its 30 value heads, 15,360 B at the
  published widths. Decode projects and norms in XLA and sweeps a row's
  own pages in ``ops/decode_block.attention_paged_rows_step``, whose
  joined layout has no one-row form: each K/V head's ONE query row rides
  with a second, zero, row that is dropped.

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
from dora_tpu.models.delta_rule import (
    delta_rule_step, head_gated_delta_rule_blocks)
from dora_tpu.models.hf.loader import TensorFiles, read_config
from dora_tpu.models.paged_window import make_paged_window
from dora_tpu.ops import decode_block as DB
from dora_tpu.ops.int8_matmul import quantize_int8_t as _quantize_t

MODEL_TYPES = ("olmo_hybrid",)

#: rows of one attention block of a full layer's CHUNK (a multiple of the
#: page): its pool is read this many positions at a time
ATTN_BLOCK = 256
#: rows of one block of the delta rule's blocked form: with one decay a
#: head a block's pairwise terms are matrix products, so a block is as
#: long as the unit's tile is wide; between blocks the state is carried
GDN_BLOCK = 64
L2_EPS = 1e-6
#: query rows a K/V head hands the decode sweep: the model's one and a
#: zero row (``attention_paged_rows_step``'s joined layout needs two)
SWEEP_ROWS = 2

#: serving knobs of the Qwen path that this model refuses (KNOWN_ISSUES.md)
NOT_OFFERED = {
    "DORA_KV_INT8": "the int8 page kernels are fused into the Qwen "
                    "attention kernels, which this model does not run",
    "DORA_SPEC_K": "a rejected draft would have stepped the delta-rule "
                   "state; a snapshot is kept at a prompt's chunk edge, "
                   "none a draft",
    "DORA_LORA_DIR": "the grouped LoRA matmul is fused into the Qwen kernels",
}

#: the counters on the device
GDN_COUNTERS = (
    "gdn_decode_ticks", "gdn_row_ticks", "gdn_chunks", "gdn_chunk_rows",
    "gdn_zero_starts", "gdn_chunk_positions", "global_kv_rows_read",
    "global_kv_rows_swept",
)


@dataclass(frozen=True)
class OlmoHybridConfig:
    vocab: int
    dim: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    norm_eps: float
    max_seq: int
    #: per layer: True = gated-delta-rule mixer, False = full attention
    linear: tuple
    gdn_heads: int
    gdn_dk: int
    gdn_dv: int
    conv: int
    #: ``linear_allow_neg_eigval``: beta is doubled
    neg_eigval: bool

    @property
    def q_width(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def gdn_key_width(self) -> int:
        return self.gdn_heads * self.gdn_dk

    @property
    def gdn_value_width(self) -> int:
        return self.gdn_heads * self.gdn_dv

    @property
    def conv_width(self) -> int:
        """Channels the convolution runs over: q, k and v side by side."""
        return 2 * self.gdn_key_width + self.gdn_value_width

    @property
    def gdn_layers(self) -> tuple:
        return tuple(i for i, s in enumerate(self.linear) if s)

    @property
    def full_layers(self) -> tuple:
        return tuple(i for i, s in enumerate(self.linear) if not s)

    @property
    def kv_bytes_per_token(self) -> int:
        """What a cached position holds in the paged pool: keys and values
        of the FULL layers alone (61,440 B at four layers of 2 x 30 x 128
        bf16 values)."""
        return (len(self.full_layers) * 2 * self.kv_width
                * jnp.dtype(L.compute_dtype()).itemsize)

    @property
    def state_bytes_per_slot(self) -> int:
        """Every slot-state leaf of one slot, which is also one snapshot:
        the float32 states and the convolution tails."""
        one = (self.gdn_heads * self.gdn_dk * self.gdn_dv * 4
               + (self.conv - 1) * self.conv_width
               * jnp.dtype(L.compute_dtype()).itemsize)
        return len(self.gdn_layers) * one

    @classmethod
    def from_hf(cls, config: dict,
                max_seq: int | None = None) -> "OlmoHybridConfig":
        if config.get("model_type") not in MODEL_TYPES:
            raise ValueError(
                f"model_type {config.get('model_type')!r} is not one of "
                f"{MODEL_TYPES}")
        n = config["num_hidden_layers"]
        kinds = config.get("layer_types")
        if kinds is None or len(kinds) != n:
            raise ValueError(
                f"olmo_hybrid: layer_types must name all {n} layers, got "
                f"{kinds!r}")
        unknown = set(kinds) - {"linear_attention", "full_attention"}
        if unknown:
            raise NotImplementedError(
                f"olmo_hybrid: layer_types {sorted(unknown)} is not written")
        heads = config["num_attention_heads"]
        if config["hidden_size"] % heads:
            raise ValueError(
                f"olmo_hybrid: hidden_size {config['hidden_size']} is no "
                f"multiple of num_attention_heads {heads}")
        theta = (config.get("rope_parameters") or {}).get("rope_theta")
        if theta is not None or config.get("rope_theta") is not None:
            raise NotImplementedError(
                f"olmo_hybrid: rope_theta {theta!r}: rotary full-attention "
                f"layers are not written (Olmo-Hybrid-7B has null)")
        if config.get("attention_bias"):
            raise NotImplementedError(
                "olmo_hybrid: attention_bias is not written")
        if config.get("tie_word_embeddings"):
            raise NotImplementedError(
                "olmo_hybrid: tied embeddings are not written")
        h = config["linear_num_value_heads"]
        if config["linear_num_key_heads"] != h:
            raise NotImplementedError(
                f"olmo_hybrid: linear_num_key_heads "
                f"{config['linear_num_key_heads']} != linear_num_value_heads "
                f"{h}: grouped delta-rule heads are not written")
        return cls(
            vocab=config["vocab_size"],
            dim=config["hidden_size"],
            layers=n,
            heads=heads,
            kv_heads=config.get("num_key_value_heads") or heads,
            head_dim=config.get("head_dim") or config["hidden_size"] // heads,
            ffn=config["intermediate_size"],
            norm_eps=config.get("rms_norm_eps", 1e-6),
            max_seq=max_seq
            or min(config.get("max_position_embeddings", 2048), 2048),
            linear=tuple(k == "linear_attention" for k in kinds),
            gdn_heads=h,
            gdn_dk=config["linear_key_head_dim"],
            gdn_dv=config["linear_value_head_dim"],
            conv=int(config.get("linear_conv_kernel_dim", 4)),
            neg_eigval=bool(config.get("linear_allow_neg_eigval", False)),
        )


# ---------------------------------------------------------------------------
# loading: one layer at a time, int8 on the device
# ---------------------------------------------------------------------------


def _pad_to_lanes(w):
    """Zero output channels (HF layout: rows) up to a multiple of 128."""
    return moe.pad_outputs(w, w.shape[0] + (-w.shape[0]) % 128)


def gate_lanes(cfg: OlmoHybridConfig) -> int:
    """Columns ``Wa`` and ``Wb`` take each in the fused input matrix."""
    return cfg.gdn_heads + (-cfg.gdn_heads) % 128


def _load_gdn(get, cfg: OlmoHybridConfig, a: str) -> dict:
    f32 = jnp.float32
    taps = [get(a + f"{n}_conv1d.weight").reshape(-1, cfg.conv) for n in "qkv"]
    return {
        # q, k, v, the output gate, the decay's and beta's logits read the
        # same row: one matrix
        "w_in": _quantize_t(
            get(a + "q_proj.weight"), get(a + "k_proj.weight"),
            get(a + "v_proj.weight"), get(a + "g_proj.weight"),
            _pad_to_lanes(get(a + "a_proj.weight")),
            _pad_to_lanes(get(a + "b_proj.weight"))),
        "conv_w": jnp.concatenate(taps, 0).T,  # [taps, channels], oldest first
        "a": jnp.exp(get(a + "A_log").astype(f32)).reshape(cfg.gdn_heads),
        "dt_bias": get(a + "dt_bias").astype(f32).reshape(cfg.gdn_heads),
        "o_norm": get(a + "o_norm.weight"),
        "wo": _quantize_t(get(a + "o_proj.weight")),
    }


def _load_full(get, cfg: OlmoHybridConfig, a: str) -> dict:
    return {
        "wqkv": _quantize_t(get(a + "q_proj.weight"), get(a + "k_proj.weight"),
                            get(a + "v_proj.weight")),
        "q_norm": get(a + "q_norm.weight"),
        "k_norm": get(a + "k_norm.weight"),
        "wo": _quantize_t(get(a + "o_proj.weight")),
    }


def load_layer(get, cfg: OlmoHybridConfig, i: int,
               prefix: str = "model.") -> dict:
    """Layer ``i``'s serving parameters from ``get(name) -> device array``
    under the HF tensor names (Olmo 3's for the norms, the full layer and
    the MLP; Qwen3-Next's ``linear_attn`` for the mixer: †4)."""
    lp = f"{prefix}layers.{i}."
    mixer = (_load_gdn(get, cfg, lp + "linear_attn.") if cfg.linear[i]
             else _load_full(get, cfg, lp + "self_attn."))
    return {
        "attn_norm": get(lp + "post_attention_layernorm.weight"),
        "ffn_norm": get(lp + "post_feedforward_layernorm.weight"),
        **mixer,
        "dense": moe.swiglu_weights(get, lp + "mlp."),
    }


def load(model_dir: str | Path, max_seq: int | None = None):
    """(config, serving params) from a HF checkpoint directory: tensors go
    from the file to the device one at a time and are quantized there; the
    embedding, the norms and the convolution stay in the compute dtype."""
    cfg = OlmoHybridConfig.from_hf(read_config(model_dir), max_seq)
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
# the gated-delta-rule mixer: one-token step (decode), blocked form (prefill)
# ---------------------------------------------------------------------------


def _gdn_in(blk, cfg: OlmoHybridConfig, x):
    """Rows -> (q|k|v before the convolution [N, channels], the output
    gate's logits [N, H d_v], the decay's and beta's logits [N, H])."""
    p = L.matmul(x, blk["w_in"])
    c, v, lanes = cfg.conv_width, cfg.gdn_value_width, gate_lanes(cfg)
    return (p[:, :c], p[:, c : c + v],
            p[:, c + v : c + v + cfg.gdn_heads],
            p[:, c + v + lanes : c + v + lanes + cfg.gdn_heads])


def _gdn_heads(cfg: OlmoHybridConfig, conv):
    """Convolved rows ``[N, channels]`` float32 -> silu, then q (l2-normed,
    scaled), k (l2-normed) ``[N, H, d_k]`` and v ``[N, H, d_v]``."""
    n, kw = conv.shape[0], cfg.gdn_key_width
    act = jax.nn.silu(conv)
    q = act[:, :kw].reshape(n, cfg.gdn_heads, cfg.gdn_dk)
    k = act[:, kw : 2 * kw].reshape(n, cfg.gdn_heads, cfg.gdn_dk)
    v = act[:, 2 * kw :].reshape(n, cfg.gdn_heads, cfg.gdn_dv)

    def l2(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + L2_EPS)

    return l2(q) * cfg.gdn_dk ** -0.5, l2(k), v


def _gdn_gates(blk, cfg: OlmoHybridConfig, a, b):
    """-> (g [N, H] = log alpha <= 0, beta [N, H] in (0, 2) with
    ``neg_eigval``), float32."""
    f32 = jnp.float32
    g = -blk["a"] * jax.nn.softplus(a.astype(f32) + blk["dt_bias"])
    beta = jax.nn.sigmoid(b.astype(f32))
    return g, beta * 2.0 if cfg.neg_eigval else beta


def _gdn_out(blk, cfg: OlmoHybridConfig, o, gate):
    """``o [N, H, d_v]`` float32, normed over the head, gated by
    ``silu(gate)``, through ``Wo``."""
    with jax.named_scope("gdn_gate_out"):
        f32 = jnp.float32
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.norm_eps)
        o = o * blk["o_norm"].astype(f32) * jax.nn.silu(
            gate.astype(f32)).reshape(o.shape)
        return L.matmul(
            o.astype(L.compute_dtype()).reshape(o.shape[0],
                                                cfg.gdn_value_width),
            blk["wo"])


def gdn_step(blk, cfg: OlmoHybridConfig, x, st, active):
    """Decode: one token a row, ``x [B, dim]``; ``st`` is the layer's
    ``{"s": [B, H, d_k, d_v] f32, "conv": [B, taps-1, channels]}`` (row =
    slot). Rows with ``active`` off leave both as they were. Returns (the
    mixer's output [B, dim], state)."""
    f32 = jnp.float32
    with jax.named_scope("gdn_proj"):
        qkv, gate, a, b = _gdn_in(blk, cfg, x)
    with jax.named_scope("gdn_conv"):
        tail = st["conv"]
        taps = jnp.concatenate([tail, qkv[:, None].astype(tail.dtype)], 1)
        conv = jnp.sum(taps.astype(f32) * blk["conv_w"].astype(f32)[None], 1)
        tail = jnp.where(active[:, None, None], taps[:, 1:], tail)
        q, k, v = _gdn_heads(cfg, conv)
        g, beta = _gdn_gates(blk, cfg, a, b)
    with jax.named_scope("gdn_step"):
        # one pass over the live rows' state; products and sums on the
        # vector unit: exact in float32
        o, s = delta_rule_step(st["s"], g, k, q, v, beta, active)
    return _gdn_out(blk, cfg, o, gate), {"s": s, "conv": tail}


def gdn_chunk(blk, cfg: OlmoHybridConfig, x, st, slot, position, valid):
    """Prefill chunk of one stream: ``x [C, dim]``; ``st`` the layer's
    slot arrays, of which row ``slot`` is this stream's. State and tail
    come in from the slot (zeros when ``position`` is 0: no reset call
    from the host; at any other position what the slot holds, a snapshot
    the engine copied there or an earlier chunk's result) and go back as
    they stand after row ``valid`` (rows past it are padding: their ``g``
    and ``beta`` are 0, so they neither decay nor write). Returns (output
    [C, dim], state)."""
    f32 = jnp.float32
    c = x.shape[0]
    fresh = position == 0
    with jax.named_scope("gdn_proj"):
        qkv, gate, a, b = _gdn_in(blk, cfg, x)
    with jax.named_scope("gdn_conv"):
        tail = jnp.where(fresh, 0, st["conv"][slot])  # [taps-1, channels]
        rows = jnp.concatenate([tail, qkv.astype(tail.dtype)], 0)
        w = blk["conv_w"].astype(f32)
        conv = sum(
            jax.lax.dynamic_slice_in_dim(rows, j, c).astype(f32) * w[j]
            for j in range(cfg.conv))
        # the last taps-1 rows that are the prompt's: rows valid-3..valid-1
        tail = jax.lax.dynamic_slice_in_dim(rows, valid, cfg.conv - 1)
        q, k, v = _gdn_heads(cfg, conv)
        g, beta = _gdn_gates(blk, cfg, a, b)
        live = jnp.arange(c) < valid
        g = jnp.where(live[:, None], g, 0.0)
        beta = jnp.where(live[:, None], beta, 0.0)
    with jax.named_scope("gdn_scan"):
        s0 = jnp.where(fresh, 0.0, st["s"][slot])
        o, s = head_gated_delta_rule_blocks(q, k, v, g, beta, s0, GDN_BLOCK)
    return _gdn_out(blk, cfg, o, gate), {
        "s": jax.lax.dynamic_update_index_in_dim(st["s"], s, slot, 0),
        "conv": jax.lax.dynamic_update_index_in_dim(
            st["conv"], tail, slot, 0),
    }


# ---------------------------------------------------------------------------
# full attention: pages, norms over the whole projection, no rotary
# ---------------------------------------------------------------------------


def _qkv(blk, cfg: OlmoHybridConfig, x):
    """Rows ``x [N, dim]`` -> q ``[N, KV, G, hd]``, k and v ``[N, KV,
    hd]``: projected, q and k normed over the whole projection."""
    n = x.shape[0]
    kv, hd = cfg.kv_heads, cfg.head_dim
    p = L.matmul(x, blk["wqkv"])
    q, k = p[:, : cfg.q_width], p[:, cfg.q_width : cfg.q_width + cfg.kv_width]
    v = p[:, cfg.q_width + cfg.kv_width :].reshape(n, kv, hd)
    with jax.named_scope("qk_norm"):
        q = L.rms_norm(q, blk["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, blk["k_norm"], cfg.norm_eps)
    return q.reshape(n, kv, cfg.heads // kv, hd), k.reshape(n, kv, hd), v


def _split_rows(cfg: OlmoHybridConfig, rows):
    """Cached rows ``[..., 2 * KV * hd]`` -> keys, values ``[..., KV, hd]``."""
    rows = rows.reshape(*rows.shape[:-1], 2, cfg.kv_heads, cfg.head_dim)
    return rows[..., 0, :, :], rows[..., 1, :, :]


def _out(blk, cfg: OlmoHybridConfig, ctx, dtype):
    return L.matmul(ctx.astype(dtype).reshape(-1, cfg.q_width), blk["wo"])


def full_decode(blk, cfg: OlmoHybridConfig, x, pool, positions, block_tables,
                counts):
    """A full layer's decode tick: writes each row's K/V into its page (a
    frozen row's, at position 0 of a zeroed table row, into the null
    page), then row ``b`` attends its first ``counts[b]`` positions
    through the block table, its own pages and no others. Returns (output
    [B, dim], pool)."""
    with jax.named_scope("attn_global"):
        page = pool.shape[1]
        b = x.shape[0]
        q, k, v = _qkv(blk, cfg, x)
        pool = pool.at[
            block_tables[jnp.arange(b), positions // page], positions % page
        ].set(L.kv_rows(cfg, k, v).astype(pool.dtype))
        rows = q.shape[2]
        if rows < SWEEP_ROWS:  # the sweep's joined layout has no one-row form
            q = jnp.pad(q, ((0, 0), (0, 0), (0, SWEEP_ROWS - rows), (0, 0)))
        ctx = DB.attention_paged_rows_step(q, pool, counts, block_tables)
        return _out(blk, cfg, ctx[:, :, :rows], x.dtype), pool


def full_chunk(blk, cfg: OlmoHybridConfig, x, pool, position, block_table,
               block: int):
    """A full layer's prefill chunk: writes the chunk's K/V as whole pages,
    then every row attends causally over ``0..its own position``."""
    with jax.named_scope("attn_global"):
        page = pool.shape[1]
        c = x.shape[0]
        q, k, v = _qkv(blk, cfg, x)
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
        return _out(blk, cfg, ctx, x.dtype), pool


# ---------------------------------------------------------------------------
# the stack, the two programs
# ---------------------------------------------------------------------------


def init_counters(cfg: OlmoHybridConfig) -> dict:
    """The counters on the device (:data:`GDN_COUNTERS`), an operand and a
    result of their own of both programs, int32 that wraps."""
    return {name: jnp.zeros((), jnp.int32) for name in GDN_COUNTERS}


def _layers(params, cfg: OlmoHybridConfig, x, pools, state, mix, attend):
    """The stack: ``mix(blk, rows, layer state) -> (out, layer state)`` for
    a linear layer, ``attend(blk, rows, pool) -> (out, pool)`` for a full
    one, then the SwiGLU; each sublayer's OUTPUT is normed and added.
    Returns (rows, pools, state)."""
    pools, state = dict(pools), dict(state)
    for i in range(cfg.layers):
        blk, key = params["blocks"][str(i)], str(i)
        if cfg.linear[i]:
            a, state[key] = mix(blk, x, state[key])
        else:
            a, kv = attend(blk, x, pools[key]["kv"])
            pools[key] = {"kv": kv}
        x = x + L.rms_norm(a, blk["attn_norm"], cfg.norm_eps).astype(x.dtype)
        with jax.named_scope("mlp"):
            y = moe.swiglu(blk["dense"], x)
        x = x + L.rms_norm(y, blk["ffn_norm"], cfg.norm_eps).astype(x.dtype)
    return x, pools, state


def paged_batch_rows(params, cfg: OlmoHybridConfig, tokens, pools, state,
                     stats, positions, block_tables, active):
    """One decode step for B = slots independent sequences: tokens,
    positions, active ``[B]``, block_tables ``[B, max_pages]`` (a frozen
    row comes with position 0 and a zeroed table row, so its K/V write
    lands in the null page; its delta-rule state and tail have no null row
    and are kept by its ``active`` bit). Returns (the final rows [B, dim],
    pools, state, stats)."""
    x = params["embed"].astype(L.compute_dtype())[tokens]
    seen = jnp.where(active, positions + 1, 0)  # rows each row attends

    def mix(blk, u, st):
        return gdn_step(blk, cfg, u, st, active)

    def attend(blk, u, pool):
        return full_decode(blk, cfg, u, pool, positions, block_tables, seen)

    x, pools, state = _layers(params, cfg, x, pools, state, mix, attend)
    i32 = jnp.int32
    live = active.sum(dtype=i32)
    n_full = len(cfg.full_layers)
    swept = 0
    if n_full:
        # a group is DB's page group of cache rows, fetched whole for its
        # last row
        group = DB.sweep_group_rows(
            next(iter(pools.values()))["kv"].shape[1], block_tables.shape[1])
        swept = n_full * group * ((seen + group - 1) // group).sum(dtype=i32)
    stats = PM.add_counts(
        stats,
        gdn_decode_ticks=(live > 0).astype(i32),
        gdn_row_ticks=len(cfg.gdn_layers) * live,
        global_kv_rows_read=n_full * seen.sum(dtype=i32),
        global_kv_rows_swept=swept,
    )
    return x, pools, state, stats


def paged_chunk_rows(params, cfg: OlmoHybridConfig, chunk_ids, pools, state,
                     stats, position, block_table, valid, slot,
                     block: int = ATTN_BLOCK):
    """One prefill chunk of the stream in ``slot``: ``chunk_ids [C]`` at
    positions ``position..position+C-1`` (page-aligned), of which the
    first ``valid`` are the prompt's. ``position``, ``valid`` and ``slot``
    are traced: one program for every chunk. Every row is computed; the
    counters count the ``valid`` ones."""
    c = chunk_ids.shape[0]
    x = params["embed"].astype(L.compute_dtype())[chunk_ids]

    def mix(blk, u, st):
        return gdn_chunk(blk, cfg, u, st, slot, position, valid)

    def attend(blk, u, pool):
        return full_chunk(blk, cfg, u, pool, position, block_table, block)

    x, pools, state = _layers(params, cfg, x, pools, state, mix, attend)
    i32 = jnp.int32
    stats = PM.add_counts(
        stats,
        gdn_chunks=jnp.ones((), i32), gdn_chunk_rows=valid.astype(i32),
        gdn_zero_starts=(position == 0).astype(i32),
        # rows in context over the prompt's rows: position + 1 of each
        gdn_chunk_positions=jnp.where(
            jnp.arange(c) < valid, position + 1 + jnp.arange(c), 0).sum(dtype=i32),
    )
    return x, pools, state, stats


paged_batch_logits, fused_paged_batch_step = PM.under_the_head(paged_batch_rows)
paged_chunk_logits, fused_paged_chunk_step = PM.under_the_head(paged_chunk_rows)


def window_program(params, cfg, k: int, eos, tokens, pools, stats,
                   positions, bts, active, emitted, max_new, state):
    """The K-tick decode window (models/paged_window.make_paged_window with
    a slot state) over :func:`fused_paged_batch_step`: the counters ride
    the window's carry beside the slot state and come back apart. Returns
    (the window's own results — pools, then state, last — and stats)."""
    def batch(tokens, pools, positions, bts, active, carried):
        state, stats = carried
        nxt, pools, state, stats = fused_paged_batch_step(
            params, cfg, tokens, pools, state, stats, positions, bts, active)
        return nxt, pools, (state, stats)

    *out, (state, stats) = make_paged_window(
        batch, k=k, eos=eos, slot_state=True)(
        tokens, pools, positions, bts, active, emitted, max_new,
        (state, stats))
    return (*out, state), stats


# ---------------------------------------------------------------------------
# the pools, the slot state and the engine
# ---------------------------------------------------------------------------


def init_page_pool(cfg: OlmoHybridConfig, num_pages: int, page_size: int,
                   dtype=None) -> dict:
    """The FULL layers' leaves alone: ``"kv" [P, page, 2 * KV * hd]``, a
    position's keys then its values. Page 0 is the null page."""
    dtype = dtype or L.compute_dtype()
    return {str(i): {"kv": jnp.zeros(
        (num_pages, page_size, 2 * cfg.kv_width), dtype)}
        for i in cfg.full_layers}


def init_slot_state(cfg: OlmoHybridConfig, rows: int) -> dict:
    """``rows`` rows of state, a linear layer each: the float32 delta-rule
    state and the convolution tail. The engine asks for ``max_slots`` rows
    (the slots' state) and for its snapshot pool's (the same leaves)."""
    return {str(i): {
        "s": jnp.zeros((rows, cfg.gdn_heads, cfg.gdn_dk, cfg.gdn_dv),
                       jnp.float32),
        "conv": jnp.zeros((rows, cfg.conv - 1, cfg.conv_width),
                          L.compute_dtype()),
    } for i in cfg.gdn_layers}


def default_sizes(cfg: OlmoHybridConfig, max_slots: int, page_size: int,
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


def report(cfg: OlmoHybridConfig, page_size: int, totals: dict,
           engine) -> dict:
    """The gauges of one engine (``paged_model.build_engine``'s
    ``report``): this module's counters, the pool, the slots' state. The
    snapshot pool's are the engine's own (``ServingMetrics``)."""
    return {
        **{name: int(totals[name]) for name in GDN_COUNTERS},
        "kv_bytes_per_token": cfg.kv_bytes_per_token,
        "kv_pool_bytes": (engine.allocator.num_pages * page_size
                          * cfg.kv_bytes_per_token),
        "kv_pages_free": engine.allocator.free_pages,
        "gdn_state_bytes": cfg.state_bytes_per_slot * engine.max_slots,
    }


def flops_per_token(cfg: OlmoHybridConfig) -> float:
    """Weight-matmul FLOPs of one token (no score or state term)."""
    gdn = (cfg.dim * (cfg.conv_width + cfg.gdn_value_width + 2 * cfg.gdn_heads)
           + cfg.gdn_value_width * cfg.dim)
    full = cfg.dim * (cfg.q_width + 2 * cfg.kv_width) + cfg.q_width * cfg.dim
    return 2.0 * (
        len(cfg.gdn_layers) * gdn + len(cfg.full_layers) * full
        + cfg.layers * 3 * cfg.dim * cfg.ffn + cfg.dim * cfg.vocab)


def make_paged_engine(params, cfg: OlmoHybridConfig, *, max_slots: int = 16,
                      eos: int | None = None, page_size: int = 16,
                      chunk: int | None = None,
                      num_pages: int | None = None,
                      window: int | None = None,
                      prefix_cache: bool | None = None,
                      prefix_cache_pages: int | None = None,
                      attn_block: int | None = None,
                      state_snapshots: int | None = None):
    """The paged continuous-batching engine
    (models/batch_engine.PagedBatchEngine) with the linear layers' states
    and tails as its slot state and pages for the full layers alone
    (``paged_model.build_engine``; the pools, the counters and the slot
    state are arguments 2, 3 and 9 of the window and 2, 3 and 6 of the
    chunk, hence the donation). **The prefix cache works**: the engine
    keeps ``state_snapshots`` rows shaped like one slot's state, copies a
    prompt's state there after its last full chunk and grants a later
    prompt the pages up to a snapshot's depth with the snapshot.
    ``num_pages`` and ``state_snapshots`` default to :func:`default_sizes`.
    Speculation, LoRA and int8 pages are not offered (KNOWN_ISSUES.md,
    PR 56)."""
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
        "olmo_hybrid", cfg, params,
        window_program=lambda p, k, *args: window_program(
            p, cfg, k, eos, *args),
        chunk_step=step, donate_window=(2, 3, 9), donate_chunk=(2, 3, 6),
        init_page_pool=lambda n: init_page_pool(cfg, n, page_size),
        init_slot_state=lambda rows: init_slot_state(cfg, rows),
        counters=init_counters(cfg), report=partial(report, cfg, page_size),
        not_offered=NOT_OFFERED, flops_per_token=flops_per_token(cfg),
        max_slots=max_slots, eos=eos, page_size=page_size, chunk=chunk,
        num_pages=num_pages, window=window, prefix_cache=prefix_cache,
        prefix_cache_pages=prefix_cache_pages,
        state_snapshots=state_snapshots if prefix_cache else 0)
