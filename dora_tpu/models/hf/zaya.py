"""ZAYA1 causal LM (``model_type`` ``zaya``) on the paged serving path:
every layer is ``hybrid``, an attention sublayer inside a convolved,
compressed latent (CCA, "Compressed Convolutional Attention") under an
expert sublayer whose router is an MLP that carries its state from
layer to layer. Every expert is held (``ep_size`` 1 unless the
checkpoint says otherwise): the layer's output is the model's.

The layer, on the residual row ``x_t`` of position ``t``; ``s_t`` is the
router state the previous layer left, zeros before layer 0 (``†`` = not
settled by the published ``config.json``, an assumption written down in
``KNOWN_ISSUES.md`` "PR 52"; the float32 reference of the same
mathematics, whole sequence, is ``zaya_reference.py``, where each † is a
switch):

    h = RMSNorm(x_t)
    c_t = [Wq h ; Wk h]                                    1,280 = (8 + 2) heads x 128
    a_t = w0[:, 0] c_{t-1} + w0[:, 1] c_t + b0             depthwise, cca_time0 = 2 taps
    d_t[g] = W1[g, 0] a_{t-1}[g] + W1[g, 1] a_t[g] + b1[g] a head at a time, cca_time1 = 2 taps
                                                           † c_{-1} = c_{-2} = 0: one padding, of the input
    m_t[i] = (q~_t[i] + k~_t[i // 4]) / 2                  the q-k mean
    q'[i] = d_t[i] + m_t[i];  k'[j] = d_t[8 + j] + mean_i m_t[i]
    q'' = sqrt(hd) q' / |q'|;  k'' = exp(tau_j) sqrt(hd) k' / |k'|     † the exp
    rotate-half rotary on the first hd / 2 dimensions of q'' and k''
    v_t = [Wv1 h_t ; Wv2 h_{t-1}]                          † head 0 from t, head 1 from t - 1
    y = Wo softmax(q'' k''^T / sqrt(hd)) v                 causal, 8 query heads over 2 K/V heads
    x = (x + rb) rs + (y + hb) hs                          † four learned vectors a sublayer

    h = RMSNorm(x_t)                                       † the router reads the normed row
    s_t = Wd h + bd + gamma s_t                            † the carry is of this pre-norm state
    p = softmax(W3 gelu(W2 gelu(W1 RMSNorm(s_t) + b1) + b2))           † two hidden layers, GELU
    e = argmax(p + bias);  y = p[e] E_e(h)                 top-1, unnormalised; † no skip output
    x = (x + rb) rs + (y + hb) hs

What this module adds to the serving path: **a cache whose rows are
built from three positions.** Attention reads the convolved, normed,
rotated key and the two-part value, so that is what a page holds: one
leaf a layer, ``"kv" [P, page, 2 * KV * hd]``, a position's two key heads
then its two value heads as one row (K-EXAONE's joined layout, 1,024 B a
token a layer at bf16). What a position's row is built FROM reaches two
positions back: the convolutions read ``c_{t-2}, c_{t-1}, c_t`` and the
value ``Wv2 h_{t-1}``. Those are the layer's **tail**, a slot's state
beside its pages (``PagedBatchEngine(init_slot_state=...)``): ``"c"
[slots, 2, 1280]`` (oldest first) and ``"v" [slots, hd]``. A decode tick
steps it, a chunk starts from it (from zeros at position 0: no reset
call from the host) and leaves it as it stands after its last VALID row,
a frozen row leaves it alone. It is a function of the last two tokens
only, so it costs 5 KB a slot a layer whatever the context.

The router state ``s`` is a carry along DEPTH inside one step, never
along time: both programs carry ``[N, router_hidden]`` float32 beside the
residual stream, and it is no cache.

The router is this file's (as Keye-VL-2.0's softmax router is
``keye_vl2.py``'s); the routed sum, its counters and the experts' stack
are ``models/moe.py``'s, called with ``top_k`` 1 and every expert held.
Every matrix goes through ``ops/int8_matmul`` (``Wq | Wk | Wv1 | Wv2``
as one matrix), the head, tied to the embedding, through
``lm_head_argmax``; the convolutions, both L2 norms, the router from
``Wd`` on and the softmaxes run in float32.
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

MODEL_TYPES = ("zaya",)

#: rows of one attention block of a CHUNK (a multiple of the page): the
#: pool is read this many positions at a time, up to the chunk's last one
ATTN_BLOCK = 256

#: serving knobs of the Qwen path that this model refuses (KNOWN_ISSUES.md)
NOT_OFFERED = {
    "DORA_KV_INT8": "the int8 page kernels are fused into the Qwen "
                    "attention kernels, which this model does not run",
    "DORA_SPEC_K": "a rejected draft would have stepped the convolution "
                   "and value-shift tails past the accepted prefix; no "
                   "snapshot is kept",
    "DORA_LORA_DIR": "the grouped LoRA matmul is fused into the Qwen kernels",
}
PREFIX_CACHE_WHY = (
    "a granted prefix needs the convolution and value-shift tails at its "
    "end, and none is kept at a page edge")

#: the CCA sublayer's counters on the device
CCA_COUNTERS = (
    "cca_decode_ticks", "cca_row_ticks", "cca_kv_rows_read",
    "cca_kv_rows_swept", "cca_tail_steps", "cca_zero_starts",
    "cca_chunks", "cca_chunk_rows", "cca_chunk_positions",
)

_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32
_log = logging.getLogger(__name__)


@dataclass(frozen=True)
class ZayaConfig:
    vocab: int
    dim: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    rotary_dim: int  # the leading dimensions of a head that rotate
    moe_ffn: int
    n_experts: int  # the router's width: every expert of the model
    router_hidden: int
    norm_eps: float
    rope_theta: float
    max_seq: int
    tied: bool
    #: this rank's share: experts ``expert_first .. +experts_held``
    expert_first: int
    experts_held: int
    # what ``moe.ExpertLayerConfig`` reads beside the share: one expert a
    # token, its unnormalised probability, no shared expert
    top_k: int = 1
    norm_topk: bool = False
    routed_scale: float = 1.0
    n_shared: int = 0

    @property
    def q_width(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def conv_width(self) -> int:
        """The pre-convolution row ``c_t``: query latent then key latent."""
        return self.q_width + self.kv_width

    @property
    def moe_layers(self) -> int:
        return self.layers

    @property
    def kv_bytes_per_token(self) -> int:
        """What a cached position holds in the paged pool: the two key
        heads and the two value heads of every layer."""
        return (self.layers * 2 * self.kv_width
                * jnp.dtype(L.compute_dtype()).itemsize)

    @property
    def tail_bytes_per_slot(self) -> int:
        """The tails of one slot: two ``c`` rows and ``Wv2 h`` a layer."""
        return (self.layers * (2 * self.conv_width + self.head_dim)
                * jnp.dtype(L.compute_dtype()).itemsize)

    @classmethod
    def from_hf(cls, config: dict, max_seq: int | None = None,
                ep_rank: int | None = None) -> "ZayaConfig":
        if config.get("model_type") not in MODEL_TYPES:
            raise ValueError(
                f"model_type {config.get('model_type')!r} is not one of "
                f"{MODEL_TYPES}"
            )
        n = config["num_hidden_layers"]
        kinds = config.get("layer_types") or ["hybrid"] * n
        if len(kinds) != n:
            raise ValueError(
                f"zaya: layer_types must name all {n} layers, got {kinds!r}")
        unknown = set(kinds) - {"hybrid"}
        if unknown:
            raise NotImplementedError(
                f"zaya: layer_types {sorted(unknown)} is not written (only "
                f"'hybrid': CCA over the expert layer, no window)")
        if config.get("sliding_window"):
            raise NotImplementedError(
                f"zaya: sliding_window {config['sliding_window']} is not "
                f"written")
        for key in ("cca_time0", "cca_time1"):
            if config.get(key, 2) != 2:
                raise NotImplementedError(
                    f"zaya: {key} {config[key]} is not written (only 2 taps: "
                    f"the tail holds two rows)")
        for key in ("attention_bias", "lm_head_bias"):
            if config.get(key):
                raise NotImplementedError(f"zaya: {key} is not written")
        if config.get("num_experts_per_tok", 1) != 1:
            raise NotImplementedError(
                f"zaya: num_experts_per_tok {config['num_experts_per_tok']} "
                f"is not written (only top-1, the unnormalised probability)")
        if config.get("hidden_act", "silu") != "silu":
            raise NotImplementedError(
                f"zaya: hidden_act {config['hidden_act']!r} is not written")
        if not config.get("router_hidden_size"):
            raise NotImplementedError(
                "zaya: a router without router_hidden_size is not written")
        rope = (config.get("rope_parameters") or {}).get("hybrid") or {}
        if rope.get("rope_type", "default") != "default" or config.get(
                "rope_scaling"):
            raise NotImplementedError(
                f"zaya: scaled rotary {config.get('rope_scaling') or rope!r} "
                f"is not written")
        heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
        if heads % kv or heads // kv < 2:
            raise NotImplementedError(
                f"zaya: {heads} query heads over {kv} K/V heads is not "
                f"written (the sweep wants at least 2 a K/V head)")
        if kv != 2:
            raise NotImplementedError(
                f"zaya: {kv} K/V heads is not written (the value shift "
                f"gives head 0 this position and head 1 the previous one)")
        hd = config.get("head_dim") or config["hidden_size"] // heads
        factor = rope.get("partial_rotary_factor",
                          config.get("partial_rotary_factor", 1.0))
        # the loader maps ``num_experts``: ``moe.expert_share`` reads HF's
        # DeepSeek names
        first, held = moe.expert_share(
            {"n_routed_experts": config["num_experts"],
             "ep_size": config.get("ep_size")}, ep_rank)
        return cls(
            vocab=config["vocab_size"],
            dim=config["hidden_size"],
            layers=n,
            heads=heads,
            kv_heads=kv,
            head_dim=hd,
            rotary_dim=int(hd * factor),
            moe_ffn=config["moe_intermediate_size"],
            n_experts=config["num_experts"],
            router_hidden=config["router_hidden_size"],
            norm_eps=config.get("rms_norm_eps", 1e-5),
            rope_theta=float(
                rope.get("rope_theta", config.get("rope_theta", 5e6))),
            max_seq=max_seq
            or min(config.get("max_position_embeddings", 2048), 2048),
            tied=bool(config.get("tie_word_embeddings", True)),
            expert_first=first,
            experts_held=held,
        )


# ---------------------------------------------------------------------------
# loading: one layer at a time, int8 on the device
# ---------------------------------------------------------------------------

#: the four vectors of a sublayer's residual scaling, as the checkpoint
#: names them under ``<sublayer>_residual.``
RESIDUAL_VECTORS = {"rb": "residual_bias", "rs": "residual_scale",
                    "hb": "hidden_bias", "hs": "hidden_scale"}


def load_layer(get, cfg: ZayaConfig, i: int, prefix: str = "model.") -> dict:
    """Layer ``i``'s serving parameters from ``get(name) -> device array``
    (compute dtype) under the tensor names this file assumes (†
    ``assumed.tensor_names``): torch's ``Conv1d`` layouts for the two
    convolutions (``[out, in / groups, taps]``, tap 0 the oldest)."""
    lp = f"{prefix}layers.{i}."
    a, m = lp + "self_attn.", lp + "mlp."
    r = m + "router."
    g, hd = cfg.heads + cfg.kv_heads, cfg.head_dim

    def f32(name):
        return get(name).astype(_F32)

    def residual(at):
        return {k: get(f"{lp}{at}_residual.{name}")
                for k, name in RESIDUAL_VECTORS.items()}

    # the grouped convolution's 327,680 weights stay in the compute dtype
    # (cast up where they are used), the small vectors float32
    conv1 = get(a + "conv_qk.1.weight").reshape(g, hd, hd, 2)  # g, out, in, tap
    return {
        "attn_norm": get(lp + "input_layernorm.weight"),
        # q | k | v1 | v2: the last ``hd`` columns of position t are kept
        # as the tail that position t + 1 reads
        "wqkv": _quantize_t(get(a + "q_proj.weight"), get(a + "k_proj.weight"),
                            get(a + "v_proj1.weight"), get(a + "v_proj2.weight")),
        "conv0_w": f32(a + "conv_qk.0.weight")[:, 0, :].T,  # [tap, 1280]
        "conv0_b": f32(a + "conv_qk.0.bias"),
        "conv1_w": conv1.transpose(3, 0, 2, 1),  # [tap, g, in, out]
        "conv1_b": f32(a + "conv_qk.1.bias"),
        "tau": f32(a + "temp"),
        "wo": _quantize_t(get(a + "o_proj.weight")),
        "attn_res": residual("attn"),
        "ffn_norm": get(lp + "post_attention_layernorm.weight"),
        "router": {
            "down": get(r + "down_proj.weight").T,  # [dim, R]
            "down_b": f32(r + "down_proj.bias"),
            "gamma": f32(r + "state_scale"),
            "norm": f32(r + "norm.weight"),
            "w1": get(r + "mlp.0.weight").T, "b1": f32(r + "mlp.0.bias"),
            "w2": get(r + "mlp.1.weight").T, "b2": f32(r + "mlp.1.bias"),
            "w3": get(r + "mlp.2.weight").T,  # [R, experts]
            "bias": f32(r + "balancing_bias"),
        },
        "experts": moe.stack_experts(get, cfg, m),
        "ffn_res": residual("mlp"),
    }


def load(model_dir: str | Path, max_seq: int | None = None,
         ep_rank: int | None = None):
    """(config, serving params) from a HF checkpoint directory, as
    ``kimi_k2.load``: tensors go from the file to the device one at a
    time and are quantized there; the embedding, the convolutions, the
    routers, the vectors and the norms are not quantized. The head is the
    embedding's int8 copy where the checkpoint ties them."""
    cfg = ZayaConfig.from_hf(read_config(model_dir), max_seq, ep_rank)
    files = TensorFiles(model_dir)
    prefix = "model." if "model.embed_tokens.weight" in files else ""
    dtype = L.compute_dtype()

    def get(name: str):
        return jnp.asarray(files.get(name)).astype(dtype)

    embed = get(f"{prefix}embed_tokens.weight")
    params = {
        "embed": embed,
        "out_norm": get(f"{prefix}norm.weight"),
        "lm_head": _quantize_t(embed if cfg.tied else get("lm_head.weight")),
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
# the CCA sublayer: tails beside pages
# ---------------------------------------------------------------------------


def rope_rows(cfg: ZayaConfig, positions):
    """``(cos, sin) [N, rotary_dim / 2]`` at ``positions``."""
    cos, sin = L.rope_table(cfg.max_seq, cfg.rotary_dim, base=cfg.rope_theta)
    return cos[positions], sin[positions]


def project(blk, cfg: ZayaConfig, u):
    """Normed rows ``u [N, dim]`` -> (``c [N, 1280]`` the query and key
    latents before the convolutions, ``v1 [N, hd]`` this position's half
    of the value, ``v2 [N, hd]`` the half the NEXT position reads)."""
    with jax.named_scope("cca_proj"):
        p = L.matmul(u, blk["wqkv"])
        at = cfg.conv_width
        return (p[:, :at], p[:, at : at + cfg.head_dim],
                p[:, at + cfg.head_dim :])


def convolve(blk, cfg: ZayaConfig, rows):
    """The two causal convolutions over ``rows [..., n + 2, 1280]``
    float32 (two earlier positions, then ``n``): ``d [..., n, 1280]``,
    the depthwise taps then the grouped ones, a head at a time."""
    with jax.named_scope("cca_conv"):
        w0, w1 = blk["conv0_w"], blk["conv1_w"].astype(_F32)
        a = rows[..., :-1, :] * w0[0] + rows[..., 1:, :] * w0[1] + blk["conv0_b"]
        a = a.reshape(*a.shape[:-1], cfg.heads + cfg.kv_heads, cfg.head_dim)
        d = (jnp.einsum("...ngi,gio->...ngo", a[..., :-1, :, :], w1[0],
                        precision=_HIGHEST)
             + jnp.einsum("...ngi,gio->...ngo", a[..., 1:, :, :], w1[1],
                          precision=_HIGHEST))
        return d.reshape(*d.shape[:-2], cfg.conv_width) + blk["conv1_b"]


def _l2(x, gain):
    """``gain * sqrt(hd) * x / |x|`` over the head, float32."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)) * gain


def mean_norm_rotate(blk, cfg: ZayaConfig, c, d, rope, dtype):
    """Latents ``c`` and their convolution ``d`` (``[N, 1280]`` float32)
    -> the queries ``[N, KV, G, hd]`` and the keys ``[N, KV, hd]`` that
    attention reads: the q-k mean added, L2-normed over the head (the
    keys times ``exp(tau)``), the leading ``rotary_dim`` dimensions
    rotated."""
    with jax.named_scope("cca_qk_mean_norm"):
        n, kv, hd = c.shape[0], cfg.kv_heads, cfg.head_dim
        g = cfg.heads // kv

        def heads(t):
            return (t[:, : cfg.q_width].reshape(n, kv, g, hd),
                    t[:, cfg.q_width :].reshape(n, kv, hd))

        (qt, kt), (dq, dk) = heads(c), heads(d)
        m = (qt + kt[:, :, None, :]) * 0.5  # [N, KV, G, hd]
        q = _l2(dq + m, 1.0)
        k = _l2(dk + m.mean(2), jnp.exp(blk["tau"])[None, :, None])
        cos, sin = rope
        r = cfg.rotary_dim

        def turn(t, cos, sin):
            return jnp.concatenate(
                [L.rotate_half(t[..., :r], cos, sin), t[..., r:]], -1)

        q = turn(q, cos[:, None, None], sin[:, None, None])
        k = turn(k, cos[:, None], sin[:, None])
        return q.astype(dtype), k.astype(dtype)


def _split_rows(cfg: ZayaConfig, rows):
    """Cached rows ``[..., 2 * KV * hd]`` -> keys, values ``[..., KV, hd]``."""
    rows = rows.reshape(*rows.shape[:-1], 2, cfg.kv_heads, cfg.head_dim)
    return rows[..., 0, :, :], rows[..., 1, :, :]


def _out(blk, cfg: ZayaConfig, ctx, dtype):
    with jax.named_scope("cca_out"):
        return L.matmul(ctx.astype(dtype).reshape(-1, cfg.q_width), blk["wo"])


def cca_decode(blk, cfg: ZayaConfig, u, pool, tail, positions, block_tables,
               counts, active, rope):
    """The CCA sublayer's decode tick: ``u [B, dim]`` (normed), row =
    slot; ``tail`` the layer's ``{"c": [B, 2, 1280], "v": [B, hd]}``. A
    row convolves ``[its tail ++ its own c]``, takes the tail's ``Wv2 h``
    as its value's second head, writes its K|V row into its page (a
    frozen row's, at position 0 of a zeroed table row, into the null
    page) and attends its first ``counts[b]`` positions through the block
    table (``attention_paged_rows_step``: its own pages, none for a
    frozen row, which gets zeros). An active row's tail moves on one
    position; a frozen row's stays. Returns (output [B, dim], pool,
    tail)."""
    b, page = u.shape[0], pool.shape[1]
    c, v1, v2 = project(blk, cfg, u)
    rows = jnp.concatenate([tail["c"], c[:, None].astype(tail["c"].dtype)], 1)
    d = convolve(blk, cfg, rows.astype(_F32))[:, 0]
    q, k = mean_norm_rotate(blk, cfg, c.astype(_F32), d, rope, u.dtype)
    # [B, KV, hd]: head 0 from this position, head 1 from the previous one
    v = jnp.stack([v1, tail["v"].astype(v1.dtype)], 1)
    tail = {
        "c": jnp.where(active[:, None, None], rows[:, 1:], tail["c"]),
        "v": jnp.where(active[:, None], v2.astype(tail["v"].dtype), tail["v"]),
    }
    with jax.named_scope("cca_attend"):
        pool = pool.at[
            block_tables[jnp.arange(b), positions // page], positions % page
        ].set(L.kv_rows(cfg, k, v).astype(pool.dtype))
        ctx = DB.attention_paged_rows_step(q, pool, counts, block_tables)
    return _out(blk, cfg, ctx, u.dtype), pool, tail


def cca_chunk(blk, cfg: ZayaConfig, u, pool, tail, slot, position, valid,
              block_table, rope, block: int):
    """The CCA sublayer's prefill chunk: ``u [C, dim]`` (normed) at
    positions ``position..position+C-1`` of the stream in ``slot``, of
    which the first ``valid`` are the prompt's. The convolutions run over
    ``[the slot's tail ++ the chunk]`` (zeros for the tail at position 0),
    the value's second head is the row before's ``Wv2 h``; the chunk's K|V
    rows go into whole pages and every row attends causally over
    ``0..its own position``; the tail as it stands after row ``valid - 1``
    goes back to the slot. Returns (output [C, dim], pool, tail)."""
    n, page = u.shape[0], pool.shape[1]
    fresh = position == 0
    c, v1, v2 = project(blk, cfg, u)
    tail_c = jnp.where(fresh, 0, tail["c"][slot])  # [2, 1280]
    tail_v = jnp.where(fresh, 0, tail["v"][slot])  # [hd]
    rows = jnp.concatenate([tail_c, c.astype(tail_c.dtype)], 0)
    d = convolve(blk, cfg, rows.astype(_F32))
    q, k = mean_norm_rotate(blk, cfg, c.astype(_F32), d, rope, u.dtype)
    before = jnp.concatenate([tail_v[None], v2.astype(tail_v.dtype)], 0)
    v = jnp.stack([v1, before[:-1].astype(v1.dtype)], 1)
    tail = {
        # chunk row j is rows[j + 2]: the last two valid rows, and the
        # last valid row's Wv2 h
        "c": jax.lax.dynamic_update_index_in_dim(
            tail["c"], jax.lax.dynamic_slice_in_dim(rows, valid, 2), slot, 0),
        "v": jax.lax.dynamic_update_index_in_dim(
            tail["v"], jax.lax.dynamic_index_in_dim(before, valid, 0, False),
            slot, 0),
    }
    with jax.named_scope("cca_attend"):
        ids = jax.lax.dynamic_slice_in_dim(block_table, position // page,
                                           n // page)
        pool = pool.at[ids].set(
            L.kv_rows(cfg, k, v).astype(pool.dtype).reshape(
                n // page, page, 2 * cfg.kv_width))
        per = block // page
        q_pos = position + jnp.arange(n)

        def kv_of(j):
            ids = jax.lax.dynamic_slice_in_dim(block_table, j * per, per)
            return _split_rows(cfg, pool[ids].reshape(block, -1))

        def visible(j):
            t = j * block + jnp.arange(block)
            return (t[None, :] <= q_pos[:, None])[:, None, None, :]

        ctx = L.attend_kv_blocks(
            cfg, q, kv_of, visible, (position + n - 1) // block + 1,
            "qkgd,tkd->qkgt", "qkgt,tkd->qkgd")
    return _out(blk, cfg, ctx, u.dtype), pool, tail


# ---------------------------------------------------------------------------
# the expert sublayer: the ZAYA router over models/moe.py's routed sum
# ---------------------------------------------------------------------------


def route(blk, cfg: ZayaConfig, x, s):
    """The ZAYA router on normed rows ``x [N, dim]`` with the state ``s
    [N, R]`` float32 the previous layer's router left: the row projected
    down to ``R``, the carried state added under ``gamma``, then an MLP
    of two GELU layers over its RMSNorm to a probability for every expert
    of the model, all float32. The choice is the largest of ``p + bias``
    (ties to the lower expert), the weight the chosen expert's ``p``
    itself. Returns (ids [N, 1] — global expert numbers —, weights [N,
    1], the state [N, R] for the next layer)."""
    with jax.named_scope("zaya_router"):
        r = blk["router"]

        def dot(a, w):
            return jnp.dot(a, w.astype(_F32), precision=_HIGHEST)

        s = dot(x.astype(_F32), r["down"]) + r["down_b"] + r["gamma"] * s
        u = s * jax.lax.rsqrt(
            jnp.mean(s * s, -1, keepdims=True) + cfg.norm_eps) * r["norm"]
        z = jax.nn.gelu(dot(u, r["w1"]) + r["b1"], approximate=False)
        z = jax.nn.gelu(dot(z, r["w2"]) + r["b2"], approximate=False)
        p = jax.nn.softmax(dot(z, r["w3"]), -1)
        ids = jnp.argmax(p + r["bias"], -1)[:, None].astype(jnp.int32)
        return ids, jnp.take_along_axis(p, ids, -1), s


def mlp(blk, cfg: ZayaConfig, x, s, live, counted):
    """The expert sublayer on normed rows ``x``: ``moe.mlp`` with
    :func:`route` in ``moe.route``'s place and neither a dense layer nor
    a shared expert to look for. Returns (output [N, dim],
    ``moe.add_layer``'s counters, the router state, the top-1 pick
    [N])."""
    ids, weights, s = route(blk, cfg, x, s)
    local = ids - cfg.expert_first
    y = moe.held_experts(blk, cfg, x, local, weights, live)
    landed = (local >= 0) & (local < cfg.experts_held) & counted[:, None]
    per_expert = (
        (local[..., None] == jnp.arange(cfg.experts_held)) & landed[..., None]
    ).sum((0, 1)).astype(jnp.int32)
    return y.astype(x.dtype), (
        counted.sum().astype(jnp.int32), landed.sum().astype(jnp.int32),
        per_expert,
    ), s, ids[:, 0]


def residual_scale(res, x, y):
    """``(x + rb) rs + (y + hb) hs``, float32, back to ``x``'s dtype."""
    with jax.named_scope("residual_scale"):
        rb, rs, hb, hs = (res[k].astype(_F32) for k in ("rb", "rs", "hb", "hs"))
        return ((x.astype(_F32) + rb) * rs
                + (y.astype(_F32) + hb) * hs).astype(x.dtype)


# ---------------------------------------------------------------------------
# the stack, the two programs
# ---------------------------------------------------------------------------


def init_counters(cfg: ZayaConfig) -> dict:
    """The counters on the device, an operand and a result of their own
    of both programs (a buffer each: donated one by one), int32 that
    wraps: ``moe`` are the expert layer's routing counters
    (``moe.init_counters``), ``cca`` this module's (:data:`CCA_COUNTERS`)."""
    return {
        "moe": moe.init_counters(cfg),
        "cca": {name: jnp.zeros((), jnp.int32) for name in CCA_COUNTERS},
    }


def _layers(params, cfg: ZayaConfig, x, pools, state, stats, attend, live,
            counted, decode: bool):
    """The stack: ``attend(blk, normed rows, layer pool, layer tail) ->
    (out, pool, tail)``, then :func:`mlp`, each under its residual
    scaling; the router state rides from layer to layer beside ``x``.
    Returns (rows, pools, state, the routing counters, every layer's
    top-1 pick [N])."""
    pools, state = dict(pools), dict(state)
    routed = dict(stats)
    per_layer, looks = [], []
    s = jnp.zeros((x.shape[0], cfg.router_hidden), _F32)
    for i in range(cfg.layers):
        blk, key = params["blocks"][str(i)], str(i)
        a, kv, state[key] = attend(
            blk, L.rms_norm(x, blk["attn_norm"], cfg.norm_eps),
            pools[key]["kv"], state[key])
        pools[key] = {"kv": kv}
        x = residual_scale(blk["attn_res"], x, a)
        y, counters, s, picked = mlp(
            blk, cfg, L.rms_norm(x, blk["ffn_norm"], cfg.norm_eps), s, live,
            counted)
        x = residual_scale(blk["ffn_res"], x, y)
        moe.add_layer(routed, per_layer, counters, decode)
        looks.append(picked)
    moe.add_stack(routed, per_layer, counted, decode)
    return x, pools, state, routed, looks


def _looks(looks):
    """Every layer's top-1 pick ``[N]`` -> ``{"expert" [layers, N] int32}``
    (what the router computed anyway: nothing is computed for the look)."""
    return {"expert": jnp.stack(looks)}


def paged_batch_rows(params, cfg: ZayaConfig, tokens, pools, state, stats,
                     positions, block_tables, active):
    """One decode step for B = slots independent sequences: tokens,
    positions, active ``[B]``, block_tables ``[B, max_pages]`` (a frozen
    row comes with position 0 and a zeroed table row, so its K|V write
    lands in the null page; its tails have no null row and are kept by its
    ``active`` bit; its routing is neither computed on nor counted).
    Returns (the final rows [B, dim], pools, state, stats, every layer's
    look: :func:`_looks`)."""
    rope = rope_rows(cfg, positions)
    x = params["embed"].astype(L.compute_dtype())[tokens]
    seen = jnp.where(active, positions + 1, 0)  # rows each row attends

    def attend(blk, u, pool, tail):
        return cca_decode(blk, cfg, u, pool, tail, positions, block_tables,
                          seen, active, rope)

    x, pools, state, routed, looks = _layers(
        params, cfg, x, pools, state, stats["moe"], attend, active, active,
        True)
    i32 = jnp.int32
    live = active.sum(dtype=i32)
    # the (row, group) steps one layer's sweep holds: a group is DB's page
    # group of cache rows, fetched whole for its last row
    group = DB.sweep_group_rows(
        next(iter(pools.values()))["kv"].shape[1], block_tables.shape[1])
    groups = ((seen + group - 1) // group).sum(dtype=i32)
    cca = PM.add_counts(
        stats["cca"],
        cca_decode_ticks=(live > 0).astype(i32),
        cca_row_ticks=cfg.layers * live,
        cca_kv_rows_read=cfg.layers * seen.sum(dtype=i32),
        cca_kv_rows_swept=cfg.layers * group * groups,
        cca_tail_steps=cfg.layers * live,
    )
    return x, pools, state, {"moe": routed, "cca": cca}, _looks(looks)


def paged_chunk_rows(params, cfg: ZayaConfig, chunk_ids, pools, state, stats,
                     position, block_table, valid, slot,
                     block: int = ATTN_BLOCK):
    """One prefill chunk of the stream in ``slot``: ``chunk_ids [C]`` at
    positions ``position..position+C-1`` (page-aligned), of which the
    first ``valid`` are the prompt's. ``position``, ``valid`` and
    ``slot`` are traced: one program for every chunk. Every row is
    computed; the routing counters count the ``valid`` ones. Every
    layer's look comes back last (:func:`_looks`)."""
    c = chunk_ids.shape[0]
    rope = rope_rows(cfg, position + jnp.arange(c))
    x = params["embed"].astype(L.compute_dtype())[chunk_ids]
    counted = jnp.arange(c) < valid

    def attend(blk, u, pool, tail):
        return cca_chunk(blk, cfg, u, pool, tail, slot, position, valid,
                         block_table, rope, block)

    x, pools, state, routed, looks = _layers(
        params, cfg, x, pools, state, stats["moe"], attend,
        jnp.ones((c,), bool), counted, False)
    i32 = jnp.int32
    cca = PM.add_counts(
        stats["cca"], cca_chunks=jnp.ones((), i32),
        cca_chunk_rows=valid.astype(i32),
        cca_chunk_positions=position.astype(i32),
        cca_zero_starts=(position == 0).astype(i32),
        cca_tail_steps=jnp.full((), cfg.layers, i32))
    return x, pools, state, {"moe": routed, "cca": cca}, _looks(looks)


paged_batch_logits, fused_paged_batch_step = PM.under_the_head(paged_batch_rows)
paged_chunk_logits, fused_paged_chunk_step = PM.under_the_head(paged_chunk_rows)


def window_program(params, cfg, k: int, eos, tokens, pools, stats,
                   positions, bts, active, emitted, max_new, state):
    """The K-tick decode window (models/paged_window.make_paged_window with a
    slot state) over :func:`fused_paged_batch_step`: the counters and every
    tick's look ride the window's carry beside the tails and come back
    apart. Returns (the window's own results — pools, then state, last —,
    stats, and the looks: ``"expert"`` ``[K, layers, B]``;
    tick ``j`` of a row that came in at position ``p`` is the row at ``p +
    j``)."""
    def batch(tokens, pools, positions, bts, active, carried):
        state, stats, tick, kept = carried
        nxt, pools, state, stats, look = fused_paged_batch_step(
            params, cfg, tokens, pools, state, stats, positions, bts, active)
        kept = jax.tree.map(
            lambda every, one: jax.lax.dynamic_update_index_in_dim(
                every, one, tick, 0), kept, look)
        return nxt, pools, (state, stats, tick + 1, kept)

    shape = (k, cfg.layers, tokens.shape[0])
    kept = {"expert": jnp.zeros(shape, jnp.int32)}
    *out, (state, stats, _, kept) = make_paged_window(
        batch, k=k, eos=eos, slot_state=True)(
        tokens, pools, positions, bts, active, emitted, max_new,
        (state, stats, jnp.zeros((), jnp.int32), kept))
    return (*out, state), stats, kept


# ---------------------------------------------------------------------------
# the pool, the tails and the engine
# ---------------------------------------------------------------------------


def init_page_pool(cfg: ZayaConfig, num_pages: int, page_size: int) -> dict:
    """One leaf a layer, ``{layer: {"kv": [P, page, 2 * KV * hd]}}``: a
    cached position is one row, its two key heads (convolved, normed,
    rotated) then its two value heads (row-major with a lane multiple as
    the minor dimension, so XLA:TPU scatters into it in place). Page 0 is
    the null page."""
    shape = (num_pages, page_size, 2 * cfg.kv_width)
    return {str(i): {"kv": jnp.zeros(shape, L.compute_dtype())}
            for i in range(cfg.layers)}


def init_slot_state(cfg: ZayaConfig, max_slots: int) -> dict:
    """The tails of every slot: ``{layer: {"c": [slots, 2, 1280] the two
    pre-convolution rows before the next position, oldest first, "v":
    [slots, hd] the previous position's Wv2 h}}``, in the dtype the
    projection gives them (what a tail holds IS a projection's row)."""
    dtype = L.compute_dtype()
    return {str(i): {"c": jnp.zeros((max_slots, 2, cfg.conv_width), dtype),
                     "v": jnp.zeros((max_slots, cfg.head_dim), dtype)}
            for i in range(cfg.layers)}


def page_pool_bytes(cfg: ZayaConfig, page_size: int) -> int:
    """Bytes one page takes over all layers."""
    return page_size * cfg.kv_bytes_per_token


def default_num_pages(cfg: ZayaConfig, max_slots: int, page_size: int) -> int:
    """The pool's default size, ``paged_model.default_num_pages``' rule in
    bytes (``pages_that_fit``). At the benchmark's cut on a 16 GB v5e the
    cap does not bind: 16 x 8,192 rows x 20,480 B = 2.68 GB, every slot
    may reach ``max_seq``."""
    return PM.default_num_pages(
        page_pool_bytes(cfg, page_size), max_slots, cfg.max_seq, page_size)


def report(cfg: ZayaConfig, page_size: int, totals: dict, engine) -> dict:
    """The gauges of one engine (``paged_model.build_engine``'s
    ``report``): the routing counters under the names every expert-layer
    model gives them (``moe.report``), this module's own, the pool and
    the tails."""
    return {
        **moe.report(totals["moe"], cfg.moe_layers),
        # raw, for a reader that takes it over a capture's ticks
        "moe_touched": int(totals["moe"]["touched"]),
        **{name: int(totals["cca"][name]) for name in CCA_COUNTERS},
        "kv_bytes_per_token": cfg.kv_bytes_per_token,
        "kv_pool_bytes": engine.allocator.num_pages * page_pool_bytes(
            cfg, page_size),
        "kv_pages_free": engine.allocator.free_pages,
        "cca_tail_bytes": cfg.tail_bytes_per_slot * engine.max_slots,
    }


def flops_per_token(cfg: ZayaConfig) -> float:
    """Weight-matmul FLOPs of one token (no score term): the projections,
    the grouped convolution, the router's MLP, the one routed expert a
    layer where it is held here, the head."""
    g, hd, r = cfg.heads + cfg.kv_heads, cfg.head_dim, cfg.router_hidden
    attn = (cfg.dim * (cfg.conv_width + 2 * hd) + cfg.q_width * cfg.dim
            + 2 * g * hd * hd)
    router = cfg.dim * r + 2 * r * r + r * cfg.n_experts
    expert = cfg.experts_held / cfg.n_experts * 3 * cfg.dim * cfg.moe_ffn
    return 2.0 * (cfg.layers * (attn + router + expert) + cfg.dim * cfg.vocab)


def make_paged_engine(params, cfg: ZayaConfig, *, max_slots: int = 16,
                      eos: int | None = None, page_size: int = 16,
                      chunk: int | None = None,
                      num_pages: int | None = None,
                      window: int | None = None,
                      prefix_cache: bool | None = None,
                      prefix_cache_pages: int | None = None,
                      attn_block: int | None = None):
    """The paged continuous-batching engine
    (models/batch_engine.PagedBatchEngine) with every layer's tail as its
    slot state beside one leaf of pages a layer: the same scheduler,
    allocator and K-tick window as the other families
    (``paged_model.build_engine``; the pools, the counters and the tails
    are arguments 2, 3 and 9 of the window and 2, 3 and 6 of the chunk,
    hence the donation). ``num_pages`` defaults to
    :func:`default_num_pages`. **No prefix cache, whatever is asked**
    (:data:`PREFIX_CACHE_WHY`). Speculation, LoRA and int8 pages are not
    offered (KNOWN_ISSUES.md, PR 52). ``engine.selection`` holds every
    layer's top-1 pick of the last chunk's rows and of the last window's
    ticks (16 x 20 words a tick, the router's own argmax: both programs
    give them always, so a cache audit's engine runs the server's own two
    programs and compiles nothing of its own)."""
    if prefix_cache or prefix_cache_pages:
        _log.warning("zaya: the prefix cache is off for this model: %s",
                     PREFIX_CACHE_WHY)
    chunk = PM.default_chunk(chunk, cfg.max_seq)
    attn_block = PM.default_attn_block(attn_block, ATTN_BLOCK, chunk,
                                       cfg.max_seq, page_size)
    if num_pages is None:
        num_pages = default_num_pages(cfg, max_slots, page_size)

    def step(p, ids, pools, stats, position, bt, state, valid, slot):
        return fused_paged_chunk_step(p, cfg, ids, pools, state, stats,
                                      position, bt, valid, slot,
                                      block=attn_block)

    selection = {"chunk": [], "window": []}
    engine = PM.build_engine(
        "zaya", cfg, params,
        window_program=lambda p, k, *args: window_program(
            p, cfg, k, eos, *args),
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
