"""Falcon-H1 causal LM (``model_type`` ``falcon_h1``) on the paged
serving path: a Mamba-2 mixer IN PARALLEL with grouped-query attention
in every layer, then a SwiGLU MLP.

The layer, as the published ``config.json`` gives it (the float32
reference of the same mathematics, token by token, is
``falcon_h1_reference.py``):

    u = RMSNorm(x; input_layernorm)
    x = x + ssm_out_multiplier * Mixer(u)
          + attention_out_multiplier * Attn(u * attention_in_multiplier)
    x = x + MLP(RMSNorm(x; pre_ff_layernorm))

``Attn``: ``q = a W_q`` (heads x head_dim wide, not the hidden size),
``k = (a W_k) * key_multiplier``, ``v = a W_v``, rotate-half rotary over
the whole head, causal softmax, ``W_o``. ``Mixer`` (Mamba-2): ``p = ((u
* ssm_in_multiplier) W_in) * mup`` with ``W_in`` -> ``[z | xBC | dt]``
and ``mup`` the five ``ssm_multipliers`` over ``[z | x | B | C | dt]``;
``xBC = silu(causal depthwise conv1d(xBC))``; ``dt = softplus(dt +
dt_bias)``; per head ``S <- exp(dt A) S + dt x B^T``, ``y = S C + D x``;
``y = RMSNorm_per_group(y * silu(z)) * w``; ``W_out``. ``MLP(v) =
((silu((v W_gate) * m0) * (v W_up)) W_down) * m1``.

What this module adds to the serving path:

* **a per-slot recurrent state** beside the K/V pages: per layer
  ``ssm [slots, H, P, N]`` float32 and the convolution's tail ``conv
  [slots, d_conv - 1, conv_dim]`` in the compute dtype. It is the
  engine's second cache kind (``PagedBatchEngine(init_slot_state=...)``):
  never a leaf of the pools, donated to and returned by both programs.
  A chunk at position 0 starts from zeros; rows past ``valid`` and
  decode rows whose ``active`` bit is off leave it as it was.
* **the mixer's two forms**: the chunked (SSD) scan for the prefill
  chunk, ``mamba_chunk_size`` rows at a time, state in from the slot
  and out at row ``valid``; and the one-token recurrence for the decode
  tick (``ops/ssm_state_step``: the state stays in HBM, rows that are
  not active move none of it).
* **folded multipliers**: every multiplier that scales a matrix's
  output channels is folded into that matrix's float32 int8 scales at
  load (``attention_in`` x ``key`` into ``wqkv``, ``attention_out``
  into ``wo``, ``ssm_in`` x ``mup`` into ``w_in``, ``ssm_out`` into
  ``w_out``, ``mlp_multipliers`` into gate and down, ``lm_head`` into
  the head): exact per output channel but for one float32 rounding of
  the scale. ``embedding_multiplier`` is applied to the gathered rows
  in float32.

Decode tick: MLP through ``ops.decode_block.mlp_step``, head through
``lm_head_argmax``, ``W_in`` / ``W_out`` and the attention projections
through ``ops/int8_matmul``, the state update through
``ops/ssm_state_step``. The attention branch itself is plain
``jax.numpy`` over a page pool of its own layout: the fused
attention kernels keep ``wqkv`` and ``wo`` whole in VMEM (31 MB of int8
here, 94 MB with their bf16 copies) and were not re-tiled in this PR
(KNOWN_ISSUES.md, PR 33). Text only.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp

from dora_tpu.models import layers as L
from dora_tpu.models import paged_model as PM
from dora_tpu.models.hf.loader import TensorFiles, read_config
from dora_tpu.models.paged_window import make_paged_window
from dora_tpu.ops import decode_block as DB
from dora_tpu.ops.int8_matmul import quantize_int8_t
from dora_tpu.ops.ssm_state_step import ssm_state_step

MODEL_TYPES = ("falcon_h1",)

#: rows of one attention block (a multiple of the page): the pool is read
#: this many positions at a time, up to the longest live context.
ATTN_BLOCK = 256

#: serving knobs of the Qwen path that this model refuses (KNOWN_ISSUES.md)
NOT_OFFERED = {
    "DORA_KV_INT8": "the int8 page kernels are fused into the Qwen "
                    "attention kernels, which this model does not run",
    "DORA_SPEC_K": "a rejected draft would need the recurrent state "
                   "rolled back; no snapshot of it is kept",
    "DORA_LORA_DIR": "the grouped LoRA matmul is fused into the Qwen kernels",
}

#: the mixer's counters on the device
COUNTERS = ("row_ticks", "decode_ticks", "chunk_rows", "zero_starts")

_HIGHEST = jax.lax.Precision.HIGHEST
_log = logging.getLogger(__name__)


@dataclass(frozen=True)
class FalconH1Config:
    vocab: int
    dim: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    d_ssm: int
    ssm_heads: int
    ssm_head_dim: int
    n_groups: int
    d_state: int
    d_conv: int
    scan_chunk: int
    norm_eps: float
    rope_theta: float
    max_seq: int
    embed_mult: float
    lm_head_mult: float
    attn_in_mult: float
    attn_out_mult: float
    key_mult: float
    ssm_in_mult: float
    ssm_out_mult: float
    ssm_mults: tuple  # over [z | x | B | C | dt]
    mlp_mults: tuple  # (gate, down)

    @property
    def conv_dim(self) -> int:
        return self.d_ssm + 2 * self.n_groups * self.d_state

    @property
    def in_width(self) -> int:
        """``W_in``'s published output width: ``[z | xBC | dt]``."""
        return self.d_ssm + self.conv_dim + self.ssm_heads

    @property
    def in_stored(self) -> int:
        """As stored: zero columns up to a lane multiple (9248 -> 9344)."""
        return -(-self.in_width // 128) * 128

    @property
    def q_width(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def state_bytes_per_slot(self) -> int:
        """Recurrent state one slot holds over all layers (float32 SSM
        state plus the convolution's tail in the compute dtype)."""
        ssm = self.ssm_heads * self.ssm_head_dim * self.d_state * 4
        conv = (self.d_conv - 1) * self.conv_dim * jnp.dtype(
            L.compute_dtype()).itemsize
        return self.layers * (ssm + conv)

    @classmethod
    def from_hf(cls, config: dict, max_seq: int | None = None) -> "FalconH1Config":
        if config.get("model_type") not in MODEL_TYPES:
            raise ValueError(
                f"model_type {config.get('model_type')!r} is not one of "
                f"{MODEL_TYPES}"
            )
        for key in ("attention_bias", "mlp_bias", "mamba_proj_bias",
                    "projectors_bias"):
            if config.get(key):
                raise NotImplementedError(f"falcon_h1: {key} is not written")
        if config.get("rope_scaling"):
            raise NotImplementedError(
                f"falcon_h1: rope_scaling {config['rope_scaling']!r}")
        if config.get("mamba_norm_before_gate") or not config.get(
                "mamba_rms_norm", True):
            raise NotImplementedError(
                "falcon_h1: only the gated RMSNorm after the gate "
                "(mamba_rms_norm, mamba_norm_before_gate false)")
        heads = config["mamba_n_heads"]
        d_ssm = config.get("mamba_d_ssm") or (
            config["mamba_expand"] * config["hidden_size"])
        if d_ssm != heads * config["mamba_d_head"]:
            raise ValueError(
                f"falcon_h1: mamba_d_ssm {d_ssm} != {heads} heads x "
                f"{config['mamba_d_head']}")
        return cls(
            vocab=config["vocab_size"],
            dim=config["hidden_size"],
            layers=config["num_hidden_layers"],
            heads=config["num_attention_heads"],
            kv_heads=config["num_key_value_heads"],
            head_dim=config.get("head_dim")
            or config["hidden_size"] // config["num_attention_heads"],
            ffn=config["intermediate_size"],
            d_ssm=d_ssm,
            ssm_heads=heads,
            ssm_head_dim=config["mamba_d_head"],
            n_groups=config["mamba_n_groups"],
            d_state=config["mamba_d_state"],
            d_conv=config["mamba_d_conv"],
            scan_chunk=config.get("mamba_chunk_size", 128),
            norm_eps=config.get("rms_norm_eps", 1e-5),
            rope_theta=float(config.get("rope_theta", 1e11)),
            max_seq=max_seq
            or min(config.get("max_position_embeddings", 2048), 2048),
            embed_mult=float(config.get("embedding_multiplier", 1.0)),
            lm_head_mult=float(config.get("lm_head_multiplier", 1.0)),
            attn_in_mult=float(config.get("attention_in_multiplier", 1.0)),
            attn_out_mult=float(config.get("attention_out_multiplier", 1.0)),
            key_mult=float(config.get("key_multiplier", 1.0)),
            ssm_in_mult=float(config.get("ssm_in_multiplier", 1.0)),
            ssm_out_mult=float(config.get("ssm_out_multiplier", 1.0)),
            ssm_mults=tuple(
                float(m) for m in config.get("ssm_multipliers") or (1.0,) * 5),
            mlp_mults=tuple(
                float(m) for m in config.get("mlp_multipliers") or (1.0, 1.0)),
        )


# ---------------------------------------------------------------------------
# loading: one layer at a time, int8 on the device, multipliers folded
# ---------------------------------------------------------------------------


def _quantize_t(mult, *weights):
    """HF ``[out, in]`` weights -> one int8 ``[in, sum(out)]`` matrix with
    per-output-channel scales (``int8_matmul.quantize_int8_t``), times ``mult``
    (a scalar or a vector over the output channels: the folded
    multipliers)."""
    q = quantize_int8_t(*weights)
    return {"int8": q["int8"], "scale": q["scale"] * mult}


def mup_vector(cfg: FalconH1Config):
    """``ssm_in_multiplier`` x the five ``ssm_multipliers`` spread over
    ``W_in``'s stored columns ``[z | x | B | C | dt | zeros]``."""
    gn = cfg.n_groups * cfg.d_state
    widths = (cfg.d_ssm, cfg.d_ssm, gn, gn, cfg.ssm_heads)
    parts = [jnp.full((w,), m, jnp.float32)
             for w, m in zip(widths, cfg.ssm_mults)]
    parts.append(jnp.ones((cfg.in_stored - cfg.in_width,), jnp.float32))
    return jnp.concatenate(parts) * cfg.ssm_in_mult


def load_layer(get, cfg: FalconH1Config, i: int, prefix: str = "model.") -> dict:
    """Layer ``i``'s serving parameters from ``get(name) -> device array``
    under the HF tensor names."""
    lp = f"{prefix}layers.{i}."
    a, m, f = lp + "self_attn.", lp + "mamba.", lp + "feed_forward."
    f32 = jnp.float32
    qkv_mult = cfg.attn_in_mult * jnp.concatenate([
        jnp.ones((cfg.q_width,), f32),
        jnp.full((cfg.kv_width,), cfg.key_mult, f32),
        jnp.ones((cfg.kv_width,), f32),
    ])
    w_in = get(m + "in_proj.weight")
    w_in = jnp.pad(w_in, ((0, cfg.in_stored - w_in.shape[0]), (0, 0)))
    gate_mult = jnp.concatenate([
        jnp.full((cfg.ffn,), cfg.mlp_mults[0], f32), jnp.ones((cfg.ffn,), f32)])
    return {
        "attn_norm": get(lp + "input_layernorm.weight"),
        "ffn_norm": get(lp + "pre_ff_layernorm.weight"),
        "wqkv": _quantize_t(
            qkv_mult, get(a + "q_proj.weight"), get(a + "k_proj.weight"),
            get(a + "v_proj.weight")),
        "wo": _quantize_t(cfg.attn_out_mult, get(a + "o_proj.weight")),
        "w_in": _quantize_t(mup_vector(cfg), w_in),
        # HF conv1d.weight is [conv_dim, 1, d_conv]: taps last
        "conv_w": get(m + "conv1d.weight")[:, 0, :].T,  # [d_conv, conv_dim]
        "conv_b": get(m + "conv1d.bias"),
        "dt_bias": get(m + "dt_bias").astype(f32),
        "a": -jnp.exp(get(m + "A_log").astype(f32)),
        "d": get(m + "D").astype(f32),
        "ssm_norm": get(m + "norm.weight"),
        "w_out": _quantize_t(cfg.ssm_out_mult, get(m + "out_proj.weight")),
        "w_gateup": _quantize_t(
            gate_mult, get(f + "gate_proj.weight"), get(f + "up_proj.weight")),
        "w_down": _quantize_t(cfg.mlp_mults[1], get(f + "down_proj.weight")),
    }


def load(model_dir: str | Path, max_seq: int | None = None):
    """(config, serving params) from a HF checkpoint directory. Tensors
    go from the file to the device one at a time and are quantized there
    (``ops/int8_matmul.quantize_int8``, per output channel), so at most
    one matrix exists in a float format at any moment; the embedding,
    the norms and the convolution stay in the compute dtype, ``A``,
    ``D`` and ``dt_bias`` in float32."""
    cfg = FalconH1Config.from_hf(read_config(model_dir), max_seq)
    files = TensorFiles(model_dir)
    prefix = "model." if "model.embed_tokens.weight" in files else ""
    dtype = L.compute_dtype()

    def get(name: str):
        return jnp.asarray(files.get(name)).astype(dtype)

    params = {
        "embed": get(f"{prefix}embed_tokens.weight"),
        "out_norm": get(f"{prefix}final_layernorm.weight"),
        "lm_head": _quantize_t(cfg.lm_head_mult, get("lm_head.weight")),
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
# the attention branch: GQA over the page pool, plain jax.numpy
# ---------------------------------------------------------------------------


def _qkv(blk, cfg: FalconH1Config, u, cos, sin):
    """Normed rows ``u [N, dim]`` at rotary rows ``cos/sin [N, hd/2]`` ->
    roped q ``[N, KV, G, hd]``, roped k and v ``[N, KV, hd]`` (the key's
    and the input's multipliers ride ``wqkv``'s scales)."""
    n = u.shape[0]
    kv, hd = cfg.kv_heads, cfg.head_dim
    p = L.matmul(u, blk["wqkv"])
    q = p[:, : cfg.q_width].reshape(n, cfg.heads, hd)
    k = p[:, cfg.q_width : cfg.q_width + cfg.kv_width].reshape(n, kv, hd)
    v = p[:, cfg.q_width + cfg.kv_width :].reshape(n, kv, hd)
    q = L.rotate_half(q, cos[:, None], sin[:, None])
    k = L.rotate_half(k, cos[:, None], sin[:, None])
    return q.reshape(n, kv, cfg.heads // kv, hd), k, v


def attn_decode(blk, cfg: FalconH1Config, u, pool, positions, block_tables,
                cos, sin, block: int):
    """Decode: ``u [B, dim]`` (normed), one new position a row. Writes
    each row's K/V into its page (a frozen row's, at position 0 of a
    zeroed table row, into the null page), then attends over positions
    ``0..positions[b]``. Returns (the branch's output [B, dim] float32,
    attention_out_multiplier included; pool)."""
    with jax.named_scope("attn_branch"):
        page = pool.shape[1]
        b = u.shape[0]
        q, k, v = _qkv(blk, cfg, u, cos, sin)
        pool = pool.at[
            block_tables[jnp.arange(b), positions // page], positions % page
        ].set(L.kv_rows(cfg, k, v).astype(pool.dtype))
        per = block // page

        def kv_of(j):
            ids = jax.lax.dynamic_slice_in_dim(block_tables, j * per, per, 1)
            rows = pool[ids].reshape(b, block, 2, cfg.kv_heads, cfg.head_dim)
            return rows[:, :, 0], rows[:, :, 1]

        def visible(j):
            t = j * block + jnp.arange(block)
            return (t[None, :] <= positions[:, None])[:, None, None, :]

        ctx = L.attend_kv_blocks(
            cfg, q, kv_of, visible, positions.max() // block + 1,
            "bkgd,btkd->bkgt", "bkgt,btkd->bkgd")
        out = L.matmul(ctx.astype(u.dtype).reshape(b, cfg.q_width), blk["wo"])
        return out.astype(jnp.float32), pool


def attn_chunk(blk, cfg: FalconH1Config, u, pool, position, block_table,
               cos, sin, block: int):
    """Prefill chunk: ``u [C, dim]`` (normed) at positions
    ``position..position+C-1`` (page-aligned), one block table. Writes
    the chunk's K/V as whole pages, then every row attends causally over
    ``0..its own position``."""
    with jax.named_scope("attn_branch"):
        page = pool.shape[1]
        c = u.shape[0]
        q, k, v = _qkv(blk, cfg, u, cos, sin)
        ids = jax.lax.dynamic_slice_in_dim(block_table, position // page,
                                           c // page)
        pool = pool.at[ids].set(
            L.kv_rows(cfg, k, v).astype(pool.dtype).reshape(
                c // page, page, 2 * cfg.kv_width))
        per = block // page
        q_pos = position + jnp.arange(c)

        def kv_of(j):
            ids = jax.lax.dynamic_slice_in_dim(block_table, j * per, per)
            rows = pool[ids].reshape(block, 2, cfg.kv_heads, cfg.head_dim)
            return rows[:, 0], rows[:, 1]

        def visible(j):
            t = j * block + jnp.arange(block)
            return (t[None, :] <= q_pos[:, None])[:, None, None, :]

        ctx = L.attend_kv_blocks(
            cfg, q, kv_of, visible, (position + c - 1) // block + 1,
            "qkgd,tkd->qkgt", "qkgt,tkd->qkgd")
        out = L.matmul(ctx.astype(u.dtype).reshape(c, cfg.q_width), blk["wo"])
        return out.astype(jnp.float32), pool


# ---------------------------------------------------------------------------
# the mixer: one-token recurrence (decode) and chunked scan (prefill)
# ---------------------------------------------------------------------------


def _split_in(cfg: FalconH1Config, p):
    """``W_in``'s output rows -> (z, xBC, dt)."""
    z = p[:, : cfg.d_ssm]
    xbc = p[:, cfg.d_ssm : cfg.d_ssm + cfg.conv_dim]
    dt = p[:, cfg.d_ssm + cfg.conv_dim : cfg.in_width]
    return z, xbc, dt


def _split_conv(cfg: FalconH1Config, xbc):
    """The convolved rows ``[N, conv_dim]`` float32 -> x ``[N, H, P]``,
    B and C ``[N, G, state]``."""
    n = xbc.shape[0]
    gn = cfg.n_groups * cfg.d_state
    x = xbc[:, : cfg.d_ssm].reshape(n, cfg.ssm_heads, cfg.ssm_head_dim)
    bm = xbc[:, cfg.d_ssm : cfg.d_ssm + gn].reshape(n, cfg.n_groups, cfg.d_state)
    cm = xbc[:, cfg.d_ssm + gn :].reshape(n, cfg.n_groups, cfg.d_state)
    return x, bm, cm


def gate_out(blk, cfg: FalconH1Config, y, z):
    """``y [N, d_ssm]`` float32 gated by ``z``, normed per group, through
    ``W_out`` (``ssm_out_multiplier`` rides its scales). float32 out."""
    with jax.named_scope("ssm_gate_out"):
        n = y.shape[0]
        g = y * jax.nn.silu(z.astype(jnp.float32))
        g = g.reshape(n, cfg.n_groups, cfg.d_ssm // cfg.n_groups)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + cfg.norm_eps)
        g = g.reshape(n, cfg.d_ssm) * blk["ssm_norm"].astype(jnp.float32)
        return L.matmul(g.astype(L.compute_dtype()), blk["w_out"]).astype(
            jnp.float32)


def mixer_step(blk, cfg: FalconH1Config, u, state, active):
    """Decode: one token a row, ``u [B, dim]`` normed; ``state`` is the
    layer's ``{"ssm": [B, H, P, N], "conv": [B, d_conv-1, conv_dim]}``
    (row = slot). Rows with ``active`` off leave both as they were.
    Returns (the mixer's output [B, dim] float32, state)."""
    f32 = jnp.float32
    with jax.named_scope("ssm_in_proj"):
        z, xbc, dt = _split_in(cfg, L.matmul(u, blk["w_in"]))
    with jax.named_scope("ssm_conv"):
        tail = state["conv"]
        taps = jnp.concatenate([tail, xbc[:, None].astype(tail.dtype)], 1)
        conv = jnp.sum(taps.astype(f32) * blk["conv_w"].astype(f32)[None], 1)
        conv = jax.nn.silu(conv + blk["conv_b"].astype(f32))
        tail = jnp.where(active[:, None, None], taps[:, 1:], tail)
    x, bm, cm = _split_conv(cfg, conv)
    dt = jax.nn.softplus(dt.astype(f32) + blk["dt_bias"])
    with jax.named_scope("ssm_state_step"):
        y, ssm = ssm_state_step(state["ssm"], x, dt, blk["a"], bm, cm,
                                blk["d"], active)
    out = gate_out(blk, cfg, y.reshape(u.shape[0], cfg.d_ssm), z)
    return out, {"ssm": ssm, "conv": tail}


def ssd_scan(cfg: FalconH1Config, x, dt, a, bm, cm, d, s0):
    """The chunked (SSD) form of the recurrence over ``C`` rows,
    ``scan_chunk`` at a time: x ``[C, H, P]``, dt ``[C, H]`` (0 for a row
    that must leave the state alone), a, d ``[H]``, bm, cm ``[C, G, N]``,
    s0 ``[H, P, N]``, all float32. Returns (y ``[C, H, P]``, the state
    after the last row). Inside a block: the decays' running sums, one
    [Q, Q] matrix of C.B products a group, masked to the causal half;
    between blocks the state is carried."""
    c, h, p = x.shape
    g, n = bm.shape[1:]
    per = h // g
    q = min(cfg.scan_chunk, c)
    assert c % q == 0, (c, q)

    def block(s, inp):
        xb, dtb, bb, cb = inp  # [Q, H, P], [Q, H], [Q, G, N], [Q, G, N]
        la = jnp.cumsum(dtb * a[None, :], axis=0)  # [Q, H], <= 0
        xdt = xb * dtb[..., None]
        # within the block: y_t += sum_{s<=t} exp(la_t - la_s) (C_t.B_s) dt_s x_s
        cb_bs = jnp.einsum("tgn,sgn->gts", cb, bb, precision=_HIGHEST)
        seen = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
        decay = jnp.exp(jnp.where(
            seen[..., None], la[:, None, :] - la[None, :, :], -jnp.inf))
        w = decay.reshape(q, q, g, per) * jnp.moveaxis(cb_bs, 0, -1)[..., None]
        y = jnp.einsum("tsgk,sgkp->tgkp", w, xdt.reshape(q, g, per, p),
                       precision=_HIGHEST).reshape(q, h, p)
        # from the state carried in: y_t += exp(la_t) S C_t
        sg = s.reshape(g, per, p, n)
        y = y + jnp.exp(la)[..., None] * jnp.einsum(
            "gkpn,tgn->tgkp", sg, cb, precision=_HIGHEST).reshape(q, h, p)
        # the state carried out
        to_end = jnp.exp(la[-1][None, :] - la)  # [Q, H]
        s_new = s * jnp.exp(la[-1])[:, None, None] + jnp.einsum(
            "sgkp,sgn->gkpn", (xdt * to_end[..., None]).reshape(q, g, per, p),
            bb, precision=_HIGHEST).reshape(h, p, n)
        return s_new, y + xb * d[None, :, None]

    def blocks(t):
        return t.reshape(c // q, q, *t.shape[1:])

    s, y = jax.lax.scan(block, s0, (blocks(x), blocks(dt), blocks(bm),
                                    blocks(cm)))
    return y.reshape(c, h, p), s


def mixer_chunk(blk, cfg: FalconH1Config, u, state, slot, position, valid):
    """Prefill chunk of one stream: ``u [C, dim]`` normed; ``state`` the
    layer's slot arrays, of which row ``slot`` is this stream's. The
    state comes in from the slot (zeros when ``position`` is 0: no reset
    call from the host) and goes back as it stands after row ``valid``
    (rows past it are padding: their ``dt`` is 0, so they neither decay
    nor add). Returns (output [C, dim] float32, state)."""
    f32 = jnp.float32
    c = u.shape[0]
    fresh = position == 0
    with jax.named_scope("ssm_in_proj"):
        z, xbc, dt = _split_in(cfg, L.matmul(u, blk["w_in"]))
    with jax.named_scope("ssm_conv"):
        tail = jnp.where(fresh, 0, state["conv"][slot])  # [d_conv-1, conv_dim]
        rows = jnp.concatenate([tail, xbc.astype(tail.dtype)], 0)
        w = blk["conv_w"].astype(f32)
        conv = sum(
            jax.lax.dynamic_slice_in_dim(rows, k, c).astype(f32) * w[k]
            for k in range(cfg.d_conv))
        conv = jax.nn.silu(conv + blk["conv_b"].astype(f32))
        # the last d_conv-1 rows that are the prompt's: rows valid-3..valid-1
        tail = jax.lax.dynamic_slice_in_dim(rows, valid, cfg.d_conv - 1)
    x, bm, cm = _split_conv(cfg, conv)
    dt = jax.nn.softplus(dt.astype(f32) + blk["dt_bias"])
    dt = jnp.where((jnp.arange(c) < valid)[:, None], dt, 0.0)
    with jax.named_scope("ssm_scan"):
        s0 = jnp.where(fresh, 0.0, state["ssm"][slot])
        y, s = ssd_scan(cfg, x, dt, blk["a"], bm, cm, blk["d"], s0)
    out = gate_out(blk, cfg, y.reshape(c, cfg.d_ssm), z)
    return out, {
        "ssm": jax.lax.dynamic_update_index_in_dim(state["ssm"], s, slot, 0),
        "conv": jax.lax.dynamic_update_index_in_dim(
            state["conv"], tail, slot, 0),
    }


# ---------------------------------------------------------------------------
# the stack, the two programs
# ---------------------------------------------------------------------------


def mlp_decode(blk, cfg: FalconH1Config, x):
    """``x + MLP(RMSNorm(x))`` for the decode rows: one fused sweep over
    the ffn tiles (``ops.decode_block.mlp_step``)."""
    gu, dn = blk["w_gateup"], blk["w_down"]
    return DB.mlp_step(
        x, blk["ffn_norm"], gu["int8"], gu["scale"],
        jnp.zeros((2 * cfg.ffn,), jnp.float32), dn["int8"], dn["scale"],
        eps=cfg.norm_eps)


def mlp_chunk(blk, cfg: FalconH1Config, x):
    """The same sublayer for a chunk's rows: two ``int8_matmul``s."""
    h = L.rms_norm(x, blk["ffn_norm"], cfg.norm_eps)
    gate, up = jnp.split(L.matmul(h, blk["w_gateup"]), 2, axis=-1)
    return x + L.matmul(jax.nn.silu(gate) * up, blk["w_down"])


def embed_rows(params, cfg: FalconH1Config, ids):
    rows = params["embed"][ids].astype(jnp.float32) * cfg.embed_mult
    return rows.astype(L.compute_dtype())


def _layers(params, cfg: FalconH1Config, x, pools, state, attend, mix, mlp):
    """``attend(blk, u, kv pool) -> (f32 out, kv pool)``, ``mix(blk, u, layer
    state) -> (f32 out, layer state)``. Returns (rows, pools, state)."""
    pools, state = dict(pools), dict(state)
    for i in range(cfg.layers):
        blk = params["blocks"][str(i)]
        u = L.rms_norm(x, blk["attn_norm"], cfg.norm_eps)
        a, kv = attend(blk, u, pools[str(i)]["kv"])
        pools[str(i)] = {"kv": kv}
        m, state[str(i)] = mix(blk, u, state[str(i)])
        x = (x.astype(jnp.float32) + a + m).astype(x.dtype)
        x = mlp(blk, cfg, x)
    return x, pools, state


def paged_batch_rows(params, cfg: FalconH1Config, tokens, pools, state, stats,
                     positions, block_tables, active, block: int = ATTN_BLOCK):
    """One decode step for B = slots independent sequences: tokens,
    positions, active ``[B]``, block_tables ``[B, max_pages]`` (a frozen
    row comes with position 0 and a zeroed table row, so its K/V write
    lands in the null page; its recurrent state has no null page and is
    kept by its ``active`` bit). Returns (the final rows [B, dim],
    pools, state, stats)."""
    cos_t, sin_t = L.rope_table(cfg.max_seq, cfg.head_dim, base=cfg.rope_theta)
    cos, sin = cos_t[positions], sin_t[positions]
    x = embed_rows(params, cfg, tokens)

    def attend(blk, u, pool):
        return attn_decode(blk, cfg, u, pool, positions, block_tables, cos,
                           sin, block)

    def mix(blk, u, st):
        return mixer_step(blk, cfg, u, st, active)

    x, pools, state = _layers(params, cfg, x, pools, state, attend, mix,
                              mlp_decode)
    live = active.sum(dtype=jnp.int32)
    stats = PM.add_counts(
        stats, row_ticks=live, decode_ticks=(live > 0).astype(jnp.int32))
    return x, pools, state, stats


def paged_chunk_rows(params, cfg: FalconH1Config, chunk_ids, pools, state,
                     stats, position, block_table, valid, slot,
                     block: int = ATTN_BLOCK):
    """One prefill chunk of the stream in ``slot``: ``chunk_ids [C]`` at
    positions ``position..position+C-1`` (page-aligned), of which the
    first ``valid`` are the prompt's. ``position``, ``valid`` and
    ``slot`` are traced: one program for every chunk."""
    c = chunk_ids.shape[0]
    cos_t, sin_t = L.rope_table(cfg.max_seq, cfg.head_dim, base=cfg.rope_theta)
    cos = jax.lax.dynamic_slice_in_dim(cos_t, position, c)
    sin = jax.lax.dynamic_slice_in_dim(sin_t, position, c)
    x = embed_rows(params, cfg, chunk_ids)

    def attend(blk, u, pool):
        return attn_chunk(blk, cfg, u, pool, position, block_table, cos, sin,
                          block)

    def mix(blk, u, st):
        return mixer_chunk(blk, cfg, u, st, slot, position, valid)

    x, pools, state = _layers(params, cfg, x, pools, state, attend, mix,
                              mlp_chunk)
    stats = PM.add_counts(
        stats, chunk_rows=valid.astype(jnp.int32),
        zero_starts=(position == 0).astype(jnp.int32))
    return x, pools, state, stats


paged_batch_logits, fused_paged_batch_step = PM.under_the_head(paged_batch_rows)
paged_chunk_logits, fused_paged_chunk_step = PM.under_the_head(paged_chunk_rows)


def window_program(params, cfg, k: int, eos, block: int, tokens, pools,
                   stats, positions, bts, active, emitted, max_new, state):
    """The K-tick decode window (models/paged_window.make_paged_window with a
    slot state) over :func:`fused_paged_batch_step`: the counters ride
    the window's carry beside the state and come back apart. Returns
    (the window's own results — pools, then state, last — and stats)."""
    def batch(tokens, pools, positions, bts, active, carried):
        nxt, pools, state, stats = fused_paged_batch_step(
            params, cfg, tokens, pools, *carried, positions, bts, active,
            block=block)
        return nxt, pools, (state, stats)

    *out, (state, stats) = make_paged_window(
        batch, k=k, eos=eos, slot_state=True)(
        tokens, pools, positions, bts, active, emitted, max_new,
        (state, stats))
    return (*out, state), stats


# ---------------------------------------------------------------------------
# the pool, the slot state and the engine
# ---------------------------------------------------------------------------


def init_page_pool(cfg: FalconH1Config, num_pages: int, page_size: int,
                   dtype=None) -> dict:
    """K/V page pools ``{layer: {"kv": [P, page, 2 * KV * hd]}}``: a
    cached position is one row, its roped keys then its values (1,024
    values = 2,048 B at bf16 for Falcon-H1-34B). Row-major with a lane
    multiple as the minor dimension, so XLA:TPU scatters into it in
    place (the ``[P, KV, page, hd]`` layout of the Qwen kernels made it
    copy the whole pool through every tick). Page 0 is the null page."""
    dtype = dtype or L.compute_dtype()
    shape = (num_pages, page_size, 2 * cfg.kv_width)
    return {str(i): {"kv": jnp.zeros(shape, dtype)} for i in range(cfg.layers)}


def init_slot_state(cfg: FalconH1Config, max_slots: int) -> dict:
    """The recurrent state of every slot, ``{layer: {"ssm": [slots, H, P,
    N] float32, "conv": [slots, d_conv-1, conv_dim] compute dtype}}``."""
    return {
        str(i): {
            "ssm": jnp.zeros((max_slots, cfg.ssm_heads, cfg.ssm_head_dim,
                              cfg.d_state), jnp.float32),
            "conv": jnp.zeros((max_slots, cfg.d_conv - 1, cfg.conv_dim),
                              L.compute_dtype()),
        }
        for i in range(cfg.layers)
    }


def init_counters() -> dict:
    """The mixer's counters on the device: an operand and a result of
    their own of both programs (a buffer each: donated one by one),
    int32 that wraps; ``paged_model.DeviceCounters`` adds up the
    differences."""
    return {name: jnp.zeros((), jnp.int32) for name in COUNTERS}


def report(cfg: FalconH1Config, totals: dict, engine) -> dict:
    """The gauges of one engine (``paged_model.build_engine``'s
    ``report``): the mixer's counters' sums and the slots' state."""
    return {
        **{f"ssm_{k}": int(totals[k]) for k in COUNTERS},
        "ssm_state_bytes": cfg.state_bytes_per_slot * engine.max_slots,
        "ssm_slots_live": engine.active,
    }


def flops_per_token(cfg: FalconH1Config) -> float:
    """Weight-matmul FLOPs of one token (no score and no scan term)."""
    attn = cfg.dim * (cfg.q_width + 2 * cfg.kv_width) + cfg.q_width * cfg.dim
    mixer = cfg.dim * cfg.in_width + cfg.d_ssm * cfg.dim
    mlp = 3 * cfg.dim * cfg.ffn
    return 2.0 * (cfg.layers * (attn + mixer + mlp) + cfg.dim * cfg.vocab)


def make_paged_engine(params, cfg: FalconH1Config, *, max_slots: int = 16,
                      eos: int | None = None, page_size: int = 16,
                      chunk: int | None = None,
                      num_pages: int | None = None,
                      window: int | None = None,
                      prefix_cache: bool | None = None,
                      prefix_cache_pages: int | None = None,
                      attn_block: int | None = None):
    """The paged continuous-batching engine
    (models/batch_engine.PagedBatchEngine) with a per-slot recurrent
    state beside the K/V pool: the same scheduler, allocator and K-tick
    window as the other families (``paged_model.build_engine``; the
    pools, the counters and the slot state are arguments 2, 3 and 9 of
    the window and 2, 3 and 6 of the chunk, hence the donation).
    ``num_pages`` defaults to every slot reaching ``max_seq``. **No
    prefix cache, whatever is asked**: a granted prefix would need the
    recurrent state at its end, and no snapshot is kept at a page
    boundary. Speculation, LoRA and int8 pages are not offered
    (KNOWN_ISSUES.md)."""
    if prefix_cache or prefix_cache_pages:
        _log.warning(
            "falcon_h1: the prefix cache is off for this model: a granted "
            "prefix needs the recurrent state at its end, and none is kept")
    chunk = PM.default_chunk(chunk, cfg.max_seq)
    assert chunk % min(cfg.scan_chunk, chunk) == 0, (chunk, cfg.scan_chunk)
    attn_block = PM.default_attn_block(attn_block, ATTN_BLOCK, chunk,
                                       cfg.max_seq, page_size)
    if num_pages is None:
        num_pages = max_slots * cfg.max_seq // page_size + 1

    def step(p, ids, pools, stats, position, bt, state, valid, slot):
        return fused_paged_chunk_step(p, cfg, ids, pools, state, stats,
                                      position, bt, valid, slot,
                                      block=attn_block)

    return PM.build_engine(
        "falcon_h1", cfg, params,
        window_program=lambda p, k, *args: window_program(
            p, cfg, k, eos, attn_block, *args),
        chunk_step=step, donate_window=(2, 3, 9), donate_chunk=(2, 3, 6),
        init_page_pool=lambda n: init_page_pool(cfg, n, page_size),
        init_slot_state=lambda slots: init_slot_state(cfg, slots),
        counters=init_counters(), report=partial(report, cfg),
        not_offered=NOT_OFFERED, flops_per_token=flops_per_token(cfg),
        max_slots=max_slots, eos=eos, page_size=page_size, chunk=chunk,
        num_pages=num_pages, window=window, prefix_cache=False,
        prefix_cache_pages=0)
