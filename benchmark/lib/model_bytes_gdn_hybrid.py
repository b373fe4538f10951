"""Operations and bytes the programs of an ``olmo_hybrid`` configuration
(gated-delta-rule layers of per-slot float32 state, full-attention layers
of ungrouped K/V pages, a dense SwiGLU in every layer) need, computed from
the benchmark's configuration file: the benchmark's side of
``decode_window_hbm_pct.gdn-hybrid``, ``prefill_chunk_mxu_pct.gdn-hybrid``
and ``gdn_state_step_hbm_pct``. Plain numbers in, plain numbers out.

Weights count 1 byte a parameter (int8). The state term counts a live
row's delta-rule state read and written once a linear layer (2 x
2,211,840 B); the cache term counts rows ATTENDED (the program's
``global_kv_rows_read``: over ticks, live rows and full layers, position +
1 rows, 15,360 B each), whatever fetched them, so a later kernel is read
by the same yardstick. The per-channel scales, the norms, the embedding
rows, the convolution and its tails and the rows written are left out, so
the bytes are a lower bound and a share computed from them cannot be
flattered.
"""

from __future__ import annotations

from model_bytes_swa_moe import capture_edges, per  # noqa: F401  (the readers' helpers)

#: rows of one block of the delta rule's blocked form (olmo_hybrid.GDN_BLOCK)
GDN_BLOCK = 64


def linear_layers(cfg: dict) -> int:
    return sum(kind == "linear_attention" for kind in cfg["layer_types"])


def full_layers(cfg: dict) -> int:
    return sum(kind == "full_attention" for kind in cfg["layer_types"])


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def linear_params(cfg: dict) -> int:
    """One delta-rule mixer: q and k (2 x 3840 x 2880), v, the output gate
    and the output projection (3 x 3840 x 5760), the decay's and beta's
    logits (2 x 3840 x 30) and the convolution (4 x 11,520): 88,750,080 at
    Olmo-Hybrid-7B's widths."""
    d, h = cfg["hidden_size"], cfg["linear_num_value_heads"]
    kw = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    vw = h * cfg["linear_value_head_dim"]
    return (2 * d * kw + 3 * d * vw + 2 * d * h
            + cfg["linear_conv_kernel_dim"] * (2 * kw + vw))


def full_params(cfg: dict) -> int:
    """q, k, v and o of one full layer (58,982,400)."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return d * (q + 2 * kv) + q * d


def mlp_params(cfg: dict) -> int:
    """gate, up and down (126,812,160)."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def always_read_params(cfg: dict) -> int:
    """int8 parameters every decode tick reads: all mixers, every layer's
    MLP and the head (3,715,276,800 at the cell's cut: 4 periods of
    832,481,280 + the head's 385,351,680; the embedding is bf16 rows, of
    which a tick reads sixteen)."""
    return (linear_layers(cfg) * linear_params(cfg)
            + full_layers(cfg) * full_params(cfg)
            + cfg["num_hidden_layers"] * mlp_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def state_bytes_per_row(cfg: dict) -> int:
    """One linear layer's float32 state of one stream (2,211,840 B)."""
    return (cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
            * cfg["linear_value_head_dim"] * 4)


def state_step_bytes(cfg: dict) -> int:
    """What the step kernel moves for one live row: its state read and
    written (4,423,680 B)."""
    return 2 * state_bytes_per_row(cfg)


def kv_row_bytes(cfg: dict, bytes_per_value: float = 2.0) -> float:
    """A cached position of ONE full layer: 30 key heads then 30 value
    heads of 128 (15,360 B)."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * bytes_per_value


def kv_bytes_per_token(cfg: dict, bytes_per_value: float = 2.0) -> float:
    """What a cached position holds in the paged pool (61,440 B)."""
    return full_layers(cfg) * kv_row_bytes(cfg, bytes_per_value)


def snapshot_bytes(cfg: dict, bytes_per_value: float = 2.0) -> float:
    """One state snapshot = one slot's state: the float32 states and the
    convolution tails of every linear layer (27,371,520 B)."""
    kw = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    vw = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    tail = (cfg["linear_conv_kernel_dim"] - 1) * (2 * kw + vw) * bytes_per_value
    return linear_layers(cfg) * (state_bytes_per_row(cfg) + tail)


def decode_tick_bytes(cfg: dict, gdn_row_ticks: float, kv_rows_read: float) -> float:
    """Bytes one decode tick must move: the always-read int8,
    ``gdn_row_ticks`` (live rows x linear layers) states read and written,
    and the K/V rows its live rows attended (already summed over the full
    layers)."""
    return (always_read_params(cfg)
            + gdn_row_ticks * state_step_bytes(cfg)
            + kv_rows_read * kv_row_bytes(cfg))


def delta_rule_flops(cfg: dict, chunk: int, block: int = GDN_BLOCK) -> float:
    """The blocked delta rule's matrix products of one layer over ``chunk``
    rows, per block of ``Q`` rows and head: ``K K^T`` and ``Q K^T`` (Q x
    d_k x Q each), the unit-triangular solve against the identity (Q^3 / 3
    multiply-adds: forward substitution), ``K S`` and ``Q S`` (Q x d_k x
    d_v each), ``T rhs`` and ``B U`` (Q x Q x d_v each) and ``K^T U`` (d_k
    x Q x d_v). The decays are vector work and not counted."""
    h = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    q = min(block, chunk)
    per_block = 2 * q * q * dk + q * q * q / 3.0 + 3 * q * dk * dv + 2 * q * q * dv
    return 2.0 * h * (chunk // q) * per_block


def chunk_flops(cfg: dict, chunk: int, context: float) -> float:
    """FLOPs of one prefill chunk of ``chunk`` rows whose rows see
    ``context`` rows on average (position + 1: the program's
    ``gdn_chunk_positions`` / ``gdn_chunk_rows``): the matrices a token
    touches, the delta rule's block products a linear layer, and a full
    layer's causal score and mix products over the rows a row sees (what
    is needed, not the whole blocks the program multiplies under its
    mask)."""
    attend = 4.0 * cfg["num_attention_heads"] * head_dim(cfg) * context
    return (chunk * 2.0 * always_read_params(cfg)
            + linear_layers(cfg) * delta_rule_flops(cfg, chunk)
            + full_layers(cfg) * chunk * attend)
