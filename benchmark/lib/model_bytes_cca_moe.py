"""Operations and bytes the programs of a ``zaya`` configuration (CCA: a
convolved, compressed latent whose tails are slot state beside 1 KB
pages; a top-1 MLP router over experts that are ALL held) need, computed
from the benchmark's configuration file: the benchmark's side of
``decode_window_hbm_pct.cca-moe``, ``prefill_chunk_mxu_pct.cca-moe`` and
``cca_kv_swept_over_read``. Plain numbers in, plain numbers out.

Matrices count 1 byte a parameter (int8), the router and the
convolutions 2 (bf16 as the checkpoint holds them). The cache term counts
rows ATTENDED (the program's ``cca_kv_rows_read``: over ticks, live rows
and layers, position + 1 rows), whatever fetched them, so a later kernel
is read by the same yardstick; the tails count the live rows' own, read
and written. The per-channel scales, the norms, the scaling vectors, the
embedding rows and the rows written are left out, so the bytes are a
lower bound and a share computed from them cannot be flattered.
"""

from __future__ import annotations

from model_bytes_swa_moe import capture_edges, per  # noqa: F401  (the readers' helpers)


def attention_params(cfg: dict) -> int:
    """q, k, v1, v2 and o of one layer (5,242,880 at ZAYA1-8B's widths:
    2048 x (1024 + 256 + 128 + 128) + 1024 x 2048)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return d * (q + kv + 2 * hd) + q * d


def conv_params(cfg: dict) -> int:
    """The two convolutions of one layer with their biases (332,800: 1,280
    channels x 2 taps, 10 heads x 128 x 128 x 2 taps, two biases)."""
    hd = cfg["head_dim"]
    width = (cfg["num_attention_heads"] + cfg["num_key_value_heads"]) * hd
    return (width * cfg["cca_time0"] + width
            + width * hd * cfg["cca_time1"] + width)


def router_params(cfg: dict) -> int:
    """One layer's router MLP (660,224: 2048 x 256 + two 256 x 256 + 256 x
    16 and the three biases of 256)."""
    d, r = cfg["hidden_size"], cfg["router_hidden_size"]
    return d * r + 2 * r * r + r * cfg["num_experts"] + 3 * r


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down (12,582,912)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def always_read_params(cfg: dict) -> int:
    """int8 parameters every decode tick reads whatever the routing: all
    layers' attention matrices and the head (373,424,128 at the cell's
    cut: 20 x 5,242,880 + 2048 x 131,136)."""
    return (cfg["num_hidden_layers"] * attention_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def kv_row_bytes(cfg: dict, bytes_per_value: float = 2.0) -> float:
    """The two key heads and the two value heads of one position in ONE
    layer (1,024 B at 2 K/V heads of 128 in bf16)."""
    return 2.0 * cfg["num_key_value_heads"] * cfg["head_dim"] * bytes_per_value


def kv_bytes_per_token(cfg: dict, bytes_per_value: float = 2.0) -> float:
    """What a cached position holds in the paged pool (20,480 B at 20
    layers)."""
    return cfg["num_hidden_layers"] * kv_row_bytes(cfg, bytes_per_value)


def tail_bytes(cfg: dict, bytes_per_value: float = 2.0) -> float:
    """One slot's tail in ONE layer: two pre-convolution rows and ``Wv2 h``
    (5,376 B: (2 x 1,280 + 128) x 2)."""
    hd = cfg["head_dim"]
    width = (cfg["num_attention_heads"] + cfg["num_key_value_heads"]) * hd
    return (2 * width + hd) * bytes_per_value


def decode_tick_bytes(cfg: dict, experts_touched: float, kv_rows: float,
                      row_ticks: float, bytes_per_weight: float = 1.0,
                      plain_bytes_per_weight: float = 2.0) -> float:
    """Bytes one decode tick must move: the always-read int8 matrices, the
    bf16 router and convolutions of every layer, ``experts_touched``
    routed experts (distinct experts a tick had to read, summed over the
    layers), the K|V of the rows its live rows attended (``kv_rows``,
    summed over layers) and the tails its live rows stepped, read and
    written (``row_ticks`` = live rows x layers)."""
    layers = cfg["num_hidden_layers"]
    return (
        bytes_per_weight * always_read_params(cfg)
        + plain_bytes_per_weight * layers * (router_params(cfg) + conv_params(cfg))
        + bytes_per_weight * experts_touched * expert_params(cfg)
        + kv_rows * kv_row_bytes(cfg)
        + 2.0 * row_ticks * tail_bytes(cfg)
    )


def chunk_flops(cfg: dict, chunk: int, position: float,
                pairs_per_token: float) -> float:
    """FLOPs of one prefill chunk of ``chunk`` rows that starts at
    ``position``: the matrices a token touches (attention, the head, and
    every layer's router, grouped convolution and ``pairs_per_token``
    experts: ONE where every expert is held) and the causal score and
    mix products (row ``i`` attends ``position + i + 1`` rows)."""
    per_pair = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]  # q.k and p.v
    layers = cfg["num_hidden_layers"]
    touched = always_read_params(cfg) + layers * (
        router_params(cfg) + conv_params(cfg) + pairs_per_token * expert_params(cfg))
    causal = chunk * position + chunk * (chunk + 1) / 2.0
    return chunk * 2.0 * touched + layers * causal * per_pair
