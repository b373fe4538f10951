"""Operations and bytes the programs of a ``KeyeVL2`` configuration
(grouped-query attention whose cached rows an indexer picks a position at
a time, over a softmax-routed expert layer of which this rank holds a
share, in every layer) need on ONE RANK, computed from the benchmark's
configuration file (where ``num_experts`` counts the experts held here and
``ep_size`` the ranks): the benchmark's side of
``decode_window_hbm_pct.gqa-dsa`` and ``prefill_chunk_mxu_pct.gqa-dsa``.
Plain numbers in, plain numbers out.

Weights count 1 byte a parameter (int8), the routers 2 (bf16). The cache
terms count indexer keys SCORED (128 B each) and K|V rows PICKED (2,048 B
each), whatever fetched them, so a later kernel is read by the same
yardstick. The per-channel scales, the norms, the embedding rows, the 112
padded columns of the indexer's head weights and the rows written are
left out, so the bytes are a lower bound and a share computed from them
cannot be flattered.
"""

from __future__ import annotations

from model_bytes_swa_moe import capture_edges, per  # noqa: F401  (the readers' helpers)


def attention_params(cfg: dict) -> int:
    """q, k, v and o of one layer (18,874,368 at Keye-VL-2.0's widths)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return d * (q + 2 * kv) + q * d


def indexer_params(cfg: dict) -> int:
    """The indexer's queries, its one key and its head weights
    (2,260,992)."""
    sa = cfg["sa_config"]
    return cfg["hidden_size"] * (
        sa["indexer_num_heads"] * sa["indexer_head_dim"] + sa["indexer_head_dim"]
        + sa["indexer_num_heads"])


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down (4,718,592)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    """The router keeps every expert of the model: held x ep_size."""
    return cfg["hidden_size"] * cfg["num_experts"] * cfg["ep_size"]


def always_read_params(cfg: dict) -> int:
    """int8 parameters every decode tick reads whatever the routing:
    every layer's attention and indexer, and the head (292,519,936 at the
    cell's cut)."""
    return (cfg["num_hidden_layers"] * (attention_params(cfg) + indexer_params(cfg))
            + cfg["hidden_size"] * cfg["vocab_size"])


def bf16_params(cfg: dict) -> int:
    """The routers, read every tick at 2 bytes."""
    return cfg["num_hidden_layers"] * router_params(cfg)


def kv_row_bytes(cfg: dict, bytes_per_value: float = 2.0) -> float:
    """A position's keys and values of one layer (2,048 B)."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * bytes_per_value


def index_row_bytes(cfg: dict, bytes_per_value: float = 2.0) -> float:
    """A position's indexer key of one layer (128 B)."""
    return cfg["sa_config"]["indexer_head_dim"] * bytes_per_value


def kv_bytes_per_token(cfg: dict, bytes_per_value: float = 2.0) -> float:
    """What a cached position holds in the paged pool, every layer
    (26,112 B for twelve)."""
    return cfg["num_hidden_layers"] * (
        kv_row_bytes(cfg, bytes_per_value) + index_row_bytes(cfg, bytes_per_value))


def decode_tick_bytes(cfg: dict, experts_touched: float, index_rows_scored: float,
                      rows_picked: float) -> float:
    """Bytes one decode tick must move: the always-read int8, the bf16
    routers, ``experts_touched`` routed experts (distinct held experts a
    tick had to read, summed over the layers), the indexer keys its
    selecting rows scored and the K|V rows its live rows picked (both
    already summed over the layers)."""
    return (
        always_read_params(cfg) + 2.0 * bf16_params(cfg)
        + experts_touched * expert_params(cfg)
        + index_rows_scored * index_row_bytes(cfg)
        + rows_picked * kv_row_bytes(cfg)
    )


def chunk_flops(cfg: dict, chunk: int, context: float,
                pairs_per_token: float) -> float:
    """FLOPs of one prefill chunk of ``chunk`` rows whose rows see
    ``context`` rows on average (position + 1: the program's
    ``dsa_chunk_rows_in_context`` a layer / ``dsa_chunk_rows``): the
    matrices a token touches (always-read, routers, and
    ``pairs_per_token`` landed (token, expert) pairs a layer), and a layer
    the index scores of the ``context`` positions a selecting row scores
    and the score and mix products over the rows it PICKS (at most
    ``topk``): what is needed, not the dense product under the mask that
    the program multiplies."""
    sa = cfg["sa_config"]
    touched = (
        always_read_params(cfg) + bf16_params(cfg)
        + cfg["num_hidden_layers"] * pairs_per_token * expert_params(cfg)
    )
    picked = min(context, sa["topk"])
    scored = context if context > sa["topk"] else 0.0
    attend = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * picked
    index = 2.0 * sa["indexer_num_heads"] * sa["indexer_head_dim"] * scored
    return chunk * (2.0 * touched + cfg["num_hidden_layers"] * (attend + index))
