"""Operations and bytes the programs of a ``glm5_next_text`` configuration
(delta-rule layers of per-slot state, sparse-latent layers whose rows an
indexer picks, residual streams, an expert layer of which this rank holds
a share) need on ONE RANK, computed from the benchmark's configuration
file (where ``n_routed_experts`` counts the experts held here and
``ep_size`` the ranks): the benchmark's side of
``decode_window_hbm_pct.kda-dsa``, ``prefill_chunk_mxu_pct.kda-dsa`` and
``dsa_rows_fetched_over_picked``. Plain numbers in, plain numbers out.

Weights count 1 byte a parameter (int8), the routers and the residual
maps 2 (bf16). The state term counts a live row's delta-rule state read
and written once a layer (8,388,608 B); the cache terms count pooled
indexer rows SCORED (256 B each) and latent rows PICKED (1,024 B each),
whatever fetched them, so a later kernel is read by the same yardstick.
The per-channel scales, the norms, the embedding rows, the convolution
tails and the rows written are left out, so the bytes are a lower bound
and a share computed from them cannot be flattered.
"""

from __future__ import annotations

from model_bytes_swa_moe import capture_edges, per  # noqa: F401  (the readers' helpers)

#: rows of one block of the delta rule's blocked form (glm5_next.KDA_BLOCK)
KDA_BLOCK = 16


def kda_layers(cfg: dict) -> int:
    return sum(kind == "linear_attention" for kind in cfg["layer_types"])


def dsa_layers(cfg: dict) -> int:
    return sum(kind == "deepseek_sparse_attention" for kind in cfg["layer_types"])


def expert_layers(cfg: dict) -> int:
    return sum(kind == "sparse" for kind in cfg["mlp_layer_types"])


def kda_params(cfg: dict) -> int:
    """One delta-rule mixer: q, k, v, o, the two low-rank gates, beta and
    the short convolutions (137,723,904 at GLM-5.3-Flash's widths)."""
    lin = cfg["linear_attn_config"]
    d, r = cfg["hidden_size"], lin["head_dim"]
    hk = lin["num_heads"] * r
    return (4 * d * hk + 2 * (d * r + r * hk) + d * lin["num_heads"]
            + 3 * hk * lin["short_conv_kernel_size"])


def dsa_params(cfg: dict) -> int:
    """One sparse-latent layer: the latent attention (117,440,512) and
    its indexer (6,946,816)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    q_rank, kv_rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, v = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    attention = (d * q_rank + q_rank * h * nope + d * kv_rank
                 + kv_rank * h * (nope + v) + h * v * d)
    indexer = (q_rank * cfg["index_n_heads"] * cfg["index_head_dim"]
               + d * cfg["index_head_dim"] + d * cfg["index_n_heads"])
    return attention + indexer


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down (25,165,824)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    """The router keeps every expert of the model: held x ep_size."""
    return cfg["hidden_size"] * cfg["n_routed_experts"] * cfg["ep_size"]


def map_params(cfg: dict) -> int:
    """The residual maps of one layer: two sublayers of n (2 + n) columns
    over n x hidden rows (2 x 393,216)."""
    n = cfg["hc_mult"]
    return 2 * n * cfg["hidden_size"] * (2 * n + n * n)


def always_read_params(cfg: dict) -> int:
    """int8 parameters every decode tick reads whatever the routing: all
    mixers, the dense layers' MLP, each expert layer's shared expert(s),
    and the head (1,006,239,744 at the cell's cut)."""
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    return (
        kda_layers(cfg) * kda_params(cfg) + dsa_layers(cfg) * dsa_params(cfg)
        + (layers - expert_layers(cfg)) * 3 * d * cfg["intermediate_size"]
        + expert_layers(cfg) * cfg["n_shared_experts"] * expert_params(cfg)
        + d * cfg["vocab_size"]
    )


def bf16_params(cfg: dict) -> int:
    """Routers and residual maps, read every tick at 2 bytes."""
    return (expert_layers(cfg) * router_params(cfg)
            + cfg["num_hidden_layers"] * map_params(cfg))


def state_bytes_per_row(cfg: dict) -> int:
    """One delta-rule layer's float32 state of one stream (4,194,304 B)."""
    lin = cfg["linear_attn_config"]
    return lin["num_heads"] * lin["head_dim"] * lin["head_dim"] * 4


def latent_row_bytes(cfg: dict, bytes_per_value: float = 2.0) -> float:
    return cfg["kv_lora_rank"] * bytes_per_value  # 1,024 B


def index_row_bytes(cfg: dict, bytes_per_value: float = 2.0) -> float:
    return cfg["index_head_dim"] * bytes_per_value  # 256 B a pooled row


def kv_bytes_per_token(cfg: dict, bytes_per_value: float = 2.0) -> float:
    """What a cached position holds in the paged pool: a latent row and a
    ``1 / index_kpool`` share of a pooled indexer row, a sparse-latent
    layer (1,088 B for one)."""
    return dsa_layers(cfg) * (
        latent_row_bytes(cfg, bytes_per_value)
        + index_row_bytes(cfg, bytes_per_value) / cfg["index_kpool"])


def decode_tick_bytes(cfg: dict, experts_touched: float, kda_row_ticks: float,
                      index_rows_scored: float, rows_picked: float) -> float:
    """Bytes one decode tick must move: the always-read int8, the bf16
    routers and maps, ``experts_touched`` routed experts (distinct held
    experts a tick had to read, summed over the expert layers),
    ``kda_row_ticks`` (live rows x delta-rule layers) states read and
    written, the pooled indexer rows its selecting rows scored and the
    latent rows its live rows picked (both already summed over the
    sparse-latent layers)."""
    return (
        always_read_params(cfg) + 2.0 * bf16_params(cfg)
        + experts_touched * expert_params(cfg)
        + kda_row_ticks * 2.0 * state_bytes_per_row(cfg)
        + index_rows_scored * index_row_bytes(cfg)
        + rows_picked * latent_row_bytes(cfg)
    )


def delta_rule_flops(cfg: dict, chunk: int, block: int = KDA_BLOCK) -> float:
    """The blocked delta rule's matrix products of one layer over
    ``chunk`` rows (what the blocked form needs at this block size; the
    pairwise decays inside a block are vector work and not counted): per
    block and head the inverse by doubling (2 (log2 Q - 1) products of Q
    x Q x Q), K S and Q S (Q x d_k x d_v each), T rhs and B U (Q x Q x
    d_v each) and K^T U (d_k x Q x d_v)."""
    lin = cfg["linear_attn_config"]
    h, dk = lin["num_heads"], lin["head_dim"]
    q = min(block, chunk)
    doubling = 2 * (q.bit_length() - 2) * q * q * q
    per_block = doubling + 3 * q * dk * dk + 2 * q * q * dk
    return 2.0 * h * (chunk // q) * per_block


def chunk_flops(cfg: dict, chunk: int, context: float,
                pairs_per_token: float) -> float:
    """FLOPs of one prefill chunk of ``chunk`` rows whose rows see
    ``context`` rows on average (position + 1: the program's
    ``dsa_chunk_rows_in_context`` / ``kda_chunk_rows``): the matrices a
    token touches (always-read, routers, maps, and ``pairs_per_token``
    landed (token, expert) pairs an expert layer), the delta rule's block
    products, and for a sparse-latent layer the index scores of the
    ``context / index_kpool`` pooled rows a row may score and the absorbed
    score and mix products over the rows it PICKS (at most ``index_topk +
    index_kpool``): what is needed, not the dense product under the mask
    that the program multiplies."""
    touched = (
        always_read_params(cfg) + bf16_params(cfg)
        + expert_layers(cfg) * pairs_per_token * expert_params(cfg)
    )
    picked = min(context, cfg["index_topk"] + cfg["index_kpool"])
    scored = context / cfg["index_kpool"] if context > cfg["index_topk"] else 0.0
    attend = 4.0 * cfg["num_attention_heads"] * cfg["kv_lora_rank"] * picked
    index = 2.0 * cfg["index_n_heads"] * cfg["index_head_dim"] * scored
    return (chunk * 2.0 * touched
            + kda_layers(cfg) * delta_rule_flops(cfg, chunk)
            + dsa_layers(cfg) * chunk * (attend + index))
