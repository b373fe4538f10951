"""Operations and bytes the programs of a ``kimi_linear`` configuration
(delta-rule KDA layers of per-slot float32 state, latent layers that sweep
every cached row, an expert layer of which this rank holds a share) need on
ONE RANK, computed from the benchmark's configuration file (where
``num_experts`` counts the experts held here and ``ep_size`` the ranks):
the benchmark's side of ``decode_window_hbm_pct.kda-mla``,
``prefill_chunk_mxu_pct.kda-mla``, ``kda_state_step_hbm_pct`` and
``latent_kv_swept_over_read``. Plain numbers in, plain numbers out.

Weights count 1 byte a parameter (int8), the routers 2 (bf16). The state
term counts a live row's delta-rule state read and written once a layer
(4,194,304 B); the cache term counts the latent rows a live row ATTENDS at
their stored width (1,280 B: 576 values kept as 640), whatever a sweep
fetched beside them, so a later kernel is read by the same yardstick. The
per-channel scales, the norms, the embedding rows, the convolution tails
and the rows written are left out, so the bytes are a lower bound and a
share computed from them cannot be flattered.
"""

from __future__ import annotations

from model_bytes_swa_moe import capture_edges, per  # noqa: F401  (the readers' helpers)

#: rows of one block of the delta rule's blocked form (kimi_linear.KDA_BLOCK)
KDA_BLOCK = 16


def kda_layers(cfg: dict) -> int:
    return len(cfg["linear_attn_config"]["kda_layers"])


def mla_layers(cfg: dict) -> int:
    return len(cfg["linear_attn_config"]["full_attn_layers"])


def expert_layers(cfg: dict) -> int:
    freq = cfg.get("moe_layer_freq", 1)
    return sum(i >= cfg["first_k_dense_replace"] and i % freq == 0
               for i in range(cfg["num_hidden_layers"]))


def kda_params(cfg: dict) -> int:
    """One KDA mixer: q, k, v, o, the two low-rank gates, beta and the
    short convolutions (39,510,016 at Kimi Linear's widths)."""
    lin = cfg["linear_attn_config"]
    d, r = cfg["hidden_size"], lin["head_dim"]
    hk = lin["num_heads"] * r
    return (4 * d * hk + 2 * (d * r + r * hk) + d * lin["num_heads"]
            + 3 * hk * lin["short_conv_kernel_size"])


def mla_params(cfg: dict) -> int:
    """One latent layer: the queries, the cached row's projection, the
    latent's two halves per head, the output (29,114,368)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, shared, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rank = cfg["kv_lora_rank"]
    return (d * h * (nope + shared) + d * (rank + shared)
            + rank * h * (nope + v) + h * v * d)


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down (7,077,888)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]  # 63,700,992


def router_params(cfg: dict) -> int:
    """The router keeps every expert of the model: held x ep_size."""
    return cfg["hidden_size"] * cfg["num_experts"] * cfg["ep_size"]


def layer_params(cfg: dict, i: int) -> int:
    """int8 parameters of layer ``i`` (from 0) on this rank: its mixer, and
    the dense MLP or the shared expert(s) and the held experts."""
    mixer = (kda_params(cfg) if i + 1 in cfg["linear_attn_config"]["kda_layers"]
             else mla_params(cfg))
    freq = cfg.get("moe_layer_freq", 1)
    if i < cfg["first_k_dense_replace"] or i % freq:
        return mixer + dense_params(cfg)
    return mixer + (cfg["num_shared_experts"] + cfg["num_experts"]) * expert_params(cfg)


def weight_bytes(cfg: dict) -> dict:
    """What the device holds of the weights: every layer's int8, the head's
    int8, the embedding and the routers at bf16."""
    layers = sum(layer_params(cfg, i) for i in range(cfg["num_hidden_layers"]))
    head = cfg["hidden_size"] * cfg["vocab_size"]
    return {"layers_int8": layers, "head_int8": head, "embedding_bf16": 2 * head,
            "routers_bf16": 2 * expert_layers(cfg) * router_params(cfg),
            "total": layers + 3 * head + 2 * expert_layers(cfg) * router_params(cfg)}


def always_read_params(cfg: dict) -> int:
    """int8 parameters every decode tick reads whatever the routing: all
    mixers, the dense layers' MLP, each expert layer's shared expert(s),
    and the head (549,494,784 at the cell's cut)."""
    layers = cfg["num_hidden_layers"]
    return (
        kda_layers(cfg) * kda_params(cfg) + mla_layers(cfg) * mla_params(cfg)
        + (layers - expert_layers(cfg)) * dense_params(cfg)
        + expert_layers(cfg) * cfg["num_shared_experts"] * expert_params(cfg)
        + cfg["hidden_size"] * cfg["vocab_size"]
    )


def bf16_params(cfg: dict) -> int:
    """The routers, read every tick at 2 bytes."""
    return expert_layers(cfg) * router_params(cfg)


def state_bytes_per_row(cfg: dict) -> int:
    """One KDA layer's float32 state of one stream (2,097,152 B)."""
    lin = cfg["linear_attn_config"]
    return lin["num_heads"] * lin["head_dim"] * lin["head_dim"] * 4


def state_step_bytes(cfg: dict) -> int:
    """What ``kda_state_step`` moves for one live row of one layer: the
    state read and written (4,194,304 B)."""
    return 2 * state_bytes_per_row(cfg)


def latent_row_bytes(cfg: dict, bytes_per_value: float = 2.0) -> float:
    """A cached row as stored: ``kv_lora_rank + qk_rope_head_dim`` values
    padded to a multiple of 128 lanes (640 values, 1,280 B)."""
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return -(-width // 128) * 128 * bytes_per_value


def kv_bytes_per_token(cfg: dict, bytes_per_value: float = 2.0) -> float:
    """What a cached position holds in the paged pool: a stored row a
    latent layer (2,560 B for two)."""
    return mla_layers(cfg) * latent_row_bytes(cfg, bytes_per_value)


def decode_tick_bytes(cfg: dict, experts_touched: float, kda_row_ticks: float,
                      rows_in_context: float) -> float:
    """Bytes one decode tick must move: the always-read int8, the bf16
    routers, ``experts_touched`` routed experts (distinct held experts a
    tick had to read, summed over the expert layers), ``kda_row_ticks``
    (live rows x KDA layers) states read and written, and the latent rows
    its live rows attended (``mla_rows_in_context``: already summed over
    the latent layers)."""
    return (
        always_read_params(cfg) + 2.0 * bf16_params(cfg)
        + experts_touched * expert_params(cfg)
        + kda_row_ticks * state_step_bytes(cfg)
        + rows_in_context * latent_row_bytes(cfg)
    )


def delta_rule_flops(cfg: dict, chunk: int, block: int = KDA_BLOCK) -> float:
    """The blocked delta rule's matrix products of one layer over ``chunk``
    rows (``model_bytes_kda_dsa.delta_rule_flops``: the same form)."""
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
    ``mla_chunk_rows_in_context`` / ``kda_chunk_rows`` a latent layer): the
    matrices a token touches (always-read, routers, and ``pairs_per_token``
    landed (token, expert) pairs an expert layer), the delta rule's block
    products, and for a latent layer the absorbed score and mix products
    over the rows a row attends (score over the 576 kept columns, mix over
    the 512 of the latent): what is needed, not the rows under the mask
    that the program multiplies."""
    touched = (
        always_read_params(cfg) + bf16_params(cfg)
        + expert_layers(cfg) * pairs_per_token * expert_params(cfg)
    )
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    attend = 2.0 * cfg["num_attention_heads"] * (width + cfg["kv_lora_rank"]) * context
    return (chunk * 2.0 * touched
            + kda_layers(cfg) * delta_rule_flops(cfg, chunk)
            + mla_layers(cfg) * chunk * attend)
