"""Operations and bytes the programs of a ``kimi_k2`` (MLA + expert
layer) configuration need on ONE RANK of its expert group, computed
from the benchmark's configuration file (where ``n_routed_experts``
counts the experts held here and ``ep_size`` the ranks): the
benchmark's side of ``decode_window_hbm_pct.mla-moe`` and
``prefill_chunk_mxu_pct.mla-moe``. Plain numbers in, plain numbers out.

Weights count 1 byte a parameter (int8); the per-channel scales, the
norms, the embedding rows of the live sequences and the latent cache
rows are left out, so the bytes are a lower bound and a share computed
from them cannot be flattered.
"""

from __future__ import annotations


def attention_params(cfg: dict) -> int:
    """q_a, kv_a, q_b, kv_b and o of one layer."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return (
        d * cfg["q_lora_rank"] + d * (cfg["kv_lora_rank"] + rope)
        + cfg["q_lora_rank"] * h * (nope + rope)
        + cfg["kv_lora_rank"] * h * (nope + v) + h * v * d
    )


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    """The router keeps every expert of the model: held x ep_size."""
    return cfg["hidden_size"] * cfg["n_routed_experts"] * cfg["ep_size"]


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def local_pairs_per_token(cfg: dict) -> float:
    """Expected routed pairs of one token that land on this rank in one
    expert layer: experts per token / ranks (0.25 for 8 over 32)."""
    return cfg["num_experts_per_tok"] / cfg["ep_size"]


def always_read_params(cfg: dict) -> int:
    """Parameters every decode tick reads whatever the routing: all
    layers' attention, the dense layers' MLP, each expert layer's
    shared expert(s), and the head."""
    d = cfg["hidden_size"]
    return (
        cfg["num_hidden_layers"] * attention_params(cfg)
        + cfg["first_k_dense_replace"] * 3 * d * cfg["intermediate_size"]
        + expert_layers(cfg) * cfg["n_shared_experts"] * expert_params(cfg)
        + d * cfg["vocab_size"]
    )


def decode_tick_bytes(cfg: dict, experts_touched: float,
                      bytes_per_weight: float = 1.0,
                      router_bytes_per_weight: float = 2.0) -> float:
    """Bytes one decode tick must read: the always-read weights, the
    bf16 router of every expert layer, and ``experts_touched`` (the mean
    number of distinct held experts a layer a tick had to read, a
    counter of the program) routed experts in every expert layer."""
    return (
        bytes_per_weight * always_read_params(cfg)
        + router_bytes_per_weight * expert_layers(cfg) * router_params(cfg)
        + bytes_per_weight * experts_touched * expert_layers(cfg) * expert_params(cfg)
    )


def matmul_flops_per_token(cfg: dict) -> float:
    """Weight-matmul FLOPs of one token on this rank, with the expected
    ``local_pairs_per_token`` routed pairs a layer; no score term."""
    return 2.0 * (
        always_read_params(cfg)
        + expert_layers(cfg) * (
            router_params(cfg) + local_pairs_per_token(cfg) * expert_params(cfg)
        )
    )


def attention_flops_absorbed(cfg: dict, queries: int, context: int) -> float:
    """Score + value FLOPs of ``queries`` rows over ``context`` cached
    latents in one layer, absorbed form: every head scores against the
    shared (kv_lora_rank + rope) row and mixes kv_lora_rank values."""
    h = cfg["num_attention_heads"]
    return 2.0 * queries * h * context * (
        2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    )


def attention_flops_expanded(cfg: dict, queries: int, context: int) -> float:
    """The same attention with keys and values expanded: kv_b applied
    to all ``context`` latents, then per-head scores over nope + rope
    and values over v_head_dim."""
    h = cfg["num_attention_heads"]
    nope, rope, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    expand = 2.0 * context * cfg["kv_lora_rank"] * h * (nope + v)
    return expand + 2.0 * queries * h * context * (nope + rope + v)
