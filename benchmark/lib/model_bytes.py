"""Operations and bytes a model's programs need, computed from its
configuration: the benchmark's side of every roofline share.

``decode_tick_weight_bytes`` is this benchmark's; the three FLOP
functions are copied from ``bench_vlm.py`` (sound arithmetic, PR 21) for
the roofline readers a later PR adds, taking plain numbers so that no
program class is imported.
"""

from __future__ import annotations


def decode_tick_weight_bytes(cfg: dict, bytes_per_weight: float = 1.0) -> float:
    """Bytes of quantized decode weights one decode tick must read: every
    layer's q/k/v/o and gate/up/down matrices and the lm_head (tied or
    not, the head is a [hidden, vocab] matrix of its own in the fused
    layout), at ``bytes_per_weight`` (1 for int8). Scales, biases, norms,
    the one embedding row per live sequence and the KV pages (28,672 B a
    live row at these widths) are left out, so this is a lower bound."""
    dim = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    hd = dim // heads
    kv = cfg["num_key_value_heads"] * hd
    ffn = cfg["intermediate_size"]
    per_layer = dim * heads * hd + 2 * dim * kv + heads * hd * dim + 3 * dim * ffn
    return bytes_per_weight * (
        cfg["num_hidden_layers"] * per_layer + dim * cfg["vocab_size"]
    )


def kv_bytes_per_row(cfg: dict, bytes_per_value: float = 2.0) -> float:
    """Bytes of keys and values one cached position holds over all layers."""
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return (
        2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * hd
        * bytes_per_value
    )


def lm_matmul_flops_per_token(cfg: dict) -> float:
    """Weight-matmul FLOPs for one LM token (no attention scores)."""
    return 2.0 * decode_tick_weight_bytes(cfg, 1.0)


def lm_attention_flops(cfg: dict, context: int) -> float:
    """Score+value FLOPs for one token attending over ``context`` keys."""
    return cfg["num_hidden_layers"] * 4.0 * context * cfg["hidden_size"]


def vision_matmul_flops(v: dict, lm_dim: int) -> float:
    """Vision tower FLOPs for one image (all patches); ``v`` holds
    image_size, patch_size, vision_dim, vision_layers, vision_ffn."""
    p = (v["image_size"] // v["patch_size"]) ** 2
    patch_dim = v["patch_size"] * v["patch_size"] * 3
    per_layer = 2 * (4 * v["vision_dim"] ** 2 + 3 * v["vision_dim"] * v["vision_ffn"])
    attn = 4.0 * p * v["vision_dim"]
    return p * (
        2 * patch_dim * v["vision_dim"]
        + v["vision_layers"] * per_layer
        + v["vision_layers"] * attn
        + 2 * v["vision_dim"] * lm_dim
    )
