"""Operations and bytes the programs of a ``falcon_h1`` configuration (a
Mamba-2 mixer beside attention in every layer) need, computed from the
benchmark's configuration file: the benchmark's side of
``ssm_state_step_hbm_pct``, ``decode_window_hbm_pct.ssm-hybrid`` and
``prefill_chunk_mxu_pct.ssm-hybrid``. Plain numbers in, plain numbers out.

Weights count 1 byte a parameter (int8); the per-channel scales, the
norms, the convolution, the embedding rows of the live sequences and the
K/V rows are left out, so the bytes are a lower bound and a share
computed from them cannot be flattered. The recurrent state is float32
and a decode tick reads and writes all of a live row's.
"""

from __future__ import annotations


def attention_params(cfg: dict) -> int:
    """q, k, v and o of one layer (q is heads x head_dim wide, not D)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return d * (q + 2 * kv) + q * d


def mixer_in_width(cfg: dict) -> int:
    """W_in's outputs: z, then x | B | C (the convolved part), then dt."""
    conv = cfg["mamba_d_ssm"] + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return cfg["mamba_d_ssm"] + conv + cfg["mamba_n_heads"]


def mixer_params(cfg: dict) -> int:
    """W_in and W_out of one layer."""
    d = cfg["hidden_size"]
    return d * mixer_in_width(cfg) + cfg["mamba_d_ssm"] * d


def mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def layer_params(cfg: dict) -> int:
    return attention_params(cfg) + mixer_params(cfg) + mlp_params(cfg)


def tick_weight_params(cfg: dict) -> int:
    """Parameters every decode tick reads: all layers and the head."""
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def state_values_a_row_a_layer(cfg: dict) -> int:
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]


def state_step_bytes(cfg: dict, bytes_per_value: float = 4.0) -> float:
    """Bytes the state update of ONE live row in ONE layer must move: the
    whole state read and written (8,388,608 at 32 x 128 x 256 float32)."""
    return 2.0 * bytes_per_value * state_values_a_row_a_layer(cfg)


def decode_tick_bytes(cfg: dict, live_rows: float,
                      bytes_per_weight: float = 1.0) -> float:
    """Bytes one decode tick must move: the weights, and the state of
    ``live_rows`` rows (a counter of the program: mean rows stepped a
    tick) in every layer."""
    return (bytes_per_weight * tick_weight_params(cfg)
            + live_rows * cfg["num_hidden_layers"] * state_step_bytes(cfg))


def matmul_flops_per_token(cfg: dict) -> float:
    """Weight-matmul FLOPs of one token; no score and no scan term."""
    return 2.0 * tick_weight_params(cfg)


def live_rows_a_tick(before: dict | None, after: dict | None) -> float | None:
    """Mean rows the state-step kernel stepped a decode tick between two
    of the model node's serving snapshots, from the program's counters
    (``ssm_row_ticks`` and ``ssm_decode_ticks`` gained). None where the
    program has no such counters or no tick ran."""
    before, after = before or {}, after or {}
    if "ssm_row_ticks" not in after or "ssm_decode_ticks" not in after:
        return None
    ticks = after["ssm_decode_ticks"] - (before.get("ssm_decode_ticks") or 0)
    rows = after["ssm_row_ticks"] - (before.get("ssm_row_ticks") or 0)
    return rows / ticks if ticks > 0 else None


def live_rows_in_capture(run: dict) -> float | None:
    """Mean live rows a tick over the ticks a traced run's capture holds:
    between the program's counters as they stood when the capture
    started and stopped (``capture_counters`` in the serving snapshot
    taken behind the capture; both edges fall between a window's
    collection and the next dispatch). None where the program does not
    say where they stood."""
    behind = run.get("serving_traced") or run.get("serving_after") or {}
    edges = behind.get("capture_counters") or {}
    if "start" not in edges or "stop" not in edges:
        return None
    return live_rows_a_tick(edges["start"], edges["stop"])
