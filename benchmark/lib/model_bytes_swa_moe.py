"""Operations and bytes the programs of an ``exaone_moe`` configuration
(sliding-window layers in a ring a slot, global layers in pages, an
expert layer of which this rank holds a share) need on ONE RANK,
computed from the benchmark's configuration file (where ``num_experts``
counts the experts held here and ``ep_size`` the ranks): the
benchmark's side of ``decode_window_hbm_pct.swa-moe``,
``prefill_chunk_mxu_pct.swa-moe`` and ``global_kv_swept_over_read``.
Plain numbers in, plain numbers out.

Weights count 1 byte a parameter (int8), the routers 2 (bf16). The
cache terms count rows ATTENDED (the program's ``global_kv_rows_read``
and ``swa_ring_rows_read``: over ticks and live rows, position + 1 rows
a global layer and min(position + 1, window) a window layer), whatever
fetched them, so a later kernel is read by the same yardstick. The
per-channel scales, the norms, the embedding rows and the rows written
are left out, so the bytes are a lower bound and a share computed from
them cannot be flattered.
"""

from __future__ import annotations


def attention_params(cfg: dict) -> int:
    """q, k, v and o of one layer (113,246,208 at K-EXAONE's widths)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return d * (q + 2 * kv) + q * d


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down (37,748,736)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    """The router keeps every expert of the model: held x ep_size."""
    return cfg["hidden_size"] * cfg["num_experts"] * cfg["ep_size"]


def expert_layers(cfg: dict) -> int:
    return sum(kind == "sparse" for kind in cfg["mlp_layer_types"])


def global_layers(cfg: dict) -> int:
    return sum(kind == "full_attention" for kind in cfg["layer_types"])


def window_layers(cfg: dict) -> int:
    return sum(kind == "sliding_attention" for kind in cfg["layer_types"])


def always_read_params(cfg: dict) -> int:
    """Parameters every decode tick reads whatever the routing: all
    layers' attention, the dense layers' MLP, each expert layer's shared
    expert(s), and the head (1,627,914,240 at the cell's cut)."""
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    return (
        layers * attention_params(cfg)
        + (layers - expert_layers(cfg)) * 3 * d * cfg["intermediate_size"]
        + expert_layers(cfg) * cfg["num_shared_experts"] * expert_params(cfg)
        + d * cfg["vocab_size"]
    )


def kv_row_bytes(cfg: dict, bytes_per_value: float = 2.0) -> float:
    """K and V of one position in ONE layer (4,096 B at 8 K/V heads of
    128 in bf16), a page's row and a ring's row alike."""
    return 2.0 * cfg["num_key_value_heads"] * cfg["head_dim"] * bytes_per_value


def kv_bytes_per_token(cfg: dict, bytes_per_value: float = 2.0) -> float:
    """What a cached position holds in the paged pool: the global layers
    alone (8,192 B for two of eight)."""
    return global_layers(cfg) * kv_row_bytes(cfg, bytes_per_value)


def decode_tick_bytes(cfg: dict, experts_touched: float, global_rows: float,
                      ring_rows: float, bytes_per_weight: float = 1.0,
                      router_bytes_per_weight: float = 2.0) -> float:
    """Bytes one decode tick must move: the always-read weights, the
    bf16 router of every expert layer, ``experts_touched`` routed experts
    (distinct held experts a tick had to read, summed over the expert
    layers), and the K/V of the rows its live rows attended:
    ``global_rows`` in the pages and ``ring_rows`` in the rings, both
    already summed over their layers."""
    return (
        bytes_per_weight * always_read_params(cfg)
        + router_bytes_per_weight * expert_layers(cfg) * router_params(cfg)
        + bytes_per_weight * experts_touched * expert_params(cfg)
        + (global_rows + ring_rows) * kv_row_bytes(cfg)
    )


def chunk_flops(cfg: dict, chunk: int, position: float,
                pairs_per_token: float) -> float:
    """FLOPs of one prefill chunk of ``chunk`` rows that starts at
    ``position``: the matrices a token touches (always-read, routers,
    and ``pairs_per_token`` landed (token, expert) pairs an expert
    layer), the causal score and mix products of the global layers (row
    ``i`` attends ``position + i + 1`` rows) and the window layers' band
    (``sliding_window`` rows a row: what is needed, not the ``[ring ++
    chunk]`` the program multiplies)."""
    per_pair = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]  # q.k and p.v
    touched = (
        always_read_params(cfg)
        + expert_layers(cfg) * (router_params(cfg) + pairs_per_token * expert_params(cfg))
    )
    causal = chunk * position + chunk * (chunk + 1) / 2.0
    band = chunk * float(cfg["sliding_window"])
    return (chunk * 2.0 * touched
            + global_layers(cfg) * causal * per_pair
            + window_layers(cfg) * band * per_pair)


def _gained(before: dict | None, after: dict | None, key: str):
    before, after = before or {}, after or {}
    if after.get(key) is None:
        return None
    return after[key] - (before.get(key) or 0)


def per(before: dict | None, after: dict | None, what: str, unit: str) -> float | None:
    """``what`` gained between two of the model node's serving snapshots
    (or a capture's two edges) for each ``unit`` gained; None where the
    program has no such counters or no ``unit`` was counted."""
    top, bottom = _gained(before, after, what), _gained(before, after, unit)
    if top is None or not bottom or bottom <= 0:
        return None
    return top / bottom


def capture_edges(run: dict) -> tuple[dict, dict] | None:
    """The program's counters as they stood when a traced run's capture
    started and stopped (``capture_counters`` in the serving snapshot
    taken behind the capture). None where the program does not say."""
    behind = run.get("serving_traced") or run.get("serving_after") or {}
    edges = behind.get("capture_counters") or {}
    if "start" not in edges or "stop" not in edges:
        return None
    return edges["start"], edges["stop"]
