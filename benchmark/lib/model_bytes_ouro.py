"""Operations and bytes the programs of an ``ouro`` configuration (a
stack of layers run ``total_ut_steps`` times a token, each pass with K/V
rows of its own) need, computed from the benchmark's configuration file:
the benchmark's side of ``decode_window_hbm_pct.looped`` and
``prefill_chunk_mxu_pct.looped``. Plain numbers in, plain numbers out.

Weights count 1 byte a parameter (int8) and are read once a PASS; the
head once a tick. The K/V term is counted, the first window share that
has one: a live row reads, in every pass and every layer, the K and V
rows of every position it attends to. The per-channel scales, the norms,
the embedding rows and the K/V rows written are left out, so the bytes
are a lower bound and a share computed from them cannot be flattered.
"""

from __future__ import annotations


def layer_params(cfg: dict) -> int:
    """q, k, v, o and the three SwiGLU matrices of one layer."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return d * (q + 2 * kv) + q * d + 3 * d * cfg["intermediate_size"]


def stack_params(cfg: dict) -> int:
    """All layers, once."""
    return cfg["num_hidden_layers"] * layer_params(cfg)


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def tick_weight_params(cfg: dict) -> int:
    """Parameters one decode tick reads: the stack once a pass, the head
    once."""
    return cfg["total_ut_steps"] * stack_params(cfg) + head_params(cfg)


def kv_entry_bytes(cfg: dict, bytes_per_value: float = 2.0) -> float:
    """K and V of one position in ONE cache entry (a layer of a pass):
    8,192 B at 16 K/V heads of 128 in bf16."""
    return 2.0 * cfg["num_key_value_heads"] * cfg["head_dim"] * bytes_per_value


def kv_bytes_per_token(cfg: dict, bytes_per_value: float = 2.0) -> float:
    """What a token's position holds over all layers and passes:
    1,572,864 B at 48 layers x 4 passes."""
    return (cfg["num_hidden_layers"] * cfg["total_ut_steps"]
            * kv_entry_bytes(cfg, bytes_per_value))


def decode_tick_bytes(cfg: dict, rows_read: float,
                      bytes_per_weight: float = 1.0) -> float:
    """Bytes one decode tick must move: its weights, and the K/V of the
    ``rows_read`` rows its live rows attended to, summed over live rows
    AND passes (the program's ``loop_kv_rows_read`` a tick), in every
    layer."""
    return (bytes_per_weight * tick_weight_params(cfg)
            + rows_read * cfg["num_hidden_layers"] * kv_entry_bytes(cfg))


def chunk_flops(cfg: dict, chunk: int, position: float) -> float:
    """FLOPs of one prefill chunk of ``chunk`` rows that starts at
    ``position``: the stack's matmuls once a pass, and the causal score
    and mix products (row ``i`` attends to ``position + i + 1`` rows) of
    every layer of every pass. The head (computed over the chunk's rows
    too) is left out: a lower bound."""
    pairs = chunk * position + chunk * (chunk + 1) / 2.0
    per_pair = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]  # q.k and p.v
    return cfg["total_ut_steps"] * (
        chunk * 2.0 * stack_params(cfg)
        + cfg["num_hidden_layers"] * pairs * per_pair)


def _gained(before: dict | None, after: dict | None, key: str):
    before, after = before or {}, after or {}
    if key not in after:
        return None
    return after[key] - (before.get(key) or 0)


def rows_read_a_tick(before: dict | None, after: dict | None) -> float | None:
    """Mean K/V rows a decode tick read (over its live rows and passes)
    between two of the model node's serving snapshots, from the
    program's counters (``loop_kv_rows_read`` and ``loop_decode_ticks``
    gained). None where the program has no such counters or no tick ran."""
    rows = _gained(before, after, "loop_kv_rows_read")
    ticks = _gained(before, after, "loop_decode_ticks")
    if rows is None or not ticks or ticks <= 0:
        return None
    return rows / ticks


def live_rows_a_tick(before: dict | None, after: dict | None,
                     passes: int) -> float | None:
    """Mean live rows a decode tick (``loop_passes`` gained / passes /
    ticks)."""
    done = _gained(before, after, "loop_passes")
    ticks = _gained(before, after, "loop_decode_ticks")
    if done is None or not ticks or ticks <= 0:
        return None
    return done / passes / ticks


def chunk_position(before: dict | None, after: dict | None) -> float | None:
    """Mean start position of the prefill chunks between two snapshots
    (``loop_chunk_positions`` / ``loop_chunks`` gained)."""
    where = _gained(before, after, "loop_chunk_positions")
    chunks = _gained(before, after, "loop_chunks")
    if where is None or not chunks or chunks <= 0:
        return None
    return where / chunks


def capture_edges(run: dict) -> tuple[dict, dict] | None:
    """The program's counters as they stood when a traced run's capture
    started and stopped (``capture_counters`` in the serving snapshot
    taken behind the capture). None where the program does not say."""
    behind = run.get("serving_traced") or run.get("serving_after") or {}
    edges = behind.get("capture_counters") or {}
    if "start" not in edges or "stop" not in edges:
        return None
    return edges["start"], edges["stop"]
