"""Benchmark trend tracking: append every ``bench.py`` run to
``BENCH_history.jsonl`` and flag regressions against the last comparable
run.

Raw bench numbers from different machines (or the same machine in a
different state) are not comparable, so every appended record carries:

* an **environment fingerprint** — platform, CPU count, Python version,
  and the perf-relevant ``DORA_*`` knobs, hashed to a short id. Only
  runs with the same fingerprint are compared.
* an **ambient-throughput calibration** — a ~0.2 s in-process hashing
  loop measured at append time. If the machine itself got slower (noisy
  neighbors, thermal throttling, a busy CI host), the calibration moves
  with it and the comparison is skipped instead of mis-flagged as a code
  regression — the same reasoning that interleaves the A/B legs in
  ``bench.py``.

A watched metric that is >10% worse than the previous fingerprint-matched
run (with calibration within 20%) is reported in ``regressions`` — the
caller prints them and ships them inside the bench JSON line; the history
file is the long-term record.
"""

from __future__ import annotations

import hashlib
import json
import os
import platform
import sys
import time
from pathlib import Path
from typing import Any

#: metric path (dot-separated into the bench record) -> better direction
WATCHED: dict[str, str] = {
    "value": "lower",  # 40 MB p50 latency (us)
    "msgs_per_sec_1kib.daemon": "higher",
    "msgs_per_sec_1kib.p2p": "higher",
    "p50_us_1kib.daemon": "lower",
    "p99_us_1kib.daemon": "lower",
    "e2e_fps": "higher",
    # Traffic-shaping soak: the on/off interactive TTFT p99 ratio —
    # a drift toward 1.0 means shaping stopped buying latency.
    "serving_qos_soak.interactive_p99_on_vs_off": "lower",
    # Shared-prefix cache A/B: hit-request TTFT p50 ratio on/off — a
    # drift toward 1.0 means cache hits stopped buying first-token
    # latency (the default-on gate is <= 0.5).
    "serving_prefix_ab.hit_p50_on_vs_off": "lower",
    # Alerting-plane A/B: msgs/sec overhead of the default rule pack
    # evaluating each history tick vs engine off — a drift upward means
    # rule evaluation crept onto the budget (the gate is <= 3%).
    "alerts_ab.overhead_pct": "lower",
    # Device-monitor A/B: wall-clock with the utilization plane on vs
    # off — a drift upward means the default-on monitor got expensive
    # (the gate is <= 3%).
    "serving_profiling_ab.overhead_pct": "lower",
    # Quantized serving: concurrent streams admitted into the fp
    # pool's byte budget, int8 vs fp — a drift downward means the
    # scale-plane overhead grew (the gate is >= 1.8).
    "serving_quant_ab.capacity.int8_capacity_ratio": "higher",
    # Spec acceptance under int8 KV: the round-18 guidance is that
    # acceptance counters, not token identity, are the drift signal
    # when KV is quantized — a downward drift means rounding started
    # flipping draft verifications.
    "serving_quant_ab.spec.spec_acceptance": "higher",
    # Fleet-digest A/B: serving wall-clock with the engine-state
    # exporter publishing at 0.5 s vs off — a drift upward means the
    # digest walk crept onto the decode path (the gate is <= 3%).
    "fleet_digest_ab.overhead_pct": "lower",
    # Multi-tenant LoRA: aggregate tok/s of one N-adapter engine vs N
    # single-tenant engines in the same HBM budget — a drift toward
    # 1.0 means the shared fused window stopped amortizing across
    # tenants (the gate is >= 1.5).
    "serving_lora_ab.lora_aggregate_ratio": "higher",
}

#: flag when a watched metric is worse than the previous run by more
REGRESSION_PCT = 10.0
#: skip the comparison when the machine's own speed moved more than this
CALIBRATION_DRIFT_PCT = 20.0

#: env knobs that change what the bench measures (part of the fingerprint)
_ENV_KNOBS = (
    "DORA_SEND_COALESCE",
    "DORA_INT8_DECODE",
    "DORA_PIPELINE_DEPTH",
    "DORA_MULTISTEP_K",
    "BENCH_SMALL_MSGS",
    "BENCH_SMALL_RUNS",
    "BENCH_LATENCY_RUNS",
)


def env_fingerprint() -> dict:
    """The comparability key: hardware/interpreter identity + the env
    knobs that change the measured configuration."""
    parts = {
        "platform": sys.platform,
        "machine": platform.machine(),
        "python": platform.python_version(),
        "cpus": os.cpu_count(),
        "env": {k: os.environ[k] for k in _ENV_KNOBS if k in os.environ},
    }
    digest = hashlib.sha256(
        json.dumps(parts, sort_keys=True).encode()
    ).hexdigest()[:12]
    return {"id": digest, **parts}


def ambient_throughput(budget_s: float = 0.2) -> float:
    """MB/s of in-process blake2b over 64 KiB blocks for ``budget_s`` —
    a quick proxy for "how fast is this machine right now"."""
    block = b"\xa5" * 65536
    n = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < budget_s:
        hashlib.blake2b(block).digest()
        n += 1
    elapsed = time.perf_counter() - t0
    return round(n * len(block) / 1e6 / elapsed, 1) if elapsed else 0.0


def _get(record: dict, path: str) -> Any:
    cur: Any = record
    for part in path.split("."):
        if not isinstance(cur, dict):
            return None
        cur = cur.get(part)
    return cur


def _load_last_matching(path: Path, fingerprint_id: str) -> dict | None:
    if not path.exists():
        return None
    last = None
    for line in path.read_text().splitlines():
        try:
            entry = json.loads(line)
        except ValueError:
            continue  # a torn write must not wedge trend tracking
        if entry.get("fingerprint", {}).get("id") == fingerprint_id:
            last = entry
    return last


def compare(
    record: dict, prev_entry: dict, ambient_mb_s: float
) -> tuple[list[dict], str | None]:
    """Watched-metric deltas vs the previous fingerprint-matched entry.

    Returns ``(regressions, note)`` — ``note`` explains a skipped
    comparison (calibration drift)."""
    prev_ambient = prev_entry.get("ambient_mb_s") or 0.0
    if prev_ambient and ambient_mb_s:
        drift = abs(ambient_mb_s - prev_ambient) / prev_ambient * 100.0
        if drift > CALIBRATION_DRIFT_PCT:
            return [], (
                f"ambient throughput moved {drift:.0f}% "
                f"({prev_ambient} -> {ambient_mb_s} MB/s): "
                "machine state changed, comparison skipped"
            )
    regressions = []
    prev_record = prev_entry.get("record", {})
    for path, direction in WATCHED.items():
        cur, prev = _get(record, path), _get(prev_record, path)
        if not isinstance(cur, (int, float)) or not isinstance(
            prev, (int, float)
        ) or not prev:
            continue
        worse_pct = (
            (cur - prev) / prev * 100.0
            if direction == "lower"
            else (prev - cur) / prev * 100.0
        )
        if worse_pct > REGRESSION_PCT:
            regressions.append({
                "metric": path,
                "previous": prev,
                "current": cur,
                "worse_pct": round(worse_pct, 1),
            })
    return regressions, None


def record_run(record: dict, history_path: Path | str) -> dict:
    """Append one bench record to the history file and diff it against
    the previous fingerprint-matched run. Returns the trend summary the
    bench line ships (fingerprint id, calibration, regressions)."""
    path = Path(history_path)
    fp = env_fingerprint()
    ambient = ambient_throughput()
    prev = _load_last_matching(path, fp["id"])
    regressions: list[dict] = []
    note = None
    baseline_ts = None
    if prev is not None:
        baseline_ts = prev.get("ts")
        regressions, note = compare(record, prev, ambient)
    entry = {
        "ts": round(time.time(), 3),
        "fingerprint": fp,
        "ambient_mb_s": ambient,
        "record": record,
    }
    with path.open("a") as f:
        f.write(json.dumps(entry, sort_keys=True) + "\n")
    out: dict[str, Any] = {
        "fingerprint": fp["id"],
        "ambient_mb_s": ambient,
        "baseline_ts": baseline_ts,
        "regressions": regressions,
    }
    if note:
        out["note"] = note
    return out
