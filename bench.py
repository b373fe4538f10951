"""Benchmark entry point (driver-run).

Primary metric — the reference's headline axis (README.md:52 benches
40 MB random-bytes messages vs ROS2): p50 end-to-end latency of a 40 MB
message from one node process to another through the daemon data plane
(shared-memory regions + shmem control channels, zero-copy receive).

Robustness (round 4): the latency is the median of ``RUNS`` independent
dataflow runs (fresh daemon + fresh node processes each), with the
min..max spread reported alongside, and the TCP-loopback baseline is
measured in the same process interleaved between runs — so a noisy
machine shows up as spread and a shifted baseline rather than silently
masquerading as a code regression (this is exactly what made the r3
number unreadable).

Additionally the line carries the north-star serving proof: the
camera → VLM-2B end-to-end FPS through the real daemon (the
BASELINE.md ≥25 FPS axis), measured by ``bench_vlm.bench_e2e`` with the
round-3 best config (int8 decode + pipelined ticks). If no TPU is
attached (or the serving bench fails) this program exits non-zero.

Small-message axis (round 6): msgs/sec and p50/p99 latency for 1 KiB
inline messages through a 3-node chain (src -> relay -> sink), measured
twice — the daemon route (tcp channels, p2p off: every hop pays the
node->daemon->node socket path, the compiled-serde + coalesced-I/O
target) and the p2p route (shmem channels + direct node->node edges).

Prints exactly ONE JSON line (the last line of stdout):
  {"metric": ..., "value": ..., "unit": ..., "vs_baseline": ...,
   "runs": N, "spread_us": [lo, hi], "baseline_us": ...,
   "msgs_per_sec_1kib": {"daemon": ..., "p2p": ...},
   "p50_us_1kib": {...}, "p99_us_1kib": {...},
   "recorder_ab": {"off_msgs_per_sec": ..., "on_msgs_per_sec": ...,
                   "overhead_pct": ...},
   "history_prom_ab": {...}, "alerts_ab": {...}, "trend": {...},
   "e2e_fps": ..., "e2e_vs_north_star": ...}

Every run is also appended to ``BENCH_history.jsonl`` (see
``dora_tpu.tools.bench_trend``) with an environment fingerprint and an
ambient-throughput calibration; >10% regressions vs the previous
fingerprint-matched run are flagged on stderr and in ``trend``.
"""

from __future__ import annotations

import json
import os
import socket
import statistics
import sys
import tempfile
import textwrap
import threading
import time
from pathlib import Path

SIZE = 40 * 1024 * 1024
ROUNDS = 30  # messages per run
RUNS = int(os.environ.get("BENCH_LATENCY_RUNS", "5"))

# Small-message leg (round 6): 1 KiB inline messages through a 3-node
# chain — the 100 Hz-1 kHz traffic shape the 40 MB axis never sees.
# Two phases per run: a burst (msgs/sec, receive-side window) and a
# 500 Hz paced tail (p50/p99 latency without self-inflicted queueing —
# a burst's latency only measures its own queue depth).
MSG_SIZE = 1024
MSG_COUNT = int(os.environ.get("BENCH_SMALL_MSGS", "2000"))
LAT_COUNT = int(os.environ.get("BENCH_SMALL_LAT_MSGS", "300"))
LAT_INTERVAL_S = 0.002
SMALL_RUNS = int(os.environ.get("BENCH_SMALL_RUNS", "3"))


def tcp_loopback_p50_us() -> float:
    """Baseline: 40 MB over a localhost TCP socket (send + full recv)."""
    server = socket.socket()
    server.bind(("127.0.0.1", 0))
    server.listen(1)
    port = server.getsockname()[1]
    payload = b"x" * SIZE
    lat: list[float] = []

    def serve():
        conn, _ = server.accept()
        with conn:
            for _ in range(ROUNDS):
                n = 0
                while n < SIZE:
                    chunk = conn.recv(1 << 20)
                    if not chunk:
                        return
                    n += len(chunk)
                conn.sendall(b"a")  # ack
    thread = threading.Thread(target=serve, daemon=True)
    thread.start()
    client = socket.create_connection(("127.0.0.1", port))
    client.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    with client:
        for _ in range(ROUNDS):
            t0 = time.perf_counter_ns()
            client.sendall(payload)
            client.recv(1)
            lat.append((time.perf_counter_ns() - t0) / 1e3)
    server.close()
    return statistics.median(lat)


def dataflow_p50_us(workdir: Path) -> float:
    """40 MB sender -> receiver through the daemon (shmem transport)."""
    sender = workdir / "bench_sender.py"
    sender.write_text(textwrap.dedent(f"""
        import os
        import time

        from dora_tpu.node import Node

        payload = os.urandom({SIZE})
        sent = 0
        with Node() as node:
            for event in node:
                if event["type"] != "INPUT":
                    continue
                # Zero-producer-copy path: produce the payload directly into
                # the shared region (a real producer writes in place), then
                # publish the region itself.
                sample = node.allocate_sample({SIZE})
                sample.view[:{SIZE}] = payload
                node.send_sample(
                    "data", sample, {SIZE}, {{"t": time.perf_counter_ns()}}
                )
                sent += 1
                if sent >= {ROUNDS}:
                    break
    """))
    receiver = workdir / "bench_receiver.py"
    receiver.write_text(textwrap.dedent(f"""
        import json
        import statistics
        import time

        from dora_tpu.node import Node

        lat = []
        node = Node()
        for event in node:
            if event["type"] != "INPUT":
                continue
            t1 = time.perf_counter_ns()
            assert len(event["value"]) == {SIZE}
            lat.append((t1 - event["metadata"]["t"]) / 1e3)
            if len(lat) >= {ROUNDS}:
                break
        node.close()
        (open("latency.json", "w")
            .write(json.dumps(statistics.median(lat))))
    """))
    spec = {
        "nodes": [
            {
                "id": "bench-sender",
                "path": "bench_sender.py",
                # The timer paces rounds (reference: 10 ms spacing,
                # examples/benchmark/node/src/main.rs).
                "inputs": {"tick": "dora/timer/millis/20"},
                "outputs": ["data"],
            },
            {
                "id": "bench-receiver",
                "path": "bench_receiver.py",
                "inputs": {"data": "bench-sender/data"},
            },
        ],
        "communication": {"local": "shmem"},
    }
    import yaml

    df = workdir / "bench.yml"
    df.write_text(yaml.safe_dump(spec))

    from dora_tpu.daemon import run_dataflow

    result = run_dataflow(df, local_comm="shmem", timeout_s=180)
    if not result.is_ok():
        raise RuntimeError(f"bench dataflow failed: {result.errors()}")
    return json.loads((workdir / "latency.json").read_text())


def small_message_run(
    workdir: Path, route: str, extra_env: dict | None = None
) -> dict:
    """One 1 KiB x MSG_COUNT run through src -> relay -> sink.

    route "daemon": tcp node channels, p2p edges off — every message
    pays the node->daemon->node socket path (the coalescing target).
    route "p2p": shmem channels + direct node->node shmem edges.

    Returns {"msgs_per_sec", "p50_us", "p99_us", "received"} measured at
    the sink (receive-side window; latency is send-stamp to arrival,
    perf_counter_ns is cross-process comparable on Linux).
    """
    src = workdir / "small_src.py"
    src.write_text(textwrap.dedent(f"""
        import time

        from dora_tpu.node import Node

        payload = b"x" * {MSG_SIZE}
        node = Node()
        for event in node:
            if event["type"] == "INPUT":
                break  # first tick: go
        # Phase 0: throughput burst.
        for _ in range({MSG_COUNT}):
            node.send_output(
                "out", payload, {{"t": time.perf_counter_ns(), "p": 0}}
            )
        # Let the chain drain the burst: latency probes must not queue
        # behind phase-0 messages still in flight downstream.
        time.sleep(3.0)
        # Phase 1: paced latency probes (below capacity, so each sample
        # measures the transport, not the probe's own queueing).
        for _ in range({LAT_COUNT}):
            time.sleep({LAT_INTERVAL_S})
            node.send_output(
                "out", payload, {{"t": time.perf_counter_ns(), "p": 1}}
            )
        node.close()
    """))
    relay = workdir / "small_relay.py"
    relay.write_text(textwrap.dedent("""
        from dora_tpu.node import Node

        node = Node()
        for event in node:
            if event["type"] != "INPUT":
                continue
            node.send_output("out", bytes(event["value"]), event["metadata"])
        node.close()
    """))
    sink = workdir / "small_sink.py"
    sink.write_text(textwrap.dedent("""
        import json
        import statistics
        import time

        from dora_tpu.node import Node

        tput_times = []  # phase-0 arrival stamps (throughput window)
        lat = []         # phase-1 per-message latencies, us
        node = Node()
        for event in node:
            if event["type"] != "INPUT":
                continue
            now = time.perf_counter_ns()
            meta = event["metadata"]
            if meta.get("p") == 0:
                tput_times.append(now)
            else:
                lat.append((now - meta["t"]) / 1e3)
        node.close()
        lat.sort()
        elapsed_s = (
            (tput_times[-1] - tput_times[0]) / 1e9
            if len(tput_times) > 1 else float("inf")
        )
        result = {
            "received": len(tput_times) + len(lat),
            "msgs_per_sec": (
                (len(tput_times) - 1) / elapsed_s
                if len(tput_times) > 1 else 0.0
            ),
            "p50_us": statistics.median(lat) if lat else None,
            "p99_us": (
                lat[min(len(lat) - 1, int(len(lat) * 0.99))] if lat else None
            ),
        }
        open("small_msg.json", "w").write(json.dumps(result))
    """))
    # queue_size >= MSG_COUNT: throughput, not the drop-oldest contract,
    # is under test — nothing may be shed mid-run.
    spec = {
        "nodes": [
            {
                "id": "small-src",
                "path": "small_src.py",
                "inputs": {"tick": "dora/timer/millis/100"},
                "outputs": ["out"],
            },
            {
                "id": "small-relay",
                "path": "small_relay.py",
                "inputs": {
                    "data": {
                        "source": "small-src/out",
                        "queue_size": MSG_COUNT + LAT_COUNT,
                    }
                },
                "outputs": ["out"],
            },
            {
                "id": "small-sink",
                "path": "small_sink.py",
                "inputs": {
                    "data": {
                        "source": "small-relay/out",
                        "queue_size": MSG_COUNT + LAT_COUNT,
                    }
                },
            },
        ],
        # The YAML block picks the node-channel transport on old AND new
        # code (both honor it when no explicit local_comm is passed).
        "communication": {"local": "tcp" if route == "daemon" else "shmem"},
    }
    import yaml

    df = workdir / "small.yml"
    df.write_text(yaml.safe_dump(spec))

    from dora_tpu.daemon import run_dataflow

    overrides = {
        # Old code ignores DORA_SEND_COALESCE (harmless): the A/B then
        # measures exactly the code change, same knobs both sides.
        "DORA_P2P": "0" if route == "daemon" else "1",
        "DORA_SEND_COALESCE": os.environ.get("DORA_SEND_COALESCE", "8192"),
    }
    if extra_env:
        overrides.update(extra_env)
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    try:
        result = run_dataflow(df, timeout_s=180)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    if not result.is_ok():
        raise RuntimeError(f"small-message dataflow failed: {result.errors()}")
    data = json.loads((workdir / "small_msg.json").read_text())
    expected = MSG_COUNT + LAT_COUNT
    if data["received"] < expected:
        data["note"] = f"only {data['received']}/{expected} delivered"
    return data


def small_message_leg(route: str) -> dict:
    """Median-of-SMALL_RUNS small-message numbers for one route."""
    runs = []
    for i in range(SMALL_RUNS):
        with tempfile.TemporaryDirectory(prefix="dora-tpu-small-") as tmp:
            runs.append(small_message_run(Path(tmp), route))
        print(
            f"# small {route} run {i + 1}/{SMALL_RUNS}: "
            f"{runs[-1]['msgs_per_sec']:.0f} msg/s, "
            f"p50 {runs[-1]['p50_us']:.0f} us",
            file=sys.stderr,
        )
    rates = sorted(r["msgs_per_sec"] for r in runs)
    return {
        "msgs_per_sec": round(statistics.median(rates), 0),
        "msgs_per_sec_spread": [round(rates[0], 0), round(rates[-1], 0)],
        "p50_us": round(statistics.median(r["p50_us"] for r in runs), 1),
        "p99_us": round(statistics.median(r["p99_us"] for r in runs), 1),
        "received": min(r["received"] for r in runs),
    }


def recorder_ab_leg() -> dict:
    """Flight-recorder A/B on the daemon route: off vs DORA_FLIGHT_RECORDER=1,
    runs interleaved so both sides see the same machine conditions. The
    recorder's hot-path budget is ≤3% on msgs_per_sec (daemon route)."""
    off: list[float] = []
    on: list[float] = []
    for i in range(SMALL_RUNS):
        with tempfile.TemporaryDirectory(prefix="dora-tpu-rec-") as tmp:
            off.append(small_message_run(Path(tmp), "daemon")["msgs_per_sec"])
        with tempfile.TemporaryDirectory(prefix="dora-tpu-rec-") as tmp:
            on.append(
                small_message_run(
                    Path(tmp), "daemon",
                    extra_env={"DORA_FLIGHT_RECORDER": "1"},
                )["msgs_per_sec"]
            )
        print(
            f"# recorder A/B run {i + 1}/{SMALL_RUNS}: "
            f"off {off[-1]:.0f} msg/s, on {on[-1]:.0f} msg/s",
            file=sys.stderr,
        )
    off_m = statistics.median(off)
    on_m = statistics.median(on)
    return {
        "off_msgs_per_sec": round(off_m, 0),
        "on_msgs_per_sec": round(on_m, 0),
        "overhead_pct": (
            round((off_m - on_m) / off_m * 100, 2) if off_m else None
        ),
    }


def tracing_ab_leg() -> dict:
    """Trace-plane A/B on the daemon route: off vs DORA_TRACING=1, runs
    interleaved so both sides see the same machine conditions. Tracing-on
    pays per-message span records end to end (node t_send, daemon
    t_route/t_deliver, receiver t_recv, ring shipping); tracing-off must
    stay within the ≤3% msgs_per_sec budget (single attribute checks)."""
    off: list[float] = []
    on: list[float] = []
    for i in range(SMALL_RUNS):
        with tempfile.TemporaryDirectory(prefix="dora-tpu-trc-") as tmp:
            off.append(small_message_run(Path(tmp), "daemon")["msgs_per_sec"])
        with tempfile.TemporaryDirectory(prefix="dora-tpu-trc-") as tmp:
            on.append(
                small_message_run(
                    Path(tmp), "daemon",
                    extra_env={"DORA_TRACING": "1"},
                )["msgs_per_sec"]
            )
        print(
            f"# tracing A/B run {i + 1}/{SMALL_RUNS}: "
            f"off {off[-1]:.0f} msg/s, on {on[-1]:.0f} msg/s",
            file=sys.stderr,
        )
    off_m = statistics.median(off)
    on_m = statistics.median(on)
    return {
        "off_msgs_per_sec": round(off_m, 0),
        "on_msgs_per_sec": round(on_m, 0),
        "overhead_pct": (
            round((off_m - on_m) / off_m * 100, 2) if off_m else None
        ),
    }



def lockcheck_ab_leg() -> dict:
    """Lock-order detector A/B on the daemon route: DORA_LOCKCHECK=0 vs
    =1, runs interleaved so both sides see the same machine conditions.
    The =0 side is the production default — the tracked_lock factories
    hand back plain threading.Lock objects at construction, so the
    budget for the disabled detector is ≤3% on msgs_per_sec (really:
    noise). The =1 side prices per-acquire order recording + the
    blocking probes, and is reported, not gated (it is a debug mode)."""
    off: list[float] = []
    on: list[float] = []
    for i in range(SMALL_RUNS):
        with tempfile.TemporaryDirectory(prefix="dora-tpu-lck-") as tmp:
            off.append(
                small_message_run(
                    Path(tmp), "daemon",
                    extra_env={"DORA_LOCKCHECK": "0"},
                )["msgs_per_sec"]
            )
        with tempfile.TemporaryDirectory(prefix="dora-tpu-lck-") as tmp:
            on.append(
                small_message_run(
                    Path(tmp), "daemon",
                    extra_env={"DORA_LOCKCHECK": "1",
                               "DORA_LOCKCHECK_REPORT": "0"},
                )["msgs_per_sec"]
            )
        print(
            f"# lockcheck A/B run {i + 1}/{SMALL_RUNS}: "
            f"off {off[-1]:.0f} msg/s, on {on[-1]:.0f} msg/s",
            file=sys.stderr,
        )
    off_m = statistics.median(off)
    on_m = statistics.median(on)
    return {
        "off_msgs_per_sec": round(off_m, 0),
        "on_msgs_per_sec": round(on_m, 0),
        "on_overhead_pct": (
            round((off_m - on_m) / off_m * 100, 2) if off_m else None
        ),
    }


def history_prom_ab_leg() -> dict:
    """Time-series-plane A/B on the daemon route: history sampling off
    (DORA_METRICS_HISTORY_S=0) vs on at an aggressive 0.5 s cadence with
    the coordinator's Prometheus endpoint bound (DORA_PROM_PORT=0 picks
    an ephemeral port), runs interleaved. Each sample is one
    metrics_snapshot + dict diff on the daemon loop — off the per-message
    hot path — so the budget is the observability ≤3% on msgs_per_sec."""
    off: list[float] = []
    on: list[float] = []
    for i in range(SMALL_RUNS):
        with tempfile.TemporaryDirectory(prefix="dora-tpu-hist-") as tmp:
            off.append(
                small_message_run(
                    Path(tmp), "daemon",
                    extra_env={"DORA_METRICS_HISTORY_S": "0"},
                )["msgs_per_sec"]
            )
        with tempfile.TemporaryDirectory(prefix="dora-tpu-hist-") as tmp:
            on.append(
                small_message_run(
                    Path(tmp), "daemon",
                    extra_env={
                        "DORA_METRICS_HISTORY_S": "0.5",
                        "DORA_PROM_PORT": "0",
                    },
                )["msgs_per_sec"]
            )
        print(
            f"# history/prom A/B run {i + 1}/{SMALL_RUNS}: "
            f"off {off[-1]:.0f} msg/s, on {on[-1]:.0f} msg/s",
            file=sys.stderr,
        )
    off_m = statistics.median(off)
    on_m = statistics.median(on)
    return {
        "off_msgs_per_sec": round(off_m, 0),
        "on_msgs_per_sec": round(on_m, 0),
        "overhead_pct": (
            round((off_m - on_m) / off_m * 100, 2) if off_m else None
        ),
    }


def alerts_ab_leg() -> dict:
    """Alerting-plane A/B on the daemon route: history sampling at the
    same aggressive 0.5 s cadence on both sides so the only difference
    is the alert engine (DORA_ALERTS=0 vs =1), runs interleaved. Each
    evaluation is one pass over the default rule pack against the ring's
    newest samples on the daemon loop — off the per-message hot path —
    so the budget is the observability ≤3% on msgs_per_sec."""
    off: list[float] = []
    on: list[float] = []
    for i in range(SMALL_RUNS):
        with tempfile.TemporaryDirectory(prefix="dora-tpu-alrt-") as tmp:
            off.append(
                small_message_run(
                    Path(tmp), "daemon",
                    extra_env={
                        "DORA_METRICS_HISTORY_S": "0.5",
                        "DORA_ALERTS": "0",
                    },
                )["msgs_per_sec"]
            )
        with tempfile.TemporaryDirectory(prefix="dora-tpu-alrt-") as tmp:
            on.append(
                small_message_run(
                    Path(tmp), "daemon",
                    extra_env={
                        "DORA_METRICS_HISTORY_S": "0.5",
                        "DORA_ALERTS": "1",
                    },
                )["msgs_per_sec"]
            )
        print(
            f"# alerts A/B run {i + 1}/{SMALL_RUNS}: "
            f"off {off[-1]:.0f} msg/s, on {on[-1]:.0f} msg/s",
            file=sys.stderr,
        )
    off_m = statistics.median(off)
    on_m = statistics.median(on)
    return {
        "off_msgs_per_sec": round(off_m, 0),
        "on_msgs_per_sec": round(on_m, 0),
        "overhead_pct": (
            round((off_m - on_m) / off_m * 100, 2) if off_m else None
        ),
    }


def serving_multistep_ab() -> dict:
    """K-sweep of the fused multi-step decode window
    (tools/bench_serving --multistep): host round-trips — engine
    dispatches + device->host fetches — per emitted token, and decode
    tok/s, at K in {1, 4, 8, 16} for 4 and 16 streams. The headline is
    ``k8_vs_k1_rt_reduction`` (the ≥4x amortization gate), a host-side
    COUNT and therefore independent of how wall-clock serving numbers
    drift between sessions. Runs in a fresh subprocess so the
    accelerator isn't claimed by the bench parent (same rule as
    serving_fps)."""
    import subprocess
    import sys as _sys

    proc = subprocess.run(
        [
            _sys.executable, "-m", "dora_tpu.tools.bench_serving",
            "--multistep",
        ],
        capture_output=True, text=True, timeout=3600,
        cwd=str(Path(__file__).resolve().parent),
    )
    data = None
    for line in (proc.stdout or "").splitlines():
        try:
            row = json.loads(line)
        except ValueError:
            continue
        if "multistep" in row:
            data = row["multistep"]
    if proc.returncode != 0 or data is None:
        return {
            "k_sweep": None,
            "k8_vs_k1_rt_reduction": None,
            "note": f"subprocess failed: {(proc.stderr or '')[-200:]!r}",
        }
    return data


def serving_trace_ab() -> dict:
    """Serving-span recorder A/B (tools/bench_serving --trace-ab): a
    16-stream paged decode run with lifecycle tracing off vs on, trials
    interleaved. Tracing-on pays per-window s_decode_window spans,
    per-chunk s_prefill_chunk spans, and admission spans into the flight
    ring; the gate is ≤3% wall-clock overhead so the serving timeline
    can stay on in production. Fresh subprocess for the same
    accelerator-claim reason as serving_fps."""
    import subprocess
    import sys as _sys

    proc = subprocess.run(
        [
            _sys.executable, "-m", "dora_tpu.tools.bench_serving",
            "--trace-ab",
        ],
        capture_output=True, text=True, timeout=1800,
        cwd=str(Path(__file__).resolve().parent),
    )
    data = None
    for line in (proc.stdout or "").splitlines():
        try:
            row = json.loads(line)
        except ValueError:
            continue
        if "trace_ab" in row:
            data = row["trace_ab"]
    if proc.returncode != 0 or data is None:
        return {
            "off_wall_s": None,
            "on_wall_s": None,
            "overhead_pct": None,
            "note": f"subprocess failed: {(proc.stderr or '')[-200:]!r}",
        }
    return data


def serving_profiling_ab() -> dict:
    """Device-monitor A/B (tools/bench_serving --profiling-ab): the
    round-16 utilization plane (window time attribution via
    block_until_ready, FLOPs ledger, dev-phase spans) on vs off at 16
    streams on the stub engine, trials interleaved. Gate: <= 3%
    wall-clock overhead so the plane can stay default-on. Fresh
    subprocess for the same accelerator-claim reason as
    serving_fps."""
    import subprocess
    import sys as _sys

    proc = subprocess.run(
        [
            _sys.executable, "-m", "dora_tpu.tools.bench_serving",
            "--profiling-ab",
        ],
        capture_output=True, text=True, timeout=1800,
        cwd=str(Path(__file__).resolve().parent),
    )
    data = None
    for line in (proc.stdout or "").splitlines():
        try:
            row = json.loads(line)
        except ValueError:
            continue
        if "profiling_ab" in row:
            data = row["profiling_ab"]
    if proc.returncode != 0 or data is None:
        return {
            "monitor_off_wall_s": None,
            "monitor_on_wall_s": None,
            "overhead_pct": None,
            "note": f"subprocess failed: {(proc.stderr or '')[-200:]!r}",
        }
    return data


def serving_fleet_digest_ab() -> dict:
    """Fleet-digest A/B (tools/bench_serving --fleet-digest-ab): the
    engine-state exporter publishing at a 0.5 s cadence (4x the shipped
    default) vs off on the 16-stream stub serving leg, interleaved
    paired trials. Gate: <= 3% wall-clock overhead so the fleet plane
    can stay default-on. Fresh subprocess for the same
    accelerator-claim reason as serving_fps."""
    import subprocess
    import sys as _sys

    proc = subprocess.run(
        [
            _sys.executable, "-m", "dora_tpu.tools.bench_serving",
            "--fleet-digest-ab",
        ],
        capture_output=True, text=True, timeout=1800,
        cwd=str(Path(__file__).resolve().parent),
    )
    data = None
    for line in (proc.stdout or "").splitlines():
        try:
            row = json.loads(line)
        except ValueError:
            continue
        if "fleet_digest_ab" in row:
            data = row["fleet_digest_ab"]
    if proc.returncode != 0 or data is None:
        return {
            "digest_off_wall_s": None,
            "digest_on_wall_s": None,
            "overhead_pct": None,
            "note": f"subprocess failed: {(proc.stderr or '')[-200:]!r}",
        }
    return data


def serving_spec_ab() -> dict:
    """Speculative-decoding sweep (tools/bench_serving --spec-ab):
    tokens per dispatch and draft acceptance at spec_k in {0, 2, 4} x
    window K in {1, 8}, on the stub engine's repetitive (best-case) and
    random (worst-case) token rules. Headlines:
    ``rep_k4_vs_k0_tpd_at_k8`` (the >=1.5x gate — speculation must
    multiply what the K-window already amortizes) and
    ``rand_k4_vs_k0_tpd_at_k8`` (the <=10%-regression bound when
    nothing accepts). Fresh subprocess for the same accelerator-claim
    reason as serving_fps."""
    import subprocess
    import sys as _sys

    proc = subprocess.run(
        [
            _sys.executable, "-m", "dora_tpu.tools.bench_serving",
            "--spec-ab",
        ],
        capture_output=True, text=True, timeout=1800,
        cwd=str(Path(__file__).resolve().parent),
    )
    data = None
    for line in (proc.stdout or "").splitlines():
        try:
            row = json.loads(line)
        except ValueError:
            continue
        if "spec_ab" in row:
            data = row["spec_ab"]
    if proc.returncode != 0 or data is None:
        return {
            "legs": None,
            "rep_k4_vs_k0_tpd_at_k8": None,
            "rand_k4_vs_k0_tpd_at_k8": None,
            "note": f"subprocess failed: {(proc.stderr or '')[-200:]!r}",
        }
    return data


def serving_qos_soak() -> dict:
    """Traffic-shaping soak (tools/bench_serving --qos-soak): open-loop
    Poisson mixed-class overload through the real serve() admission
    path on the stub engine, QoS on vs off over the identical arrival
    trace. Headline: ``interactive_p99_on_vs_off`` < 1.0 — shaping
    must buy the interactive class TTFT under overload; shed rate and
    preempt/resume counts ride along. Fresh subprocess for the same
    accelerator-claim reason as serving_fps."""
    import subprocess
    import sys as _sys

    proc = subprocess.run(
        [
            _sys.executable, "-m", "dora_tpu.tools.bench_serving",
            "--qos-soak",
        ],
        capture_output=True, text=True, timeout=1800,
        cwd=str(Path(__file__).resolve().parent),
    )
    data = None
    for line in (proc.stdout or "").splitlines():
        try:
            row = json.loads(line)
        except ValueError:
            continue
        if "qos_soak" in row:
            data = row["qos_soak"]
    if proc.returncode != 0 or data is None:
        return {
            "qos_on": None,
            "qos_off": None,
            "interactive_p99_on_vs_off": None,
            "note": f"subprocess failed: {(proc.stderr or '')[-200:]!r}",
        }
    return data


def serving_prefix_ab() -> dict:
    """Shared-prefix cache A/B (tools/bench_serving --prefix-ab): a
    Zipf-popular template workload replayed open-loop on the stub paged
    engine, prefix cache on vs off over the identical arrival trace.
    Headline: ``hit_p50_on_vs_off`` <= 0.5 — a cache hit must at least
    halve hit-request TTFT vs the same requests uncached (the serving
    default-on gate); hit rate, prefill-chunk deltas, and eviction
    counts ride along. Fresh subprocess for the same accelerator-claim
    reason as serving_fps."""
    import subprocess
    import sys as _sys

    proc = subprocess.run(
        [
            _sys.executable, "-m", "dora_tpu.tools.bench_serving",
            "--prefix-ab",
        ],
        capture_output=True, text=True, timeout=1800,
        cwd=str(Path(__file__).resolve().parent),
    )
    data = None
    for line in (proc.stdout or "").splitlines():
        try:
            row = json.loads(line)
        except ValueError:
            continue
        if "prefix_ab" in row:
            data = row["prefix_ab"]
    if proc.returncode != 0 or data is None:
        return {
            "cache_on": None,
            "cache_off": None,
            "hit_p50_on_vs_off": None,
            "note": f"subprocess failed: {(proc.stderr or '')[-200:]!r}",
        }
    return data


def serving_quant_ab() -> dict:
    """Quantized-serving A/B (tools/bench_serving --quant-ab): fp-KV vs
    int8-KV vs int8-KV + int4-weight engines on the identical prompt
    set — tokens/s, greedy token agreement vs the fp leg, and the
    capacity leg counting concurrent admissions into the same pool
    byte budget. Headline: ``int8_capacity_ratio`` >= 1.8 (concurrent
    streams in the fp pool's HBM footprint). Fresh subprocess for the
    same accelerator-claim reason as serving_fps."""
    import subprocess
    import sys as _sys

    proc = subprocess.run(
        [
            _sys.executable, "-m", "dora_tpu.tools.bench_serving",
            "--quant-ab",
        ],
        capture_output=True, text=True, timeout=1800,
        cwd=str(Path(__file__).resolve().parent),
    )
    data = None
    for line in (proc.stdout or "").splitlines():
        try:
            row = json.loads(line)
        except ValueError:
            continue
        if "quant_ab" in row:
            data = row["quant_ab"]
    if proc.returncode != 0 or data is None:
        return {
            "greedy_agreement_vs_fp": None,
            "capacity": None,
            "note": f"subprocess failed: {(proc.stderr or '')[-200:]!r}",
        }
    return data


def serving_lora_ab() -> dict:
    """Multi-tenant LoRA A/B (tools/bench_serving --lora-ab): aggregate
    tok/s of one paged engine serving N adapter tenants vs N separate
    engines splitting the same HBM budget, plus the adapter-churn leg
    counting steady-state compiles. Headline: ``lora_aggregate_ratio``
    >= 1.5 and ``churn.steady_state_compiles`` == 0. Fresh subprocess
    for the same accelerator-claim reason as serving_fps."""
    import subprocess
    import sys as _sys

    proc = subprocess.run(
        [
            _sys.executable, "-m", "dora_tpu.tools.bench_serving",
            "--lora-ab",
        ],
        capture_output=True, text=True, timeout=1800,
        cwd=str(Path(__file__).resolve().parent),
    )
    data = None
    for line in (proc.stdout or "").splitlines():
        try:
            row = json.loads(line)
        except ValueError:
            continue
        if "lora_ab" in row:
            data = row["lora_ab"]
    if proc.returncode != 0 or data is None:
        raise RuntimeError(
            f"--lora-ab leg failed (exit {proc.returncode}): "
            f"{(proc.stderr or '')[-400:]}"
        )
    return data


def serving_fps() -> dict:
    """North-star axis: camera -> VLM-2B -> sink FPS through the daemon.

    Round-3 best-known config: int8 decode weights + pipelined async
    ticks, 4 new tokens per frame. This process never touches JAX (one
    process per chip: the VLM node of the dataflow owns the device), so
    there is no backend probe — a leg that finds no chip fails in the
    node (dora_tpu/backend.py) and fails this program with it.
    """
    import subprocess
    import sys as _sys

    # The camera stream must outlive the model's jit compile by enough
    # to reach steady state: 6000 frames at the 20 ms tick is a 2-minute
    # stream (the r3 methodology). 400 frames ends during compile and
    # measures a meaningless burst of flushed tail frames — exactly what
    # the validity floor rejects.
    #
    # The whole leg runs as a FRESH `bench_vlm.py e2e` subprocess: the
    # same measurement in-process after the latency phase read 24 FPS
    # where an isolated run read 36-42 — leftover daemon/baseline state
    # in this process taxes the serving pipeline by ~40%.
    env = dict(os.environ)
    env.setdefault("DORA_INT8_DECODE", "1")
    env.setdefault("DORA_PIPELINE_DEPTH", "8")
    # DORA_FETCH_EVERY (the round-5 device-side output ring) is NOT
    # defaulted here: a same-session A/B measured the ring at 22.1 FPS
    # mean vs 25.4 without (peak window 39.9 vs 32.3) — a late group
    # delays N frames at once, dragging the mean. The ring stays an
    # opt-in for fetch-latency-bound deployments.
    env.setdefault("BENCH_MAX_NEW", "4")
    env.setdefault("BENCH_FRAMES", "6000")
    proc = subprocess.run(
        [_sys.executable,
         str(Path(__file__).resolve().parent / "bench_vlm.py"), "e2e"],
        env=env, capture_output=True, text=True, timeout=1800,
    )
    data = None
    for line in (proc.stdout or "").splitlines():
        try:
            row = json.loads(line)
        except ValueError:
            continue
        if "end-to-end FPS" in str(row.get("metric", "")):
            data = row
    if proc.returncode != 0 or data is None:
        raise RuntimeError(
            f"serving leg failed (exit {proc.returncode}): "
            f"{(proc.stderr or '')[-400:]}"
        )
    measured = data.get("measured_outputs") or 0
    if measured < 30:
        return {
            "fps": None,
            "note": (
                f"invalid: only {measured} steady-state outputs — stream "
                "shorter than model compile; raise BENCH_FRAMES"
            ),
        }
    return {
        "fps": data["value"],
        "note": "camera->vlm-2b, 4 tok/frame, int8+pipeline-depth-8",
        "outputs": measured,
        "p50_gap_ms": round(data.get("p50_gap_ms", 0.0), 1),
    }


def main() -> int:
    sys.path.insert(0, str(Path(__file__).resolve().parent))

    # Interleave dataflow runs and baseline runs so both see the same
    # machine conditions; medians of each side make the ratio robust.
    # A failing run reports as nulls + note (same contract as the other
    # legs): environments without working native shmem must still emit
    # the small-message and serving axes.
    ours_runs: list[float] = []
    base_runs: list[float] = []
    headline_note = None
    for i in range(RUNS):
        try:
            with tempfile.TemporaryDirectory(prefix="dora-tpu-bench-") as tmp:
                ours_runs.append(dataflow_p50_us(Path(tmp)))
        except Exception as exc:
            headline_note = f"40MB leg failed: {exc!r}"[:200]
            print(f"# run {i + 1}/{RUNS}: {headline_note}", file=sys.stderr)
            break
        base_runs.append(tcp_loopback_p50_us())
        print(f"# run {i + 1}/{RUNS}: ours {ours_runs[-1]:.1f} us, "
              f"baseline {base_runs[-1]:.1f} us", file=sys.stderr)
    ours = statistics.median(ours_runs) if ours_runs else None
    baseline = statistics.median(base_runs) if base_runs else None

    # Small-message axis: both routes; a failure reports as nulls + note
    # rather than sinking the headline metric.
    small: dict = {}
    for route in ("daemon", "p2p"):
        try:
            small[route] = small_message_leg(route)
        except Exception as exc:
            small[route] = {
                "msgs_per_sec": None,
                "p50_us": None,
                "p99_us": None,
                "note": f"failed: {exc!r}"[:200],
            }

    try:
        recorder_ab = recorder_ab_leg()
    except Exception as exc:
        recorder_ab = {
            "off_msgs_per_sec": None,
            "on_msgs_per_sec": None,
            "overhead_pct": None,
            "note": f"failed: {exc!r}"[:200],
        }

    try:
        tracing_ab = tracing_ab_leg()
    except Exception as exc:
        tracing_ab = {
            "off_msgs_per_sec": None,
            "on_msgs_per_sec": None,
            "overhead_pct": None,
            "note": f"failed: {exc!r}"[:200],
        }

    try:
        lockcheck_ab = lockcheck_ab_leg()
    except Exception as exc:
        lockcheck_ab = {
            "off_msgs_per_sec": None,
            "on_msgs_per_sec": None,
            "on_overhead_pct": None,
            "note": f"failed: {exc!r}"[:200],
        }

    try:
        history_prom_ab = history_prom_ab_leg()
    except Exception as exc:
        history_prom_ab = {
            "off_msgs_per_sec": None,
            "on_msgs_per_sec": None,
            "overhead_pct": None,
            "note": f"failed: {exc!r}"[:200],
        }

    try:
        alerts_ab = alerts_ab_leg()
    except Exception as exc:
        alerts_ab = {
            "off_msgs_per_sec": None,
            "on_msgs_per_sec": None,
            "overhead_pct": None,
            "note": f"failed: {exc!r}"[:200],
        }

    try:
        multistep_ab = serving_multistep_ab()
    except Exception as exc:
        multistep_ab = {
            "k_sweep": None,
            "k8_vs_k1_rt_reduction": None,
            "note": f"failed: {exc!r}"[:200],
        }

    try:
        trace_ab = serving_trace_ab()
    except Exception as exc:
        trace_ab = {
            "off_wall_s": None,
            "on_wall_s": None,
            "overhead_pct": None,
            "note": f"failed: {exc!r}"[:200],
        }

    try:
        spec_ab = serving_spec_ab()
    except Exception as exc:
        spec_ab = {
            "legs": None,
            "rep_k4_vs_k0_tpd_at_k8": None,
            "rand_k4_vs_k0_tpd_at_k8": None,
            "note": f"failed: {exc!r}"[:200],
        }

    try:
        profiling_ab = serving_profiling_ab()
    except Exception as exc:
        profiling_ab = {
            "monitor_off_wall_s": None,
            "monitor_on_wall_s": None,
            "overhead_pct": None,
            "note": f"failed: {exc!r}"[:200],
        }

    try:
        fleet_digest_ab = serving_fleet_digest_ab()
    except Exception as exc:
        fleet_digest_ab = {
            "digest_off_wall_s": None,
            "digest_on_wall_s": None,
            "overhead_pct": None,
            "note": f"failed: {exc!r}"[:200],
        }

    try:
        qos_soak = serving_qos_soak()
    except Exception as exc:
        qos_soak = {
            "qos_on": None,
            "qos_off": None,
            "interactive_p99_on_vs_off": None,
            "note": f"failed: {exc!r}"[:200],
        }

    try:
        prefix_ab = serving_prefix_ab()
    except Exception as exc:
        prefix_ab = {
            "cache_on": None,
            "cache_off": None,
            "hit_p50_on_vs_off": None,
            "note": f"failed: {exc!r}"[:200],
        }

    try:
        quant_ab = serving_quant_ab()
    except Exception as exc:
        quant_ab = {
            "greedy_agreement_vs_fp": None,
            "capacity": None,
            "note": f"failed: {exc!r}"[:200],
        }

    lora_ab = serving_lora_ab()  # a failed leg fails the program

    # A serving leg that fails — no chip, a node that died — fails this
    # program: it exits non-zero instead of printing a null and a note.
    e2e = serving_fps()

    record = {
        "metric": "40MB inter-node message p50 latency",
        "value": None if ours is None else round(ours, 1),
        "unit": "us",
        "vs_baseline": (
            None if ours is None or baseline is None
            else round(baseline / ours, 2)
        ),
        "runs": RUNS,
        "spread_us": (
            None if not ours_runs
            else [round(min(ours_runs), 1), round(max(ours_runs), 1)]
        ),
        "baseline_us": None if baseline is None else round(baseline, 1),
        "baseline_spread_us": (
            None if not base_runs
            else [round(min(base_runs), 1), round(max(base_runs), 1)]
        ),
        "headline_note": headline_note,
        "msgs_per_sec_1kib": {
            route: small[route]["msgs_per_sec"] for route in small
        },
        "p50_us_1kib": {route: small[route]["p50_us"] for route in small},
        "p99_us_1kib": {route: small[route]["p99_us"] for route in small},
        "small_msg_detail": small,
        "recorder_ab": recorder_ab,
        "tracing_ab": tracing_ab,
        "lockcheck_ab": lockcheck_ab,
        "history_prom_ab": history_prom_ab,
        "alerts_ab": alerts_ab,
        "serving_multistep_ab": multistep_ab,
        "serving_trace_ab": trace_ab,
        "serving_spec_ab": spec_ab,
        "serving_profiling_ab": profiling_ab,
        "fleet_digest_ab": fleet_digest_ab,
        "serving_qos_soak": qos_soak,
        "serving_prefix_ab": prefix_ab,
        "serving_quant_ab": quant_ab,
        "serving_lora_ab": lora_ab,
        "e2e_fps": None if e2e["fps"] is None else round(e2e["fps"], 1),
        "e2e_vs_north_star": (
            None if e2e["fps"] is None else round(e2e["fps"] / 25.0, 2)
        ),
        "e2e_p50_gap_ms": e2e.get("p50_gap_ms"),
        "e2e_note": e2e["note"],
    }
    # Trend tracking: append this run to BENCH_history.jsonl and flag
    # >10% regressions vs the previous fingerprint-matched run (skipped
    # when the machine's own calibration moved).
    try:
        from dora_tpu.tools import bench_trend

        record["trend"] = bench_trend.record_run(
            record, Path(__file__).resolve().parent / "BENCH_history.jsonl"
        )
        for reg in record["trend"].get("regressions", []):
            print(
                f"# REGRESSION {reg['metric']}: {reg['previous']} -> "
                f"{reg['current']} ({reg['worse_pct']}% worse)",
                file=sys.stderr,
            )
    except Exception as exc:  # trend tracking must never sink the bench
        record["trend"] = {"note": f"trend tracking failed: {exc!r}"[:200]}
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(main())
