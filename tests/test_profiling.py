"""Device utilization plane (round 16): analytic FLOPs model,
DeviceMonitor fallback behavior, window-time attribution counters and
spans, the metrics -> history -> prom -> CLI surfaces, and the
control-plane StartProfile round trip on the stub engine.

Everything here runs on CPU: the FLOPs model is config arithmetic, the
stub engine feeds synthetic per-token FLOPs, and deep capture degrades
to a synthetic artifact when the backend has no profiler plugin — the
acceptance contract that tier-1 exercises the whole plane without a
TPU.
"""

from __future__ import annotations

import asyncio
import json
import os
import textwrap

import pytest

from dora_tpu import profiling

# ---------------------------------------------------------------------------
# analytic FLOPs model vs hand arithmetic
# ---------------------------------------------------------------------------

#: the tiny test config used across these tests
_CFG = dict(dim=8, layers=2, heads=2, kv_heads=1, ffn=16, vocab=32)


def test_flops_per_token_matches_hand_arithmetic():
    # Hand reference, spelled out term by term (head_dim = 8/2 = 4):
    #   q+o projections: 2 * (2 * 8 * 8)          = 256
    #   k+v projections: 2 * (2 * 8 * 1 * 4)      = 128
    #   SwiGLU 3 matmuls: 3 * (2 * 8 * 16)        = 768
    #   per layer                                  = 1152, x2 layers = 2304
    #   lm_head: 2 * 8 * 32                        = 512
    assert profiling.flops_per_token(**_CFG) == 2304 + 512 == 2816


def test_flops_per_token_config_object():
    class Cfg:
        dim, layers, heads, kv_heads, ffn, vocab = 8, 2, 2, 1, 16, 32

    assert profiling.flops_per_token_config(Cfg()) == 2816


@pytest.mark.parametrize("k", [1, 8])
@pytest.mark.parametrize("spec_k", [0, 2])
def test_window_flops_across_k_and_spec_k(k, spec_k):
    # A fused window runs K ticks per active stream, each tick
    # forwarding spec_k + 1 positions (draft + verify tail).
    fpt = profiling.flops_per_token(**_CFG)
    got = profiling.window_flops(
        flops_per_token=fpt, active=3, k=k, spec_k=spec_k
    )
    assert got == 3 * k * (spec_k + 1) * 2816


# ---------------------------------------------------------------------------
# DeviceMonitor: every memory_stats failure mode degrades to None
# ---------------------------------------------------------------------------


class _NoStatsDevice:
    pass


class _RaisingDevice:
    def memory_stats(self):
        raise NotImplementedError("no allocator stats on this backend")


class _NoneDevice:
    def memory_stats(self):
        return None


class _EmptyDevice:
    def memory_stats(self):
        return {}


class _FullDevice:
    def memory_stats(self):
        return {
            "bytes_in_use": 100,
            "bytes_limit": 1000,
            "peak_bytes_in_use": 500,
        }


class _ReservableDevice:
    def memory_stats(self):
        # Older plugins spell the limit differently.
        return {"bytes_in_use": 7, "bytes_reservable_limit": 70}


@pytest.mark.parametrize(
    "device", [_NoStatsDevice(), _RaisingDevice(), _NoneDevice(),
               _EmptyDevice()],
    ids=["no-method", "raises", "returns-none", "empty-dict"],
)
def test_device_monitor_absent_stats_degrade_to_none(device):
    mem = profiling.DeviceMonitor(device).memory()
    assert mem == {"used": None, "limit": None, "peak": None}


def test_device_monitor_maps_allocator_stats():
    mem = profiling.DeviceMonitor(_FullDevice()).memory()
    assert mem == {"used": 100, "limit": 1000, "peak": 500}
    mem = profiling.DeviceMonitor(_ReservableDevice()).memory()
    assert mem["used"] == 7
    assert mem["limit"] == 70
    assert mem["peak"] is None


def test_detect_peak_flops(monkeypatch):
    monkeypatch.setenv("DORA_DEVICE_PEAK_FLOPS", "123.5e9")
    assert profiling.detect_peak_flops() == 123.5e9
    monkeypatch.delenv("DORA_DEVICE_PEAK_FLOPS")

    class _Kind:
        def __init__(self, kind):
            self.device_kind = kind

    assert profiling.detect_peak_flops(_Kind("TPU v5e")) == 197e12
    assert profiling.detect_peak_flops(_Kind("TPU v4")) == 275e12
    # Unknown kind: 0.0 so MFU renders as a dash, never a fabrication.
    assert profiling.detect_peak_flops(_Kind("mystery accelerator")) == 0.0


def test_monitor_enabled_gate(monkeypatch):
    monkeypatch.delenv("DORA_DEVICE_MONITOR", raising=False)
    assert profiling.monitor_enabled()  # default on
    for off in ("0", "false", ""):
        monkeypatch.setenv("DORA_DEVICE_MONITOR", off)
        assert not profiling.monitor_enabled()
    monkeypatch.setenv("DORA_DEVICE_MONITOR", "1")
    assert profiling.monitor_enabled()


# ---------------------------------------------------------------------------
# engine attribution: the stub engine accumulates the three-way split
# and the FLOPs ledger, so the whole plane is exercised on CPU
# ---------------------------------------------------------------------------


def test_stub_engine_accumulates_attribution_and_flops(monkeypatch):
    monkeypatch.setenv("DORA_DEVICE_MONITOR", "1")
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    engine = make_stub_paged_engine(
        max_slots=2, max_seq=64, page_size=8, chunk=8, window=4
    )
    assert engine.device_monitor
    assert engine.flops_per_token > 0
    assert engine.device_peak_flops > 0
    engine.submit("a", [3, 4, 5], 8)
    engine.submit("b", [6, 7], 8)
    emitted = 2  # submit returns the first token of each stream
    for _ in range(12):
        emitted += len(engine.step())
    assert emitted >= 2
    # The three-way wall split accumulated on the dispatch path...
    assert engine.host_dispatch_ns > 0
    assert engine.device_compute_ns > 0
    assert engine.device_fetch_ns > 0
    # ...and the ledger: dispatched counts full windows (frozen rows
    # included), useful counts emitted tokens only, so useful never
    # exceeds dispatched.
    assert engine.dispatched_flops > 0
    assert 0 < engine.useful_flops <= engine.dispatched_flops
    assert engine.useful_flops % engine.flops_per_token == 0


def test_stub_engine_monitor_off_strips_the_hooks(monkeypatch):
    monkeypatch.setenv("DORA_DEVICE_MONITOR", "0")
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    engine = make_stub_paged_engine(
        max_slots=1, max_seq=32, page_size=8, chunk=8, window=4
    )
    assert not engine.device_monitor
    engine.submit("a", [3, 4], 6)
    for _ in range(8):
        engine.step()
    assert engine.device_compute_ns == 0
    assert engine.dispatched_flops == 0
    assert engine.useful_flops == 0


def test_serving_metrics_snapshot_carries_device_fields():
    from dora_tpu.metrics import ServingMetrics

    s = ServingMetrics(engine="paged").snapshot()
    for name in ("device_compute_ns", "host_dispatch_ns",
                 "device_fetch_ns", "dispatched_flops", "useful_flops"):
        assert s[name] == 0
    for name in ("mfu", "device_busy_fraction", "hbm_used_bytes",
                 "hbm_limit_bytes", "hbm_peak_bytes"):
        assert name in s and s[name] is None


# ---------------------------------------------------------------------------
# history plane: presence-gated gauges, derived util block
# ---------------------------------------------------------------------------


def _serving_snap(**extra) -> dict:
    base = {"engine": "paged", "decode_tokens": 10, "requests": 1}
    base.update(extra)
    return {"serving": {"llm": base}}


def test_flatten_gates_device_gauges_on_presence():
    from dora_tpu.metrics_history import flatten_snapshot

    counters, gauges, _ = flatten_snapshot(
        _serving_snap(device_compute_ns=5, mfu=None, hbm_used_bytes=None)
    )
    # Counters always flatten (0 when absent) — they delta-encode fine.
    assert counters["srv:llm:device_compute_ns"] == 5
    assert counters["srv:llm:useful_flops"] == 0
    # None gauges are NOT recorded: history series must never fabricate
    # a zero-MFU sample out of "unknown".
    assert "srv:llm:mfu" not in gauges
    assert "srv:llm:hbm_used_bytes" not in gauges
    counters, gauges, _ = flatten_snapshot(_serving_snap(mfu=0.37))
    assert gauges["srv:llm:mfu"] == 0.37


def test_derive_util_latest_per_node():
    from dora_tpu.metrics_history import derive_util

    samples = [
        {"gauges": {"srv:llm:mfu": 0.2, "srv:llm:hbm_used_bytes": 100,
                    "srv:asr:mfu": 0.5}},
        {"gauges": {"srv:llm:mfu": 0.4,
                    # qos_depth keys share the srv: prefix; the split
                    # must not misfile them into the util block
                    "srv:llm:qos_depth:interactive": 3}},
    ]
    util = derive_util(samples)
    assert util["llm"]["mfu"] == 0.4  # latest wins
    assert util["llm"]["hbm_used_bytes"] == 100  # falls back to older
    assert util["asr"]["mfu"] == 0.5
    assert "qos_depth:interactive" not in util["llm"]
    # Pre-round-16 histories (no device gauges at all) derive empty.
    assert derive_util([{"gauges": {"srv:llm:used_pages": 4}}]) == {}


def test_merge_history_ships_util_block():
    from dora_tpu.metrics_history import merge_history_snapshots

    merged = merge_history_snapshots([
        {"interval_s": 5.0, "samples": [
            {"t_ns": 1, "hlc_ns": 1, "counters": {},
             "gauges": {"srv:llm:mfu": 0.3}, "hist": {}},
        ]},
    ])
    assert merged["util"] == {"llm": {"mfu": 0.3}}


# ---------------------------------------------------------------------------
# prom exposition: new families render and lint clean
# ---------------------------------------------------------------------------


def test_prom_covers_device_families():
    from dora_tpu import prom

    # self_check renders the synthetic cluster (which carries the
    # device block) through the real exposition path and lints it.
    assert prom.self_check() == []
    snap = _serving_snap(
        device_compute_ns=900, host_dispatch_ns=80, device_fetch_ns=20,
        useful_flops=4096, dispatched_flops=16384, mfu=0.41,
        device_busy_fraction=0.9, hbm_used_bytes=12 << 30,
        hbm_limit_bytes=16 << 30, hbm_peak_bytes=13 << 30,
    )
    text = prom.render_exposition({"flow": snap})
    assert prom.validate_exposition(text) == []
    assert 'dora_tpu_mfu{dataflow="flow",node="llm"} 0.41' in text
    assert (
        'dora_tpu_device_compute_ns_total{dataflow="flow",node="llm"} 900'
        in text
    )
    assert (
        'dora_tpu_device_dispatched_flops_total'
        '{dataflow="flow",node="llm"} 16384' in text
    )
    # Old snapshots without the fields still render (gauges as 0 — prom
    # has no "absent"; the dash rendering is the CLIs' job).
    text = prom.render_exposition({"flow": _serving_snap()})
    assert prom.validate_exposition(text) == []


def test_tracing_self_check_covers_dev_spans():
    from dora_tpu import tracing

    # the three s_dev_* kinds became the loop's phases: chunk_launch /
    # window_launch, first_token_wait / window_wait hold their intervals
    assert tracing.self_check() == []
    assert "s_loop_phase" in tracing.SERVING_SPAN_KINDS
    assert not [k for k in tracing.SERVING_SPAN_KINDS if k.startswith("s_dev_")]


# ---------------------------------------------------------------------------
# CLI rendering: UTIL tables, dash backward-compat, counter-reset rates
# ---------------------------------------------------------------------------


def test_metrics_view_renders_util_table_and_sparkline():
    from dora_tpu.cli.metrics_view import render_metrics

    snap = _serving_snap(
        mfu=0.415, device_busy_fraction=0.9, hbm_used_bytes=12 << 30,
        hbm_limit_bytes=16 << 30, hbm_peak_bytes=13 << 30,
        device_compute_ns=900_000_000, host_dispatch_ns=80_000_000,
        device_fetch_ns=20_000_000,
    )
    out = render_metrics("u", snap, history=[snap])
    assert "UTIL" in out
    assert "41.5%" in out  # mfu
    assert "90%" in out  # busy
    assert "12.0GiB/16.0GiB" in out
    assert "mfu llm [" in out  # sparkline line


def test_metrics_view_old_snapshot_renders_no_util_table():
    # PR-5 contract: snapshots recorded before round 16 carry none of
    # the device keys — the UTIL table must not appear, nothing crashes.
    from dora_tpu.cli.metrics_view import render_metrics

    out = render_metrics("u", _serving_snap())
    assert "SERVING" in out
    assert "UTIL" not in out


def test_metrics_view_unknown_gauges_render_dashes():
    # Monitor on but CPU backend: counters real, HBM/MFU unknown (None).
    from dora_tpu.cli.metrics_view import render_metrics

    snap = _serving_snap(
        mfu=None, device_busy_fraction=None, hbm_used_bytes=None,
        hbm_limit_bytes=None, hbm_peak_bytes=None,
        device_compute_ns=1_000_000, host_dispatch_ns=2_000_000,
        device_fetch_ns=3_000_000,
    )
    out = render_metrics("u", snap)
    util_line = next(
        line for line in out.splitlines() if line.startswith("llm ")
        and "ms" in line
    )
    assert "-" in util_line


def test_top_view_util_panel_and_backward_compat():
    from dora_tpu.cli.top_view import render_top

    snap = {"serving": {"llm": {
        "engine": "paged", "decode_tokens": 5, "mfu": 0.25,
        "device_busy_fraction": 0.5, "hbm_used_bytes": 1 << 30,
        "hbm_limit_bytes": 2 << 30, "hbm_peak_bytes": 1 << 30,
    }}}
    history = {"samples": [], "rates": {}, "percentiles": {},
               "util": {"llm": {"mfu": 0.25}}}
    out = render_top("u", snap, history)
    assert "UTIL" in out
    assert "25.0%" in out
    # Old snapshot + old history (no util block, no device keys): the
    # panel drops out entirely instead of fabricating zeros.
    out = render_top(
        "u", {"serving": {"llm": {"engine": "paged"}}},
        {"samples": [], "rates": {}, "percentiles": {}},
    )
    assert "UTIL" not in out


def test_rate_counter_reset_rates_fresh_value():
    # A restored engine re-reports counters from zero: the negative
    # delta means "cur IS the progress since reset" (mirrors the
    # history ring's delta decoder); the old "-" blanked a full tick.
    from dora_tpu.cli.metrics_view import _rate

    assert _rate(150, 100, 2.0) == "25.0"
    assert _rate(5, 100, 1.0) == "5.0"  # reset: rate the fresh value
    assert _rate(0, 100, 1.0) == "0.0"


def test_watch_rates_survive_engine_restore():
    # End-to-end through render_metrics: the TOK/S cell after a restore
    # (cur < prev) shows the fresh rate, not a dash.
    from dora_tpu.cli.metrics_view import render_metrics

    prev = _serving_snap(decode_tokens=1000)
    cur = _serving_snap(decode_tokens=40)
    out = render_metrics("u", cur, prev=prev, interval=2.0)
    row = next(
        line for line in out.splitlines() if line.startswith("llm ")
    )
    assert "20.0" in row  # 40 / 2.0s


# ---------------------------------------------------------------------------
# deep capture: artifact contract
# ---------------------------------------------------------------------------


def test_stop_capture_synthetic_artifact_on_start_failure(tmp_path):
    out_dir = str(tmp_path / "cap")
    artifact = profiling.stop_capture(out_dir, "RuntimeError: no plugin")
    assert os.path.exists(artifact)
    marker = json.loads(open(artifact).read())
    assert marker["synthetic"] is True
    assert "no plugin" in marker["reason"]


def test_start_stop_capture_roundtrip_always_yields_artifact(tmp_path):
    # On CPU the profiler plugin may or may not exist; either way the
    # contract is a real path on disk.
    out_dir = str(tmp_path / "cap2")
    err = profiling.start_capture(out_dir)
    artifact = profiling.stop_capture(out_dir, err)
    assert os.path.exists(artifact)


# ---------------------------------------------------------------------------
# control plane e2e: StartProfile against a live two-daemon cluster
# ---------------------------------------------------------------------------


_CLIENT = textwrap.dedent(
    """
    import pyarrow as pa
    from dora_tpu.node import Node

    with Node() as node:
        sent = False
        for event in node:
            if event["type"] == "STOP":
                break
            if not sent:
                node.send_output(
                    "text", pa.array(["hi"]),
                    {"request_id": "r0", "max_new_tokens": 4},
                )
                sent = True
    """
)

_SINK = textwrap.dedent(
    """
    from dora_tpu.node import Node

    with Node() as node:
        for event in node:
            if event["type"] == "STOP":
                break
    """
)


def test_start_profile_end_to_end_two_daemons(tmp_path):
    from dora_tpu.coordinator import Coordinator
    from dora_tpu.daemon.core import Daemon
    from dora_tpu.message import coordinator as cm
    from tests.test_coordinator_multidaemon import _wait_machines

    (tmp_path / "client.py").write_text(_CLIENT)
    (tmp_path / "sink.py").write_text(_SINK)
    profile_root = tmp_path / "profiles"
    spec = {
        "nodes": [
            {
                "id": "client",
                "path": "client.py",
                # Timer-held: the stream stays open so the llm node
                # keeps serving until StopRequest.
                "inputs": {"tick": "dora/timer/millis/200"},
                "outputs": ["text"],
                "deploy": {"machine": "A"},
            },
            {
                "id": "llm",
                "path": "module:dora_tpu.nodehub.llm_server",
                "inputs": {"text": "client/text"},
                "outputs": ["response"],
                "env": {
                    "DORA_STUB_ENGINE": "1",
                    "DORA_BATCH_SLOTS": "2",
                    "DORA_MAX_NEW_TOKENS": "4",
                    "JAX_PLATFORMS": "cpu",
                    "DORA_PROFILE_DIR": str(profile_root),
                },
                "deploy": {"machine": "B"},
            },
            {
                "id": "sink",
                "path": "sink.py",
                "inputs": {"resp": "llm/response"},
                "deploy": {"machine": "A"},
            },
        ]
    }

    async def main():
        coord = Coordinator()
        await coord.start()
        addr = f"127.0.0.1:{coord.daemon_port}"
        daemon_a, daemon_b = Daemon(), Daemon()
        tasks = [
            asyncio.create_task(daemon_a.run(addr, "A")),
            asyncio.create_task(daemon_b.run(addr, "B")),
        ]
        try:
            await _wait_machines(coord, {"A", "B"})
            start = await coord.handle_control_request(
                cm.Start(dataflow=spec, name="profiled",
                         local_working_dir=str(tmp_path))
            )
            assert isinstance(start, cm.DataflowStarted), start

            # Wait for the serving node's first report: the device
            # gauges are in the snapshot (stub engine sets synthetic
            # peak FLOPs, so mfu is derived even on CPU).
            deadline = asyncio.get_running_loop().time() + 300
            while True:
                mreply = await coord.handle_control_request(
                    cm.QueryMetrics(dataflow_uuid=start.uuid)
                )
                s = None
                if isinstance(mreply, cm.MetricsReply):
                    s = (mreply.metrics.get("serving") or {}).get("llm")
                if s is not None and s.get("requests", 0) >= 1:
                    assert "mfu" in s, sorted(s)
                    assert "device_compute_ns" in s
                    assert s["mfu"] is not None
                    break
                assert asyncio.get_running_loop().time() < deadline, (
                    "llm node never reported serving metrics"
                )
                await asyncio.sleep(0.2)

            # Stop with no active capture: the error propagates back
            # through the daemon as a ProfileReply, not a timeout.
            reply = await asyncio.wait_for(
                coord.handle_control_request(
                    cm.StopProfile(dataflow_uuid=start.uuid,
                                   node_id="llm")
                ),
                timeout=60,
            )
            assert isinstance(reply, cm.ProfileReply), reply
            assert reply.error, reply

            # The real thing: a short capture on machine B's node,
            # artifact path reported back through daemon B.
            reply = await asyncio.wait_for(
                coord.handle_control_request(
                    cm.StartProfile(dataflow_uuid=start.uuid,
                                    node_id="llm", seconds=0.2)
                ),
                timeout=120,
            )
            assert isinstance(reply, cm.ProfileReply), reply
            assert not reply.error, reply
            assert reply.node_id == "llm"
            assert reply.artifact
            assert os.path.exists(reply.artifact), reply.artifact

            stopped = await asyncio.wait_for(
                coord.handle_control_request(
                    cm.StopRequest(dataflow_uuid=start.uuid,
                                   grace_duration_s=10)
                ),
                timeout=120,
            )
            assert isinstance(stopped, cm.DataflowStopped), stopped
            assert stopped.result.is_ok(), stopped.result.errors()
        finally:
            await coord.handle_control_request(cm.Destroy())
            for t in tasks:
                t.cancel()
            await coord.close()

    asyncio.run(main())
