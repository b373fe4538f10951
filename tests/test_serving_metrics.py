"""Serving metrics plane: node-side ServingMetrics -> ReportServing ->
daemon -> coordinator QueryMetrics -> CLI SERVING table."""

from __future__ import annotations

import asyncio
import textwrap

import pytest

from dora_tpu.coordinator import Coordinator
from dora_tpu.daemon.core import Daemon
from dora_tpu.message import coordinator as cm
from dora_tpu.metrics import ServingMetrics, merge_snapshots


def test_serving_snapshot_shape():
    m = ServingMetrics(engine="paged")
    m.requests = 3
    m.decode_tokens = 40
    m.prefill_chunks = 7
    m.slots_active = 2
    m.slots_total = 16
    m.free_pages = 100
    m.total_pages = 128
    m.backlog_depth = 1
    m.ttft.observe(2_500.0)
    m.ttft.observe(9_000.0)
    m.host_dispatches = 16
    m.host_fetches = 12
    m.dispatch_gap.observe(700.0)
    m.emit_messages = 8
    m.emit.observe(300.0)
    m.emit.observe(500.0)
    snap = m.snapshot()
    assert snap["engine"] == "paged"
    assert snap["decode_tokens"] == 40
    # 40 tokens in 8 response messages: 5 tokens a message; the flush
    # of one dispatch, beside a running window, took 300 and 500 us
    assert snap["emit_messages"] == 8
    assert snap["emit_us"]["count"] == 2 and snap["emit_us"]["sum_us"] == 800.0
    assert ServingMetrics().snapshot()["emit_us"]["count"] == 0
    assert snap["ttft_us"]["count"] == 2
    assert snap["ttft_us"]["p50_us"] is not None
    # Round-trip amortization keys (multi-step window observability).
    assert snap["host_dispatches"] == 16
    assert snap["host_fetches"] == 12
    assert snap["tokens_per_dispatch"] == 2.5  # 40 / 16
    assert snap["dispatch_gap_us"]["count"] == 1
    # No dispatches yet -> no rate, not a div-by-zero.
    assert ServingMetrics().snapshot()["tokens_per_dispatch"] is None
    # Speculative-decoding counters: acceptance is accepted/drafted,
    # None (not 0/0) when the engine never drafted.
    m.spec_drafted = 40
    m.spec_accepted = 30
    m.spec_accept_len.observe(3)
    m.spec_accept_len.observe(5)
    snap = m.snapshot()
    assert snap["spec_drafted"] == 40
    assert snap["spec_accepted"] == 30
    assert snap["spec_acceptance"] == 0.75
    assert snap["spec_accept_len"]["count"] == 2
    assert ServingMetrics().snapshot()["spec_acceptance"] is None


def test_merge_unions_serving_across_daemons():
    a = {"serving": {"llm": {"engine": "paged", "decode_tokens": 5}}}
    b = {"serving": {"llm2": {"engine": "dense", "decode_tokens": 9}}}
    merged = merge_snapshots([a, {}, b])
    assert set(merged["serving"]) == {"llm", "llm2"}
    assert merged["serving"]["llm"]["decode_tokens"] == 5
    # no serving anywhere -> the key stays absent (CLI renders nothing)
    assert "serving" not in merge_snapshots([{"links": {}}])


def test_render_serving_table_with_rates():
    from dora_tpu.cli.metrics_view import render_metrics

    def snap(tokens: int) -> dict:
        return {
            "serving": {
                "llm": {
                    "engine": "paged",
                    "requests": 4,
                    "decode_tokens": tokens,
                    "slots_active": 3,
                    "slots_total": 16,
                    "free_pages": 120,
                    "total_pages": 128,
                    "used_pages": 8,
                    "peak_used_pages": 24,
                    "largest_contig_free": 96,
                    "compiles": 6,
                    "backlog_depth": 2,
                    "host_dispatches": 30,
                    "host_fetches": 28,
                    "tokens_per_dispatch": 5.0,
                    "spec_drafted": 200,
                    "spec_accepted": 130,
                    "spec_acceptance": 0.65,
                    "ttft_us": {
                        "count": 4, "p50_us": 2500.0, "p90_us": 8000.0,
                        "p99_us": 9000.0,
                    },
                    "dispatch_gap_us": {
                        "count": 30, "p50_us": 512.0, "p99_us": 4096.0,
                    },
                    "emit_us": {
                        "count": 29, "p50_us": 128.0, "p99_us": 2048.0,
                    },
                    "fetch_us": {
                        "count": 28, "p50_us": 256.0, "p99_us": 1024.0,
                    },
                }
            }
        }

    out = render_metrics("u", snap(150), prev=snap(50), interval=2.0)
    assert "SERVING" in out and "llm (paged)" in out
    assert "3/16" in out  # slots
    assert "8/128" in out  # pages: OCCUPANCY (used/total)
    assert "50.0" in out  # (150 - 50) / 2.0 tok/s
    assert "2.5ms" in out  # ttft p50
    assert "TOK/DISP" in out and "5.0" in out  # tokens per dispatch
    assert "ACC%" in out and "65%" in out  # speculative acceptance rate
    assert "GAP P50" in out and "512µs" in out  # dispatch-gap histogram
    assert "EMIT P50" in out and "128µs" in out  # one dispatch's flush
    assert "FETCH P50" in out and "256µs" in out  # fetch split from gap
    assert "COMPILES" in out and "6" in out  # xla compile audit counter
    # Page sparkline with peak + fragmentation gauges.
    assert "pages llm [" in out and "peak 24" in out and "contig 96" in out
    one_shot = render_metrics("u", snap(150))
    assert "llm (paged)" in one_shot  # renders without watch deltas too
    # Snapshots predating the window metrics render with dashes.
    bare = snap(10)
    for key in ("tokens_per_dispatch", "dispatch_gap_us", "emit_us",
                "fetch_us",
                "used_pages", "peak_used_pages", "largest_contig_free",
                "compiles", "spec_drafted", "spec_accepted",
                "spec_acceptance"):
        del bare["serving"]["llm"][key]
    assert "llm (paged)" in render_metrics("u", bare)


def test_render_watch_rate_clamps_and_reset():
    """Satellite fix: the watch-mode rate divides by MEASURED wall time
    between snapshots from different daemons — a ~0 interval must clamp
    to 1 ms (no exploded rate, no ZeroDivisionError), and a counter
    that went BACKWARD (node restart) renders '-' instead of a negative
    rate."""
    from dora_tpu.cli.metrics_view import render_metrics

    snap = {
        "links": {"a/out": {"msgs": 100, "bytes": 1000}},
        "serving": {
            "llm": {"engine": "paged", "decode_tokens": 10, "requests": 1},
        },
    }
    prev = {
        "links": {"a/out": {"msgs": 50, "bytes": 500}},
        "serving": {
            "llm": {"engine": "paged", "decode_tokens": 400, "requests": 9},
        },
    }
    # interval 0 (same-instant snapshots): clamps to 1 ms -> 50 msgs /
    # 0.001 s = 50000/s, finite and rendered.
    out = render_metrics("u", snap, prev=prev, interval=0.0)
    assert "50000.0" in out
    # decode_tokens went 400 -> 10: reset renders '-', never "-195000.0".
    serving_line = next(ln for ln in out.splitlines() if "llm (" in ln)
    assert "-195" not in serving_line
    # Sparkline history renders one cell per snapshot.
    hist_snap = {
        "serving": {
            "llm": {
                "engine": "paged", "total_pages": 100, "used_pages": 100,
            }
        }
    }
    older = {
        "serving": {
            "llm": {"engine": "paged", "total_pages": 100, "used_pages": 0}
        }
    }
    out = render_metrics("u", hist_snap, history=[older, hist_snap])
    assert "pages llm [ ██]" in out


REPORTER = textwrap.dedent(
    """
    from dora_tpu.metrics import ServingMetrics
    from dora_tpu.node import Node

    node = Node()
    m = ServingMetrics(engine="paged")
    m.requests = 2
    m.decode_tokens = 17
    m.slots_active = 1
    m.slots_total = 16
    m.free_pages = 99
    m.total_pages = 127
    m.ttft.observe(1234.0)
    node.report_serving(m.snapshot())
    node.report_serving(m.snapshot())  # latest-wins, re-reports are fine
    node.close()
    """
)


def test_report_serving_reaches_query_metrics(tmp_path, monkeypatch):
    monkeypatch.setenv("DORA_P2P", "0")
    (tmp_path / "serving_reporter.py").write_text(REPORTER)
    spec = {
        "nodes": [
            {"id": "llm", "path": "serving_reporter.py", "outputs": []},
        ]
    }

    async def main():
        from tests.test_metrics import _wait_finished, _wait_machines

        coord = Coordinator()
        await coord.start()
        daemon = Daemon()
        task = asyncio.create_task(
            daemon.run(f"127.0.0.1:{coord.daemon_port}", "A")
        )
        try:
            await _wait_machines(coord, {"A"})
            start = await coord.handle_control_request(
                cm.Start(
                    dataflow=spec,
                    name="served",
                    local_working_dir=str(tmp_path),
                )
            )
            assert isinstance(start, cm.DataflowStarted), start
            result = await _wait_finished(coord, start.uuid)
            assert result.is_ok(), result.errors()
            reply = await coord.handle_control_request(
                cm.QueryMetrics(dataflow_uuid=start.uuid)
            )
            assert isinstance(reply, cm.MetricsReply), reply
            serving = reply.metrics.get("serving")
            assert serving is not None, reply.metrics
            s = serving["llm"]
            assert s["engine"] == "paged"
            assert s["decode_tokens"] == 17
            assert s["ttft_us"]["count"] == 1

            from dora_tpu.cli.metrics_view import render_metrics

            out = render_metrics(start.uuid, reply.metrics)
            assert "llm (paged)" in out
        finally:
            await coord.handle_control_request(cm.Destroy())
            task.cancel()
            await coord.close()

    asyncio.run(main())
