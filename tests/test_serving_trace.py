"""Serving-engine observability plane: request-lifecycle spans through
the flight-recorder ring (llm_server.serve + batch_engine hooks), TTFT
fidelity under the fused decode window, ring/daemon truncation counters,
the runtime XLA compile audit, HLC-skewed serving-span merge, and the
3-process end-to-end trace (client -> llm_server(stub) -> sink) with
QueryTrace + Chrome export."""

from __future__ import annotations

import asyncio
import json
import textwrap
import time

import pytest

import dora_tpu.telemetry as tel
from dora_tpu.metrics import ServingMetrics
from dora_tpu.telemetry import (
    OTEL_CTX_KEY,
    FlightRecorder,
    trace_id_of,
)
from dora_tpu.tracing import (
    ENGINE_TID,
    SERVING_SPAN_KINDS,
    merge_trace_snapshots,
    to_chrome_trace,
    validate_chrome_trace,
)


@pytest.fixture
def tracing_on(monkeypatch):
    monkeypatch.setenv("DORA_TRACING", "1")
    tel.TRACING.configure_from_env()
    tel.FLIGHT.configure_from_env()
    tel.FLIGHT.clear()
    yield
    monkeypatch.undo()
    tel.TRACING.configure_from_env()
    tel.FLIGHT.configure_from_env()
    tel.FLIGHT.clear()


# ---------------------------------------------------------------------------
# in-process serving over the REAL serve() loop + stub paged engine
# ---------------------------------------------------------------------------


class _ServeNode:
    """Node fake for llm_server.serve: queued input events, captured
    outputs and serving reports, stream ends when events run out."""

    def __init__(self, events):
        self._events = list(events)
        self.stream_ended = False
        self.sent: list[tuple[str, object, dict]] = []
        self.serving: list[dict] = []
        self.closed = False

    def recv(self, timeout=None):
        if self._events:
            return self._events.pop(0)
        self.stream_ended = True
        return None

    def send_output(self, output_id, value, metadata=None):
        self.sent.append((output_id, value, dict(metadata or {})))

    def report_serving(self, snapshot):
        self.serving.append(snapshot)

    def close(self):
        self.closed = True


def _req(text: str, max_new: int, ctx: str = "") -> dict:
    meta: dict = {"request_id": f"wire-{text}", "max_new_tokens": max_new}
    if ctx:
        meta[OTEL_CTX_KEY] = ctx
    return {"type": "INPUT", "metadata": meta, "value": text.encode()}


def _serve_once(engine, metrics, events) -> _ServeNode:
    from dora_tpu.nodehub.llm_server import serve

    node = _ServeNode(events)
    serve(
        node, engine, metrics,
        encode=lambda text: [ord(ch) % 97 for ch in text] or [1],
        decode_one=lambda t: f" t{t}",
        max_new_cap=8,
    )
    return node


def _engine_events(key: str) -> list[tuple]:
    """Ring events whose ``a`` field belongs to request ``key``."""
    return [
        e for e in tel.FLIGHT.events()
        if str(e[3] or "").split(" ", 1)[0] == key
    ]


def test_lifecycle_spans_through_the_real_serve_loop(tracing_on):
    """One slot, two requests: req-1 runs the full chain immediately;
    req-2 parks (s_page_wait instant), waits in the backlog (s_queued
    with a real duration), then runs its own full chain — every span of
    a request linked by the trace id of the message that carried it."""
    pytest.importorskip("jax")
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    engine = make_stub_paged_engine(max_slots=1, window=2)
    metrics = ServingMetrics(engine="paged")
    ctx1 = tel.child_context("")
    ctx2 = tel.child_context("")
    node = _serve_once(
        engine, metrics, [_req("hi", 4, ctx1), _req("yo", 3, ctx2)]
    )
    assert node.closed

    # req-1: full lifecycle chain, in order, one trace id — the carrier
    # message's.
    ev1 = _engine_events("req-1")
    kinds = [e[2] for e in ev1]
    first_of = {k: kinds.index(k) for k in dict.fromkeys(kinds)}
    want = ["s_queued", "s_admitted", "s_prefill_chunk",
            "s_decode_window", "s_finish"]
    assert [k for k in kinds if k in want[:3]] == want[:3], kinds
    assert first_of["s_decode_window"] > first_of["s_prefill_chunk"]
    assert kinds[-1] == "s_finish" and "length" in str(ev1[-1][3])
    ids1 = {trace_id_of(str(e[4] or "")) for e in ev1}
    assert ids1 == {trace_id_of(ctx1)}

    # The prefill chunk span carries base/chunk, the window span carries
    # K/emitted/frozen_at — the fields the drift walkthrough reads.
    chunk_detail = next(str(e[3]) for e in ev1 if e[2] == "s_prefill_chunk")
    assert "base=0" in chunk_detail and "final" in chunk_detail
    win_detail = next(str(e[3]) for e in ev1 if e[2] == "s_decode_window")
    assert "K=2" in win_detail and "emitted=" in win_detail

    # req-2: parked behind the single slot -> page-wait instant, then a
    # queued span with an actual backlog duration, then its own chain.
    ev2 = _engine_events("req-2")
    kinds2 = [e[2] for e in ev2]
    assert "s_page_wait" in kinds2
    queued = next(e for e in ev2 if e[2] == "s_queued")
    assert int(queued[5] or 0) > 0  # waited a real interval
    assert kinds2[-1] == "s_finish"
    assert {trace_id_of(str(e[4] or "")) for e in ev2} == {trace_id_of(ctx2)}

    # Metrics the engine fed through its hooks.
    snap = metrics.snapshot()
    assert snap["requests"] == 2
    assert snap["ttft_us"]["count"] == 2
    assert snap["fetch_us"]["count"] > 0
    assert snap["backlog_wait_us"]["count"] == 2
    assert snap["grant_pages"]  # page-grant size histogram populated
    # Final report carries the allocator gauges.
    last = node.serving[-1]
    assert last["total_pages"] > 0
    assert last["peak_used_pages"] > 0
    assert last["used_pages"] == 0  # both streams finished and freed
    assert "compiles" in last

    # The same ring exports as a valid Chrome trace with the chain on
    # the engine track.
    snapshot = {
        "machine": "M",
        "wall_ns": time.time_ns(),
        "hlc_ns": time.time_ns(),
        "processes": {"llm": [list(e) for e in tel.FLIGHT.events()]},
    }
    trace = to_chrome_trace(merge_trace_snapshots([snapshot]))
    assert validate_chrome_trace(trace) == []
    serving_spans = [
        e for e in trace["traceEvents"]
        if e["ph"] == "X" and e.get("cat") == "serving"
    ]
    assert serving_spans
    assert all(e["tid"] == ENGINE_TID for e in serving_spans)
    metas = [
        e for e in trace["traceEvents"]
        if e["ph"] == "M" and e["name"] == "thread_name"
    ]
    assert any(m["args"]["name"] == "engine" for m in metas)
    chain1 = [
        e["name"].split(" ", 1)[0] for e in serving_spans
        if e.get("args", {}).get("trace_id") == trace_id_of(ctx1)
    ]
    assert chain1[0] == "queued" and chain1[-1] == "finish"
    assert "prefill_chunk" in chain1 and "decode_window" in chain1


def test_rejects_record_instants_not_spans(tracing_on):
    """max_new<=0 and oversized prompts close the stream empty and stamp
    an s_reject instant — no lifecycle chain, no leaked tracer context."""
    pytest.importorskip("jax")
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    engine = make_stub_paged_engine(max_slots=1, window=1, max_seq=32)
    metrics = ServingMetrics(engine="paged")
    node = _serve_once(
        engine, metrics,
        [_req("zero", 0), _req("x" * 200, 4)],  # 200 ids never fit 32 rows
    )
    kinds = [e[2] for e in tel.FLIGHT.events() if str(e[2]).startswith("s_")]
    assert kinds.count("s_reject") == 2
    assert "s_admitted" not in kinds
    assert metrics.rejected == 2 and metrics.requests == 2
    # Both streams still answered: one empty done chunk each.
    # max_new<=0 closes as "length" (the request asked for nothing);
    # the oversized prompt gets the structured retriable "rejected"
    # with the sizing detail a client needs to split the request.
    dones = {m.get("request_id"): m for _, _, m in node.sent if m.get("done")}
    assert len(dones) == 2
    assert dones["wire-zero"]["finish"] == "length"
    over = dones["wire-" + "x" * 200]
    assert over["finish"] == "rejected"
    assert over["reject_reason"] == "oversized"
    assert over["pages_needed"] > over["pool_pages"] or \
        200 + 4 > over["max_seq"]


def test_ttft_not_quantized_to_the_decode_window():
    """Satellite regression: the first token of a request lands host-side
    when its final prefill chunk fetches, and the same tick's K-step
    decode window is launched right after — a loop that sent the token
    only once that window was back would inflate TTFT by the whole
    window at K=16 with a measurable per-tick cost. The loop sends it
    after ``dispatch()``, before it waits in ``collect()``: the client
    really gets it a window earlier, and the server's histogram needs
    no correction.

    With tick_sleep_s=8ms the K=16 window takes >=128ms of modelled
    device time (a held first token would land in histogram bucket
    >=131072us); the observed TTFT is the admission->fetch->send
    interval only, asserted an order of magnitude under the window
    (octave-resolution histogram: bucket <=65536us)."""
    pytest.importorskip("jax")
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    tick = 0.008
    eng16 = make_stub_paged_engine(max_slots=2, window=16, tick_sleep_s=tick)
    warm = ServingMetrics(engine="paged")
    _serve_once(eng16, warm, [_req("warm", 3)])
    measured = ServingMetrics(engine="paged")
    _serve_once(eng16, measured, [_req("measure", 3)])
    p50 = measured.snapshot()["ttft_us"]["p50_us"]
    assert p50 is not None and p50 <= 65536, p50
    # Compile audit: the measured (steady-state) request compiled
    # nothing — the counter delta between the two serves is zero.
    assert measured.compiles == warm.compiles
    # K=1 control: per-token dispatch has no window to hide in; same
    # sub-window TTFT magnitude (the K=16 number above matches it
    # instead of sitting ~K ticks higher).
    eng1 = make_stub_paged_engine(max_slots=2, window=1, tick_sleep_s=tick)
    _serve_once(eng1, ServingMetrics(engine="paged"), [_req("warm", 3)])
    m1 = ServingMetrics(engine="paged")
    _serve_once(eng1, m1, [_req("measure", 3)])
    p50_k1 = m1.snapshot()["ttft_us"]["p50_us"]
    assert p50_k1 is not None and p50_k1 <= 65536, p50_k1


# ---------------------------------------------------------------------------
# saturation is not silent: ring wrap + daemon cap counters
# ---------------------------------------------------------------------------


def test_flight_recorder_counts_wrap_loss_between_reads():
    r = FlightRecorder(size=4, enabled=True)
    r.record("route", "x")
    _, cur = r.events_since(0)
    for i in range(10):
        r.record("route", "x", i)
    events, _ = r.events_since(cur)
    assert len(events) == 4  # ring holds the newest 4
    assert r.dropped == 6  # idx=11, floor=7, cursor=1 -> 6 lost
    r.clear()
    assert r.dropped == 0


def test_node_flusher_ships_synthetic_trace_truncated():
    """Ring wrap between node flushes rides the EXISTING ReportTrace
    format as a synthetic trace_truncated event (count in slot a), and
    the watermark ensures each loss is reported once."""
    from dora_tpu.node import Node

    class FakeControl:
        def __init__(self):
            self.msgs = []

        def queue(self, msg):
            self.msgs.append(msg)

    node = Node.__new__(Node)
    node._flight = FlightRecorder(size=4, enabled=True)
    node._trace_cursor = 0
    node._trace_dropped_sent = 0
    node._control = FakeControl()

    node._flight.record("t_send", "out", "ctx", 1)
    node._queue_trace_report()
    assert [e[2] for e in node._control.msgs[0].events] == ["t_send"]

    for i in range(10):  # wraps well past the shipped cursor
        node._flight.record("t_send", "out", "ctx", i)
    node._queue_trace_report()
    events = node._control.msgs[1].events
    assert events[0][2] == "trace_truncated"
    assert events[0][3] == 6  # exactly the wrapped-out count
    assert len(events) == 5  # marker + the 4 slots the ring still held

    node._flight.record("t_send", "out", "ctx", 99)
    node._queue_trace_report()  # no new loss -> no second marker
    assert all(
        e[2] != "trace_truncated" for e in node._control.msgs[2].events
    )


def test_daemon_trace_buffer_cap_counts_trims():
    from types import SimpleNamespace

    from dora_tpu.daemon.core import (
        MAX_NODE_TRACE_EVENTS,
        _extend_trace_buffer,
    )

    df = SimpleNamespace(node_traces={}, node_trace_drops={})
    _extend_trace_buffer(
        df, "llm", [[1, 1, "t_send", "a", None, None]] * 10
    )
    assert df.node_trace_drops == {}  # under the cap: nothing counted
    big = [
        [i, i, "t_send", "a", None, None]
        for i in range(MAX_NODE_TRACE_EVENTS)
    ]
    _extend_trace_buffer(df, "llm", big)
    assert len(df.node_traces["llm"]) == MAX_NODE_TRACE_EVENTS
    assert df.node_trace_drops["llm"] == 10  # oldest-first trim, counted
    assert df.node_traces["llm"][0][0] == 0  # head is the new chunk


def test_export_marks_daemon_truncated_tracks():
    merged = merge_trace_snapshots(
        [
            {
                "machine": "A",
                "wall_ns": 0,
                "hlc_ns": 0,
                "processes": {
                    "llm": [[1, 1000, "s_finish", "req-1 stop", None, 0]]
                },
                "dropped_events": {"llm": 12},
            }
        ]
    )
    assert merged["processes"][0]["dropped_events"] == 12
    trace = to_chrome_trace(merged)
    assert validate_chrome_trace(trace) == []
    names = [e["name"] for e in trace["traceEvents"] if e["ph"] == "i"]
    assert "trace truncated (12 events lost)" in names


# ---------------------------------------------------------------------------
# merge: serving spans from HLC-skewed machines stay monotonic
# ---------------------------------------------------------------------------


def test_serving_spans_merge_monotonically_across_skewed_clocks():
    base = 1_000_000_000_000
    ctx = "traceparent:00-" + "ab" * 16 + "-" + "cd" * 8 + "-01;"
    # The client's machine lags the cluster HLC by 3 ms; the serving
    # machine runs 2 ms ahead. Raw llm stamps overlap the send's raw
    # stamp range — only alignment orders them correctly.
    client = {
        "machine": "A",
        "wall_ns": base,
        "hlc_ns": base + 3_000_000,
        "processes": {
            "client": [[1, base + 1_000_000, "t_send", "text", ctx, 50_000]]
        },
    }
    llm = {
        "machine": "B",
        "wall_ns": base + 2_000_000,
        "hlc_ns": base,
        "processes": {
            "llm": [
                [2, base + 7_000_000, "s_queued", "req-1", ctx, 100_000],
                [3, base + 7_100_000, "s_admitted", "req-1 pages=1", ctx,
                 10_000],
                [4, base + 7_300_000, "s_prefill_chunk",
                 "req-1 base=0 chunk=16 final", ctx, 150_000],
                [5, base + 7_900_000, "s_decode_window",
                 "req-1 K=8 emitted=3 frozen_at=2", ctx, 400_000],
                [6, base + 8_000_000, "s_finish", "req-1 stop", ctx, 0],
            ]
        },
    }
    merged = merge_trace_snapshots([llm, client])  # order must not matter
    by_proc = {p["process"]: p["events"] for p in merged["processes"]}
    send_wall = by_proc["client"][0][1]
    assert send_wall == base + 1_000_000 + 3_000_000
    walls = [e[1] for e in by_proc["llm"]]
    assert walls == sorted(walls)  # per-track monotonic after alignment
    assert all(w > send_wall for w in walls)  # lifecycle after the send
    # Export keeps the chain order and the shared trace id.
    trace = to_chrome_trace(merged)
    assert validate_chrome_trace(trace) == []
    spans = sorted(
        (
            e for e in trace["traceEvents"]
            if e["ph"] == "X" and e.get("cat") == "serving"
        ),
        key=lambda e: e["ts"] + e["dur"],
    )
    assert [e["name"].split(" ", 1)[0] for e in spans] == [
        "queued", "admitted", "prefill_chunk", "decode_window", "finish"
    ]
    ids = {e["args"].get("trace_id") for e in spans}
    assert ids == {"ab" * 16}


# ---------------------------------------------------------------------------
# runtime XLA compile audit
# ---------------------------------------------------------------------------


def test_compile_listener_counts_and_stamps_the_ring(tracing_on):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    assert tel.install_compile_listener()
    tel.FLIGHT.clear()
    before = tel.compile_count()

    @jax.jit
    def fresh(x):
        return (x * 3 + 1) ^ 7

    fresh(jnp.arange(5)).block_until_ready()
    assert tel.compile_count() > before
    compiles = [e for e in tel.FLIGHT.events() if e[2] == "xla_compile"]
    assert compiles
    assert int(compiles[-1][5] or 0) > 0  # elapsed ns rides in slot c


# ---------------------------------------------------------------------------
# end to end: client -> llm_server (stub engine) -> sink, one trace id
# from the carrier message through the whole lifecycle chain
# ---------------------------------------------------------------------------


CLIENT = textwrap.dedent(
    """
    import pyarrow as pa
    from dora_tpu.node import Node

    node = Node()
    for i, text in enumerate(["hi there", "ok go"]):
        node.send_output(
            "text", pa.array([text]),
            {"request_id": f"r{i}", "max_new_tokens": 3},
        )
    node.close()
    """
)

SINK = textwrap.dedent(
    """
    import sys
    from dora_tpu.node import Node

    done = 0
    with Node() as node:
        for event in node:
            if event["type"] == "STOP":
                break
            if event["type"] == "INPUT":
                meta = event["metadata"] or {}
                if meta.get("done"):
                    done += 1
    if done < 2:
        print(f"expected 2 finished streams, saw {done}", file=sys.stderr)
        sys.exit(1)
    """
)


def _serving_spec() -> dict:
    env = {"DORA_TRACING": "1"}
    return {
        "nodes": [
            {
                "id": "client",
                "path": "client.py",
                "outputs": ["text"],
                "env": dict(env),
            },
            {
                "id": "llm",
                "path": "module:dora_tpu.nodehub.llm_server",
                "inputs": {"text": "client/text"},
                "outputs": ["response"],
                "env": {
                    **env,
                    "DORA_STUB_ENGINE": "1",
                    "DORA_MULTISTEP_K": "2",
                    "DORA_BATCH_SLOTS": "2",
                    "DORA_MAX_NEW_TOKENS": "4",
                    "JAX_PLATFORMS": "cpu",
                },
            },
            {
                "id": "sink",
                "path": "sink.py",
                "inputs": {"resp": "llm/response"},
                "env": dict(env),
            },
        ]
    }


def test_serving_trace_end_to_end(tmp_path, monkeypatch, capsys):
    from dora_tpu.coordinator import Coordinator
    from dora_tpu.daemon.core import Daemon
    from dora_tpu.message import coordinator as cm
    from tests.test_trace import _wait_finished, _wait_machines

    monkeypatch.setenv("DORA_P2P", "0")  # daemon route: full message chain
    monkeypatch.setenv("DORA_TRACING", "1")
    tel.TRACING.configure_from_env()
    tel.FLIGHT.configure_from_env()
    tel.FLIGHT.clear()
    (tmp_path / "client.py").write_text(CLIENT)
    (tmp_path / "sink.py").write_text(SINK)

    out_path = tmp_path / "serving_trace.json"
    cli_out: dict = {}

    async def main():
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
                    dataflow=_serving_spec(),
                    name="served-traced",
                    local_working_dir=str(tmp_path),
                )
            )
            assert isinstance(start, cm.DataflowStarted), start
            # The llm node imports jax + compiles the stub window.
            result = await _wait_finished(coord, start.uuid, timeout=300)
            assert result.is_ok(), result.errors()

            # Archived dataflow (already finished): the engine track is
            # still queryable from the daemon's kept buffers.
            reply = await coord.handle_control_request(
                cm.QueryTrace(dataflow_uuid=start.uuid)
            )
            assert isinstance(reply, cm.TraceReply), reply
            procs = {
                p["process"]: p["events"] for p in reply.trace["processes"]
            }
            assert {"client", "llm", "sink", "(daemon)"} <= set(procs), (
                set(procs)
            )

            # Per-request lifecycle chains in the llm track, keyed by
            # trace id.
            chains: dict[str, set[str]] = {}
            for e in procs["llm"]:
                if e[2] in SERVING_SPAN_KINDS:
                    tid = trace_id_of(str(e[4] or ""))
                    if tid:
                        chains.setdefault(tid, set()).add(
                            SERVING_SPAN_KINDS[e[2]]
                        )
            full = {
                tid for tid, kinds in chains.items()
                if {"queued", "admitted", "prefill_chunk",
                    "decode_window", "finish"} <= kinds
            }
            assert full, chains

            # The lifecycle trace id IS the carrier message's: the same
            # id appears in the client's t_send records.
            send_ids = {
                trace_id_of(str(e[4] or ""))
                for e in procs["client"]
                if e[2] == "t_send" and e[4]
            }
            assert full & send_ids, (full, send_ids)

            # Page-pool occupancy reached the metrics plane.
            mreply = await coord.handle_control_request(
                cm.QueryMetrics(dataflow_uuid=start.uuid)
            )
            assert isinstance(mreply, cm.MetricsReply), mreply
            s = (mreply.metrics.get("serving") or {}).get("llm")
            assert s is not None, mreply.metrics
            assert s["engine"] == "paged"
            assert s["total_pages"] > 0
            assert s["peak_used_pages"] > 0
            assert s["requests"] == 2
            assert "compiles" in s

            from dora_tpu.cli.main import main as cli_main

            addr = f"127.0.0.1:{coord.control_port}"
            cli_out["rc"] = await asyncio.to_thread(
                cli_main,
                [
                    "trace", "--uuid", start.uuid,
                    "--coordinator-addr", addr,
                    "--out", str(out_path),
                ],
            )
        finally:
            await coord.handle_control_request(cm.Destroy())
            task.cancel()
            await coord.close()

    try:
        asyncio.run(main())
    finally:
        monkeypatch.undo()
        tel.TRACING.configure_from_env()
        tel.FLIGHT.configure_from_env()
        tel.FLIGHT.clear()

    assert cli_out["rc"] == 0
    trace = json.loads(out_path.read_text())
    assert validate_chrome_trace(trace) == []
    events = trace["traceEvents"]
    serving_spans = [
        e for e in events if e["ph"] == "X" and e.get("cat") == "serving"
    ]
    assert serving_spans
    assert all(e["tid"] == ENGINE_TID for e in serving_spans)
    # One trace id covers the message plane (client pid, tid 0) AND the
    # llm engine track (tid 1) in the exported file.
    tracks_by_id: dict[str, set[tuple[int, int]]] = {}
    for e in events:
        if e["ph"] not in ("X", "i"):
            continue
        tid = (e.get("args") or {}).get("trace_id")
        if tid:
            tracks_by_id.setdefault(tid, set()).add((e["pid"], e["tid"]))
    assert any(
        len({p for p, _ in tracks}) >= 2
        and any(t == ENGINE_TID for _, t in tracks)
        for tracks in tracks_by_id.values()
    ), tracks_by_id
