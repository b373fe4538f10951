"""A request's stages up to its first message sent
(``telemetry.REQUEST_STAGES``): the three that ``ServingTracer`` keeps on
the request add up, with its wait for a slot, to what ``ttft_us``
observed for it; each is observed once, with ``ttft_us``, or not at all;
and no entry outlives its request.

``llm_server.serve`` is driven over a fake node and the stub paged
engine on a clock that steps a millisecond a read, so every sum below is
exact; the capture's tests drive ``_run_loop`` over
``test_loop_phases``' scripted clock and engine.
"""

from __future__ import annotations

import re
import signal
import time
from pathlib import Path

import pytest

from dora_tpu import telemetry
from dora_tpu.metrics import Histogram, ServingMetrics
from dora_tpu.nodehub.llm_server import ProfileCapture, serve
from dora_tpu.telemetry import REQUEST_STAGES, stage_histogram_key
from tests.test_checkpoint_resume import _CrashNode
from tests.test_loop_phases import Clock, PhasedEngine, _drive, _input
from tests.test_serving_trace import _ServeNode

ROOT = Path(__file__).resolve().parent.parent
TILED = ["prefill_queue", "prefill", "first_emit"]
ARRIVAL = ["front", "route_in"]


class SteppingClock:
    """Every read is a millisecond after the last."""

    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        self.t += 0.001
        return self.t


class Recording(Histogram):
    """A histogram that also keeps what it observed, in order."""

    __slots__ = ("values",)

    def __init__(self):
        super().__init__()
        self.values: list[float] = []

    def observe(self, value_us: float) -> None:
        super().observe(value_us)
        self.values.append(value_us)


def _req(rid: str, text: str, max_new: int, **meta) -> dict:
    return {"type": "INPUT", "value": text.encode(),
            "metadata": {"request_id": rid, "max_new_tokens": max_new, **meta}}


def _stub(**kw):
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    return make_stub_paged_engine(**{"max_seq": 128, "chunk": 16, **kw})


def _serve(engine, events, node=None, max_new_cap=64):
    metrics = ServingMetrics()
    metrics.ttft = Recording()
    metrics.backlog_wait = Recording()
    for name in metrics.stages:
        metrics.stages[name] = Recording()
    clock = SteppingClock()
    tracer = telemetry.ServingTracer(clock=clock)
    node = node or _ServeNode(events)
    node.profiles = []
    node.report_profile = lambda *reply: node.profiles.append(reply)
    error = None
    try:
        serve(node, engine, metrics, tracer=tracer, clock=clock,
              encode=lambda text: [ord(ch) % 97 + 1 for ch in text] or [1],
              decode_one=lambda tok: f" t{tok}", max_new_cap=max_new_cap)
    except RuntimeError as e:
        error = e
    return node, metrics, tracer, error


def _counts(metrics) -> dict[str, int]:
    return {name: metrics.stages[name].count for name in metrics.stages}


def _assert_tiled(metrics) -> None:
    """The three stages hold the requests ``ttft_us`` holds, and for each
    of them backlog wait + the three = what ``ttft_us`` observed."""
    ttft = metrics.ttft.values
    parts = [metrics.stages[name].values for name in TILED]
    assert [len(p) for p in parts] == [len(ttft)] * 3
    # admitted first in, first out, and first tokens come in that order
    waits = metrics.backlog_wait.values[:len(ttft)]
    for whole, wait, *three in zip(ttft, waits, *parts):
        assert all(v > 0 for v in three)
        assert wait + sum(three) == pytest.approx(whole, abs=1e-3)


@pytest.mark.parametrize("prefix_cache", [False, True])
def test_a_requests_stages_and_its_backlog_wait_add_up_to_its_ttft(prefix_cache):
    # two slots, seven prompts of one to four chunks: some wait for a
    # slot, all wait behind other prompts' chunks, most chunks go ahead
    engine = _stub(max_slots=2, window=4, prefix_cache=prefix_cache)
    events = [_req(f"w{i}", "x" * (10 + 9 * i), 6) for i in range(7)]
    node, metrics, tracer, _ = _serve(engine, events)
    assert metrics.ttft.count == 7 and engine.chunks_ahead >= 3
    assert max(metrics.backlog_wait.values) > 0
    _assert_tiled(metrics)
    assert tracer._edges == {}
    if prefix_cache:
        # the later prompts start behind pages that the earlier ones left:
        # the first chunk they still run ends their wait in the queue
        assert engine.prefix_cache.hits >= 3
    # the front's stamps were on no request: nothing observed, nothing raised
    assert [metrics.stages[name].count for name in ARRIVAL] == [0, 0]


def test_a_chunk_that_went_ahead_ends_the_queue_as_one_in_line_does():
    clock = Clock()
    engine = _stub(max_slots=2, window=2)
    tracer = engine.tracer = telemetry.ServingTracer(clock=clock)
    stages = tracer.stage_histograms = {n: Recording() for n in TILED}
    for key in ("a", "b"):
        tracer.request_pushed(key, clock())
        tracer.request_admitted(key, 0.0)
        engine.submit(key, list(range(1, 21)), 4)  # two chunks each
    clock.t = 110.0
    assert engine.dispatch() == []  # a's first chunk, in line
    engine.collect()
    clock.t = 120.0
    assert engine.dispatch()  # a's final chunk, in line, and its window
    clock.t = 130.0
    engine.ahead()  # b's first chunk, behind that window
    assert engine.chunks_ahead == 1
    engine.collect()
    clock.t = 140.0
    engine.dispatch()  # adopts it; no edge of b's is here
    assert tracer._edges["a"] == [100.0, 100.0, 110.0, 120.0]
    assert tracer._edges["b"] == [100.0, 100.0, 130.0]
    tracer.request_sent("a", 125.0)
    assert [stages[n].values for n in TILED] == [[10e6], [10e6], [5e6]]
    tracer.finish("b")
    assert tracer._edges == {}


def test_shed_and_rejected_requests_observe_no_stage_and_leave_no_entry(monkeypatch):
    monkeypatch.setenv("DORA_QOS_DEPTH_STANDARD", "1")
    engine = _stub(max_slots=1, window=4)
    events = [
        _req("served", "hello", 6),
        _req("parked", "hello", 6),
        _req("shed", "hello", 6),  # the class's backlog is full: shed at the door
        _req("nothing", "hello", 0),  # asks for no token
        _req("oversized", "x" * 500, 6),  # can never fit
    ]
    node, metrics, tracer, _ = _serve(engine, events)
    finishes = {m["request_id"]: m["finish"] for _o, _v, m in node.sent if m.get("done")}
    assert finishes == {"served": "length", "parked": "length", "shed": "overloaded",
                        "nothing": "length", "oversized": "rejected"}
    assert metrics.shed == 1 and metrics.rejected == 2
    assert metrics.ttft.count == 2
    _assert_tiled(metrics)
    assert tracer._edges == {}


def test_a_preempted_and_resumed_stream_observes_its_stages_once(monkeypatch):
    monkeypatch.setenv("DORA_QOS_PREEMPT", "1")
    engine = _stub(max_slots=1, window=4)
    node, metrics, tracer, _ = _serve(engine, [
        _req("w-b", "hello world", 24, qos_class="batch"),
        _req("w-i", "quick", 4, qos_class="interactive"),
    ])
    assert metrics.preempted >= 1 and metrics.resumed >= 1
    # admitted three times, a first message twice
    assert metrics.backlog_wait.count == 3 and metrics.ttft.count == 2
    assert _counts(metrics) == {"front": 0, "route_in": 0, "prefill_queue": 2,
                                "prefill": 2, "first_emit": 2}
    assert tracer._edges == {}


def test_a_restored_stream_observes_nothing_a_second_time(tmp_path, monkeypatch):
    monkeypatch.setenv("DORA_CHECKPOINT_DIR", str(tmp_path / "ckpt"))
    monkeypatch.setenv("DORA_CHECKPOINT_EVERY", "1")
    prev_term = signal.getsignal(signal.SIGTERM)
    try:
        events = [_req("ab", "ab", 8), _req("cd", "cd", 8)]
        node1, first, _tracer, error = _serve(
            _stub(max_slots=2), [], node=_CrashNode(events, crash_after=6), max_new_cap=8)
        assert "simulated kill" in str(error) and first.ttft.count == 2
        node2, second, tracer, _ = _serve(_stub(max_slots=2), [], max_new_cap=8)
    finally:
        signal.signal(signal.SIGTERM, prev_term)
    assert second.restored_streams == 2
    assert sum(m.get("done", False) for _o, _v, m in node2.sent) == 2
    assert second.ttft.count == 0 and not any(_counts(second).values())
    assert tracer._edges == {}


def test_an_engine_error_is_no_first_token():
    engine = _stub(max_slots=1)
    collect, calls = engine.collect, [0]

    def wedge():
        calls[0] += 1
        if calls[0] > 2:
            raise RuntimeError("device wedged")
        return collect()

    engine.collect = wedge
    node, metrics, tracer, error = _serve(
        engine, [_req("ab", "ab", 8), _req("cd", "cd", 8)], max_new_cap=8)
    assert "device wedged" in str(error)
    # one stream had its first token, the parked one got an error alone
    assert metrics.ttft.count == 1
    _assert_tiled(metrics)
    assert tracer._edges == {}


def test_the_tracers_dict_is_empty_after_a_thousand_requests():
    engine = _stub(max_slots=8, window=4)
    events = [_req(f"r{i}", "x" * (3 + i % 40), 1 + i % 3) for i in range(1000)]
    events[500] = _req("r500", "x" * 500, 2)  # one of them rejected
    node, metrics, tracer, _ = _serve(engine, events)
    assert metrics.requests == 1000 and metrics.ttft.count == 999
    assert set(_counts(metrics).values()) == {0, 999}
    assert tracer._edges == {} and tracer._ctx == {}


def test_the_fronts_stamps_are_observed_at_intake_and_only_where_both_are_there():
    now = time.time_ns()
    stamped = {"t_http_ns": now - 5_000_000, "t_publish_ns": now - 3_000_000}
    events = [
        _req("both", "hello", 2, **stamped),
        _req("neither", "hello", 2),
        _req("one", "hello", 2, t_http_ns=now),
        _req("not-a-stamp", "hello", 2, t_http_ns="soon", t_publish_ns=None),
        _req("rejected", "x" * 500, 2, **stamped),  # observed all the same
    ]
    node, metrics, tracer, _ = _serve(_stub(max_slots=4), events)
    front, route_in = (metrics.stages[name] for name in ARRIVAL)
    assert front.values == [2000.0, 2000.0]
    assert route_in.count == 2 and all(3000.0 <= v < 60e6 for v in route_in.values)
    assert metrics.ttft.count == 4


def test_t_emit_ns_rides_a_streams_first_message_only():
    node, metrics, _tracer, _ = _serve(_stub(max_slots=2, window=4), [
        _req("a", "hello", 9), _req("b", "hello there", 9), _req("no", "x" * 500, 9),
    ])
    before = time.time_ns()
    by_rid: dict[str, list[dict]] = {}
    for _o, _v, meta in node.sent:
        by_rid.setdefault(meta["request_id"], []).append(meta)
    assert len(by_rid["a"]) >= 3 and len(by_rid["b"]) >= 3
    for rid in ("a", "b"):
        first, *rest = by_rid[rid]
        assert first["seq"] == 0 and 0 < first["t_emit_ns"] <= before
        assert all("t_emit_ns" not in m for m in rest)
    # a reject is a stream's first message and no first token
    assert [m["finish"] for m in by_rid["no"]] == ["rejected"]
    assert "t_emit_ns" not in by_rid["no"][0]


def test_the_table_is_closed_and_says_who_holds_each_row():
    assert list(REQUEST_STAGES) == [
        "front", "route_in", "intake", "backlog", "prefill_queue", "prefill",
        "first_emit", "route_out", "sse"]
    assert telemetry.stages_observed("arrival") == ARRIVAL
    assert telemetry.stages_observed("first_send") == TILED
    assert telemetry.stages_observed("api") == ["route_out", "sse"]
    assert stage_histogram_key("prefill_queue") == "stage_prefill_queue_us"
    # two rows name the histograms that held them before the table did
    assert stage_histogram_key("intake") == "phase_intake_handle_input_us"
    assert stage_histogram_key("backlog") == "backlog_wait_us"
    with pytest.raises(KeyError):
        stage_histogram_key("other")
    tracer = telemetry.ServingTracer()
    tracer.stage_histograms = {}  # a sink that lacks the table's rows
    tracer.request_pushed("k", 1.0)
    tracer.request_admitted("k", 0.5)
    tracer.request_chunk("k", 2.0)
    tracer.request_chunk("k", 3.0)  # a later chunk is no edge
    tracer.request_token("k", 4.0)
    with pytest.raises(KeyError):
        tracer.request_sent("k", 5.0)
    assert tracer._edges == {}
    # edges out of order, or of a request never pushed, are passed over
    tracer.request_token("k", 1.0)
    tracer.request_pushed("j", 1.0)
    tracer.request_token("j", 2.0)
    tracer.request_sent("j", 3.0)
    tracer.request_sent("j", 4.0)
    assert tracer._edges == {}


def test_the_snapshot_has_one_histogram_a_recorded_row_and_the_other_planes_name_none():
    snap = ServingMetrics().snapshot()
    recorded = [stage_histogram_key(s) for s in ARRIVAL + TILED]
    assert [k for k in snap if k.startswith("stage_")] == recorded
    for stage in REQUEST_STAGES:
        by = REQUEST_STAGES[stage]
        key = stage_histogram_key(stage)
        # every row of this process's is a histogram of its snapshot,
        # the api's two are in none of its keys
        assert (key in snap) == (by != "api"), stage
        if by != "api":
            assert set(snap[key]) >= {"count", "sum_us", "counts"}
    for name in ("prom.py", "alerts.py", "metrics_history.py",
                 "cli/metrics_view.py", "cli/top_view.py"):
        text = (ROOT / "dora_tpu" / name).read_text()
        assert "stage_" not in text and ".stages" not in text, name


def test_request_stages_is_the_only_place_that_spells_a_stage():
    new = [s for s in REQUEST_STAGES
           if stage_histogram_key(s) == f"stage_{s}_us" and s != "front"]
    assert len(new) == 6
    spelled = re.compile(
        r"stage_(%s)_us|[\"'](%s)[\"']" % ("|".join(REQUEST_STAGES), "|".join(new)))
    for path in sorted((ROOT / "dora_tpu").rglob("*.py")):
        if path.name != "telemetry.py":
            found = spelled.findall(path.read_text())
            assert not found, (path, found)


def test_under_tracing_the_ring_gains_no_span_kind():
    flight = telemetry.FlightRecorder(size=4096, enabled=True)
    tracer = telemetry.ServingTracer(flight, telemetry.TracingState(True))
    tracer.stage_histograms = ServingMetrics().stages
    now = time.time_ns()
    tracer.request_arrived({"t_http_ns": now - 9, "t_publish_ns": now - 5}, now)
    tracer.request_pushed("k", 1.0)
    tracer.request_admitted("k", 0.5)
    tracer.request_chunk("k", 2.0)
    tracer.request_token("k", 3.0)
    tracer.request_sent("k", 4.0)
    tracer.request_pushed("gone", 1.0)
    tracer.release("gone")
    assert flight.events() == [] and tracer._edges == {}
    assert tracer.stage_histograms["prefill"].count == 1


# ---------------------------------------------------------------------------
# the profiler capture: its deadline is the loop's to meet, every turn
# ---------------------------------------------------------------------------


class _ProfileReplies:
    def __init__(self):
        self.replies: list[tuple] = []

    def report_profile(self, artifact, error):
        self.replies.append((artifact, error))


def _profile(action: str, seconds: float = 0.0) -> dict:
    return {"type": "PROFILE", "metadata": {"action": action, "seconds": seconds}}


def test_a_capture_whose_deadline_passes_between_two_reports_stops_at_the_next_turn():
    clock = Clock()
    engine = PhasedEngine(clock, slots=1, cap=400)  # some 130 turns of 30 ms
    edges: list[tuple[str, float]] = []
    reports: list[float] = []
    replies = _ProfileReplies()

    def stop(out_dir, start_error):
        clock.tick(0.5)  # the write holds the loop
        return out_dir

    capture = ProfileCapture(
        replies, telemetry.ServingTracer(), clock,
        lambda edge: edges.append((edge, clock())),
        start=lambda out_dir: None, stop=stop,
    )
    run = _drive(
        [(100.0, _input("a")), (100.2, _profile("start", 0.1)),
         (100.25, _profile("start", 5.0))],
        engine=engine, clock=clock, capture=capture,
    )
    assert run["error"] is None
    (_, started), (_, stopped) = edges
    assert [e for e, _ in edges] == ["start", "stop"]
    # met within a turn of the loop (some 30 ms), where the report that
    # used to look at it came up to a second later
    assert 0.1 <= stopped - started < 0.1 + 0.05
    # the second start came while the first was active: refused; then
    # the artifact, reported by the turn that wrote it
    assert replies.replies[0] == ("", "capture already active")
    artifact, error = replies.replies[1]
    assert len(replies.replies) == 2 and "capture-" in artifact and error is None
    assert not capture.active
    # the write is the loop's own time, in housekeeping as before
    assert max(i for i, c in enumerate(
        run["metrics"].phases["housekeeping"].counts) if c) == 19  # 0.26-0.52 s


def test_a_stop_event_writes_at_once_and_a_failed_write_is_said():
    clock = Clock()
    replies = _ProfileReplies()
    edges: list[str] = []

    def stop(out_dir, start_error):
        raise RuntimeError("profiler left no artifact")

    capture = ProfileCapture(
        replies, telemetry.ServingTracer(), clock, edges.append,
        start=lambda out_dir: None, stop=stop,
    )
    capture.handle(_profile("stop"))
    assert replies.replies == [("", "no capture active")]
    capture.handle(_profile("start", 60.0))
    capture.tick(clock())
    assert capture.active and edges == ["start"]  # the deadline is far
    capture.handle(_profile("stop"))
    assert replies.replies[1:] == [("", "RuntimeError: profiler left no artifact")]
    assert not capture.active and edges == ["start", "stop"]
    capture.handle(_profile("stop"))
    assert replies.replies[2:] == [("", "no capture active")]


def test_a_capture_that_cannot_start_replies_and_stays_inactive():
    replies = _ProfileReplies()

    def start(out_dir):
        raise RuntimeError("no profiler")

    capture = ProfileCapture(
        replies, telemetry.ServingTracer(), Clock(), lambda edge: None,
        start=start, stop=lambda out_dir, start_error: out_dir,
    )
    capture.handle(_profile("start", 1.0))
    assert replies.replies == [("", "RuntimeError: no profiler")]
    assert not capture.active
