"""The serving loop's phases (``telemetry.LOOP_PHASES``): one turn of
``llm_server._run_loop`` is tiled by them, each leaving goes to three
sinks by one call, and what ``dispatch_gap_us`` observes is the sum of
the phases that lie in it.

The loop is driven over a fake node, a fake engine that enters the
engine's phases where ``PagedBatchEngine`` does, and a clock that moves
only where the script says — so every sum below is exact.
"""

from __future__ import annotations

import subprocess
import sys
from pathlib import Path

import pytest

from dora_tpu import telemetry, tracing
from dora_tpu.metrics import ServingMetrics
from dora_tpu.nodehub.llm_server import AdmissionQueue, _run_loop
from dora_tpu.telemetry import LOOP_PHASES, phase_histogram_key

ROOT = Path(__file__).resolve().parent.parent
TOP = [p for p in LOOP_PHASES if "." not in p]
IN_GAP = [p for p in TOP if LOOP_PHASES[p]]


class Clock:
    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def tick(self, dt: float) -> None:
        self.t += dt


class Annotations:
    """The profiler's side of the tracer: records each ``loop.*`` span
    as (name, enter, exit) on the fake clock, and the order of events."""

    def __init__(self, clock):
        self.clock = clock
        self.spans: list[list] = []
        self.open: list[list] = []
        self.balanced = True

    def __call__(self, name: str):
        sink = self

        class Span:
            def __enter__(self):
                self.row = [name, sink.clock(), None, len(sink.open)]
                sink.open.append(self.row)
                sink.spans.append(self.row)

            def __exit__(self, *exc):
                # left in the order opposite to entering
                sink.balanced &= sink.open.pop() is self.row
                self.row[2] = sink.clock()

        return Span()


class PhasedEngine:
    """Streams of ``cap`` tokens, K a window, one prefill chunk a
    stream. Enters the engine's phases where PagedBatchEngine does:
    chunk, set-slot, rebuild, launch, and then the read of the first
    token beside the window — or, ``blocking`` (an engine that
    speculates), the read inside the chunk's launch, before the window.
    ``ahead()`` puts the next stream's chunk behind the window that runs
    (never under ``blocking``: its chunks are final), and the dispatch
    that finds it adopts it, set-slot and all, as part of its rebuild."""

    tracer = None
    CHUNK, FIRST, SET_SLOT, REBUILD, LAUNCH, WAIT, UNPACK = (
        0.003, 0.005, 0.0003, 0.001, 0.0005, 0.020, 0.0002
    )

    def __init__(self, clock, slots=2, k=3, cap=13, fail_at=None,
                 blocking=False):
        self.clock, self.slots, self.k, self.cap = clock, slots, k, cap
        self.fail_at, self.blocking = fail_at, blocking
        self.streams: dict[str, int] = {}
        self.prefillq: list[str] = []
        self.decoding: list[str] = []
        self.dirty = False
        self.in_flight = False
        self.launched_at = None
        self.dispatches = self.chunks = self.rebuilds = self.windows = 0
        self.reads = self.chunks_ahead = 0
        self.went = None  # the stream whose chunk went ahead
        self.can_admit_calls = 0
        self.collect_returns: list[float] = []
        self.launch_ends: list[float] = []

    @property
    def active(self) -> int:
        return len(self.streams)

    def can_admit(self, n, max_new, adapter=None) -> bool:
        self.can_admit_calls += 1
        self.clock.tick(0.0001)
        return len(self.streams) < self.slots

    def admit_blocker(self, n, max_new, adapter=None):
        self.can_admit_calls += 1
        self.clock.tick(0.00005)
        return "capacity"

    def submit(self, key, ids, max_new) -> None:
        self.streams[key] = 0
        self.prefillq.append(key)

    def _read(self, key):
        self.clock.tick(self.FIRST)
        if self.fail_at == self.dispatches:
            raise RuntimeError("the device fell over")
        return (key, 0, False)

    def dispatch(self):
        tr, tick = self.tracer, self.clock.tick
        self.dispatches += 1
        self.launched_at = None
        first, read = [], None
        went, self.went = self.went, None
        if went is not None or self.prefillq:
            if went is not None:
                tr.switch("rebuild")
            else:
                tr.switch("chunk_launch")
                tick(self.CHUNK)
                self.chunks += 1
            tick(self.SET_SLOT)
            key = self.prefillq.pop(0)
            assert went in (None, key)
            self.streams[key] = 1
            self.decoding.append(key)
            self.dirty = True
            if self.blocking:
                tr.enter("first_token_wait")
                first.append(self._read(key))
                tr.leave()
            else:
                read = key
        if self.decoding:
            if self.dirty:
                if went is None:
                    tr.switch("rebuild")
                tick(self.REBUILD)
                self.rebuilds += 1
                self.dirty = False
            tr.switch("window_launch")
            tick(self.LAUNCH)
            self.in_flight = True
            self.windows += 1
            self.launch_ends.append(self.clock())
            if read is not None:
                self.launched_at = tr.switch("first_token_read")
                self.reads += 1
                first.append(self._read(read))
        return first

    def ahead(self):
        if (not self.in_flight or not self.prefillq or self.blocking
                or self.went is not None):
            return
        self.tracer.switch("chunk_ahead")
        self.clock.tick(self.CHUNK)
        self.chunks += 1
        self.chunks_ahead += 1
        self.went = self.prefillq[0]

    def collect(self):
        if not self.in_flight:
            return []
        tr, tick = self.tracer, self.clock.tick
        self.in_flight = False
        tr.switch("window_wait")
        tick(self.WAIT)
        tr.switch("unpack")
        tick(self.UNPACK)
        out = []
        for key in list(self.decoding):
            for _ in range(self.k):
                self.streams[key] += 1
                done = self.streams[key] >= self.cap
                out.append((key, self.streams[key], done))
                if done:
                    del self.streams[key]
                    self.decoding.remove(key)
                    self.dirty = True
                    break
        self.collect_returns.append(self.clock())
        return out


class TimedNode:
    """Delivers ``(due, event)`` once the clock has reached ``due``; a
    timed recv waits (moves the clock) up to its timeout."""

    POLL = 0.00001

    def __init__(self, clock, script):
        self.clock = clock
        self.script = sorted(script, key=lambda e: e[0])
        self.stream_ended = False
        self.timed_waits: list[float] = []

    def recv(self, timeout=None):
        self.clock.tick(self.POLL)
        if timeout:
            due = self.script[0][0] if self.script else self.clock()
            self.timed_waits.append(max(0.0, min(timeout, due - self.clock())))
            self.clock.tick(self.timed_waits[-1])
        if self.script and self.script[0][0] <= self.clock():
            return self.script.pop(0)[1]
        if not self.script:
            self.stream_ended = True
        return None


def _input(rid: str) -> dict:
    return {"type": "INPUT", "metadata": {"request_id": rid}, "value": rid}


def _drive(script, *, engine=None, clock=None, tracing_on=False, **hooks):
    clock = clock or Clock()
    engine = engine or PhasedEngine(clock)
    metrics = ServingMetrics()
    sink = Annotations(clock)
    flight = telemetry.FlightRecorder(size=4096, enabled=tracing_on)
    tracer = telemetry.ServingTracer(
        flight, telemetry.TracingState(tracing_on), clock=clock
    )
    tracer.histograms = metrics.phases
    tracer.annotation = sink
    engine.tracer = tracer
    backlog = AdmissionQueue(
        engine, lambda k, ids, mn, adapter: engine.submit(k, ids, mn),
        clock=clock, tracer=tracer,
    )
    samples: list[dict] = []

    def handle_input(event):
        clock.tick(0.001)  # parse, encode
        backlog.push(event["metadata"]["request_id"], [1, 2], engine.cap)

    def emit(key, tokens, done):
        clock.tick(0.002)

    def report(now):
        clock.tick(0.004)

    def on_step():
        # right behind collect()'s return, before the clock moves again
        samples.append({
            "t": clock(),
            "gap_sum_us": metrics.dispatch_gap.sum_us,
            "gap_count": metrics.dispatch_gap.count,
            **{p: metrics.phases[p].sum_us for p in LOOP_PHASES},
        })
        clock.tick(0.0007)

    run = dict(
        node=TimedNode(clock, script), engine=engine, metrics=metrics,
        sink=sink, tracer=tracer, flight=flight, samples=samples,
        clock=clock, start=clock(), error=None,
    )
    try:
        _run_loop(
            run["node"], engine, backlog, metrics, handle_input, emit, report,
            clock=clock, on_step=on_step, tracer=tracer, **hooks,
        )
    except RuntimeError as e:
        run["error"] = e
    return run


# two streams that overlap, then a lull the loop parks in, then a third
SCRIPT = [(100.0, _input("a")), (100.01, _input("b")), (100.9, _input("c"))]


def test_the_phases_between_two_collect_returns_add_up_to_the_clocks_advance():
    run = _drive(SCRIPT)
    samples = run["samples"]
    assert len(samples) >= 6
    for a, b in zip(samples, samples[1:]):
        gained = sum(b[p] - a[p] for p in TOP)
        assert gained == pytest.approx((b["t"] - a["t"]) * 1e6, abs=1e-3)
    # the samples were taken where collect() returned — but for the
    # flush that comes first where it left the engine idle
    returns = run["engine"].collect_returns
    assert len(samples) == len(returns)
    late = [round(s["t"] - r, 9) for s, r in zip(samples, returns)]
    assert late.count(0.0) >= 6 and set(late) <= {0.0, 0.002, 0.004}


def test_the_whole_run_is_tiled_each_phase_beginning_where_the_last_ended():
    run = _drive(SCRIPT)
    top = [s for s in run["sink"].spans if s[3] == 0]
    assert top[0][1] == run["start"] and top[-1][2] == run["clock"]()
    for a, b in zip(top, top[1:]):
        assert b[1] == a[2], (a, b)
    total = sum(run["metrics"].phases[p].sum_us for p in TOP)
    assert total == pytest.approx((run["clock"]() - run["start"]) * 1e6, abs=1e-3)


@pytest.mark.parametrize("streams,slots,ahead", [(2, 2, 1), (6, 6, 5)])
def test_the_gaps_phases_add_up_to_what_dispatch_gap_us_gained(streams, slots, ahead):
    # a stretch in which the engine never idles: every turn observes a gap
    engine = PhasedEngine(Clock(), slots=slots, cap=40)
    run = _drive([(100.0, _input(f"r{i}")) for i in range(streams)],
                 engine=engine, clock=engine.clock)
    samples = run["samples"]
    a, b = samples[0], samples[-2]  # the last collect() left the engine idle
    assert b["gap_count"] - a["gap_count"] == len(samples) - 2 >= 10
    parts = sum(b[p] - a[p] for p in IN_GAP)
    assert parts == pytest.approx(b["gap_sum_us"] - a["gap_sum_us"], abs=1e-3)
    assert IN_GAP == ["housekeeping", "admit", "intake", "chunk_launch",
                      "first_token_wait", "rebuild", "window_launch", "emit_alone"]
    # emit_us is observed as before: once a window that ran beside a flush
    assert run["metrics"].emit.count == engine.windows
    assert run["metrics"].phases["emit"].count == engine.windows
    # a chunk that found a window to go behind left the gap: it is
    # entered under chunk_ahead, outside it, and the two phases' counts
    # add up to the chunks run
    phases = run["metrics"].phases
    assert not LOOP_PHASES["chunk_ahead"] and LOOP_PHASES["chunk_launch"]
    assert phases["chunk_ahead"].count == engine.chunks_ahead == ahead
    assert (phases["chunk_launch"].count + phases["chunk_ahead"].count
            == engine.chunks == streams)
    assert phases["chunk_ahead"].sum_us == pytest.approx(
        ahead * engine.CHUNK * 1e6, abs=1e-3)
    # in line a chunk holds its set-slot; one that went ahead leaves it
    # to the rebuild of the dispatch that adopts it
    assert phases["chunk_launch"].sum_us == pytest.approx(
        (streams - ahead) * (engine.CHUNK + engine.SET_SLOT) * 1e6, abs=1e-3)
    assert phases["rebuild"].count == engine.rebuilds
    assert phases["first_token_read"].count == engine.reads == streams


def test_a_phase_that_did_not_run_observed_nothing():
    engine = PhasedEngine(Clock(), slots=1, cap=30)
    run = _drive([(100.0, _input("a"))], engine=engine, clock=engine.clock)
    phases = run["metrics"].phases
    assert phases["chunk_launch"].count == engine.chunks == 1
    # the one first token was read beside the window it joined
    assert phases["first_token_read"].count == engine.reads == 1
    assert phases["first_token_wait"].count == 0
    # membership changed twice: the stream began, the stream ended
    assert phases["rebuild"].count == engine.rebuilds == 1
    assert phases["window_launch"].count == engine.windows == 10
    assert phases["window_wait"].count == phases["unpack"].count == 10
    # the one flush that no window ran beside: the last, with the engine idle
    assert phases["emit_alone"].count == 1
    assert phases["chunk_launch"].sum_us == pytest.approx(
        (engine.CHUNK + engine.SET_SLOT) * 1e6, abs=1e-3)
    assert phases["first_token_read"].sum_us == pytest.approx(
        engine.FIRST * 1e6, abs=1e-3)
    # the read left window_launch: that phase holds its own time alone
    assert phases["window_launch"].sum_us == pytest.approx(
        10 * engine.LAUNCH * 1e6, abs=1e-3)


def test_a_read_that_blocks_the_launch_is_carved_out_of_the_chunks_launch():
    engine = PhasedEngine(Clock(), slots=1, cap=30, blocking=True)
    run = _drive([(100.0, _input("a"))], engine=engine, clock=engine.clock)
    phases = run["metrics"].phases
    assert phases["first_token_wait"].count == 1
    assert phases["first_token_read"].count == engine.reads == 0
    # chunk_launch holds its own time, not the wait carved out of it
    assert phases["chunk_launch"].sum_us == pytest.approx(
        (engine.CHUNK + engine.SET_SLOT) * 1e6, abs=1e-3)
    assert phases["first_token_wait"].sum_us == pytest.approx(
        engine.FIRST * 1e6, abs=1e-3)


@pytest.mark.parametrize("blocking", [False, True])
def test_the_gap_ends_on_the_stamp_that_leaves_window_launch(blocking):
    # the engine never idles, so every turn observes a gap; most of
    # the dispatches read a first token, beside the window or (blocking)
    # before its launch
    script = [(100.0, _input(f"r{i}")) for i in range(12)]
    engine = PhasedEngine(Clock(), slots=3, cap=10, blocking=blocking)
    run = _drive(script, engine=engine, clock=engine.clock)
    metrics, phases = run["metrics"], run["metrics"].phases
    assert engine.chunks == 12 and engine.reads == (0 if blocking else 12)
    # each observed gap runs from a collect()'s return to the end of the
    # next launch — not to dispatch()'s return, a read later
    ends, returns = engine.launch_ends, engine.collect_returns
    gaps = [e - r for e, r in zip(ends[1:], returns)]
    assert metrics.dispatch_gap.count == len(gaps) == engine.windows - 1 >= 12
    assert metrics.dispatch_gap.sum_us == pytest.approx(sum(gaps) * 1e6, abs=1e-2)
    # and its phases still add up to it, the read among them only where
    # the launch waited for it
    samples = run["samples"]
    a, b = samples[0], samples[-2]
    parts = sum(b[p] - a[p] for p in IN_GAP)
    assert parts == pytest.approx(b["gap_sum_us"] - a["gap_sum_us"], abs=1e-3)
    assert b["gap_count"] - a["gap_count"] == len(samples) - 2
    where = "first_token_wait" if blocking else "first_token_read"
    assert phases[where].sum_us == pytest.approx(12 * engine.FIRST * 1e6, abs=1e-3)
    assert not LOOP_PHASES["first_token_read"] and LOOP_PHASES["first_token_wait"]
    # the pair the server's exit line prints: how often the read left the gap
    assert metrics.first_token_reads() == (
        {"deferred": 0, "blocking": 12, "deferred_share": 0.0} if blocking
        else {"deferred": 12, "blocking": 0, "deferred_share": 1.0})
    assert ServingMetrics().first_token_reads()["deferred_share"] is None
    # emit_us begins where the read ended, not where the gap did
    assert metrics.emit.count == engine.windows
    assert metrics.emit.sum_us == pytest.approx(phases["emit"].sum_us, abs=1e-3)


def test_only_the_timed_recv_of_an_idle_turn_is_parked():
    run = _drive(SCRIPT)
    phases, node = run["metrics"].phases, run["node"]
    assert max(node.timed_waits) == 0.25
    # parked = the timed waits (+ the poll's own cost), nothing else
    assert phases["parked"].count == len(node.timed_waits) >= 4
    assert phases["parked"].sum_us == pytest.approx(
        (sum(node.timed_waits) + phases["parked"].count * node.POLL) * 1e6, abs=1e-3)
    # the handler of the event that ended the wait is intake, and no
    # occurrence of intake is anywhere near a quarter of a second
    assert phases["intake.handle_input"].count == 3
    assert phases["intake"].sum_us < 0.01 * 1e6


@pytest.mark.parametrize("blocking", [True, False])
def test_a_dispatch_that_raises_leaves_every_phase_left_and_the_sink_balanced(blocking):
    failed = []
    engine = PhasedEngine(Clock(), fail_at=1, blocking=blocking)
    run = _drive([(100.0, _input("a"))], engine=engine, clock=engine.clock,
                 on_engine_error=lambda: failed.append(True))
    assert isinstance(run["error"], RuntimeError) and failed == [True]
    sink = run["sink"]
    assert run["tracer"]._open == [] and sink.open == [] and sink.balanced
    assert all(s[2] is not None for s in sink.spans)
    # the phases that were open when it raised were observed, once: the
    # chunk's launch and the wait inside it, or the read after the launch
    phases = run["metrics"].phases
    assert phases["chunk_launch"].count == 1
    assert phases["first_token_wait"].count == blocking
    assert phases["first_token_read"].count == (not blocking)
    assert phases["window_launch"].count == (not blocking)


@pytest.mark.parametrize("blocking", [True, False])
def test_the_annotation_sink_sees_loop_names_children_inside_parents(blocking):
    engine = PhasedEngine(Clock(), blocking=blocking)
    run = _drive(SCRIPT, engine=engine, clock=engine.clock)
    sink = run["sink"]
    assert sink.balanced and not sink.open
    assert {s[0] for s in sink.spans} <= {f"loop.{p}" for p in LOOP_PHASES}
    parents = {"loop.intake.handle_input": {"loop.intake"},
               # drain() runs in admit, and under a handler's push()
               "loop.admit.can_admit": {"loop.admit", "loop.intake.handle_input"}}
    if blocking:
        parents["loop.first_token_wait"] = {"loop.chunk_launch"}
    else:  # the read beside the window is a phase of the turn's own tiling
        assert any(s[0] == "loop.first_token_read" and s[3] == 0 for s in sink.spans)
    seen = set()
    for name, start, end, depth in sink.spans:
        if depth == 0:
            assert name not in parents
            continue
        inside = [s for s in sink.spans
                  if s[3] == depth - 1 and s[1] <= start and end <= s[2]]
        assert inside and inside[-1][0] in parents[name], (name, inside)
        seen.add(name)
    assert seen == set(parents)
    assert run["metrics"].phases["admit.can_admit"].count == run["engine"].can_admit_calls


def test_without_a_sink_the_loop_runs_and_jax_stays_out():
    code = """
import sys
import dora_tpu.telemetry
sys.path.insert(0, {tests!r})
import test_loop_phases as t
clock = t.Clock()
engine = t.PhasedEngine(clock)
metrics = t.ServingMetrics()
backlog = t.AdmissionQueue(engine, lambda k, ids, mn, ad: engine.submit(k, ids, mn), clock=clock)
t._run_loop(t.TimedNode(clock, t.SCRIPT), engine, backlog, metrics,
            lambda ev: backlog.push(ev["metadata"]["request_id"], [1], 13),
            lambda key, tokens, done: None, lambda now: None, clock=clock)
assert engine.tracer.annotation is None
assert metrics.phases["window_wait"].count == engine.windows > 3
assert "jax" not in sys.modules, "jax was imported"
print("ok")
""".format(tests=str(ROOT / "tests"))
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, timeout=120,
                         capture_output=True, text=True)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr[-2000:]


def test_the_snapshot_has_one_histogram_a_row_and_the_other_planes_name_none():
    snap = ServingMetrics().snapshot()
    keys = [phase_histogram_key(p) for p in LOOP_PHASES]
    assert len(set(keys)) == len(LOOP_PHASES) == 16
    for key in keys:
        assert set(snap[key]) >= {"count", "sum_us", "counts"}
    assert "phase_admit_can_admit_us" in keys and "phase_intake_handle_input_us" in keys
    assert [k for k in snap if k.startswith("phase_")] == keys
    for name in ("prom.py", "alerts.py", "metrics_history.py",
                 "cli/metrics_view.py", "cli/top_view.py"):
        text = (ROOT / "dora_tpu" / name).read_text()
        assert "phase_" not in text and ".phases" not in text, name


def test_loop_phase_spans_come_out_on_the_engine_track():
    run = _drive(SCRIPT, tracing_on=True)
    events = [list(e) for e in run["flight"].events() if e[2] == "s_loop_phase"]
    assert {e[3] for e in events} == {
        p for p in LOOP_PHASES if run["metrics"].phases[p].count}
    merged = tracing.merge_trace_snapshots([{
        "machine": "A", "wall_ns": 0, "hlc_ns": 0, "processes": {"llm": events},
    }])
    trace = tracing.to_chrome_trace(merged)
    assert tracing.validate_chrome_trace(trace) == []
    spans = [ev for ev in trace["traceEvents"] if ev["ph"] == "X"]
    assert spans and all(ev["tid"] == tracing.ENGINE_TID for ev in spans)
    assert {ev["name"] for ev in spans} == {f"loop_phase {e[3]}" for e in events}
    waits = [ev for ev in spans if ev["name"] == "loop_phase window_wait"]
    assert waits and all(
        ev["dur"] == pytest.approx(PhasedEngine.WAIT * 1e6) for ev in waits)


def test_with_tracing_off_no_phase_reaches_the_ring():
    run = _drive(SCRIPT)
    assert run["flight"].events() == []


def test_a_switch_returns_its_one_stamp_and_the_table_is_closed():
    clock = Clock()
    tracer = telemetry.ServingTracer(clock=clock)  # no sink: stamps alone
    assert tracer.switch("housekeeping") == 100.0
    clock.tick(1.0)
    hists = tracer.histograms = ServingMetrics().phases
    assert tracer.enter("admit.can_admit") == 101.0
    clock.tick(0.5)
    assert tracer.switch("admit") == 101.5  # closes the child and its parent
    assert hists["housekeeping"].sum_us == pytest.approx(1.5e6)  # children stay in
    assert hists["admit.can_admit"].sum_us == pytest.approx(0.5e6)
    clock.tick(1.0)
    assert tracer.close() == 102.5
    assert hists["admit"].count == 1
    with pytest.raises(KeyError):
        tracer.switch("other")
    assert tracer._open == []


@pytest.mark.parametrize("ahead", [False, True])
def test_the_real_engine_reports_its_phases_once_an_occurrence(ahead):
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    engine = make_stub_paged_engine(max_slots=4, window=4, chunk=16, max_seq=64)
    phases = ServingMetrics().phases
    engine.tracer.histograms = phases
    prompts = {"a": 5, "b": 20, "c": 40, "d": 7, "e": 33}  # 1, 2, 3, 1, 3 chunks
    pending = list(prompts)
    finals = rebuilds = windows = 0
    while pending or engine.active:
        while pending and engine.can_admit(prompts[pending[0]], 9):
            key = pending.pop(0)
            engine.submit(key, list(range(1, prompts[key] + 1)), 9)
        before = (engine._maxnew_dev, engine._bt_dec)
        finals += len(engine.dispatch())
        rebuilds += (engine._maxnew_dev is not before[0]
                     or engine._bt_dec is not before[1])
        windows += engine.in_flight
        if ahead:  # where the loop calls it: after its flush
            engine.ahead()
        engine.collect()
    engine.tracer.close()
    assert engine.chunks_run == 10 and finals == 5 and windows > 5
    # a chunk is launched in the gap or goes ahead, behind a window
    assert phases["chunk_ahead"].count == engine.chunks_ahead
    assert engine.chunks_ahead >= 6 if ahead else engine.chunks_ahead == 0
    assert (phases["chunk_launch"].count + phases["chunk_ahead"].count
            == engine.chunks_run)
    # every first token was read beside the window its stream joined
    assert phases["first_token_read"].count == finals
    assert phases["first_token_wait"].count == 0
    assert phases["rebuild"].count == rebuilds >= 5
    assert phases["window_launch"].count == windows
    assert phases["window_wait"].count == phases["unpack"].count == windows
    assert engine.dispatches == engine.chunks_run + windows
