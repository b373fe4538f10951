"""Tracing and metrics.

Reference parity: libraries/extensions/telemetry — trace context is
carried in message metadata under the ``open_telemetry_context``
parameter, serialized as a ``k:v;`` string
(telemetry/tracing/src/telemetry.rs:35-70); the daemon/runtime propagate
it across process boundaries. Works standalone (pure string codec); when
the ``opentelemetry`` package is installed and OTLP env vars are set,
spans and system metrics export for real.
"""

from __future__ import annotations

import logging
import os
import time
from contextlib import contextmanager

OTEL_CTX_KEY = "open_telemetry_context"

logger = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# tracing gate (hot-path: a single attribute check when off)
# ---------------------------------------------------------------------------


class TracingState:
    """Process-wide tracing switch (``DORA_TRACING=1``).

    The hot path (node publish, daemon route, event-stream recv) guards
    every trace-plane action behind ``TRACING.active`` — one attribute
    load when tracing is off. Daemons and nodes call
    :meth:`configure_from_env` at startup so an env knob set after module
    import (e.g. a bench A/B leg) still takes effect in-process.
    """

    __slots__ = ("active",)

    def __init__(self, active: bool = False):
        self.active = active

    def configure_from_env(self) -> None:
        self.active = os.environ.get("DORA_TRACING", "") not in ("", "0")


TRACING = TracingState(os.environ.get("DORA_TRACING", "") not in ("", "0"))


# ---------------------------------------------------------------------------
# span / trace id generation (per-process base + counter; no per-message
# os.urandom — one seed read per process, fork-safe via the pid check)
# ---------------------------------------------------------------------------

_U64 = (1 << 64) - 1
_U128 = (1 << 128) - 1


class _IdGen:
    __slots__ = ("pid", "span_base", "trace_base", "count")

    def __init__(self):
        self.pid = -1  # forces a reseed on first use (and after fork)
        self.span_base = 0
        self.trace_base = 0
        self.count = 0

    def reseed(self) -> None:
        self.pid = os.getpid()
        self.span_base = int.from_bytes(os.urandom(8), "big")
        self.trace_base = int.from_bytes(os.urandom(16), "big")
        self.count = 0


_IDS = _IdGen()


def next_span_id() -> str:
    """16-hex span id from the per-process random base + counter."""
    g = _IDS
    if g.pid != os.getpid():
        g.reseed()
    g.count += 1
    return format((g.span_base + g.count) & _U64, "016x")


def next_trace_id() -> str:
    """32-hex trace id; the counter lands in the high half so trace ids
    never collide with each other or with span ids."""
    g = _IDS
    if g.pid != os.getpid():
        g.reseed()
    g.count += 1
    return format((g.trace_base + (g.count << 64)) & _U128, "032x")


def child_context(parent_ctx: str = "") -> str:
    """A serialized child trace context: same trace id as ``parent_ctx``
    (fresh one if absent/malformed), new span id. The allocation-light
    core of :func:`span`'s SDK-less fallback, callable directly from the
    per-message hot path without generator overhead."""
    trace_id = None
    if parent_ctx:
        parent = parse_otel_context(parent_ctx).get("traceparent")
        if parent and parent.count("-") == 3:
            trace_id = parent.split("-")[1]
    if trace_id is None:
        trace_id = next_trace_id()
    return f"traceparent:00-{trace_id}-{next_span_id()}-01;"


def trace_id_of(ctx: str) -> str | None:
    """The 32-hex trace id inside a serialized context, or None."""
    if not ctx:
        return None
    parent = parse_otel_context(ctx).get("traceparent")
    if parent and parent.count("-") == 3:
        return parent.split("-")[1]
    return None


def otlp_endpoint() -> str | None:
    """Single resolution rule for the OTLP export endpoint, shared by
    tracing and metrics: ``OTEL_EXPORTER_OTLP_ENDPOINT`` wins, with
    ``DORA_JAEGER_TRACING`` (the reference's legacy spelling) as the
    fallback. Both exporters MUST use this helper so setting either
    variable lights up the whole telemetry export path."""
    return (
        os.environ.get("OTEL_EXPORTER_OTLP_ENDPOINT")
        or os.environ.get("DORA_JAEGER_TRACING")
        or None
    )


# ---------------------------------------------------------------------------
# flight recorder (hot-path forensics)
# ---------------------------------------------------------------------------


class FlightRecorder:
    """Fixed-size, allocation-free ring of timestamped hot-path events.

    The message plane records route / enqueue / drop-oldest / coalesce
    flush / fastroute hit-or-fallback events here when enabled
    (``DORA_FLIGHT_RECORDER=1``; size via ``DORA_FLIGHT_RECORDER_SIZE``,
    default 4096). ``DORA_TRACING=1`` also enables the ring — it is the
    storage for the trace plane's per-message span records
    (``t_send`` / ``t_route`` / ``t_deliver`` / ``t_recv``). Slots are
    preallocated lists mutated in place, so the steady state allocates
    nothing; when disabled, :meth:`record` is a single attribute check
    and return, so the hot path pays ~0.

    Slot layout: ``[monotonic_ns, wall_ns, kind, a, b, c]``. The wall
    clock (``time.time_ns``, the base of the HLC physical component)
    rides along so rings from different processes and machines merge
    onto one timeline — monotonic clocks have per-process epochs and
    cannot be compared across boundaries.

    Recording stays lock-free; readers (:meth:`events`,
    :meth:`events_since`) snapshot defensively and drop slots a
    concurrent writer may have overwritten mid-copy. Saturation is NOT
    silent: ``dropped`` counts events that wrapped out of the ring
    before the incremental reader shipped them, and the node flusher
    turns growth of that counter into a ``trace_truncated`` event on
    the timeline. The ring is dumped on SIGUSR2 alongside the asyncio
    task dump (daemons) or via :func:`install_flight_dump` (nodes).
    """

    __slots__ = ("enabled", "dropped", "_slots", "_size", "_idx")

    def __init__(self, size: int = 4096, enabled: bool = False):
        self._size = max(1, size)
        self._slots = [[0, 0, "", None, None, None] for _ in range(self._size)]
        self._idx = 0
        self.enabled = enabled
        #: events overwritten before :meth:`events_since` could ship
        #: them (ring wrap between incremental reads)
        self.dropped = 0

    def configure_from_env(self) -> None:
        """Re-read the env knobs (daemons/nodes call this at startup, so
        a knob set after module import — e.g. a bench A/B leg — still
        takes effect in-process). A disabled->enabled toggle clears the
        ring: events from a previous enablement must not leak into a new
        capture."""
        enabled = (
            os.environ.get("DORA_FLIGHT_RECORDER", "") not in ("", "0")
            or os.environ.get("DORA_TRACING", "") not in ("", "0")
        )
        size = int(os.environ.get("DORA_FLIGHT_RECORDER_SIZE", "0") or "0")
        if size > 0 and size != self._size:
            self._size = size
            self._slots = [[0, 0, "", None, None, None] for _ in range(size)]
            self._idx = 0
        if enabled and not self.enabled:
            self.clear()
        self.enabled = enabled

    def record(self, kind: str, a=None, b=None, c=None) -> None:
        if not self.enabled:
            return
        slot = self._slots[self._idx % self._size]
        slot[0] = time.monotonic_ns()
        slot[1] = time.time_ns()
        slot[2] = kind
        slot[3] = a
        slot[4] = b
        slot[5] = c
        self._idx += 1

    def _snapshot(self, start: int) -> list[tuple]:
        """Copy slots [start, idx) oldest first, then drop any prefix a
        concurrent writer advanced over while we copied (those slots were
        overwritten under us and may be torn)."""
        idx = self._idx
        start = max(start, idx - self._size)
        out = [tuple(self._slots[i % self._size]) for i in range(start, idx)]
        overrun = (self._idx - self._size) - start
        if overrun > 0:
            out = out[overrun:] if overrun < len(out) else []
        # An unwritten slot has no kind (possible when a writer bumped
        # _idx but hasn't filled the slot yet).
        return [e for e in out if e[2]]

    def events(self) -> list[tuple]:
        """Recorded events, oldest first (filled slots only); safe to
        call while another thread records."""
        return self._snapshot(self._idx - min(self._idx, self._size))

    def events_since(self, cursor: int) -> tuple[list[tuple], int]:
        """Events recorded since ``cursor`` (a previous return value; 0
        to start) plus the new cursor — the incremental-shipping API the
        node flusher uses to stream ring growth to its daemon. Events
        that wrapped out between reads are gone; they are COUNTED
        (``dropped``) so saturation is observable, not silent."""
        idx = self._idx
        floor = idx - min(idx, self._size)
        if cursor < floor:
            self.dropped += floor - cursor
        return self._snapshot(max(cursor, floor)), idx

    def clear(self) -> None:
        self._idx = 0
        self.dropped = 0
        for slot in self._slots:
            slot[0] = 0
            slot[1] = 0
            slot[2] = ""
            slot[3] = None
            slot[4] = None
            slot[5] = None

    def dump(self, file=None) -> None:
        import sys

        file = file or sys.stderr
        events = self.events()
        print(
            f"--- flight recorder ({len(events)} events, "
            f"{self._idx} recorded total, {self.dropped} dropped)",
            file=file,
        )
        for mono, _wall, kind, a, b, c in events:
            extra = " ".join(str(x) for x in (a, b, c) if x is not None)
            print(f"  {mono} {kind} {extra}".rstrip(), file=file)
        file.flush()


#: Process-wide recorder; env-configured at import, re-read by
#: Daemon()/Node() via configure_from_env so late env changes count.
FLIGHT = FlightRecorder(
    size=int(os.environ.get("DORA_FLIGHT_RECORDER_SIZE", "4096") or "4096"),
    enabled=(
        os.environ.get("DORA_FLIGHT_RECORDER", "") not in ("", "0")
        or os.environ.get("DORA_TRACING", "") not in ("", "0")
    ),
)


# ---------------------------------------------------------------------------
# serving-engine lifecycle tracer (request spans on the cluster timeline)
# ---------------------------------------------------------------------------


#: The serving loop's phases — the ONE table of their names; value: does
#: the phase lie inside what ``dispatch_gap_us`` observes (host time from
#: ``collect()`` returning to the next launch)? One turn of
#: ``llm_server._run_loop`` is tiled by them: the loop and the engine
#: :meth:`ServingTracer.switch` from one to the next on ONE clock read,
#: so between two ``collect()`` returns every nanosecond lies in exactly
#: one phase. A dotted name is a child, entered inside its parent and
#: counted in the parent's histogram too. ``first_token_wait`` runs
#: inside ``chunk_launch`` (inside ``rebuild`` behind a chunk that went
#: ahead) and is carved out of it: the host is blocked
#: on the device there, which is no part of launching a chunk. The gap
#: ends on the stamp that leaves ``window_launch``: the engine's own
#: switch to ``first_token_read`` where a first token is read beside
#: the window (``PagedBatchEngine.launched_at``), else the loop's to
#: ``emit``.
LOOP_PHASES = {
    # on_tick, on_step, the 1 Hz report() / fleet_tick
    "housekeeping": True,
    # backlog.drain(); the child: its calls into engine.can_admit /
    # admit_blocker, the prefix-cache walk among them
    "admit": True,
    "admit.can_admit": True,
    # the burst of node.recv and its handlers; the child: handle_input
    # (parse, encode, push)
    "intake": True,
    "intake.handle_input": True,
    # dispatch(), where no chunk went ahead of this period: the chunk's
    # operands and enqueue, the prefix-cache
    # insert, _set_slot (the first token goes to its slot on the device)
    # — less first_token_wait: the read of a final chunk's first token
    # where it still comes BEFORE the launch and blocks it (speculation's
    # history mirror needs the value; no window follows), which is
    # device time inside the host's gap
    "chunk_launch": True,
    "first_token_wait": True,
    # dispatch(): membership and block table, the jnp.asarray calls;
    # behind a final chunk that went ahead, its adoption too
    "rebuild": True,
    "window_launch": True,
    # dispatch(): the read of a final chunk's first token AFTER the
    # launch, for the wire: the chunk's remaining device time and the
    # token's way to the host, while the device goes on to the window
    "first_token_read": False,
    # ahead(), after the flush: the NEXT period's chunk, its operands
    # and enqueue, handed over behind the window that runs; a chunk
    # that goes ahead is no chunk_launch, and the two phases' counts add
    # up to the chunks run
    "chunk_ahead": False,
    # the flush after a dispatch: beside the window it launched, or
    # (emit_alone) with nothing running, where the device waits for it
    "emit": False,
    "emit_alone": True,
    # collect(): the wait and the one fetch; then unpack and _free_slot
    "window_wait": False,
    "unpack": False,
    # the timed recv of a turn that finds the engine idle, and the
    # keep_alive sleep: the device idles for want of work
    "parked": False,
}

_LOOP_SPAN_NAMES = {name: f"loop.{name}" for name in LOOP_PHASES}


def phase_histogram_key(phase: str) -> str:
    """A phase's key in the ``ServingMetrics`` snapshot."""
    return f"phase_{phase.replace('.', '_')}_us"


#: A REQUEST's life up to its first delta on the wire — the ONE table of
#: its stages' names, in the order it passes them, as ``LOOP_PHASES``
#: tiles a turn of the loop. The value says who observes the stage:
#:
#: * ``"arrival"`` — by ``llm_server.handle_input``, once a request, off
#:   the stamps the HTTP front put into the request's metadata
#:   (:data:`STAMP_HTTP`, :data:`STAMP_PUBLISH`; ``time.time_ns()``, the
#:   message plane's cross-process convention, as the daemon's
#:   ``t_route``). A request without them (another node sent it)
#:   observes neither.
#: * ``"first_send"`` — kept on the request as it passes each edge
#:   (admitted, its first chunk handed to the device, its first token on
#:   the host; the loop's and the engine's one clock) and observed
#:   TOGETHER where its first message is sent, beside ``ttft_us``: the
#:   three and ``ttft_us`` hold the same requests, and for each of them
#:   backlog wait + these three = what ``ttft_us`` observed.
#: * ``"api"`` — in the HTTP front's own process, off :data:`STAMP_EMIT`
#:   (``time.time_ns()`` again), printed in its ``front`` report lines.
#: * anything else is the snapshot key of a histogram that held the
#:   stage before this table did; the row names it, nothing new is
#:   recorded.
#:
#: Recorded rows have the key ``stage_<name>_us``
#: (:func:`stage_histogram_key`). Read by the benchmark and the nodes'
#: exit lines, and deliberately by no Prometheus family, alert, history
#: series or CLI view; nothing of it goes to the ring (``s_queued``,
#: ``s_admitted``, ``s_prefill_chunk``, ``s_finish`` draw a request
#: there already).
REQUEST_STAGES = {
    # do_POST has read the body -> the stamp taken inside the send lock,
    # just before node.send_output (JSON, metadata, the wait for the lock)
    "front": "arrival",
    # -> handle_input entered: node publish, daemon route, the receiver's
    # event queue, the wait for the loop's next intake
    "route_in": "arrival",
    # -> backlog.push: parse, encode
    "intake": "phase_intake_handle_input_us",
    # -> slot and pages granted
    "backlog": "backlog_wait_us",
    # -> the request's FIRST chunk handed to the device (in line or
    # ahead; behind a prefix grant, the first chunk it still runs): the
    # wait behind other prompts' chunks, one a period
    "prefill_queue": "first_send",
    # -> its first token on the host: its own chunks and the read
    "prefill": "first_send",
    # -> the stream's first ``response`` message handed to
    # node.send_output (ttft_us's own stamp): the rest of the dispatch,
    # the wait in ``held``, the flush's order
    "first_emit": "first_send",
    # -> the front's main loop has received that message
    "route_out": "api",
    # -> the handler thread's flush of the first content delta returned
    "sse": "api",
}

#: metadata keys of the three cross-process stamps (``time.time_ns()``)
STAMP_HTTP, STAMP_PUBLISH, STAMP_EMIT = "t_http_ns", "t_publish_ns", "t_emit_ns"


def stages_observed(how: str) -> list[str]:
    """The stages one observer records (``"arrival"``, ``"first_send"``,
    ``"api"``), in the table's order."""
    return [name for name, by in REQUEST_STAGES.items() if by == how]


def stage_histogram_key(stage: str) -> str:
    """A stage's key in the snapshot that holds it (the table is
    closed: KeyError)."""
    by = REQUEST_STAGES[stage]
    return f"stage_{stage}_us" if by in ("arrival", "first_send", "api") else by


_ARRIVAL_STAGES = stages_observed("arrival")
_FIRST_SEND_STAGES = stages_observed("first_send")


class _Phase:
    """``with tracer.phase(name):`` — a nested phase, left on every path."""

    __slots__ = ("_tracer", "_name")

    def __init__(self, tracer: "ServingTracer", name: str):
        self._tracer = tracer
        self._name = name

    def __enter__(self) -> None:
        self._tracer.enter(self._name)

    def __exit__(self, *exc) -> None:
        self._tracer.leave()


class ServingTracer:
    """Per-request lifecycle spans for the serving engine, recorded
    through the flight-recorder ring — and the serving loop's phases
    (:data:`LOOP_PHASES`), the one thing here that describes a turn of
    the loop and not a request.

    One instance per serving process, shared between the server loop
    (``nodehub/llm_server``: queued / finish / reject / page-wait) and
    the engine (``models/batch_engine``: admitted / prefill_chunk /
    decode_window) via ``engine.tracer``. Slot discipline matches the
    message plane: ``a`` = request key (+ detail), ``b`` = the
    request's serialized trace context, ``c`` = span duration in ns —
    so ``tracing.to_chrome_trace`` links the whole chain by one trace
    id on the per-process ENGINE track.

    :meth:`begin` derives the request context from the arriving
    message's ``open_telemetry_context`` when present, so engine spans
    share the trace id of the message-plane ``send → route → deliver →
    recv`` chain that carried the request in. Every method is one
    attribute check when tracing is off — engines keep a tracer
    attached unconditionally and pay ~0 without ``DORA_TRACING=1``.

    A phase is entered and left here and nowhere else, and each leaving
    is written to three sinks by that one call: ``histograms[phase]``
    (``ServingMetrics.phases``, always on: the phase's duration less
    what was carved out of it), an ``annotation`` named
    ``loop.<phase>`` (``jax.profiler.TraceAnnotation``, handed in by
    the process that holds the chip — this module imports no JAX — so
    the phase lands on the ``/host:CPU`` plane of a profiler capture,
    on the device planes' clock; it records nothing while no capture
    runs), and an ``s_loop_phase`` span in the ring under
    ``DORA_TRACING=1``. A tracer with neither sink still gives the
    stamps: ``enter`` / ``leave`` / ``switch`` return the clock read
    they made, and callers that need the time at a phase's edge take it
    from there.

    A request's stages (:data:`REQUEST_STAGES`) are entered and left
    here and nowhere else too, always on, into ``stage_histograms``
    (``ServingMetrics.stages``) alone: :meth:`request_arrived` observes
    the two the front stamped; :meth:`request_pushed` ..
    :meth:`request_token` keep a stamp an edge on the request, and
    :meth:`request_sent` observes the three they bound, once, where the
    first message goes. One dict entry a live request until then, or
    until :meth:`finish` (shed, failed) or :meth:`release` (migrated
    away): it does not grow with request count.
    """

    __slots__ = ("_flight", "_tracing", "_ctx", "clock", "histograms",
                 "annotation", "_open", "stage_histograms", "_edges")

    def __init__(self, flight: FlightRecorder | None = None,
                 tracing: TracingState | None = None,
                 clock=time.monotonic):
        self._flight = flight if flight is not None else FLIGHT
        self._tracing = tracing if tracing is not None else TRACING
        #: request key -> serialized trace context, begin() .. finish()
        self._ctx: dict[str, str] = {}
        #: seconds; the loop's and the engine's one clock
        self.clock = clock
        self.histograms: dict | None = None
        self.annotation = None
        #: open phases, outermost first: [name, start, carved s, annotation]
        self._open: list[list] = []
        self.stage_histograms: dict | None = None
        #: request key -> the stamps of the edges it has passed, on
        #: ``clock``: [pushed, admitted, first chunk, first token]
        self._edges: dict[str, list[float]] = {}

    # -- loop phases ---------------------------------------------------------

    def enter(self, phase: str) -> float:
        """Open ``phase`` inside whatever is open; returns the stamp."""
        now = self.clock()
        self._push(phase, now)
        return now

    def leave(self) -> float:
        """Close the innermost open phase; returns the stamp."""
        now = self.clock()
        self._pop(now)
        return now

    def switch(self, phase: str) -> float:
        """Close everything open and open ``phase``, on one clock read:
        how the loop's turn is tiled."""
        now = self.close()
        self._push(phase, now)
        return now

    def close(self) -> float:
        """Close everything open (the loop's exit, and its error path)."""
        now = self.clock()
        while self._open:
            self._pop(now)
        return now

    def phase(self, name: str) -> _Phase:
        return _Phase(self, name)

    def _push(self, phase: str, now: float) -> None:
        name = _LOOP_SPAN_NAMES[phase]  # the table is closed: KeyError
        span = None
        if self.annotation is not None:
            span = self.annotation(name)
            span.__enter__()
        self._open.append([phase, now, 0.0, span])

    def _pop(self, now: float) -> None:
        phase, start, carved, span = self._open.pop()
        if span is not None:
            span.__exit__(None, None, None)
        dur = now - start
        if self._open and "." not in phase:
            self._open[-1][2] += dur
        if self.histograms is not None:
            self.histograms[phase].observe((dur - carved) * 1e6)
        if self._tracing.active:
            self._flight.record("s_loop_phase", phase, None, int(dur * 1e9))

    # -- request stages ------------------------------------------------------

    def request_arrived(self, metadata: dict, now_ns: int) -> None:
        """``handle_input`` was entered at ``now_ns`` (``time.time_ns()``)
        for a request with this metadata: observe what the front's two
        stamps bound. A request without them observes nothing."""
        stamps = (metadata.get(STAMP_HTTP), metadata.get(STAMP_PUBLISH), now_ns)
        if self.stage_histograms is None or not all(
            isinstance(t, int) for t in stamps
        ):
            return
        for stage, start, end in zip(_ARRIVAL_STAGES, stamps, stamps[1:]):
            self.stage_histograms[stage].observe((end - start) / 1e3)

    def request_pushed(self, key: str, now: float) -> None:
        """The request goes to the backlog: ``ttft_us`` counts from
        ``now``, and so do its stages."""
        self._edges[key] = [now]

    def request_admitted(self, key: str, waited_s: float) -> None:
        """Slot and pages granted after ``waited_s`` in the backlog (what
        ``backlog_wait_us`` observes). A stream admitted again (resumed
        behind a preemption) keeps its first admission's stamp."""
        edges = self._edges.get(key)
        if edges is not None:
            self._edge(key, 1, edges[0] + waited_s)

    def request_chunk(self, key: str, now: float) -> None:
        """A chunk of the request's prompt was handed to the device on
        the stamp ``now``; its first is an edge."""
        self._edge(key, 2, now)

    def request_token(self, key: str, now: float) -> None:
        """The request's first token reached the host on the stamp ``now``."""
        self._edge(key, 3, now)

    def _edge(self, key: str, passed: int, now: float) -> None:
        """Stamp the request's next edge, if it has passed just
        ``passed`` of them: an edge met again, or out of turn, or of a
        request never pushed, is passed over."""
        edges = self._edges.get(key)
        if edges is not None and len(edges) == passed:
            edges.append(now)

    def request_sent(self, key: str, now: float) -> None:
        """The request's first message is handed to the node on the stamp
        ``now`` (``ttft_us``'s own): observe its stages, and forget it."""
        edges = self._edges.pop(key, None)
        if self.stage_histograms is None or edges is None or len(edges) != 4:
            return
        edges.append(now)
        for stage, start, end in zip(_FIRST_SEND_STAGES, edges[1:], edges[2:]):
            self.stage_histograms[stage].observe((end - start) * 1e6)

    # -- request lifecycle ---------------------------------------------------

    @property
    def active(self) -> bool:
        return self._tracing.active

    def begin(self, key: str, parent_ctx: str = "") -> None:
        """Open a request's trace context (same trace id as the carrier
        message when ``parent_ctx`` holds its serialized context)."""
        if not self._tracing.active:
            return
        self._ctx[key] = child_context(parent_ctx)

    def span(self, kind: str, key: str, detail: str | None = None,
             dur_ns: int = 0) -> None:
        """One completed lifecycle span (recorded at END; the exporter
        derives the start from ``wall - dur`` like the message plane)."""
        if not self._tracing.active:
            return
        self._flight.record(
            kind, f"{key} {detail}" if detail else key,
            self._ctx.get(key), int(dur_ns),
        )

    def instant(self, kind: str, key: str, detail: str | None = None) -> None:
        """A point event on the engine track (admission reject,
        page-grant failure, preempt-free backlog wait)."""
        if not self._tracing.active:
            return
        self._flight.record(
            kind, f"{key} {detail}" if detail else key,
            self._ctx.get(key), None,
        )

    def finish(self, key: str, reason: str = "stop") -> None:
        """Close a request: records ``s_finish`` and releases its
        context (the dict must not grow with request count)."""
        ctx = self._ctx.pop(key, None)
        self._edges.pop(key, None)
        if not self._tracing.active:
            return
        self._flight.record("s_finish", f"{key} {reason}", ctx, 0)

    def context(self, key: str) -> str:
        """The request's serialized trace context (empty when tracing is
        off or the key is unknown). Checkpoint/migration handoffs carry
        this so the resumed stream keeps the same trace id."""
        return self._ctx.get(key) or ""

    def release(self, key: str) -> None:
        """Drop a request's context without an ``s_finish`` span — for
        streams that migrate away rather than finishing here."""
        self._ctx.pop(key, None)
        self._edges.pop(key, None)


# ---------------------------------------------------------------------------
# XLA compile audit (runtime promotion of the tier-1 compile listener)
# ---------------------------------------------------------------------------

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_state = {"count": 0, "seconds": 0.0, "installed": False}


def install_compile_listener() -> bool:
    """Stamp every XLA backend compile onto the timeline.

    The zero-steady-state-recompile invariant (paged engine: exactly
    one program per closure, tests/test_paged_engine.py) was only
    observable under pytest; this promotes the same jax monitoring hook
    into runtime telemetry: each compile records an ``xla_compile``
    instant in the flight-recorder ring (elapsed ns; the traced
    callable's name when jax provides it) and bumps a process-wide
    counter that ``ServingMetrics`` ships to ``dora-tpu metrics`` — a
    nonzero delta while serving steady traffic IS the regression.

    Idempotent. Reaches into ``jax._src.monitoring`` (there is no
    public hook); it works on the installed JAX, and a JAX that moves it
    fails here loudly rather than serving with the guard silently off."""
    if _compile_state["installed"]:
        return True
    from jax._src import monitoring

    def _on_duration(event: str, duration: float, **kwargs) -> None:
        if event != _COMPILE_EVENT:
            return
        _compile_state["count"] += 1
        _compile_state["seconds"] += duration
        FLIGHT.record(
            "xla_compile",
            str(kwargs.get("fun_name", "") or "backend_compile"),
            None,
            int(duration * 1e9),
        )

    monitoring.register_event_duration_secs_listener(_on_duration)
    _compile_state["installed"] = True
    return True


def compile_seconds() -> float:
    """Seconds inside XLA backend compiles (or persistent-cache
    retrievals standing in for them) since the listener was installed."""
    return _compile_state["seconds"]


def compile_count() -> int:
    """XLA backend compiles observed since :func:`install_compile_listener`."""
    return _compile_state["count"]


def install_flight_dump() -> None:
    """`kill -USR2 <pid>` dumps the flight-recorder ring to stderr — the
    node-process counterpart of the daemon's task dump (nodes are
    synchronous; there is no asyncio loop to hang a handler on). Chains
    any pre-existing SIGUSR2 handler; no-op off the main thread or when
    DORA_NO_STACK_DUMP=1."""
    if os.environ.get("DORA_NO_STACK_DUMP"):
        return
    import signal

    try:
        previous = signal.getsignal(signal.SIGUSR2)

        def _handler(signum, frame):
            FLIGHT.dump()
            if callable(previous) and previous not in (
                signal.SIG_IGN,
                signal.SIG_DFL,
            ):
                previous(signum, frame)

        signal.signal(signal.SIGUSR2, _handler)
    except (ValueError, AttributeError, OSError):
        pass  # not the main thread / no SIGUSR2 on this platform


def install_stack_dump() -> None:
    """`kill -USR1 <pid>` dumps all Python stacks to stderr (the
    daemon-side log file) — a wedged node in a stuck dataflow can always
    be inspected post-hoc. Chains any pre-existing SIGUSR1 handler; opt
    out with DORA_NO_STACK_DUMP=1 (e.g. when the host app owns the
    signal entirely). Idempotent, process-level; called by Node() and
    the runtime entry point."""
    if os.environ.get("DORA_NO_STACK_DUMP"):
        return
    try:
        import faulthandler
        import signal

        faulthandler.register(signal.SIGUSR1, chain=True)
    except (ValueError, AttributeError, OSError):
        pass  # no SIGUSR1 on this platform / not callable here


def install_task_dump(loop) -> None:
    """`kill -USR2 <pid>` dumps every asyncio task's await stack to
    stderr — the counterpart of :func:`install_stack_dump` for coroutines
    (which faulthandler cannot see: a parked coroutine is not on any
    thread's stack). Used by the standalone daemon; forensics for wedged
    dataflows."""
    if os.environ.get("DORA_NO_STACK_DUMP"):
        return
    import signal
    import sys
    import traceback

    def _dump() -> None:
        import asyncio

        print(f"--- asyncio task dump ({len(asyncio.all_tasks(loop))} tasks)",
              file=sys.stderr)
        for task in asyncio.all_tasks(loop):
            print(f"task {task.get_name()}: {task}", file=sys.stderr)
            for frame in task.get_stack():
                traceback.print_stack(frame, limit=1, file=sys.stderr)
        FLIGHT.dump(sys.stderr)
        sys.stderr.flush()

    try:
        loop.add_signal_handler(signal.SIGUSR2, _dump)
    except (ValueError, NotImplementedError, OSError, RuntimeError):
        pass


def remove_task_dump(loop) -> None:
    """Unbind the SIGUSR2 handler (the loop is about to close; a later
    signal must not hit a dead loop's wakeup fd)."""
    import signal

    try:
        loop.remove_signal_handler(signal.SIGUSR2)
    except (ValueError, NotImplementedError, OSError, RuntimeError):
        pass


# ---------------------------------------------------------------------------
# context string codec (reference: serialize_context / deserialize_context)
# ---------------------------------------------------------------------------


def serialize_context(ctx: dict[str, str]) -> str:
    return "".join(f"{k}:{v};" for k, v in ctx.items())


def parse_otel_context(raw: str) -> dict[str, str]:
    out: dict[str, str] = {}
    for part in raw.split(";"):
        if ":" in part:
            k, _, v = part.partition(":")
            out[k] = v
    return out


def inject_context(metadata: dict, ctx: str | dict) -> dict:
    """Attach a trace context to outgoing message metadata."""
    if isinstance(ctx, dict):
        ctx = serialize_context(ctx)
    if ctx:
        metadata[OTEL_CTX_KEY] = ctx
    return metadata


def extract_context(metadata: dict) -> dict[str, str]:
    return parse_otel_context(str(metadata.get(OTEL_CTX_KEY, "")))


# ---------------------------------------------------------------------------
# optional OpenTelemetry integration
# ---------------------------------------------------------------------------

_tracer = None


def set_up_tracing(name: str):
    """Configure logging and, if available + configured, OTLP tracing
    (reference: set_up_tracing_opts, tracing/src/lib.rs:22-65)."""
    level = os.environ.get("DORA_LOG", os.environ.get("RUST_LOG", "info")).upper()
    logging.basicConfig(
        level=getattr(logging, level, logging.INFO),
        format=f"%(asctime)s {name} %(levelname)s %(name)s: %(message)s",
    )
    global _tracer
    endpoint = otlp_endpoint()
    if not endpoint:
        return None
    try:
        from opentelemetry import trace
        from opentelemetry.exporter.otlp.proto.grpc.trace_exporter import (
            OTLPSpanExporter,
        )
        from opentelemetry.sdk.resources import Resource
        from opentelemetry.sdk.trace import TracerProvider
        from opentelemetry.sdk.trace.export import BatchSpanProcessor

        provider = TracerProvider(
            resource=Resource.create({"service.name": name})
        )
        provider.add_span_processor(
            BatchSpanProcessor(OTLPSpanExporter(endpoint=endpoint))
        )
        trace.set_tracer_provider(provider)
        _tracer = trace.get_tracer(name)
        return _tracer
    except ImportError:
        logger.warning("opentelemetry not installed; tracing is log-only")
        return None


@contextmanager
def span(name: str, parent_ctx: str = ""):
    """A span context manager that yields the serialized context to embed in
    outgoing metadata. Without the otel SDK (and with ``DORA_TRACING`` set)
    this synthesizes W3C-style traceparent ids so traces still correlate
    across processes; with tracing off it forwards the parent unchanged at
    the cost of one attribute check."""
    if _tracer is None and not TRACING.active:
        yield parent_ctx
        return
    if _tracer is not None:
        from opentelemetry import trace as otrace
        from opentelemetry.trace.propagation.tracecontext import (
            TraceContextTextMapPropagator,
        )

        propagator = TraceContextTextMapPropagator()
        parent = propagator.extract(parse_otel_context(parent_ctx))
        with _tracer.start_as_current_span(name, context=parent):
            carrier: dict[str, str] = {}
            propagator.inject(carrier)
            yield serialize_context(carrier)
        return
    # Fallback: keep a coherent traceparent chain without the SDK
    # (per-process seeded ids — no os.urandom per span).
    yield child_context(parent_ctx)


# ---------------------------------------------------------------------------
# metrics (reference: dora-metrics, OTLP system metrics)
# ---------------------------------------------------------------------------


class MetricsSampler:
    """Per-process system metrics (reference: dora-metrics exports
    process CPU/memory/disk through an OTLP meter,
    telemetry/metrics/src/lib.rs:25-49).

    ``sample()`` always works (resource/psutil, no SDK needed) — the
    daemon can log it or answer control-API queries with it. When the
    OpenTelemetry *SDK* is installed and ``OTEL_EXPORTER_OTLP_ENDPOINT``
    is set, the same samples also export periodically as OTLP gauges.
    """

    def __init__(self, name: str):
        self.name = name
        self.exporting = False
        self._proc = None
        self._cached: dict | None = None
        try:
            import psutil

            self._proc = psutil.Process()
            # Prime cpu_percent: psutil computes it from the delta since
            # the previous call, so the first interval=None reading is
            # garbage (0.0). Paying the baseline read here makes the
            # first sample() meaningful.
            self._proc.cpu_percent(interval=None)
        except Exception:
            self._proc = None

    def sample(self) -> dict:
        import resource

        usage = resource.getrusage(resource.RUSAGE_SELF)
        out = {
            "max_rss_kb": usage.ru_maxrss,
            "user_s": usage.ru_utime,
            "system_s": usage.ru_stime,
            "time": time.time(),
        }
        if self._proc is not None:
            with self._proc.oneshot():
                out["rss_bytes"] = self._proc.memory_info().rss
                # psutil needs real time between cpu_percent calls; the
                # previous call's timestamp provides it on every sample
                # after the first.
                out["cpu_percent"] = self._proc.cpu_percent(interval=None)
                out["threads"] = self._proc.num_threads()
        self._cached = out
        return out

    def sample_cached(self, max_age_s: float = 1.0) -> dict:
        """The last sample if it is fresh, else a new one — so several
        per-gauge OTLP callbacks in one export cycle share one reading
        (back-to-back cpu_percent calls would read garbage)."""
        if self._cached and time.time() - self._cached["time"] < max_age_s:
            return self._cached
        return self.sample()


def init_metrics(name: str, interval_s: float = 10.0) -> MetricsSampler:
    """System-metrics handle; wires periodic OTLP export when the otel SDK
    and an endpoint are both present, mirroring ``set_up_tracing``."""
    sampler = MetricsSampler(name)
    endpoint = otlp_endpoint()  # same resolution as set_up_tracing
    if not endpoint:
        return sampler
    try:
        from opentelemetry.exporter.otlp.proto.grpc.metric_exporter import (
            OTLPMetricExporter,
        )
        from opentelemetry.metrics import set_meter_provider
        from opentelemetry.sdk.metrics import MeterProvider
        from opentelemetry.sdk.metrics.export import (
            PeriodicExportingMetricReader,
        )
        from opentelemetry.sdk.resources import Resource

        reader = PeriodicExportingMetricReader(
            OTLPMetricExporter(endpoint=endpoint),
            export_interval_millis=interval_s * 1000,
        )
        provider = MeterProvider(
            resource=Resource.create({"service.name": name}),
            metric_readers=[reader],
        )
        set_meter_provider(provider)
        meter = provider.get_meter(name)

        def observe(key: str):
            def callback(_options):
                from opentelemetry.metrics import Observation

                # Cached: the three gauges of one export cycle must share
                # one reading (see MetricsSampler.sample_cached).
                value = sampler.sample_cached().get(key, 0.0)
                return [Observation(float(value))]

            return callback

        for key in ("rss_bytes", "cpu_percent", "max_rss_kb"):
            meter.create_observable_gauge(
                f"process.{key}", callbacks=[observe(key)]
            )
        sampler.exporting = True
    except ImportError:
        logger.warning(
            "opentelemetry SDK not installed; system metrics are local-only"
        )
    return sampler


def init_cluster_metrics_export(
    name: str, collect, interval_s: float = 15.0
):
    """OTLP push for the coordinator's cluster metrics plane.

    ``collect`` is an async callable returning ``{dataflow_label:
    merged_snapshot}`` (the Prometheus endpoint's collector); samples are
    flattened through ``dora_tpu.prom.iter_samples`` so both exporters
    share one catalogue. Uses the same endpoint resolution as tracing
    (:func:`otlp_endpoint`); returns the export task, or None when no
    endpoint is configured or the otel metrics SDK is absent.

    Instruments are observable gauges created lazily per family the
    first time a sample for it appears; the periodic reader then pulls
    the latest collected values through their callbacks.
    """
    endpoint = otlp_endpoint()
    if not endpoint:
        return None
    try:
        from opentelemetry.exporter.otlp.proto.grpc.metric_exporter import (
            OTLPMetricExporter,
        )
        from opentelemetry.metrics import Observation
        from opentelemetry.sdk.metrics import MeterProvider
        from opentelemetry.sdk.metrics.export import (
            PeriodicExportingMetricReader,
        )
        from opentelemetry.sdk.resources import Resource
    except ImportError:
        logger.warning(
            "opentelemetry SDK not installed; cluster metrics are "
            "Prometheus/local-only"
        )
        return None

    from dora_tpu.prom import iter_samples

    reader = PeriodicExportingMetricReader(
        OTLPMetricExporter(endpoint=endpoint),
        export_interval_millis=interval_s * 1000,
    )
    provider = MeterProvider(
        resource=Resource.create({"service.name": name}),
        metric_readers=[reader],
    )
    meter = provider.get_meter(name)
    #: family -> [(labels, value)], refreshed by the collect loop and
    #: read by the per-family gauge callbacks at export time
    latest: dict[str, list] = {}

    def family_callback(family: str):
        def callback(_options):
            return [
                Observation(float(value), dict(labels))
                for labels, value in latest.get(family, [])
            ]

        return callback

    registered: set[str] = set()

    async def _loop():
        import asyncio

        while True:
            try:
                snapshots = await collect()
                fresh: dict[str, list] = {}
                for family, labels, value in iter_samples(snapshots):
                    fresh.setdefault(family, []).append((labels, value))
                latest.clear()
                latest.update(fresh)
                for family in fresh:
                    if family not in registered:
                        registered.add(family)
                        meter.create_observable_gauge(
                            family, callbacks=[family_callback(family)]
                        )
            except Exception:
                logger.exception("cluster metrics export failed")
            await asyncio.sleep(interval_s)

    import asyncio

    return asyncio.create_task(_loop())
