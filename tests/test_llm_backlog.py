"""Backlog admission in the serving loop (nodehub/llm_server).

Regression: ``admit_backlog()`` used to run only after an engine step,
so a request parked while the engine was busy (or briefly out of
pages) could sit with ZERO active streams until unrelated traffic
arrived to push the loop around. The loop now drains the backlog on
every tick — after a push, after a step freed capacity, and on the
IDLE path — via llm_server.AdmissionQueue + _run_loop.
"""

from __future__ import annotations

from dora_tpu.metrics import ServingMetrics
from dora_tpu.nodehub.llm_server import AdmissionQueue, _run_loop


class FakeEngine:
    """Slot-only engine: submit fills a slot, each step emits one token
    per stream and finishes it at max_new. ``deny_admits`` makes
    can_admit refuse its first N calls (simulating pages still held
    elsewhere) without any event or step ever flipping it back — only
    an unconditional drain can admit once the countdown clears."""

    def __init__(self, slots: int = 1, deny_admits: int = 0):
        self.max_slots = slots
        self.streams: dict[str, list[int]] = {}
        self.emitted: dict[str, int] = {}
        self.caps: dict[str, int] = {}
        self.deny_admits = deny_admits
        self.steps = 0
        self.submits: list[tuple[str, int]] = []

    @property
    def active(self) -> int:
        return len(self.streams)

    def fits(self, plen: int, max_new: int) -> bool:
        return plen + max_new <= 64

    def can_admit(self, plen: int, max_new: int, adapter=None) -> bool:
        if self.deny_admits > 0:
            self.deny_admits -= 1
            return False
        return self.active < self.max_slots and self.fits(plen, max_new)

    def admit_blocker(self, plen: int, max_new: int, adapter=None):
        return "capacity"

    def submit(self, key: str, ids: list[int], max_new: int):
        assert self.active < self.max_slots
        self.streams[key] = list(ids)
        self.emitted[key] = 0
        self.caps[key] = max_new
        self.submits.append((key, self.steps))
        return None

    in_flight = False
    launched_at = None  # no first token read beside a window

    def dispatch(self):
        return []

    def collect(self):
        self.steps += 1
        out = []
        for key in list(self.streams):
            self.emitted[key] += 1
            done = self.emitted[key] >= self.caps[key]
            out.append((key, 7, done))
            if done:
                del self.streams[key]
        return out


class FakeNode:
    def __init__(self, events):
        self._events = list(events)
        self.stream_ended = False

    def recv(self, timeout=None):
        if self._events:
            return self._events.pop(0)
        self.stream_ended = True
        return None


def _input(rid: str) -> dict:
    return {"type": "INPUT", "metadata": {"request_id": rid}, "value": rid}


def _drive(engine, events):
    """Run the real serving loop over fakes; returns emitted tokens."""
    metrics = ServingMetrics()
    emitted: list[tuple[str, int, bool]] = []
    backlog = AdmissionQueue(
        engine, lambda k, ids, mn, adapter: engine.submit(k, ids, mn)
    )

    def handle_input(event):
        rid = event["metadata"]["request_id"]
        backlog.push(rid, [1, 2, 3], 2)

    _run_loop(
        FakeNode(events) if not hasattr(events, "recv") else events,
        engine,
        backlog,
        metrics,
        handle_input,
        # one message a stream a flush; flattened back to token triples
        lambda key, tokens, done: emitted.extend(
            (key, token, done and i == len(tokens) - 1)
            for i, token in enumerate(tokens)
        ),
        lambda now: None,
    )
    return emitted, backlog


def test_push_admits_immediately_when_capacity_allows():
    engine = FakeEngine(slots=2)
    q = AdmissionQueue(
        engine, lambda k, ids, mn, adapter: engine.submit(k, ids, mn)
    )
    q.push("a", [1, 2], 4)
    assert engine.active == 1 and len(q) == 0


def test_backlogged_request_admitted_after_slot_frees():
    """Second request parks while the only slot is busy, then admits
    the same tick the first stream finishes — no extra traffic."""
    engine = FakeEngine(slots=1)
    emitted, backlog = _drive(engine, [_input("a"), _input("b")])
    assert len(backlog) == 0
    keys = {k for k, _, _ in emitted}
    assert keys == {"a", "b"}
    # b was admitted by the drain right after a's finishing step — not
    # by a later event (there were none left).
    assert dict(engine.submits)["b"] == engine.steps - 2


def test_idle_path_drains_backlog_without_traffic():
    """THE regression: a request parks while can_admit is temporarily
    false, the engine goes fully idle, and NO further events arrive.
    The idle tick's drain must admit it anyway."""
    engine = FakeEngine(slots=1, deny_admits=2)
    emitted, backlog = _drive(engine, [_input("a")])
    # Admitted with zero engine steps run at that point: the push drain
    # and the post-step drain were both denied, so only the idle-path
    # drain can have started it.
    assert engine.submits == [("a", 0)]
    assert [k for k, _, _ in emitted] == ["a", "a"]
    assert len(backlog) == 0
