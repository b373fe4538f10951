"""Traffic-shaped serving: QoS classes on the admission queue, page
preemption with recompute-on-resume, load shedding, and the fused-window
retune surface.

The tier-1 acceptance bars live here: under synthetic overload the
interactive TTFT p99 must be strictly better with QoS on than off, no
request may be silently lost (every stream ends in a done chunk carrying
the wire id + seq), and a preempted stream's resumed output must be
token-identical to an unpreempted run.
"""

from __future__ import annotations

import time

import pytest

from dora_tpu.metrics import ServingMetrics
from dora_tpu.nodehub.llm_server import (
    QOS_CLASSES,
    AdmissionQueue,
    QosConfig,
    serve,
)


# ---------------------------------------------------------------------------
# scheduler-only tests (no jax): weighted drain, aging, shedding
# ---------------------------------------------------------------------------


class SlotEngine:
    """Slot-count-only engine for AdmissionQueue tests."""

    def __init__(self, slots: int = 1):
        self.max_slots = slots
        self.active = 0
        self.started: list[str] = []

    def fits(self, plen: int, max_new: int) -> bool:
        return True

    def can_admit(self, plen: int, max_new: int, adapter=None) -> bool:
        return self.active < self.max_slots

    def admit_blocker(self, plen: int, max_new: int, adapter=None):
        return "capacity"

    def start(self, key: str, ids: list[int], max_new: int,
              adapter=None) -> None:
        self.active += 1
        self.started.append(key)

    def release(self) -> None:
        self.active -= 1


def _queue(engine, clock, qos=None, on_shed=None, preempt=None):
    return AdmissionQueue(
        engine, engine.start, clock=clock, qos=qos,
        on_shed=on_shed, preempt=preempt,
    )


def test_interactive_head_beats_fresh_batch_head():
    t = [0.0]
    engine = SlotEngine(slots=1)
    engine.active = 1  # occupied: everything parks
    q = _queue(engine, lambda: t[0])
    q.push("b", [1], 2, "batch")
    q.push("i", [1], 2, "interactive")
    engine.release()
    q.drain()
    assert engine.started == ["i"]


def test_aged_batch_head_admits_under_sustained_interactive_load():
    """Starvation bar: batch weight 1 vs interactive 8 means a parked
    batch head overtakes a FRESH interactive head once it has waited
    more than (8 - 1) * aging_s. Before that it keeps losing; after, a
    stream of newly-arrived interactive requests can no longer starve
    it."""
    t = [0.0]
    engine = SlotEngine(slots=1)
    engine.active = 1
    q = _queue(engine, lambda: t[0], qos=QosConfig(aging_s=1.0))
    q.push("b", [1], 2, "batch")

    # Sustained interactive load, one fresh arrival per free slot:
    # while b's age is under the crossover the newcomer wins every time.
    for n in range(3):
        t[0] += 1.0
        q.push(f"i{n}", [1], 2, "interactive")
        engine.release()
        q.drain()
        engine.active = 1  # next interactive burst finds the slot busy
    assert engine.started == ["i0", "i1", "i2"]

    # Past the crossover (waited 20s > 7s) the aged batch head outscores
    # even a brand-new interactive arrival.
    t[0] = 20.0
    q.push("i3", [1], 2, "interactive")
    engine.release()
    q.drain()
    assert engine.started[3] == "b"
    assert q.queued("i3") and not q.queued("b")


def test_depth_bound_sheds_at_the_door():
    t = [0.0]
    engine = SlotEngine(slots=1)
    engine.active = 1
    shed: list[tuple[str, str]] = []
    q = _queue(
        engine, lambda: t[0],
        qos=QosConfig(depths={"batch": 1}),
        on_shed=lambda k, reason, w: shed.append((k, reason)),
    )
    assert q.push("b0", [1], 2, "batch")
    assert not q.push("b1", [1], 2, "batch")
    assert shed == [("b1", "depth:batch")]
    assert q.push("i0", [1], 2, "interactive")  # other classes unaffected
    assert len(q) == 2


def test_queue_wait_deadline_sheds_parked_entries():
    t = [0.0]
    engine = SlotEngine(slots=1)
    engine.active = 1
    shed: list[tuple[str, str, float]] = []
    q = _queue(
        engine, lambda: t[0],
        qos=QosConfig(shed_wait_s=10.0),
        on_shed=lambda k, reason, w: shed.append((k, reason, w)),
    )
    q.push("slow", [1], 2, "standard")
    q.push("dl", [1], 2, "standard", deadline_s=1.0)  # tighter than config
    t[0] = 2.0
    q.drain()
    assert [(k, r) for k, r, _ in shed] == [("dl", "queue_wait")]
    t[0] = 11.0
    q.drain()
    assert [k for k, _, _ in shed] == ["dl", "slow"]
    assert len(q) == 0


def test_preempt_hook_retries_drain_and_requeue_resets_age():
    """drain consults the preempt hook when the best head cannot admit;
    a True return re-scores and retries. The victim re-parks at the
    FRONT of its class with its wait clock reset — it must NOT re-age
    into immediately outscoring its preemptor (ping-pong)."""
    t = [100.0]
    engine = SlotEngine(slots=1)
    engine.active = 1
    calls: list[str] = []
    q = _queue(engine, lambda: t[0], qos=QosConfig(aging_s=1.0))

    def preempt(cls):
        # One-shot, like the real hook: no victims left -> False (a
        # hook that always returns True would spin drain forever).
        calls.append(cls)
        if len(calls) > 1:
            return False
        engine.release()  # evicted the occupant...
        q.requeue("victim", [9], 4, "batch")  # ...and re-parked it
        return True

    q._preempt = preempt
    q.push("i", [1], 2, "interactive")
    assert calls[0] == "interactive"
    assert engine.started == ["i"]
    # Fresh wait clock: entry t_in is the requeue time, not process 0.
    assert q.queued("victim")
    assert q._q["batch"][0][3] == 100.0


def test_qos_config_from_env(monkeypatch):
    monkeypatch.setenv("DORA_QOS_DEFAULT_CLASS", "interactive")
    monkeypatch.setenv("DORA_QOS_DEPTH_BATCH", "3")
    monkeypatch.setenv("DORA_QOS_SHED_WAIT_MS", "1500")
    monkeypatch.setenv("DORA_QOS_AGING_S", "5")
    monkeypatch.setenv("DORA_QOS_PREEMPT", "1")
    cfg = QosConfig.from_env()
    assert cfg.default_class == "interactive"
    assert cfg.depths["batch"] == 3 and cfg.depths["interactive"] is None
    assert cfg.shed_wait_s == 1.5
    assert cfg.aging_s == 5.0
    assert cfg.preempt_on
    monkeypatch.setenv("DORA_QOS_DEFAULT_CLASS", "bogus")
    assert QosConfig.from_env().default_class == "standard"


# ---------------------------------------------------------------------------
# serve()-level tests over the real stub paged engine
# ---------------------------------------------------------------------------


class _Node:
    """Node fake: queued input events, timestamped captured outputs."""

    def __init__(self, events):
        self._events = list(events)
        self.stream_ended = False
        self.sent: list[tuple[float, str, dict]] = []
        self.closed = False

    def recv(self, timeout=None):
        if self._events:
            return self._events.pop(0)
        self.stream_ended = True
        return None

    def send_output(self, output_id, value, metadata=None):
        # the message's text rides in the captured metadata as "text"
        self.sent.append((
            time.monotonic(), output_id,
            dict(metadata or {}, text=value.to_pylist()[0]),
        ))

    def report_serving(self, snapshot):
        pass

    def close(self):
        self.closed = True


def _req(rid: str, text: str, max_new: int, qos: str | None = None) -> dict:
    meta: dict = {"request_id": rid, "max_new_tokens": max_new}
    if qos:
        meta["qos_class"] = qos
    return {"type": "INPUT", "metadata": meta, "value": text.encode()}


def _serve(engine, events) -> tuple[_Node, ServingMetrics]:
    metrics = ServingMetrics(engine="paged")
    node = _Node(events)
    serve(
        node, engine, metrics,
        encode=lambda text: [ord(ch) % 97 + 1 for ch in text] or [1],
        decode_one=lambda tok: f" t{tok}",
        max_new_cap=64,
    )
    return node, metrics


def _streams(node: _Node) -> dict[str, dict]:
    """Per-wire-id view: first-chunk time, each message's (seq,
    n_tokens), final meta. A message's ``seq`` is the number of the
    request's tokens sent before it."""
    out: dict[str, dict] = {}
    for ts, _oid, meta in node.sent:
        rid = meta.get("request_id")
        if rid is None:
            continue
        s = out.setdefault(rid, {"t0": ts, "seqs": [], "final": None})
        assert meta["seq"] == sum(n for _s, n in s["seqs"]), (rid, meta)
        s["seqs"].append((meta["seq"], meta["n_tokens"]))
        if meta.get("done"):
            s["final"] = meta
    return out


def _tokens(node: _Node, rid: str) -> list[int]:
    """Emitted token values for ``rid`` parsed back out of the ' t<N>'
    stub decode strings — identity comparisons key on these. A message
    holds ``n_tokens`` of them."""
    toks = []
    for _ts, _oid, meta in node.sent:
        if meta.get("request_id") == rid:
            words = meta["text"].split()
            assert len(words) == meta["n_tokens"], meta
            toks += [int(w[1:]) for w in words]
    return toks


@pytest.mark.parametrize("window", [1, 8])
@pytest.mark.parametrize("spec_k", [0, 2])
def test_preempted_stream_resumes_token_identical(
    monkeypatch, window, spec_k
):
    """One slot: a batch stream is mid-decode when an interactive
    request arrives; preemption evicts it (pages freed whole), the
    interactive request runs, then the victim re-prefills prompt +
    emitted and finishes — its full output byte-identical to an
    unpreempted reference run, across fused-window and speculative
    configs."""
    pytest.importorskip("jax")
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    def build():
        return make_stub_paged_engine(
            max_slots=1, window=window, spec_k=spec_k, max_seq=128,
        )

    def text(node, rid):
        return "".join(
            m["text"] for _t, _o, m in node.sent
            if m.get("request_id") == rid
        )

    # Reference: the batch request alone, QoS off.
    ref_node, _ = _serve(build(), [_req("w-b", "hello world", 24, "batch")])
    ref = _tokens(ref_node, "w-b")
    ref_text = text(ref_node, "w-b")
    assert len(ref) == 24  # the stub actually decoded something
    if spec_k == 0:
        # one message a stream a flush: the first token, then a window's
        assert [n for _s, n in _streams(ref_node)["w-b"]["seqs"]] == (
            [1] * 24 if window == 1 else [1, 8, 8, 7]
        )

    monkeypatch.setenv("DORA_QOS_PREEMPT", "1")
    node, metrics = _serve(
        build(),
        [
            _req("w-b", "hello world", 24, "batch"),
            _req("w-i", "quick", 4, "interactive"),
        ],
    )
    streams = _streams(node)
    assert streams["w-b"]["final"] is not None
    assert streams["w-i"]["final"] is not None
    assert metrics.preempted >= 1 and metrics.resumed >= 1
    # Byte-identical text; the messages may be cut elsewhere (the
    # preemption flushes, the resume sends a first token of its own),
    # but their seq / n_tokens cover the same 24 tokens in order.
    assert text(node, "w-b") == ref_text
    assert _tokens(node, "w-b") == ref
    last_seq, last_n = streams["w-b"]["seqs"][-1]
    assert last_seq + last_n == 24
    assert streams["w-b"]["final"]["finish"] == \
        _streams(ref_node)["w-b"]["final"]["finish"]


def test_overload_ab_interactive_ttft_and_no_silent_loss(monkeypatch):
    """Synthetic overload, QoS on vs off over identical workloads: 8
    batch streams saturate both slots before 3 interactive requests
    arrive. With shaping ON (classes + preemption) the interactive
    p99 TTFT must be strictly better than the unshaped FIFO run. In
    BOTH runs every request must end in a done chunk (stop / length /
    overloaded / rejected / error) carrying the wire id + seq."""
    pytest.importorskip("jax")
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    def build():
        return make_stub_paged_engine(
            max_slots=2, window=4, max_seq=128, tick_sleep_s=0.004,
        )

    def workload(classes: bool):
        events = [
            _req(f"w-b{n}", f"bulk request {n}", 12,
                 "batch" if classes else None)
            for n in range(8)
        ]
        events += [
            _req(f"w-i{n}", f"hi {n}", 3,
                 "interactive" if classes else None)
            for n in range(3)
        ]
        return events

    def interactive_p99(node):
        t_start = min(ts for ts, _o, _m in node.sent)
        streams = _streams(node)
        waits = [
            streams[f"w-i{n}"]["t0"] - t_start for n in range(3)
        ]
        return max(waits)

    monkeypatch.setenv("DORA_QOS_PREEMPT", "1")
    node_on, m_on = _serve(build(), workload(classes=True))
    monkeypatch.delenv("DORA_QOS_PREEMPT")
    node_off, m_off = _serve(build(), workload(classes=False))

    for node in (node_on, node_off):
        streams = _streams(node)
        assert len(streams) == 11  # nothing silently lost
        for rid, s in streams.items():
            assert s["final"] is not None, rid
            assert s["final"]["finish"] in (
                "stop", "length", "overloaded", "rejected", "error"
            )
            assert s["final"]["request_id"] == rid
            assert isinstance(s["final"]["seq"], int)

    p99_on, p99_off = interactive_p99(node_on), interactive_p99(node_off)
    assert p99_on < p99_off, (p99_on, p99_off)
    assert m_on.preempted >= 1
    assert m_off.preempted == 0


def test_shed_streams_end_with_retriable_overloaded_chunk(monkeypatch):
    """Depth-bounded batch class under a slot-starved engine: the
    overflow requests are shed at the door with a DONE chunk tagged
    finish="overloaded" + retry_after_ms — never silently dropped —
    and shed requests never pollute the TTFT histogram."""
    pytest.importorskip("jax")
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    # Depth bound only — a queue-wait deadline here would race the
    # first dispatch's XLA compile and shed the legitimately parked
    # stream on a slow machine.
    monkeypatch.setenv("DORA_QOS_DEPTH_BATCH", "1")
    engine = make_stub_paged_engine(max_slots=1, window=2, max_seq=64)
    node, metrics = _serve(
        engine,
        [
            _req("w-hold", "occupy the slot", 10, "batch"),
            _req("w-park", "parks in batch", 4, "batch"),
            _req("w-shed", "overflows the bound", 4, "batch"),
        ],
    )
    streams = _streams(node)
    assert metrics.shed >= 1
    final = streams["w-shed"]["final"]
    assert final is not None
    assert final["finish"] == "overloaded"
    assert final["retry_after_ms"] >= 100
    # The two admitted streams completed normally.
    for rid in ("w-hold", "w-park"):
        assert streams[rid]["final"]["finish"] in ("stop", "length")


def test_qos_depth_gauges_in_snapshot():
    m = ServingMetrics(engine="paged")
    m.shed = 2
    m.preempted = 1
    m.resumed = 1
    m.retunes = 3
    m.autotune_k = 8
    m.qos_depth = {"interactive": 0, "standard": 2, "batch": 5}
    snap = m.snapshot()
    assert snap["shed"] == 2 and snap["preempted"] == 1
    assert snap["resumed"] == 1 and snap["retunes"] == 3
    assert snap["autotune_k"] == 8
    assert snap["qos_depth"] == {"interactive": 0, "standard": 2, "batch": 5}


# ---------------------------------------------------------------------------
# fused-window retuning (the autotuner's engine surface)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec_k", [0, 2])
def test_set_window_mid_stream_is_token_identical(spec_k):
    """Retuning K (and pausing/resuming speculation) at a window
    boundary must not change a single emitted token — the autotuner
    trades latency for throughput, never correctness."""
    pytest.importorskip("jax")
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    def run(retune: bool) -> list[tuple[str, int, bool]]:
        e = make_stub_paged_engine(
            max_slots=2, window=8, spec_k=spec_k, max_seq=128,
        )
        e.submit("r", [5, 3, 9], 24)
        out: list[tuple[str, int, bool]] = []
        steps = 0
        while e.active:
            out.extend(e.step())
            steps += 1
            if retune and steps == 2:
                assert e.set_window(1, spec_on=False)
                assert e.window == 1 and e.spec_k == 0
            if retune and steps == 6:
                assert e.set_window(8, spec_on=True)
                assert e.spec_k == spec_k
        return out

    assert run(retune=True) == run(retune=False)


def test_set_window_caches_compiled_windows():
    pytest.importorskip("jax")
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    e = make_stub_paged_engine(max_slots=1, window=4, max_seq=64)
    assert not e.set_window(4)  # no-op: already there
    assert e.set_window(8)
    fn8 = e.window_step
    assert e.set_window(4)
    assert e.set_window(8)
    assert e.window_step is fn8  # cache hit, no rebuild


def test_burn_window_complete_gating():
    from dora_tpu.metrics_history import burn_window_complete

    assert burn_window_complete(12, 60.0, 5.0)
    assert not burn_window_complete(11, 60.0, 5.0)
    assert burn_window_complete(1, 3.0, 5.0)  # window shorter than tick
    assert not burn_window_complete(100, 60.0, 0.0)  # degenerate interval


def test_preempt_resume_repays_only_unshared_prefill_on_cache_hit(
    monkeypatch,
):
    """KNOWN_ISSUES round 14 retired for cache hits: a preempted
    stream's resume used to re-pay its WHOLE prefill. With the prefix
    cache on, preemption pins the victim's prompt+emitted path, so the
    re-submit maps the cached pages and re-prefills only the unshared
    tail — strictly fewer prefill chunks than the cache-off run, same
    tokens."""
    pytest.importorskip("jax")
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    class _GatedNode(_Node):
        """Holds the interactive request back until the victim emitted
        its first token — which guarantees the victim's final prefill
        chunk ran (and, cache-on, its prompt pages were inserted)."""

        def __init__(self, first, gated):
            super().__init__([first])
            self._gated = gated

        def recv(self, timeout=None):
            if self._gated and any(
                m.get("request_id") == "w-b" and not m.get("done")
                for _t, _o, m in self.sent
            ):
                return self._gated.pop(0)
            if self._events:
                return self._events.pop(0)
            if self._gated:
                return None  # stream stays open until the gate releases
            self.stream_ended = True
            return None

    def leg(cache: bool):
        engine = make_stub_paged_engine(
            max_slots=1, window=4, max_seq=128, prefix_cache=cache,
        )
        node = _GatedNode(
            _req("w-b", "0123456789abcdef", 20, "batch"),  # 16 tokens
            [_req("w-i", "hi", 3, "interactive")],
        )
        metrics = ServingMetrics(engine="paged")
        serve(
            node, engine, metrics,
            encode=lambda text: [ord(ch) % 97 + 1 for ch in text] or [1],
            decode_one=lambda tok: f" t{tok}",
            max_new_cap=64,
        )
        return engine, node, metrics

    monkeypatch.setenv("DORA_QOS_PREEMPT", "1")
    e_off, n_off, m_off = leg(cache=False)
    e_on, n_on, m_on = leg(cache=True)
    for m in (m_off, m_on):
        assert m.preempted >= 1 and m.resumed >= 1
    for rid in ("w-b", "w-i"):
        assert _tokens(n_on, rid) == _tokens(n_off, rid), rid
    assert e_on.prefix_cache.hits >= 1  # the resume mapped cached pages
    assert e_on.chunks_run < e_off.chunks_run, (
        e_on.chunks_run, e_off.chunks_run
    )
    e_on.check_invariants()
