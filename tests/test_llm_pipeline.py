"""The serving loop is pipelined by one window (nodehub/llm_server
``_run_loop`` over ``engine.dispatch()`` / ``engine.collect()``).

* ORDER: in steady state the loop launches window N+1, sends window N's
  tokens while it runs, and only then waits — and a prompt's first
  token leaves right after the dispatch that read it, ahead of them.
* ONE MESSAGE PER STREAM PER FLUSH: a flush sends every token it holds
  for a stream as one ``response``: the texts concatenated in order,
  ``done`` and ``finish`` those of the last, ``seq`` the number of the
  request's tokens sent before it and ``n_tokens`` how many it holds.
* FLUSH POINTS: tokens collected and not yet sent are state the wire
  has not seen. Whatever reads per-request state — preemption,
  migration, a checkpoint, the error path, STOP, the end of the input
  stream, an engine gone idle — finds every one of them on the wire
  first, in order, each message's ``seq`` the tokens sent before it.
* TOKEN IDENTITY: streams served through ``dispatch()`` + ``collect()``
  under the loop equal those of ``step()`` called in a loop, on the
  real paged engine at a tiny size (plain / speculative / LoRA /
  prefix-cache hit).
"""

from __future__ import annotations

import numpy as np
import pytest

from dora_tpu.metrics import ServingMetrics
from dora_tpu.nodehub.llm_server import AdmissionQueue, _run_loop, serve


# ---------------------------------------------------------------------------
# fakes
# ---------------------------------------------------------------------------


class _Streams:
    """One stream per slot, ``k`` tokens a window; token values count
    up from 1 so order and loss read off the numbers."""

    def __init__(self, log: list, slots: int = 2, k: int = 3):
        self.log = log
        self.max_slots = slots
        self.k = k
        self.streams: dict[str, list[int]] = {}  # key -> [emitted, cap]
        self.fresh: list[str] = []

    @property
    def active(self) -> int:
        return len(self.streams)

    def fits(self, plen: int, max_new: int) -> bool:
        return True

    def can_admit(self, plen: int, max_new: int, adapter=None) -> bool:
        return self.active < self.max_slots

    def admit_blocker(self, plen: int, max_new: int, adapter=None):
        return "capacity"

    def submit(self, key: str, ids, max_new: int, adapter=None):
        self.streams[key] = [0, max_new]
        self.fresh.append(key)

    def _advance(self, key: str) -> tuple[str, int, bool]:
        s = self.streams[key]
        s[0] += 1
        done = s[0] >= s[1]
        if done:
            del self.streams[key]
        return key, s[0], done

    def _first(self) -> list:
        """A new stream's first token (its "final prefill chunk")."""
        return [self._advance(self.fresh.pop(0))] if self.fresh else []

    def _window(self, keys) -> list:
        out = []
        for key in keys:
            for _ in range(self.k):
                tok = self._advance(key)
                out.append(tok)
                if tok[2]:
                    break
        return out


class SplitEngine(_Streams):
    """Split like the paged engine: ``dispatch()`` launches (and hands
    back a new stream's first token), ``collect()`` returns the
    window's tokens. Every call lands in ``log``, which the test's
    ``emit`` shares."""

    in_flight = False
    launched_at = None  # no first token read beside a window
    launched = 0  # dispatches that left a window in flight

    def dispatch(self):
        self.log.append(("dispatch",))
        first = self._first()
        self.in_flight = bool(self.streams)
        self.launched += self.in_flight
        return first

    def ahead(self):
        self.log.append(("ahead",))

    def collect(self):
        self.log.append(("collect",))
        if not self.in_flight:
            return []
        self.in_flight = False
        return self._window(
            [k for k in self.streams if k not in self.fresh]
        )


class NothingInFlightEngine(_Streams):
    """The same engine when ``dispatch()`` launches nothing (as after a
    prefill-only dispatch): ``collect()`` does the whole step."""

    in_flight = False
    launched_at = None  # no first token read beside a window

    def dispatch(self):
        return []

    def collect(self):
        self.log.append(("step",))
        first = self._first()
        return first + self._window(
            [k for k in self.streams if k not in self.fresh]
        )


class ScriptNode:
    """Delivers event i once ``sent()`` has reached its threshold; the
    stream ends when the script is empty (unless ``hold_open``)."""

    def __init__(self, script, sent=lambda: 0, hold_open: int = 0):
        self._script = list(script)
        self._sent = sent
        self._hold = hold_open
        self.stream_ended = False
        self.recvs: list[tuple[float | None, int]] = []

    def recv(self, timeout=None):
        self.recvs.append((timeout, self._sent()))
        if self._script and self._sent() >= self._script[0][0]:
            return self._script.pop(0)[1]
        if not self._script:
            if self._hold > 0:
                # an open stream: STOP after a few parked (idle) polls
                self._hold -= bool(timeout)
                if self._hold == 0:
                    return {"type": "STOP"}
            else:
                self.stream_ended = True
        return None


def _drive(engine, log, script, max_new=7, **hooks):
    metrics = ServingMetrics()
    backlog = AdmissionQueue(
        engine, lambda k, ids, mn, adapter: engine.submit(k, ids, mn)
    )

    def emit(key, tokens, done):
        # one log line a token (only a key's last can be its done one)
        log.append(("message", key, len(tokens)))
        for i, token in enumerate(tokens):
            log.append(("emit", key, token, done and i == len(tokens) - 1,
                        bool(engine.in_flight)))

    def handle_input(event):
        backlog.push(event["metadata"]["request_id"], [1, 2], max_new)

    node = ScriptNode(
        script, sent=lambda: sum(e[0] == "emit" for e in log),
        hold_open=hooks.pop("hold_open", 0),
    )
    _run_loop(node, engine, backlog, metrics, handle_input, emit,
              lambda now: None, **hooks)
    return metrics, node


def _input(rid: str) -> dict:
    return {"type": "INPUT", "metadata": {"request_id": rid}, "value": rid}


def _emits(log, key=None):
    return [e for e in log if e[0] == "emit" and key in (None, e[1])]


def _messages(log, key=None):
    return [e for e in log if e[0] == "message" and key in (None, e[1])]


# ---------------------------------------------------------------------------
# the order
# ---------------------------------------------------------------------------


def test_steady_state_is_dispatch_then_previous_windows_tokens_then_collect():
    log: list = []
    metrics, _ = _drive(SplitEngine(log, slots=1, k=3), log, [(0, _input("a"))])
    kinds = [e[0] for e in log]
    # first token right after the dispatch that read it, before collect;
    # the engine is offered the next period's chunk once nothing is left
    # to send, never before
    assert kinds[:5] == ["dispatch", "message", "emit", "ahead", "collect"]
    assert log[2][1:4] == ("a", 1, False)
    # then: dispatch, the three tokens of the window before as ONE
    # message, ahead, collect
    assert kinds[5:12] == [
        "dispatch", "message", "emit", "emit", "emit", "ahead", "collect"
    ]
    assert log[6] == ("message", "a", 3)
    assert [e[2] for e in log[7:10]] == [2, 3, 4]
    assert [e[2] for e in _messages(log)] == [1, 3, 3]
    # in order, nothing lost, done last
    assert [e[2] for e in _emits(log)] == [1, 2, 3, 4, 5, 6, 7]
    assert [e[3] for e in _emits(log)] == [False] * 6 + [True]
    # a collect is never followed by an emit of ITS tokens before the
    # next dispatch — except where the engine went idle (the last one)
    for i, e in enumerate(log[:-5]):
        if e[0] == "collect":
            assert log[i + 1][0] == "dispatch", log[i : i + 3]


def test_emit_overlapped_counts_tokens_sent_beside_a_window():
    log: list = []
    engine = SplitEngine(log, slots=2, k=3)
    metrics, _ = _drive(
        engine, log, [(0, _input("a")), (0, _input("b"))], max_new=20,
    )
    beside = sum(e[4] for e in _emits(log))
    assert metrics.emit_overlapped == beside
    # the flush's host time is observed once a dispatch that has a
    # window running beside it
    assert metrics.emit.count == engine.launched > 6
    # one message a stream a flush, each holding the stream's window
    assert len(_messages(log)) < len(_emits(log)) / 2
    assert {e[2] for e in _messages(log)} == {1, 3}
    # everything but the idle flush of the two streams' last window
    # went out beside a running window
    assert len(_emits(log)) == 40
    assert 0 < len(_emits(log)) - beside <= 6
    assert metrics.dispatch_gap.count >= 6


def test_a_dispatch_that_launched_nothing_counts_no_token_as_overlapped():
    log: list = []
    metrics, _ = _drive(
        NothingInFlightEngine(log, slots=2, k=3), log,
        [(0, _input("a")), (0, _input("b"))],
    )
    assert [e[2] for e in _emits(log, "a")] == [1, 2, 3, 4, 5, 6, 7]
    assert [e[2] for e in _emits(log, "b")] == [1, 2, 3, 4, 5, 6, 7]
    assert metrics.emit_overlapped == 0
    assert metrics.emit.count == 0  # no window ran beside any flush
    assert "dispatch" not in {e[0] for e in log}


def test_flush_groups_the_held_tokens_by_stream():
    """3 streams x 8 tokens as the engine hands them over (tick by
    tick, rows interleaved) behind one first token: four emits, a
    key's tokens in the order held, the keys in the order of their
    first held token, ``done`` that of the key's last."""
    from dora_tpu.nodehub.llm_server import _flush

    held = [("d", 900, False)]
    for tick in range(8):
        for key, base in (("a", 100), ("b", 200), ("c", 300)):
            held.append((key, base + tick, key == "b" and tick == 7))
    calls: list = []
    n = _flush(held, lambda key, tokens, done: calls.append(
        (key, list(tokens), done)
    ))
    assert n == 25 and held == []
    assert calls == [
        ("d", [900], False),
        ("a", list(range(100, 108)), False),
        ("b", list(range(200, 208)), True),
        ("c", list(range(300, 308)), False),
    ]
    assert _flush(held, calls.append) == 0 and len(calls) == 4
    # a done can only be a key's last token
    with pytest.raises(AssertionError, match="after its last"):
        _flush([("a", 1, True), ("a", 2, False)], lambda *a: None)


def test_loop_flushes_before_migrate_error_stop_and_idle():
    """The readers the loop itself can see: when each acts, what the
    engine has produced equals what has been emitted."""

    def produced_and_emitted(engine, log):
        return engine.streams["a"][0], len(_emits(log))

    # MIGRATE: held tokens are out before the hook runs
    log: list = []
    engine = SplitEngine(log, slots=1, k=3)
    seen: list = []
    _drive(
        engine, log,
        [(0, _input("a")), (2, {"type": "MIGRATE", "metadata": {}})],
        max_new=20,
        handle_migrate=lambda ev: seen.append(
            produced_and_emitted(engine, log)
        ),
    )
    assert len(seen) == 1 and seen[0][0] == seen[0][1] >= 4, seen
    # engine error in dispatch(): the held window, then the hook
    log = []
    engine = SplitEngine(log, slots=1, k=3)
    real = engine.dispatch
    calls = [0]

    def wedge():
        calls[0] += 1
        if calls[0] == 3:
            raise RuntimeError("wedged")
        return real()

    engine.dispatch = wedge
    seen = []
    with pytest.raises(RuntimeError, match="wedged"):
        _drive(engine, log, [(0, _input("a"))], max_new=20,
               on_engine_error=lambda: seen.append(
                   produced_and_emitted(engine, log)
               ))
    assert seen == [(7, 7)]  # first token + two collected windows
    # STOP mid-generation: the loop leaves with nothing in hand
    log = []
    engine = SplitEngine(log, slots=1, k=3)
    _drive(engine, log, [(0, _input("a")), (3, {"type": "STOP"})],
           max_new=50)
    produced, emitted = produced_and_emitted(engine, log)
    assert produced == emitted >= 4
    # idle: the tokens are out BEFORE the loop parks in recv(0.25)
    log = []
    _, node = _drive(SplitEngine(log, slots=1, k=3), log,
                     [(0, _input("a"))], hold_open=3)
    parks = [n for t, n in node.recvs[1:] if t == 0.25]
    assert parks and parks[0] == 7


# ---------------------------------------------------------------------------
# flush points through serve(), over the stub paged engine
# ---------------------------------------------------------------------------


class _Wire(ScriptNode):
    """Node fake for serve(): the script is paced by tokens sent so
    far; captures the messages' metadata (and text, as ``text``)."""

    def __init__(self, script, hold_open: int = 0):
        super().__init__(script, sent=self.count, hold_open=hold_open)
        self.sent: list[dict] = []
        self.closed = False

    def count(self, rid: str | None = None) -> int:
        """Tokens on the wire (of ``rid``, or of every stream)."""
        return sum(
            m["n_tokens"] for m in self.sent
            if rid in (None, m.get("request_id"))
        )

    def send_output(self, output_id, value, metadata=None):
        self.sent.append(
            dict(metadata or {}, text=value.to_pylist()[0])
        )

    def report_serving(self, snapshot):
        pass

    def close(self):
        self.closed = True


def _serve_stub(wire, engine, metrics) -> None:
    """The real ``serve()`` over a stub engine: ids from the prompt's
    characters, one `` t<N>`` word a token."""
    serve(
        wire, engine, metrics,
        encode=lambda text: [ord(ch) % 97 + 1 for ch in text] or [1],
        decode_one=lambda tok: f" t{tok}",
        max_new_cap=64,
    )


def _req(rid: str, max_new: int, qos: str | None = None) -> dict:
    meta: dict = {"request_id": rid, "max_new_tokens": max_new}
    if qos:
        meta["qos_class"] = qos
    return {"type": "INPUT", "metadata": meta, "value": b"hello world"}


def _audit(engine, name: str, wire: _Wire, rids: list[str], seen: list):
    """Wrap ``engine.<name>``: when it is called, what the engine counts
    as emitted for every live stream must already be on the wire (engine
    keys are ``req-N`` in arrival order)."""
    real = getattr(engine, name)

    def wrapped(*a, **kw):
        for s in engine.slots:
            if s is not None and s.request_id.startswith("req-"):
                rid = rids[int(s.request_id[4:]) - 1]
                seen.append((name, rid, s.emitted, wire.count(rid)))
        return real(*a, **kw)

    setattr(engine, name, wrapped)


def _assert_consecutive(wire: _Wire) -> dict[str, list[dict]]:
    by_rid: dict[str, list[dict]] = {}
    for m in wire.sent:
        by_rid.setdefault(m["request_id"], []).append(m)
    for rid, chunks in by_rid.items():
        # seq = the request's tokens sent before the message; the text
        # holds n_tokens of the stub's " t<N>" words
        sent_before = 0
        for m in chunks:
            assert m["seq"] == sent_before, (rid, chunks)
            assert m["text"].count(" t") == m["n_tokens"], m
            sent_before += m["n_tokens"]
        assert all(not m["done"] and m["n_tokens"] for m in chunks[:-1]), rid
    return by_rid


def _n(chunks: list[dict]) -> int:
    return sum(m["n_tokens"] for m in chunks)


FLUSH_CASES = [
    "preempt", "migrate", "checkpoint", "engine_error", "stop",
    "stream_end", "idle",
]


@pytest.mark.parametrize("case", FLUSH_CASES)
def test_every_held_token_is_on_the_wire_before(case, monkeypatch, tmp_path):
    pytest.importorskip("jax")
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    engine = make_stub_paged_engine(max_slots=1, window=4, max_seq=128)
    seen: list = []
    rids = ["w-a", "w-b"]
    script = [(0, _req("w-a", 24))]
    hold_open = 0
    raises = None
    if case == "preempt":
        monkeypatch.setenv("DORA_QOS_PREEMPT", "1")
        script = [(0, _req("w-a", 24, "batch")),
                  (6, _req("w-b", 4, "interactive"))]
    elif case == "migrate":
        script.append((6, {"type": "MIGRATE",
                           "metadata": {"handoff_dir": str(tmp_path / "h")}}))
    elif case == "checkpoint":
        monkeypatch.setenv("DORA_CHECKPOINT_DIR", str(tmp_path / "ck"))
        monkeypatch.setenv("DORA_CHECKPOINT_EVERY", "2")
    elif case == "engine_error":
        real = engine.dispatch
        calls = [0]

        def wedge():
            calls[0] += 1
            if calls[0] == 4:
                raise RuntimeError("device wedged")
            return real()

        engine.dispatch = wedge
        raises = "device wedged"
    elif case == "stop":
        script.append((6, {"type": "STOP"}))
    elif case == "idle":
        hold_open = 3
    wire = _Wire(script, hold_open=hold_open)
    audited = {"preempt": "preempt", "migrate": "drain_streams",
               "checkpoint": "checkpoint_state"}.get(case)
    if audited:
        _audit(engine, audited, wire, rids, seen)
    metrics = ServingMetrics(engine="paged")

    if raises:
        with pytest.raises(RuntimeError, match=raises):
            _serve_stub(wire, engine, metrics)
    else:
        _serve_stub(wire, engine, metrics)
    by_rid = _assert_consecutive(wire)
    if audited:
        live = [s for s in seen if s[2] > 0]
        assert live, seen  # the action met a stream mid-generation
        for name, rid, emitted, on_wire in seen:
            assert emitted == on_wire, (name, rid, emitted, on_wire)
    if case == "preempt":
        assert metrics.preempted >= 1 and metrics.resumed >= 1
        assert by_rid["w-a"][-1]["done"] and _n(by_rid["w-a"]) == 24
    elif case == "migrate":
        assert metrics.migrated_out == 1
        assert not by_rid["w-a"][-1]["done"]  # it moved, it did not end
    elif case == "checkpoint":
        assert metrics.checkpoints >= 3
        assert _n(by_rid["w-a"]) == 24 and by_rid["w-a"][-1]["done"]
    elif case == "engine_error":
        # every token the engine counts (the first, then three
        # collected windows: the last of them was held), then the error
        chunks = by_rid["w-a"]
        assert _n(chunks) == engine.slots[0].emitted == 1 + 3 * 4
        assert [m["n_tokens"] for m in chunks] == [1, 4, 4, 4, 0]
        assert chunks[-1]["finish"] == "error" and chunks[-1]["done"]
        assert chunks[-1]["seq"] == 13 and chunks[-1]["text"] == ""
    elif case == "stop":
        slot = engine.slots[0]
        assert slot is not None and _n(by_rid["w-a"]) == slot.emitted
    elif case == "stream_end":
        # the first token alone, then a window's four a message (the
        # last window has three left)
        assert [m["n_tokens"] for m in by_rid["w-a"]] == [1] + [4] * 5 + [3]
        assert by_rid["w-a"][-1]["finish"] == "length"
        assert metrics.decode_tokens == 24 and metrics.emit_messages == 7
    elif case == "idle":
        # the loop parked (recv with a timeout) only with all 24 out
        after = [n for t, n in wire.recvs if t and n > 0]
        assert after and after[0] == 24
    assert metrics.emit_overlapped > 0
    if case in ("stream_end", "idle"):
        # all but the idle flush of the last window went out beside one
        assert metrics.decode_tokens - metrics.emit_overlapped <= 4


def _stub_words(prompt: bytes, n: int) -> list[str]:
    """The stub engine's stream for ``prompt``: the affine chain from
    its last token id, one `` t<N>`` word a token."""
    t = [ch % 97 + 1 for ch in prompt][-1]
    words = []
    for _ in range(n):
        t = (7 * t + 3) % 97
        words.append(f" t{t}")
    return words


def test_a_flush_sends_one_message_a_stream_through_serve():
    """K = 8, three streams decoding, a fourth admitted beside them,
    served through the real ``serve()`` over the stub paged engine. The
    flush after the dispatch that read the fourth's first token sends 4
    messages for 25 tokens: the first token ahead, then each stream's
    eight as one text; ``seq`` counts the request's tokens sent before,
    ``n_tokens`` those in the message, and the finishing stream's
    ``done`` / ``finish`` ride on its one message. Counters: tokens,
    messages, and one ``emit_us`` reading a dispatch that left a window
    in flight."""
    pytest.importorskip("jax")
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    engine = make_stub_paged_engine(max_slots=4, window=8, max_seq=128)
    caps = {"w-a": 40, "w-b": 25, "w-c": 40, "w-d": 3}
    wire = _Wire([(0, _req("w-a", 40)), (0, _req("w-b", 25)),
                  (0, _req("w-c", 40)), (28, _req("w-d", 3))])
    flushes: list[list[dict]] = []
    launched = [0]
    mark = [0]
    real_dispatch, real_collect = engine.dispatch, engine.collect

    def dispatch():
        out = real_dispatch()
        launched[0] += bool(engine.in_flight)
        mark[0] = len(wire.sent)
        return out

    def collect():
        flushes.append(wire.sent[mark[0]:])  # what the loop sent between
        return real_collect()

    engine.dispatch, engine.collect = dispatch, collect
    metrics = ServingMetrics(engine="paged")
    _serve_stub(wire, engine, metrics)
    # the loop offered the engine the next period's chunk after every
    # flush beside a window: w-b's and w-c's went ahead, w-a's (nothing
    # ran yet) was launched in line
    assert engine.chunks_run == 4 and engine.chunks_ahead >= 2
    assert metrics.chunks_ahead == metrics.phases["chunk_ahead"].count
    assert metrics.chunks_ahead_share() == engine.chunks_ahead / 4
    assert ServingMetrics().chunks_ahead_share() is None
    by_rid = _assert_consecutive(wire)
    want = {rid: _stub_words(b"hello world", cap) for rid, cap in caps.items()}
    for rid, chunks in by_rid.items():
        # every message is its tokens' texts concatenated, in order
        for m in chunks:
            assert m["text"] == "".join(
                want[rid][m["seq"]:m["seq"] + m["n_tokens"]]
            ), m
        assert _n(chunks) == caps[rid]
        assert [m["done"] for m in chunks] == [False] * (len(chunks) - 1) + [True]
        assert chunks[-1]["finish"] == "length"
        assert all("finish" not in m for m in chunks[:-1])
    # THE flush: a first token and three streams' windows
    flush = next(f for f in flushes if f and f[0]["request_id"] == "w-d")
    assert [
        (m["request_id"], m["seq"], m["n_tokens"], m["done"]) for m in flush
    ] == [
        ("w-d", 0, 1, False), ("w-a", 25, 8, False),
        ("w-b", 17, 8, True), ("w-c", 9, 8, False),
    ]
    assert flush[2]["finish"] == "length"
    assert flush[2]["text"] == "".join(want["w-b"][17:25])
    # counters: tokens, the messages that carried them, the flushes
    assert metrics.decode_tokens == sum(caps.values()) == 108
    assert metrics.emit_messages == len(wire.sent) == 18
    assert metrics.emit.count == launched[0] > 0
    snap = metrics.snapshot()
    assert snap["emit_messages"] == 18
    assert snap["emit_us"]["count"] == launched[0]


def test_a_lone_k1_stream_sends_the_messages_it_always_sent():
    """One stream, K = 1: every flush holds one token, so the wire
    carries what it carried before messages could hold several — seq
    0, 1, 2, ..., one word each, ``done`` on the last."""
    pytest.importorskip("jax")
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    engine = make_stub_paged_engine(max_slots=1, window=1, max_seq=128)
    wire = _Wire([(0, _req("w-a", 12))])
    metrics = ServingMetrics(engine="paged")
    _serve_stub(wire, engine, metrics)
    assert [m["seq"] for m in wire.sent] == list(range(12))
    assert [m["n_tokens"] for m in wire.sent] == [1] * 12
    assert [m["text"] for m in wire.sent] == _stub_words(b"hello world", 12)
    assert [m["done"] for m in wire.sent] == [False] * 11 + [True]
    assert metrics.decode_tokens == metrics.emit_messages == 12


# ---------------------------------------------------------------------------
# token identity on the real paged engine, tiny
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """Tiny random Qwen2 in the fused int8 layout, plus two adapters."""
    import os

    import torch
    from transformers import Qwen2Config, Qwen2ForCausalLM

    from dora_tpu.models.hf import qwen2

    config = Qwen2Config(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rope_theta=10000.0,
        rms_norm_eps=1e-6, tie_word_embeddings=False,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    path = tmp_path_factory.mktemp("qwen2-pipeline")
    Qwen2ForCausalLM(config).eval().save_pretrained(
        path, safe_serialization=True
    )
    lora_dir = tmp_path_factory.mktemp("adapters")
    rng = np.random.default_rng(7)
    for name, scale, rank in (("ta", 0.3, 4), ("tb", 0.5, 8)):
        np.savez(
            lora_dir / f"{name}.npz",
            **{f"a_{i}": rng.normal(size=(64, rank)).astype(np.float32)
               * scale for i in range(2)},
            **{f"b_{i}": rng.normal(size=(rank, 64)).astype(np.float32)
               * scale for i in range(2)},
        )
    cfg, params = qwen2.load(str(path), max_seq=64)
    os.environ["DORA_INT8_DECODE"] = "1"
    try:
        params = qwen2.quantize_decode(params, cfg)
    finally:
        os.environ.pop("DORA_INT8_DECODE", None)
    return cfg, params, str(lora_dir)


#: (prompt, max_new, adapter) by request; "p2" repeats "p0"'s prompt,
#: two full pages of it, and arrives after p0 finished its prefill.
LONG = [7, 3, 11, 5, 2, 13, 17, 19, 23, 29, 31, 37, 41, 43, 47, 53, 59, 61]
REQUESTS = {
    "p0": (LONG, 9, None),
    "p1": ([9, 4, 6], 12, "ta"),
    "p2": (LONG, 7, None),
    "p3": ([1, 2, 3, 4, 5], 10, "tb"),
}


@pytest.mark.parametrize(
    "variant", ["plain", "spec_k", "lora", "prefix_hit"]
)
def test_dispatch_collect_under_the_loop_equals_step_in_a_loop(tiny, variant):
    from dora_tpu.models.hf import qwen2

    cfg, params, lora_dir = tiny
    kw: dict = {}
    if variant == "spec_k":
        kw["spec_k"] = 2
    elif variant == "lora":
        kw["lora_dir"] = lora_dir
    elif variant == "prefix_hit":
        kw["prefix_cache"] = True
    engine = qwen2.make_paged_engine(
        params, cfg, max_slots=3, page_size=8, chunk=8, window=4, **kw
    )
    reqs = {
        k: (ids, mn, ad if variant == "lora" else None)
        for k, (ids, mn, ad) in REQUESTS.items()
    }

    def submit(key):
        ids, mn, ad = reqs[key]
        if ad:
            engine.submit(key, ids, mn, adapter=ad)
        else:
            engine.submit(key, ids, mn)

    # reference: step() in a loop; p2 joins once p0's prompt is cached
    want: dict[str, list[tuple[int, bool]]] = {}
    for key in ("p0", "p1"):
        submit(key)
    pending = ["p2", "p3"]
    for _ in range(200):
        for key, tok, done in engine.step():
            want.setdefault(key, []).append((int(tok), bool(done)))
        while pending and len(want.get("p0", ())) >= 2 and engine.free_slots:
            submit(pending.pop(0))
        if not engine.active and not pending:
            break
    assert {k: len(v) for k, v in want.items()} == {
        k: mn for k, (_i, mn, _a) in reqs.items()
    }
    hits_before = engine.prefix_cache.hits if variant == "prefix_hit" else 0

    # the same requests through _run_loop
    got: dict[str, list[tuple[int, bool]]] = {}
    beside = [0]
    metrics = ServingMetrics(engine="paged")
    backlog = AdmissionQueue(
        engine,
        lambda k, ids, mn, adapter: engine.submit(
            k, ids, mn, adapter=adapter
        ),
    )

    def emit(key, toks, done):
        got.setdefault(key, []).extend(
            (int(tok), bool(done) and i == len(toks) - 1)
            for i, tok in enumerate(toks)
        )
        beside[0] += len(toks) * engine.in_flight

    def handle_input(event):
        key = event["metadata"]["request_id"]
        ids, mn, ad = reqs[key]
        backlog.push(key, ids, mn, adapter=ad)

    sent = lambda: sum(len(v) for v in got.values())  # noqa: E731
    node = ScriptNode(
        [(0, _input("p0")), (0, _input("p1")), (3, _input("p2")),
         (3, _input("p3"))],
        sent=sent,
    )
    _run_loop(node, engine, backlog, metrics, handle_input, emit,
              lambda now: None)
    assert got == want
    assert metrics.emit_overlapped == beside[0] > 0
    assert not engine.in_flight and engine.active == 0
    engine.check_invariants()
    if variant == "prefix_hit":
        assert engine.prefix_cache.hits > hits_before
    if variant == "spec_k":
        assert engine.spec_k == 2
