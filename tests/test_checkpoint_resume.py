"""Serving-state checkpoint/resume: engine snapshots restore
token-identically mid-generation, drain/admit moves live streams between
engines, and the serve() loop resumes a crashed node from its last
cadence checkpoint with (request_id, seq)-dedup producing byte-identical
output. Also the engine-failure path: in-flight requests close with a
retriable ``finish="error"`` instead of dangling."""

from __future__ import annotations

import json
import signal

import pytest

from dora_tpu.metrics import ServingMetrics
from tests.test_serving_trace import _ServeNode, _req


def _mk_engine(max_slots: int = 2, window: int = 1):
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    return make_stub_paged_engine(
        max_slots=max_slots, max_seq=64, page_size=8, chunk=16,
        window=window,
    )


def _run_to_done(engine, tokens: dict[str, list[int]], max_steps=200) -> None:
    """Step until every stream finished, appending tokens per request."""
    for _ in range(max_steps):
        if engine.active == 0 and not getattr(engine, "_prefillq", None):
            return
        for key, token, done in engine.step():
            tokens.setdefault(key, []).append(int(token))
    raise AssertionError("engine did not finish")


def _reference_tokens() -> dict[str, list[int]]:
    ref = _mk_engine()
    ref.submit("r0", [5], 10)
    ref.submit("r1", [9], 10)
    tokens: dict[str, list[int]] = {}
    _run_to_done(ref, tokens)
    assert len(tokens["r0"]) == 10 and len(tokens["r1"]) == 10
    return tokens


# ---------------------------------------------------------------------------
# engine layer: snapshot / restore / drain / admit token identity
# ---------------------------------------------------------------------------


def test_checkpoint_restore_token_identical():
    """Tokens emitted before the snapshot plus tokens emitted by a fresh
    engine restored from it concatenate to exactly the uninterrupted
    reference stream — the mid-generation resume contract."""
    ref = _reference_tokens()

    a = _mk_engine()
    a.submit("r0", [5], 10)
    a.submit("r1", [9], 10)
    pre: dict[str, list[int]] = {}
    for _ in range(4):
        for key, token, done in a.step():
            pre.setdefault(key, []).append(int(token))
    snap = a.checkpoint_state()
    # JSON round-trip: the snapshot must survive the state.json file.
    snap = json.loads(json.dumps(snap))

    b = _mk_engine()
    restored = b.restore_state(snap)
    assert set(restored) == {"r0", "r1"}
    post: dict[str, list[int]] = {}
    _run_to_done(b, post)
    for rid in ("r0", "r1"):
        assert pre.get(rid, []) + post.get(rid, []) == ref[rid], rid
    b.check_invariants()


def test_drain_admit_streams_token_identical():
    """drain_streams releases every slot/page on the source; admit on a
    second engine continues each stream token-identically (fresh slots,
    fresh pages — the migrate-in path never pins physical ids)."""
    ref = _reference_tokens()

    a = _mk_engine()
    a.submit("r0", [5], 10)
    a.submit("r1", [9], 10)
    pre: dict[str, list[int]] = {}
    for _ in range(3):
        for key, token, done in a.step():
            pre.setdefault(key, []).append(int(token))
    state = a.drain_streams()
    assert a.active == 0
    assert a.free_pages == a.allocator.num_pages - 1  # every page back

    b = _mk_engine()
    admitted = b.admit_streams(json.loads(json.dumps(state)))
    assert set(admitted) == {"r0", "r1"}
    post: dict[str, list[int]] = {}
    _run_to_done(b, post)
    for rid in ("r0", "r1"):
        assert pre.get(rid, []) + post.get(rid, []) == ref[rid], rid
    a.check_invariants()
    b.check_invariants()


def test_checkpoint_restore_rebuilds_shared_page_custody():
    """Prefix-shared pages appear in SEVERAL slots' grants (and in the
    cache's radix tree): restore with pin_slots must rebuild the exact
    refcounts — first holder takes each physical page, later holders
    ref-share it — or a restored engine would double-take or leak on
    the next preemption."""
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    def build():
        return make_stub_paged_engine(
            max_slots=3, max_seq=64, page_size=8, chunk=16,
            prefix_cache=True,
        )

    tmpl = list(range(1, 33))  # 4 shared pages once cached
    a = build()
    a.submit("warm", tmpl + [50, 51], 4)
    tokens: dict[str, list[int]] = {}
    _run_to_done(a, tokens)  # template now cached
    a.submit("r0", tmpl + [60, 61], 8)
    a.submit("r1", tmpl + [70, 71, 72], 8)
    pre: dict[str, list[int]] = {}
    while a.prefilling:  # snapshot at a decode boundary: slots pinned
        for key, token, done in a.step():
            pre.setdefault(key, []).append(int(token))
    assert a.shared_pages >= 8  # both streams map the cached prefix
    a.check_invariants()
    snap = json.loads(json.dumps(a.checkpoint_state()))
    shared_counts = [m["shared"] for m in snap["slots"]]
    assert all(n >= 4 for n in shared_counts), shared_counts
    # the SAME physical pages appear in both slots' grants
    grants = [m["pages"] for m in snap["slots"]]
    overlap = set(grants[0]) & set(grants[1])
    assert len(overlap) >= 4, grants

    b = build()
    restored = b.restore_state(snap, pin_slots=True)
    assert set(restored) == {"r0", "r1"}
    # claimed-set custody: each shared page was taken once and
    # ref-shared by the second slot — refcount equals its holders
    for p in overlap:
        assert b.allocator.refcount(p) == 2, p
    b.check_invariants()
    post: dict[str, list[int]] = {}
    _run_to_done(b, post)
    b.check_invariants()
    assert b.free_pages == b.allocator.num_pages - 1  # every page home

    # The uninterrupted reference: same prompts, cold engine.
    ref_engine = build()
    ref_engine.submit("warm", tmpl + [50, 51], 4)
    _run_to_done(ref_engine, {})
    ref_engine.submit("r0", tmpl + [60, 61], 8)
    ref_engine.submit("r1", tmpl + [70, 71, 72], 8)
    ref: dict[str, list[int]] = {}
    _run_to_done(ref_engine, ref)
    for rid in ("r0", "r1"):
        assert pre.get(rid, []) + post.get(rid, []) == ref[rid], rid


# ---------------------------------------------------------------------------
# speculation × recovery: resume/migrate mid-generation with drafting on
# ---------------------------------------------------------------------------


def _mk_spec_engine(max_slots: int = 2, spec_k: int = 4, window: int = 1):
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    # cycle rule: period-4 token loop, the prompt-lookup best case —
    # drafts actually accept, so the snapshot carries real history.
    return make_stub_paged_engine(
        max_slots=max_slots, max_seq=64, page_size=8, chunk=16,
        window=window, spec_k=spec_k, cycle=4,
    )


def _spec_reference(max_new: int = 10) -> dict[str, list[int]]:
    ref = _mk_spec_engine(spec_k=0)
    ref.submit("r0", [5], max_new)
    ref.submit("r1", [6], max_new)
    tokens: dict[str, list[int]] = {}
    _run_to_done(ref, tokens)
    assert len(tokens["r0"]) == max_new and len(tokens["r1"]) == max_new
    return tokens


# One K=8 spec window can emit up to K*(spec_k+1) = 40 tokens, so the
# mid-generation snapshot needs max_new past that (and one step); K=1
# uses the small/slow shape.
@pytest.mark.parametrize(
    "window,max_new,pre_steps", [(1, 10, 4), (8, 45, 1)]
)
def test_spec_checkpoint_restore_token_identical(window, max_new, pre_steps):
    """Checkpoint/restore with speculation ON: the snapshot carries the
    draft-lookup history, and pre + post tokens equal the uninterrupted
    spec-off reference — verification keeps resumes greedy-exact."""
    ref = _spec_reference(max_new)

    a = _mk_spec_engine(window=window)
    a.submit("r0", [5], max_new)
    a.submit("r1", [6], max_new)
    pre: dict[str, list[int]] = {}
    for _ in range(pre_steps):
        for key, token, done in a.step():
            pre.setdefault(key, []).append(int(token))
    assert a.active == 2, "snapshot must land mid-generation"
    snap = json.loads(json.dumps(a.checkpoint_state()))
    for meta in snap["slots"]:
        if meta.get("decode"):
            assert meta.get("history"), "spec snapshot must carry history"

    b = _mk_spec_engine(window=window)
    assert set(b.restore_state(snap)) == {"r0", "r1"}
    post: dict[str, list[int]] = {}
    _run_to_done(b, post)
    for rid in ("r0", "r1"):
        assert pre.get(rid, []) + post.get(rid, []) == ref[rid], rid


def test_spec_restore_from_specless_snapshot():
    """A snapshot written by a spec-OFF engine (no history field)
    restores into a spec-ON engine token-identically: the lookup seeds
    from the last token (cold acceptance), and verification makes the
    output exact regardless of draft quality."""
    ref = _spec_reference()

    a = _mk_spec_engine(spec_k=0)
    a.submit("r0", [5], 10)
    a.submit("r1", [6], 10)
    pre: dict[str, list[int]] = {}
    for _ in range(4):
        for key, token, done in a.step():
            pre.setdefault(key, []).append(int(token))
    snap = json.loads(json.dumps(a.checkpoint_state()))
    assert all("history" not in m for m in snap["slots"])

    b = _mk_spec_engine(spec_k=4)
    b.restore_state(snap)
    post: dict[str, list[int]] = {}
    _run_to_done(b, post)
    for rid in ("r0", "r1"):
        assert pre.get(rid, []) + post.get(rid, []) == ref[rid], rid


def test_spec_drain_admit_token_identical():
    """Live migration with speculation ON: drain releases every page on
    the source; the target continues each stream token-identically and
    its acceptance counters actually move (history traveled too)."""
    ref = _spec_reference()

    a = _mk_spec_engine()
    a.submit("r0", [5], 10)
    a.submit("r1", [6], 10)
    pre: dict[str, list[int]] = {}
    for _ in range(3):
        for key, token, done in a.step():
            pre.setdefault(key, []).append(int(token))
    state = a.drain_streams()
    assert a.active == 0
    assert a.free_pages == a.allocator.num_pages - 1

    b = _mk_spec_engine()
    b.serving_metrics = ServingMetrics(engine="paged")
    assert set(b.admit_streams(json.loads(json.dumps(state)))) == {
        "r0", "r1",
    }
    post: dict[str, list[int]] = {}
    _run_to_done(b, post)
    for rid in ("r0", "r1"):
        assert pre.get(rid, []) + post.get(rid, []) == ref[rid], rid
    sm = b.serving_metrics
    assert sm.spec_drafted > 0
    assert 0 < sm.spec_accepted <= sm.spec_drafted


def test_page_allocator_take_specific_pages():
    from dora_tpu.models.batch_engine import PageAllocator

    alloc = PageAllocator(8)
    assert alloc.take([1, 2])
    assert alloc.in_use == 2
    assert not alloc.take([2, 3])  # 2 already granted: all-or-nothing
    assert not alloc.take([4, 4])  # duplicate ids rejected
    assert alloc.in_use == 2  # failed takes granted nothing
    assert alloc.take([3, 4])
    assert alloc.in_use == 4


# ---------------------------------------------------------------------------
# serve() layer: crash mid-generation, resume from cadence checkpoint
# ---------------------------------------------------------------------------


class _CrashNode(_ServeNode):
    """Delivers its events, then raises out of recv after ``crash_after``
    calls — the in-process stand-in for kill -9 mid-generation."""

    def __init__(self, events, crash_after: int):
        super().__init__(events)
        self._calls = 0
        self._crash_after = crash_after

    def recv(self, timeout=None):
        self._calls += 1
        if self._calls > self._crash_after:
            raise RuntimeError("simulated kill")
        if self._events:
            return self._events.pop(0)
        return None  # stream stays open: more polls until the "kill"


def _expected_text(prompt: str, max_new: int) -> str:
    """Analytic stub output: affine chain from the last prompt id."""
    ids = [ord(ch) % 97 for ch in prompt] or [1]
    t = ids[-1]
    out = []
    for _ in range(max_new):
        t = (7 * t + 3) % 97
        out.append(f" t{t}")
    return "".join(out)


def _merge_chunks(*nodes, replayed: list | None = None) -> dict[str, str]:
    """The consumer contract that turns at-least-once replay into
    byte-identical streams. A message's ``seq`` is the number of the
    request's tokens sent before it and ``n_tokens`` how many it holds;
    a replay starts on a message's edge (checkpoints are taken with
    nothing held) and sends the same text for the same tokens. So the
    consumer keeps the FIRST text it got for each token and takes from a
    replayed message only what reaches past it — wherever the restored
    engine cuts its windows. ``replayed`` collects the (request_id, seq)
    of messages that brought nothing new."""
    texts: dict[str, str] = {}
    #: request -> token index -> characters of the stream before it
    edges: dict[str, dict[int, int]] = {}
    for node in nodes:
        for _out, value, meta in node.sent:
            rid = meta.get("request_id")
            if rid is None:
                continue
            text = value.to_pylist()[0]
            seq, n = int(meta["seq"]), int(meta["n_tokens"])
            at = edges.setdefault(rid, {0: 0})[seq]
            have = texts.get(rid, "")
            overlap = have[at:at + len(text)]
            assert text.startswith(overlap), (rid, seq, overlap, text)
            if at + len(text) > len(have):
                texts[rid] = have[:at] + text
            elif n and replayed is not None:
                replayed.append((rid, seq))
            edges[rid][seq + n] = at + len(text)
    return texts


@pytest.mark.parametrize(
    "window, every, max_new, crash_after",
    [(1, 1, 8, 6), (4, 3, 20, 7)],
    ids=["one-token-messages", "window-messages-replayed"],
)
def test_serve_crash_and_resume_byte_identical(
    tmp_path, monkeypatch, window, every, max_new, crash_after
):
    """serve() checkpointing on a cadence dies mid-generation (recv
    raises); a second serve() over a FRESH engine restores the snapshot
    and completes both streams. Merged chunks, deduped by token range
    (``seq`` / ``n_tokens``), equal the analytic uninterrupted output —
    with one token a message (K = 1) and with a window's tokens a
    message, some of them sent twice (the crash came after sends the
    last checkpoint had not seen)."""
    from dora_tpu.nodehub.llm_server import serve

    monkeypatch.setenv("DORA_CHECKPOINT_DIR", str(tmp_path / "ckpt"))
    monkeypatch.setenv("DORA_CHECKPOINT_EVERY", str(every))
    prev_term = signal.getsignal(signal.SIGTERM)
    kwargs = dict(
        encode=lambda text: [ord(ch) % 97 for ch in text] or [1],
        decode_one=lambda t: f" t{t}",
        max_new_cap=max_new,
    )
    try:
        node1 = _CrashNode(
            [_req("ab", max_new), _req("cd", max_new)],
            crash_after=crash_after,
        )
        with pytest.raises(RuntimeError, match="simulated kill"):
            serve(node1, _mk_engine(window=window), ServingMetrics(),
                  **kwargs)
        assert (tmp_path / "ckpt" / "state.json").exists()
        # The crash must NOT have produced complete streams on its own.
        done1 = [m for _o, _v, m in node1.sent if m.get("done")]
        assert len(done1) < 2

        metrics2 = ServingMetrics()
        node2 = _ServeNode([])  # no new traffic: pure resume
        serve(node2, _mk_engine(window=window), metrics2, **kwargs)
        assert metrics2.restored_streams == 2
    finally:
        signal.signal(signal.SIGTERM, prev_term)

    replayed: list = []
    texts = _merge_chunks(node1, node2, replayed=replayed)
    assert texts == {
        "wire-ab": _expected_text("ab", max_new),
        "wire-cd": _expected_text("cd", max_new),
    }
    sizes = {m["n_tokens"] for _o, _v, m in node1.sent + node2.sent}
    if window == 1:
        assert sizes == {1}
    else:
        # a window's tokens travel as one message, and the replay sent
        # some again: the same text under the same (request_id, seq),
        # which _merge_chunks asserted as it dropped them
        assert window in sizes and replayed, (sizes, replayed)
        first_run = {(m["request_id"], m["seq"]) for _o, _v, m in node1.sent}
        assert set(replayed) <= first_run


def test_serve_replayed_input_not_readmitted(tmp_path, monkeypatch):
    """Checkpoint mode dedups daemon input replay by wire request_id: a
    rid the restored engine already owns is dropped, not double-run."""
    from dora_tpu.nodehub.llm_server import serve

    monkeypatch.setenv("DORA_CHECKPOINT_DIR", str(tmp_path / "ckpt"))
    monkeypatch.setenv("DORA_CHECKPOINT_EVERY", "1")
    prev_term = signal.getsignal(signal.SIGTERM)
    kwargs = dict(
        encode=lambda text: [ord(ch) % 97 for ch in text] or [1],
        decode_one=lambda t: f" t{t}",
        max_new_cap=8,
    )
    try:
        node1 = _CrashNode([_req("ab", 8)], crash_after=4)
        with pytest.raises(RuntimeError):
            serve(node1, _mk_engine(), ServingMetrics(), **kwargs)

        # The daemon replays the un-acked input after respawn: same rid.
        metrics2 = ServingMetrics()
        node2 = _ServeNode([_req("ab", 8)])
        serve(node2, _mk_engine(), metrics2, **kwargs)
        assert metrics2.restored_streams == 1
        assert metrics2.requests == 0  # replayed rid rejected, not re-run
    finally:
        signal.signal(signal.SIGTERM, prev_term)

    texts = _merge_chunks(node1, node2)
    assert texts == {"wire-ab": _expected_text("ab", 8)}


# ---------------------------------------------------------------------------
# engine failure: in-flight requests fail retriable, never dangle
# ---------------------------------------------------------------------------


def test_engine_exception_fails_inflight_with_error_finish():
    """When the engine wedges mid-step, every in-flight request — the
    active stream AND the parked one — closes with a done-chunk carrying
    ``finish="error"`` before the exception propagates (the respawn
    policy handles the node; clients see a retriable error, not a
    silent dead SSE stream)."""
    from dora_tpu.nodehub.llm_server import serve

    engine = _mk_engine(max_slots=1)
    steps = [0]
    orig_collect = engine.collect

    def wedge():
        # The wait is where a wedged device shows: the loop drives the
        # engine by dispatch() + collect(), not step().
        steps[0] += 1
        if steps[0] > 2:
            raise RuntimeError("device wedged")
        return orig_collect()

    engine.collect = wedge
    node = _ServeNode([_req("ab", 8), _req("cd", 8)])
    with pytest.raises(RuntimeError, match="device wedged"):
        serve(
            node, engine, ServingMetrics(),
            encode=lambda text: [ord(ch) % 97 for ch in text] or [1],
            decode_one=lambda t: f" t{t}",
            max_new_cap=8,
        )
    errors = {
        m.get("request_id"): m.get("finish")
        for _o, _v, m in node.sent
        if m.get("done")
    }
    assert errors == {"wire-ab": "error", "wire-cd": "error"}
    assert node.closed  # serve's finally still ran


# ---------------------------------------------------------------------------
# migrate-in back-pressure: undersized targets defer, races fail retriable
# ---------------------------------------------------------------------------


class _MigrateTargetNode(_ServeNode):
    """Open stream (keep_alive target) that delivers STOP once the
    engine has gone idle and a few polls have passed — long enough for
    the migrate-in poll to run, short enough to keep the test fast."""

    def __init__(self, engine, min_polls: int = 3):
        super().__init__([])
        self._engine = engine
        self._min_polls = min_polls
        self._polls = 0

    def recv(self, timeout=None):
        self._polls += 1
        if (
            self._polls >= self._min_polls
            and self._engine.active == 0
            and not self._engine._prefillq
        ):
            return {"type": "STOP"}
        return None


def _write_handoff(migrate_dir, source_engine) -> tuple[str, dict[str, int]]:
    """Drain ``source_engine`` into a handoff file the target's
    ``DORA_MIGRATE_DIR`` poll sees, mirroring handle_migrate's format."""
    import os

    state = source_engine.drain_streams()
    keys = [m["request_id"] for m in state["slots"]]
    payload = {
        "engine": state,
        "backlog": [],
        "wire_ids": {k: f"wire-{k}" for k in keys},
        "seqs": {k: 3 for k in keys},
        "ctxs": {k: "" for k in keys},
    }
    os.makedirs(migrate_dir, exist_ok=True)
    path = os.path.join(migrate_dir, "streams-1-1.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)
    return path, {k: len(m["pages"]) for k, m in zip(
        keys, state["slots"]
    )}


def test_migrate_in_defers_handoff_target_cannot_admit(tmp_path, monkeypatch):
    """An undersized target must LEAVE an oversized handoff on disk —
    unclaimed, for a bigger peer or a later retry — instead of claiming
    streams it cannot admit and losing them (round-7 known issue)."""
    import os

    from dora_tpu.nodehub.llm_server import serve

    src = _mk_engine(max_slots=2)
    src.submit("r0", [5], 10)
    src.submit("r1", [9], 10)
    for _ in range(3):
        src.step()
    path, _pages = _write_handoff(str(tmp_path), src)

    monkeypatch.setenv("DORA_MIGRATE_DIR", str(tmp_path))
    target = _mk_engine(max_slots=1)  # one slot for a two-stream handoff
    metrics = ServingMetrics()
    node = _MigrateTargetNode(target)
    serve(
        node, target, metrics,
        encode=lambda text: [ord(ch) % 97 for ch in text] or [1],
        decode_one=lambda t: f" t{t}",
        max_new_cap=8,
    )
    assert os.path.exists(path), "handoff must stay on disk, unclaimed"
    assert not os.path.exists(path + ".claimed")
    assert metrics.migrated_in == 0
    assert node.sent == []  # no half-admitted tokens, no error chunks


def test_migrate_in_admit_race_fails_streams_retriable(tmp_path, monkeypatch):
    """If capacity vanishes between the peek-time fits check and the
    claim, every handoff stream closes with a retriable
    ``finish="error"`` chunk under its own wire id — the client can
    retry; before the fix the streams silently vanished."""
    import os

    from dora_tpu.nodehub.llm_server import serve

    src = _mk_engine(max_slots=2)
    src.submit("r0", [5], 10)
    src.submit("r1", [9], 10)
    for _ in range(3):
        src.step()
    path, _pages = _write_handoff(str(tmp_path), src)

    monkeypatch.setenv("DORA_MIGRATE_DIR", str(tmp_path))
    target = _mk_engine(max_slots=2)  # fits at peek time...

    def raced(state):  # ...but the admit itself loses the race
        raise RuntimeError("no free slot for migrated stream")

    target.admit_streams = raced
    metrics = ServingMetrics()
    node = _MigrateTargetNode(target)
    serve(
        node, target, metrics,
        encode=lambda text: [ord(ch) % 97 for ch in text] or [1],
        decode_one=lambda t: f" t{t}",
        max_new_cap=8,
    )
    assert not os.path.exists(path)  # claimed: the failure was consumed
    errors = {
        m.get("request_id"): (m.get("finish"), m.get("seq"))
        for _o, _v, m in node.sent
        if m.get("done")
    }
    # Error chunks carry the MIGRATED seq counter, so consumers dedup
    # them against the source's stream like any other chunk.
    assert errors == {"wire-r0": ("error", 3), "wire-r1": ("error", 3)}
    assert metrics.migrated_in == 0
    assert metrics.rejected == 2
