"""Continuous-batching LLM responder for the OpenAI server.

Reference parity: node-hub/dora-openai-server pairs with ONE llm node
that answers one request at a time (openai-proxy-server/src/main.rs:
30-50 — requests serialize through the dataflow). This node batches:
every ``text`` input carrying a ``request_id`` is admitted into a
serving-engine slot, and each engine step advances ALL active requests
one token off a single LM weight stream (the batched fused kernels,
ops/decode_block). Token deltas stream back on ``response`` tagged
``{request_id, done, seq, n_tokens}`` — the openai_server's concurrent
mode routes them to the right SSE stream. A message holds every token
the loop holds for its stream when it sends (a window's, up to K; a
first token alone): ``n_tokens`` of them, ``seq`` being the number of
the request's tokens sent before it.

The engine (models/batch_engine.PagedBatchEngine): KV lives in a pool
of page-size blocks routed through per-slot block tables, prompts
prefill in fixed-shape chunks interleaved with decode — concurrency
scales with actual context held, long prompts don't stall active
streams, and admission is page-aware (a request is admitted only while
free pages cover prompt + max_new, so an admitted stream can never OOM
mid-decode).

Model: a Qwen2-family checkpoint from ``DORA_HF_CHECKPOINT`` (quantized
into the fused decode layout — int8 by default, DORA_INT4_DECODE=1 for
int4); without a checkpoint the node refuses loudly (a chat server with
random weights helps nobody).

The event loop runs at WINDOW granularity: each engine step launches
one fused K-tick decode window (DORA_MULTISTEP_K, default 8) and gets
up to K tokens per stream back off a single device round-trip, so host
dispatch/fetch cost amortizes across K tokens. Admissions, prefill
chunks and backlog draining happen at window boundaries — backlog
latency quantizes to one window. The loop is pipelined by one window:
it launches window N+1 (``engine.dispatch()``), sends window N's
tokens while the device runs — one message per stream — and only then
waits (``engine.collect()``); a prompt's first token leaves right after
the launch, ahead of them and before its window is collected; with
nothing left to send, the next period's prefill chunk goes to the device
behind the running window (``engine.ahead()``), so the host's work after
``collect()`` runs beside a chunk instead of an idle chip. Tokens
collected and not yet sent are flushed before anything reads
per-request state (preemption, migration, checkpoints, errors, exit, an
engine gone idle).

Env: DORA_BATCH_SLOTS (default 16) concurrent streams;
DORA_MAX_NEW_TOKENS (default 32) per-request cap (a request's
``max_tokens`` lowers it); DORA_MAX_SEQ cache length; DORA_PAGE_SIZE
(default 16) KV rows per page; DORA_PREFILL_CHUNK prefill chunk rows
(default min(256, max_seq)); DORA_MULTISTEP_K (default 8) fused decode
ticks per dispatch (1 = per-token dispatch); DORA_SPEC_K (default 0 =
off) drafts k tokens per tick via prompt-lookup and verifies them in
the same dispatch — up to K·(k+1) tokens per round trip, greedy-exact —
with DORA_SPEC_NGRAM (default 2) the lookup ngram width.

Traffic shaping (descriptor ``qos:`` block -> DORA_QOS_* env):
requests carry a priority class (``interactive``/``standard``/
``batch``, wire metadata ``qos_class``, default
DORA_QOS_DEFAULT_CLASS) and optionally a queue-wait ``deadline_ms``;
admission drains classes by aged weight (DORA_QOS_AGING_S) so batch
never starves; DORA_QOS_DEPTH_{INTERACTIVE,STANDARD,BATCH} bound the
per-class backlog and DORA_QOS_SHED_WAIT_MS bounds queue wait — both
shed with a retriable ``overloaded`` chunk (+retry_after_ms) instead
of growing the backlog; DORA_QOS_PREEMPT=1 lets a blocked higher-class
request evict a lower-class decode (page grant freed whole; the victim
re-admits later and resumes token-identically by re-prefilling
prompt+emitted). DORA_AUTOTUNE_K=1 adds the SLO-driven window
autotuner (DORA_AUTOTUNE_INTERVAL_S / _LADDER / _HYSTERESIS /
_BURN_WINDOW_S): TTFT burn or shedding steps K down a rung and pauses
speculation, saturated decode-heavy windows step it back up.

Serving metrics (slots, free pages, backlog, decode tokens/s, TTFT
histogram) ship to the daemon every second and surface in
``dora-tpu metrics [--watch]``.

Elastic recovery: ``DORA_CHECKPOINT_DIR`` (+
``DORA_CHECKPOINT_EVERY``, default 8 windows) snapshots live serving
state atomically — and on SIGTERM — and restores it on respawn,
resuming mid-generation streams token-identically; every response
chunk carries ``seq`` and ``n_tokens`` (a replay sends the same text
under the same pair) so consumers dedup the at-least-once replay.
``DORA_MIGRATE_DIR`` makes this node a migration target: it stays
alive past end-of-stream and admits handoff files drained by
``dora-tpu migrate`` from another engine, continuing each stream
under its original trace id.

Dataflow usage::

    - id: llm
      path: module:dora_tpu.nodehub.llm_server
      inputs: {text: api/text}
      outputs: [response]
"""

from __future__ import annotations

import os
import sys
import time

import pyarrow as pa

from dora_tpu import profiling, telemetry
from dora_tpu.metrics import percentile_from_counts
from dora_tpu.node import Node


#: checkpoint ``model_type`` -> the module under ``models/hf/`` that
#: serves it (``load``, ``quantize_decode``, ``make_paged_engine``). A
#: ``config.json`` without the key is taken for the Qwen2 family, as
#: before the table existed.
MODEL_MODULES = {
    "qwen2": "qwen2",
    "kimi_k2": "kimi_k2",
    "deepseek_v3": "kimi_k2",
    "falcon_h1": "falcon_h1",
    "ouro": "ouro",
    "exaone_moe": "exaone_moe",
    "glm5_next_text": "glm5_next",
    "KeyeVL2": "keye_vl2",
    "zaya": "zaya",
    "olmo_hybrid": "olmo_hybrid",
    "kimi_linear": "kimi_linear",
}


def model_module(model_type: str | None):
    """The ``models/hf`` module for a checkpoint's ``model_type``; an
    unknown type is refused by name."""
    import importlib

    name = MODEL_MODULES.get(model_type or "qwen2")
    if name is None:
        raise RuntimeError(
            f"llm_server cannot serve model_type {model_type!r}: it knows "
            f"{sorted(MODEL_MODULES)}"
        )
    return importlib.import_module(f"dora_tpu.models.hf.{name}")


def make_engine(params, cfg, eos=None, module=None, **model_kw):
    """Build the serving engine from the env knobs. ``module`` is the
    model's ``models/hf`` module (default: qwen2); ``model_kw`` goes to
    its ``make_paged_engine`` as it stands (a cache audit's, never the
    server's)."""
    if module is None:
        from dora_tpu.models.hf import qwen2 as module

    slots = int(os.environ.get("DORA_BATCH_SLOTS", "16"))
    page_size = int(os.environ.get("DORA_PAGE_SIZE", "16"))
    chunk_env = os.environ.get("DORA_PREFILL_CHUNK")
    chunk = int(chunk_env) if chunk_env else None
    window = int(os.environ.get("DORA_MULTISTEP_K", "8"))
    # Shared-prefix radix cache: default ON at the serving front door
    # (DORA_PREFIX_CACHE=0 restores the exact pre-cache program).
    prefix_on = os.environ.get("DORA_PREFIX_CACHE", "1") != "0"
    prefix_pages = int(os.environ.get("DORA_PREFIX_CACHE_PAGES", "0"))
    return module.make_paged_engine(
        params, cfg, max_slots=slots, eos=eos, page_size=page_size,
        chunk=chunk, window=window, prefix_cache=prefix_on,
        prefix_cache_pages=prefix_pages, **model_kw,
    )


#: QoS priority classes, highest first. Weights are drain-order scores,
#: not shares: the scheduler admits the class whose HEAD has the top
#: score, where aging multiplies a head's weight by
#: ``1 + waited / aging_s`` — a parked ``batch`` head overtakes a fresh
#: ``interactive`` one after ``(8/1 - 1) * aging_s`` seconds, so batch
#: never starves forever but never jumps a live interactive burst.
QOS_CLASSES = ("interactive", "standard", "batch")
QOS_WEIGHTS = {"interactive": 8.0, "standard": 4.0, "batch": 1.0}

#: request ``model`` values that mean "the base model" (no LoRA
#: adapter): the OpenAI gateway's default, and the explicit aliases.
#: Any OTHER name is a multi-tenant LoRA adapter, resolved against the
#: engine's resident-adapter catalog (DORA_LORA_DIR stems).
BASE_MODEL_NAMES = ("", "dora-tpu", "base")


class QosConfig:
    """Traffic-shaping knobs, from the descriptor ``qos:`` block (the
    daemon injects it as ``DORA_QOS_*`` env at spawn; descriptor
    ``env:`` entries override). All bounds optional: unset = the
    pre-QoS behavior (single-class FIFO, never shed, never preempt)."""

    __slots__ = ("default_class", "depths", "shed_wait_s", "aging_s",
                 "preempt_on")

    def __init__(self, *, default_class="standard", depths=None,
                 shed_wait_s=None, aging_s=10.0, preempt_on=False):
        assert default_class in QOS_CLASSES, default_class
        self.default_class = default_class
        #: per-class parked-entry bound (None = unbounded)
        self.depths: dict[str, int | None] = {
            c: (depths or {}).get(c) for c in QOS_CLASSES
        }
        #: queue-wait shed deadline, seconds (None = wait forever)
        self.shed_wait_s = shed_wait_s
        #: aging time constant, seconds (0/None disables aging)
        self.aging_s = aging_s
        self.preempt_on = preempt_on

    @classmethod
    def from_env(cls) -> "QosConfig":
        def _f(key):
            raw = os.environ.get(key, "")
            try:
                return float(raw) if raw else None
            except ValueError:
                return None

        def _i(key):
            v = _f(key)
            return int(v) if v is not None else None

        default = os.environ.get("DORA_QOS_DEFAULT_CLASS", "standard")
        if default not in QOS_CLASSES:
            default = "standard"
        shed_ms = _f("DORA_QOS_SHED_WAIT_MS")
        aging = _f("DORA_QOS_AGING_S")
        return cls(
            default_class=default,
            depths={
                "interactive": _i("DORA_QOS_DEPTH_INTERACTIVE"),
                "standard": _i("DORA_QOS_DEPTH_STANDARD"),
                "batch": _i("DORA_QOS_DEPTH_BATCH"),
            },
            shed_wait_s=shed_ms / 1000.0 if shed_ms is not None else None,
            aging_s=aging if aging is not None else 10.0,
            preempt_on=os.environ.get("DORA_QOS_PREEMPT", "") == "1",
        )


class AdmissionQueue:
    """Per-class weighted backlog in front of a serving engine.

    Only ``fits()``-admissible requests ever enter (the caller rejects
    never-admissible ones up front), so every head can eventually start
    once capacity frees. :meth:`drain` must run at EVERY point capacity
    may have appeared — after a push, after an engine step freed
    slots/pages, and on the idle path — a parked request must never
    wait for unrelated traffic to trigger its admission (regression:
    tests/test_llm_backlog.py).

    Scheduling: each drain iteration admits the class whose HEAD entry
    scores highest (class weight aged by wait time, see QOS_WEIGHTS);
    within a class, FIFO. With every entry in one class this IS the old
    FIFO queue. There is deliberately no cross-class bypass: a small
    ``batch`` request never slips past a blocked ``interactive`` head —
    that's what preemption is for.

    Overload turns into signals instead of unbounded backlog:
    ``on_shed(key, reason, waited_s)`` fires when a push overflows its
    class depth bound or a parked entry exceeds the queue-wait deadline
    (config ``shed_wait_s``, tightened per-request by ``deadline_s``).
    ``preempt(cls)`` (optional) is consulted when the best head cannot
    be admitted: return True after evicting a lower-class victim (and
    re-parking it via :meth:`requeue`) to make drain re-score and
    retry; return False to leave the head parked.

    ``on_admit(key, waited_s)`` (optional) fires just before a parked
    request starts, with how long it sat in the backlog — the server
    feeds the ``backlog_wait`` histogram and the ``queued`` lifecycle
    span from it.

    ``tracer`` (a telemetry.ServingTracer) times the calls into the
    engine's admission test as the loop phase ``admit.can_admit``."""

    def __init__(self, engine, start, on_admit=None, clock=time.monotonic,
                 qos: QosConfig | None = None, on_shed=None, preempt=None,
                 on_stall=None, tracer=None):
        self._engine = engine
        self._tracer = tracer or telemetry.ServingTracer()
        self._start = start
        self._on_admit = on_admit
        self._clock = clock
        self._qos = qos or QosConfig()
        self._on_shed = on_shed
        self._preempt = preempt
        self._on_stall = on_stall
        #: key -> why its head-of-class admission is blocked
        #: (engine.admit_blocker); set once per parking episode so
        #: ``on_stall`` fires once, cleared on admit/shed.
        self._stall_reasons: dict[str, str] = {}
        #: class -> [[key, ids, max_new, t_in, deadline_s, adapter], ...]
        #: FIFO. ``adapter`` is the stream's LoRA tenant (None = base);
        #: it parks with the request and rides admission into
        #: ``engine.submit`` — a parked tenant must not lose its model.
        self._q: dict[str, list[list]] = {c: [] for c in QOS_CLASSES}

    def __len__(self) -> int:
        return sum(len(q) for q in self._q.values())

    def depths(self) -> dict[str, int]:
        """Per-class parked depth (the qos_depth gauges)."""
        return {c: len(q) for c, q in self._q.items()}

    def queued(self, key: str) -> bool:
        """Is ``key`` still parked (pushed but not yet admitted)?"""
        return any(
            entry[0] == key for q in self._q.values() for entry in q
        )

    def stall_reason(self, key: str) -> str | None:
        """Why ``key``'s current parking episode is blocked (None when
        it never reached the head while inadmissible). Valid inside the
        on_admit/on_shed callbacks — cleared right after."""
        return self._stall_reasons.get(key)

    def push(self, key: str, ids: list[int], max_new: int,
             qos: str | None = None, deadline_s: float | None = None,
             adapter: str | None = None) -> bool:
        """Park (then drain). Returns False when the entry was shed at
        the door because its class queue is at its depth bound."""
        cls = qos if qos in QOS_CLASSES else self._qos.default_class
        cap = self._qos.depths.get(cls)
        if cap is not None and len(self._q[cls]) >= cap:
            if self._on_shed is not None:
                self._on_shed(key, f"depth:{cls}", 0.0)
            return False
        self._q[cls].append(
            [key, ids, max_new, self._clock(), deadline_s, adapter]
        )
        self.drain()
        return True

    def requeue(self, key: str, ids: list[int], max_new: int,
                qos: str | None = None,
                adapter: str | None = None) -> None:
        """Park a preempted stream at the FRONT of its class, wait clock
        reset (aging credit is forfeited — a re-aged victim outscoring
        its preemptor would ping-pong the slot). No drain: only called
        from inside the preempt hook, mid-drain."""
        cls = qos if qos in QOS_CLASSES else self._qos.default_class
        self._q[cls].insert(
            0, [key, ids, max_new, self._clock(), None, adapter]
        )

    def _shed_expired(self) -> None:
        if self._on_shed is None:
            return
        now = self._clock()
        for q in self._q.values():
            kept = []
            for entry in q:
                limit = self._qos.shed_wait_s
                if entry[4] is not None:
                    limit = entry[4] if limit is None else min(limit, entry[4])
                waited = now - entry[3]
                if limit is not None and waited > limit:
                    self._on_shed(entry[0], "queue_wait", waited)
                    self._stall_reasons.pop(entry[0], None)
                else:
                    kept.append(entry)
            q[:] = kept

    def _best(self, now: float) -> str | None:
        best_cls, best_score = None, -1.0
        for cls in QOS_CLASSES:
            q = self._q[cls]
            if not q:
                continue
            score = QOS_WEIGHTS[cls]
            if self._qos.aging_s:
                score *= 1.0 + (now - q[0][3]) / self._qos.aging_s
            if score > best_score:
                best_cls, best_score = cls, score
        return best_cls

    def drain(self) -> None:
        self._shed_expired()
        while True:
            now = self._clock()
            cls = self._best(now)
            if cls is None:
                return
            key, ids, max_new, t_in, _dl, adapter = self._q[cls][0]
            with self._tracer.phase("admit.can_admit"):
                fits = self._engine.can_admit(len(ids), max_new, adapter)
            if not fits:
                if self._preempt is not None and self._preempt(cls):
                    continue  # a victim was evicted: re-score and retry
                # Attribute the stall: "adapter_residency" means
                # everything else admits but the tenant's adapter
                # cannot evict a pinned resident — without this tag it
                # reads as plain overload. Re-evaluated every drain
                # (a capacity stall can become adapter-gated as pages
                # free), but on_stall fires only on transitions.
                with self._tracer.phase("admit.can_admit"):
                    reason = self._engine.admit_blocker(
                        len(ids), max_new, adapter
                    ) or "capacity"
                if self._stall_reasons.get(key) != reason:
                    self._stall_reasons[key] = reason
                    if self._on_stall is not None:
                        self._on_stall(key, reason)
                return
            self._q[cls].pop(0)
            if self._on_admit is not None:
                self._on_admit(key, now - t_in)
            self._stall_reasons.pop(key, None)
            self._start(key, ids, max_new, adapter)

    def pending(self) -> list[tuple[str, list[int], int, str, str | None]]:
        """Parked requests in class-priority order — serialized into
        checkpoints and migration handoffs (the wait-start time and
        deadline are process-local and deliberately dropped)."""
        return [
            (k, list(ids), mn, cls, ad)
            for cls in QOS_CLASSES
            for k, ids, mn, _t, _dl, ad in self._q[cls]
        ]

    def take_all(self) -> list[tuple[str, list[int], int, str, str | None]]:
        """Drain the backlog without starting anything (migrate-out:
        parked requests travel with the live streams)."""
        out = self.pending()
        for q in self._q.values():
            q.clear()
        self._stall_reasons.clear()
        return out


def _flush(held: list, emit) -> int:
    """Send every held token, one ``emit`` a stream: a key's tokens in
    the order they were held, the keys in the order of their first held
    token, ``done`` that of the key's last. Returns how many tokens
    went."""
    n = len(held)
    by_key: dict[str, list[int]] = {}
    ended: set[str] = set()
    for key, token, done in held:
        assert key not in ended, f"{key}: a token held after its last"
        by_key.setdefault(key, []).append(token)
        if done:
            ended.add(key)
    held.clear()
    for key, tokens in by_key.items():
        emit(key, tokens, key in ended)
    return n


class ProfileCapture:
    """An on-demand profiler capture (``cm.StartProfile`` /
    ``StopProfile``), as the serving loop sees it. ``handle`` takes the
    PROFILE events; while ``active`` the loop calls ``tick(now)`` every
    turn, so the deadline is met within a turn and not at the next
    once-a-second report. Both edges fall between a ``collect()`` and
    the next ``dispatch()``: the model's counters are read there
    (``on_edge``), so the two reads bracket exactly the ticks the
    capture holds (a chunk that went ahead may be in flight, and the
    read waits for it, at either edge). Writing the capture
    (``stop``) runs on the loop's thread and holds it, seconds to a
    minute on the chip: on a thread of its own the write let the loop go
    on but took several times as long, past the end of a benchmark
    window (``PERF.md`` section 6, PR 54)."""

    def __init__(self, node, tracer, clock, on_edge,
                 start=profiling.start_capture, stop=profiling.stop_capture):
        self._node, self._tracer, self._clock = node, tracer, clock
        self._on_edge, self._start, self._stop = on_edge, start, stop
        self.active = False
        self._dir = ""
        self._deadline = 0.0
        self._start_error: str | None = None

    def _reply(self, artifact: str, error: str | None) -> None:
        try:
            self._node.report_profile(artifact, error)
        except Exception:
            pass  # capture is best-effort; serving never blocks on it

    def handle(self, event) -> None:
        md = event.get("metadata") or {}
        action = md.get("action", "")
        if action == "start":
            if self.active:
                self._reply("", "capture already active")
                return
            self._dir = os.path.join(
                profiling.profile_dir(),
                f"capture-{os.getpid()}-{int(time.time())}",
            )
            try:
                self._start_error = self._start(self._dir)
            except Exception as exc:  # on the chip: an error reply
                self._reply("", f"{type(exc).__name__}: {exc}")
                return
            self.active = True
            self._on_edge("start")
            self._deadline = self._clock() + float(md.get("seconds") or 0.0)
            self._tracer.instant("profile_start", "(engine)", self._dir)
        elif action == "stop":
            if self.active:
                self._finish()
            else:
                self._reply("", "no capture active")

    def tick(self, now: float) -> None:
        """One turn of the loop with a capture active."""
        if now >= self._deadline:
            self._finish()

    def _finish(self) -> None:
        artifact, error = "", None
        self._on_edge("stop")
        try:
            artifact = self._stop(self._dir, self._start_error)
        except Exception as exc:  # on the chip a failed capture is said so
            error = f"{type(exc).__name__}: {exc}"
        self.active = False
        self._start_error = None
        self._tracer.instant("profile_stop", "(engine)", artifact or error)
        self._reply(artifact, error)


def _run_loop(node, engine, backlog, metrics, handle_input, emit,
              report, clock=time.monotonic, on_tick=None, on_step=None,
              handle_migrate=None,
              on_engine_error=None, keep_alive=False,
              fleet_tick=None, held: list | None = None,
              tracer=None, capture: ProfileCapture | None = None) -> None:
    """Window-granular serving loop, factored out of :func:`main` so
    tests can drive it with fake nodes/engines. Each iteration: drain
    the pending events, ``engine.dispatch()`` (one prefill chunk, then
    the launch of one K-tick decode window), emit the first tokens the
    dispatch returned and the tokens HELD from the previous window, one
    message a stream (:func:`_flush`) — the device runs the new window
    meanwhile — then ``engine.ahead()`` queues the NEXT period's chunk
    behind that window (the next ``dispatch()`` then launches none: what
    the host does from here to the next launch runs beside a chunk),
    then ``engine.collect()`` waits for the window and its
    tokens become the held ones. Then ALWAYS drain the backlog —
    capacity appears when a step frees slots/pages, but also the idle
    path must admit (a parked request with zero active streams used to
    sit until unrelated traffic arrived).

    ``held`` is state the wire has not seen, and whatever reads
    per-request state sends it first (:func:`_flush`). The loop does
    so wherever it sees the reader: before a MIGRATE event, before
    ``on_engine_error()``, when a ``collect()`` leaves the engine idle
    (the loop may then park in ``recv`` or exit) and when it stops.
    Readers it cannot see — preemption inside admission, a checkpoint
    inside ``on_tick``/``on_step`` — share the list (``held=``) and
    flush it themselves.

    Recovery hooks (all optional, wired by :func:`serve` when the env
    enables them): ``on_tick()`` runs first each iteration and returns
    True to stop (SIGTERM checkpoint), ``on_step()`` runs after
    ``collect()`` (checkpoint cadence — never with a window in flight,
    where the device's state is a window ahead of the slots', and never
    with tokens held, where the snapshot would count tokens the wire
    never saw), ``handle_migrate(event)`` drains live streams at this
    window boundary, ``on_engine_error()`` fails in-flight requests
    before a step exception propagates. ``keep_alive`` parks instead of
    exiting when the input stream ends (migration targets wait for
    handoffs until STOP). ``capture`` (a :class:`ProfileCapture`) takes
    the PROFILE events and, while one is active, a look every turn: its
    deadline is met within a turn, not at the next report.

    A turn is tiled by the phases of ``telemetry.LOOP_PHASES``: the
    loop ``tracer.switch()``es from one to the next here, the engine
    does inside ``dispatch()`` and ``collect()`` (the tracer is the one
    the engine holds, its clock this loop's), and the stamps that
    ``dispatch_gap`` and ``emit`` are observed from are those switches'
    own — so the gap's phases add up to the gap."""
    if held is None:
        held = []
    if tracer is None:
        tracer = telemetry.ServingTracer(clock=clock)
        tracer.histograms = metrics.phases
        engine.tracer = tracer

    def half(call):
        """Run one half of the engine's step; if it raises, every
        stream is told before the exception goes on."""
        try:
            return call()
        except Exception:
            tracer.close()
            _flush(held, emit)
            if on_engine_error is not None:
                on_engine_error()
            raise

    last_collect_end: float | None = None
    report_last = tracer.switch("housekeeping")
    try:
        while True:
            if on_tick is not None and on_tick():
                break
            # Drain a BURST of pending events before the next window
            # (the first recv parks when the engine is idle; the rest
            # only poll). One recv per step would cap intake at one
            # request per dispatch — under an arrival burst the overload
            # then queues UPSTREAM of the admission plane, where QoS
            # classes, queue deadlines and preemption cannot see it
            # (regression: the --qos-soak bench leg read zero sheds at
            # 2x overload). The bound keeps a flood from starving the
            # decode loop itself.
            event = None
            stop = False
            # Only that first, timed recv is ``parked``: a quarter of a
            # second's wait is not time spent draining the burst.
            parked = not engine.active
            tracer.switch("parked" if parked else "intake")
            for _burst in range(128):
                event = node.recv(timeout=0.25 if parked else 0.0)
                if parked:
                    tracer.switch("intake")
                    parked = False
                if event is None:
                    break
                if event["type"] == "STOP":
                    stop = True
                    break
                if event["type"] == "INPUT":
                    with tracer.phase("intake.handle_input"):
                        handle_input(event)
                elif event["type"] == "MIGRATE" and handle_migrate is not None:
                    _flush(held, emit)
                    handle_migrate(event)
                elif event["type"] == "PROFILE" and capture is not None:
                    capture.handle(event)
            if stop:
                break
            if (
                event is None
                and node.stream_ended
                and engine.active == 0
                and len(backlog) == 0
            ):
                if not keep_alive:
                    break
                # Stream closed but handoffs may still arrive: don't
                # spin (recv returns immediately once the queue is
                # closed).
                tracer.switch("parked")
                time.sleep(0.05)
            if engine.active:
                first = half(engine.dispatch)
                overlapped = engine.in_flight
                t_emit = tracer.switch("emit" if overlapped else "emit_alone")
                # The gap ends on the stamp that left ``window_launch``:
                # the engine's, where it went on to read a first token
                # beside the window, else this switch.
                t_launch = engine.launched_at
                if t_launch is None:
                    t_launch = t_emit
                # First tokens lead: ttft waits for them, and their
                # streams have no token in the window held (it ran
                # before them).
                held[:0] = first
                sent = _flush(held, emit)
                if overlapped:
                    metrics.emit_overlapped += sent
                    # The emit side of max(device, emit) in this period.
                    metrics.emit.observe((tracer.clock() - t_emit) * 1e6)
                    # Nothing is left to send: the next period's chunk
                    # goes to the device now, behind the window, where
                    # the prefill queue holds one (phase ``chunk_ahead``).
                    half(engine.ahead)
                else:
                    # Nothing ran beside the emit (a prefill-only
                    # dispatch): the device waited for it too.
                    t_launch = tracer.switch("housekeeping")
                if last_collect_end is not None:
                    # Host time from the previous window's tokens
                    # reaching the host to the launch of the next device
                    # work: what the device sits idle for in each period
                    # (p50/p99 in the SERVING table).
                    metrics.dispatch_gap.observe(
                        (t_launch - last_collect_end) * 1e6
                    )
                held.extend(half(engine.collect))
                # collect()'s return: its last phase ends and the gap
                # begins on this stamp.
                last_collect_end = tracer.switch("housekeeping")
                if engine.active == 0:
                    # The loop may now park in recv or exit: nothing
                    # stays in hand.
                    tracer.switch("emit_alone")
                    _flush(held, emit)
                    tracer.switch("housekeeping")
                if on_step is not None:
                    on_step()
            else:
                last_collect_end = None  # a gap across idle is queue wait
            tracer.switch("admit")
            backlog.drain()
            now = tracer.switch("housekeeping")
            if capture is not None and capture.active:
                capture.tick(now)
            if now - report_last >= 1.0:
                report(now)
                report_last = now
            elif fleet_tick is not None:
                # Fleet digests can run FASTER than the 1 Hz metrics
                # report (DORA_FLEET_DIGEST_S below 1); report() itself
                # also ticks the publisher, so the slow cadence costs
                # nothing extra.
                fleet_tick(now)
    finally:
        tracer.close()
    _flush(held, emit)


def serve(node, engine, metrics, *, encode, decode_one, eos=None,
          max_new_cap=32, tracer=None, clock=time.monotonic) -> None:
    """Run the serving loop over an already-built engine until the
    input stream ends, then close the node. Factored out of
    :func:`main` (which only adds checkpoint loading) so tests and
    demo dataflows can serve a stub engine through the REAL admission /
    backlog / lifecycle-tracing paths.

    Attaches the observability plane: a ``ServingTracer`` shared with
    the engine (request-lifecycle spans through the flight-recorder
    ring, linked to the carrier message's trace context), the
    ``ServingMetrics`` histograms the engine feeds (fetch latency,
    grant sizes), and the runtime XLA compile listener whose counter
    ships with every metrics report."""
    if tracer is None:
        tracer = telemetry.ServingTracer(clock=clock)
    # The engine records admitted/prefill_chunk/decode_window spans and
    # fetch/grant histograms through these hooks; both are no-ops /
    # plain counters unless DORA_TRACING=1. The loop's phases go to
    # the metrics' histograms always, and into a profiler capture where
    # this process has loaded JAX (the one that holds the chip does;
    # nothing here imports it).
    tracer.histograms = metrics.phases
    tracer.stage_histograms = metrics.stages
    jax = sys.modules.get("jax")
    if jax is not None:
        tracer.annotation = jax.profiler.TraceAnnotation
    engine.tracer = tracer
    engine.serving_metrics = metrics
    telemetry.install_compile_listener()
    # Elastic-recovery env knobs; all off by default.
    ckpt_dir = os.environ.get("DORA_CHECKPOINT_DIR")
    ckpt_every = int(os.environ.get("DORA_CHECKPOINT_EVERY", "8") or 0)
    migrate_dir = os.environ.get("DORA_MIGRATE_DIR")
    # SLO targets: the daemon injects the descriptor's `slo:` block as
    # DORA_SLO_* at spawn. The daemon-side history ring is the
    # authoritative burn-rate source; the node-side check exists so a
    # violation ALSO lands on this process's ENGINE trace track, with
    # the observed value at engine granularity.
    def _slo_env(key: str) -> float | None:
        raw = os.environ.get(key, "")
        try:
            return float(raw) if raw else None
        except ValueError:
            return None

    slo_ttft_ms = _slo_env("DORA_SLO_TTFT_P99_MS")
    slo_tok_s = _slo_env("DORA_SLO_TOKENS_PER_S_MIN")
    slo_queue = _slo_env("DORA_SLO_QUEUE_DEPTH_MAX")
    slo_prev: dict = {"t": None, "tokens": 0, "ttft": []}
    # Traffic shaping (descriptor qos: block -> DORA_QOS_* env).
    qos = QosConfig.from_env()
    #: per-request QoS bookkeeping. req_prompt/req_emitted (token ids)
    #: exist so a preempted stream can resume by re-prefilling
    #: prompt + emitted — only tracked while preemption is on.
    req_class: dict[str, str] = {}
    #: engine key -> LoRA tenant name (absent/None = base model). Kept
    #: for every request while live so preemption requeues and
    #: migrate-out carry the stream's model with it.
    req_adapter: dict[str, str | None] = {}
    req_prompt: dict[str, list[int]] = {}
    req_emitted: dict[str, list[int]] = {}
    admit_seq: dict[str, int] = {}
    admit_counter = [0]
    preempted_keys: set[str] = set()
    #: engine key -> tokens whose cached-prefix path is PINNED while
    #: the preempted victim waits to resume (refcount custody, not slot
    #: custody: the pages stay in the prefix cache, immune to pool-
    #: pressure eviction, so resume re-prefills only the unshared tail)
    pinned_prefix: dict[str, list[int]] = {}
    #: engine key -> wire request_id. The ENGINE key is always unique
    #: (req-N): two in-flight requests carrying the same wire
    #: ``request_id`` must not share a slot key, or their token streams
    #: silently interleave — the wire id is carried separately and only
    #: stamped on the outgoing chunks.
    wire_ids: dict[str, str | None] = {}
    #: engine key -> arrival wall time, pending first token (TTFT)
    t_admitted: dict[str, float] = {}
    req_counter = [0]
    #: engine key -> tokens of the request already sent, the next
    #: message's ``seq``. Recovery replays are at-least-once: after a
    #: crash-restore the engine re-decodes from the checkpoint (taken
    #: with nothing held, so on a message's edge), re-emitting tokens
    #: the wire already saw — the same text under the SAME
    #: (request_id, seq) pair, and ``n_tokens`` says how far a message
    #: reaches, so consumers dedup instead of double-printing.
    seqs: dict[str, int] = {}
    #: wire request_ids already admitted (checkpoint mode only): a
    #: daemon replay of an un-acked input must not re-admit a stream
    #: the restored engine is already running.
    seen_rids: dict[str, None] = {}

    def _forget(key: str) -> None:
        req_class.pop(key, None)
        req_adapter.pop(key, None)
        req_prompt.pop(key, None)
        req_emitted.pop(key, None)
        admit_seq.pop(key, None)
        stall_tags.pop(key, None)
        preempted_keys.discard(key)
        pinned = pinned_prefix.pop(key, None)
        if pinned is not None:
            # A parked victim that never resumed (shed, error, drain)
            # must release its eviction pin.
            engine.prefix_unpin(pinned)

    def emit_text(
        key: str, text: str, done: bool, finish: str | None = None,
        extra: dict | None = None, n_tokens: int = 0,
    ) -> None:
        meta: dict = {"done": bool(done)}
        if done:
            # Done-by-EOS ("stop") vs done-by-cap ("length"): the server
            # reports this as the OpenAI finish_reason. Capacity signals
            # are retriable: "rejected" (could NEVER fit: pages needed
            # vs pool size ride in the payload) and "overloaded" (could
            # fit, shed under load; retry_after_ms rides along).
            meta["finish"] = finish or "stop"
        if extra:
            meta.update(extra)
        stalled = stall_tags.pop(key, None)
        if stalled is not None and "stall_reason" not in meta:
            meta["stall_reason"] = stalled
        # seq: tokens of this request sent before this message (0, 1,
        # 2, ... for a stream that sends one token a message).
        seq = seqs.get(key, 0)
        meta["seq"] = seq
        meta["n_tokens"] = n_tokens
        if done:
            seqs.pop(key, None)
        else:
            seqs[key] = seq + n_tokens
        rid = wire_ids.get(key)
        if rid is not None:
            meta["request_id"] = rid
        t0 = t_admitted.pop(key, None)
        if t0 is not None:
            # The loop sends a first token right after the dispatch
            # that read it, before the window it joins is collected:
            # what is observed here is what the client saw. The
            # request's stages end on the same stamp, and the send
            # itself is the front's to time: its stamp rides this, the
            # stream's first message, alone.
            now = clock()
            metrics.ttft.observe(max(0.0, now - t0) * 1e6)
            tracer.request_sent(key, now)
            meta[telemetry.STAMP_EMIT] = time.time_ns()
        node.send_output("response", pa.array([text]), meta)
        if done:
            wire_ids.pop(key, None)
            _forget(key)
            tracer.finish(key, finish or "stop")

    def emit(key: str, tokens: list[int], done: bool) -> None:
        """One ``response`` message: every token a flush holds for the
        stream, ``done`` and the finish reason those of the last."""
        finish = None
        if done:
            finish = (
                "stop" if (eos is not None and tokens[-1] == eos)
                else "length"
            )
        metrics.decode_tokens += len(tokens)
        metrics.emit_messages += 1
        if qos.preempt_on and key in req_emitted:
            req_emitted[key].extend(tokens)
        emit_text(
            key, "".join(decode_one(t) for t in tokens), done, finish,
            n_tokens=len(tokens),
        )

    #: tokens the engine has handed over and the wire has not seen: the
    #: loop sends them while the next window runs (_run_loop). Readers
    #: of per-request state the loop cannot see flush() first.
    held: list[tuple[str, int, bool]] = []

    def flush() -> None:
        _flush(held, emit)

    #: keys whose backlog wait was attributed to adapter residency —
    #: the next wire chunk (first token or shed) carries the tag so the
    #: client can tell "tenant blocked" from plain overload.
    stall_tags: dict[str, str] = {}

    def on_stall(key: str, reason: str) -> None:
        if reason == "adapter_residency":
            metrics.adapter_stalls += 1
            tracer.instant("s_page_wait", key, "adapter_residency")

    def on_admit(key: str, waited_s: float) -> None:
        metrics.backlog_wait.observe(waited_s * 1e6)
        tracer.request_admitted(key, waited_s)
        reason = backlog.stall_reason(key)
        if reason == "adapter_residency":
            stall_tags[key] = reason
        # The queued span closes at admission; the exporter derives its
        # start from the duration, so it covers the whole backlog wait.
        tracer.span("s_queued", key, dur_ns=int(waited_s * 1e9))

    def start(key: str, ids: list[int], max_new: int,
              adapter: str | None = None) -> None:
        admit_counter[0] += 1
        admit_seq[key] = admit_counter[0]
        if key in preempted_keys:
            # A preempted stream re-admitting: its prefill recomputes
            # prompt + emitted, so everything it decodes from here is
            # token-identical to the unpreempted run.
            preempted_keys.discard(key)
            metrics.resumed += 1
            tracer.span("s_resume", key, f"recompute={len(ids)}")
        # submit queues the prefill; the first token is emitted by a
        # later dispatch(), when the final chunk lands.
        engine.submit(key, ids, max_new, adapter=adapter)
        pinned = pinned_prefix.pop(key, None)
        if pinned is not None:
            # Unpin AFTER submit: the resume lookup refs the shared
            # pages into the new grant first, so dropping the eviction
            # pin can no longer lose them.
            engine.prefix_unpin(pinned)

    def on_shed(key: str, reason: str, waited_s: float) -> None:
        # Overload -> fast retriable signal, never unbounded backlog:
        # the stream closes with finish "overloaded" and a retry hint
        # (clients with backoff re-enter the front door fresh).
        metrics.shed += 1
        t_admitted.pop(key, None)  # a shed stream has no first token
        tracer.instant("s_shed", key, f"{reason} waited={waited_s:.3f}s")
        retry_ms = int(max(100.0, (qos.shed_wait_s or 1.0) * 1000.0))
        extra = {"retry_after_ms": retry_ms}
        if backlog.stall_reason(key) == "adapter_residency":
            extra["stall_reason"] = "adapter_residency"
        emit_text(key, "", True, finish="overloaded", extra=extra)

    def try_preempt(cls: str) -> bool:
        """A ``cls`` head is blocked on capacity: evict ONE victim of a
        strictly lower class (lowest class first, then youngest — the
        cheapest recompute), park it for resume, and report whether
        anything was freed. The queue re-scores and retries after True,
        so multi-victim evictions happen one grant at a time."""
        if not qos.preempt_on:
            return False
        flush()  # req_emitted must match the victim's slot.emitted
        rank = QOS_CLASSES.index(cls)
        victim, vkey = None, (-1, -1)
        for s in engine.slots:
            if s is None:
                continue
            k = s.request_id
            r = QOS_CLASSES.index(req_class.get(k, qos.default_class))
            if r <= rank:
                continue  # only strictly lower classes are victims
            if k not in req_prompt:
                # No resume bookkeeping (e.g. a checkpoint-restored
                # stream): evicting it could not be token-identical.
                continue
            cand = (r, admit_seq.get(k, 0))
            if cand > vkey:
                victim, vkey = k, cand
        if victim is None:
            return False
        meta = engine.preempt(victim)
        if meta is None:
            return False
        remaining = meta["max_new"] - meta["emitted"]
        if remaining <= 0:
            # Raced with completion; the slot is free either way.
            emit_text(victim, "", True, finish="length")
            return True
        preempted_keys.add(victim)
        resume_ids = (
            list(req_prompt.get(victim, []))
            + list(req_emitted.get(victim, []))
        )
        if engine.prefix_pin(resume_ids):
            # The victim's cached prefix pages survive the park on
            # refcount custody: resume re-prefills only the unshared
            # tail instead of re-paying the whole prefill.
            pinned_prefix[victim] = resume_ids
        backlog.requeue(victim, resume_ids, remaining,
                       req_class.get(victim),
                       adapter=req_adapter.get(victim))
        return True

    #: requests that arrived while the engine couldn't admit them
    backlog = AdmissionQueue(
        engine, start, on_admit=on_admit, clock=clock,
        qos=qos, on_shed=on_shed,
        preempt=try_preempt if qos.preempt_on else None,
        on_stall=on_stall, tracer=tracer,
    )

    def handle_input(event) -> None:
        from dora_tpu.telemetry import OTEL_CTX_KEY

        t_in_ns = time.time_ns()
        meta = event.get("metadata") or {}
        rid = meta.get("request_id")
        if ckpt_dir and rid is not None:
            # Checkpoint mode: the daemon replays un-acked inputs after
            # a respawn; a rid the restored engine already owns must not
            # be admitted twice.
            if rid in seen_rids:
                tracer.instant("s_reject", f"req:{rid}", "replay-dup")
                return
            seen_rids[rid] = None
            while len(seen_rids) > 4096:
                seen_rids.pop(next(iter(seen_rids)))
        value = event["value"]
        text = (
            value.to_pylist()[0]
            if isinstance(value, pa.Array)
            else bytes(value or b"").decode(errors="replace")
        )
        req_counter[0] += 1
        key = f"req-{req_counter[0]}"
        wire_ids[key] = rid
        metrics.requests += 1
        tracer.request_arrived(meta, t_in_ns)
        # Engine spans join the trace of the message that carried the
        # request in — one trace id covers send → route → deliver →
        # queued → admitted → … → finish.
        tracer.begin(key, str(meta.get(OTEL_CTX_KEY, "") or ""))
        ids = encode(text) or [0]
        max_new = min(
            int(meta.get("max_new_tokens", max_new_cap)),
            max_new_cap,
        )
        cls = meta.get("qos_class") or meta.get("priority")
        if cls not in QOS_CLASSES:
            cls = qos.default_class
        try:
            dl = float(meta.get("deadline_ms", "") or 0) / 1000.0
        except (TypeError, ValueError):
            dl = 0.0
        deadline_s = dl if dl > 0 else None
        req_class[key] = cls
        # Per-request model routing (the OpenAI ``model`` field, wired
        # through like qos_class): a non-base name is a LoRA tenant
        # served out of THIS engine's adapter pool — same slots, same
        # pages, one window executable.
        model = str(meta.get("model") or "")
        adapter = model if model not in BASE_MODEL_NAMES else None
        lora_pool = engine.lora
        req_adapter[key] = adapter
        if max_new <= 0:
            # max_tokens <= 0 asks for nothing: close the stream
            # empty instead of fabricating a token.
            metrics.rejected += 1
            tracer.instant("s_reject", key, "max_new<=0")
            emit_text(key, "", True, finish="length")
        elif adapter is not None and (
            lora_pool is None or not lora_pool.has(adapter)
        ):
            # Unknown tenant: NEVER servable here (no catalog entry /
            # no adapter pool at all) — a structured non-retriable
            # reject, distinct from capacity signals.
            metrics.rejected += 1
            tracer.instant("s_reject", key, f"unknown model {adapter!r}")
            emit_text(
                key, "", True, finish="rejected",
                extra={"reject_reason": "unknown_model", "model": adapter},
            )
        elif not engine.fits(len(ids), max_new, adapter):
            # NEVER admissible: close the stream empty with a
            # structured retriable "rejected" (distinct from the shed
            # path's "overloaded" — retrying the same body cannot
            # help, the payload says why: its page grant exceeds the
            # whole pool / block table).
            metrics.rejected += 1
            extra = {
                "reject_reason": "oversized",
                "pages_needed": engine.pages_needed(len(ids), max_new),
                "pool_pages": engine.allocator.num_pages - 1,
                "max_seq": engine.max_seq,
            }
            tracer.instant("s_reject", key, f"oversized len={len(ids)}")
            emit_text(key, "", True, finish="rejected", extra=extra)
        else:
            t_admitted[key] = clock()
            tracer.request_pushed(key, t_admitted[key])
            if qos.preempt_on:
                req_prompt[key] = list(ids)
                req_emitted[key] = []
            if not backlog.push(key, ids, max_new, cls, deadline_s,
                                adapter=adapter):
                return  # shed at the door (class depth bound)
            # push drains: admits now when the engine can, else parks
            # until capacity frees
            if backlog.queued(key):
                # Parked: no slot, or the page pool couldn't cover the
                # grant — the backlog wait (or a preemption) begins
                # here.
                tracer.instant(
                    "s_page_wait", key,
                    f"qos={cls} backlog={len(backlog)} "
                    f"free_pages={engine.free_pages}",
                )

    def check_slo(now: float) -> None:
        """Evaluate the DORA_SLO_* targets over the deltas since the
        previous report tick. TTFT p99 comes from this tick's histogram
        delta; tok/s is only judged while the engine is actually serving
        (an idle server decodes 0 tok/s without violating anything)."""
        if slo_ttft_ms is None and slo_tok_s is None and slo_queue is None:
            return
        prev_t, slo_prev["t"] = slo_prev["t"], now
        toks = metrics.decode_tokens
        counts = list(metrics.ttft.counts)
        if prev_t is None or now <= prev_t:
            slo_prev["tokens"] = toks
            slo_prev["ttft"] = counts
            return
        dt = now - prev_t
        if slo_ttft_ms is not None:
            delta = [c - p for c, p in zip(counts, slo_prev["ttft"])]
            if any(d > 0 for d in delta):
                p99 = percentile_from_counts(delta, 99)
                if p99 is not None and p99 > slo_ttft_ms * 1000.0:
                    tracer.instant(
                        "slo_violation", "(engine)",
                        f"ttft_p99_ms observed={p99 / 1000.0:.1f} "
                        f"target={slo_ttft_ms:g}",
                    )
        if slo_tok_s is not None:
            rate = (toks - slo_prev["tokens"]) / dt
            if (engine.active or toks > slo_prev["tokens"]) \
                    and rate < slo_tok_s:
                tracer.instant(
                    "slo_violation", "(engine)",
                    f"tokens_per_s observed={rate:.1f} "
                    f"target={slo_tok_s:g}",
                )
        if slo_queue is not None and len(backlog) > slo_queue:
            tracer.instant(
                "slo_violation", "(engine)",
                f"queue_depth observed={len(backlog)} "
                f"target={slo_queue:g}",
            )
        slo_prev["tokens"] = toks
        slo_prev["ttft"] = counts

    # ------------------------------------------------------------------
    # SLO-driven K autotuner (DORA_AUTOTUNE_K=1): a slow control loop
    # re-selecting the fused-window K from live signals. TTFT burn
    # (interval p99 over the DORA_SLO_TTFT_P99_MS target) or shedding
    # steps K DOWN one ladder rung and pauses speculation — shorter
    # windows mean finer admission boundaries and faster first tokens;
    # a saturated window (tokens/dispatch >= 3/4 of K) with no burn
    # steps K UP and resumes speculation — decode-heavy mixes drift
    # toward K=16. Hysteresis: a signal must hold
    # for DORA_AUTOTUNE_HYSTERESIS consecutive intervals, and after a
    # retune the loop cools down as many intervals (change-rate cap:
    # at most one rung per hysteresis window). The loop never acts
    # before its burn window has a full complement of samples
    # (metrics_history.burn_window_complete — a freshly started
    # dataflow must not retune off a 3-sample "burn").
    # ------------------------------------------------------------------
    at_on = (
        os.environ.get("DORA_AUTOTUNE_K", "") == "1"
        and engine._window_factory is not None
    )
    at_interval = float(os.environ.get("DORA_AUTOTUNE_INTERVAL_S", "5") or 5)
    at_hyst = max(1, int(os.environ.get("DORA_AUTOTUNE_HYSTERESIS", "2") or 2))
    at_burn_win = float(
        os.environ.get("DORA_AUTOTUNE_BURN_WINDOW_S", "60") or 60
    )
    _ladder_env = os.environ.get("DORA_AUTOTUNE_LADDER", "4,8,16")
    try:
        at_ladder = sorted(
            {int(x) for x in _ladder_env.split(",") if int(x) >= 1}
            | {engine.window}
        )
    except ValueError:
        at_ladder = sorted({4, 8, 16} | {engine.window})
    at_state = {
        "t": None, "tokens": 0, "dispatches": 0, "ttft": [],
        "samples": 0, "burn": 0, "calm": 0, "cooldown": 0,
        "shed": 0,
        "rung": at_ladder.index(engine.window),
    }

    def autotune(now: float) -> None:
        if not at_on:
            return
        if at_state["t"] is None:
            at_state["t"] = now
            at_state["tokens"] = metrics.decode_tokens
            at_state["dispatches"] = metrics.host_dispatches
            at_state["ttft"] = list(metrics.ttft.counts)
            at_state["shed"] = metrics.shed
            return
        if now - at_state["t"] < at_interval:
            return
        from dora_tpu.metrics_history import burn_window_complete

        d_tok = metrics.decode_tokens - at_state["tokens"]
        d_disp = metrics.host_dispatches - at_state["dispatches"]
        d_shed = metrics.shed - at_state["shed"]
        counts = list(metrics.ttft.counts)
        d_ttft = [c - p for c, p in zip(counts, at_state["ttft"])]
        at_state["t"] = now
        at_state["tokens"] = metrics.decode_tokens
        at_state["dispatches"] = metrics.host_dispatches
        at_state["ttft"] = counts
        at_state["shed"] = metrics.shed
        at_state["samples"] += 1
        burn = d_shed > 0
        if slo_ttft_ms is not None and any(d > 0 for d in d_ttft):
            p99 = percentile_from_counts(d_ttft, 99)
            if p99 is not None and p99 > slo_ttft_ms * 1000.0:
                burn = True
        tpd = (d_tok / d_disp) if d_disp else 0.0
        k_now = at_ladder[at_state["rung"]]
        if burn:
            at_state["burn"] += 1
            at_state["calm"] = 0
        elif d_disp and tpd >= 0.75 * k_now:
            at_state["calm"] += 1
            at_state["burn"] = 0
        else:
            at_state["burn"] = 0
            at_state["calm"] = 0
        if not burn_window_complete(
            at_state["samples"], at_burn_win, at_interval
        ):
            return
        if at_state["cooldown"] > 0:
            at_state["cooldown"] -= 1
            return
        new_rung, spec_on, reason = None, None, ""
        if at_state["burn"] >= at_hyst and at_state["rung"] > 0:
            new_rung, spec_on = at_state["rung"] - 1, False
            reason = "shed" if d_shed > 0 else "ttft_burn"
        elif (
            at_state["calm"] >= at_hyst
            and at_state["rung"] < len(at_ladder) - 1
        ):
            new_rung, spec_on = at_state["rung"] + 1, True
            reason = "decode_heavy"
        if new_rung is None:
            return
        new_k = at_ladder[new_rung]
        if not engine.set_window(new_k, spec_on=spec_on):
            return
        at_state["rung"] = new_rung
        at_state["burn"] = at_state["calm"] = 0
        at_state["cooldown"] = at_hyst
        metrics.retunes += 1
        metrics.autotune_k = new_k
        tracer.instant(
            "k_retune", "(engine)",
            f"K {k_now}->{new_k} spec_k={engine.spec_k} "
            f"reason={reason} tpd={tpd:.2f}",
        )

    # Device utilization plane (dora_tpu.profiling): HBM gauges sampled
    # at report cadence, engine attribution/FLOPs counters copied into
    # the snapshot, and mfu / device_busy_fraction derived from the
    # interval deltas (reset-safe: a restored engine re-counts from
    # zero, so a negative delta is treated as the whole interval).
    monitor = (
        profiling.DeviceMonitor() if profiling.monitor_enabled() else None
    )
    util_prev = {"busy_ns": 0, "flops": 0, "t": clock()}
    # On-demand deep capture (cm.StartProfile/StopProfile): the loop
    # looks at an active one every turn (ProfileCapture).
    def _capture_edge(edge: str) -> None:
        if engine.model_counters is not None:
            metrics.capture_counters[edge] = engine.model_counters()

    capture = ProfileCapture(node, tracer, clock, _capture_edge)

    # Fleet plane: publish this engine's state digest on its own cadence
    # (DORA_FLEET_DIGEST_S; 0 disables), piggybacked on the report path
    # so it never adds a wakeup to the serving loop.
    from dora_tpu import fleet as _fleet

    fleet_pub = _fleet.DigestPublisher(
        node, engine, tracer=tracer, clock=clock,
        hbm=lambda: (
            getattr(metrics, "hbm_used_bytes", 0) or 0,
            getattr(metrics, "hbm_limit_bytes", 0) or 0,
        ),
    )

    def report(now: float) -> None:
        metrics.slots_active = engine.active
        metrics.slots_total = engine.max_slots
        metrics.backlog_depth = len(backlog)
        metrics.prefill_chunks = engine.chunks_run
        metrics.chunks_ahead = engine.chunks_ahead
        metrics.host_dispatches = engine.dispatches
        metrics.host_fetches = engine.fetches
        metrics.compiles = telemetry.compile_count()
        metrics.free_pages = engine.free_pages
        alloc = engine.allocator
        metrics.total_pages = alloc.num_pages - 1
        metrics.used_pages = alloc.in_use
        metrics.peak_used_pages = alloc.peak_in_use
        metrics.largest_contig_free = alloc.largest_contiguous_free()
        pc = engine.prefix_cache
        if pc is not None:
            metrics.prefix_hits = pc.hits
            metrics.prefix_misses = pc.misses
            metrics.prefix_hit_tokens = pc.hit_tokens
            metrics.prefix_cached_pages = pc.size
            metrics.prefix_shared_pages = engine.shared_pages
            metrics.prefix_cow_copies = pc.cow_copies
            metrics.prefix_evictions = pc.evicted_pages
            metrics.prefix_evict_calls = pc.evict_calls
            metrics.prefix_evict_visits = pc.evict_visits
        metrics.kv_dtype = engine.kv_dtype
        metrics.kv_pool_bytes = engine.kv_pool_bytes()
        metrics.kv_quant_err = engine.kv_quant_error()
        lp = engine.lora
        if lp is not None:
            metrics.lora_resident = lp.resident
            metrics.lora_max_resident = lp.max_resident
            metrics.lora_resident_bytes = lp.resident_bytes()
            metrics.lora_loads = lp.loads
            metrics.lora_evictions = lp.evictions
            metrics.adapter_streams = lp.streams_by_adapter()
        counters = engine.model_counters
        if counters is not None:
            # The model's own counters (an expert layer's routing, the
            # latent pool): read here, after collect(), with no window
            # in flight — so the read waits for a chunk that went ahead
            # at most, once a second, and for nothing where none did.
            metrics.model = counters()
        metrics.qos_depth = backlog.depths()
        metrics.autotune_k = engine.window
        if monitor is not None:
            metrics.device_compute_ns = engine.device_compute_ns
            metrics.host_dispatch_ns = engine.host_dispatch_ns
            metrics.device_fetch_ns = engine.device_fetch_ns
            metrics.dispatched_flops = engine.dispatched_flops
            metrics.useful_flops = engine.useful_flops
            mem = monitor.memory()
            metrics.hbm_used_bytes = mem["used"]
            metrics.hbm_limit_bytes = mem["limit"]
            metrics.hbm_peak_bytes = mem["peak"]
            dt = now - util_prev["t"]
            if dt > 0:
                d_busy = metrics.device_compute_ns - util_prev["busy_ns"]
                if d_busy < 0:  # engine restored: counters restarted at 0
                    d_busy = metrics.device_compute_ns
                metrics.device_busy_fraction = min(
                    1.0, max(0.0, d_busy / (dt * 1e9))
                )
                d_flops = metrics.useful_flops - util_prev["flops"]
                if d_flops < 0:
                    d_flops = metrics.useful_flops
                peak = engine.device_peak_flops
                metrics.mfu = (
                    min(1.0, (d_flops / dt) / peak) if peak > 0 else None
                )
            util_prev["busy_ns"] = metrics.device_compute_ns
            util_prev["flops"] = metrics.useful_flops
            util_prev["t"] = now
        check_slo(now)
        autotune(now)
        try:
            node.report_serving(metrics.snapshot())
        except Exception:
            pass  # metrics are best-effort; serving never blocks on them
        fleet_pub.tick(now)

    # ------------------------------------------------------------------
    # elastic recovery: checkpoint/restore, drain-and-migrate, SIGTERM
    # ------------------------------------------------------------------
    import json

    def write_checkpoint(reason: str) -> None:
        """Snapshot everything a respawn needs to resume mid-generation
        token-identically. Written atomically (tmp + rename) so a kill
        mid-write leaves the previous snapshot intact. Only ever called
        at a window boundary, after ``collect()``, and it sends the
        held tokens first: the engine's emitted counters must not count
        tokens the wire hasn't seen (restore must produce duplicates,
        never gaps)."""
        flush()
        t0 = clock()
        state = {
            "engine": engine.checkpoint_state(),
            "backlog": [
                [k, list(ids), mn, cls, ad]
                for k, ids, mn, cls, ad in backlog.pending()
            ],
            "wire_ids": dict(wire_ids),
            "seqs": dict(seqs),
            "ctxs": {k: tracer.context(k) for k in wire_ids},
            "req_counter": req_counter[0],
            "seen_rids": list(seen_rids),
        }
        os.makedirs(ckpt_dir, exist_ok=True)
        tmp = os.path.join(ckpt_dir, "state.json.tmp")
        with open(tmp, "w") as f:
            json.dump(state, f)
        os.replace(tmp, os.path.join(ckpt_dir, "state.json"))
        if os.environ.get("DORA_CHECKPOINT_PAGES") == "1":
            # KV page pools via orbax, for engines whose decode reads
            # the cache. Best-effort: pool persistence failing must not
            # take serving down with it.
            try:
                engine.save_pools(os.path.join(ckpt_dir, "pools"))
            except Exception:
                pass
        metrics.checkpoints += 1
        metrics.last_checkpoint_unix = time.time()
        tracer.span(
            "s_checkpoint", "(engine)",
            f"streams={len(state['engine']['slots'])} {reason}",
            dur_ns=int((clock() - t0) * 1e9),
        )

    def restore_checkpoint() -> None:
        spath = os.path.join(ckpt_dir, "state.json")
        if not os.path.exists(spath):
            return
        t0 = clock()
        with open(spath) as f:
            saved = json.load(f)
        pools = os.path.join(ckpt_dir, "pools")
        if os.environ.get("DORA_CHECKPOINT_PAGES") == "1" and os.path.isdir(
            pools
        ):
            try:
                engine.restore_pools(pools)
            except Exception:
                pass
        req_counter[0] = int(saved.get("req_counter", 0))
        wire_ids.update(saved.get("wire_ids") or {})
        seqs.update(
            {k: int(v) for k, v in (saved.get("seqs") or {}).items()}
        )
        for rid in saved.get("seen_rids") or []:
            seen_rids[rid] = None
        # Same context => same trace id: the resumed stream's spans
        # continue the pre-crash chain on the timeline.
        for k, ctx in (saved.get("ctxs") or {}).items():
            tracer.begin(k, ctx or "")
        restored = engine.restore_state(saved.get("engine") or {"slots": []})
        for entry in saved.get("backlog") or []:
            # Entries are [k, ids, max_new] pre-QoS, [.., class] after,
            # [.., adapter] after multi-tenant LoRA; the wait clock and
            # any deadline restart on restore.
            cls = entry[3] if len(entry) > 3 else None
            ad = entry[4] if len(entry) > 4 else None
            backlog.push(entry[0], list(entry[1]), int(entry[2]), cls,
                         adapter=ad)
        metrics.restored_streams += len(restored)
        tracer.span(
            "s_restore", "(engine)", f"streams={len(restored)}",
            dur_ns=int((clock() - t0) * 1e9),
        )

    migrations = [0]

    def handle_migrate(event) -> None:
        """Drain every live stream (and the parked backlog) into a
        handoff file another engine's ``DORA_MIGRATE_DIR`` poll admits.
        Runs at a window boundary, so clients see at most one window of
        added latency."""
        handoff_dir = (event.get("metadata") or {}).get("handoff_dir", "")
        if not handoff_dir:
            return
        t0 = clock()
        state = engine.drain_streams()
        parked = backlog.take_all()
        keys = [m["request_id"] for m in state["slots"]]
        keys += [entry[0] for entry in parked]
        payload = {
            "engine": state,
            "backlog": [
                [k, list(ids), mn, cls, ad]
                for k, ids, mn, cls, ad in parked
            ],
            "wire_ids": {k: wire_ids.get(k) for k in keys},
            "seqs": {k: seqs.get(k, 0) for k in keys},
            "ctxs": {k: tracer.context(k) for k in keys},
        }
        migrations[0] += 1
        fname = f"streams-{os.getpid()}-{migrations[0]}.json"
        os.makedirs(handoff_dir, exist_ok=True)
        tmp = os.path.join(handoff_dir, fname + ".tmp")
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, os.path.join(handoff_dir, fname))
        dur = int((clock() - t0) * 1e9)
        for k in keys:
            # Span BEFORE release: it must carry the stream's trace id
            # so the migrate-out leg links to the same chain the target
            # continues. No s_finish here — the stream isn't done, it
            # moved.
            tracer.span("s_migrate_out", k, f"dir={handoff_dir}", dur_ns=dur)
            tracer.release(k)
            wire_ids.pop(k, None)
            seqs.pop(k, None)
            t_admitted.pop(k, None)
            _forget(k)
        metrics.migrated_out += len(keys)

    def _admit_handoff(payload: dict, src: str) -> None:
        t0 = clock()
        mapping: dict[str, str] = {}

        def fresh(old: str) -> str:
            # Local keys are req-N; a migrated-in req-N from another
            # engine could collide, so every incoming stream gets a
            # fresh local key. The wire request_id and seq counter
            # travel untouched — dedup and SSE routing don't notice.
            req_counter[0] += 1
            nk = f"req-{req_counter[0]}"
            mapping[old] = nk
            return nk

        state = payload.get("engine") or {"slots": []}
        for m in state["slots"]:
            m["request_id"] = fresh(m["request_id"])
        parked = [
            (
                fresh(entry[0]), list(entry[1]), int(entry[2]),
                entry[3] if len(entry) > 3 else None,
                entry[4] if len(entry) > 4 else None,
            )
            for entry in payload.get("backlog") or []
        ]
        src_wire = payload.get("wire_ids") or {}
        src_seqs = payload.get("seqs") or {}
        src_ctxs = payload.get("ctxs") or {}
        for old, nk in mapping.items():
            wire_ids[nk] = src_wire.get(old)
            seqs[nk] = int(src_seqs.get(old, 0))
            # begin() with the source's serialized context keeps the
            # trace id — ONE contiguous trace spans both engines.
            tracer.begin(nk, src_ctxs.get(old) or "")
        try:
            engine.admit_streams(state)
        except RuntimeError:
            # Capacity raced away between the peek-time fits check and
            # the claim (local admissions landed first). restore_state
            # is not transactional — roll back whatever it admitted,
            # then close EVERY handoff stream with a retriable "error"
            # finish. The pre-fix failure mode dropped the streams with
            # no signal to the client at all (round-7 known issue).
            fresh_keys = set(mapping.values())
            for b, s in enumerate(engine.slots):
                if s is not None and s.request_id in fresh_keys:
                    if b in engine._prefillq:
                        engine._prefillq.remove(b)
                    engine._free_slot(b)
            for nk in mapping.values():
                metrics.rejected += 1
                tracer.instant("s_reject", nk, f"migrate-in overflow {src}")
                emit_text(nk, "", True, finish="error")
            return
        for nk, ids, mn, cls, ad in parked:
            backlog.push(nk, ids, mn, cls, adapter=ad)
        dur = int((clock() - t0) * 1e9)
        for nk in mapping.values():
            tracer.span("s_migrate_in", nk, f"from={src}", dur_ns=dur)
        metrics.migrated_in += len(mapping)

    def _handoff_fits(payload: dict) -> bool:
        """Can the target admit EVERY stream in the handoff right now?
        Decode streams re-take exactly the pages the source granted;
        mid-prefill streams re-submit through the normal admission
        math (chunk padding + speculative headroom included)."""
        metas = (payload.get("engine") or {}).get("slots") or []
        if len(metas) > engine.free_slots:
            return False
        pages = 0
        for m in metas:
            ad = m.get("adapter")
            if ad:
                # Tenant custody rides the stream: the target must be
                # able to serve (load) the stream's adapter or the
                # handoff stays on disk for a peer that can.
                lp = engine.lora
                if lp is None or not lp.has(ad):
                    return False
            if m.get("decode"):
                n = len(m.get("pages") or ())
                if n * engine.page_size > engine.max_seq:
                    return False  # block table too short for the stream
                pages += n
            else:
                plen = len(m.get("prompt") or ())
                mn = int(m.get("max_new", 0))
                if not engine.fits(plen, mn):
                    return False
                pages += engine.pages_needed(plen, mn)
        for entry in payload.get("backlog") or []:
            ad = entry[4] if len(entry) > 4 else None
            if ad:
                lp = engine.lora
                if lp is None or not lp.has(ad):
                    return False
        return pages <= engine.free_pages

    def poll_migrate_in() -> None:
        try:
            names = sorted(os.listdir(migrate_dir))
        except OSError:
            return
        for fname in names:
            if not (fname.startswith("streams-")
                    and fname.endswith(".json")):
                continue
            path = os.path.join(migrate_dir, fname)
            # Peek BEFORE claiming: an undersized target leaves the
            # handoff on disk — for a bigger peer polling the same dir,
            # or for a later poll once its own streams drain — instead
            # of claiming streams it cannot admit. Handoff files are
            # written once (tmp + rename), so the peeked content is the
            # claimed content.
            try:
                with open(path) as f:
                    payload = json.load(f)
            except (OSError, ValueError):
                continue
            if not _handoff_fits(payload):
                tracer.instant(
                    "s_migrate_defer", fname,
                    f"free_slots={engine.free_slots} "
                    f"free_pages={engine.free_pages}",
                )
                continue
            claimed = path + ".claimed"
            try:
                os.rename(path, claimed)  # atomic claim
            except OSError:
                continue
            _admit_handoff(payload, fname)
            try:
                os.remove(claimed)
            except OSError:
                pass

    stop_now = [False]
    step_count = [0]
    engine_failed = [False]

    def on_tick() -> bool:
        if migrate_dir:
            poll_migrate_in()
        if stop_now[0]:
            if ckpt_dir:
                try:
                    write_checkpoint("sigterm")
                except Exception:
                    pass
            return True
        return False

    def on_step() -> None:
        step_count[0] += 1
        if ckpt_every > 0 and step_count[0] % ckpt_every == 0:
            write_checkpoint("cadence")

    def on_engine_error() -> None:
        # A wedged engine must not leave SSE streams dangling: every
        # in-flight request (active or parked) closes with a retriable
        # "error" finish before the exception propagates and the
        # restart policy respawns the node.
        engine_failed[0] = True
        for key in list(wire_ids):
            t_admitted.pop(key, None)  # an error is no first token
            try:
                emit_text(key, "", True, finish="error")
            except Exception:
                pass

    recovery_on = bool(ckpt_dir or migrate_dir)
    if ckpt_dir:
        import signal

        def _term(signum, frame):
            # Graceful drain: the loop checkpoints and exits cleanly on
            # the next tick instead of dying mid-window.
            stop_now[0] = True

        try:
            signal.signal(signal.SIGTERM, _term)
        except (ValueError, OSError):
            pass  # not the main thread (test harness)
        restore_checkpoint()

    clean = False
    try:
        _run_loop(
            node, engine, backlog, metrics, handle_input, emit, report,
            clock=clock,
            on_tick=on_tick if recovery_on else None,
            on_step=on_step if ckpt_dir else None,
            handle_migrate=handle_migrate,
            capture=capture,
            on_engine_error=on_engine_error,
            keep_alive=bool(migrate_dir),
            fleet_tick=fleet_pub.tick if fleet_pub.enabled else None,
            held=held, tracer=tracer,
        )
        clean = True
    finally:
        # Only a CLEAN exit snapshots: after a crash (engine wedge, lost
        # daemon, anything that raised out of the loop) the last cadence
        # checkpoint is the trustworthy state — overwriting it with a
        # post-crash "exit" snapshot would resume from poisoned state.
        if ckpt_dir and clean and not engine_failed[0]:
            try:
                write_checkpoint("exit")
            except Exception:
                pass
        report(clock())
        node.close()


def _stub_main() -> None:
    """Serve the weight-free stub engine (``DORA_STUB_ENGINE=1``): the
    real admission / backlog / lifecycle-tracing / reporting paths over
    ``models.batch_engine.make_stub_paged_engine`` — what the
    observability e2e test and the serving-trace demo dataflow run when
    no checkpoint is available. Tokens are the stub's deterministic
    affine chain rendered as ``t<id>`` words, not language —
    ``DORA_STUB_CYCLE=N`` swaps in the period-N repeating rule (the
    speculative-decoding best case; pair with ``DORA_SPEC_K``)."""
    from dora_tpu.metrics import ServingMetrics
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    cycle_env = os.environ.get("DORA_STUB_CYCLE", "")
    window = int(os.environ.get("DORA_MULTISTEP_K", "4"))
    delay = float(os.environ.get("DORA_STEP_DELAY_S", "0") or 0)
    engine = make_stub_paged_engine(
        max_slots=int(os.environ.get("DORA_BATCH_SLOTS", "4")),
        window=window,
        spec_k=int(os.environ.get("DORA_SPEC_K", "0") or 0),
        spec_ngram=int(os.environ.get("DORA_SPEC_NGRAM", "2") or 2),
        cycle=int(cycle_env) if cycle_env else None,
        prefix_cache=os.environ.get("DORA_PREFIX_CACHE", "1") != "0",
        prefix_cache_pages=int(
            os.environ.get("DORA_PREFIX_CACHE_PAGES", "0") or 0
        ),
        # Multi-tenant LoRA front door over the stub (any model name
        # resolves to a deterministic shift adapter — see
        # make_stub_paged_engine): the --lora-ab bench and the routing
        # tests exercise admission/eviction/gauges engine-free.
        lora_max_resident=int(
            os.environ.get("DORA_LORA_MAX_RESIDENT", "0") or 0
        ),
        # Chaos-harness hook: the stub decodes in microseconds, far too
        # fast to land a mid-generation kill deterministically. A
        # window that takes DORA_STEP_DELAY_S of modelled device time
        # stretches generation into a predictable strike window
        # without touching token content.
        tick_sleep_s=delay / window,
    )
    serve(
        Node(), engine, ServingMetrics(),
        encode=lambda text: [ord(ch) % 97 for ch in text] or [1],
        decode_one=lambda t: f" t{t}",
        max_new_cap=int(os.environ.get("DORA_MAX_NEW_TOKENS", "8")),
    )


def main() -> None:
    from dora_tpu import backend
    from dora_tpu.metrics import ServingMetrics
    from dora_tpu.models.hf.loader import read_config

    # The chip, or an explicit JAX_PLATFORMS=cpu — never a silent
    # fallback; and the compile cache placed before the first jit.
    backend.init_compile_cache()
    backend.require_accelerator("llm_server")
    telemetry.install_compile_listener()
    path = os.environ.get("DORA_HF_CHECKPOINT")
    if not path:
        if os.environ.get("DORA_STUB_ENGINE", "") not in ("", "0"):
            return _stub_main()
        raise RuntimeError(
            "llm_server needs DORA_HF_CHECKPOINT (a Qwen2-family, "
            "kimi_k2/deepseek_v3, falcon_h1 or ouro safetensors directory; or "
            "DORA_STUB_ENGINE=1 for the weight-free stub engine)"
        )
    max_seq = int(os.environ.get("DORA_MAX_SEQ", "2048"))
    max_new_cap = int(os.environ.get("DORA_MAX_NEW_TOKENS", "32"))

    module = model_module(read_config(path).get("model_type"))
    cfg, params = module.load(path, max_seq=max_seq)
    if not os.environ.get("DORA_INT8_DECODE") and not os.environ.get(
        "DORA_INT4_DECODE"
    ):
        os.environ["DORA_INT8_DECODE"] = "1"  # engine needs the fused layout
    params = module.quantize_decode(params, cfg)

    from dora_tpu.nodehub.ops import _hf_tokenizer

    tok = _hf_tokenizer(path)
    eos = None
    if tok is not None:
        for name in ("<|im_end|>", "<|endoftext|>", "</s>", "<|eot_id|>"):
            if name in tok.added:
                eos = tok.added[name]
                break

    def encode(text: str) -> list[int]:
        if tok is not None:
            return tok.encode(text)
        from dora_tpu.models import tokenizer

        return [t % cfg.vocab for t in tokenizer.encode(text)]

    def decode_one(token: int) -> str:
        if tok is not None:
            return tok.decode([token])
        from dora_tpu.models import tokenizer

        return tokenizer.decode([token])

    engine = make_engine(params, cfg, eos=eos, module=module)
    metrics = ServingMetrics()
    backend.report("engine_built", {
        "engine": metrics.engine, "layers": cfg.layers, "dim": cfg.dim,
        "memory": backend.memory_report(),
    })
    try:
        serve(
            Node(), engine, metrics,
            encode=encode, decode_one=decode_one, eos=eos,
            max_new_cap=max_new_cap,
        )
    finally:
        backend.report("compiles", {
            "count": telemetry.compile_count(),
            "seconds": round(telemetry.compile_seconds(), 3),
        })
        # Whether the loop's pipelining engaged over this process's
        # life: the share of tokens sent beside a running window, and
        # the host time the device was left waiting each period.
        backend.report("serving", {
            "decode_tokens": metrics.decode_tokens,
            "emit_messages": metrics.emit_messages,
            "emit_overlapped": metrics.emit_overlapped,
            "dispatch_gap_us": metrics.dispatch_gap.snapshot(),
            "emit_us": metrics.emit.snapshot(),
            # what the gap and the emit are made of: one histogram a
            # loop phase (telemetry.LOOP_PHASES), octave counts and all
            **metrics.phase_snapshots(),
            # a request's time to its first message sent, and what it
            # is made of: the wait for a slot and one histogram a stage
            # (telemetry.REQUEST_STAGES) that this process records
            "ttft_us": metrics.ttft.snapshot(),
            "backlog_wait_us": metrics.backlog_wait.snapshot(),
            **metrics.stage_snapshots(),
            # how often a first token's read left the gap (deferred,
            # beside the window) and how often it still held the launch
            "first_token_reads": metrics.first_token_reads(),
            # the share of prefill chunks that went to the device
            # behind a running window, ahead of their period
            "prefill_chunks": metrics.prefill_chunks,
            "chunks_ahead": metrics.chunks_ahead,
            "chunks_ahead_share": metrics.chunks_ahead_share(),
            # what eviction cost admission: nodes looked at a page freed
            "prefix_evictions": metrics.prefix_evictions,
            "prefix_evict_calls": metrics.prefix_evict_calls,
            "prefix_evict_visits": metrics.prefix_evict_visits,
            **metrics.model,
        })


if __name__ == "__main__":
    main()
