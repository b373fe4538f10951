"""Fusion compiler: all jax operators of one runtime node become ONE
jit-compiled XLA computation per tick.

Graph lowering (SURVEY.md §7 step 5c): intra-node edges between jax
operators become SSA values inside the traced function — they never
materialize to Arrow, never cross a process boundary, and stay in device
HBM. Only inputs arriving from outside the node and outputs consumed
outside the node touch the Arrow data plane. Operator state is threaded
through the jit with donation, so it lives in HBM across ticks.

Tick semantics (the async-graph ↔ synchronous-XLA impedance match): timer
inputs are the tick triggers when present (the reference's vlm example
pattern — 20 ms camera timer, 100 ms model timer); otherwise every
external data input triggers. Non-trigger inputs are sampled latest-wins,
which is the reference's ``queue_size: 1`` idiom.
"""

from __future__ import annotations

from dora_tpu import backend
from dora_tpu.analysis.lockcheck import tracked_lock

import logging
import time
from dataclasses import dataclass, field
from typing import Any

from dora_tpu.core.config import TimerMapping, UserMapping
from dora_tpu.core.descriptor import (
    Descriptor,
    JaxSource,
    OperatorDefinition,
    ResolvedNode,
    RuntimeNode,
)
from dora_tpu.tpu.api import JaxOperator, load_jax_operator

logger = logging.getLogger(__name__)


@dataclass
class FusedGraph:
    """The static structure of one node's fused jax subgraph."""

    node_id: str
    operators: dict[str, JaxOperator]  # op id -> operator
    definitions: dict[str, OperatorDefinition]
    topo: list[str]  # op ids in dataflow order
    #: (op, input) -> (src op, src output): intra-node SSA edges
    intra_edges: dict[tuple[str, str], tuple[str, str]]
    #: event ids ("<op>/<input>") carrying data from outside the node
    external_inputs: set[str]
    #: event ids fed by daemon timers (trigger, no payload)
    timer_inputs: set[str]
    #: output ids ("<op>/<output>") consumed outside the node
    external_outputs: set[str]

    @property
    def trigger_inputs(self) -> set[str]:
        return self.timer_inputs or self.external_inputs

    # -- construction -------------------------------------------------------

    @classmethod
    def build(
        cls,
        node: ResolvedNode,
        descriptor: Descriptor | None = None,
        working_dir=None,
    ) -> "FusedGraph":
        assert isinstance(node.kind, RuntimeNode)
        jax_defs = {
            str(op.id): op
            for op in node.kind.operators
            if isinstance(op.source, JaxSource)
        }
        operators = {
            op_id: load_jax_operator(op.source.source, working_dir)
            for op_id, op in jax_defs.items()
        }

        intra: dict[tuple[str, str], tuple[str, str]] = {}
        external_inputs: set[str] = set()
        timer_inputs: set[str] = set()
        for op_id, op in jax_defs.items():
            for input_id, inp in op.inputs.items():
                if isinstance(inp.mapping, TimerMapping):
                    timer_inputs.add(f"{op_id}/{input_id}")
                    continue
                mapping: UserMapping = inp.mapping
                if str(mapping.source) == str(node.id):
                    # Sibling edge "<self>/<src_op>/<src_out>".
                    src_op, _, src_out = str(mapping.output).partition("/")
                    if src_op in jax_defs:
                        intra[(op_id, str(input_id))] = (src_op, src_out)
                        continue
                external_inputs.add(f"{op_id}/{input_id}")

        topo = _topo_sort(list(jax_defs), intra)

        # Outputs with consumers outside this fused subgraph (other nodes, or
        # python operators of the same node). Without a full descriptor we
        # conservatively export everything.
        external_outputs: set[str] = set()
        if descriptor is not None:
            for consumer in descriptor.nodes:
                for input_id, inp in consumer.inputs.items():
                    if isinstance(inp.mapping, TimerMapping):
                        continue
                    m: UserMapping = inp.mapping
                    if str(m.source) != str(node.id):
                        continue
                    out = str(m.output)  # "<op>/<output>"
                    src_op = out.partition("/")[0]
                    if src_op not in jax_defs:
                        continue
                    consumes_internally = (
                        str(consumer.id) == str(node.id)
                        and (str(input_id).partition("/")[0]) in jax_defs
                        and (
                            str(input_id).partition("/")[0],
                            str(input_id).partition("/")[2],
                        )
                        in intra
                    )
                    if not consumes_internally:
                        external_outputs.add(out)
        else:
            for op_id, op in jax_defs.items():
                external_outputs |= {f"{op_id}/{o}" for o in op.outputs}

        return cls(
            node_id=str(node.id),
            operators=operators,
            definitions=jax_defs,
            topo=topo,
            intra_edges=intra,
            external_inputs=external_inputs,
            timer_inputs=timer_inputs,
            external_outputs=external_outputs,
        )

    # -- the traced function ------------------------------------------------

    def step_fn(self, states: dict, ext_inputs: dict) -> tuple[dict, dict]:
        """The pure fused step: runs every operator in topo order with
        sibling edges as local SSA values. jit-compiled by the executor;
        unused outputs are dead-code-eliminated by XLA."""
        produced: dict[str, dict[str, Any]] = {}
        new_states: dict[str, Any] = {}
        for op_id in self.topo:
            operator = self.operators[op_id]
            definition = self.definitions[op_id]
            inputs: dict[str, Any] = {}
            for input_id in definition.inputs:
                iid = str(input_id)
                edge = self.intra_edges.get((op_id, iid))
                if edge is not None:
                    inputs[iid] = produced[edge[0]][edge[1]]
                else:
                    event_id = f"{op_id}/{iid}"
                    if event_id in ext_inputs:
                        inputs[iid] = ext_inputs[event_id]
            new_states[op_id], outputs = operator.step(states[op_id], inputs)
            produced[op_id] = outputs
        external = {
            out_id: produced[out_id.partition("/")[0]][out_id.partition("/")[2]]
            for out_id in sorted(self.external_outputs)
            if out_id.partition("/")[2] in produced.get(out_id.partition("/")[0], {})
        }
        return new_states, external


def _topo_sort(op_ids: list[str], intra: dict[tuple[str, str], tuple[str, str]]) -> list[str]:
    deps: dict[str, set[str]] = {op: set() for op in op_ids}
    for (dst, _), (src, _) in intra.items():
        deps[dst].add(src)
    order: list[str] = []
    ready = sorted(op for op, d in deps.items() if not d)
    while ready:
        op = ready.pop(0)
        order.append(op)
        for other, d in deps.items():
            if op in d:
                d.discard(op)
                if not d and other not in order and other not in ready:
                    ready.append(other)
                    ready.sort()
    if len(order) != len(op_ids):
        cyclic = sorted(set(op_ids) - set(order))
        raise ValueError(f"cycle among fused jax operators: {cyclic}")
    return order


def mesh_from_env():
    """Device mesh from ``DORA_MESH`` ("tp=4" / "dp=2,tp=2,sp=2"), or None.

    Multi-chip serving inside one runtime node (SURVEY §2.9 "pjit-sharded
    ops within a node"): the fused step jits over this mesh, operator
    states place per their sharding rules, and XLA inserts the
    collectives over ICI.
    """
    import os

    spec = os.environ.get("DORA_MESH", "").strip()
    if not spec:
        return None
    from dora_tpu.parallel.mesh import make_mesh

    # Unspecified dp absorbs the remaining devices, so "tp=4" just works
    # on any host (make_mesh resolves dp=-1).
    axes = {"dp": None, "tp": 1, "sp": 1}
    for part in spec.split(","):
        name, _, value = part.partition("=")
        name = name.strip()
        if name not in axes:
            raise ValueError(f"DORA_MESH: unknown axis {name!r} in {spec!r}")
        axes[name] = int(value)
    if axes["dp"] is None:
        axes["dp"] = -1
    return make_mesh(**axes)


def _fetch(value):
    """The one device→host transfer point of the pipelined executor —
    kept as a module hook so tests can inject fetch latency."""
    import numpy as np

    return np.asarray(value)


def fetch_every_from_env() -> int:
    """Frames per device→host fetch (DORA_FETCH_EVERY, default 1).

    Every tick pays one device→host round trip, and concurrent fetches
    only amortize it to ~RTT/depth. With N > 1, tick outputs accumulate
    ON DEVICE (a jnp.stack ring) and one fetch moves N frames —
    per-frame fetch cost drops to ~RTT/N plus a few bytes of copy,
    decoupling steady-state FPS from the fetch latency. Outputs arrive
    in bursts of N (up to N-1 frames of added latency): a
    serving-throughput config for
    continuous streams, not for request/response flows — hence opt-in.
    A partial group flushes after DORA_FETCH_LINGER_MS (default 100) so
    sporadic streams never stall."""
    import os

    return max(1, int(os.environ.get("DORA_FETCH_EVERY", "1")))


def pipeline_depth_from_env() -> int:
    """In-flight tick budget (DORA_PIPELINE_DEPTH). Default 4 on
    accelerators: JAX dispatch is asynchronous, so in-flight ticks
    overlap the device→host fetch with on-device compute of the next
    frames. Each fetch costs a host round-trip even for a ready array
    (about 1 ms on the v5e's own host, chip_smoke.py "facts"), and
    *concurrent* fetches from separate threads amortize it — so the
    harvest fetches on a thread pool and the depth sets how many
    round-trips amortize. 0 = synchronous (the
    CPU/test default: interpret-mode ticks are host work and gain
    nothing)."""
    import os

    value = os.environ.get("DORA_PIPELINE_DEPTH")
    if value is not None:
        return max(0, int(value))
    return 4 if backend.on_tpu() else 0


class FusedExecutor:
    """Runtime driver of one fused graph: latest-wins input sampling, tick
    triggering, jit with state donation — over a device mesh when
    ``DORA_MESH`` is set (operator ``sharding`` rules place the state).

    With ``pipeline_depth`` > 0 ticks dispatch asynchronously: the jit
    call returns device futures immediately, the (states, outputs) pair
    is queued, and completed outputs are harvested in tick order — frames
    are pipelined, output order is preserved, and the serving loop never
    sits idle in a device→host fetch while the chip could be working on
    the next frame (BASELINE.md north star)."""

    def __init__(self, graph: FusedGraph, mesh=None, pipeline_depth=None,
                 fetch_every=None):
        import jax

        self.graph = graph
        self.mesh = mesh if mesh is not None else mesh_from_env()
        #: ONE host operator (JaxOperator.host) opts the WHOLE node out of
        #: tracing: its step branches on data (data-dependent output
        #: shapes), so every sibling operator fused into this node also
        #: runs eagerly and never pipelines. To keep jit+pipelining for
        #: the rest of the graph, put host operators in their own node in
        #: the dataflow YAML — fusion is per-node by design.
        self.eager = any(op.host for op in graph.operators.values())
        #: optional zero-arg callback fired (from a fetch worker thread)
        #: whenever a pipelined tick's device→host fetch completes; the
        #: runtime points this at ``node.wake`` so its event loop parks in
        #: ``recv(None)`` instead of polling for completed ticks.
        self.on_fetch_done = None
        self.pipeline_depth = (
            pipeline_depth_from_env() if pipeline_depth is None
            else pipeline_depth
        )
        if self.eager:
            self.pipeline_depth = 0
        self.states = {}
        for op_id, op in graph.operators.items():
            if self.mesh is not None and op.sharding is not None:
                from dora_tpu.parallel.mesh import shard_params

                self.states[op_id] = shard_params(
                    op.init_state, self.mesh, op.sharding
                )
            else:
                self.states[op_id] = jax.device_put(op.init_state)
        #: latest device value per external data input (latest-wins sampling)
        self.latest: dict[str, Any] = {}
        #: in-flight tick emissions as (future, n_ticks) pairs, oldest
        #: first; each future resolves to a LIST of tick-output dicts
        #: (fetch groups). Guarded by _stage_lock (harvest/backpressure
        #: run on the event thread, submission on the linger timer's).
        self._in_flight: list[tuple[Any, int]] = []
        self._fetch_pool = None
        #: device-side output ring: tick outputs staged for the next
        #: grouped fetch (fetch_every > 1 — see fetch_every_from_env)
        self.fetch_every = (
            fetch_every_from_env() if fetch_every is None else fetch_every
        )
        if self.eager:
            self.fetch_every = 1
        self._staged: list[dict] = []
        self._linger_s = (
            float(__import__("os").environ.get("DORA_FETCH_LINGER_MS", "100"))
            / 1000.0
        )
        self._linger_timer = None
        # The linger timer flushes from its own thread; staging and
        # group submission must not race it.
        import threading

        self._stage_lock = tracked_lock("tpu.fuse.stage")
        if self.pipeline_depth > 0:
            from concurrent.futures import ThreadPoolExecutor

            # One worker per in-flight tick: every dispatched tick's
            # device→host fetch starts immediately on its own thread, so
            # the round-trips run concurrently instead of serializing on
            # the event loop (the fetch cost is per-call, not
            # per-byte). depth+1 workers: the
            # backpressure check runs after dispatch, so depth+1 ticks
            # can briefly be in flight and the newest one still needs a
            # free worker.
            self._fetch_pool = ThreadPoolExecutor(
                max_workers=self.pipeline_depth + 1,
                thread_name_prefix=f"dora-fetch-{graph.node_id}",
            )
        self._compiled_once = False
        # Donate state so it is updated in place in HBM; on CPU donation is
        # unimplemented and only produces warnings, so skip it there.
        donate = (0,) if backend.on_tpu() else ()
        step = (
            graph.step_fn if self.eager
            else jax.jit(graph.step_fn, donate_argnums=donate)
        )
        self._jit = step if self.mesh is None else self._meshed(step)
        self._required = graph.external_inputs - graph.timer_inputs

    def _meshed(self, step):
        """Call the (jitted) step with the mesh set: bare PartitionSpecs
        in operator code resolve against it, and code being traced can
        see that XLA will partition the program
        (``backend.partitioned_by_xla``). ``jax.set_mesh`` goes around
        the jit call — it cannot be used inside a traced function."""
        import jax

        def run(states, latest):
            with jax.set_mesh(self.mesh):
                return step(states, latest)

        return run

    def observe(self, event_id: str, value, metadata: dict | None) -> None:
        """Record an input's latest value without ticking. Non-trigger
        inputs only update the sample the next tick will read (latest
        wins); backlog bounding itself is the queue layer's job
        (daemon drop-oldest + the node's bounded event buffer)."""
        from dora_tpu.tpu.bridge import arrow_to_device

        if event_id in self._required and value is not None:
            self.latest[event_id] = arrow_to_device(value, metadata)

    def tick_if_ready(self):
        """Run one tick when every required input has produced."""
        if not all(k in self.latest for k in self._required):
            return None  # warm-up: not every input has produced yet
        return self.tick()

    def on_event(self, event_id: str, value, metadata: dict | None):
        """Feed one arriving event; returns {output_id: (arrow, metadata)}
        when the event triggered a tick, else None."""
        self.observe(event_id, value, metadata)
        if event_id not in self.graph.trigger_inputs:
            return None
        return self.tick_if_ready()

    def tick(self):
        from dora_tpu.tpu.bridge import device_to_arrow

        t0 = time.perf_counter()
        self.states, outputs = self._jit(self.states, dict(self.latest))
        if not self._compiled_once:
            self._first_tick_done(time.perf_counter() - t0)
        return {
            out_id: device_to_arrow(value) for out_id, value in outputs.items()
        }

    def _first_tick_done(self, seconds: float) -> None:
        """Once per node: the first tick compiled (or hit the compile
        cache) and every operator's state sits where its sharding rules
        put it — report both."""
        self._compiled_once = True
        backend.report("first_tick", {
            "node": self.graph.node_id,
            "seconds_incl_compile": round(seconds, 3),
            "memory": backend.memory_report(),
        })

    # -- pipelined dispatch (pipeline_depth > 0) ----------------------------

    def on_event_async(self, event_id: str, value, metadata: dict | None) -> None:
        """Pipelined on_event: dispatch the tick without fetching. The new
        state chains on-device behind the in-flight computation; results
        are picked up by :meth:`harvest`. With ``fetch_every`` > 1 the
        outputs stage in a device-side ring and N ticks share ONE
        device→host fetch."""
        self.observe(event_id, value, metadata)
        if event_id not in self.graph.trigger_inputs:
            return
        if not all(k in self.latest for k in self._required):
            return
        t0 = time.perf_counter()
        self.states, outputs = self._jit(self.states, dict(self.latest))
        if not self._compiled_once:
            self._first_tick_done(time.perf_counter() - t0)
        with self._stage_lock:
            self._staged.append(outputs)
            if len(self._staged) >= self.fetch_every:
                self._submit_group_locked()
            elif self._linger_timer is None:
                # Partial group: guarantee a flush even if no further
                # tick arrives (sporadic streams must not stall N-1
                # frames).
                import threading

                self._linger_timer = threading.Timer(
                    self._linger_s, self._linger_flush
                )
                self._linger_timer.daemon = True
                self._linger_timer.start()
        # Backpressure: bound in-flight TICKS (and their HBM) by waiting
        # out the oldest fetch. The bound is pipeline_depth ticks of
        # unfetched output plus the group currently staging (a resolved
        # future's buffers are already on host). The waited result is
        # not dropped — it stays queued for the next harvest in order.
        limit = self.pipeline_depth + self.fetch_every - 1
        while self._unfetched_ticks() > limit:
            with self._stage_lock:
                oldest = next(
                    (f for f, _ in self._in_flight if not f.done()), None
                )
            if oldest is None:
                break
            oldest.result()  # wait outside the lock

    def _unfetched_ticks(self) -> int:
        with self._stage_lock:
            pending = sum(
                n for f, n in self._in_flight if not f.done()
            )
            return pending + len(self._staged)

    def _submit_group(self) -> None:
        with self._stage_lock:
            self._submit_group_locked()

    def _submit_group_locked(self) -> None:
        """Move the staged ring into one fetch job. The per-output stack
        happens here (an async device op); the worker thread then pays a
        single device→host round trip for all staged ticks."""
        if not self._staged:
            return
        timer, self._linger_timer = self._linger_timer, None
        if timer is not None:
            timer.cancel()
        staged, self._staged = self._staged, []
        if len(staged) == 1:
            payload = staged[0]
        else:
            import jax.numpy as jnp

            payload = {
                key: jnp.stack([tick[key] for tick in staged])
                for key in staged[0]
            }
        # The tick count travels as a submit argument AND in the
        # in-flight pair — never attached to the future post-submit
        # (a worker could observe the future before the attribute).
        future = self._fetch_pool.submit(self._emit, payload, len(staged))
        self._in_flight.append((future, len(staged)))
        if self.on_fetch_done is not None:
            future.add_done_callback(lambda _f: self.on_fetch_done())

    def _linger_flush(self) -> None:
        with self._stage_lock:
            self._linger_timer = None
            self._submit_group_locked()

    def _emit(self, outputs: dict, n_ticks: int = 1) -> list[dict]:
        from dora_tpu.tpu.bridge import device_to_arrow

        # The device→host transfer goes through the module-level _fetch
        # hook (tests inject fetch latency there); the Arrow conversion
        # below then runs on host arrays at zero device cost.
        host = {out_id: _fetch(v) for out_id, v in outputs.items()}
        if n_ticks == 1:
            return [
                {out_id: device_to_arrow(v) for out_id, v in host.items()}
            ]
        # ONE fetch per output id moved all n_ticks frames; the split
        # back into per-tick frames is host-side numpy slicing.
        return [
            {out_id: device_to_arrow(v[i]) for out_id, v in host.items()}
            for i in range(n_ticks)
        ]

    @property
    def has_in_flight(self) -> bool:
        with self._stage_lock:
            return bool(self._in_flight) or bool(self._staged)

    def harvest(self, block: bool = False) -> list[dict]:
        """Completed tick outputs in dispatch order. Non-blocking by
        default: drains the queue head while its fetch has finished.
        ``block`` waits for everything (stream-end flush), including a
        partially filled fetch group."""
        if block:
            self._submit_group()
        done: list[dict] = []
        while True:
            with self._stage_lock:
                if not self._in_flight:
                    break
                future, _ = self._in_flight[0]
                if not (block or future.done()):
                    break
                self._in_flight.pop(0)
            done.extend(future.result())  # may wait: outside the lock
        return done

    def close(self) -> None:
        """Release the fetch pool. Call after the stream-end flush
        (``harvest(block=True)``); any still-queued fetches are drained
        so their device buffers are not abandoned mid-copy."""
        with self._stage_lock:
            timer, self._linger_timer = self._linger_timer, None
            in_flight, self._in_flight = self._in_flight, []
        if timer is not None:
            timer.cancel()
        if self._fetch_pool is not None:
            for future, _ in in_flight:
                try:
                    future.result()
                except Exception:
                    pass
            self._fetch_pool.shutdown(wait=True)
            self._fetch_pool = None
