"""The operator runtime: a node process hosting operators.

Reference parity: binaries/runtime — a special node that bridges daemon
events to operator callbacks. Improvements over the reference: one runtime
hosts MANY operators (the reference supports exactly one per process,
runtime/src/lib.rs:44-51), and jax operators fuse into a single XLA
computation per tick (dora_tpu.tpu.fuse) with edges resident in HBM.

Python operators keep the reference convention: the source file defines
``class Operator`` with ``on_event(event, send_output) -> DoraStatus``
(binaries/runtime/src/operator/python.rs:93-107), with hot-reload that
preserves the instance ``__dict__`` (python.rs:129-185).
"""

from __future__ import annotations

import importlib.util
import logging
import sys
from pathlib import Path
from typing import Any

from dora_tpu.core.descriptor import (
    Descriptor,
    JaxSource,
    OperatorDefinition,
    PythonSource,
    RuntimeNode,
    SharedLibrarySource,
    WasmSource,
)
from dora_tpu.node import Node
from dora_tpu.telemetry import OTEL_CTX_KEY, span
from dora_tpu.tpu.api import DoraStatus

logger = logging.getLogger(__name__)


class PythonOperatorHost:
    """Hosts one Python operator instance (reference: operator/python.rs)."""

    def __init__(self, definition: OperatorDefinition, node: Node, working_dir: Path):
        self.definition = definition
        self.node = node
        self.working_dir = working_dir
        self.stopped = False
        self.instance = self._instantiate()

    def _load_module(self):
        source: PythonSource = self.definition.source
        path = Path(source.source)
        if not path.is_absolute():
            path = self.working_dir / path
        spec = importlib.util.spec_from_file_location(
            f"dora_tpu_pyop_{self.definition.id}", path
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def _instantiate(self):
        module = self._load_module()
        cls = getattr(module, "Operator")
        instance = cls()
        # Reference sets the dataflow descriptor as a class attribute
        # (python.rs: `dataflow_descriptor`).
        instance.dataflow_descriptor = self.node.dataflow_descriptor()
        return instance

    def reload(self) -> None:
        """Re-import the source, preserving operator state (__dict__)."""
        old_dict = dict(self.instance.__dict__)
        try:
            self.instance = self._instantiate()
            self.instance.__dict__.update(old_dict)
            logger.info("operator %s reloaded", self.definition.id)
        except Exception:
            logger.exception("hot-reload of %s failed; keeping old code",
                             self.definition.id)

    def on_event(self, event: dict) -> DoraStatus:
        if self.stopped:
            return DoraStatus.STOP

        # With tracing off, span() is a single attribute check that
        # forwards parent_ctx unchanged; with it on, the operator span
        # parents the node's per-message t_send spans downstream.
        parent_ctx = str((event.get("metadata") or {}).get(OTEL_CTX_KEY, ""))
        with span(f"{self.definition.id}/on_event", parent_ctx) as ctx:

            def send_output(output_id: str, data=None, metadata=None):
                metadata = dict(metadata or {})
                # Propagate the trace continuation downstream (reference:
                # runtime/src/operator/python.rs:188-213).
                if ctx:
                    metadata.setdefault(OTEL_CTX_KEY, ctx)
                self.node.send_output(
                    f"{self.definition.id}/{output_id}", data, metadata
                )

            status = self.instance.on_event(event, send_output)
        if status is None:
            return DoraStatus.CONTINUE
        status = DoraStatus(int(status))
        if status == DoraStatus.STOP:
            self.stopped = True
        return status


def run() -> int:
    """Runtime node main loop (spawned with DORA_NODE_CONFIG set).

    Operator loading happens BEFORE ``Node()`` joins the start barrier:
    a jax operator factory may initialize gigabytes of model weights on
    the TPU, and subscribing first would release upstream producers (a
    camera on a timer) minutes before this node can consume — the
    barrier exists exactly to prevent that."""
    import os as _os

    from dora_tpu.daemon.spawn import NODE_CONFIG_ENV, decode_node_config

    raw_config = _os.environ.get(NODE_CONFIG_ENV)
    if not raw_config:
        raise RuntimeError("runtime must be spawned by a daemon "
                           f"({NODE_CONFIG_ENV} is not set)")
    config = decode_node_config(raw_config)
    descriptor = Descriptor.parse(config.dataflow_descriptor)
    me = descriptor.node(config.node_id)
    if not isinstance(me.kind, RuntimeNode):
        raise RuntimeError(f"node {config.node_id!r} is not a runtime node")
    working_dir = Path.cwd()

    has_jax = any(
        isinstance(op.source, JaxSource) for op in me.kind.operators
    )
    if has_jax:
        # Multi-host tensor plane (SURVEY §2.9): when the deployment sets
        # the DORA_JAX_* contract, this runtime joins the global mesh
        # (one runtime node per TPU host) before any operator loads, so
        # DORA_MESH sharding spans hosts — ICI within a slice, DCN across.
        from dora_tpu import backend
        from dora_tpu.parallel.distributed import maybe_init_distributed

        maybe_init_distributed()
        # The chip or an explicit JAX_PLATFORMS=cpu — never a silent
        # fallback (dora_tpu/backend.py); and the compile cache placed
        # before the first operator jits.
        backend.init_compile_cache()
        backend.require_accelerator(f"runtime node {config.node_id}")
    for op in me.kind.operators:
        if isinstance(op.source, WasmSource):
            # Reference parity: declared, not runnable
            # (binaries/runtime/src/operator/mod.rs:65-67).
            raise RuntimeError(
                f"operator {op.id!r}: WASM operators are not supported yet"
            )

    fused = None
    if has_jax:
        import time as _time

        from dora_tpu.tpu.fuse import FusedExecutor, FusedGraph

        t0 = _time.perf_counter()
        graph = FusedGraph.build(me, descriptor, working_dir)
        fused = FusedExecutor(graph)
        logger.info(
            "fused %d jax operators in %.1fs (topo %s); external in=%s out=%s",
            len(graph.operators), _time.perf_counter() - t0, graph.topo,
            sorted(graph.external_inputs | graph.timer_inputs),
            sorted(graph.external_outputs),
        )

    node = Node()  # subscribes: joins the start barrier only now
    logger.info("subscribed; start barrier passed")
    python_hosts: dict[str, Any] = {}  # callback-style hosts (python + C ABI)
    for op in me.kind.operators:
        if isinstance(op.source, PythonSource):
            python_hosts[str(op.id)] = PythonOperatorHost(op, node, working_dir)
        elif isinstance(op.source, SharedLibrarySource):
            from dora_tpu.runtime.shared_lib import SharedLibOperatorHost

            python_hosts[str(op.id)] = SharedLibOperatorHost(
                op, node, working_dir
            )

    # Per-event processing honors the YAML queue_size contract end to
    # end: while a tick runs, the node's bounded event buffer
    # (EventStream.DEFAULT_MAX_QUEUE) stops pulling, events back up in
    # the daemon's per-input queues, and drop-oldest applies there — a
    # camera with queue_size 1 lags the fused model by at most the few
    # in-flight events, never by an unbounded replayed backlog.
    if fused is not None and fused.pipeline_depth > 0:
        # Completed pipelined fetches wake the parked recv below, so the
        # loop emits finished tick outputs immediately even when the
        # trigger stream goes quiet — no polling interval, no idle burn.
        fused.on_fetch_done = node.wake

    stop_all = False
    while True:
        event = node.recv()
        # Emit every completed pipelined tick on EVERY iteration, not
        # just on WAKE: a wake dropped against a full event queue (full
        # queue == more events coming == more iterations) must not
        # strand a finished output behind non-harvesting events.
        if fused is not None and fused.has_in_flight:
            for outputs in fused.harvest():
                for out_id, (arr, meta) in outputs.items():
                    node.send_output(out_id, arr, meta)
        if event is None:
            if node.stream_ended:
                break
            continue
        if event["type"] == "WAKE":
            continue  # handled by the harvest above
        if event["type"] == "INPUT":
            op_id, _, input_id = (event["id"] or "").partition("/")
            host = python_hosts.get(op_id)
            if host is not None:
                status = host.on_event(
                    {
                        "type": "INPUT",
                        "id": input_id,
                        "value": event["value"],
                        "metadata": event["metadata"],
                    }
                )
                if status == DoraStatus.STOP_ALL:
                    stop_all = True
            elif fused is not None:
                if fused.pipeline_depth > 0:
                    # Async serving: dispatch without fetching, then emit
                    # whatever earlier ticks have completed — the fetch
                    # round-trip overlaps the next frame's compute.
                    fused.on_event_async(
                        event["id"], event["value"], event["metadata"]
                    )
                    for outputs in fused.harvest():
                        for out_id, (arr, meta) in outputs.items():
                            node.send_output(out_id, arr, meta)
                else:
                    outputs = fused.on_event(
                        event["id"], event["value"], event["metadata"]
                    )
                    if outputs:
                        for out_id, (arr, meta) in outputs.items():
                            node.send_output(out_id, arr, meta)
        elif event["type"] == "RELOAD":
            target = event.get("operator_id")
            for op_id, host in python_hosts.items():
                if target in (None, op_id):
                    if hasattr(host, "reload"):  # C-ABI ops don't hot-reload
                        host.reload()
        elif event["type"] == "INPUT_CLOSED":
            continue
        elif event["type"] == "STOP":
            break
        if stop_all or (
            python_hosts
            and all(h.stopped for h in python_hosts.values())
            and fused is None
        ):
            break

    if fused is not None and fused.pipeline_depth > 0:
        # Stream end: flush in-flight ticks so the tail frames are
        # delivered before the node leaves (order preserved).
        try:
            for outputs in fused.harvest(block=True):
                for out_id, (arr, meta) in outputs.items():
                    node.send_output(out_id, arr, meta)
        except Exception:
            logger.exception("pipelined flush failed")
    if fused is not None:
        fused.close()

    for host in python_hosts.values():
        if not host.stopped:
            try:
                host.on_event({"type": "STOP", "id": None, "value": None,
                               "metadata": {}})
            except Exception:
                pass
        close = getattr(host, "close", None)
        if close is not None:
            close()
    node.close()
    return 0
