"""The daemon: spawn dataflows, route messages, own timers and buffers.

Reference parity: binaries/daemon/src/lib.rs — per-machine data plane with
a start barrier (pending.rs), output routing with bounded per-input queues,
shared-memory drop-token lifecycle (§2.8 of SURVEY.md), stop with grace
kill, and failure classification (grace_duration / cascading / other).

Two modes, like the reference (lib.rs:93-224):
  * attached: `Daemon.run(coordinator_addr, machine_id)` — register with a
    coordinator, serve Spawn/Stop/… events (dora_tpu.daemon.coordinator_conn);
  * standalone: `run_dataflow(descriptor)` — run one dataflow to completion
    in-process (CLI `dora daemon --run-dataflow`, tests, examples).
"""

from __future__ import annotations

import asyncio
import logging
import random
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable

from dora_tpu import PROTOCOL_VERSION
from dora_tpu.clock import HLC
from dora_tpu.core.config import TimerMapping, UserMapping
from dora_tpu.core.descriptor import CustomNode, Descriptor, new_dataflow_uuid
from dora_tpu.daemon import spawn as spawn_mod
from dora_tpu.daemon.connection import (
    NodeConnection,
    ShmemConnection,
    serve_stream,
)
from dora_tpu.transport.framing import ConnectionClosed
from dora_tpu.daemon.queues import DropQueue, NodeEventQueue, QueueEntry
from dora_tpu.daemon.replay_buffer import ReplayBuffer
from dora_tpu.ids import DataId, InputId, NodeId, OutputId
from dora_tpu.message import daemon_to_node as d2n
from dora_tpu.message import node_to_daemon as n2d
from dora_tpu.message.common import (
    InlineData,
    Metadata,
    NodeError,
    NodeErrorCause,
    NodeExitStatus,
    NodeResult,
    DataflowResult,
    SharedMemoryData,
    TypeInfo,
    ENCODING_RAW,
)
from dora_tpu.message import fastroute
from dora_tpu import fleet
from dora_tpu.alerts import AlertEngine, engine_for
from dora_tpu.metrics import DataflowMetrics
from dora_tpu.metrics_history import MetricsHistoryRing, history_interval_s
from dora_tpu.telemetry import FLIGHT, OTEL_CTX_KEY, TRACING
from dora_tpu.message.serde import (
    Timestamped,
    decode_timestamped,
    encode,
    encode_timestamped,
)
from dora_tpu.native import ShmemChannel, ShmemRegion

logger = logging.getLogger(__name__)

#: Default stop grace period before leftover nodes are killed
#: (reference: binaries/daemon/src/lib.rs:1616).
DEFAULT_GRACE_S = 15.0

#: Control-channel shmem capacity. Payloads ≥ the zero-copy threshold travel
#: in their own regions; the channel only carries control messages and
#: inline payloads.
SHMEM_CHANNEL_CAPACITY = 1 << 20

#: Trace plane: cap on buffered ReportTrace events per node (oldest
#: dropped first — same recency-wins policy as the ring itself).
MAX_NODE_TRACE_EVENTS = 20_000


def _extend_trace_buffer(df, node_id: str, events: list) -> None:
    """Append a node's ReportTrace chunk to its bounded daemon-side
    buffer. Trimming is COUNTED (``node_trace_drops``), not silent: the
    count rides the trace snapshot so QueryTrace replies and the Chrome
    export can say how many events this second truncation point lost
    (the ring's own wrap losses are already ``trace_truncated`` events
    inside the stream)."""
    buf = df.node_traces.setdefault(node_id, [])
    buf.extend(events)
    if len(buf) > MAX_NODE_TRACE_EVENTS:
        trim = len(buf) - MAX_NODE_TRACE_EVENTS
        df.node_trace_drops[node_id] = (
            df.node_trace_drops.get(node_id, 0) + trim
        )
        del buf[:trim]


@dataclass
class TokenState:
    """One shared-memory region in flight: who owns it, how many receivers
    still reference it."""

    owner: str  # node id
    pending: int = 0


@dataclass
class RunningNode:
    node_id: str
    process: Any = None  # asyncio.subprocess.Process | None (dynamic)
    finished: bool = False
    dynamic: bool = False


@dataclass
class DataflowState:
    id: str
    descriptor: Descriptor
    working_dir: Path
    local_nodes: set[str]  # node ids this machine runs
    #: OutputId -> receiver InputIds (local and remote alike)
    mappings: dict[OutputId, set[InputId]] = field(default_factory=dict)
    open_outputs: set[OutputId] = field(default_factory=set)
    #: receiver node -> its user (non-timer) inputs that are still open
    open_inputs: dict[str, set[str]] = field(default_factory=dict)
    #: interval_ns -> receiver InputIds
    timers: dict[int, set[InputId]] = field(default_factory=dict)
    timer_tasks: list[asyncio.Task] = field(default_factory=list)
    queues: dict[str, NodeEventQueue] = field(default_factory=dict)
    drop_queues: dict[str, DropQueue] = field(default_factory=dict)
    #: shmem drop tokens still referenced by receivers
    tokens: dict[str, TokenState] = field(default_factory=dict)
    #: per-receiver tokens delivered in a NextEvents batch but not yet acked
    delivered_tokens: dict[str, set[str]] = field(default_factory=dict)
    #: (sender, output_id) -> (OutputId, [(receiver node, input id)]) —
    #: the wire fast path's view of ``mappings`` with the id parsing and
    #: stringification done once (mappings are fixed after spawn; the
    #: mutable open_outputs/open_inputs/p2p_edges are re-checked per send)
    route_cache: dict[tuple[str, str], Any] = field(default_factory=dict)
    running_nodes: dict[str, RunningNode] = field(default_factory=dict)
    node_results: dict[str, NodeResult] = field(default_factory=dict)
    stderr_rings: dict[str, list[str]] = field(default_factory=dict)
    #: start barrier
    pending_nodes: set[str] = field(default_factory=set)
    started: asyncio.Event = field(default_factory=asyncio.Event)
    barrier_error: str | None = None
    #: node whose pre-subscribe exit poisoned the barrier (structured
    #: cascading-cause attribution; never recovered from the message text)
    barrier_failed_node: str | None = None
    #: failure bookkeeping
    failed_nodes: list[str] = field(default_factory=list)
    grace_kills: set[str] = field(default_factory=set)
    stop_sent: bool = False
    done: asyncio.Future = field(default_factory=lambda: asyncio.get_event_loop().create_future())
    #: regions this daemon mapped for routing (closed on finish)
    mapped_regions: dict[str, ShmemRegion] = field(default_factory=dict)
    #: shmem node-channel connections created for this dataflow
    shmem_conns: list[Any] = field(default_factory=list)
    #: multi-machine: machine id -> daemon listen addr (inter-daemon data)
    machine_listen_ports: dict[str, str] = field(default_factory=dict)
    #: node id -> set when its control-channel connection has fully drained;
    #: exit handling waits on this so in-flight SendMessages are not lost
    control_done: dict[str, asyncio.Event] = field(default_factory=dict)
    #: peer-to-peer: node -> {input_id: shmem channel name} announced
    #: pre-barrier (the announcement marks sender capability too)
    p2p_listeners: dict[str, dict[str, str]] = field(default_factory=dict)
    #: edges assigned p2p at barrier release; send_out skips these
    #: (sender, output, receiver, input)
    p2p_edges: set = field(default_factory=set)
    #: hot-path counters + latency histograms (dora_tpu.metrics)
    metrics: DataflowMetrics = field(default_factory=DataflowMetrics)
    #: trace plane: node id -> flight-recorder events the node shipped
    #: via ReportTrace (bounded; see MAX_NODE_TRACE_EVENTS)
    node_traces: dict[str, list] = field(default_factory=dict)
    #: trace plane: node id -> events the daemon-side cap trimmed away
    #: (the ring's own wrap losses arrive as trace_truncated events;
    #: this counts the second truncation point, the buffer here)
    node_trace_drops: dict[str, int] = field(default_factory=dict)
    #: serving plane: node id -> latest ServingMetrics snapshot the node
    #: shipped via ReportServing (latest-wins; snapshots are cumulative)
    node_serving: dict[str, dict] = field(default_factory=dict)
    #: fleet plane: node id -> {"digest": dict, "recv_wall_ns": int} —
    #: the latest EngineStateDigest shipped via ReportEngineState with
    #: its receive stamp (digest age is measured from the stamp, so a
    #: wedged exporter shows as a growing age, not silence)
    node_fleet: dict[str, dict] = field(default_factory=dict)
    #: elastic recovery: node id -> respawn attempts consumed so far
    respawn_attempts: dict[str, int] = field(default_factory=dict)
    #: nodes between death and respawn — the finish check treats them
    #: as still running, so the dataflow cannot conclude under them
    respawning: set[str] = field(default_factory=set)
    #: node id -> un-acked delivered-input window, redelivered on
    #: respawn (nodes with a ``restart`` policy only)
    replay_buffers: dict[str, ReplayBuffer] = field(default_factory=dict)
    #: node id -> the asyncio task consuming its event queue. Respawn
    #: cancels the dead incarnation's task BEFORE replaying: a loop
    #: parked in next_batch cannot see its socket die, and waking it
    #: with the replayed entries would hand them to a dead connection.
    event_tasks: dict[str, asyncio.Task] = field(default_factory=dict)
    #: metrics time series: bounded ring of delta-encoded samples
    #: (dora_tpu.metrics_history; None when DORA_METRICS_HISTORY_S <= 0).
    #: Retained after finish so QueryMetricsHistory covers archived runs.
    history: MetricsHistoryRing | None = None
    #: the sampler task feeding ``history`` (cancelled on finish)
    history_task: asyncio.Task | None = None
    #: alerting plane: rules engine evaluated on the sampler tick over
    #: ``history`` (dora_tpu.alerts; None when history is off or
    #: DORA_ALERTS=0). Retained after finish like the ring, so
    #: QueryAlerts covers archived runs.
    alerts: AlertEngine | None = None
    #: structured log severity: node id -> [error lines, warn lines]
    #: counted by on_node_log from the parsed level prefixes
    log_counts: dict[str, list[int]] = field(default_factory=dict)

    def node_machine(self, node_id: str) -> str:
        return self.descriptor.node(node_id).deploy.machine or ""


class Daemon:
    """One data-plane daemon (per machine)."""

    def __init__(
        self,
        machine_id: str = "",
        local_comm: str = "tcp",
        uds_dir: str | None = None,
    ):
        self.machine_id = machine_id
        self.local_comm = local_comm
        self.uds_dir = uds_dir
        # Re-read the flight-recorder/tracing env knobs: the daemon may
        # be constructed long after module import (bench A/B legs, tests).
        FLIGHT.configure_from_env()
        TRACING.configure_from_env()
        self.clock = HLC()
        self.dataflows: dict[str, DataflowState] = {}
        self._server: asyncio.AbstractServer | None = None
        self._server_addr: str | None = None
        self._dynamic_server: asyncio.AbstractServer | None = None
        self.dynamic_port: int | None = None
        #: hook for attached mode: send InterDaemonEvent to another machine
        self.inter_daemon_send: Callable[..., Any] | None = None
        #: hook for attached mode: notify coordinator (ReadyOnMachine, logs, …)
        self.coordinator_notify: Callable[..., Any] | None = None
        #: optional sink for log lines (LogSubscribe streaming)
        self.log_sink: Callable[..., Any] | None = None
        #: hook for attached mode: forward a node's finished deep-capture
        #: artifact (n2d.ReportProfile) to the coordinator's waiting
        #: StartProfile/StopProfile reply
        self.profile_sink: Callable[..., Any] | None = None

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    async def start(self, dynamic_port: int | None = 0) -> None:
        """Start the node-channel accept loop (tcp/uds) and the dynamic-node
        bootstrap listener."""
        if self.local_comm == "uds":
            import tempfile

            d = self.uds_dir or tempfile.mkdtemp(prefix="dora-tpu-")
            path = str(Path(d) / f"daemon-{id(self):x}.sock")
            self._server, self._server_addr = await serve_stream(
                self._handle_connection, uds_path=path
            )
        else:
            self._server, self._server_addr = await serve_stream(
                self._handle_connection
            )
        if dynamic_port is not None:
            self._dynamic_server = await asyncio.start_server(
                self._handle_dynamic_client, host="127.0.0.1", port=dynamic_port
            )
            self.dynamic_port = self._dynamic_server.sockets[0].getsockname()[1]

    async def close(self) -> None:
        for df in list(self.dataflows.values()):
            for t in df.timer_tasks:
                t.cancel()
            if df.history_task is not None:
                df.history_task.cancel()
                df.history_task = None
            # Teardown reaper: node processes must never outlive the
            # daemon (an aborted/timed-out dataflow otherwise leaks
            # wedged nodes holding mapped shmem — observed as orphaned
            # checker.py processes in round 2). The graceful path
            # (stop_dataflow + grace kill) has already run by the time a
            # healthy dataflow gets here, so these are stragglers: kill.
            # Before the servers close, not after: ``wait_closed`` waits
            # for every open connection, and a node wedged in a device
            # call (a timed-out dataflow's reason for being here) keeps
            # its connection open for ever — the daemon then never
            # reached this line and the node kept the chip.
            self._kill_stragglers(df)
            self._close_shmem_conns(df)
            for region in df.mapped_regions.values():
                try:
                    region.close(unlink=False, force=True)
                except Exception:
                    pass
        for server in (self._server, self._dynamic_server):
            if server is not None:
                server.close()
                try:
                    await asyncio.wait_for(server.wait_closed(), timeout=5)
                except Exception:
                    pass

    async def run(
        self,
        coordinator_addr: str,
        machine_id: str = "",
        register_timeout_s: float = 30.0,
    ) -> None:
        """Attached mode: register with a coordinator and serve its events
        until destroyed (reference: Daemon::run, daemon/src/lib.rs:93-155)."""
        from dora_tpu.daemon.coordinator_conn import run_attached

        await run_attached(self, coordinator_addr, machine_id, register_timeout_s)

    # ------------------------------------------------------------------
    # dataflow spawn
    # ------------------------------------------------------------------

    async def spawn_dataflow(
        self,
        descriptor: Descriptor,
        dataflow_id: str | None = None,
        working_dir: str | Path | None = None,
        local_nodes: set[str] | None = None,
        machine_listen_ports: dict[str, str] | None = None,
    ) -> DataflowState:
        """Build routing tables and spawn this machine's (non-dynamic) nodes."""
        dataflow_id = dataflow_id or new_dataflow_uuid()
        working_dir = Path(working_dir or Path.cwd()).resolve()
        if local_nodes is None:
            local_nodes = {
                str(n.id)
                for n in descriptor.nodes
                if (n.deploy.machine or "") == self.machine_id
            }

        df = DataflowState(
            id=dataflow_id,
            descriptor=descriptor,
            working_dir=working_dir,
            local_nodes=local_nodes,
            machine_listen_ports=dict(machine_listen_ports or {}),
        )
        self.dataflows[dataflow_id] = df

        # Metrics history ring + sampler (DORA_METRICS_HISTORY_S <= 0
        # disables). SLO targets come from the descriptor's per-node
        # ``slo:`` blocks; violations flag ring samples and land in the
        # flight recorder as instants on the trace timeline.
        interval = history_interval_s()
        if interval > 0:
            slo_targets = {
                str(n.id): n.slo.as_targets()
                for n in descriptor.nodes
                if n.slo is not None
            }
            df.history = MetricsHistoryRing(
                interval_s=interval, slo_targets=slo_targets
            )
            # Alert engine rides the same cadence: default rule pack
            # merged under the descriptor's ``alerts:`` block, sinks
            # from DORA_ALERT_SINK (dora_tpu.alerts; DORA_ALERTS=0
            # disables evaluation while keeping the ring).
            df.alerts = engine_for(descriptor.alerts, interval_s=interval)
            df.history_task = asyncio.create_task(self._history_sampler(df))

        # Routing tables (reference: daemon/src/lib.rs:628-660).
        for node in descriptor.nodes:
            for output in node.outputs:
                df.open_outputs.add(OutputId(node.id, output))
        for node in descriptor.nodes:
            nid = str(node.id)
            fused_internal = node.fused_internal_inputs()
            for input_id, inp in node.inputs.items():
                if input_id in fused_internal:
                    # Edge between two fused jax operators: an SSA value
                    # inside the node's XLA computation, not a routed input.
                    continue
                target = InputId(node.id, input_id)
                if isinstance(inp.mapping, TimerMapping):
                    df.timers.setdefault(inp.mapping.interval_ns, set()).add(target)
                else:
                    mapping: UserMapping = inp.mapping
                    df.mappings.setdefault(mapping.output_id, set()).add(target)
                    df.open_inputs.setdefault(nid, set()).add(str(input_id))

        # Per-local-node queues + barrier membership.
        for node in descriptor.nodes:
            nid = str(node.id)
            if nid not in local_nodes:
                continue
            queue_sizes = {
                str(iid): inp.queue_size for iid, inp in node.inputs.items()
            }
            df.queues[nid] = NodeEventQueue(
                node_id=nid,
                queue_sizes=queue_sizes,
                on_token_unref=lambda token, df=df: self._unref_token(df, token),
                metrics=df.metrics,
            )
            df.drop_queues[nid] = DropQueue()
            df.control_done[nid] = asyncio.Event()
            if node.restart is not None:
                df.replay_buffers[nid] = ReplayBuffer(
                    nid, spill_dir=working_dir / ".dora-replay" / dataflow_id
                )
            dynamic = isinstance(node.kind, CustomNode) and node.kind.is_dynamic
            df.running_nodes[nid] = RunningNode(node_id=nid, dynamic=dynamic)
            if not dynamic:
                df.pending_nodes.add(nid)

        # Spawn processes.
        for node in descriptor.nodes:
            nid = str(node.id)
            if nid not in local_nodes or df.running_nodes[nid].dynamic:
                continue
            node_config = self._make_node_config(df, nid)
            try:
                process = await spawn_mod.spawn_node(self, df, node, node_config)
            except RuntimeError as e:
                self.handle_node_exit(df, node.id, None, error=str(e))
                continue
            df.running_nodes[nid].process = process

        if not df.pending_nodes:
            self._release_barrier(df)
        return df

    def _make_node_config(self, df: DataflowState, node_id: str) -> d2n.NodeConfig:
        node = df.descriptor.node(node_id)
        run_config = d2n.RunConfig(
            inputs={str(i): inp.queue_size for i, inp in node.inputs.items()},
            outputs=[str(o) for o in node.outputs],
        )
        if self.local_comm == "shmem":
            import uuid as uuid_mod

            # Random component: uuid7 time prefixes repeat across nearby
            # runs, and a crashed run's leaked segments must never collide
            # with a new one (shm_open O_EXCL would fail).
            prefix = f"dtp-{df.id[:8]}-{uuid_mod.uuid4().hex[:8]}-{node_id}"
            comm: Any = d2n.ShmemCommunication(
                control_region_id=f"{prefix}-ctl",
                events_region_id=f"{prefix}-evt",
                drop_region_id=f"{prefix}-drop",
            )
            for name in (comm.control_region_id, comm.events_region_id,
                         comm.drop_region_id):
                channel = ShmemChannel.create(name, SHMEM_CHANNEL_CAPACITY)
                conn = ShmemConnection(channel)
                df.shmem_conns.append(conn)
                asyncio.create_task(self._handle_connection(conn))
        elif self.local_comm == "uds":
            comm = d2n.UnixDomainCommunication(socket_file=self._server_addr)
        else:
            comm = d2n.TcpCommunication(socket_addr=self._server_addr)
        return d2n.NodeConfig(
            dataflow_id=df.id,
            node_id=node_id,
            run_config=run_config,
            daemon_communication=comm,
            dataflow_descriptor=dict(df.descriptor.raw),
            dynamic=df.running_nodes.get(node_id, RunningNode(node_id)).dynamic,
        )

    # ------------------------------------------------------------------
    # start barrier (reference: binaries/daemon/src/pending.rs)
    # ------------------------------------------------------------------

    def _node_subscribed(self, df: DataflowState, node_id: str) -> None:
        if node_id in df.pending_nodes:
            df.pending_nodes.discard(node_id)
            if not df.pending_nodes:
                if self._is_multi_machine(df):
                    # Multi-machine: coordinator aggregates ReadyOnMachine and
                    # broadcasts AllNodesReady (coordinator/src/lib.rs:221-267).
                    self.coordinator_notify("ready", df, [])
                else:
                    self._release_barrier(df)

    def _release_barrier(
        self,
        df: DataflowState,
        error: str | None = None,
        failed_node: str | None = None,
    ) -> None:
        df.barrier_error = error
        df.barrier_failed_node = failed_node
        if error is None:
            self._compute_p2p(df)
        df.started.set()
        if error is None:
            self._start_timers(df)

    def _compute_p2p(self, df: DataflowState) -> None:
        """Assign peer-to-peer edges (TPU-build extension): an edge goes
        direct when both endpoints are local, both announced (python
        clients that will serve/query the channels), the receiver serves
        that input, and the output is produced by the node itself (a
        send_stdout_as output is published by the daemon's stdout pump,
        which must keep routing it). Assigned edges are skipped by
        send_out — the sender publishes into the receiver's channel."""
        import os

        if os.environ.get("DORA_P2P", "1") in ("", "0"):
            return
        for oid, targets in df.mappings.items():
            sender = str(oid.node)
            if sender not in df.local_nodes or sender not in df.p2p_listeners:
                continue
            node = df.descriptor.node(sender)
            if node.send_stdout_as == str(oid.output):
                continue
            for target in targets:
                rnode = str(target.node)
                listeners = df.p2p_listeners.get(rnode)
                if (
                    rnode in df.local_nodes
                    and listeners is not None
                    and str(target.input) in listeners
                    # A restartable receiver's inputs stay daemon-routed:
                    # crash replay needs the daemon to hold the un-acked
                    # in-flight window (ReplayBuffer), and p2p events
                    # bypass it entirely.
                    and df.descriptor.node(rnode).restart is None
                ):
                    df.p2p_edges.add(
                        (sender, str(oid.output), rnode, str(target.input))
                    )

    def _p2p_edges_reply(self, df: DataflowState, node_id: str) -> Any:
        outputs: dict[str, Any] = {}
        for oid, targets in df.mappings.items():
            if str(oid.node) != node_id:
                continue
            edges = []
            daemon_route = False
            for target in targets:
                rnode = str(target.node)
                key = (node_id, str(oid.output), rnode, str(target.input))
                if key in df.p2p_edges:
                    edges.append(
                        d2n.P2PEdge(
                            channel=df.p2p_listeners[rnode][str(target.input)],
                            input_id=str(target.input),
                            receiver=rnode,
                        )
                    )
                else:
                    daemon_route = True
            if edges:
                outputs[str(oid.output)] = d2n.P2POutput(
                    edges=edges, daemon_route=daemon_route
                )
        return d2n.P2PEdgesReply(outputs=outputs)

    def release_barrier(self, df: DataflowState) -> None:
        """Coordinator broadcast AllNodesReady: release the start barrier."""
        if not df.started.is_set():
            self._release_barrier(df)

    def _is_multi_machine(self, df: DataflowState) -> bool:
        return self.coordinator_notify is not None and len(df.descriptor.machines()) > 1

    def poison_barrier(self, df: DataflowState, failed_node: str) -> None:
        """A node exited before subscribing: fail the whole start barrier
        (reference: pending.rs:160-190)."""
        if not df.started.is_set():
            self._release_barrier(
                df,
                error=f"node {failed_node!r} exited before subscribing",
                failed_node=failed_node,
            )

    # ------------------------------------------------------------------
    # timers (reference: daemon/src/lib.rs:1539-1592)
    # ------------------------------------------------------------------

    def _start_timers(self, df: DataflowState) -> None:
        for interval_ns, targets in df.timers.items():
            df.timer_tasks.append(
                asyncio.create_task(self._timer_loop(df, interval_ns, targets))
            )

    async def _timer_loop(self, df, interval_ns: int, targets: set[InputId]):
        period = interval_ns / 1e9
        timer_id = str(TimerMapping(interval_ns=interval_ns).data_id)
        next_tick = time.monotonic() + period
        while True:
            delay = next_tick - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            next_tick += period
            metadata = Metadata(
                type_info=TypeInfo(encoding=ENCODING_RAW, len=0),
                parameters={"timer": timer_id},
            )
            for target in targets:
                queue = df.queues.get(str(target.node))
                if queue is None:
                    continue
                event = d2n.Input(id=str(target.input), metadata=metadata, data=None)
                ts = self.clock.new_timestamp()
                queue.push(
                    Timestamped(inner=event, timestamp=ts),
                    input_id=str(target.input),
                    send_ns=ts.physical_ns,
                )

    # ------------------------------------------------------------------
    # routing (reference: daemon/src/lib.rs:955-1003, 1314-1390)
    # ------------------------------------------------------------------

    def send_out(
        self,
        df: DataflowState,
        sender: str,
        output_id: str,
        metadata: Metadata,
        data: Any,
        send_ns: int = 0,
    ) -> None:
        """Route one output to all local receiver queues and remote machines.

        ``send_ns`` is the sender's HLC physical timestamp (from the
        Timestamped frame); it seeds the send→deliver latency histograms.
        0 means unknown — the routed events fall back to route time."""
        oid = OutputId(NodeId(sender), DataId(output_id))
        token = data.drop_token if isinstance(data, SharedMemoryData) else None
        if oid not in df.open_outputs:
            if token:
                self._notify_owner(df, sender, token)
            return
        receivers = df.mappings.get(oid, ())
        if token is not None:
            df.tokens[token] = TokenState(owner=sender)
        nbytes = metadata.type_info.len
        df.metrics.count_link(sender, output_id, nbytes)
        if FLIGHT.enabled:
            FLIGHT.record("route", f"{sender}/{output_id}", nbytes)
        if TRACING.active:
            FLIGHT.record(
                "t_route",
                f"{sender}/{output_id}",
                str(metadata.parameters.get(OTEL_CTX_KEY, "")),
                max(0, time.time_ns() - send_ns) if send_ns else 0,
            )

        remote_machines: set[str] = set()
        for target in receivers:
            rnode = str(target.node)
            if (sender, output_id, rnode, str(target.input)) in df.p2p_edges:
                continue  # the sender published this edge peer-to-peer
            if rnode in df.local_nodes:
                queue = df.queues.get(rnode)
                open_inputs = df.open_inputs.get(rnode, set())
                if queue is None or str(target.input) not in open_inputs:
                    continue
                if token is not None:
                    df.tokens[token].pending += 1
                event = d2n.Input(
                    id=str(target.input), metadata=metadata, data=data
                )
                ts = self.clock.new_timestamp()
                queue.push(
                    Timestamped(inner=event, timestamp=ts),
                    input_id=str(target.input),
                    drop_token=token,
                    send_ns=send_ns or ts.physical_ns,
                )
            else:
                remote_machines.add(df.node_machine(rnode))

        if remote_machines and self.inter_daemon_send is not None:
            # Shared memory never crosses machines: copy payload to bytes.
            payload = self._payload_bytes(df, data)
            for machine in remote_machines:
                self.inter_daemon_send(df, machine, str(oid), metadata, payload)

        # The token can already be gone: a push into a closed/dropping
        # queue (receiver died mid-dataflow) releases synchronously and
        # deletes it before we get here.
        token_state = df.tokens.get(token) if token is not None else None
        if token_state is not None and token_state.pending == 0:
            del df.tokens[token]
            self._notify_owner(df, sender, token)

    def send_out_wire(
        self, df: DataflowState, sender: str, fast: "fastroute.FastSend"
    ) -> bool:
        """Route a shallow-parsed inline SendMessage by splicing wire
        bytes — no metadata/data object trees, no re-encode on delivery.

        Returns False (nothing pushed) when any receiver is remote: the
        inter-daemon path needs the decoded metadata, so the caller
        falls back to the reflective route for the whole frame.
        """
        key = (sender, fast.output_id)
        cached = df.route_cache.get(key)
        if cached is None:
            oid = OutputId(NodeId(sender), DataId(fast.output_id))
            cached = (
                oid,
                [(str(t.node), str(t.input)) for t in df.mappings.get(oid, ())],
                f"{sender}/{fast.output_id}",  # flight label, built once
            )
            df.route_cache[key] = cached
        oid, receivers, label = cached
        if oid not in df.open_outputs:
            return True  # dropped, like send_out on a closed output
        if any(rnode not in df.local_nodes for rnode, _ in receivers):
            return False
        df.metrics.count_link(sender, fast.output_id, fast.payload_len)
        if FLIGHT.enabled:
            FLIGHT.record("fastroute_hit", label, fast.payload_len)
        send_ns = fast.timestamp.physical_ns
        if TRACING.active:
            # Context spliced off the wire by parse_send_message (no
            # metadata object tree exists on this path).
            FLIGHT.record(
                "t_route", label, fast.ctx, max(0, time.time_ns() - send_ns)
            )
        for rnode, input_id in receivers:
            if (sender, fast.output_id, rnode, input_id) in df.p2p_edges:
                continue  # the sender published this edge peer-to-peer
            queue = df.queues.get(rnode)
            if queue is None or input_id not in df.open_inputs.get(rnode, set()):
                continue
            queue.push(
                None,
                input_id=input_id,
                wire=fastroute.build_input_event(
                    input_id, fast.body, self.clock.new_timestamp()
                ),
                send_ns=send_ns,
            )
        return True

    def deliver_remote_output(
        self, df: DataflowState, output_id: str, metadata: Metadata, payload: bytes | None
    ) -> None:
        """An output forwarded from another machine's daemon."""
        oid = OutputId.parse(output_id)
        data = InlineData(data=payload) if payload is not None else None
        nbytes = metadata.type_info.len
        df.metrics.count_link(str(oid.node), str(oid.output), nbytes)
        if FLIGHT.enabled:
            FLIGHT.record("route_remote", output_id, nbytes)
        for target in df.mappings.get(oid, ()):  # local receivers only
            rnode = str(target.node)
            if rnode not in df.local_nodes:
                continue
            queue = df.queues.get(rnode)
            open_inputs = df.open_inputs.get(rnode, set())
            if queue is None or str(target.input) not in open_inputs:
                continue
            event = d2n.Input(id=str(target.input), metadata=metadata, data=data)
            # Latency measured from local arrival time: remote HLC
            # physical clocks are not comparable across machines.
            ts = self.clock.new_timestamp()
            queue.push(
                Timestamped(inner=event, timestamp=ts),
                input_id=str(target.input),
                send_ns=ts.physical_ns,
            )

    def metrics_snapshot(self, df: DataflowState) -> dict:
        """JSON-able metrics snapshot for one dataflow on this machine —
        the payload of a MetricsRequest reply (daemon → coordinator)."""
        depths: dict[str, int] = {}
        for nid, queue in df.queues.items():
            for input_id, count in queue.input_counts.items():
                if count:
                    depths[f"{nid}/{input_id}"] = count
        snap = df.metrics.snapshot(depths)
        snap["fastroute"]["fallback_reasons"] = dict(fastroute.FALLBACKS)
        if df.node_serving:
            snap["serving"] = {
                nid: dict(s) for nid, s in df.node_serving.items()
            }
        if df.node_fleet:
            now_ns = time.time_ns()
            snap["fleet"] = {
                nid: fleet.fleet_gauges(
                    e["digest"], (now_ns - e["recv_wall_ns"]) / 1e9
                )
                for nid, e in df.node_fleet.items()
            }
        if df.history is not None and df.history.slo_targets:
            snap["slo"] = df.history.slo_status()
        if df.log_counts:
            snap["logs"] = {
                nid: {"errors": c[0], "warns": c[1]}
                for nid, c in df.log_counts.items()
            }
        if df.node_trace_drops:
            snap["trace"] = {"drops": dict(df.node_trace_drops)}
        if df.alerts is not None:
            snap["alerts"] = df.alerts.status()
        return snap

    async def _history_sampler(self, df: DataflowState) -> None:
        """Feed the dataflow's history ring on the configured cadence.
        SLO violations detected by the ring are recorded as flight
        instants so they show up on the `dora-tpu trace` timeline."""
        interval = df.history.interval_s
        while True:
            await asyncio.sleep(interval)
            try:
                self.sample_history(df)
            except Exception:
                logger.exception("history sample failed (%s)", df.id)

    def sample_history(self, df: DataflowState) -> None:
        """Take one history sample now (sampler tick / final flush)."""
        if df.history is None:
            return
        snap = self.metrics_snapshot(df)
        wall_ns = time.time_ns()
        hlc_ns = self.clock.new_timestamp().physical_ns
        events = df.history.sample(snap, wall_ns, hlc_ns)
        for node, objective, observed, target in events:
            FLIGHT.record(
                "slo_violation", f"{node}:{objective}",
                f"observed={observed} target={target}", None,
            )
        # Alert evaluation rides the sampler tick: transitions become
        # flight instants on this daemon's trace track (and fan out to
        # the configured sinks inside the engine).
        if df.alerts is not None:
            for ev in df.alerts.evaluate_ring(df.history, wall_ns):
                FLIGHT.record(
                    f"alert_{ev['phase']}",
                    f"{ev['rule']}:{ev['instance']}",
                    f"value={ev['value']} threshold={ev['threshold']}",
                    None,
                )

    def history_snapshot(self, df: DataflowState) -> dict:
        """Per-machine history-ring snapshot — the payload of a
        MetricsHistoryRequest reply. Carries a ``(wall_ns, hlc_ns)``
        pair captured back to back so the merge
        (dora_tpu.metrics_history) can align this machine's sample
        stamps onto the cluster HLC timeline, exactly like the trace
        merge."""
        if df.history is None:
            return {}
        out = df.history.snapshot()
        out["machine_id"] = self.machine_id
        out["hlc_ns"] = self.clock.new_timestamp().physical_ns
        out["wall_ns"] = time.time_ns()
        return out

    def fleet_snapshot(self, df: DataflowState) -> dict:
        """Per-machine fleet snapshot — the payload of a FleetRequest
        reply. Latest digest per replica with its receive stamp, plus
        the back-to-back ``(wall_ns, hlc_ns)`` pair so the merge
        (dora_tpu.fleet.merge_fleet_snapshots) can align receive stamps
        onto the cluster HLC timeline, exactly like metrics history."""
        if not df.node_fleet:
            return {}
        return {
            "machine_id": self.machine_id,
            "hlc_ns": self.clock.new_timestamp().physical_ns,
            "wall_ns": time.time_ns(),
            "replicas": {
                nid: {**e["digest"], "recv_wall_ns": e["recv_wall_ns"]}
                for nid, e in df.node_fleet.items()
            },
        }

    def alerts_snapshot(self, df: DataflowState) -> dict:
        """Per-machine alert-engine status — the payload of an
        AlertsRequest reply. No clock alignment needed (states, not
        samples); the machine id lets the coordinator's merge attribute
        instances."""
        if df.alerts is None:
            return {}
        out = df.alerts.status()
        out["machine_id"] = self.machine_id
        return out

    def trace_snapshot(self, df: DataflowState) -> dict:
        """Per-machine trace snapshot for one dataflow — the payload of a
        TraceRequest reply. Carries this daemon's own ring plus every
        ring chunk its nodes shipped via ReportTrace, and a
        ``(wall_ns, hlc_ns)`` pair captured back to back so the merge
        (dora_tpu.tracing) can align this machine's wall stamps onto the
        cluster HLC timeline. The daemon ring is process-wide, so
        concurrent dataflows share its events."""
        processes: dict[str, list] = {
            nid: [list(e) for e in events]
            for nid, events in df.node_traces.items()
        }
        daemon_events = [list(e) for e in FLIGHT.events()]
        if daemon_events:
            processes["(daemon)"] = daemon_events
        hlc_ns = self.clock.new_timestamp().physical_ns
        out = {
            "machine": self.machine_id,
            "wall_ns": time.time_ns(),
            "hlc_ns": hlc_ns,
            "processes": processes,
        }
        if df.node_trace_drops:
            out["dropped_events"] = dict(df.node_trace_drops)
        return out

    def _payload_bytes(self, df: DataflowState, data: Any) -> bytes | None:
        if data is None:
            return None
        if isinstance(data, InlineData):
            return bytes(data.data)
        region = self._map_region(df, data.shmem_id)
        return bytes(region.buf[: data.len])

    def _map_region(self, df: DataflowState, shmem_id: str) -> ShmemRegion:
        region = df.mapped_regions.get(shmem_id)
        if region is None:
            region = ShmemRegion.open(shmem_id)
            df.mapped_regions[shmem_id] = region
        return region

    def publish_stdout_line(
        self, df: DataflowState, node_id: NodeId, output: str, line: str
    ) -> None:
        """Re-publish a stdout line as a dataflow output (``send_stdout_as``,
        reference: daemon/src/lib.rs:1174-1220). Payload is an Arrow string
        array in IPC format so receivers decode it like any other input."""
        from dora_tpu.node.arrow import ipc_bytes_str

        payload = ipc_bytes_str(line)
        metadata = Metadata(
            type_info=TypeInfo(encoding="arrow-ipc", len=len(payload)),
            parameters={},
        )
        self.send_out(df, str(node_id), output, metadata, InlineData(data=payload))

    # ------------------------------------------------------------------
    # drop tokens (reference: SURVEY.md §2.8)
    # ------------------------------------------------------------------

    def _unref_token(self, df: DataflowState, token: str) -> None:
        state = df.tokens.get(token)
        if state is None:
            return
        state.pending -= 1
        if state.pending <= 0:
            del df.tokens[token]
            self._notify_owner(df, state.owner, token)

    def _notify_owner(self, df: DataflowState, owner: str, token: str) -> None:
        drop_queue = df.drop_queues.get(owner)
        if drop_queue is not None:
            drop_queue.push(token)

    def ack_tokens(self, df: DataflowState, node_id: str, tokens: list[str]) -> None:
        delivered = df.delivered_tokens.get(node_id)
        for token in tokens:
            if delivered is not None:
                delivered.discard(token)
            self._unref_token(df, token)

    # ------------------------------------------------------------------
    # output closing / node exit
    # ------------------------------------------------------------------

    def close_outputs(self, df: DataflowState, node_id: str, outputs: list[str]) -> None:
        """Close outputs; propagate InputClosed/AllInputsClosed downstream
        (and InputsClosed to remote machines)."""
        remote_closed: dict[str, list[str]] = {}
        for output in outputs:
            oid = OutputId(NodeId(node_id), DataId(output))
            if oid not in df.open_outputs:
                continue
            df.open_outputs.discard(oid)
            for target in df.mappings.get(oid, ()):
                rnode = str(target.node)
                if rnode not in df.local_nodes:
                    remote_closed.setdefault(
                        df.node_machine(rnode), []
                    ).append(str(target))
                    continue
                self._close_local_input(df, rnode, str(target.input))
        if remote_closed and self.inter_daemon_send is not None:
            for machine, inputs in remote_closed.items():
                self.inter_daemon_send(df, machine, None, None, None, closed=inputs)

    def _close_local_input(self, df: DataflowState, rnode: str, input_id: str) -> None:
        open_inputs = df.open_inputs.get(rnode)
        if open_inputs is None or input_id not in open_inputs:
            return
        open_inputs.discard(input_id)
        queue = df.queues.get(rnode)
        if queue is None:
            return
        queue.push(
            Timestamped(
                inner=d2n.InputClosed(id=input_id),
                timestamp=self.clock.new_timestamp(),
            )
        )
        if not open_inputs and not self._has_timer_inputs(df, rnode):
            queue.push(
                Timestamped(
                    inner=d2n.AllInputsClosed(),
                    timestamp=self.clock.new_timestamp(),
                )
            )
            queue.close()

    def close_remote_inputs(self, df: DataflowState, inputs: list[str]) -> None:
        """InputsClosed forwarded from another machine."""
        for s in inputs:
            node, _, input_id = s.partition("/")
            self._close_local_input(df, node, input_id)

    def _has_timer_inputs(self, df: DataflowState, node_id: str) -> bool:
        return any(
            str(t.node) == node_id for targets in df.timers.values() for t in targets
        )

    def handle_node_exit(
        self,
        df: DataflowState,
        node_id: NodeId | str,
        returncode: int | None,
        error: str | None = None,
    ) -> None:
        nid = str(node_id)
        running = df.running_nodes.get(nid)
        if running is None or running.finished:
            return
        running.finished = True

        if error is not None:
            status = NodeExitStatus(success=False, error=error)
        elif returncode == 0:
            status = NodeExitStatus(success=True, code=0)
        elif returncode is not None and returncode < 0:
            status = NodeExitStatus(success=False, signal=-returncode)
        else:
            status = NodeExitStatus(success=False, code=returncode)

        # Elastic recovery: a failed node with remaining restart budget
        # respawns instead of failing the dataflow. Decided BEFORE any
        # failure bookkeeping — recording the failure would cascade the
        # rest of the dataflow, and closing the queue would propagate
        # AllInputsClosed downstream and finish the run under us.
        if not status.success and self._should_respawn(df, nid):
            attempt = df.respawn_attempts.get(nid, 0) + 1
            df.respawn_attempts[nid] = attempt
            df.respawning.add(nid)
            df.metrics.count_respawn(nid)
            if FLIGHT.enabled:
                FLIGHT.record("node_respawn", nid, attempt)
            logger.warning(
                "node %s/%s failed (%s); respawn attempt %d",
                df.id, nid, error or f"code {returncode}", attempt,
            )
            asyncio.create_task(self._respawn_node(df, nid, attempt, status))
            return

        self._record_exit_result(df, nid, status)

        # Barrier poison: node died before subscribing. In multi-machine
        # mode the coordinator must learn about it so the other machines'
        # barriers fail too (reference: pending.rs ReadyOnMachine with
        # exited_before_subscribe).
        if nid in df.pending_nodes:
            df.pending_nodes.discard(nid)
            if not status.success:
                if self._is_multi_machine(df):
                    self.coordinator_notify("ready", df, [nid])
                self.poison_barrier(df, nid)
            elif not df.pending_nodes:
                if self._is_multi_machine(df):
                    self.coordinator_notify("ready", df, [])
                else:
                    self._release_barrier(df)

        # Release buffers the dead node still referenced.
        queue = df.queues.get(nid)
        if queue is not None:
            queue.release_all_tokens()
            queue.close()
        for token in df.delivered_tokens.pop(nid, set()):
            self._unref_token(df, token)
        drop_queue = df.drop_queues.get(nid)
        if drop_queue is not None:
            drop_queue.close()
        buffer = df.replay_buffers.get(nid)
        if buffer is not None:
            buffer.close()

        # Output closing + finish-check are deferred until the node's control
        # connection has drained: SendMessages still in the socket buffer at
        # exit time must route before the outputs close.
        asyncio.create_task(self._finalize_node_exit(df, nid))

    def _record_exit_result(self, df: DataflowState, nid: str,
                            status: NodeExitStatus) -> None:
        """Classify an exit (grace_duration / cascading / other) and
        record the NodeResult + failure bookkeeping."""
        if status.success:
            result = NodeResult(error=None)
        else:
            if nid in df.grace_kills:
                cause = NodeErrorCause(kind="grace_duration")
            elif df.failed_nodes:
                cause = NodeErrorCause(
                    kind="cascading", caused_by_node=df.failed_nodes[0]
                )
            elif df.barrier_error is not None and nid != df.barrier_failed_node:
                cause = NodeErrorCause(
                    kind="cascading", caused_by_node=df.barrier_failed_node
                )
            else:
                stderr = "\n".join(df.stderr_rings.get(nid, [])) or None
                cause = NodeErrorCause(kind="other", stderr=stderr)
            result = NodeResult(error=NodeError(exit_status=status, cause=cause))
            df.failed_nodes.append(nid)
        df.node_results[nid] = result

    def _should_respawn(self, df: DataflowState, nid: str) -> bool:
        """A failed exit respawns only while the dataflow is otherwise
        healthy: barrier released cleanly, no stop in flight, the node was
        not grace-killed, no other node has already failed (that failure
        is about to end the run anyway), and restart budget remains."""
        node = df.descriptor.node(nid)
        if node.restart is None:
            return False
        if df.stop_sent or nid in df.grace_kills or df.done.done():
            return False
        if not df.started.is_set() or df.barrier_error is not None:
            return False
        if df.failed_nodes:
            return False
        return df.respawn_attempts.get(nid, 0) < node.restart.max_attempts

    async def _respawn_node(
        self,
        df: DataflowState,
        nid: str,
        attempt: int,
        status: NodeExitStatus,
    ) -> None:
        """Backoff, replay the un-acked input window, spawn a fresh
        incarnation. If the dataflow stopped during the backoff, fall back
        to recording the original failure like a normal exit."""
        node = df.descriptor.node(nid)
        policy = node.restart
        delay = min(
            policy.backoff_base_s * (2 ** (attempt - 1)), policy.backoff_max_s
        )
        # Jitter decorrelates simultaneous respawns across a machine.
        await asyncio.sleep(delay * (0.75 + 0.5 * random.random()))

        if df.stop_sent or df.done.done():
            df.respawning.discard(nid)
            self._record_exit_result(df, nid, status)
            queue = df.queues.get(nid)
            if queue is not None:
                queue.release_all_tokens()
                queue.close()
            for token in df.delivered_tokens.pop(nid, set()):
                self._unref_token(df, token)
            dq = df.drop_queues.get(nid)
            if dq is not None:
                dq.close()
            buffer = df.replay_buffers.get(nid)
            if buffer is not None:
                buffer.close()
            await self._finalize_node_exit(df, nid)
            return

        # Fresh control-drain latch for the new incarnation (the old one
        # is set — its connection is gone).
        df.control_done[nid] = asyncio.Event()

        # The dead incarnation's events loop can still be parked in
        # queue.next_batch (a coroutine awaiting the queue never sees its
        # socket drop). Left alive, the replay below would WAKE it: it
        # would consume the requeued entries and send them to the dead
        # connection. Cancel it before touching the queue — next_batch
        # is cancellation-safe (a cancel while parked consumes nothing).
        stale = df.event_tasks.pop(nid, None)
        if stale is not None and not stale.done():
            stale.cancel()
            try:
                await stale
            except (asyncio.CancelledError, Exception):
                pass

        # Replay: un-acked in-flight inputs go back to the FRONT of the
        # queue, ahead of anything routed while the node was down.
        buffer = df.replay_buffers.get(nid)
        if buffer is not None and len(buffer):
            entries = buffer.drain()
            queue = df.queues.get(nid)
            if queue is not None:
                queue.requeue_front(entries)
            df.metrics.count_replayed(nid, len(entries))
            if FLIGHT.enabled:
                FLIGHT.record("replay_inputs", nid, len(entries))
            logger.info(
                "node %s/%s: replaying %d un-acked input(s) on respawn",
                df.id, nid, len(entries),
            )

        was_dynamic = df.running_nodes[nid].dynamic
        df.running_nodes[nid] = RunningNode(node_id=nid, dynamic=was_dynamic)
        df.respawning.discard(nid)
        node_config = self._make_node_config(df, nid)
        try:
            process = await spawn_mod.spawn_node(self, df, node, node_config)
        except RuntimeError as e:
            self.handle_node_exit(df, nid, None, error=str(e))
            return
        df.running_nodes[nid].process = process

    async def _finalize_node_exit(self, df: DataflowState, nid: str) -> None:
        done = df.control_done.get(nid)
        if done is not None and not done.is_set():
            try:
                await asyncio.wait_for(done.wait(), timeout=2)
            except asyncio.TimeoutError:
                pass
        node = df.descriptor.node(nid)
        self.close_outputs(df, nid, [str(o) for o in node.outputs])
        self._check_dataflow_finished(df)

    def _check_dataflow_finished(self, df: DataflowState) -> None:
        pending = [
            r
            for r in df.running_nodes.values()
            if (not r.finished or r.node_id in df.respawning) and not r.dynamic
        ]
        if pending:
            return
        for t in df.timer_tasks:
            t.cancel()
        df.timer_tasks.clear()
        if df.history_task is not None:
            df.history_task.cancel()
            df.history_task = None
            # Final flush: the ring keeps serving archived
            # QueryMetricsHistory, so capture the tail of the run.
            try:
                self.sample_history(df)
            except Exception:
                pass
        for queue in df.queues.values():
            queue.release_all_tokens()
            queue.close()
        for dq in df.drop_queues.values():
            dq.close()
        for buffer in df.replay_buffers.values():
            buffer.close()
        for region in df.mapped_regions.values():
            try:
                region.close(unlink=False, force=True)
            except Exception:
                pass
        df.mapped_regions.clear()
        # Deferred close (never block the live loop); the conns stay in
        # df.shmem_conns so Daemon.close() can still force the unlink
        # synchronously before process exit (close_sync is close-once
        # safe against this deferred path).
        for conn in df.shmem_conns:
            conn.close()
        # Safety net: unlink announced p2p edge channels a SIGKILLed node
        # may have leaked (nodes normally unlink their own on close).
        from dora_tpu.native import unlink_region

        for listeners in df.p2p_listeners.values():
            for name in listeners.values():
                for victim in (name, name + "-a"):  # data + ack channels
                    try:
                        unlink_region(victim)
                    except Exception:
                        pass
        df.p2p_listeners.clear()
        result = DataflowResult(
            uuid=df.id,
            node_results={
                nid: df.node_results.get(nid, NodeResult(error=None))
                for nid, r in df.running_nodes.items()
                if not r.dynamic or nid in df.node_results
            },
        )
        if not df.done.done():
            df.done.set_result(result)
        if self.coordinator_notify is not None:
            self.coordinator_notify("finished", df, result)

    # ------------------------------------------------------------------
    # stop (reference: daemon/src/lib.rs:1594-1636)
    # ------------------------------------------------------------------

    def stop_dataflow(self, df: DataflowState, grace_s: float | None = None) -> None:
        if df.stop_sent:
            return
        df.stop_sent = True
        if not df.started.is_set():
            self._release_barrier(df, error="dataflow stopped before start")
        for nid, queue in df.queues.items():
            running = df.running_nodes.get(nid)
            if running is not None and running.finished:
                continue
            queue.push(
                Timestamped(inner=d2n.Stop(), timestamp=self.clock.new_timestamp())
            )
            queue.close()
        asyncio.create_task(self._grace_kill(df, grace_s or DEFAULT_GRACE_S))

    async def _grace_kill(self, df: DataflowState, grace_s: float) -> None:
        await asyncio.sleep(grace_s)
        self._kill_stragglers(df, record_grace=True)

    @staticmethod
    def _kill_stragglers(df: DataflowState, record_grace: bool = False) -> None:
        for nid, running in df.running_nodes.items():
            if running.finished or running.process is None:
                continue
            if record_grace:
                df.grace_kills.add(nid)
            try:
                running.process.kill()
            except ProcessLookupError:
                pass

    @staticmethod
    def _close_shmem_conns(df: DataflowState) -> None:
        """Synchronous close + unlink (teardown path — must not outlive
        the process; see ShmemConnection.close_sync)."""
        for conn in df.shmem_conns:
            conn.close_sync()
        df.shmem_conns.clear()

    def reload_node(self, df: DataflowState, node_id: str, operator_id: str | None) -> None:
        queue = df.queues.get(node_id)
        if queue is not None:
            queue.push(
                Timestamped(
                    inner=d2n.Reload(operator_id=operator_id),
                    timestamp=self.clock.new_timestamp(),
                )
            )

    def migrate_node(self, df: DataflowState, node_id: str, handoff_dir: str) -> None:
        """Ask a serving node to drain its live streams into
        ``handoff_dir`` at the next window boundary (cm.MigrateNode)."""
        queue = df.queues.get(node_id)
        if queue is not None:
            queue.push(
                Timestamped(
                    inner=d2n.Migrate(handoff_dir=handoff_dir),
                    timestamp=self.clock.new_timestamp(),
                )
            )

    def profile_node(self, df: DataflowState, node_id: str, action: str,
                     seconds: float) -> None:
        """Ask a serving node to start/stop an on-demand deep profile
        capture (cm.StartProfile/StopProfile)."""
        queue = df.queues.get(node_id)
        if queue is not None:
            queue.push(
                Timestamped(
                    inner=d2n.Profile(action=action, seconds=seconds),
                    timestamp=self.clock.new_timestamp(),
                )
            )

    # ------------------------------------------------------------------
    # logging
    # ------------------------------------------------------------------

    def on_node_log(self, df: DataflowState, node_id: str, level: str, text: str) -> None:
        # Structured severity: feed the per-node error/warn counters the
        # metrics plane exports (prom, history series, log-errors alert).
        if level in ("error", "warn"):
            counts = df.log_counts.get(node_id)
            if counts is None:
                counts = df.log_counts[node_id] = [0, 0]
            counts[0 if level == "error" else 1] += 1
        if self.log_sink is not None:
            from dora_tpu.message.common import LogMessage

            self.log_sink(
                LogMessage(
                    dataflow_id=df.id,
                    level=level,
                    message=text,
                    node_id=node_id,
                    machine_id=self.machine_id,
                )
            )

    # ------------------------------------------------------------------
    # node-channel listeners
    # ------------------------------------------------------------------

    async def _handle_connection(self, conn: NodeConnection) -> None:
        try:
            frame = await conn.recv()
            if frame is None:
                return
            ts = decode_timestamped(frame, self.clock)
            register = ts.inner
            if not isinstance(register, n2d.Register):
                await self._reply(conn, d2n.ReplyResult(error="expected Register"))
                return
            error = self._check_register(register)
            await self._reply(conn, d2n.ReplyResult(error=error))
            if error is not None:
                return
            df = self.dataflows[register.dataflow_id]
            node_id = register.node_id
            if register.channel == n2d.CHANNEL_CONTROL:
                await self._control_loop(df, node_id, conn)
            elif register.channel == n2d.CHANNEL_EVENTS:
                await self._events_loop(df, node_id, conn)
            elif register.channel == n2d.CHANNEL_DROP:
                await self._drop_loop(df, node_id, conn)
        except (ConnectionError, ConnectionClosed):
            pass  # node went away mid-reply; its exit watcher reports it
        except Exception:
            logger.exception("node connection failed")
        finally:
            conn.close()

    def _check_register(self, register: n2d.Register) -> str | None:
        ours = PROTOCOL_VERSION.split(".")[:2]
        theirs = register.protocol_version.split(".")[:2]
        if ours != theirs:
            return (
                f"incompatible protocol version {register.protocol_version} "
                f"(daemon speaks {PROTOCOL_VERSION})"
            )
        df = self.dataflows.get(register.dataflow_id)
        if df is None:
            return f"unknown dataflow {register.dataflow_id!r}"
        if register.node_id not in df.queues:
            return f"unknown node {register.node_id!r} on this machine"
        return None

    async def _reply(self, conn: NodeConnection, msg: Any) -> None:
        await conn.send(encode_timestamped(msg, self.clock))

    async def _control_loop(self, df: DataflowState, node_id: str, conn) -> None:
        try:
            await self._control_loop_inner(df, node_id, conn)
        finally:
            done = df.control_done.get(node_id)
            if done is not None:
                done.set()

    async def _control_loop_inner(self, df: DataflowState, node_id: str, conn) -> None:
        while True:
            frame = await conn.recv()
            if frame is None:
                return
            # Hot path: inline-payload SendMessage frames route as wire
            # bytes (message/fastroute.py) — the metadata/data subtrees
            # are never built as objects. Anything the fast path cannot
            # prove routable takes the reflective decode below.
            fast = fastroute.parse_send_message(frame)
            if fast is not None:
                # Clock first: the routed events' fresh timestamps must
                # be causally after the sender's.
                self.clock.update_with_timestamp(fast.timestamp)
                if self.send_out_wire(df, node_id, fast):
                    df.metrics.fastroute_hits += 1
                    continue
                # Remote receivers: re-decode below (the second clock
                # update is harmless — HLC updates are monotone).
            tsd = decode_timestamped(frame, self.clock)
            msg = tsd.inner
            if isinstance(msg, n2d.SendMessage):
                df.metrics.fastroute_fallbacks += 1
                self.send_out(
                    df, node_id, msg.output_id, msg.metadata, msg.data,
                    send_ns=tsd.timestamp.physical_ns,
                )
            elif isinstance(msg, n2d.ReportDropTokens):
                self.ack_tokens(df, node_id, msg.drop_tokens)
            elif isinstance(msg, n2d.ReportTrace):
                _extend_trace_buffer(df, node_id, msg.events)
            elif isinstance(msg, n2d.ReportServing):
                df.node_serving[node_id] = msg.snapshot
            elif isinstance(msg, n2d.ReportEngineState):
                df.node_fleet[node_id] = {
                    "digest": fleet.digest_as_dict(msg.digest),
                    "recv_wall_ns": time.time_ns(),
                }
            elif isinstance(msg, n2d.ReportProfile):
                if self.profile_sink is not None:
                    self.profile_sink(df.id, node_id, msg.artifact, msg.error)
            elif isinstance(msg, n2d.P2PAnnounce):
                df.p2p_listeners[node_id] = dict(msg.listeners)
                await self._reply(conn, d2n.ReplyResult())
            elif isinstance(msg, n2d.P2PEdgesRequest):
                await self._reply(conn, self._p2p_edges_reply(df, node_id))
            elif isinstance(msg, n2d.CloseOutputs):
                self.close_outputs(df, node_id, msg.outputs)
                await self._reply(conn, d2n.ReplyResult())
            elif isinstance(msg, n2d.OutputsDone):
                node = df.descriptor.node(node_id)
                # The send_stdout_as output is produced by the daemon-side
                # stdout pump, not the node's control channel — it closes at
                # exit-finalize time, after the pump drained (otherwise the
                # node's own close() races its final stdout lines away).
                stdout_output = node.send_stdout_as
                self.close_outputs(
                    df,
                    node_id,
                    [str(o) for o in node.outputs if str(o) != stdout_output],
                )
                await self._reply(conn, d2n.ReplyResult())
            else:
                await self._reply(
                    conn,
                    d2n.ReplyResult(error=f"unexpected control request {type(msg).__name__}"),
                )

    async def _events_loop(self, df: DataflowState, node_id: str, conn) -> None:
        frame = await conn.recv()
        if frame is None:
            return
        msg = decode_timestamped(frame, self.clock).inner
        if not isinstance(msg, n2d.Subscribe):
            await self._reply(conn, d2n.ReplyResult(error="expected Subscribe"))
            return
        # Start barrier: withhold the reply until all nodes subscribed.
        self._node_subscribed(df, node_id)
        await df.started.wait()
        await self._reply(conn, d2n.ReplyResult(error=df.barrier_error))
        if df.barrier_error is not None:
            return

        queue = df.queues[node_id]
        delivered = df.delivered_tokens.setdefault(node_id, set())
        replay = df.replay_buffers.get(node_id)
        df.event_tasks[node_id] = asyncio.current_task()
        first_poll = True
        while True:
            frame = await conn.recv()
            if frame is None:
                return
            msg = decode_timestamped(frame, self.clock).inner
            if isinstance(msg, n2d.NextEvent):
                self.ack_tokens(df, node_id, msg.drop_tokens)
                if replay is not None and not first_poll:
                    # The poll is the ack seam: batch k+1 is requested
                    # only after batch k was consumed — but only on THIS
                    # connection. A fresh incarnation's first poll has
                    # consumed nothing and must not ack the window the
                    # dead incarnation left behind.
                    replay.ack()
                first_poll = False
                batch = await queue.next_batch()
                if replay is not None:
                    replay.remember(batch)
                wires = []
                deliver_ns = time.time_ns()
                for entry in batch:
                    if entry.drop_token is not None:
                        delivered.add(entry.drop_token)
                    if entry.send_ns and entry.input_id is not None:
                        # HLC physical time is time_ns-based, so on one
                        # machine the difference is real send→deliver
                        # latency (including queue wait).
                        df.metrics.observe_latency(
                            node_id, entry.input_id,
                            (deliver_ns - entry.send_ns) / 1000.0,
                        )
                        if TRACING.active:
                            # Daemon-side span covering queue wait: no
                            # ctx (the wire path never decodes metadata
                            # at delivery); the timeline still lines up
                            # via the wall stamps.
                            FLIGHT.record(
                                "t_deliver",
                                f"{node_id}/{entry.input_id}",
                                None,
                                max(0, deliver_ns - entry.send_ns),
                            )
                    # Fast-path entries carry their wire image; others
                    # (timers, close events, shmem inputs) encode here.
                    wires.append(
                        entry.wire if entry.wire is not None
                        else encode(entry.event)
                    )
                await conn.send(
                    fastroute.build_next_events_frame(
                        wires, self.clock.new_timestamp()
                    )
                )
            elif isinstance(msg, n2d.EventStreamDropped):
                queue.release_all_tokens()
                queue.close()
                await self._reply(conn, d2n.ReplyResult())
            else:
                await self._reply(
                    conn,
                    d2n.ReplyResult(error=f"unexpected event request {type(msg).__name__}"),
                )

    async def _drop_loop(self, df: DataflowState, node_id: str, conn) -> None:
        frame = await conn.recv()
        if frame is None:
            return
        msg = decode_timestamped(frame, self.clock).inner
        if not isinstance(msg, n2d.SubscribeDrop):
            await self._reply(conn, d2n.ReplyResult(error="expected SubscribeDrop"))
            return
        await self._reply(conn, d2n.ReplyResult())
        drop_queue = df.drop_queues[node_id]
        while True:
            frame = await conn.recv()
            if frame is None:
                return
            msg = decode_timestamped(frame, self.clock).inner
            if isinstance(msg, n2d.NextDropEvents):
                tokens = await drop_queue.next_batch()
                await self._reply(conn, d2n.DropEvents(drop_tokens=tokens))
            elif isinstance(msg, n2d.ReportDropTokens):
                self.ack_tokens(df, node_id, msg.drop_tokens)
            else:
                await self._reply(
                    conn,
                    d2n.ReplyResult(error=f"unexpected drop request {type(msg).__name__}"),
                )

    # ------------------------------------------------------------------
    # dynamic-node bootstrap (reference: daemon/src/local_listener.rs)
    # ------------------------------------------------------------------

    async def _handle_dynamic_client(self, reader, writer) -> None:
        from dora_tpu.transport.framing import recv_frame_async, send_frame_async

        try:
            frame = await recv_frame_async(reader)
            msg = decode_timestamped(frame, self.clock).inner
            if not isinstance(msg, n2d.NodeConfigRequest):
                reply = d2n.NodeConfigReply(error="expected NodeConfigRequest")
            else:
                reply = self._dynamic_node_config(msg.node_id)
            await send_frame_async(
                writer, encode_timestamped(reply, self.clock)
            )
        except Exception:
            pass
        finally:
            try:
                writer.close()
            except Exception:
                pass

    def _dynamic_node_config(self, node_id: str) -> d2n.NodeConfigReply:
        matches = []
        for df in self.dataflows.values():
            running = df.running_nodes.get(node_id)
            if running is not None and running.dynamic and not running.finished:
                matches.append(df)
        if not matches:
            return d2n.NodeConfigReply(
                error=f"no running dataflow has a dynamic node {node_id!r}"
            )
        if len(matches) > 1:
            return d2n.NodeConfigReply(
                error=f"multiple running dataflows have a dynamic node {node_id!r}; "
                f"cannot disambiguate"
            )
        df = matches[0]
        return d2n.NodeConfigReply(node_config=self._make_node_config(df, node_id))


# ---------------------------------------------------------------------------
# standalone mode (reference: daemon/src/lib.rs:157-224)
# ---------------------------------------------------------------------------


async def run_dataflow_async(
    dataflow: str | Path | Descriptor,
    working_dir: str | Path | None = None,
    local_comm: str | None = None,
    timeout_s: float | None = None,
) -> DataflowResult:
    """Run one dataflow to completion with an in-process daemon.

    ``local_comm=None`` (default) means "use the YAML's
    ``communication: {local: uds|shmem|tcp}`` block (or the reference's
    ``_unstable_local`` spelling), else tcp" — the dataflow_socket.yml
    idiom (reference examples/rust-dataflow/dataflow_socket.yml). Any
    explicit string — including ``"tcp"`` — overrides the YAML."""
    if isinstance(dataflow, Descriptor):
        descriptor = dataflow
        working_dir = Path(working_dir or Path.cwd())
    else:
        path = Path(dataflow)
        descriptor = Descriptor.read(path)
        working_dir = Path(working_dir or path.parent)
    descriptor.check(working_dir)
    if local_comm is None:  # any explicit choice wins over YAML
        local_comm = descriptor.communication.local.kind

    from dora_tpu.telemetry import install_task_dump, remove_task_dump

    loop = asyncio.get_running_loop()
    install_task_dump(loop)
    daemon = Daemon(local_comm=local_comm)
    await daemon.start()
    try:
        df = await daemon.spawn_dataflow(
            descriptor,
            working_dir=working_dir,
            local_nodes={str(n.id) for n in descriptor.nodes},
        )
        if timeout_s is not None:
            return await asyncio.wait_for(asyncio.shield(df.done), timeout_s)
        return await df.done
    finally:
        await daemon.close()
        remove_task_dump(loop)


def run_dataflow(
    dataflow: str | Path | Descriptor,
    working_dir: str | Path | None = None,
    local_comm: str | None = None,
    timeout_s: float | None = None,
) -> DataflowResult:
    return asyncio.run(
        run_dataflow_async(dataflow, working_dir, local_comm, timeout_s)
    )
