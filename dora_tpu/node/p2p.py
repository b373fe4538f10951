"""Peer-to-peer edge data plane (TPU-build extension).

The reference routes every message through the daemon; the measured
cost here is ~0.5-0.9 ms p50 per hop chain (sender control channel →
daemon pump thread → asyncio routing → receiver event channel).
This module moves the data plane of
eligible edges onto direct shared-memory channels between the two node
processes, keeping the daemon as the control plane:

* Each python node pre-creates one shmem channel pair (data + ack) per
  SENDER feeding it — grouping that sender's inputs so their relative
  order survives, exactly like the daemon's single per-receiver queue —
  and announces the names on its control channel BEFORE subscribing
  (``P2PAnnounce``): by the time any sender can learn a name, the
  channel exists, so there is no connect race.
* At barrier release the daemon pairs capable local endpoints per edge,
  excludes those edges from its own routing, and answers each sender's
  ``P2PEdgesRequest`` with the channel assignments.
* A send is one fire-and-forget futex-paced frame (~10 µs sender cost)
  — the same ``Timestamped(Input)`` the daemon would deliver; payloads
  ≥ 4 KiB still travel as shared-memory regions by name, zero-copy.
  The channel's one-outstanding-frame flow control is the only
  backpressure, so the sender never waits out the receiver's thread
  wake-ups. Drop-token acks return on the companion ack channel
  (separate because the futex channel's payload area is shared between
  its two directions), drained by a per-channel reader thread — region
  recycling flows sender←receiver without the daemon bookkeeping
  either.
* The receiver side enforces the YAML ``queue_size`` contract locally:
  each per-sender thread keeps a FIFO backlog with per-input
  drop-oldest (dropping an event releases its region via the same
  finalizer path as a consumed one) and merges into the node's event
  stream.

Timers, stdout-forwarding outputs, C/C++ clients, dynamic nodes, and
cross-machine edges keep the daemon path — eligibility is decided
per-edge by the daemon, so mixed dataflows just work. Kill switch:
``DORA_P2P=0`` (either side).
"""

from __future__ import annotations

import collections
import logging
import queue as queue_mod
import threading

from dora_tpu.analysis.lockcheck import tracked_lock
import time
import uuid
from typing import Any

from dora_tpu.message import daemon_to_node as d2n
from dora_tpu.message.common import SharedMemoryData
from dora_tpu.message.serde import decode_timestamped, encode_timestamped
from dora_tpu.native import Disconnected, ShmemChannel

logger = logging.getLogger(__name__)

#: Edge channel capacity: control frames only (metadata + region ids;
#: big payloads ride regions), but inline payloads up to the 4 KiB
#: zero-copy threshold plus metadata must fit comfortably.
EDGE_CHANNEL_CAPACITY = 1 << 20

#: How long a sender retries opening an announced channel (the server
#: exists pre-announce; retries only cover fs visibility latency).
OPEN_RETRY_S = 5.0


def ack_name(channel_name: str) -> str:
    """The companion ack channel of a data channel (receiver->sender
    drop-token returns; separate channel because the futex channel's
    payload area is shared between its two directions)."""
    return channel_name + "-a"


class _EdgeServer:
    """All inbound edges from ONE sender: a shmem channel server plus a
    FIFO backlog with per-input drop-oldest. One channel per sender —
    not per input — so the cross-input event ORDER from a given sender
    is preserved exactly as the daemon's single per-receiver queue
    preserves it (phase-marker protocols depend on this)."""

    def __init__(self, endpoint: "P2PEndpoint", sender: str,
                 queue_sizes: dict[str, int], channel: ShmemChannel,
                 ack_channel: ShmemChannel):
        self.endpoint = endpoint
        self.sender = sender
        self.queue_sizes = {k: max(1, v) for k, v in queue_sizes.items()}
        self.channel = channel
        #: acks ride a SEPARATE channel: the futex channel's payload
        #: area is shared between directions (request-reply discipline),
        #: so pushing acks on the data channel's reverse direction would
        #: clobber in-flight data frames (measured: scattered losses).
        self.ack_channel = ack_channel
        self.backlog: collections.deque = collections.deque()  # (input, ev)
        self.counts: dict[str, int] = {}
        #: last time the channel was observed EMPTY (recv timed out) —
        #: the stream-end barrier uses this to know no frame is in
        #: flight inside the channel itself.
        self.last_idle = 0.0
        self._acks: list[str] = []
        self._acks_lock = tracked_lock("node.p2p.edge_acks")
        self.thread = threading.Thread(
            target=self._run, name=f"dora-p2p-{sender}", daemon=True
        )

    # -- ack routing (called from GC finalizers, arbitrary threads) ---------

    def queue_ack(self, token: str) -> None:
        with self._acks_lock:
            self._acks.append(token)

    def take_acks(self) -> list[str]:
        with self._acks_lock:
            acks, self._acks = self._acks, []
            return acks

    # -- receive loop -------------------------------------------------------

    def _drain(self) -> None:
        events = self.endpoint.events
        first = True
        while self.backlog:
            input_id, event = self.backlog[0]
            try:
                # Block briefly on the FIRST put: when the consumer is
                # the bottleneck this hands the event over the moment a
                # queue slot frees instead of sleeping out a recv tick
                # (the 10 ms poll capped a backlogged edge at ~200
                # events/s; the sender is flow-controlled to one
                # outstanding frame either way).
                if first:
                    events._queue.put(event, timeout=0.01)
                else:
                    events._queue.put_nowait(event)
            except queue_mod.Full:
                return
            first = False
            self.backlog.popleft()
            self.counts[input_id] -= 1

    def _append(self, input_id: str, event) -> None:
        """FIFO append with the daemon's per-input drop-oldest bound."""
        self.backlog.append((input_id, event))
        count = self.counts.get(input_id, 0) + 1
        self.counts[input_id] = count
        if count > self.queue_sizes.get(input_id, 1):
            for i, (iid, _ev) in enumerate(self.backlog):
                if iid == input_id:
                    # Releasing the event fires its finalizer, which
                    # acks its drop token back through us.
                    del self.backlog[i]
                    self.counts[input_id] -= 1
                    break

    def _push_acks(self) -> None:
        """Opportunistically push accumulated acks back to the sender
        (its ack-reader thread drains them). try_send: if the previous
        push is still unconsumed, keep the acks for the next chance."""
        with self._acks_lock:
            if not self._acks:
                return
            acks = list(self._acks)
        frame = encode_timestamped(
            d2n.DropEvents(drop_tokens=acks), self.endpoint.node._clock
        )
        try:
            if self.ack_channel.try_send(frame):
                with self._acks_lock:
                    del self._acks[: len(acks)]
        except Exception:
            pass

    def _run(self) -> None:
        node = self.endpoint.node
        events = self.endpoint.events
        while not self.endpoint.closed.is_set():
            self._drain()
            self._push_acks()
            try:
                frame = self.channel.recv(timeout=0.01 if self.backlog else 0.2)
            except Disconnected:
                break
            except Exception:
                break
            if frame is None:
                self.last_idle = time.monotonic()
                continue  # tick: drain backlog / flush acks
            try:
                inner = decode_timestamped(frame, node._clock).inner
                if isinstance(inner, d2n.Input):
                    data = inner.data
                    if isinstance(data, SharedMemoryData) and data.drop_token:
                        node._register_p2p_token(data.drop_token, self)
                    event = events._convert(inner)
                    if event is not None:
                        self._append(inner.id, event)
                # NextDropEvents frames are pure ack-flush pings.
            except Exception:
                logger.exception("p2p edges from %s: bad frame", self.sender)
        # Surface any undelivered backlog before exiting (stream-end
        # barrier in EventStream waits on us via backlog_empty).
        deadline = time.monotonic() + 2.0
        while self.backlog and time.monotonic() < deadline:
            self._drain()
            time.sleep(0.005)


class P2PEndpoint:
    """Per-node p2p state: inbound edge servers + outbound assignments."""

    def __init__(self, node: Any):
        self.node = node
        self.events: Any = None  # EventStream, attached post-subscribe
        self.closed = threading.Event()
        self.servers: dict[str, _EdgeServer] = {}
        self.listeners: dict[str, str] = {}
        #: output_id -> d2n.P2POutput
        self.outbound: dict[str, Any] = {}
        self._out_channels: dict[str, ShmemChannel] = {}
        self._out_lock = tracked_lock("node.p2p.out")
        self._readers: list[threading.Thread] = []
        # One channel per SENDER (grouping that sender's inputs): the
        # descriptor knows each input's source; the announce format
        # stays {input: channel}, so inputs sharing a sender simply
        # announce the same channel name.
        for sender, inputs in self._inputs_by_sender(node).items():
            name = f"dtp-p2p-{uuid.uuid4().hex[:16]}"
            try:
                channel = ShmemChannel.create(name, EDGE_CHANNEL_CAPACITY)
                ack_channel = ShmemChannel.create(ack_name(name), 1 << 16)
            except Exception:
                logger.exception("p2p: channel create failed; edges from "
                                 "%s fall back to daemon routing", sender)
                continue
            self.servers[sender] = _EdgeServer(
                self, sender, dict(inputs), channel, ack_channel
            )
            for input_id in inputs:
                self.listeners[input_id] = name

    @staticmethod
    def _inputs_by_sender(node) -> dict[str, dict[str, int]]:
        """{sender node id: {input id: queue size}} from the descriptor
        (timer inputs and fused-internal edges stay with the daemon)."""
        from dora_tpu.core.config import UserMapping
        from dora_tpu.core.descriptor import Descriptor

        try:
            desc = Descriptor.parse(node._config.dataflow_descriptor)
            me = desc.node(node._config.node_id)
            internal = me.fused_internal_inputs()
        except Exception:
            return {}
        out: dict[str, dict[str, int]] = {}
        for input_id, inp in me.inputs.items():
            if input_id in internal:
                continue
            if isinstance(inp.mapping, UserMapping):
                out.setdefault(str(inp.mapping.source), {})[str(input_id)] \
                    = inp.queue_size
        return out

    # -- lifecycle ----------------------------------------------------------

    def start(self, events) -> None:
        """Attach the event stream and start the edge threads (call after
        the start barrier, before the first event is consumed)."""
        self.events = events
        events.pre_end = self.backlog_barrier
        for server in self.servers.values():
            server.thread.start()

    def set_outbound(self, reply: Any) -> None:
        self.outbound = dict(reply.outputs or {})

    def backlog_empty(self) -> bool:
        return all(not s.backlog for s in self.servers.values())

    def backlog_barrier(self, timeout: float = 5.0) -> None:
        """Stream-end ordering: daemon-delivered AllInputsClosed must not
        overtake p2p events still in flight. Flow control bounds the
        exposure to ONE unconsumed frame per edge (a sender's send(n)
        returns only after frame n-1 was consumed), so the barrier
        waits until every edge thread has both an empty backlog and has
        observed an EMPTY channel (an idle recv tick) since the barrier
        began — then nothing can still be queued anywhere."""
        start = time.monotonic()
        deadline = start + timeout
        while time.monotonic() < deadline:
            settled = True
            for s in self.servers.values():
                if not s.thread.is_alive():
                    continue
                if s.backlog or s.last_idle <= start:
                    settled = False
                    break
            if settled:
                return
            time.sleep(0.005)

    # -- sender side --------------------------------------------------------

    def publish(self, output_id: str, metadata, data) -> bool:
        """Publish to this output's p2p edges. Returns True when the
        caller must STILL send the daemon SendMessage (non-p2p receivers
        exist), False when fully handled."""
        out = self.outbound.get(output_id)
        if out is None:
            return True
        token = (
            data.drop_token if isinstance(data, SharedMemoryData) else None
        )
        if token is not None:
            # One ack expected per p2p receiver, plus the daemon's if it
            # still routes this output anywhere.
            self.node._set_token_refs(
                token, len(out.edges) + (1 if out.daemon_route else 0)
            )
        for edge in out.edges:
            frame = encode_timestamped(
                d2n.Input(id=edge.input_id, metadata=metadata, data=data),
                self.node._clock,
            )
            try:
                self._send(edge, frame)
            except Disconnected:
                # Receiver is gone; the daemon's failure handling will
                # stop the dataflow — account the ack we will never get.
                logger.warning("p2p edge to %s/%s disconnected",
                               edge.receiver, edge.input_id)
                if token is not None:
                    self.node._reclaim_regions([token])
        return out.daemon_route

    def _send(self, edge, frame: bytes) -> None:
        """Fire-and-forget publish: the channel's per-direction flow
        control is the only backpressure (one outstanding frame — the
        daemon SendMessage discipline), so the sender never waits out
        the receiver's thread wake-ups. Acks flow back asynchronously
        on the reverse direction, drained by a per-channel reader."""
        # _out_lock guards only the channel-table bookkeeping; the send
        # happens OUTSIDE it. Holding it across channel.send() made the
        # ack-flush path serialize behind a receiver stuck in its flow-
        # control window (lockcheck: held-across-blocking). Callers are
        # single-sender per the node.send_output contract, so the bare
        # send needs no lock of its own.
        with self._out_lock:
            channel = self._out_channels.get(edge.channel)
            if channel is None:
                channel = self._open(edge.channel)
                self._out_channels[edge.channel] = channel
                acks = self._open(ack_name(edge.channel))
                self._out_channels[ack_name(edge.channel)] = acks
                reader = threading.Thread(
                    target=self._ack_reader, args=(acks,),
                    name=f"dora-p2p-acks-{edge.receiver}", daemon=True,
                )
                reader.start()
                self._readers.append(reader)
        channel.send(frame)

    def _ack_reader(self, channel: ShmemChannel) -> None:
        while not self.closed.is_set():
            try:
                frame = channel.recv(timeout=0.5)
            except Exception:
                return
            if frame is None:
                continue
            try:
                inner = decode_timestamped(frame, self.node._clock).inner
                if isinstance(inner, d2n.DropEvents) and inner.drop_tokens:
                    self.node._reclaim_regions(inner.drop_tokens)
            except Exception:
                continue

    @staticmethod
    def _open(name: str) -> ShmemChannel:
        deadline = time.monotonic() + OPEN_RETRY_S
        while True:
            try:
                return ShmemChannel.open(name)
            except Exception:
                if time.monotonic() >= deadline:
                    raise
                time.sleep(0.01)

    def flush_acks(self) -> None:
        """Ping every outbound edge once so lingering receiver-side acks
        come home (close path: lets the region wait finish promptly —
        the acks arrive asynchronously via the readers)."""
        from dora_tpu.message import node_to_daemon as n2d

        for out in self.outbound.values():
            for edge in out.edges:
                frame = encode_timestamped(
                    n2d.NextDropEvents(), self.node._clock
                )
                try:
                    self._send(edge, frame)
                except Exception:
                    continue

    def close(self) -> None:
        if self.closed.is_set():
            return
        self.closed.set()
        for server in self.servers.values():
            try:
                server.channel.disconnect()
                server.ack_channel.disconnect()
            except Exception:
                pass
        for server in self.servers.values():
            if server.thread.ident is not None:
                server.thread.join(timeout=2)
            try:
                server.channel.close(unlink=True)
                server.ack_channel.close(unlink=True)
            except Exception:
                pass
        with self._out_lock:
            for channel in self._out_channels.values():
                try:
                    channel.disconnect()
                except Exception:
                    pass
        for reader in self._readers:
            reader.join(timeout=1)
        with self._out_lock:
            for channel in self._out_channels.values():
                try:
                    channel.close(unlink=False)
                except Exception:
                    pass
            self._out_channels.clear()
