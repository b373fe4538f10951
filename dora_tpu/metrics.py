"""Dataflow metrics plane: counters + latency histograms, snapshot/merge.

The daemon keeps one :class:`DataflowMetrics` per dataflow and feeds it
from the routing hot path (``daemon/core.py``), the per-node queues
(``daemon/queues.py``), and the wire fast path (``message/fastroute.py``):

* per-(sender, output) routed message/byte counters,
* per-(node, input) drop-oldest counters and live queue depth,
* fastroute hit/fallback counters (wire-splice vs reflective route),
* send→deliver latency histograms computed from the HLC timestamps every
  ``Timestamped`` frame already carries (physical ns, same machine, so
  the difference is a real wall-clock latency including queue wait).

Everything is plain dicts and ints so the hot-path cost is one dict get
and one add; ``snapshot()`` produces a JSON-able dict the control plane
ships daemon → coordinator → CLI, and :func:`merge_snapshots` aggregates
across machines (histogram bucket counts add; percentiles recompute).
"""

from __future__ import annotations

from typing import Any

from dora_tpu import telemetry

#: Histogram buckets are powers of two in microseconds: bucket ``i``
#: holds values in [2^(i-1), 2^i) µs; bucket 0 holds < 1 µs. 27 buckets
#: span 1 µs .. ~67 s, which covers everything from a shmem splice to a
#: wedged queue.
HISTOGRAM_BUCKETS = 27


class Histogram:
    """Fixed-bucket log2 latency histogram (microseconds)."""

    __slots__ = ("counts", "count", "sum_us")

    def __init__(self):
        self.counts = [0] * HISTOGRAM_BUCKETS
        self.count = 0
        self.sum_us = 0.0

    def observe(self, value_us: float) -> None:
        if value_us < 0:
            value_us = 0.0  # HLC logical ticks can run ahead of wall time
        bucket = min(int(value_us).bit_length(), HISTOGRAM_BUCKETS - 1)
        self.counts[bucket] += 1
        self.count += 1
        self.sum_us += value_us

    def snapshot(self) -> dict:
        out = {
            "count": self.count,
            "sum_us": round(self.sum_us, 1),
            "counts": list(self.counts),
        }
        for p in (50, 90, 99):
            out[f"p{p}_us"] = percentile_from_counts(self.counts, p)
        return out


def bucket_upper_us(i: int) -> float:
    """Upper bound of bucket ``i`` in µs (reported percentile value)."""
    return float(1 << i)


def percentile_from_counts(counts: list[int], p: float) -> float | None:
    """The p-th percentile latency from histogram bucket counts — the
    upper bound of the bucket the rank falls in (pessimistic by at most
    one octave, which is the histogram's stated resolution)."""
    total = sum(counts)
    if total == 0:
        return None
    rank = total * p / 100.0
    seen = 0
    for i, c in enumerate(counts):
        seen += c
        if seen >= rank:
            return bucket_upper_us(i)
    return bucket_upper_us(len(counts) - 1)


class DataflowMetrics:
    """Hot-path counters for one dataflow (daemon side)."""

    __slots__ = (
        "links",
        "drops",
        "latency",
        "fastroute_hits",
        "fastroute_fallbacks",
        "respawns",
        "replayed_inputs",
    )

    def __init__(self):
        #: (sender, output) -> [msgs, bytes]
        self.links: dict[tuple[str, str], list] = {}
        #: (node, input) -> dropped-oldest count
        self.drops: dict[tuple[str, str], int] = {}
        #: (node, input) -> send→deliver Histogram
        self.latency: dict[tuple[str, str], Histogram] = {}
        self.fastroute_hits = 0
        self.fastroute_fallbacks = 0
        #: node -> times the daemon respawned it (restart policy)
        self.respawns: dict[str, int] = {}
        #: node -> un-acked inputs requeued to it across respawns
        self.replayed_inputs: dict[str, int] = {}

    # -- hot-path feeders ---------------------------------------------------

    def count_link(self, sender: str, output: str, nbytes: int) -> None:
        entry = self.links.get((sender, output))
        if entry is None:
            entry = self.links[(sender, output)] = [0, 0]
        entry[0] += 1
        entry[1] += nbytes

    def count_drop(self, node: str, input_id: str) -> None:
        key = (node, input_id)
        self.drops[key] = self.drops.get(key, 0) + 1

    def observe_latency(self, node: str, input_id: str, us: float) -> None:
        hist = self.latency.get((node, input_id))
        if hist is None:
            hist = self.latency[(node, input_id)] = Histogram()
        hist.observe(us)

    def count_respawn(self, node: str) -> None:
        self.respawns[node] = self.respawns.get(node, 0) + 1

    def count_replayed(self, node: str, n: int) -> None:
        if n > 0:
            self.replayed_inputs[node] = self.replayed_inputs.get(node, 0) + n

    # -- export -------------------------------------------------------------

    def snapshot(self, queue_depths: dict[str, int] | None = None) -> dict:
        hits, falls = self.fastroute_hits, self.fastroute_fallbacks
        routed = hits + falls
        out = {
            "links": {
                f"{s}/{o}": {"msgs": v[0], "bytes": v[1]}
                for (s, o), v in self.links.items()
            },
            "drops": {f"{n}/{i}": c for (n, i), c in self.drops.items()},
            "queue_depth": dict(queue_depths or {}),
            "fastroute": {
                "hits": hits,
                "fallbacks": falls,
                "hit_ratio": round(hits / routed, 4) if routed else None,
            },
            "latency_us": {
                f"{n}/{i}": h.snapshot() for (n, i), h in self.latency.items()
            },
        }
        if self.respawns or self.replayed_inputs:
            out["recovery"] = {
                "respawns": dict(self.respawns),
                "replayed_inputs": dict(self.replayed_inputs),
            }
        return out


class ServingMetrics:
    """Node-side serving counters (the LLM server's view of its engine).

    Lives in the serving node's process, shipped to its daemon as a
    fire-and-forget ``n2d.ReportServing`` snapshot (same plane as
    ReportTrace) and surfaced through the coordinator's metrics fan-out
    next to the dataflow counters — ``dora-tpu metrics [--watch]`` shows
    slots, pages, backlog, decode tokens/s and the TTFT histogram.

    Counters are cumulative (the CLI derives rates from consecutive
    snapshots in watch mode); gauges are set just before ``snapshot``.
    """

    __slots__ = (
        "ttft", "dispatch_gap", "emit", "fetch_latency", "backlog_wait",
        "grant_pages", "decode_tokens", "emit_messages", "emit_overlapped",
        "prefill_chunks", "chunks_ahead",
        "requests", "rejected", "slots_active", "slots_total",
        "free_pages", "total_pages", "used_pages", "peak_used_pages",
        "largest_contig_free", "backlog_depth", "host_dispatches",
        "host_fetches", "compiles", "engine",
        "checkpoints", "last_checkpoint_unix", "restored_streams",
        "migrated_out", "migrated_in",
        "spec_drafted", "spec_accepted", "spec_accept_len",
        "shed", "preempted", "resumed", "qos_depth",
        "autotune_k", "retunes",
        "prefix_hits", "prefix_misses", "prefix_hit_tokens",
        "prefix_cached_pages", "prefix_shared_pages",
        "prefix_cow_copies", "prefix_evictions",
        "prefix_evict_calls", "prefix_evict_visits",
        "device_compute_ns", "host_dispatch_ns", "device_fetch_ns",
        "dispatched_flops", "useful_flops",
        "hbm_used_bytes", "hbm_limit_bytes", "hbm_peak_bytes",
        "mfu", "device_busy_fraction",
        "kv_dtype", "kv_pool_bytes", "kv_quant_err",
        "lora_resident", "lora_max_resident", "lora_resident_bytes",
        "lora_loads", "lora_evictions", "adapter_streams",
        "adapter_stalls", "model", "capture_counters", "phases",
        "stages",
    )

    def __init__(self, engine: str = "paged"):
        self.ttft = Histogram()
        #: host time from one window's tokens reaching the host
        #: (``collect()`` returning) to the launch of the next device
        #: work, while decode is active — the time the device sits idle
        #: for in every period, which the multi-step window amortizes
        #: (each gap buys up to K tokens, not 1). Sending tokens counts
        #: only where no window ran beside it (see ``emit_overlapped``).
        #: Split from fetch_latency on purpose: the gap is pure
        #: host/scheduler time, the fetch is the blocking device->host
        #: transfer — a slow device->host path moves the fetch track, a
        #: host-side regression moves the gap track.
        self.dispatch_gap = Histogram()
        #: host time of one dispatch's flush (the previous window's
        #: tokens and the dispatch's first tokens, one message a
        #: stream), observed only where a window ran beside it: the
        #: emit side of a period's max(device, emit). The device waits
        #: for the host wherever this outlasts the window.
        self.emit = Histogram()
        #: the serving loop's phases (telemetry.LOOP_PHASES), one
        #: histogram a row of that table, fed by ServingTracer as each
        #: phase is left: what ``dispatch_gap`` and ``emit`` are made
        #: of. Top-level snapshot keys ``phase_<phase>_us``; read by the
        #: benchmark and the server's exit line, and deliberately not by
        #: prom / alerts / metrics_history / the CLI views.
        self.phases = {name: Histogram() for name in telemetry.LOOP_PHASES}
        #: a request's stages up to its first message sent
        #: (telemetry.REQUEST_STAGES), one histogram a row that this
        #: process records, fed by ServingTracer: what ``ttft`` is made
        #: of. Top-level snapshot keys ``stage_<stage>_us``, read as the
        #: phases are and by nothing else.
        self.stages = {
            name: Histogram()
            for name in telemetry.stages_observed("arrival")
            + telemetry.stages_observed("first_send")
        }
        #: blocking device->host fetch durations (the sync points:
        #: chunk greedy reads, the [B, K+1] window matrix), observed by
        #: the engine via its ``serving_metrics`` hook
        self.fetch_latency = Histogram()
        #: time requests spent parked in the admission backlog before
        #: their slot/page grant (AdmissionQueue on_admit)
        self.backlog_wait = Histogram()
        #: per-admission page-grant size -> count (exact — grant sizes
        #: are small ints; fed by the paged engine at submit)
        self.grant_pages: dict[int, int] = {}
        self.decode_tokens = 0
        #: ``response`` messages that carried those tokens: a flush
        #: sends one a stream, holding every token it holds for it
        #: (``decode_tokens`` / ``emit_messages`` = tokens a message)
        self.emit_messages = 0
        #: of ``decode_tokens``, those sent while a decode window was
        #: in flight on the device (the serving loop emits window N
        #: beside window N+1); the rest were sent with the device
        #: waiting — a share well under 1 at steady load means the
        #: pipelining does not engage
        self.emit_overlapped = 0
        self.prefill_chunks = 0
        #: of ``prefill_chunks``, those the engine queued behind a
        #: running window, a period ahead (``PagedBatchEngine.ahead``):
        #: the host's work after ``collect()`` runs beside such a chunk
        self.chunks_ahead = 0
        self.requests = 0
        self.rejected = 0
        self.slots_active = 0
        self.slots_total = 0
        self.free_pages = 0
        self.total_pages = 0
        self.used_pages = 0
        #: high-water mark of pages in use (allocator-tracked)
        self.peak_used_pages = 0
        #: longest run of physically-adjacent free pages — the
        #: fragmentation gauge (how large a contiguous grant could be)
        self.largest_contig_free = 0
        self.backlog_depth = 0
        #: device program launches / device->host fetches (engine
        #: counters, set just before snapshot like the gauges)
        self.host_dispatches = 0
        self.host_fetches = 0
        #: XLA compiles observed process-wide (telemetry.compile_count,
        #: runtime listener) — a nonzero delta at steady state is a
        #: recompile regression, now visible outside pytest
        self.compiles = 0
        self.engine = engine
        #: serving-state checkpoints written (DORA_CHECKPOINT_EVERY /
        #: SIGTERM), and the wall time of the last one — snapshot()
        #: derives checkpoint_age_s from it so the staleness of the
        #: recovery point is visible in `dora-tpu metrics`
        self.checkpoints = 0
        self.last_checkpoint_unix = 0.0
        #: streams resumed mid-generation from a checkpoint on respawn
        self.restored_streams = 0
        #: live streams drained to / admitted from a migration handoff
        self.migrated_out = 0
        self.migrated_in = 0
        #: prompt-lookup speculation (paged engine, DORA_SPEC_K):
        #: drafts proposed vs drafts the verification pass accepted —
        #: the acceptance rate is the lever behind tokens_per_dispatch
        self.spec_drafted = 0
        self.spec_accepted = 0
        #: tokens emitted per verification pass (accepted + the bonus
        #: token, 1..spec_k+1) as a log2 histogram — the accepted-length
        #: distribution, reusing the octave buckets (values are token
        #: counts here, not µs)
        self.spec_accept_len = Histogram()
        #: traffic shaping (QoS): requests shed on overload (bounded
        #: class depth or queue-wait deadline -> retriable "overloaded"
        #: chunk), streams evicted by page preemption, and preempted
        #: streams re-admitted (recompute-on-resume)
        self.shed = 0
        self.preempted = 0
        self.resumed = 0
        #: per-class admission-queue depth gauge (set before snapshot)
        self.qos_depth: dict[str, int] = {}
        #: live fused-window K (gauge, 0 before the first report) and
        #: autotuner retunes applied
        self.autotune_k = 0
        self.retunes = 0
        #: shared-prefix KV cache (paged engine, DORA_PREFIX_CACHE):
        #: admission lookups that mapped cached pages (hits) vs cold
        #: prefills (misses), tokens served from cache, pages the radix
        #: cache holds / currently mapped shared into live streams
        #: (gauges), copy-on-write boundary pages re-materialized, and
        #: cached pages evicted back to the pool under admission
        #: pressure
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_hit_tokens = 0
        self.prefix_cached_pages = 0
        self.prefix_shared_pages = 0
        self.prefix_cow_copies = 0
        self.prefix_evictions = 0
        #: ``PrefixCache.evict`` calls that freed a page and the nodes
        #: they looked at: visits over evictions is what a freed page
        #: costs the admission that waits for it
        self.prefix_evict_calls = 0
        self.prefix_evict_visits = 0
        #: device utilization plane (dora_tpu.profiling, DORA_DEVICE_MONITOR):
        #: cumulative window/chunk wall time attributed by the engine to
        #: host dispatch vs device compute vs the device->host fetch (ns
        #: counters fed on the step path), plus the FLOPs ledger — work
        #: dispatched to the device vs work behind EMITTED tokens (the
        #: two differ by speculation's rejected tails)
        self.device_compute_ns = 0
        self.host_dispatch_ns = 0
        self.device_fetch_ns = 0
        self.dispatched_flops = 0
        self.useful_flops = 0
        #: HBM gauges sampled off device.memory_stats() just before
        #: snapshot; None when the backend exposes no allocator stats
        #: (CPU) — the CLI renders dashes, prom exports 0
        self.hbm_used_bytes: int | None = None
        self.hbm_limit_bytes: int | None = None
        self.hbm_peak_bytes: int | None = None
        #: model FLOPs utilization over the last report interval
        #: (useful_flops delta / wall / peak; None without a known peak)
        #: and the fraction of wall time the device was computing
        self.mfu: float | None = None
        self.device_busy_fraction: float | None = None
        #: quantized-serving plane: KV pool number format ("fp" or
        #: "int8"), total pool HBM bytes (values + scale planes), and
        #: the per-page quantization-error gauge (mean relative
        #: quantization step over sampled allocated pages —
        #: PagedBatchEngine.kv_quant_error; None on fp pools)
        self.kv_dtype = "fp"
        self.kv_pool_bytes: int | None = None
        self.kv_quant_err: float | None = None
        #: multi-tenant LoRA plane (paged engine, DORA_LORA_DIR):
        #: resident adapters vs pool capacity, their HBM bytes, and the
        #: cumulative load/eviction churn (a high eviction rate against
        #: a small resident pool is the swap-thrash signature — see
        #: KNOWN_ISSUES round 19). ``adapter_streams`` is a dict gauge:
        #: live streams pinned per resident adapter (tenant name keys,
        #: the qos_depth idiom).
        self.lora_resident = 0
        self.lora_max_resident = 0
        self.lora_resident_bytes = 0
        self.lora_loads = 0
        self.lora_evictions = 0
        self.adapter_streams: dict[str, int] = {}
        #: backlog entries shed (or admitted late) because the N+1-th
        #: tenant's adapter could not evict — every resident adapter
        #: pinned by a live stream. Split from plain queue overload so
        #: the two are distinguishable (KNOWN_ISSUES round 19); the
        #: wire chunk carries the same attribution as
        #: ``stall_reason="adapter_residency"``.
        self.adapter_stalls = 0
        #: the model module's own counters, as the engine's
        #: ``model_counters()`` returns them at the 1 Hz report (a dict,
        #: merged into the snapshot and into the node's exit line; empty
        #: for models that have none). models/moe.report, as kimi_k2 adds to it:
        #: ``moe_tokens`` — rows routed, summed over expert layers (a
        #: chunk's right padding and frozen decode rows are not
        #: counted); ``moe_local_pairs`` — (row, expert) pairs that
        #: landed on an expert this rank holds (expect ``top_k * held /
        #: n_experts`` of ``moe_tokens``: 0.25 for 12 of 384 at top-8);
        #: ``moe_expert_tokens`` — those pairs per held expert, summed
        #: over layers (the load skew); ``moe_experts_touched`` — mean
        #: distinct held experts a layer a decode tick had to read;
        #: ``latent_rows_in_use`` / ``latent_pool_bytes`` — cache rows
        #: held by streams and the prefix cache, and the pool's size.
        #: Device counters that the window and the chunk program take
        #: and give back beside the pools, read after ``collect()``: no
        #: extra device->host fetch per window.
        self.model: dict = {}
        #: the same counters as they stood when a profiler capture
        #: started and stopped (``{"start": {...}, "stop": {...}}``), so
        #: that a reader of the capture counts what the captured ticks
        #: did and not what a longer stretch of serving did.
        self.capture_counters: dict = {}

    def snapshot(self) -> dict:
        import time

        return {
            "engine": self.engine,
            "requests": self.requests,
            "rejected": self.rejected,
            "decode_tokens": self.decode_tokens,
            "emit_messages": self.emit_messages,
            "emit_overlapped": self.emit_overlapped,
            "prefill_chunks": self.prefill_chunks,
            "chunks_ahead": self.chunks_ahead,
            "slots_active": self.slots_active,
            "slots_total": self.slots_total,
            "free_pages": self.free_pages,
            "total_pages": self.total_pages,
            "used_pages": self.used_pages,
            "peak_used_pages": self.peak_used_pages,
            "largest_contig_free": self.largest_contig_free,
            "backlog_depth": self.backlog_depth,
            "host_dispatches": self.host_dispatches,
            "host_fetches": self.host_fetches,
            "compiles": self.compiles,
            "tokens_per_dispatch": (
                round(self.decode_tokens / self.host_dispatches, 2)
                if self.host_dispatches
                else None
            ),
            "grant_pages": {
                str(k): v for k, v in sorted(self.grant_pages.items())
            },
            "ttft_us": self.ttft.snapshot(),
            "dispatch_gap_us": self.dispatch_gap.snapshot(),
            "emit_us": self.emit.snapshot(),
            "fetch_us": self.fetch_latency.snapshot(),
            "backlog_wait_us": self.backlog_wait.snapshot(),
            "checkpoints": self.checkpoints,
            "checkpoint_age_s": (
                round(time.time() - self.last_checkpoint_unix, 3)
                if self.last_checkpoint_unix
                else None
            ),
            "restored_streams": self.restored_streams,
            "migrated_out": self.migrated_out,
            "migrated_in": self.migrated_in,
            "spec_drafted": self.spec_drafted,
            "spec_accepted": self.spec_accepted,
            "spec_acceptance": (
                round(self.spec_accepted / self.spec_drafted, 4)
                if self.spec_drafted
                else None
            ),
            "spec_accept_len": self.spec_accept_len.snapshot(),
            "shed": self.shed,
            "preempted": self.preempted,
            "resumed": self.resumed,
            "qos_depth": dict(self.qos_depth),
            "autotune_k": self.autotune_k,
            "retunes": self.retunes,
            "prefix_hits": self.prefix_hits,
            "prefix_misses": self.prefix_misses,
            "prefix_hit_rate": (
                round(
                    self.prefix_hits
                    / (self.prefix_hits + self.prefix_misses),
                    4,
                )
                if (self.prefix_hits + self.prefix_misses)
                else None
            ),
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prefix_cached_pages": self.prefix_cached_pages,
            "prefix_shared_pages": self.prefix_shared_pages,
            "prefix_cow_copies": self.prefix_cow_copies,
            "prefix_evictions": self.prefix_evictions,
            "prefix_evict_calls": self.prefix_evict_calls,
            "prefix_evict_visits": self.prefix_evict_visits,
            "device_compute_ns": self.device_compute_ns,
            "host_dispatch_ns": self.host_dispatch_ns,
            "device_fetch_ns": self.device_fetch_ns,
            "dispatched_flops": self.dispatched_flops,
            "useful_flops": self.useful_flops,
            "hbm_used_bytes": self.hbm_used_bytes,
            "hbm_limit_bytes": self.hbm_limit_bytes,
            "hbm_peak_bytes": self.hbm_peak_bytes,
            "mfu": self.mfu,
            "device_busy_fraction": self.device_busy_fraction,
            "kv_dtype": self.kv_dtype,
            "kv_pool_bytes": self.kv_pool_bytes,
            "kv_quant_err": self.kv_quant_err,
            "lora_resident": self.lora_resident,
            "lora_max_resident": self.lora_max_resident,
            "lora_resident_bytes": self.lora_resident_bytes,
            "lora_loads": self.lora_loads,
            "lora_evictions": self.lora_evictions,
            "adapter_streams": dict(self.adapter_streams),
            "adapter_stalls": self.adapter_stalls,
            "capture_counters": dict(self.capture_counters),
            **self.phase_snapshots(),
            **self.stage_snapshots(),
            **self.model,
        }

    def phase_snapshots(self) -> dict:
        return {
            telemetry.phase_histogram_key(name): h.snapshot()
            for name, h in self.phases.items()
        }

    def stage_snapshots(self) -> dict:
        return {
            telemetry.stage_histogram_key(name): h.snapshot()
            for name, h in self.stages.items()
        }

    def first_token_reads(self) -> dict:
        """How often a prompt's first token was read beside the window
        its dispatch had launched (``deferred``: phase
        ``first_token_read``) and how often before the launch, holding
        it (``blocking``: ``first_token_wait`` — speculation, or no
        window followed). A share of 1.0 is the mechanism engaged:
        ``phase_first_token_wait_us`` then counts nothing, which is not
        a reader gone blind."""
        deferred = self.phases["first_token_read"].count
        blocking = self.phases["first_token_wait"].count
        total = deferred + blocking
        return {
            "deferred": deferred, "blocking": blocking,
            "deferred_share": deferred / total if total else None,
        }

    def chunks_ahead_share(self) -> float | None:
        """The share of prefill chunks that went to the device behind a
        running window (``PagedBatchEngine.ahead``): near 1 where prompts
        queue, near 0 where the queue is empty at nearly every launch."""
        if not self.prefill_chunks:
            return None
        return self.chunks_ahead / self.prefill_chunks


def merge_snapshots(snapshots: list[dict]) -> dict:
    """Aggregate per-daemon snapshots into one cluster view (coordinator).

    Counters add; queue depths union (each input queue lives on exactly
    one machine); histogram bucket counts add and percentiles recompute
    from the merged buckets."""
    links: dict[str, dict] = {}
    drops: dict[str, int] = {}
    depth: dict[str, int] = {}
    hits = falls = 0
    lat_counts: dict[str, list[int]] = {}
    lat_sum: dict[str, float] = {}
    serving: dict[str, dict] = {}
    respawns: dict[str, int] = {}
    replayed: dict[str, int] = {}
    slo: dict[str, dict] = {}
    logs: dict[str, dict] = {}
    trace_drops: dict[str, int] = {}
    alert_statuses: list[dict] = []
    for snap in snapshots:
        if not snap:
            continue
        # Each serving node lives on exactly one machine: union. Same
        # for the SLO burn block — objectives attach to a node, and the
        # node's daemon evaluates them against its own history ring —
        # and the per-node log counters. Alert engines run per daemon;
        # their statuses merge instance-wise (dora_tpu.alerts).
        serving.update(snap.get("serving", {}))
        slo.update(snap.get("slo", {}))
        logs.update(snap.get("logs", {}))
        for node, c in (snap.get("trace") or {}).get("drops", {}).items():
            trace_drops[node] = trace_drops.get(node, 0) + c
        if snap.get("alerts"):
            alert_statuses.append(snap["alerts"])
        recovery = snap.get("recovery") or {}
        for key, c in recovery.get("respawns", {}).items():
            respawns[key] = respawns.get(key, 0) + c
        for key, c in recovery.get("replayed_inputs", {}).items():
            replayed[key] = replayed.get(key, 0) + c
        for key, v in snap.get("links", {}).items():
            entry = links.setdefault(key, {"msgs": 0, "bytes": 0})
            entry["msgs"] += v.get("msgs", 0)
            entry["bytes"] += v.get("bytes", 0)
        for key, c in snap.get("drops", {}).items():
            drops[key] = drops.get(key, 0) + c
        depth.update(snap.get("queue_depth", {}))
        fr = snap.get("fastroute", {})
        hits += fr.get("hits", 0)
        falls += fr.get("fallbacks", 0)
        for key, h in snap.get("latency_us", {}).items():
            counts = lat_counts.setdefault(key, [0] * HISTOGRAM_BUCKETS)
            for i, c in enumerate(h.get("counts", [])[:HISTOGRAM_BUCKETS]):
                counts[i] += c
            lat_sum[key] = lat_sum.get(key, 0.0) + h.get("sum_us", 0.0)
    routed = hits + falls
    latency = {}
    for key, counts in lat_counts.items():
        entry = {
            "count": sum(counts),
            "sum_us": round(lat_sum[key], 1),
            "counts": counts,
        }
        for p in (50, 90, 99):
            entry[f"p{p}_us"] = percentile_from_counts(counts, p)
        latency[key] = entry
    out = {
        "links": links,
        "drops": drops,
        "queue_depth": depth,
        "fastroute": {
            "hits": hits,
            "fallbacks": falls,
            "hit_ratio": round(hits / routed, 4) if routed else None,
        },
        "latency_us": latency,
    }
    if serving:
        out["serving"] = serving
    if slo:
        out["slo"] = slo
    if logs:
        out["logs"] = logs
    if trace_drops:
        out["trace"] = {"drops": trace_drops}
    if alert_statuses:
        from dora_tpu.alerts import merge_alert_status

        out["alerts"] = merge_alert_status(alert_statuses)
    if respawns or replayed:
        out["recovery"] = {
            "respawns": respawns,
            "replayed_inputs": replayed,
        }
    return out
