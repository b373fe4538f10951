"""Fleet state plane: per-replica engine digests and placement scoring.

The ROADMAP's multi-replica router needs to answer "which replica should
serve this prompt?" without inspecting any data-plane internals. This
module is the observability half of that answer — replicas *export*
state, the control plane aggregates it, and a pure function ranks
candidates:

* every serving engine publishes an ``EngineStateDigest``
  (message/common.py) on the ``DORA_FLEET_DIGEST_S`` cadence — a
  bounded radix-cache digest (top-N cached prefixes as incremental
  ``(hash_chain, token_len, pages)`` tuples, see
  models/prefix_cache.py), live page/HBM occupancy, the ``fits()``-
  derived free-stream capacity, the resident adapter set, and a config
  fingerprint that makes interchangeable replicas comparable;
* the plane mirrors the metrics plane wire-for-wire:
  ``n2d.ReportEngineState`` (fire-and-forget) -> daemon keeps
  latest-per-node with a receive stamp -> ``cm.QueryFleet`` fans out
  ``FleetRequest`` per machine and merges the per-daemon snapshots with
  :func:`merge_fleet_snapshots` (HLC-offset alignment, exactly like
  metrics_history);
* :func:`score_placement` is the deterministic placement function the
  future router calls — longest cached prefix wins, occupancy breaks
  ties, and a digest older than the staleness bound is discounted
  toward zero (a stale cache claim is a guess, not a fact).

Staleness bound: placement decisions can lag true cache state by up to
one publish cadence (see KNOWN_ISSUES round 21) — the discount makes
that lag degrade placement *quality*, never correctness, because a
mis-placed request only re-prefills what a hit would have skipped.
"""

from __future__ import annotations

import hashlib
import os
import time
from typing import Any

from dora_tpu.message.common import EngineStateDigest
from dora_tpu.models.prefix_cache import prompt_hash_chain

#: publish cadence in seconds; 0 disables the exporter entirely
DIGEST_INTERVAL_ENV = "DORA_FLEET_DIGEST_S"
DEFAULT_DIGEST_INTERVAL_S = 2.0
#: cached prefixes shipped per digest (bound the wire, not the tree)
TOP_PREFIXES_ENV = "DORA_FLEET_TOP_PREFIXES"
DEFAULT_TOP_PREFIXES = 32
#: a digest older than STALE_FACTOR cadences scores as no information
#: (and trips the `fleet-digest-stale` default alert rule)
STALE_FACTOR = 3.0


def digest_interval_s() -> float:
    try:
        return float(
            os.environ.get(DIGEST_INTERVAL_ENV, DEFAULT_DIGEST_INTERVAL_S)
        )
    except ValueError:
        return DEFAULT_DIGEST_INTERVAL_S


def digest_top_n() -> int:
    try:
        return int(os.environ.get(TOP_PREFIXES_ENV, DEFAULT_TOP_PREFIXES))
    except ValueError:
        return DEFAULT_TOP_PREFIXES


def stale_after_s(interval_s: float | None = None) -> float:
    """Age past which a digest carries no placement signal (and the
    default alert pack considers the exporter wedged)."""
    base = digest_interval_s() if interval_s is None else interval_s
    return STALE_FACTOR * base


def weight_bits_from_env() -> int:
    """Weight precision of the serving process, from the same env knobs
    the engine builders read (int4 wins when both are set, matching the
    builder's precedence)."""
    if os.environ.get("DORA_INT4_DECODE", "0") == "1":
        return 4
    if os.environ.get("DORA_INT8_DECODE", "0") == "1":
        return 8
    return 16


def model_id_from_env() -> str:
    ckpt = os.environ.get("DORA_HF_CHECKPOINT", "")
    return os.path.basename(ckpt.rstrip("/")) or "stub"


def config_fingerprint(*, model_id: str, window: int, spec_k: int,
                       kv_dtype: str, weight_bits: int,
                       page_size: int) -> str:
    """Replicas with equal fingerprints are interchangeable targets:
    same model, same decode window K, same speculation width, same KV
    dtype / weight precision, same page geometry. Deterministic across
    processes (blake2b, never the salted builtin hash)."""
    h = hashlib.blake2b(digest_size=8)
    h.update(
        f"{model_id}|K={window}|spec={spec_k}|kv={kv_dtype}"
        f"|w={weight_bits}|ps={page_size}".encode()
    )
    return h.hexdigest()


# ---------------------------------------------------------------------------
# digest construction (replica side)
# ---------------------------------------------------------------------------


def free_stream_capacity(engine, *, prompt_len: int | None = None,
                         max_new: int = 16) -> int:
    """Streams the engine could admit RIGHT NOW, derived from the same
    ``fits``/``pages_needed`` math admission uses: free slots capped by
    the pages a typical stream (one prefill chunk + ``max_new`` decode
    rows) would claim from the free pool plus evictable cached pages.
    Conservative by construction — a router acting on it may under-fill
    a replica, never overload one."""
    free_slots = int(getattr(engine, "free_slots", 0))
    if prompt_len is None:
        prompt_len = int(getattr(engine, "chunk", 0)) or 1
    if free_slots == 0 or not engine.fits(prompt_len, max_new):
        return 0
    avail = engine.free_pages
    cache = getattr(engine, "prefix_cache", None)
    if cache is not None:
        avail += cache.evictable_pages()
    per_stream = max(1, engine.pages_needed(prompt_len, max_new))
    return min(free_slots, avail // per_stream)


def build_digest(
    engine,
    *,
    model_id: str | None = None,
    seq: int = 0,
    top_n: int | None = None,
    hbm_used_bytes: int = 0,
    hbm_limit_bytes: int = 0,
    unix_ts: float | None = None,
) -> EngineStateDigest:
    """Snapshot one engine into the wire digest. Pure reads off the
    scheduler thread's own state — bounded work (top-N walk of the
    radix tree), no device sync, so publishing on a cadence stays off
    the decode critical path."""
    if model_id is None:
        model_id = model_id_from_env()
    window = int(getattr(engine, "window", 0) or 0)
    spec_k = int(getattr(engine, "spec_k", 0) or 0)
    kv_dtype = str(getattr(engine, "kv_dtype", "fp") or "fp")
    weight_bits = weight_bits_from_env()
    page_size = int(getattr(engine, "page_size", 0) or 0)
    alloc = getattr(engine, "allocator", None)
    if alloc is not None:
        # page 0 is the allocator's reserved null page — mirror the
        # metrics plane's total_pages convention.
        total_pages = alloc.num_pages - 1
        used_pages = alloc.in_use
        free_pages = alloc.free_pages
    else:
        total_pages = used_pages = free_pages = 0
    cache = getattr(engine, "prefix_cache", None)
    if cache is not None:
        prefixes = [
            [chain, token_len, pages]
            for chain, token_len, pages in cache.digest(
                digest_top_n() if top_n is None else top_n
            )
        ]
        prefix_pages = cache.size
    else:
        prefixes = []
        prefix_pages = 0
    lora = getattr(engine, "lora", None)
    adapters = (
        sorted(lora.streams_by_adapter()) if lora is not None else []
    )
    return EngineStateDigest(
        model_id=model_id,
        fingerprint=config_fingerprint(
            model_id=model_id, window=window, spec_k=spec_k,
            kv_dtype=kv_dtype, weight_bits=weight_bits, page_size=page_size,
        ),
        page_size=page_size,
        window=window,
        spec_k=spec_k,
        kv_dtype=kv_dtype,
        weight_bits=weight_bits,
        max_slots=int(getattr(engine, "max_slots", 0) or 0),
        free_streams=free_stream_capacity(engine),
        used_pages=used_pages,
        free_pages=free_pages,
        total_pages=total_pages,
        prefix_pages=prefix_pages,
        hbm_used_bytes=int(hbm_used_bytes or 0),
        hbm_limit_bytes=int(hbm_limit_bytes or 0),
        adapters=adapters,
        prefixes=prefixes,
        seq=seq,
        unix_ts=time.time() if unix_ts is None else unix_ts,
    )


class DigestPublisher:
    """Owns one serving node's publish cadence: ``tick(now)`` from the
    serving loop's per-second report path; publishes (fire-and-forget)
    when ``DORA_FLEET_DIGEST_S`` elapsed since the last digest. A
    cadence of 0 disables the plane — the A/B bench's "off" arm."""

    def __init__(self, node, engine, *, model_id: str | None = None,
                 interval_s: float | None = None, tracer=None,
                 hbm=None, clock=time.monotonic):
        self.node = node
        self.engine = engine
        self.model_id = model_id
        self.interval_s = (
            digest_interval_s() if interval_s is None else interval_s
        )
        self.tracer = tracer
        #: optional () -> (used_bytes, limit_bytes) from the device monitor
        self.hbm = hbm
        self.clock = clock
        self.seq = 0
        self._last: float | None = None
        self.enabled = (
            self.interval_s > 0 and hasattr(node, "report_engine_state")
        )

    def tick(self, now: float | None = None) -> bool:
        if not self.enabled:
            return False
        now = self.clock() if now is None else now
        if self._last is not None and now - self._last < self.interval_s:
            return False
        self._last = now
        self.seq += 1
        used = limit = 0
        if self.hbm is not None:
            try:
                used, limit = self.hbm()
            except Exception:
                used = limit = 0
        digest = build_digest(
            self.engine, model_id=self.model_id, seq=self.seq,
            hbm_used_bytes=used, hbm_limit_bytes=limit,
        )
        try:
            self.node.report_engine_state(digest)
        except Exception:
            return False  # fleet state is best-effort, like metrics
        if self.tracer is not None:
            self.tracer.instant(
                "fleet_digest", "(engine)",
                f"seq={self.seq} prefixes={len(digest.prefixes)} "
                f"free_streams={digest.free_streams}",
            )
        return True


# ---------------------------------------------------------------------------
# daemon side
# ---------------------------------------------------------------------------


def digest_as_dict(digest) -> dict[str, Any]:
    """The wire dataclass as the plain dict the daemon stores and the
    snapshot/merge plumbing ships (control-plane payloads are dicts so
    old CLIs tolerate new fields)."""
    import dataclasses

    return dataclasses.asdict(digest)


def fleet_gauges(digest: dict, age_s: float) -> dict[str, Any]:
    """The per-replica gauge block spliced into the daemon's metrics
    snapshot (``snap["fleet"][node]``) — what the history ring flattens
    to ``fleet:<node>:*`` series, the alert pack watches, and prom
    exports as ``dora_fleet_*``."""
    total = int(digest.get("total_pages", 0) or 0)
    used = int(digest.get("used_pages", 0) or 0)
    return {
        "digest_age_s": round(max(0.0, age_s), 3),
        "free_streams": int(digest.get("free_streams", 0) or 0),
        "used_pages": used,
        "total_pages": total,
        "occupancy": round(used / total, 4) if total else 0.0,
        "prefix_pages": int(digest.get("prefix_pages", 0) or 0),
        "seq": int(digest.get("seq", 0) or 0),
    }


# ---------------------------------------------------------------------------
# merge (coordinator side)
# ---------------------------------------------------------------------------


def merge_fleet_snapshots(snapshots: list[dict]) -> dict[str, Any]:
    """Merge per-daemon fleet snapshots (Daemon.fleet_snapshot) into
    one cluster view.

    Each snapshot stamps its machine's wall and HLC clocks back to
    back; the difference is that machine's offset from the cluster HLC
    axis, so per-replica receive stamps land on one comparable ``t_ns``
    axis regardless of wall-clock skew (the metrics_history idiom).
    Digest ages are computed against the *local* wall pair — same
    clock, skew-free — so a skewed machine never reads as stale."""
    replicas: dict[str, dict] = {}
    machines: list[str] = []
    cluster_now = 0
    for snap in snapshots:
        if not isinstance(snap, dict) or not snap:
            continue
        offset = int(snap.get("hlc_ns", 0)) - int(snap.get("wall_ns", 0))
        cluster_now = max(cluster_now, int(snap.get("wall_ns", 0)) + offset)
        machine = str(snap.get("machine_id", ""))
        if machine not in machines:
            machines.append(machine)
        wall_ns = int(snap.get("wall_ns", 0))
        for node, entry in (snap.get("replicas") or {}).items():
            recv_ns = int(entry.get("recv_wall_ns", 0))
            merged = {
                k: v for k, v in entry.items() if k != "recv_wall_ns"
            }
            merged["machine"] = machine
            merged["t_ns"] = recv_ns + offset
            merged["age_s"] = round(max(0, wall_ns - recv_ns) / 1e9, 3)
            prev = replicas.get(node)
            if prev is None or merged["t_ns"] >= prev["t_ns"]:
                replicas[node] = merged
    return {
        "replicas": replicas,
        "machines": sorted(machines),
        "t_ns": cluster_now,
    }


# ---------------------------------------------------------------------------
# placement scoring (router side)
# ---------------------------------------------------------------------------


def score_placement(
    prompt_tokens,
    adapter: str | None,
    replicas: dict[str, dict],
    *,
    stale_after: float | None = None,
) -> list[dict[str, Any]]:
    """Rank replicas for one prompt, best first. Deterministic: the
    same inputs always produce the same order, so a router fleet makes
    consistent decisions without coordination.

    ``replicas`` is the ``merge_fleet_snapshots`` ``"replicas"``
    mapping (digest fields + ``age_s``). Ordering:

    1. score — longest cached prefix (token count) matched by hashing
       the prompt with :func:`prompt_hash_chain` at each replica's own
       page size, discounted linearly to 0 as the digest age approaches
       ``stale_after`` (default 3x the publish cadence);
    2. occupancy — lower used/total page fraction wins ties;
    3. free streams (more is better), then replica id.
    """
    if stale_after is None:
        stale_after = stale_after_s()
    chains_by_ps: dict[int, dict[str, int]] = {}
    ranked: list[dict[str, Any]] = []
    for rid in sorted(replicas):
        d = replicas[rid]
        ps = int(d.get("page_size", 0) or 0)
        if ps > 0 and ps not in chains_by_ps:
            chains_by_ps[ps] = {
                chain: token_len
                for chain, token_len in prompt_hash_chain(
                    prompt_tokens, ps, adapter
                )
            }
        chains = chains_by_ps.get(ps, {})
        matched = 0
        for entry in d.get("prefixes") or []:
            chain, token_len = str(entry[0]), int(entry[1])
            if chains.get(chain) == token_len and token_len > matched:
                matched = token_len
        total = int(d.get("total_pages", 0) or 0)
        used = int(d.get("used_pages", 0) or 0)
        occupancy = round(used / total, 4) if total else 0.0
        age = float(d.get("age_s", 0.0) or 0.0)
        discount = (
            max(0.0, 1.0 - age / stale_after) if stale_after > 0 else 1.0
        )
        ranked.append({
            "replica": rid,
            "matched_tokens": matched,
            "score": round(matched * discount, 3),
            "occupancy": occupancy,
            "age_s": age,
            "free_streams": int(d.get("free_streams", 0) or 0),
            "fingerprint": d.get("fingerprint", ""),
        })
    ranked.sort(key=lambda e: (
        -e["score"], e["occupancy"], -e["free_streams"], e["replica"],
    ))
    return ranked
