"""Continuous batching over a paged KV pool: the serving engine.

Batch-1 decode is HBM-bandwidth-bound: every token pays the full LM
weight stream. The paged kernels (ops.decode_block.
attention_paged_batch_step under the window programs of
models/paged_window.py)
run B independent sequences off ONE weight stream, so B concurrent
chats decode at nearly the cost of one.

:class:`PagedBatchEngine` is the host-side slot and page manager over
those kernels; :class:`PageAllocator` is its refcounted block
allocator. New requests join mid-flight at window boundaries — no
barrier, no draining: that is the "continuous" in continuous batching.

The engine is model-family-agnostic: construction takes the family's
``init_pool`` / ``chunk_prefill`` / ``window_step`` closures (see
models/hf/qwen2.make_paged_engine, models/hf/kimi_k2.make_paged_engine).

Reference parity: the reference's openai-proxy-server serializes
requests through the dataflow (node-hub/openai-proxy-server/src/
main.rs:30-50 — one request in flight at a time); this beats it on the
axis its own design concedes.
"""

from __future__ import annotations

import itertools
import time
from collections import deque
from dataclasses import dataclass, field

from dora_tpu import backend, profiling, telemetry
from dora_tpu.models.paged_window import (
    make_paged_spec_window,
    make_paged_window,
    spec_window_row_stats,
    window_row_stats,
)


class PageAllocator:
    """Fixed-pool block allocator over page-size KV blocks, with
    per-page refcounts so pages can be SHARED across block tables.

    Physical page 0 is RESERVED as the null page: a zeroed block-table
    entry points there, so masked/idle rows of the batched kernels dump
    their harmless writes into it instead of a live slot's context.
    Allocation is all-or-nothing (``alloc`` returns None rather than a
    partial grant) — admission is page-aware up front, so a admitted
    stream can never OOM mid-decode (the preempt-free watermark).

    Refcounts are the custody model behind the prefix cache
    (models/prefix_cache.py): a page granted by ``alloc``/``take``
    starts at refcount 1; every additional holder — a second stream's
    block table mapping the same prefix page, or the prefix cache
    itself — calls :meth:`ref`, and releases with :meth:`unref`. The
    page returns to the free list only when the LAST holder lets go.
    Shared pages (refcount > 1) are immutable by convention: the paged
    engine only ever maps a shared page into block-table positions the
    stream never writes (divergent rows get fresh pages — the
    copy-on-write boundary is re-materialized, never written in place).

    :meth:`free` keeps the legacy exclusive-release contract and is now
    HARDENED: freeing a page that is not allocated (double free) or
    that another holder still references (free-while-shared) raises
    instead of silently corrupting the free list."""

    def __init__(self, num_pages: int):
        assert num_pages >= 2, num_pages
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, 0, -1))
        #: page id -> refcount; only pages with refcount >= 1 appear
        self._ref: dict[int, int] = {}
        #: high-water mark of pages in use (telemetry: a pool sized to
        #: peak_in_use + headroom is the capacity-planning answer)
        self.peak_in_use = 0

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        """Pages currently granted (null page excluded)."""
        return self.num_pages - 1 - len(self._free)

    def largest_contiguous_free(self) -> int:
        """Longest run of physically-adjacent free page ids — the
        fragmentation gauge. Grants are id-scattered (block tables
        indirect every access) so fragmentation never blocks a grant
        here; the gauge exists because a future device-side contiguous
        fast path would care, and because a collapsing value under
        churn is the early signal. O(free) — called at snapshot
        cadence, not on the grant path."""
        if not self._free:
            return 0
        ids = sorted(self._free)
        best = run = 1
        for prev, cur in zip(ids, ids[1:]):
            run = run + 1 if cur == prev + 1 else 1
            if run > best:
                best = run
        return best

    def alloc(self, n: int) -> list[int] | None:
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._ref[p] = 1
        if self.in_use > self.peak_in_use:
            self.peak_in_use = self.in_use
        return pages

    def take(self, pages: list[int]) -> bool:
        """Claim SPECIFIC page ids — checkpoint restore, where saved
        block tables reference physical ids. All-or-nothing like
        :meth:`alloc`; O(pool), restore-path only. A page another
        holder already references cannot be taken (the restore path
        :meth:`ref`-shares those instead)."""
        free = set(self._free)
        if len(set(pages)) != len(pages) or not all(p in free for p in pages):
            return False
        claim = set(pages)
        self._free = [p for p in self._free if p not in claim]
        for p in pages:
            self._ref[p] = 1
        if self.in_use > self.peak_in_use:
            self.peak_in_use = self.in_use
        return True

    def refcount(self, page: int) -> int:
        """Current holder count for one page (0 = free)."""
        return self._ref.get(page, 0)

    def ref(self, pages: list[int]) -> None:
        """Add one reference per page — a new holder of already-granted
        pages (prefix sharing). Raises on pages nobody holds: sharing a
        free page would let the allocator grant it again underneath the
        new holder."""
        for p in pages:
            rc = self._ref.get(p, 0)
            if rc <= 0:
                raise RuntimeError(
                    f"cannot ref page {p}: not allocated (refcount 0)"
                )
            self._ref[p] = rc + 1

    def unref(self, pages: list[int]) -> None:
        """Drop one reference per page; a page returns to the free list
        when its LAST reference drops. Raises on double free (the page
        is already free)."""
        for p in pages:
            rc = self._ref.get(p, 0)
            if rc <= 0:
                raise RuntimeError(
                    f"double free: page {p} is not allocated"
                )
            if rc == 1:
                del self._ref[p]
                self._free.append(p)
            else:
                self._ref[p] = rc - 1

    def free(self, pages: list[int]) -> None:
        """Exclusive release: the caller asserts it is the SOLE holder.
        Raises on double free AND on free-while-shared — both silently
        corrupted the free list before refcounting (a shared page would
        land on the free list while another block table still pointed
        at it). Holders that may share pages release with
        :meth:`unref` instead."""
        for p in pages:
            rc = self._ref.get(p, 0)
            if rc <= 0:
                raise RuntimeError(
                    f"double free: page {p} is not allocated"
                )
            if rc > 1:
                raise RuntimeError(
                    f"free of shared page {p} (refcount {rc}); "
                    f"shared holders release via unref"
                )
        self.unref(pages)

    def check_invariants(self) -> None:
        """Every page is exactly one of {null, free, refcounted}, the
        free list holds no duplicates, refcounts are >= 1, and
        ``in_use + free == total - 1``. Cheap enough to assert after
        every chaos/migration test (O(pool))."""
        free = self._free
        assert len(set(free)) == len(free), "duplicate pages in free list"
        assert all(0 < p < self.num_pages for p in free), \
            "free list holds out-of-range or null page ids"
        assert all(rc >= 1 for rc in self._ref.values()), \
            "zero/negative refcount retained"
        assert all(0 < p < self.num_pages for p in self._ref), \
            "refcounted out-of-range or null page"
        assert set(free).isdisjoint(self._ref), \
            "page both free and refcounted"
        assert len(free) + len(self._ref) == self.num_pages - 1, (
            f"page accounting broken: {len(free)} free + "
            f"{len(self._ref)} in use != {self.num_pages - 1}"
        )


@dataclass
class _PagedSlot:
    request_id: str
    emitted: int
    max_new: int
    pages: list[int]
    prompt: list[int] | None  # pending prompt ids; None once decoding
    true_len: int
    chunk_base: int = 0
    #: leading pages of ``pages`` mapped SHARED from the prefix cache
    #: (refcounted, immutable); the stream's own writes start past them
    shared: int = 0
    #: LoRA adapter identity (stable tenant NAME; None = base model)
    #: plus its resident stack slot index — the index is pinned for the
    #: stream's lifetime (AdapterPool refcount custody), so it rides
    #: the traced adapter-id vector unchanged between rebuilds.
    adapter: str | None = None
    adapter_idx: int = 0
    #: state snapshots (a slot-state engine with a prefix cache): the
    #: radix node whose snapshot this stream was granted and has not
    #: copied into its slot yet, and the ``(depth, row)`` of the one it
    #: saved and has not handed to the cache yet
    snap_from: object | None = None
    snap_saved: tuple | None = None
    #: where the prompt leaves what the radix tree already knew: the last
    #: chunk edge inside its match and past its grant (0 = none), and the
    #: ``(depth, row)`` of the snapshot saved there, the stream's until
    #: its final chunk is adopted
    branch_edge: int = 0
    snap_branch: tuple | None = None


class PagedBatchEngine:
    """Continuous batching over a paged KV pool with chunked prefill.

    KV lives in a fixed pool of page-size blocks; each slot holds a
    block TABLE (``[max_pages]`` int32 of physical page ids) and pages
    are granted at admission for the context the stream can actually
    reach (``max(chunk-padded prompt, prompt + max_new)`` rows), so
    concurrency is capped by the context streams use, not by
    ``max_slots`` times the worst case.

    Prefill runs as fixed-shape chunks interleaved with decode: one
    chunk of the head-of-line prefilling stream per :meth:`step`, then
    one batched decode pass for every decoding stream — a 2k-token
    prompt no longer freezes active streams for its whole prefill, and
    because the chunk shape is FIXED (position is a traced scalar),
    prefill compiles exactly one XLA program ever.

    Decode runs at WINDOW granularity: each :meth:`step` launches ONE
    fused K-tick program (``window_step``,
    models/paged_window.make_paged_window with ``k = window``) that detects per-stream completion on device
    and freezes finished rows mid-window, then fetches one [B, K+1]
    token matrix — host dispatch and device->host fetch cost amortize
    over K emitted tokens instead of being paid per token. The host
    unpacks the matrix honoring each stream's done offset (-1 marks
    ticks past a row's completion) and frees slots/pages; scheduling
    decisions — admissions, prefill interleave, backlog — happen only
    at window boundaries. ``window=1`` is the per-token behavior.

    Greedy outputs are bit-identical to the serial reference
    (models/hf/qwen2.generate) at every K: the paged kernels run the
    same per-row math, only the cache indexing routes through the
    block table, and the window carries exactly the state a per-tick
    loop would carry (asserted in tests/test_paged_engine.py).

    Closures (see models/hf/qwen2.make_paged_engine):
      * ``init_pool(num_pages)`` -> pools pytree
      * ``chunk_prefill(ids [C], pools, position, bt_row)`` ->
        (greedy [C], pools); with ``chunk_valid_rows`` a fifth operand,
        the count of the chunk's rows that are the prompt's
      * ``window_step(tokens [B], pools, positions [B], bts [B, P],
        active [B], emitted [B], max_new [B])`` ->
        (mat [B, K+1], tokens, positions, active, emitted, pools)

    **Slot state, the second cache kind.** A model whose streams own a
    fixed per-slot state beside their pages (a state-space mixer's
    recurrent state; a sliding-window layer's ring of its last
    ``window`` K/V rows) passes ``init_slot_state(max_slots)`` -> a
    pytree of ``[max_slots, ...]`` arrays, row ``b`` being slot ``b``'s.
    It is never a leaf of ``pools`` (every leaf of those is indexed by
    page; a model may give only some of its layers leaves there). With
    it, ``chunk_prefill`` takes two more trailing operands, the slot
    index and the state, and ``window_step`` one, the state
    (models/paged_window.make_paged_window, ``slot_state=True``); both
    return the state last, updated in place. The programs keep it right and the
    host makes no reset call: a recurrent state starts from zeros in a
    chunk at position 0; a ring needs no zero-start at all (a row counts
    by the position it holds, and what an earlier stream left is masked
    until overwritten); a chunk's padding rows and a frozen or
    mid-prefill row's decode ticks leave the state as it was. So
    :meth:`preempt` just drops the slot, :meth:`save_pools` /
    :meth:`restore_pools` carry the state beside the pages for
    :meth:`restore_state` with pinned slots, and what cannot take the
    state along is refused by name: speculation, LoRA,
    :meth:`admit_streams` of a stream in mid-decode, and the prefix
    cache unless the model keeps **state snapshots** (``state_snapshots``
    rows: a granted prefix needs the state at its end). With them the
    engine holds ``snapshot_pool``, ``init_slot_state(state_snapshots)``,
    the same leaves with a snapshot a row. The chunk program leaves the
    slot's state as it stands after the chunk's last valid row, so after
    a prompt's last FULL chunk the slot holds the state at a chunk (and
    so page) boundary: a copy program enqueued right behind that chunk
    (``state_snapshot``; on the device, every leaf in its own dtype)
    puts it in a row of the pool, and the prompt's pages enter the radix
    cache down to THAT depth with the row on the deepest node. A grant
    is trimmed to the deepest node of the match that holds a row, never
    between two, and the row is copied into the slot right before the
    stream's first chunk, which then starts at that depth. **A second
    snapshot where a prompt branches**: at admission the radix match
    reaches a depth ``m`` and the grant a depth ``d <= m``; where a chunk
    edge ``e`` with ``d < e <= m`` exists, the last such edge gets a row
    too, behind the chunk that ends there, and the row goes to the node
    at ``e`` (first writer wins). The tree has seen two prompts share
    ``[0, m)``, which is the evidence that a third will, and the third is
    granted to within a chunk and a page of the shared depth; a session
    that resends its history has ``m - d`` under a chunk and saves none.
    Rows are counted as pages are (:meth:`check_invariants`). :meth:`preempt`
    gives back what its stream had not handed over and leaves the
    cache's rows where they are; :meth:`save_pools` does not carry the
    snapshot pool and :meth:`checkpoint_state` not the radix tree, so a
    restored engine starts with none.

    With ``spec_k > 0`` (prompt-lookup speculation,
    models/paged_window.make_paged_spec_window) the window signature instead
    takes and returns two extra per-stream device buffers —
    ``history [B, hist_buf]`` and ``hist_len [B]`` — and ``mat`` is the
    ragged ``[B, K*(spec_k+1) + 1]`` emission matrix; each dispatch can
    then emit up to K*(spec_k+1) tokens per stream. Emitted tokens are
    identical to ``spec_k = 0`` at every (K, k): drafts are verified by
    the same greedy model pass, and the host unpack replays the
    device's acceptance walk token by token.
    """

    def __init__(self, *, init_pool, chunk_prefill, window_step,
                 max_slots: int = 16, max_seq: int, page_size: int,
                 chunk: int, num_pages: int, eos: int | None = None,
                 window: int = 8, spec_k: int = 0, spec_ngram: int = 2,
                 window_factory=None, prefix_cache: bool = False,
                 prefix_cache_pages: int = 0, lora_pool=None,
                 chunk_valid_rows: bool = False, init_slot_state=None,
                 state_snapshots: int = 0):
        import jax
        import jax.numpy as jnp
        import numpy as np

        assert page_size % 8 == 0, page_size  # sublane-aligned RMW window
        assert chunk % page_size == 0, (chunk, page_size)
        assert max_seq % chunk == 0, (max_seq, chunk)
        assert window >= 1, window
        assert spec_k >= 0, spec_k
        self._jnp = jnp
        self._np = np
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.page_size = page_size
        self.chunk = chunk
        self.eos = eos
        self.chunk_prefill = chunk_prefill
        #: ``chunk_prefill`` takes a trailing traced operand: how many of
        #: the chunk's rows are the prompt's (the rest is the tail
        #: chunk's right padding), for closures that count rows.
        if chunk_valid_rows and lora_pool is not None:
            # No model's chunk program takes both; the chunk's operand
            # list would be a signature that nothing was written for.
            raise NotImplementedError(
                "chunk_valid_rows and a LoRA pool: no chunk program takes "
                "the valid-row count beside the adapter operands")
        self.chunk_valid_rows = chunk_valid_rows
        self.window_step = window_step
        self.window = window
        self.max_pages = max_seq // page_size
        self.pools = init_pool(num_pages)
        #: per-slot state (None = the model has none): see the class
        #: docstring. Donated to and replaced by both programs.
        self.slot_state = None
        #: rows shaped like one slot's state that the prefix cache's nodes
        #: hold (None = no snapshots): see the class docstring
        self.snapshot_pool = None
        if init_slot_state is not None:
            for knob, on in (("speculation", spec_k),
                             ("a LoRA pool", lora_pool is not None)):
                if on:
                    raise NotImplementedError(
                        f"a slot-state engine cannot run with {knob}: the "
                        f"per-slot state would not follow")
            if prefix_cache and not state_snapshots:
                raise NotImplementedError(
                    "a slot-state engine cannot run with a prefix cache "
                    "unless its model keeps state snapshots (its "
                    "make_paged_engine passes state_snapshots; this one "
                    "passes none): a granted prefix needs the per-slot "
                    "state at its end")
            self.slot_state = init_slot_state(max_slots)
            if prefix_cache:
                self.snapshot_pool = init_slot_state(state_snapshots)
        self.allocator = PageAllocator(num_pages)
        #: shared-prefix subsystem (models/prefix_cache.py): radix
        #: lookup at admission maps cached prefix pages straight into
        #: the new stream's block table and prefill starts at the
        #: divergence point. Off (None) by default at the raw-engine
        #: level — serving factories enable it via DORA_PREFIX_CACHE.
        if prefix_cache:
            from dora_tpu.models.prefix_cache import PrefixCache

            self.prefix_cache = PrefixCache(
                self.allocator, page_size, max_pages=prefix_cache_pages,
                snapshots=state_snapshots if self.snapshot_pool is not None
                else 0,
            )
        else:
            self.prefix_cache = None
        # Host-side block tables (the scheduler's source of truth) plus
        # a device DECODE view with non-decoding rows zeroed: a slot
        # mid-prefill holds real pages, and letting its masked decode
        # row (pinned at position 0) write through them would clobber
        # prefilled context — zeroed rows route those writes to the
        # null page instead.
        self._bt = np.zeros((max_slots, self.max_pages), np.int32)
        self._bt_dec = jnp.asarray(self._bt)
        self._bt_dirty = False
        self.tokens = jnp.zeros((max_slots,), jnp.int32)
        self.positions = jnp.zeros((max_slots,), jnp.int32)
        self.slots: list[_PagedSlot | None] = [None] * max_slots
        self._decode = [False] * max_slots
        self._prefillq: deque[int] = deque()
        self._mask = jnp.zeros((max_slots,), bool)
        # Per-slot device vectors carried through the decode window:
        # tokens emitted so far and the max_new cap — the window's
        # on-device completion test. Rebuilt from the host slots only
        # when membership changes (a window boundary); otherwise the
        # window's returned state carries forward untouched.
        self._emitted_dev = jnp.zeros((max_slots,), jnp.int32)
        self._maxnew_dev = jnp.zeros((max_slots,), jnp.int32)
        self._members_dirty = True
        #: multi-tenant LoRA serving (models/lora_pool.AdapterPool):
        #: when attached, every window/chunk dispatch carries a per-row
        #: adapter slot-id vector plus the resident adapter stack as
        #: traced operands — mixed-tenant batches share ONE window
        #: program and adapter churn never recompiles. None = the exact
        #: pre-LoRA engine (window signatures unchanged).
        self.lora = lora_pool
        self._adapter_dev = jnp.zeros((max_slots,), jnp.int32)
        #: prompt-lookup speculation (0 = off = the exact pre-spec
        #: program). With spec_k > 0 the window is the
        #: make_paged_spec_window variant and carries two extra device
        #: buffers: per-stream token history and its lengths, mirrored
        #: host-side (_hist) so membership rebuilds, checkpoints and
        #: migration stay plain-python — the mirror IS the stream's
        #: prompt + emissions, which the host already knows.
        self.spec_k = spec_k
        self.spec_ngram = spec_ngram
        #: configured speculation width — :meth:`set_window` can pause
        #: speculation (spec_k -> 0) and resume it (spec_k -> _spec_cfg)
        #: at window boundaries, so history mirrors and the admission
        #: headroom follow the CONFIGURED width: pages stay reserved for
        #: the verify tail while paused, making the toggle always safe.
        self._spec_cfg = spec_k
        #: ``(k, spec_k) -> window_step`` builder for runtime retuning
        #: (the SLO autotuner); None pins the window program for life.
        self._window_factory = window_factory
        self._window_cache = {(window, spec_k): window_step}
        if self._spec_cfg:
            self._hist_buf = max_seq + spec_k + 1
            self._hist: list[list[int]] = [[] for _ in range(max_slots)]
            self._hist_dev = jnp.zeros((max_slots, self._hist_buf), jnp.int32)
            self._histlen_dev = jnp.zeros((max_slots,), jnp.int32)
        #: the programs :meth:`_launch` has called once
        self._launched: set = set()
        #: prefill chunks run (serving metrics), and how many of them
        #: :meth:`ahead` handed over behind a running window
        self.chunks_run = 0
        self.chunks_ahead = 0
        #: host->device program launches / device->host token fetches
        #: (round-trip accounting behind tokens_per_dispatch)
        self.dispatches = 0
        self.fetches = 0
        #: observability hooks: ``tracer`` is a telemetry.ServingTracer
        #: (request-lifecycle spans through the flight recorder, and
        #: the step path's loop phases, whose stamps are the step
        #: path's clock) — the serving node replaces this sink-less one
        #: with the one it shares with its loop; ``serving_metrics`` a
        #: metrics.ServingMetrics (fetch/grant histograms), None until
        #: the serving node attaches one.
        self.tracer = telemetry.ServingTracer()
        self.serving_metrics = None
        #: the window :meth:`dispatch` launched and :meth:`collect` has
        #: not fetched yet: ``(mat, launch start, compute counted
        #: from)`` (the last a stamp of its own only under
        #: ``device_monitor``). While
        #: it is set the device-carried state (``tokens``, ``positions``,
        #: ``pools`` …) is one window AHEAD of the host slots, so every
        #: reader of both (checkpoint, preempt, drain) runs only after
        #: ``collect()``.
        self._flight: tuple | None = None
        #: the chunk :meth:`ahead` enqueued behind that window and the
        #: next :meth:`dispatch` has not adopted yet: ``(slot object,
        #: slot index, the chunk's result)``. Until then the
        #: host's side of its stream is untouched (``chunk_base``, the
        #: prefill queue, ``_decode``, ``emitted``), so whatever reads
        #: slots between the two sees what it would with the chunk still
        #: to come, and a stream preempted or drained meanwhile is told
        #: from a live one by its slot object.
        self._ahead: tuple | None = None
        #: the stamp on which the last :meth:`dispatch` left
        #: ``window_launch`` itself, for a first token's read beside the
        #: window: from there on the device has work, so the host's gap
        #: ends there. None where the phase is still open when
        #: ``dispatch()`` returns and the caller's next switch leaves it.
        self.launched_at: float | None = None
        #: device utilization plane (dora_tpu.profiling): when the
        #: monitor is on, the step path splits each window/chunk's wall
        #: time into host-dispatch / device-compute / device-fetch (a
        #: block_until_ready between dispatch and the host read) and
        #: keeps an analytic FLOPs ledger; the serving node turns the
        #: interval deltas into mfu / device_busy_fraction gauges.
        self.device_monitor = profiling.monitor_enabled()
        self.host_dispatch_ns = 0
        self.device_compute_ns = 0
        self.device_fetch_ns = 0
        #: FLOPs dispatched (every active row × K × (spec_k+1)) vs
        #: useful (emitted tokens only) — the gap is frozen rows plus
        #: speculation's rejected tails.
        self.dispatched_flops = 0
        self.useful_flops = 0
        #: analytic per-token forward FLOPs (0 = model unknown: the
        #: ledger stays zero and MFU renders as a dash) and the device's
        #: peak FLOP/s for MFU's denominator — set by engine factories.
        self.flops_per_token = 0
        self.device_peak_flops = 0.0
        #: the model's own counters, ``() -> dict`` merged into
        #: ``ServingMetrics.model`` at llm_server's 1 Hz report (None =
        #: the model has none) — set by engine factories.
        self.model_counters = None
        #: KV number format, detected from the pool layout: int8 pools
        #: carry parallel ``ks``/``vs`` scale planes per layer
        #: (models/hf/qwen2.init_page_pool). Checkpoint custody keys on
        #: this — an fp snapshot's page bytes are meaningless in an
        #: int8 pool and vice versa, so restore_state rejects a
        #: mismatch instead of silently corrupting pages.
        first = next(iter(self.pools.values()), None)
        self.kv_dtype = (
            "int8" if isinstance(first, dict) and "ks" in first else "fp"
        )

        def _set_slot(tokens, positions, greedy, row, pos, b):
            # ``row`` is a traced operand: one program for every prompt
            # length, where a Python index would compile a slice for
            # each remainder of a prompt modulo the chunk. The token
            # goes from the chunk's result to the slot on the device;
            # the host reads it for the wire alone.
            token = jax.lax.dynamic_index_in_dim(greedy, row, keepdims=True)
            tokens = jax.lax.dynamic_update_slice(
                tokens, token.astype(tokens.dtype), (b,)
            )
            positions = jax.lax.dynamic_update_slice(
                positions, pos.reshape(1), (b,)
            )
            return tokens, positions

        self._set_slot = jax.jit(_set_slot, donate_argnums=(0, 1))

        def _copy_row(into, of, to_row, from_row):
            # every leaf's row ``from_row`` of ``of`` into row ``to_row``
            # of ``into``, in the leaf's own dtype: bits, not values
            with jax.named_scope("state_snapshot"):
                return jax.tree.map(
                    lambda a, b: jax.lax.dynamic_update_index_in_dim(
                        a, jax.lax.dynamic_index_in_dim(
                            b, from_row, keepdims=False), to_row, 0),
                    into, of)

        self._copy_row = jax.jit(_copy_row, donate_argnums=(0,))
        #: snapshots copied out of a slot and into one, and their bytes
        self.snapshots_saved = 0
        self.snapshots_restored = 0
        #: of the saved, those at a prompt's branch edge
        self.snapshots_branch_saved = 0
        self.snapshot_bytes = (
            sum(x.nbytes // x.shape[0]
                for x in jax.tree.leaves(self.snapshot_pool))
            if self.snapshot_pool is not None else 0)
        if self.snapshot_pool is not None:
            # both copies compile here, at start-up, over zeros: the first
            # grant of a session may come minutes into serving
            zero = jnp.zeros((), jnp.int32)
            self.snapshot_pool = self._launch(
                self._copy_row, self.snapshot_pool, self.slot_state, zero, zero)
            self.slot_state = self._launch(
                self._copy_row, self.slot_state, self.snapshot_pool, zero, zero)

    # -- admission -----------------------------------------------------------

    @property
    def free_slots(self) -> int:
        return sum(s is None for s in self.slots)

    @property
    def active(self) -> int:
        return self.max_slots - self.free_slots

    @property
    def prefilling(self) -> int:
        return len(self._prefillq)

    @property
    def free_pages(self) -> int:
        return self.allocator.free_pages

    def spec_headroom(self) -> int:
        """Extra rows a speculative verification pass may touch past
        ``prompt + max_new``: the last verify launches at position
        ``prompt + max_new - 1`` and writes ``spec_k + 1`` rows, so the
        admission math must reserve sequence room AND pages for the
        tail — the serial gate's contract (spec_decode.check_headroom),
        now in page units. 0 with speculation off, keeping the
        admission math byte-identical to the pre-spec engine. Uses the
        CONFIGURED width, not the live one: a stream admitted while the
        autotuner has speculation paused must still own its verify-tail
        pages for when speculation resumes."""
        return self._spec_cfg + 1 if self._spec_cfg else 0

    def fits(self, prompt_len: int, max_new: int,
             adapter: str | None = None) -> bool:
        """Admissible EVER: length fits the block table, the whole
        pool could grant its pages (a request that can never fit must
        be rejected up front, not parked in a backlog forever), and —
        multi-tenant serving — the named adapter is one this engine
        can make resident (residency bytes are the adapter pool's
        fixed stack; what varies is whether the tenant is servable at
        all)."""
        if adapter and (self.lora is None or not self.lora.has(adapter)):
            return False
        return (
            prompt_len + max_new + self.spec_headroom() <= self.max_seq
            and self.pages_needed(prompt_len, max_new)
            <= self.allocator.num_pages - 1
        )

    def pages_needed(self, prompt_len: int, max_new: int,
                     cached: int = 0) -> int:
        """Pages a stream can touch end to end: chunk-padded prefill
        writes (whole pages) vs prompt + max_new decode rows (+ the
        speculative verification tail), whichever reaches further.
        With ``cached`` tokens mapped from the prefix cache, prefill
        restarts at the (page-aligned) divergence point, so its write
        reach is ``cached`` plus the chunk-padded remainder — the
        result still COUNTS the shared pages (total footprint; the
        fresh grant is ``pages_needed - cached // page_size``)."""
        chunk_rows = cached + -(-(prompt_len - cached) // self.chunk) * self.chunk
        rows = max(chunk_rows, prompt_len + max_new + self.spec_headroom())
        return -(-rows // self.page_size)

    def can_admit(self, prompt_len: int, max_new: int,
                  adapter: str | None = None) -> bool:
        avail = self.free_pages
        if (
            self.prefix_cache is not None
            and self.pages_needed(prompt_len, max_new) > avail
        ):
            # Eviction yields to admission: unpinned, unshared cached
            # pages are free-in-waiting, never a reason to shed. Counted
            # only when the free list alone falls short: the count walks
            # every cached page.
            avail += self.prefix_cache.evictable_pages()
        if adapter and (self.lora is None or not self.lora.fits(adapter)):
            # Adapter residency is admission state like pages: every
            # resident slot pinned by live streams means this tenant
            # must wait for a release, exactly like a full page pool.
            return False
        return (
            self.free_slots > 0
            and self.fits(prompt_len, max_new, adapter)
            and self.pages_needed(prompt_len, max_new) <= avail
        )

    def admit_blocker(self, prompt_len: int, max_new: int,
                      adapter: str | None = None) -> str | None:
        """Why :meth:`can_admit` says no — stall attribution for the
        admission queue. ``"adapter_residency"`` singles out the
        multi-tenant case where everything else admits but the N+1-th
        tenant's adapter cannot evict a pinned resident (KNOWN_ISSUES
        round 19: this used to be indistinguishable from plain
        overload in the shed counters); ``"capacity"`` covers slots /
        pages / length, ``None`` means admissible."""
        if self.can_admit(prompt_len, max_new, adapter):
            return None
        if (
            adapter
            and self.lora is not None
            and self.lora.has(adapter)
            and not self.lora.fits(adapter)
            and self.can_admit(prompt_len, max_new, None)
        ):
            return "adapter_residency"
        return "capacity"

    def submit(self, request_id: str, prompt_ids, max_new: int,
               adapter: str | None = None) -> None:
        """Admit a stream: grant its pages, write its block table and
        queue its prefill. Returns None — the first token is emitted by
        a later :meth:`step` (prefill is chunked and interleaved, not
        synchronous). ``adapter``
        names the stream's LoRA tenant (None = base model); admission
        pins it resident for the stream's lifetime."""
        ids = [int(t) for t in prompt_ids]
        if not self.can_admit(len(ids), max_new, adapter):
            raise RuntimeError(
                f"cannot admit: {self.free_slots} slots, "
                f"{self.free_pages} pages free vs "
                f"{self.pages_needed(len(ids), max_new)} needed "
                f"({len(ids)}+{max_new}, max_seq {self.max_seq}"
                + (f", adapter {adapter!r}" if adapter else "")
                + ")"
            )
        aidx = 0
        if adapter:
            aidx = self.lora.acquire(adapter)
            if aidx is None:
                raise RuntimeError(
                    f"cannot admit {request_id!r}: adapter pool full "
                    f"of pinned adapters ({adapter!r} not resident)"
                )
        b = self.slots.index(None)
        base0, shared, granted, branch = (0, [], None, 0)
        if self.prefix_cache is not None:
            base0, shared, granted, branch = self._prefix_grant(
                ids, max_new, adapter)
        need = self.pages_needed(len(ids), max_new, base0) - len(shared)
        if need > self.allocator.free_pages and self.prefix_cache is not None:
            self.prefix_cache.evict(need - self.allocator.free_pages)
        fresh = self.allocator.alloc(need)
        if fresh is None:
            if shared:
                self.allocator.unref(shared)
            if granted is not None:
                self.prefix_cache.snapshot_release(granted)
            if adapter:
                self.lora.release(adapter)
            raise RuntimeError(
                f"cannot admit {request_id!r}: page pool exhausted "
                f"({need} fresh needed, {self.free_pages} free)"
            )
        pages = shared + fresh
        self._bt[b, :] = 0
        self._bt[b, : len(pages)] = pages
        self.slots[b] = _PagedSlot(
            request_id, emitted=0, max_new=max_new, pages=pages,
            prompt=ids, true_len=len(ids), chunk_base=base0,
            shared=len(shared), adapter=adapter, adapter_idx=aidx,
            snap_from=granted, branch_edge=branch,
        )
        self._decode[b] = False
        self._prefillq.append(b)
        self._bt_dirty = True
        if self._spec_cfg:
            self._hist[b] = list(ids)  # draft lookup sees the prompt too
        if self.serving_metrics is not None:
            g = self.serving_metrics.grant_pages
            g[len(pages)] = g.get(len(pages), 0) + 1
        if self.tracer.active:
            if base0:
                self.tracer.span(
                    "s_prefix_hit", request_id,
                    f"tokens={base0}/{len(ids)} pages={len(shared)}",
                )
            self.tracer.span(
                "s_admitted", request_id,
                f"slot={b} pages={len(pages)}"
                + (f" shared={len(shared)}" if shared else ""),
            )
        return None

    def _prefix_grant(self, ids: list[int], max_new: int,
                      adapter: str | None = None
                      ) -> tuple[int, list[int], object | None, int]:
        """Longest usable cached prefix for a new prompt: looks up the
        radix cache, trims the match so (a) at least the final prompt
        token is re-prefilled (the first generated token comes off the
        divergence chunk's logits), (b) the chunk-padded write reach
        stays inside the block table, and (c) the fresh-page need fits
        free + evictable pages (sharing must never turn an admissible
        request inadmissible). Refs the shared pages into this stream's
        custody and returns ``(divergence_base, shared_page_ids, node,
        branch_edge)``: ``node`` is the radix node whose snapshot the
        grant ends at, promised to this admission (None without snapshots
        or a grant); ``branch_edge`` is the last chunk edge past the grant
        and inside the match (0 without snapshots or such an edge): where
        this prompt's second snapshot goes.

        Trimmed boundary pages are re-materialized privately by the
        divergence chunk — the copy-on-write boundary copy (the copy
        and the divergent write fuse into one chunk pass, so shared
        pages are never written in place)."""
        ps = self.page_size
        cache = self.prefix_cache
        # Tenancy: the lookup walks the stream's OWN adapter tree
        # (prefix_cache keys on (adapter, tokens)), so two tenants with
        # identical prompts can never map each other's KV.
        matched, pages, mid_page = cache.lookup(ids, adapter)
        cap = (len(ids) - 1) // ps * ps
        lo = min(matched, cap)
        #: with state snapshots a grant may end only where one stands:
        #: the depths of the match that hold one, shallowest first
        stands = None
        if self.snapshot_pool is not None:
            stands = cache.snapshots_on_path(ids, adapter, lo)
            lo = stands[-1][0] if stands else 0

        def step_down(lo: int) -> int:
            if stands is None:
                return lo - ps
            while stands and stands[-1][0] >= lo:
                stands.pop()
            return stands[-1][0] if stands else 0

        while lo and (
            lo + -(-(len(ids) - lo) // self.chunk) * self.chunk
            > self.max_seq
        ):
            lo = step_down(lo)
        shared = pages[: lo // ps]
        if shared:
            self.allocator.ref(shared)
        # Sharing consumes evictable pages without shrinking the fresh
        # need below the no-cache grant in every geometry (the chunk
        # overhang past a non-chunk-aligned divergence can cost one
        # extra page) — back off page by page until the grant this
        # admission was promised still fits. lo == 0 always fits:
        # can_admit checked the no-cache grant against free+evictable.
        while shared:
            need = self.pages_needed(len(ids), max_new, lo) - len(shared)
            free = self.allocator.free_pages
            if need <= free or need <= free + cache.evictable_pages():
                break
            was, lo = lo, step_down(lo)
            for _ in range((was - lo) // ps):
                self.allocator.unref([shared.pop()])
        if not shared:
            lo = 0
        # the node whose snapshot the grant ends at: promised here, so
        # that no other admission's save takes its row before this
        # stream's first chunk has copied it
        granted = None
        if stands is not None and lo:
            granted = stands[-1][1]
            cache.snapshot_promise(granted)
        if lo:
            cache.hits += 1
            cache.hit_tokens += lo
        else:
            cache.misses += 1
        # Boundary pages the cache held but this stream re-materializes
        # privately: a divergence mid-page, or a match trimmed by the
        # final-token / reach / capacity rules above.
        if matched > lo or mid_page:
            cache.cow_copies += 1
        branch = 0
        if stands is not None:
            # the stream's chunks start at ``lo``: its edges are lo + n chunk
            edge = lo + (min(matched, cap) - lo) // self.chunk * self.chunk
            branch = edge if edge > lo else 0
        return lo, shared, granted, branch

    def _free_slot(self, b: int) -> None:
        # unref, not free: leading pages may be shared with the prefix
        # cache / other streams — the page pool reclaims each page only
        # when its last holder lets go.
        self.allocator.unref(self.slots[b].pages)
        self._let_go_of_snapshots(self.slots[b])
        if self.lora is not None and self.slots[b].adapter:
            # Drop the stream's residency pin; the adapter STAYS warm
            # until eviction needs its slot (prefix-cache discipline).
            self.lora.release(self.slots[b].adapter)
        self._bt[b, :] = 0
        self.slots[b] = None
        self._decode[b] = False
        self._bt_dirty = True
        self._members_dirty = True
        if self._spec_cfg:
            self._hist[b] = []

    def _let_go_of_snapshots(self, s: _PagedSlot) -> None:
        """A stream leaves (or its final chunk is adopted) with a promise
        it did not use or a row it did not hand over."""
        if s.snap_from is not None:
            self.prefix_cache.snapshot_release(s.snap_from)
            s.snap_from = None
        for kept in (s.snap_saved, s.snap_branch):
            if kept is not None:
                self.prefix_cache.snapshot_give_back(kept[1])
        s.snap_saved = s.snap_branch = None

    def snapshot_stats(self) -> dict:
        """The snapshot pool's counters and gauges (empty without one)."""
        if self.snapshot_pool is None:
            return {}
        cache = self.prefix_cache
        return {
            "state_snapshots_saved": self.snapshots_saved,
            "state_snapshots_restored": self.snapshots_restored,
            "state_snapshots_branch_saved": self.snapshots_branch_saved,
            "state_snapshots_evicted": cache.snapshots_evicted,
            "state_snapshot_bytes_copied": self.snapshot_bytes * (
                self.snapshots_saved + self.snapshots_restored),
            "state_snapshots_held": cache.snapshots_held,
            "state_snapshot_pool_bytes": self.snapshot_bytes * cache.snapshots,
        }

    # -- prefix-cache custody / invariants -----------------------------------

    @property
    def shared_pages(self) -> int:
        """Pages currently mapped SHARED into live streams' block
        tables (the prefix cache's own holdings are cached_pages)."""
        return sum(s.shared for s in self.slots if s is not None)

    def prefix_pin(self, ids, adapter: str | None = None) -> int:
        """Pin the cached path for ``ids`` against eviction (a
        preempted victim's prefix survives the wait to resume on
        refcount custody, not slot custody). No-op without a cache."""
        if self.prefix_cache is None:
            return 0
        return self.prefix_cache.pin(ids, adapter)

    def prefix_unpin(self, ids, adapter: str | None = None) -> None:
        if self.prefix_cache is not None:
            self.prefix_cache.unpin(ids, adapter)

    def check_invariants(self) -> None:
        """Allocator bookkeeping plus cross-custody: every allocated
        page's refcount equals the number of holders that can name it
        (live slots' grants + prefix-cache nodes), and nothing else
        holds pages. Callable from tests after any chaos/migration
        sequence."""
        from collections import Counter

        self.allocator.check_invariants()
        held: Counter = Counter()
        for s in self.slots:
            if s is not None:
                held.update(s.pages)
        if self.prefix_cache is not None:
            held.update(self.prefix_cache.pages())
        for p, n in held.items():
            rc = self.allocator.refcount(p)
            assert rc == n, (
                f"page {p}: refcount {rc} != {n} holders"
            )
        assert self.allocator.in_use == len(held), (
            f"{self.allocator.in_use} pages in use but only "
            f"{len(held)} held by slots/cache"
        )
        if self.snapshot_pool is not None:
            # snapshot rows, as pages: every row is free, on a node, or on
            # its way to one in a stream's hands, and in one place only
            cache = self.prefix_cache
            rows = Counter(cache._snap_free)
            rows.update(cache.snapshot_rows())
            rows.update(kept[1] for s in self.slots if s is not None
                        for kept in (s.snap_saved, s.snap_branch)
                        if kept is not None)
            assert sorted(rows) == list(range(cache.snapshots)) and all(
                n == 1 for n in rows.values()), (
                f"snapshot rows {dict(rows)} of {cache.snapshots}")
            for s in self.slots:
                if s is not None and s.snap_from is not None:
                    assert s.snap_from.snap is not None and (
                        s.snap_from.snap_pins > 0), (
                        f"{s.request_id}: promised a snapshot that is gone")

    # -- preemption / retuning (window-boundary only) ------------------------

    def preempt(self, request_id: str) -> dict | None:
        """Evict a live stream, freeing its slot and its whole page
        grant (all-or-nothing grants make the victim's footprint exact).
        Call between step()s — a window boundary (after ``collect()``),
        where host slots and device vectors agree; the freed row's
        zeroed block table routes any stale in-flight writes to the
        null page.

        Returns ``{"emitted", "max_new", "pages", "was_decoding"}`` for
        the caller's resume bookkeeping, or None if the id is not live.
        The engine does NOT hold the victim's emitted token values —
        the server does — so resume is a plain re-submit of
        prompt + emitted with the remaining budget: chunked prefill is
        deterministic, making the recomputed stream token-identical
        (recompute-on-resume; no pool serialization on the hot path)."""
        assert self._flight is None, "preempt() with a window in flight"
        for b, s in enumerate(self.slots):
            if s is not None and s.request_id == request_id:
                break
        else:
            return None
        if s.prompt is not None:
            # Still prefilling: just drop it from the chunk queue.
            try:
                self._prefillq.remove(b)
            except ValueError:
                pass
        meta = {
            "emitted": s.emitted,
            "max_new": s.max_new,
            "pages": len(s.pages),
            "was_decoding": bool(self._decode[b]),
            "adapter": s.adapter,
        }
        self._free_slot(b)
        if self.serving_metrics is not None:
            self.serving_metrics.preempted += 1
        if self.tracer.active:
            self.tracer.span(
                "s_preempt", request_id,
                f"slot={b} pages={meta['pages']} emitted={meta['emitted']}",
            )
        return meta

    def set_window(self, k: int, *, spec_on: bool | None = None) -> bool:
        """Re-select the fused-window K (and toggle speculation) at a
        window boundary — the SLO autotuner's actuator. Requires the
        ``window_factory`` closure (``(k, spec_k) -> window_step``);
        programs are cached per (k, spec) so the ladder compiles each
        rung once. Returns True when the program actually changed.

        Safe mid-stream: the device-carried window state is per-stream
        vectors independent of K, and ``_members_dirty`` forces a
        rebuild for the spec <-> plain signature change. Greedy outputs
        are identical at every K and spec setting, so retuning never
        perturbs in-flight streams' tokens."""
        assert k >= 1, k
        assert self._flight is None, "set_window() with a window in flight"
        if self._window_factory is None:
            return False
        if spec_on is None:
            want_spec = self.spec_k
        else:
            want_spec = self._spec_cfg if spec_on else 0
        if k == self.window and want_spec == self.spec_k:
            return False
        key = (k, want_spec)
        fn = self._window_cache.get(key)
        if fn is None:
            fn = self._window_factory(k, want_spec)
            self._window_cache[key] = fn
        self.window_step = fn
        self.window = k
        self.spec_k = want_spec
        self._members_dirty = True
        return True

    # -- the interleaved step ------------------------------------------------

    @property
    def in_flight(self) -> bool:
        """A window is launched and not collected yet."""
        return self._flight is not None

    def step(self) -> list[tuple[str, int, bool]]:
        """One scheduler tick = one WINDOW boundary: ONE prefill chunk
        for the head-of-line prefilling stream, then ONE fused K-tick
        decode window advancing every decoding stream up to K tokens
        (device-side completion freezes finished streams mid-window).
        Returns [(request_id, token, done)] in stream order; a stream's
        first token appears the tick its final chunk lands, the rest
        arrive up to K per tick off a single device round-trip.

        ``dispatch()`` then ``collect()``, every chunk in line: the
        serving loop calls the halves itself, sends the previous
        window's tokens between them while the device runs this one, and
        then puts the next period's chunk behind it (:meth:`ahead`)."""
        return self.dispatch() + self.collect()

    def dispatch(self) -> list[tuple[str, int, bool]]:
        """The launching half of :meth:`step`: the period's prefill
        chunk, the membership / block-table rebuild and the launch of
        the window program — everything up to the point where the host
        would start to wait for the window. The chunk is the one
        :meth:`ahead` put behind the previous window, adopted here, or,
        where none went ahead, the next of the prefill queue's head,
        enqueued here in line: one chunk a period either way. Returns
        the first token of a stream whose final chunk that was, usually
        nothing. That token goes to its slot on the device
        (``_set_slot`` gathers it from the chunk's result), so the
        window is launched behind the chunk without it, and the host
        reads it for the wire AFTER the launch, beside the window (phase
        ``first_token_read``): the read returns when the chunk is done,
        not when the window is. The read comes before the launch,
        blocking (phase ``first_token_wait``), only where the host needs
        the value first — speculation is on (``spec_k``: the history
        mirror is rebuilt into the window's operands) — or no window
        follows to read beside."""
        assert self._flight is None, "dispatch() before collect()"
        self.launched_at = None
        jnp = self._jnp
        np = self._np
        emitted: list[tuple[str, int, bool]] = []
        # The step path's phases (telemetry.LOOP_PHASES): each begins
        # where the one before ends, on one clock read, and the stamps
        # the counters below need are the phases' own.
        tracer = self.tracer
        #: a final chunk's ``(stream, slot, result, row)`` whose first
        #: token is read once the window is launched
        first = None
        rebuilding = False

        went, self._ahead = self._ahead, None
        if went is not None:
            # The period's chunk is on the device since the last window's
            # launch. Its stream may have lost its slot meanwhile
            # (preempt, drain): then the chunk wrote pages and a state
            # row that nobody holds, as a preempted stream's chunks
            # always have, and the period still has had its chunk.
            s, b, greedy = went
            if self.slots[b] is s:
                if self._final_chunk(s, s.chunk_base):
                    # a stream starts: membership work, as its rebuild is
                    tracer.switch("rebuild")
                    rebuilding = True
                first = self._adopt_chunk(s, b, greedy, emitted)
        elif self._prefillq:
            t_chunk = tracer.switch("chunk_launch")
            b = self._prefillq[0]
            s = self.slots[b]
            base = s.chunk_base
            greedy = self._enqueue_chunk(s, b, t_chunk)
            first = self._adopt_chunk(s, b, greedy, emitted)
            # Chunks are async dispatches, so the span is dispatch cost
            # only — but for a final chunk whose first token was read
            # here, blocking (speculation, or no window to read it
            # beside): that span holds the read too.
            self._chunk_span(s, base, t_chunk)

        if any(self._decode):
            if (self._members_dirty or self._bt_dirty) and not rebuilding:
                tracer.switch("rebuild")
            if self._members_dirty:
                # Membership changed at this boundary: rebuild the
                # device-carried window state from the host slots. (No
                # position pin needed — the window pins inactive rows
                # to 0 itself, every tick, via freeze_inactive.)
                self._mask = jnp.asarray(self._decode, dtype=bool)
                self._emitted_dev = jnp.asarray(
                    [
                        s.emitted if s is not None and self._decode[i] else 0
                        for i, s in enumerate(self.slots)
                    ],
                    jnp.int32,
                )
                self._maxnew_dev = jnp.asarray(
                    [
                        s.max_new if s is not None and self._decode[i] else 0
                        for i, s in enumerate(self.slots)
                    ],
                    jnp.int32,
                )
                if self.lora is not None:
                    # Per-row adapter slot ids — rebuilt ONLY here, at
                    # membership changes: a stream's resident index is
                    # refcount-pinned for its whole life, so between
                    # boundaries the vector cannot go stale.
                    self._adapter_dev = jnp.asarray(
                        [
                            s.adapter_idx
                            if s is not None and self._decode[i]
                            else 0
                            for i, s in enumerate(self.slots)
                        ],
                        jnp.int32,
                    )
                if self.spec_k:
                    # History only needs rebuilding when membership
                    # changes too: between boundaries the device carries
                    # it forward and the host mirror appends the same
                    # tokens the unpack loop emits.
                    hist = np.zeros(
                        (self.max_slots, self._hist_buf), np.int32
                    )
                    hlen = np.zeros((self.max_slots,), np.int32)
                    for i, s in enumerate(self.slots):
                        if s is None or not self._decode[i]:
                            continue
                        row = self._hist[i][: self._hist_buf]
                        hist[i, : len(row)] = row
                        hlen[i] = len(row)
                    self._hist_dev = jnp.asarray(hist)
                    self._histlen_dev = jnp.asarray(hlen)
                self._members_dirty = False
            if self._bt_dirty:
                self._bt_dec = jnp.asarray(
                    self._bt * np.asarray(self._decode, np.int32)[:, None]
                )
                self._bt_dirty = False
            t_win = tracer.switch("window_launch")
            #: multi-tenant serving: adapter ids + the resident stack
            #: ride every dispatch as trailing traced operands (fixed
            #: shapes — churn rewrites stack contents, never the
            #: program).
            if self.lora is not None:
                extra = (self._adapter_dev, self.lora.state())
            elif self.slot_state is not None:
                extra = (self.slot_state,)  # carried, and returned last
            else:
                extra = ()
            if self.spec_k:
                (
                    mat,
                    self.tokens,
                    self.positions,
                    self._mask,
                    self._emitted_dev,
                    self.pools,
                    self._hist_dev,
                    self._histlen_dev,
                ) = self._launch(
                    self.window_step,
                    self.tokens, self.pools, self.positions, self._bt_dec,
                    self._mask, self._emitted_dev, self._maxnew_dev,
                    self._hist_dev, self._histlen_dev, *extra,
                )
            else:
                (
                    mat,
                    self.tokens,
                    self.positions,
                    self._mask,
                    self._emitted_dev,
                    self.pools,
                    *state,
                ) = self._launch(
                    self.window_step,
                    self.tokens, self.pools, self.positions, self._bt_dec,
                    self._mask, self._emitted_dev, self._maxnew_dev,
                    *extra,
                )
                if state:
                    (self.slot_state,) = state
            self.dispatches += 1
            t_launched = t_win
            if self.device_monitor:
                t_launched = tracer.clock()
                self.host_dispatch_ns += int((t_launched - t_win) * 1e9)
            if first is not None:
                # The device has the chunk and the window in its queue:
                # the host's gap ends here, and the read returns when
                # the chunk is done, while the window runs.
                self.launched_at = tracer.switch("first_token_read")
                emitted.append(self._first_token(*first, self.launched_at))
                if self.device_monitor:
                    # the chunk's wait is counted; the window's begins
                    # where that ended
                    t_launched = tracer.clock()
            self._flight = (mat, t_win, t_launched)
        return emitted

    def _launch(self, program, *operands):
        """Hand the device a chunk or window program. A program's FIRST
        call traces, lowers and compiles it, seconds of Python whose
        speed hangs on how many bytes of stack lie below it (the chunk
        edge of CPython's frame stack): that call runs in a roomy frame
        (``backend.roomy``), so the serving loop's frames, this
        module's among them, cannot make a start-up slower."""
        if program in self._launched:
            return program(*operands)
        self._launched.add(program)
        return backend.roomy(program, *operands)

    def ahead(self) -> None:
        """Between :meth:`dispatch` and :meth:`collect`, once the
        dispatch's tokens have left: hand the device the NEXT period's
        chunk now, behind the window that runs, where the prefill queue
        holds one. It starts the instant the window ends, and what the
        host does after ``collect()`` runs beside it; the next
        ``dispatch()`` adopts it and launches none of its own. The
        device's order — chunk, window, chunk, window — and each
        program's operands are those of chunks enqueued in line. Nothing
        goes ahead where no window was launched, nor a FINAL chunk while
        speculation is on (its token is needed on the host before the
        launch it would precede)."""
        if self._flight is None or self._ahead is not None or not self._prefillq:
            return
        b = self._prefillq[0]
        s = self.slots[b]
        if self.spec_k and self._final_chunk(s, s.chunk_base):
            return
        t_chunk = self.tracer.switch("chunk_ahead")
        self._ahead = (s, b, self._enqueue_chunk(s, b, t_chunk))
        self.chunks_ahead += 1
        self._chunk_span(s, s.chunk_base, t_chunk)

    def _final_chunk(self, s: _PagedSlot, base: int) -> bool:
        """Does the chunk at ``base`` hold the prompt's last row?"""
        return base + self.chunk >= s.true_len

    def _enqueue_chunk(self, s: _PagedSlot, b: int, t_chunk: float):
        """Hand the device the chunk of slot ``b``'s prompt at
        ``s.chunk_base``; returns its result (greedy ``[C]``), a future.
        ``pools`` and ``slot_state`` become the chunk's results; the
        host's side of the stream is :meth:`_adopt_chunk`'s."""
        jnp = self._jnp
        base = s.chunk_base
        if s.snap_from is not None:
            # the stream's first chunk: the granted snapshot goes into
            # the slot ahead of it, and the promise ends (the device
            # runs what it is handed in order)
            self.slot_state = self._launch(
                self._copy_row, self.slot_state, self.snapshot_pool,
                jnp.asarray(b, jnp.int32),
                jnp.asarray(s.snap_from.snap, jnp.int32))
            self.prefix_cache.snapshot_release(s.snap_from)
            s.snap_from = None
            self.snapshots_restored += 1
        piece = s.prompt[base : base + self.chunk]
        valid = (
            (jnp.asarray(len(piece), jnp.int32),)
            if self.chunk_valid_rows else ()
        )
        piece = piece + [0] * (self.chunk - len(piece))
        operands = list(valid)
        if self.lora is not None:
            # Adapter id rides as a traced operand (an int32 device
            # scalar, never a python constant) so chunk prefill
            # keeps its one-compiled-shape discipline across
            # tenants.
            operands += [jnp.asarray(s.adapter_idx, jnp.int32),
                         self.lora.state()]
        elif self.slot_state is not None:
            # Which slot the chunk fills, and the slots' state: the
            # program reads row ``b`` (zeros at position 0) and
            # writes it back as it stands after the prompt's rows.
            operands += [jnp.asarray(b, jnp.int32), self.slot_state]
        # The slot's row of the block table goes as a COPY:
        # ``jnp.asarray`` of a numpy view need not copy before it
        # returns, and _free_slot() zeroes the row in place — a whole
        # window may pass between this enqueue and the chunk's run.
        greedy, self.pools, *state = self._launch(
            self.chunk_prefill, jnp.asarray(piece, jnp.int32), self.pools,
            jnp.asarray(base, jnp.int32), jnp.asarray(self._bt[b].copy()),
            *operands,
        )
        if state:
            (self.slot_state,) = state
        if self.snapshot_pool is not None:
            self._save_snapshot(s, b, base + self.chunk)
        # the request's first chunk ends its wait in the prefill queue
        self.tracer.request_chunk(s.request_id, t_chunk)
        self.chunks_run += 1
        self.dispatches += 1
        if self.device_monitor:
            self.host_dispatch_ns += int((self.tracer.clock() - t_chunk) * 1e9)
            if self.flops_per_token:
                self.dispatched_flops += self.chunk * self.flops_per_token
                self.useful_flops += (
                    min(self.chunk, s.true_len - base) * self.flops_per_token
                )
        return greedy

    def _save_snapshot(self, s: _PagedSlot, b: int, depth: int) -> None:
        """Behind the chunk that ends at ``depth``: where that was the
        prompt's last FULL chunk or its branch edge (where it leaves what
        the radix tree knew at its admission), the slot now holds the
        state at ``depth`` and nothing but the stream's next chunk will
        change it (a decode tick leaves a prefilling row's state alone),
        so copy it into a row of the pool here, in line behind the chunk.
        The row is the stream's until its final chunk is adopted and the
        cache takes it (:meth:`_adopt_chunk`). No row where the depth
        holds one already, or every row is promised."""
        last_full = depth == s.true_len // self.chunk * self.chunk
        if not last_full and depth != s.branch_edge:
            return
        cache = self.prefix_cache
        stands = cache.snapshots_on_path(s.prompt, s.adapter, depth)
        if stands and stands[-1][0] == depth:
            return
        row = cache.snapshot_take()
        if row is None:
            return
        self.snapshot_pool = self._launch(
            self._copy_row, self.snapshot_pool, self.slot_state,
            self._jnp.asarray(row, self._jnp.int32),
            self._jnp.asarray(b, self._jnp.int32))
        if last_full:
            s.snap_saved = (depth, row)
        else:
            s.snap_branch = (depth, row)
        self.snapshots_saved += 1
        if depth == s.branch_edge:
            self.snapshots_branch_saved += 1
            if self.tracer.active:
                self.tracer.span("s_branch_snapshot", s.request_id,
                                 f"depth={depth} row={row}")

    def _chunk_span(self, s: _PagedSlot, base: int, t_chunk: float) -> None:
        tracer = self.tracer
        if tracer.active:
            tracer.span(
                "s_prefill_chunk", s.request_id,
                f"base={base} chunk={self.chunk}"
                + (" final" if self._final_chunk(s, base) else ""),
                dur_ns=int((tracer.clock() - t_chunk) * 1e9),
            )

    def _adopt_chunk(self, s: _PagedSlot, b: int, greedy,
                     emitted: list) -> tuple | None:
        """The host's side of a chunk that is on the device, in
        ``dispatch()``, before the rebuild: the stream's ``chunk_base``
        and, behind a FINAL chunk, the start of its decode — off the
        prefill queue, its pages into the prefix cache, its first token
        to its slot on the device, the dirty flags. Returns the
        ``(stream, slot, result, row)`` of a first token to read once
        the window is launched; one read here, blocking, goes to
        ``emitted``."""
        jnp = self._jnp
        tracer = self.tracer
        base = s.chunk_base
        s.chunk_base = base + self.chunk
        if not self._final_chunk(s, base):
            return None
        # final chunk: stream starts
        assert self._prefillq[0] == b, (self._prefillq, b)
        self._prefillq.popleft()
        if self.prefix_cache is not None:
            # The prompt's fully-populated pages are immutable
            # from here on (decode writes start at true_len,
            # past them): adopt them into the radix cache so
            # later prompts map them instead of re-prefilling.
            n_full = s.true_len // self.page_size
            if self.snapshot_pool is not None:
                # ... down to the depth whose state this stream saved,
                # and no further: a page past the deepest snapshot can
                # be granted to nobody
                saved = [kept for kept in (s.snap_branch, s.snap_saved)
                         if kept is not None]
                n_full = max((d for d, _ in saved), default=0) // self.page_size
            if n_full:
                self.prefix_cache.insert(
                    s.prompt[: n_full * self.page_size],
                    s.pages[:n_full],
                    s.adapter,
                )
            if self.snapshot_pool is not None:
                # a row whose depth holds one already stays the stream's,
                # and goes back with whatever else it did not hand over
                if s.snap_branch and self.prefix_cache.snapshot_attach(
                        s.prompt, *s.snap_branch, s.adapter):
                    s.snap_branch = None
                if s.snap_saved and self.prefix_cache.snapshot_attach(
                        s.prompt, *s.snap_saved, s.adapter):
                    s.snap_saved = None
            self._let_go_of_snapshots(s)
        s.prompt = None
        # Its first token exists, on the device: the window's
        # completion counter (rebuilt from here) starts behind it.
        s.emitted = 1
        row = s.true_len - 1 - base
        if s.max_new > 1:
            # The stream decodes from the window this dispatch
            # launches, whatever its first token is: were it
            # ``eos``, the read below frees the slot and
            # collect() passes the row over; what the row wrote
            # meanwhile fell in pages it held for itself, past
            # the prompt's full pages that the cache adopted.
            self._decode[b] = True
            self.tokens, self.positions = self._set_slot(
                self.tokens, self.positions, greedy,
                jnp.asarray(row, jnp.int32),
                jnp.asarray(s.true_len, jnp.int32),
                jnp.asarray(b, jnp.int32),
            )
            self._members_dirty = True
            self._bt_dirty = True
        # else one token is all it asked for: the row never decodes.
        # Its slot too is freed at the read, not here: whatever reads
        # the slot between a chunk's enqueue and its token's read finds
        # the stream as it stands.
        if self.spec_k or not any(self._decode):
            t_fetch = tracer.enter("first_token_wait")
            emitted.append(self._first_token(s, b, greedy, row, t_fetch))
            tracer.leave()
            return None
        return s, b, greedy, row

    def _first_token(self, s: _PagedSlot, b: int, greedy, row: int,
                     t_fetch: float) -> tuple[str, int, bool]:
        """The host's read of a final chunk's first token, begun on the
        stamp ``t_fetch`` (its phase's own), and what the value decides:
        ``(request_id, token, done)``, the slot freed where the stream
        ends with it (``eos``, or one token asked for): the chunk is
        done by then, so nothing reads the slot's pages or its row of
        the block table any more but a window that passes the row over."""
        tracer = self.tracer
        if self.device_monitor:
            # Chunks that are not final stay async (their device time
            # surfaces as the next window's compute wait); a final
            # chunk is waited for, so split that wait into compute and
            # fetch here.
            greedy.block_until_ready()
            t_ready = tracer.clock()
            self.device_compute_ns += int((t_ready - t_fetch) * 1e9)
        # Host-index AFTER a full [C] fetch: 1 KB, no program.
        token = int(self._np.asarray(greedy)[row])
        t_first = tracer.clock()
        tracer.request_token(s.request_id, t_first)
        if self.device_monitor:
            self.device_fetch_ns += int((t_first - t_ready) * 1e9)
        self.fetches += 1
        if self.serving_metrics is not None:
            self.serving_metrics.fetch_latency.observe(
                (t_first - t_fetch) * 1e6
            )
        done = s.max_new <= 1 or (self.eos is not None and token == self.eos)
        if done:
            self._free_slot(b)
        elif self._spec_cfg:
            self._hist[b].append(token)
        return s.request_id, token, done

    def collect(self) -> list[tuple[str, int, bool]]:
        """The waiting half of :meth:`step`: block on the window
        :meth:`dispatch` launched, fetch its one [B, K+1] matrix, unpack
        it and free the slots of finished streams. Returns the window's
        tokens; nothing when no window was launched."""
        if self._flight is None:
            return []
        np = self._np
        emitted: list[tuple[str, int, bool]] = []
        sm = self.serving_metrics
        mat, t_win, t_launched = self._flight
        self._flight = None
        tracer = self.tracer
        t_fetch = tracer.switch("window_wait")
        if self.device_monitor:
            # Block BEFORE the host read so compute and transfer
            # separate cleanly; np.asarray alone conflates them.
            # Compute is counted from the launch: what the host did
            # between dispatch() and here ran beside the window (an
            # upper bound where that outlasted it).
            mat.block_until_ready()
            t_ready = tracer.clock()
            self.device_compute_ns += int((t_ready - t_launched) * 1e9)
        host = np.asarray(mat)  # ONE [B, K+1] device->host transfer
        t_done = tracer.switch("unpack")
        self.fetches += 1
        if self.device_monitor:
            self.device_fetch_ns += int((t_done - t_ready) * 1e9)
            if self.flops_per_token:
                self.dispatched_flops += profiling.window_flops(
                    flops_per_token=self.flops_per_token,
                    active=sum(self._decode), k=self.window,
                    spec_k=self.spec_k,
                )
        if sm is not None:
            sm.fetch_latency.observe((t_done - t_fetch) * 1e6)
        if tracer.active:
            # Span per decoding stream BEFORE the unpack loop frees
            # finished slots; all rows share the window's host span
            # (one dispatch serves them all).
            win_ns = int((t_done - t_win) * 1e9)
            for b, slot in enumerate(self.slots):
                if slot is None or not self._decode[b]:
                    continue
                if self.spec_k:
                    n_emit, frozen = spec_window_row_stats(
                        host[b], self.window, self.spec_k + 1
                    )
                else:
                    n_emit, frozen = window_row_stats(
                        host[b], self.window
                    )
                tracer.span(
                    "s_decode_window", slot.request_id,
                    f"K={self.window} emitted={n_emit} "
                    f"frozen_at={frozen}",
                    dur_ns=win_ns,
                )
        if self.spec_k:
            self._unpack_spec(host, emitted, sm)
        else:
            for b, slot in enumerate(self.slots):
                if slot is None or not self._decode[b]:
                    continue
                # Unpack this row up to its done offset: the host
                # completion test mirrors the device's exactly (same
                # emitted counter, same cap, same eos), so the first
                # host-done token is precisely where the device froze
                # the row; later columns hold the -1 sentinel.
                for j in range(self.window):
                    token = int(host[b, j])
                    if token < 0:
                        break
                    slot.emitted += 1
                    if self._spec_cfg:
                        # Speculation is paused, not absent: keep the
                        # host history mirror current so resuming it
                        # rebuilds warm draft lookup state.
                        self._hist[b].append(token)
                    done = (
                        slot.emitted >= slot.max_new
                        or (self.eos is not None and token == self.eos)
                    )
                    emitted.append((slot.request_id, token, done))
                    if done:
                        self._free_slot(b)
                        break
        if self.device_monitor and self.flops_per_token:
            # Useful work = tokens this window actually emitted;
            # dispatched-minus-useful is the frozen-row + rejected-
            # tail overhead MFU deliberately excludes.
            self.useful_flops += len(emitted) * self.flops_per_token
        return emitted

    def _unpack_spec(self, host, emitted, sm) -> None:
        """Unpack the spec window's ragged ``[B, K*(spec_k+1) + 1]``
        matrix by replaying the device's acceptance/completion walk: a
        ``-1`` inside a tick-block only pads past that tick's accepted
        length (the stream may emit again next tick), so the walk
        advances tick by tick and stops a stream only where the host's
        own completion test fires — which is, by construction, exactly
        where the device froze it. Also feeds the host history mirror
        and the draft acceptance metrics (drafted = spec_k per live
        verification pass; accepted = emissions minus the bonus
        token)."""
        m = self.spec_k + 1
        for b, slot in enumerate(self.slots):
            if slot is None or not self._decode[b]:
                continue
            stream_done = False
            for t in range(self.window):
                got = 0
                for i in range(m):
                    token = int(host[b, t * m + i])
                    if token < 0:
                        break
                    got += 1
                    slot.emitted += 1
                    self._hist[b].append(token)
                    done = (
                        slot.emitted >= slot.max_new
                        or (self.eos is not None and token == self.eos)
                    )
                    emitted.append((slot.request_id, token, done))
                    if done:
                        stream_done = True
                        break
                if sm is not None and got:
                    sm.spec_drafted += self.spec_k
                    sm.spec_accepted += got - 1
                    sm.spec_accept_len.observe(got)
                if stream_done:
                    self._free_slot(b)
                    break

    # -- checkpoint / restore / migration ------------------------------------

    def checkpoint_state(self) -> dict:
        """JSON-able snapshot of every live stream: slot metadata, page
        grants, per-slot last token and position. Call between step()s —
        a window boundary (after ``collect()``), where host slots and
        device vectors agree: with a window in flight the device's
        tokens and positions are a window ahead of the slots' counters.
        Pool CONTENTS are not included; :meth:`save_pools` covers engines
        whose decode reads KV (the stub's affine rule does not)."""
        assert self._flight is None, "checkpoint with a window in flight"
        np = self._np
        toks = np.asarray(self.tokens)
        pos = np.asarray(self.positions)
        slots = []
        for b, s in enumerate(self.slots):
            if s is None:
                continue
            meta = {
                "slot": b,
                "request_id": s.request_id,
                "emitted": s.emitted,
                "max_new": s.max_new,
                "pages": [int(p) for p in s.pages],
                "shared": s.shared,
                "prompt": list(s.prompt) if s.prompt is not None else None,
                "true_len": s.true_len,
                "chunk_base": s.chunk_base,
                "decode": bool(self._decode[b]),
                "last_token": int(toks[b]),
                "position": int(pos[b]),
            }
            if s.adapter:
                # Stable tenant NAME, never the resident slot index —
                # indices are recycled by eviction and mean nothing to
                # another engine. Absent for base streams, so pre-LoRA
                # snapshots and LoRA-era base snapshots are one format.
                meta["adapter"] = s.adapter
            if self._spec_cfg:
                # Draft-lookup history (prompt + emissions). Output
                # identity does NOT depend on it — verification makes
                # the emitted tokens exact whatever the drafts — but
                # restoring it keeps post-resume acceptance rates (and
                # so dispatch counts) identical too.
                meta["history"] = [int(t) for t in self._hist[b]]
            slots.append(meta)
        state = {"slots": slots, "kv_dtype": self.kv_dtype}
        if self.slot_state is not None:
            # The rows themselves travel with the pages (save_pools);
            # the flag says that a decoding stream here has some.
            state["slot_state"] = True
        return state

    def restore_state(self, state: dict, *, pin_slots: bool = True) -> list[str]:
        """Rebuild live streams from :meth:`checkpoint_state`; returns
        the restored request ids.

        Decoding streams resume from ``(last_token, position)`` — with
        ``pin_slots`` they reclaim their exact slot index and page ids
        (required when pool contents were restored via
        :meth:`restore_pools`: the block tables reference physical
        pages); without, any free slot/pages serve (the migrate-in path,
        where pools are not shipped). Mid-prefill streams re-submit from
        scratch — chunked prefill is deterministic and they emitted
        nothing yet, so replaying the chunks is token-exact.

        The snapshot's ``kv_dtype`` must match this engine's (missing
        defaults to "fp" — pre-quantization snapshots): block tables
        reference physical pages whose BYTES are format-specific, and
        int8 pages additionally carry scale planes an fp engine has
        nowhere to put. A mismatch raises instead of corrupting."""
        jnp = self._jnp
        snap_dtype = state.get("kv_dtype", "fp")
        if snap_dtype != self.kv_dtype:
            raise ValueError(
                f"checkpoint kv_dtype {snap_dtype!r} does not match engine "
                f"kv_dtype {self.kv_dtype!r}: re-serve the snapshot on an "
                f"engine built with the same DORA_KV_INT8 setting"
            )
        if bool(state.get("slot_state")) != (self.slot_state is not None):
            raise ValueError(
                "checkpoint and engine disagree on a per-slot state: "
                "restore it on an engine of the same model"
            )
        if self.slot_state is not None and not pin_slots and any(
            m.get("decode") for m in state.get("slots", [])
        ):
            raise RuntimeError(
                "cannot admit a stream in mid-decode into another slot: "
                "its per-slot state does not travel with the handoff "
                "(no state transfer yet); re-submit it from its prompt"
            )
        restored: list[str] = []
        metas = state.get("slots", [])
        #: pages already claimed by an earlier slot of THIS restore —
        #: prefix-shared pages appear in several slots' grants, so the
        #: first slot takes them and later slots ref-share them (the
        #: checkpoint is one engine's consistent snapshot; refcount
        #: custody rebuilds exactly).
        claimed: set[int] = set()
        # Decoding slots first: with pin_slots their index is fixed, and
        # a prefill re-submit must not claim it out from under them.
        for meta in sorted(metas, key=lambda m: not m.get("decode")):
            if not meta.get("decode"):
                self.submit(
                    meta["request_id"],
                    meta["prompt"],
                    meta["max_new"],
                    adapter=meta.get("adapter"),
                )
                restored.append(meta["request_id"])
                continue
            # Adapter custody rides the stream: re-pin it resident
            # before the slot exists, so the first window already
            # gathers the right slab. A snapshot without "adapter"
            # (pre-LoRA, or a base stream) resolves to slot 0.
            adapter = meta.get("adapter")
            if adapter and self.lora is None:
                raise RuntimeError(
                    f"cannot restore stream {meta['request_id']!r}: "
                    f"snapshot names adapter {adapter!r} but this "
                    f"engine has no adapter pool"
                )
            aidx = self.lora.acquire(adapter) if self.lora is not None else 0
            if aidx is None:
                raise RuntimeError(
                    f"cannot restore stream {meta['request_id']!r}: "
                    f"adapter {adapter!r} cannot be made resident"
                )
            n_pages = len(meta["pages"])
            if pin_slots:
                b = meta["slot"]
                pages = [int(p) for p in meta["pages"]]
                fresh = [p for p in pages if p not in claimed]
                if self.slots[b] is not None or not self.allocator.take(fresh):
                    raise RuntimeError(
                        f"cannot restore stream {meta['request_id']!r}: "
                        f"slot {b} or its pages are busy"
                    )
                reshared = [p for p in pages if p in claimed]
                if reshared:
                    self.allocator.ref(reshared)
                claimed.update(pages)
            else:
                if self.free_slots == 0:
                    raise RuntimeError(
                        f"no free slot for migrated stream "
                        f"{meta['request_id']!r}"
                    )
                pages = self.allocator.alloc(n_pages)
                if pages is None:
                    raise RuntimeError(
                        f"no pages for migrated stream {meta['request_id']!r}"
                    )
                b = self.slots.index(None)
            self._bt[b, :] = 0
            self._bt[b, :n_pages] = pages
            self.slots[b] = _PagedSlot(
                meta["request_id"],
                emitted=meta["emitted"],
                max_new=meta["max_new"],
                pages=pages,
                prompt=None,
                true_len=meta["true_len"],
                chunk_base=meta["chunk_base"],
                # Migrate-in re-grants fresh pages, so sharing does not
                # survive the hop (pool contents are not shipped either).
                shared=meta.get("shared", 0) if pin_slots else 0,
                adapter=adapter,
                adapter_idx=aidx,
            )
            self._decode[b] = True
            if self._spec_cfg:
                # A snapshot from a spec-off engine (or an older build)
                # carries no history: seed with the last token — the
                # lookup's fallback draft — which keeps resumes legal
                # and still token-exact, just with cold acceptance.
                self._hist[b] = [
                    int(t)
                    for t in meta.get("history") or [meta["last_token"]]
                ]
            # the chunk's own program: a result's worth of the token
            self.tokens, self.positions = self._set_slot(
                self.tokens,
                self.positions,
                jnp.full((self.chunk,), meta["last_token"], jnp.int32),
                jnp.asarray(0, jnp.int32),
                jnp.asarray(meta["position"], jnp.int32),
                jnp.asarray(b, jnp.int32),
            )
            self._bt_dirty = True
            self._members_dirty = True
            restored.append(meta["request_id"])
        return restored

    def drain_streams(self) -> dict:
        """Serialize every live stream and release its slot/pages — the
        migrate-out half of live migration. Call between step()s (a
        window boundary); feed the result to :meth:`admit_streams` on
        the target engine."""
        state = self.checkpoint_state()
        for b, s in enumerate(self.slots):
            if s is not None:
                self._free_slot(b)
        self._prefillq.clear()
        self._ahead = None  # its stream went with the rest
        return state

    def admit_streams(self, state: dict) -> list[str]:
        """Admit streams drained from another engine (migrate-in). Slot
        indices and page ids are re-granted fresh; without KV-page
        transfer this is token-exact only for engines whose step depends
        on (token, position) alone — see KNOWN_ISSUES."""
        return self.restore_state(state, pin_slots=False)

    def save_pools(self, path) -> None:
        """Persist the KV pool pytree (orbax, models/checkpoint.py) —
        needed only for engines whose decode reads the pool. A slot
        state is saved beside it, under its own key."""
        from dora_tpu.models import checkpoint

        checkpoint.save(path, self._cache_tree())

    def restore_pools(self, path) -> None:
        from dora_tpu.models import checkpoint

        tree = checkpoint.restore(path, self._cache_tree())
        if self.slot_state is None:
            self.pools = tree
        else:
            self.pools, self.slot_state = tree["pools"], tree["slot_state"]

    def _cache_tree(self):
        """What a checkpoint of the cache holds: the pools as they
        always were, or both cache kinds where there are two."""
        if self.slot_state is None:
            return self.pools
        return {"pools": self.pools, "slot_state": self.slot_state}

    def kv_pool_bytes(self) -> int:
        """Total device bytes of the KV pool pytree — int8 pools count
        their scale planes, so the gauge reflects the true HBM
        footprint the capacity math is denominated in."""
        import jax

        return sum(
            x.nbytes for x in jax.tree.leaves(self.pools)
            if hasattr(x, "nbytes")
        )

    def kv_quant_error(self, sample_pages: int = 64) -> float | None:
        """Per-page quantization error gauge for int8 pools: the mean
        RELATIVE quantization step — ``scale / (2 * rms(dequantized
        row) + eps)`` — over up to ``sample_pages`` allocated pages of
        layer 0. It is computable from the pool alone (no fp shadow is
        kept): symmetric rounding's worst-case per-element error is
        scale/2, so this is the worst-case error as a fraction of the
        row's RMS magnitude. None on fp pools (renders as a dash)."""
        if self.kv_dtype != "int8":
            return None
        np = self._np
        held = sorted(
            p for p, c in self.allocator._ref.items() if c > 0 and p != 0
        )[:sample_pages]
        if not held:
            return 0.0
        idx = np.asarray(held)
        lp = self.pools[next(iter(self.pools))]
        errs = []
        for name, sname in (("k", "ks"), ("v", "vs")):
            q = np.asarray(lp[name][idx], np.float32)  # [n, KV, page, hd]
            s = np.asarray(lp[sname][idx], np.float32)  # [n, KV, page]
            deq = q * s[..., None]
            rms = np.sqrt(np.mean(deq * deq, axis=-1))
            errs.append(np.mean(s / (2.0 * rms + 1e-8)))
        return float(np.mean(errs))


def make_stub_paged_engine(*, max_slots: int = 4, max_seq: int = 64,
                           page_size: int = 8, chunk: int = 16,
                           num_pages: int | None = None,
                           eos: int | None = None, window: int = 1,
                           vocab: int = 97, tick_sleep_s: float = 0.0,
                           spec_k: int = 0, spec_ngram: int = 2,
                           cycle: int | None = None,
                           prefix_cache: bool = False,
                           prefix_cache_pages: int = 0,
                           chunk_sleep_s: float = 0.0,
                           flops_per_token: int = 1_000_000,
                           peak_flops: float = 1e12,
                           lora_max_resident: int = 0):
    """A weight-free :class:`PagedBatchEngine` over the REAL window
    machinery: the decode window is ``paged_window.make_paged_window``
    (the same ``lax.scan`` + ``freeze_inactive`` program serving runs) with the
    model's batched step replaced by the affine token rule
    ``next = (7*t + 3) % vocab``, applied identically by the chunk-
    prefill stub — so token streams are deterministic, cheap to compile
    on CPU, and identical across window sizes, while every scheduler
    path (page grants, chunked prefill, mid-window freeze, slot free)
    is the production code.

    This is the engine the observability tests and the serving-trace
    bench drive, and what a 3-process demo dataflow serves when no
    checkpoint is available. ``tick_sleep_s`` and ``chunk_sleep_s``
    model device time: a window occupies the modelled device for
    ``tick_sleep_s * window``, a prefill chunk for ``chunk_sleep_s``,
    one after the other from their launch, and the host feels that
    where it waits for the device — at the top of ``collect()`` and at
    a final chunk's first-token read — not where it launches. What
    the host does between ``dispatch()`` and ``collect()`` so runs
    beside the window, as on the chip (the TTFT regression test needs
    windows that measurably take K ticks).

    ``spec_k > 0`` swaps in ``paged_window.make_paged_spec_window``
    (prompt-lookup speculation, the production serving path's window) with the
    stub rule doubling as the verifier: the rule is memoryless, so
    verifying candidate ``c`` is just ``rule(c)``, and emitted streams
    stay identical to the spec-off stub at every (K, k). ``cycle``
    selects the deterministic REPETITIVE rule ``next = (t + 1) % cycle``
    instead of the affine one: its period-``cycle`` token loop is
    exactly what trailing-ngram lookup predicts, so acceptance goes to
    ~100% after one period — while the affine rule (period ~vocab)
    keeps acceptance near zero. Together they drive both the
    draft-accept and draft-reject paths engine-free (the
    ``DORA_STUB_ENGINE=1`` A/B legs of bench_serving --spec-ab).

    ``lora_max_resident > 0`` attaches an :class:`AdapterPool` whose
    stub "adapter" is a scalar int32 SHIFT derived from the tenant
    name, and the rule becomes ``(rule(t) + shift[g]) % vocab`` — slot
    0's zero shift keeps base streams identical to the lora-off stub,
    while each tenant's stream is a distinct deterministic sequence.
    That is exactly the multi-tenant identity contract (per-tenant
    streams must match a single-tenant engine token for token) with
    adapter math cheap enough for tier-1, and the bench_serving
    --lora-ab legs drive churn/eviction through it engine-free."""
    import jax
    import jax.numpy as jnp

    if num_pages is None:
        num_pages = max_slots * (max_seq // page_size) + 1

    if cycle is None:
        def rule(t):
            return (t * 7 + 3) % vocab
    else:
        def rule(t):
            return (t + 1) % cycle

    lora_pool = None
    if lora_max_resident:
        from dora_tpu.models.lora_pool import AdapterPool

        def stub_loader(name):
            # Deterministic, engine-free: the tenant name IS the
            # adapter (a nonzero shift), so A/B legs need no weight
            # files and restores on a fresh process resolve the same
            # shift from the same name.
            return jnp.asarray(
                (sum(ord(c) for c in name) * 131 + 17) % vocab, jnp.int32
            )

        lora_pool = AdapterPool(
            stub_loader,
            jnp.asarray(0, jnp.int32),
            max_resident=lora_max_resident,
        )

        def step_fn(tokens, pools, positions, bts, adapters, shifts):
            del positions, bts
            return (rule(tokens) + shifts[adapters]) % vocab, pools

        def spec_step_fn(chunks, pools, positions, bts, adapters, shifts):
            del positions, bts
            return (rule(chunks) + shifts[adapters][:, None]) % vocab, pools
    else:
        def step_fn(tokens, pools, positions, bts):
            del positions, bts
            return rule(tokens), pools

        def spec_step_fn(chunks, pools, positions, bts):
            del positions, bts
            return rule(chunks), pools

    def window_factory(k, sk):
        if sk:
            base = jax.jit(
                make_paged_spec_window(
                    spec_step_fn, k=k, spec_k=sk, ngram=spec_ngram, eos=eos,
                    lora=lora_pool is not None,
                )
            )
        else:
            base = jax.jit(
                make_paged_window(
                    step_fn, k=k, eos=eos, lora=lora_pool is not None,
                )
            )
        return base

    if lora_pool is not None:
        chunk_fn = jax.jit(
            lambda ids, pools, position, bt, adapter, shifts: (
                (rule(ids) + shifts[adapter]) % vocab, pools
            ),
            donate_argnums=(1,),
        )
    else:
        chunk_fn = jax.jit(
            lambda ids, pools, position, bt: (rule(ids), pools),
            # Same donation contract as the real chunk fns (hf/qwen2.py):
            # the engine replaces its pools reference with the return value,
            # so the stale buffer must not stay alive.
            donate_argnums=(1,),
        )

    class StubEngine(PagedBatchEngine):
        """The modelled device: one queue, busy until ``_busy_until``;
        what is handed to it is done where the queue stood plus its own
        time, and a wait is for one piece of work, not for the queue."""

        _busy_until = 0.0
        _chunk_done_at = 0.0
        _window_done_at = 0.0

        def _occupy(self, seconds: float) -> float:
            self._busy_until = (
                max(self._busy_until, time.perf_counter()) + seconds
            )
            return self._busy_until

        @staticmethod
        def _wait_until(done_at: float) -> None:
            wait = done_at - time.perf_counter()
            if wait > 0:
                time.sleep(wait)

        def _enqueue_chunk(self, *args):
            if chunk_sleep_s:
                self._chunk_done_at = self._occupy(chunk_sleep_s)
            return super()._enqueue_chunk(*args)

        def dispatch(self):
            first = super().dispatch()
            if tick_sleep_s and self.in_flight:
                # behind the period's chunk, which a first token's read
                # inside super().dispatch() has waited for already
                self._window_done_at = self._occupy(
                    tick_sleep_s * self.window
                )
            return first

        def _first_token(self, *args):
            # the read returns when the chunk is done, whatever is
            # queued behind it
            self._wait_until(self._chunk_done_at)
            return super()._first_token(*args)

        def collect(self):
            if self.in_flight:
                self._wait_until(self._window_done_at)
            return super().collect()

    engine = StubEngine(
        init_pool=lambda n: {"null": jnp.zeros((1,), jnp.int32)},
        chunk_prefill=chunk_fn,
        window_step=window_factory(window, spec_k),
        window_factory=window_factory,
        max_slots=max_slots,
        max_seq=max_seq,
        page_size=page_size,
        chunk=chunk,
        num_pages=num_pages,
        eos=eos,
        window=window,
        spec_k=spec_k,
        spec_ngram=spec_ngram,
        prefix_cache=prefix_cache,
        prefix_cache_pages=prefix_cache_pages,
        lora_pool=lora_pool,
    )
    # Synthetic FLOPs constants so the utilization plane (MFU gauges,
    # attribution spans, UTIL panels) is exercised end-to-end by tier-1
    # on CPU: round numbers, so test expectations stay hand-checkable.
    engine.flops_per_token = flops_per_token
    engine.device_peak_flops = peak_flops
    return engine
