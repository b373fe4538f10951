"""The seam between the paged engine and a model.

``models/batch_engine.PagedBatchEngine`` knows slots, pages and windows
and nothing of a model; a model file (``models/hf/``) knows its layers,
its cache kinds and its two programs and nothing of the engine's loop.
What every counter-carrying model needs between the two is here, once:

* :func:`build_engine`: the refusal of serving knobs the model does not
  offer, the defaults ``llm_server`` leaves open, the jit and donation
  wiring of the window and the chunk program around counters that are an
  operand and a result of both, and the ``PagedBatchEngine`` itself;
* :class:`DeviceCounters`: those counters' host side (:func:`add_counts`
  is their adder inside a program);
* :func:`default_num_pages` / :func:`pages_that_fit`: the pool's default
  size as a rule in bytes;
* :func:`head_logits` / :func:`head_argmax`: the final norm and the head.

A model file gives its programs, its pool and state initialisers, its
counters' tree and a function that names gauges; it keeps its own
checks. ``qwen2.make_paged_engine`` (LoRA, speculation, int8 pages, no
counters) is built beside this, not through it.
"""

from __future__ import annotations

import os
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from dora_tpu import profiling
from dora_tpu.models import layers as L
from dora_tpu.models.batch_engine import PagedBatchEngine
from dora_tpu.ops import decode_block as DB

#: device memory the default pool leaves free beside the weights: the two
#: programs' temporaries, the allocator's fragmentation (Ouro: 96 arrays of
#: 100 MB) and a profiler capture. Stated, not tuned: a traced run's
#: ``memory_peak_bytes`` says how much of it is used.
POOL_HEADROOM_BYTES = 4 << 30


class DeviceCounters:
    """A model's counters of one engine: ``device``, the pytree of int32
    arrays that wrap, which the two programs take and give back, and its
    host side. :meth:`gained` fetches a few hundred bytes; ``llm_server``'s
    1 Hz report reaches it (through ``engine.model_counters``) at a window
    boundary, after ``collect()``, when the arrays are ready and nothing
    waits."""

    def __init__(self, device):
        self.device = device
        self._last = None
        #: int64 sums of every difference read so far, in ``device``'s shape
        self.totals = jax.tree.map(
            lambda v: np.zeros(v.shape, np.int64), device)

    def gained(self):
        """Fetch ``device`` once; the differences since the last call,
        modulo 2^32, which are also added to :attr:`totals`."""
        now = jax.tree.map(
            lambda v: np.asarray(v, np.int64), jax.device_get(self.device))
        last = self._last or jax.tree.map(np.zeros_like, now)
        self._last = now
        gained = jax.tree.map(lambda a, b: (a - b) & 0xFFFFFFFF, now, last)
        self.totals = jax.tree.map(np.add, self.totals, gained)
        return gained


def add_counts(stats: dict, **adds) -> dict:
    """Inside a program: the counters ``stats`` with ``adds`` added to
    the ones they name."""
    return {k: v + adds.get(k, 0) for k, v in stats.items()}


def pages_that_fit(page_bytes: int, limit: int, used: int, max_slots: int,
                   max_seq: int, page_size: int, *, multiple: int = 1) -> int:
    """The rule of :func:`default_num_pages`, in plain numbers."""
    fits = (limit - used - POOL_HEADROOM_BYTES) // page_bytes
    fits -= fits % multiple
    return int(max(min(max_slots * max_seq // page_size + 1, fits),
                   2 * max_seq // page_size))


def snapshots_that_fit(snapshot_bytes: int, left: int, max_slots: int) -> int:
    """Rows of a state-snapshot pool (``PagedBatchEngine``'s
    ``state_snapshots``) as a rule in bytes: an eighth of ``left`` (what
    the device has after the weights, the slots' own state and
    :data:`POOL_HEADROOM_BYTES`; the pages take the rest) in whole
    snapshots of ``snapshot_bytes``, never fewer than two a slot (a
    session's newest snapshot, and the one its turn in flight is about to
    leave) nor more than four."""
    return int(min(4 * max_slots,
                   max(2 * max_slots, left // 8 // snapshot_bytes)))


def default_num_pages(page_bytes: int, max_slots: int, max_seq: int,
                      page_size: int, *, multiple: int = 1) -> int:
    """A pool's default size as a rule in bytes, for a model whose cache
    and not whose weights decides it: what the device has
    (``bytes_limit``), less what is in use now (the weights, loaded
    before the engine is built), less :data:`POOL_HEADROOM_BYTES`, in
    whole pages of ``page_bytes`` (over every layer that has pages),
    rounded down to a ``multiple``; never more than every slot reaching
    ``max_seq``, never fewer than two streams' worth. Where the device
    reports no memory figures (the CPU) the Qwen engine's ``4 * max_seq``
    rows."""
    stats = jax.devices()[0].memory_stats() or {}
    limit, used = stats.get("bytes_limit"), stats.get("bytes_in_use")
    if not limit or used is None:
        return 4 * max_seq // page_size
    return pages_that_fit(page_bytes, limit, used, max_slots, max_seq,
                          page_size, multiple=multiple)


def head_logits(params, cfg, x):
    """Final rows ``x [N, dim]`` -> logits ``[N, vocab]`` float32."""
    h = L.rms_norm(x, params["out_norm"], cfg.norm_eps)
    return L.matmul(h, params["lm_head"]).astype(jnp.float32)


def head_argmax(params, cfg, x):
    """Final rows -> greedy tokens ``[N]``: the norm and the int8 head
    streamed by vocabulary tile (``ops.decode_block.lm_head_argmax``)."""
    w = params["lm_head"]
    return DB.lm_head_argmax(x, params["out_norm"], w["int8"], w["scale"],
                             eps=cfg.norm_eps)


def under_the_head(rows):
    """A program's body ``rows(params, cfg, ...) -> (final rows, *rest)``
    under the head, both ways: ``(logits(...), greedy(...))``, which
    return ``(logits [N, vocab] float32, *rest)`` for a test that
    compares logits and ``(greedy tokens [N], *rest)`` for the engine."""

    def logits(params, cfg, *args, **kw):
        x, *rest = rows(params, cfg, *args, **kw)
        return head_logits(params, cfg, x), *rest

    def greedy(params, cfg, *args, **kw):
        x, *rest = rows(params, cfg, *args, **kw)
        return head_argmax(params, cfg, x), *rest

    return logits, greedy


def default_chunk(chunk: int | None, max_seq: int) -> int:
    return chunk or min(256, max_seq)


def default_attn_block(attn_block: int | None, model_block: int, chunk: int,
                       max_seq: int, page_size: int) -> int:
    """Rows of one block of cached rows in a program that reads its pool
    a block at a time: the model's own where it divides ``max_seq``,
    else the chunk; a multiple of the page either way."""
    if attn_block is None:
        attn_block = model_block if max_seq % model_block == 0 else chunk
    assert attn_block % page_size == 0 and max_seq % attn_block == 0, (
        attn_block, page_size, max_seq,
    )
    return attn_block


def build_engine(name: str, cfg, params, *, window_program, chunk_step,
                 donate_window: tuple, donate_chunk: tuple, init_page_pool,
                 init_slot_state=None, counters, report, not_offered: dict,
                 flops_per_token: float, looks: dict | None = None,
                 max_slots: int, eos: int | None, page_size: int,
                 chunk: int, num_pages: int, window: int | None,
                 prefix_cache: bool | None, prefix_cache_pages: int | None,
                 state_snapshots: int = 0):
    """A ``PagedBatchEngine`` over a model's two programs.

    ``window_program(params, k, tokens, pools, counters, *rest)`` is the
    K-tick window (``rest``: positions, block tables, active, emitted,
    max_new and, with a slot state, the state last) and returns ``(the
    window's own results, counters, *looks)``. ``chunk_step`` is the
    prefill chunk, jitted under its own name as it stands: ``(params, ids,
    pools, counters, position, block_table, valid)`` -> ``(greedy, pools,
    counters)``, or with ``init_slot_state`` ``(params, ids, pools,
    counters, position, block_table, state, valid, slot)`` -> ``(greedy,
    pools, state, counters, *looks)``. Each keeps the argument order and
    the ``donate_*`` its model had before this function existed: the order
    is part of the lowered module.

    ``params`` ride as an ARGUMENT of both, never a closed-over constant:
    a closed-over array lowers to a constant, which would bake gigabytes
    of weights into the window program, the chunk program and every
    autotune rung. ``counters`` (a pytree of int32 device arrays) is
    argument 3 of both and comes back from both; the engine sees pools
    and state alone, and reads ``report(totals, engine) -> dict`` of the
    counters' host sums through ``engine.model_counters``. Where a program
    returns anything after its counters (an audit's look at a selection;
    nothing in a served engine) it is left in ``looks["window"]`` /
    ``looks["chunk"]``.

    ``not_offered`` names the serving knobs of the Qwen path that the
    model refuses, each with its reason. ``window``, ``prefix_cache`` and
    ``prefix_cache_pages`` left None take ``DORA_MULTISTEP_K`` (8),
    ``DORA_PREFIX_CACHE`` (off) and ``DORA_PREFIX_CACHE_PAGES`` (0).
    ``state_snapshots`` is a slot-state model's way to opt in to the
    prefix cache: the rows of the engine's snapshot pool (0 = none, and
    such a model with a prefix cache is refused); the pool's counters and
    gauges (``engine.snapshot_stats``) join the model's in ``report``."""
    for knob, why in not_offered.items():
        if os.environ.get(knob, "0") not in ("", "0"):
            raise NotImplementedError(f"{name}: {knob} is not offered: {why}")
    if window is None:
        window = int(os.environ.get("DORA_MULTISTEP_K", "8"))
    if prefix_cache is None:
        prefix_cache = os.environ.get("DORA_PREFIX_CACHE", "0") != "0"
    if prefix_cache_pages is None:
        prefix_cache_pages = int(os.environ.get("DORA_PREFIX_CACHE_PAGES", "0"))
    counters = DeviceCounters(counters)

    def keep(kind, look):
        if looks is not None:
            looks[kind] = look[0] if look else []

    def window_factory(k, sk):
        assert not sk, f"{name}: no speculative window"

        def program(p, *args):
            return window_program(p, k, *args)

        jitted = jax.jit(program, donate_argnums=donate_window)

        def window_step(tokens, pools, *rest):
            out, counters.device, *look = jitted(
                params, tokens, pools, counters.device, *rest)
            keep("window", look)
            return out

        return window_step

    chunk_jitted = jax.jit(chunk_step, donate_argnums=donate_chunk)

    if init_slot_state is None:
        def chunk_prefill(ids, pools, position, bt, valid):
            greedy, pools, counters.device = chunk_jitted(
                params, ids, pools, counters.device, position, bt, valid)
            return greedy, pools
    else:
        def chunk_prefill(ids, pools, position, bt, valid, slot, state):
            greedy, pools, state, counters.device, *look = chunk_jitted(
                params, ids, pools, counters.device, position, bt, state,
                valid, slot)
            keep("chunk", look)
            return greedy, pools, state

    engine = PagedBatchEngine(
        init_pool=init_page_pool,
        init_slot_state=init_slot_state,
        chunk_prefill=chunk_prefill,
        chunk_valid_rows=True,
        window_step=window_factory(window, 0),
        window_factory=window_factory,
        window=window,
        max_slots=max_slots,
        max_seq=cfg.max_seq,
        page_size=page_size,
        chunk=chunk,
        num_pages=num_pages,
        eos=eos,
        prefix_cache=prefix_cache,
        prefix_cache_pages=prefix_cache_pages,
        state_snapshots=state_snapshots,
    )
    engine.flops_per_token = flops_per_token
    engine.device_peak_flops = profiling.detect_peak_flops()

    # no cycle through the engine: it, its pools and the parameters its
    # programs hold go the moment their last holder lets go (a cache
    # audit's engine shares the chip with the reference that runs after it)
    alive = weakref.ref(engine)

    def model_counters() -> dict:
        counters.gained()
        engine = alive()
        return {**report(counters.totals, engine), **engine.snapshot_stats()}

    engine.model_counters = model_counters
    return engine
