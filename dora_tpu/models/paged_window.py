"""The paged engine's K-tick decode window.

``models/batch_engine.PagedBatchEngine`` dispatches ONE device program a
window: ``k`` batched decode ticks under a ``lax.scan``, completion
detected on the device, one ``[B, k+1]`` token matrix fetched back.
:func:`make_paged_window` builds that program over any model's batched
paged decode step, :func:`make_paged_spec_window` the same with
prompt-lookup speculation folded into every tick; the two ``*_row_stats``
read one stream's row of the matrix on the host. Every serving model
file (``models/hf/``) wraps its step in one of the two; the engine reads
the matrix with the other two. No model lives here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dora_tpu.models import spec_decode
from dora_tpu.ops import decode_block as DB


def make_paged_window(step_fn, *, k: int, eos: int | None = None,
                      lora: bool = False, slot_state: bool = False):
    """Fused K-step decode window over a paged batch step.

    ONE jitted program runs ``k`` batched decode ticks on device,
    carrying ``(tokens, positions, active, emitted)`` plus the shared
    KV pools through a ``lax.scan``. Per-row completion — EOS hit or
    ``emitted >= max_new`` (``max_new`` ships as a per-slot device
    vector) — is detected ON DEVICE, and a finished row freezes
    mid-window: :func:`ops.decode_block.freeze_inactive` pins its
    position to 0 and zeroes its block-table row, routing the frozen
    row's KV writes to the reserved null page exactly like the
    engine's between-step masked-decode view. The host gets one
    ``[B, k+1]`` int32 matrix back — k emitted-token columns (``-1``
    where a row was already frozen) plus the final active mask as the
    last column — ONE device->host fetch per window instead of one per
    token.

    ``step_fn(tokens, pools, positions, bts) -> (greedy [B], pools)``
    is the family's batched paged decode closure (e.g.
    ``qwen2.fused_paged_batch_step`` partially applied). ``k`` and
    ``eos`` are closed over; every traced operand keeps a fixed [B] /
    [B, P] shape, so the window compiles exactly one XLA program ever
    (the PR-4 chunk-prefill discipline).

    Returns ``window(tokens, pools, positions, bts, active, emitted,
    max_new) -> (mat [B, k+1], tokens, positions, active, emitted,
    pools)`` — the carried state comes back so the host replaces its
    device refs and only rebuilds them when slot membership changes.

    With ``lora=True`` (multi-tenant adapter serving) the window takes
    two extra TRAILING traced operands — per-row adapter slot ids
    ``adapters [B]`` and the resident adapter stack pytree — and
    ``step_fn`` is called as ``step_fn(tokens, pools, positions, bts,
    adapters, lora_state)``. Both are fixed-shape (the stack's slot
    count never changes; admission/eviction rewrite contents), so the
    single-program discipline extends to adapter churn.

    With ``slot_state=True`` (a model that keeps a recurrent state per
    slot beside its pages; not together with ``lora``) the window takes
    ONE extra trailing operand, the slots' state pytree, carries it
    through the scan beside the pools and returns it last; ``step_fn``
    is called as ``step_fn(tokens, pools, positions, bts, active,
    state) -> (greedy, pools, state)``. It is told the rows' ``active``
    bits because a frozen row's K/V write can go to the null page but
    its state has none: the step must leave it as it was.
    """
    assert not (lora and slot_state), "no adapters over a slot state"

    def window(tokens, pools, positions, bts, active, emitted, max_new,
               *operands):
        # operands: (adapters, lora_state) with ``lora``, (state,) with
        # ``slot_state``; only the state is carried.
        def tick(carry, _):
            tokens, pools, positions, active, emitted, *state = carry
            alive = active.astype(jnp.int32)
            pos_in, bts_in = DB.freeze_inactive(positions, bts, active)
            if slot_state:
                nxt, pools, state[0] = step_fn(
                    tokens, pools, pos_in, bts_in, active, state[0]
                )
            elif lora:
                nxt, pools = step_fn(
                    tokens, pools, pos_in, bts_in, *operands
                )
            else:
                nxt, pools = step_fn(tokens, pools, pos_in, bts_in)
            out = jnp.where(active, nxt, -1)  # -1 = row was frozen
            emitted = emitted + alive
            done = emitted >= max_new
            if eos is not None:
                done = done | (nxt == eos)
            # A frozen row keeps its last real token/position so the
            # host never has to rewrite them before the next window.
            tokens = jnp.where(active, nxt, tokens)
            positions = pos_in + alive
            active = active & ~done
            return (tokens, pools, positions, active, emitted, *state), out

        carried = operands if slot_state else ()
        (tokens, pools, positions, active, emitted, *state), toks = (
            jax.lax.scan(
                tick, (tokens, pools, positions, active, emitted, *carried),
                None, length=k,
            )
        )
        mat = jnp.concatenate(
            [toks.T, active.astype(jnp.int32)[:, None]], axis=1
        )
        return (mat, tokens, positions, active, emitted, pools, *state)

    return window


def make_paged_spec_window(spec_step_fn, *, k: int, spec_k: int,
                           ngram: int, eos: int | None = None,
                           lora: bool = False):
    """Fused K-step decode window with prompt-lookup SPECULATION folded
    into every tick: one dispatch can emit up to ``k * (spec_k + 1)``
    tokens per stream instead of ``k``.

    Each of the ``k`` scanned ticks, per stream and entirely on device:
    draft ``spec_k`` tokens by trailing-ngram lookup against that
    stream's history buffer (models/spec_decode.lookup, vmapped over
    slots), verify the (last token + drafts) chunk in ONE batched
    chunk pass through ``spec_step_fn``, accept the longest agreeing
    prefix plus the bonus token (the serial ``run_loop`` test,
    verbatim), then append the emissions to the history carry and
    advance the stream's position by the accepted length — so rejected
    tail rows in the paged KV are overwritten by the next chunk before
    any sweep can attend them (the spec_decode invariant). Mid-chunk
    completion is honoured exactly like the base window's mid-window
    completion: an EOS or ``max_new`` hit at candidate i truncates the
    tick's emission at i and freezes the stream
    (:func:`ops.decode_block.freeze_inactive` null-page routing,
    unchanged).

    ``spec_step_fn(chunks [B, spec_k+1], pools, positions, bts) ->
    (greedy [B, spec_k+1], pools)`` is the family's batched paged
    verification closure (e.g. ``qwen2.fused_paged_spec_step``
    partially applied).

    Emission is RAGGED: the host gets one ``[B, k*(spec_k+1) + 1]``
    int32 matrix — k tick-blocks of spec_k+1 token columns, ``-1``
    sentinels padding each tick past its accepted length (and filling
    whole blocks for frozen streams), plus the final active mask as
    the last column. The host unpacks it by replaying the same
    acceptance/completion walk (the PR-5 device/host contract), so
    device and host can never disagree on what was emitted.

    Returns ``window(tokens, pools, positions, bts, active, emitted,
    max_new, history, hist_len) -> (mat, tokens, positions, active,
    emitted, pools, history, hist_len)`` — two extra carried device
    buffers vs the base window: per-stream token history
    ``[B, hist_buf]`` and its lengths ``[B]``, which the engine
    rebuilds from its host mirror only when slot membership changes.

    With ``lora=True`` the window takes the same two extra TRAILING
    operands as :func:`make_paged_window` (``adapters [B]`` and the
    resident adapter stack) and the verification pass is called as
    ``spec_step_fn(chunks, pools, positions, bts, adapters,
    lora_state)`` — drafts AND verify read the tenant's own adapter,
    so acceptance is self-consistent per tenant.
    """
    m = spec_k + 1

    def window(tokens, pools, positions, bts, active, emitted, max_new,
               history, hist_len, adapters=None, lora_state=None):
        hbuf = history.shape[1]
        nslots = tokens.shape[0]

        def tick(carry, _):
            (tokens, pools, positions, active, emitted, history,
             hist_len) = carry
            alive = active.astype(jnp.int32)
            pos_in, bts_in = DB.freeze_inactive(positions, bts, active)
            draft = jax.vmap(
                lambda h, hl: spec_decode.lookup(h, hl, hbuf, spec_k, ngram)
            )(history, hist_len)  # [B, spec_k]
            chunks = jnp.concatenate([tokens[:, None], draft], axis=1)
            if lora:
                greedy, pools = spec_step_fn(
                    chunks, pools, pos_in, bts_in, adapters, lora_state
                )
            else:
                greedy, pools = spec_step_fn(chunks, pools, pos_in, bts_in)
            # The serial acceptance test (spec_decode.run_loop),
            # vectorised: longest agreeing draft prefix + bonus token.
            agree = greedy[:, :spec_k] == draft
            accepted = jnp.argmin(
                jnp.concatenate(
                    [agree, jnp.zeros((nslots, 1), bool)], axis=1
                ).astype(jnp.int32), axis=1,
            )
            n_emit = accepted + 1  # [B] — always >= 1 (bonus token)
            # Mid-chunk completion: candidate i is the
            # (emitted+i+1)-th token; the first accepted candidate
            # that hits EOS or max_new truncates the emission AT that
            # token and freezes the stream.
            idx = jnp.arange(m)[None, :]
            in_acc = idx < n_emit[:, None]
            stop = (emitted[:, None] + idx + 1) >= max_new[:, None]
            if eos is not None:
                stop = stop | (greedy == eos)
            stop = stop & in_acc
            has_stop = jnp.any(stop, axis=1)
            first_stop = jnp.argmax(stop.astype(jnp.int32), axis=1)
            e = jnp.where(has_stop, first_stop + 1, n_emit) * alive
            out = jnp.where((idx < e[:, None]) & active[:, None], greedy, -1)
            last = jnp.take_along_axis(
                greedy, jnp.maximum(e - 1, 0)[:, None], axis=1
            )[:, 0]
            # A frozen row keeps its last real token (base-window
            # contract); e is already 0 there so positions / emitted /
            # history stay pinned too.
            tokens = jnp.where(active, last, tokens)
            positions = pos_in + e
            emitted = emitted + e
            active = active & ~has_stop

            def commit(h, hl, cand, ee):
                w = jax.lax.dynamic_slice(h, (hl,), (m,))
                w = jnp.where(jnp.arange(m) < ee, cand, w)
                return jax.lax.dynamic_update_slice(h, w, (hl,))

            history = jax.vmap(commit)(history, hist_len, greedy, e)
            hist_len = hist_len + e
            return (tokens, pools, positions, active, emitted, history,
                    hist_len), out

        (tokens, pools, positions, active, emitted, history,
         hist_len), toks = jax.lax.scan(
            tick,
            (tokens, pools, positions, active, emitted, history, hist_len),
            None, length=k,
        )
        flat = toks.transpose(1, 0, 2).reshape(nslots, k * m)
        mat = jnp.concatenate(
            [flat, active.astype(jnp.int32)[:, None]], axis=1
        )
        return (mat, tokens, positions, active, emitted, pools, history,
                hist_len)

    return window


def window_row_stats(row, k: int) -> tuple[int, int | None]:
    """Decode one stream's row of the window's ``[B, k+1]`` token matrix
    into ``(emitted, frozen_at)``: how many real tokens the row emitted
    this window and the tick index at which the device froze it (None if
    it ran the full window). Columns past a row's completion hold the
    ``-1`` sentinel; column ``k`` is the final active flag, not a token.
    Host-side observability helper (engine span details, TTFT tick
    offsets) — never traced."""
    emitted = 0
    for j in range(k):
        if int(row[j]) < 0:
            return emitted, j
        emitted += 1
    return emitted, (None if int(row[k]) else k)


def spec_window_row_stats(row, k: int, m: int) -> tuple[int, int | None]:
    """Ragged counterpart of :func:`window_row_stats` for the spec
    window's ``[B, k*m + 1]`` matrix (m = spec_k + 1): returns
    ``(emitted, frozen_at)`` where ``emitted`` counts the row's real
    tokens across all k tick-blocks and ``frozen_at`` is the tick on
    which the device froze the stream (None if still active after the
    window). Within a tick-block a ``-1`` only pads past the accepted
    length — the stream may well emit again next tick — so freezing is
    read from the final active flag, not from the first sentinel."""
    emitted = 0
    last_live = None
    for t in range(k):
        got = 0
        for i in range(m):
            if int(row[t * m + i]) < 0:
                break
            got += 1
        if got:
            last_live = t
        emitted += got
    if int(row[k * m]):
        return emitted, None
    return emitted, (last_live if last_live is not None else 0)
