"""Prompt-lookup speculative decoding — the shared loop.

Drafts are the continuation of the most recent earlier occurrence of
the sequence's trailing ngram (no draft model); a k+1-token
verification pass costs the same LM weight stream as one decode step,
so accepted drafts are nearly free, and every emitted token is an
argmax of the full model — output is bit-identical to vanilla greedy.

Three model families share this loop (models/vlm.py, models/hf/
qwen2_vl.py, models/hf/internvl.py); each supplies a ``verify``
closure that runs its own LM over the chunk (the only real difference
is position bookkeeping: M-RoPE vs standard RoPE). The KV cache stays
static-shape: verification writes positions p..p+k, and rejected tail
entries are provably overwritten before they become attendable (the
next chunk starts at the first rejected position).

Serving gates reserve ``spec_headroom()`` (k+1) tokens of max_seq
slack — the minimum one verification pass writes — so the loop can
never hit the context limit with tokens still owed (which would break
exactness). Round 5: ``DORA_SPEC_BODY`` fuses N passes per while body
(the while-loop equivalent of the decode scan's unroll), which can
overshoot by up to N-1 discarded passes after max_new; the out/history
buffers carry N*(k+1) of slack and callers pick the largest N whose
overshoot still fits max_seq (``fitting_body_passes``) — the k+1 gate
stays sufficient because N degrades to 1 in tight contexts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: Default draft length / lookup ngram; headroom every gate must check.
SPEC_K = 4
SPEC_NGRAM = 2
SPEC_HEADROOM = SPEC_K + 1  # single-pass slack; gates use spec_headroom()


def body_passes() -> int:
    """Speculation passes fused into one while_loop body (DORA_SPEC_BODY,
    default 4). Round-5 profiling showed the
    whole worst-case floor gap is the while_loop losing the decode
    scan's unroll amortization: a fused chunk-5 pass costs the SAME as
    one un-unrolled single step (0.99x), while unroll=4 makes single
    steps ~15% cheaper per token. Running N passes back to back inside
    one body removes N-1 loop boundaries per body — the while-loop
    equivalent of unroll. Cost: the loop can overshoot by up to N-1
    passes after max_new is reached (discarded tokens, headroom slack
    grows to N*(k+1))."""
    import os

    return max(1, int(os.environ.get("DORA_SPEC_BODY", "4")))


def spec_headroom(k: int = SPEC_K) -> int:
    """MINIMUM max_seq slack speculation needs (one pass of k+1 cache
    rows). The body factor degrades to fit (fitting_body_passes), so
    gates reserve only this — identical to the round-4 contract."""
    return k + 1


def fitting_body_passes(context_len: int, max_new_tokens: int,
                        max_seq: int, k: int = SPEC_K) -> int:
    """Largest body factor (≤ DORA_SPEC_BODY) whose overshoot slack
    still fits max_seq — tight-context configs degrade toward body=1
    (round-4 behavior) instead of refusing to speculate."""
    ppb = body_passes()
    while ppb > 1 and context_len + max_new_tokens + ppb * (k + 1) > max_seq:
        ppb //= 2
    return max(1, ppb)

#: Adaptive gating (round 4): speculation must never lose. A k+1-token
#: verification pass is ~15% dearer than a single decode step (extra
#: attention rows, the history lookup, and losing the vanilla scan's
#: unroll), so an adversarial non-repetitive stream that rejects every
#: draft would pay that tax on every pass. The loop therefore carries an
#: acceptance EMA: below ADAPT_THRESHOLD it takes single-token passes
#: (same cost as vanilla decode) and only probes a full chunk again once
#: the EMA has drifted back up — worst-case overhead is one probe in
#: ~ceil(threshold/(2*ADAPT_RECOVER)) passes. Exactness is untouched:
#: both branches emit argmaxes of the full model.
ADAPT_THRESHOLD = 0.3
ADAPT_ALPHA = 0.5     # EMA weight of the newest acceptance rate
ADAPT_RECOVER = 0.03  # drift per plain pass back toward probing


def lookup(history, hist_len, seq: int, k: int, ngram: int):
    """Draft k tokens from the most recent earlier occurrence of the
    trailing ngram; falls back to repeating the last token (any draft is
    safe — verification decides acceptance)."""
    tail_start = hist_len - ngram
    tail = jax.lax.dynamic_slice(
        history, (jnp.maximum(tail_start, 0),), (ngram,)
    )
    idx = jnp.arange(seq)
    windows = jnp.stack(
        [jnp.roll(history, -j) for j in range(ngram)], axis=-1
    )  # windows[i] = history[i : i+ngram] (wraparound masked below)
    match = jnp.all(windows == tail, axis=-1)
    valid = match & (idx + ngram <= hist_len - 1) & (idx < tail_start)
    m = jnp.max(jnp.where(valid, idx, -1))
    start = jnp.clip(m + ngram, 0, seq - k)
    draft = jax.lax.dynamic_slice(history, (start,), (k,))
    fallback = jnp.broadcast_to(
        jax.lax.dynamic_slice(history, (jnp.maximum(hist_len - 1, 0),), (1,)),
        (k,),
    )
    return jnp.where(m >= 0, draft, fallback)


def run_loop(*, caches, history, hist_len, first, max_new_tokens: int,
             seq: int, verify, k: int = SPEC_K, ngram: int = SPEC_NGRAM,
             adaptive: bool | None = None, return_stats: bool = False,
             body: int | None = None):
    """The speculation while_loop (call inside a jit).

    ``history`` is a [seq] int32 buffer holding the known token ids
    (prompt text + ``first``); ``hist_len`` is how many are filled.
    ``verify(chunk [1, W] int32, n_emitted, caches) -> (greedy [W],
    new_caches)`` runs the family's LM over the chunk (W is k+1 for a
    speculative pass, 1 for an adaptive plain pass — closures must size
    positions from ``chunk.shape[1]``), where greedy[i] is the argmax
    continuation of the prefix through chunk[0, i], and n_emitted counts
    tokens emitted so far (``first`` included) — the chunk's first token
    is generated index n_emitted-1.

    With ``adaptive`` (default), passes switch to single-token when the
    acceptance EMA falls below ADAPT_THRESHOLD — see the constants
    above — so throughput never drops below vanilla beyond the probe
    overhead, even on adversarial streams.

    Returns (tokens [1, max_new_tokens], model_passes); with
    ``return_stats`` additionally the number of full k+1 passes.
    """
    if adaptive is None:
        import os

        # Default OFF: measured on-chip the lax.cond dual-mode costs
        # ~1 ms/pass (the branch carries the KV pytree) — more than the
        # chunk/plain delta it saves; the fused M-row chunk verify is
        # the mechanism that actually bounds the worst case (round-4
        # speculation matrix).
        adaptive = os.environ.get("DORA_SPEC_ADAPTIVE", "0") not in ("", "0")
    ppb = body_passes() if body is None else max(1, body)
    out = jnp.zeros((max_new_tokens + ppb * (k + 1),), jnp.int32)
    out = out.at[0].set(first)

    def commit(carry, greedy, emitted, width, ema, spec_inc):
        caches_, history, hist_len, out, n_emitted, passes, _, spec_passes \
            = carry
        out = jax.lax.dynamic_update_slice(out, greedy, (n_emitted,))
        history = jax.lax.dynamic_update_slice(
            history,
            jnp.where(
                jnp.arange(width) < emitted,
                greedy,
                jax.lax.dynamic_slice(history, (hist_len,), (width,)),
            ),
            (hist_len,),
        )
        # Body-fused loops overshoot by up to body-1 passes after
        # max_new is reached; those passes' outputs are discarded, so
        # the stats only count passes that still owed tokens.
        useful = (n_emitted < max_new_tokens).astype(jnp.int32)
        return (
            caches_, history, hist_len + emitted, out,
            n_emitted + emitted, passes + useful, ema,
            spec_passes + spec_inc * useful,
        )

    def spec_pass(carry):
        import os

        caches_, history, hist_len, out, n_emitted, _, ema, _ = carry
        last = jax.lax.dynamic_slice(out, (n_emitted - 1,), (1,))[0]
        draft = lookup(history, hist_len, seq, k, ngram)
        if os.environ.get("DORA_SPEC_WORST_CASE"):
            # Measurement-only (read at trace time): force near-zero
            # acceptance to bench the adversarial-stream floor — drafts
            # an implausible arithmetic run instead of the lookup.
            draft = last + 1 + jnp.arange(k, dtype=jnp.int32)
        chunk = jnp.concatenate([last[None], draft])[None]  # [1, k+1]
        greedy, new_caches = verify(chunk, n_emitted, caches_)
        agree = greedy[:k] == draft
        # first mismatch index == number of accepted draft tokens
        accepted = jnp.argmin(jnp.concatenate([agree, jnp.zeros((1,), bool)]))
        emitted = accepted + 1  # accepted drafts + the bonus token
        ema = (1 - ADAPT_ALPHA) * ema + ADAPT_ALPHA * (accepted / k)
        carry = (new_caches, *carry[1:])
        return commit(carry, greedy, emitted, k + 1, ema,
                      jnp.asarray(1, jnp.int32))

    def plain_pass(carry):
        caches_, history, hist_len, out, n_emitted, _, ema, _ = carry
        last = jax.lax.dynamic_slice(out, (n_emitted - 1,), (1,))
        greedy, new_caches = verify(last[None], n_emitted, caches_)
        ema = jnp.minimum(ema + ADAPT_RECOVER, jnp.float32(1.0))
        carry = (new_caches, *carry[1:])
        return commit(carry, greedy, jnp.asarray(1, jnp.int32), 1, ema,
                      jnp.asarray(0, jnp.int32))

    if adaptive:
        def one_pass(carry):
            return jax.lax.cond(
                carry[6] >= ADAPT_THRESHOLD, spec_pass, plain_pass, carry
            )
    else:
        one_pass = spec_pass

    def body(carry):
        # N passes back to back per while iteration (see body_passes):
        # XLA overlaps the tail of pass i with the head of pass i+1 the
        # same way the vanilla decode scan's unroll does — without this,
        # each pass pays ~15% un-amortized step overhead and the
        # worst-case floor sits at ~0.86x instead of >=0.95x.
        for _ in range(ppb):
            carry = one_pass(carry)
        return carry

    def cond(carry):
        return carry[4] < max_new_tokens

    carry = (caches, history, hist_len, out, jnp.asarray(1, jnp.int32),
             jnp.asarray(1, jnp.int32), jnp.float32(1.0),
             jnp.asarray(0, jnp.int32))
    carry = jax.lax.while_loop(cond, body, carry)
    tokens = carry[3][:max_new_tokens][None]
    if return_stats:
        return tokens, carry[5], carry[7]
    return tokens, carry[5]


def check_headroom(context_len: int, max_new_tokens: int, max_seq: int,
                   what: str, k: int = SPEC_K) -> None:
    """Trace-time exactness guard shared by every entry point."""
    headroom = spec_headroom(k)
    total = context_len + max_new_tokens + headroom
    if total > max_seq:
        raise ValueError(
            f"{what} ({context_len}) + max_new_tokens ({max_new_tokens}) "
            f"+ speculation headroom ({headroom}) exceeds max_seq "
            f"({max_seq})"
        )


def fits(context_len: int, max_new_tokens: int, max_seq: int,
         k: int = SPEC_K) -> bool:
    """Gate helper for serving paths that degrade instead of raising."""
    return context_len + max_new_tokens + spec_headroom(k) <= max_seq


def gate_speculation(context_len: int, max_new_tokens: int, max_seq: int,
                     batch_ok: bool = True) -> bool:
    """The serving-path DORA_SPEC_DECODE gate, shared by every operator
    factory: True when the env asks for speculation AND the constraints
    (batch-1, k+1 headroom within max_seq) allow it; otherwise warns
    loudly and degrades to vanilla greedy."""
    import logging
    import os

    if not os.environ.get("DORA_SPEC_DECODE"):
        return False
    if batch_ok and fits(context_len, max_new_tokens, max_seq):
        return True
    logging.getLogger(__name__).warning(
        "DORA_SPEC_DECODE disabled: needs batch-1 and %d tokens of "
        "context within max_seq (%d); serving vanilla greedy",
        context_len + max_new_tokens + spec_headroom(), max_seq,
    )
    return False
