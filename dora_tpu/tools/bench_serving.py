"""A/B legs of the paged serving engine, one JSON line on stdout each.

``--multistep``: the K-sweep of the fused
multi-step decode window (K in {1, 4, 8, 16}) at 4 and 16 streams,
reporting HOST ROUND-TRIPS (engine dispatches + device->host fetches)
per emitted token next to tok/s. Round-trips are host-side counts:
the dispatch-amortization claim rides the counters, not the clock.

Model: ``DORA_HF_CHECKPOINT`` when set (real numbers on the TPU box);
otherwise a tiny random Qwen2 is built in-process and the numbers are
relative-only (CPU smoke A/B, same code path).

``--trace-ab``: the 16-stream paged run with the
serving observability plane attached, tracing off vs on (interleaved),
reporting the wall-clock overhead of the request-lifecycle span
records — the serving counterpart of bench.py's recorder A/B gate
(≤3%).

``--spec-ab``: speculative decoding inside the
fused window (DORA_SPEC_K), spec_k in {0, 2, 4} x K in {1, 8} on the
stub engine's repetitive (best-case acceptance) and random (worst-case)
token rules — tokens per dispatch and acceptance rate per cell.

``--qos-soak``: open-loop Poisson mixed-class
overload through the REAL serve() admission path (stub engine, no
weights), QoS shaping on vs off over the identical arrival trace —
per-class TTFT p50/p99, shed rate, preempt/resume counts. The
acceptance headline is ``interactive_p99_on_vs_off`` < 1.0: shaping
must buy the interactive class latency under overload, paid for by the
batch class, never by silent loss (completion accounting rides along).

``--prefix-ab``: the shared-prefix KV cache at
admission (DORA_PREFIX_CACHE), a Zipf-popular template workload (hot
system prompts, unique tails) replayed open-loop with the cache on vs
off over the identical arrival trace — hit rate, TTFT p50/p99 for hit
requests vs the same requests uncached, prefill-chunk deltas, pool
occupancy. The acceptance headline is ``hit_p50_on_vs_off`` <= 0.5: a
cache hit must at least halve first-token latency to justify the
serving default-on.

``--quant-ab``: quantized serving
(DORA_KV_INT8 / DORA_WEIGHT_BITS) — the same 4-stream workload on fp
vs int8-KV vs int8-KV + int4-weight engines (greedy token agreement
against the fp leg rides along), plus a capacity leg that counts how
many concurrent streams each KV dtype admits into the SAME pool byte
budget through the real ``can_admit``/``submit`` path. The
acceptance headline is ``int8_capacity_ratio`` >= 1.8 (a
spec-acceptance leg rides along: acceptance counters under int8 KV vs
fp — the round-18 drift signal).

``--lora-ab``: multi-tenant LoRA serving — the
aggregate tokens/s of ONE paged engine serving N adapter tenants vs N
separate engines splitting the same HBM budget, plus an adapter-churn
leg asserting zero steady-state compiles while tenants rotate through
the resident budget. The acceptance headline is
``lora_aggregate_ratio`` >= 1.5.

Usage::

    python -m dora_tpu.tools.bench_serving (--multistep | --trace-ab |
                                            --spec-ab | --qos-soak |
                                            --prefix-ab | --quant-ab |
                                            --lora-ab)
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import tempfile
import time
from collections import deque


def _tiny_checkpoint(tmp: str) -> str:
    import torch
    from transformers import Qwen2Config, Qwen2ForCausalLM

    config = Qwen2Config(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rope_theta=10000.0,
        rms_norm_eps=1e-6, tie_word_embeddings=False,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    Qwen2ForCausalLM(config).eval().save_pretrained(
        tmp, safe_serialization=True
    )
    return tmp


def _serve(engine, prompts, max_new: int):
    """Push every request at t0, drain to completion. Returns
    (tokens_emitted, wall_s, ttft_s per request) — TTFT includes queue
    wait, which is the point: an engine that can't admit pays it."""
    backlog = deque(enumerate(prompts))
    t0 = time.perf_counter()
    ttft: dict[int, float] = {}
    tokens = 0
    active_keys: set[int] = set()
    while backlog or active_keys:
        while backlog and engine.can_admit(len(backlog[0][1]), max_new):
            rid, ids = backlog.popleft()
            active_keys.add(rid)
            engine.submit(str(rid), ids, max_new)
        for key, _token, done in engine.step():
            rid = int(key)
            tokens += 1
            ttft.setdefault(rid, time.perf_counter() - t0)
            if done:
                active_keys.discard(rid)
    return tokens, time.perf_counter() - t0, list(ttft.values())


def _stats(tokens: int, wall: float, ttfts: list[float]) -> dict:
    ordered = sorted(ttfts)
    return {
        "decode_tok_s": round(tokens / wall, 1) if wall > 0 else None,
        "wall_s": round(wall, 3),
        "tokens": tokens,
        "ttft_p50_ms": round(statistics.median(ordered) * 1e3, 1),
        "ttft_p99_ms": round(
            ordered[min(len(ordered) - 1, int(len(ordered) * 0.99))] * 1e3, 1
        ),
    }


def _multistep_sweep(qwen2, path: str, real: bool) -> dict:
    """K-sweep of the multi-step decode window: host round-trips per
    emitted token + tok/s at K in {1, 4, 8, 16}, 4 and 16 streams.

    The workload is decode-heavy on purpose (short prompts, long
    generations): the window amortizes per-TOKEN dispatch/fetch cost,
    so the regime where decode dominates prefill is the one the ≥4x
    K=8-vs-K=1 round-trip gate is stated for. Warmup legs run short
    (shapes are identical regardless of max_new, so compiles are the
    same); measured legs read counter DELTAS around the run."""
    import jax
    import numpy as np

    # A longer cache than the engine-A/B smoke so generations are long
    # enough for decode to dominate (tiny CPU: 4-token prompts, 120 new
    # tokens inside max_seq 128).
    if real:
        max_seq = int(os.environ.get("DORA_MAX_SEQ", "512"))
        page_size, chunk, plen = 16, 64, 64
        max_new = {4: min(256, max_seq - plen), 16: 32}
    else:
        max_seq, page_size, chunk, plen = 128, 8, 8, 4
        max_new = {4: 120, 16: 24}

    cfg, params = qwen2.load(path, max_seq=max_seq)
    os.environ.setdefault("DORA_INT8_DECODE", "1")
    params = qwen2.quantize_decode(params, cfg)
    rng = np.random.default_rng(3)

    def prompts(n: int) -> list[list[int]]:
        return [
            rng.integers(0, cfg.vocab, size=plen).tolist() for _ in range(n)
        ]

    out: dict = {
        "backend": jax.default_backend(),
        "model": "checkpoint" if real else "tiny-random",
        "plen": plen,
        "max_new": {str(s): m for s, m in max_new.items()},
        "k_sweep": {},
    }
    per_k: dict[int, dict] = {}
    for streams in (4, 16):
        leg: dict = {}
        for k in (1, 4, 8, 16):
            engine = qwen2.make_paged_engine(
                params, cfg, max_slots=streams, page_size=page_size,
                chunk=chunk, window=k,
            )
            _serve(engine, prompts(streams), 4)  # warmup: compile only
            d0, f0 = engine.dispatches, engine.fetches
            tokens, wall, ttfts = _serve(
                engine, prompts(streams), max_new[streams]
            )
            trips = (engine.dispatches - d0) + (engine.fetches - f0)
            stats = _stats(tokens, wall, ttfts)
            stats["round_trips"] = trips
            stats["rt_per_token"] = round(trips / tokens, 4)
            stats["tokens_per_dispatch"] = round(
                tokens / (engine.dispatches - d0), 2
            )
            leg[f"k{k}"] = stats
        out["k_sweep"][f"streams{streams}"] = leg
        per_k[streams] = leg
    # The acceptance headline: K=8 vs K=1 round-trips per token at 4
    # streams (the decode-dominated leg).
    s4 = per_k[4]
    out["k8_vs_k1_rt_reduction"] = round(
        s4["k1"]["rt_per_token"] / s4["k8"]["rt_per_token"], 2
    )
    return out


def _trace_ab(qwen2, path: str, real: bool) -> dict:
    """Serving-span instrumentation overhead: the 16-stream paged run
    with the full observability plane attached (ServingTracer +
    ServingMetrics on the engine, lifecycle spans through the
    flight-recorder ring) A/B'd tracing-off vs tracing-on, trials
    interleaved so both sides see the same machine conditions — the
    recorder-A/B methodology from bench.py's message-plane legs applied
    to the engine step path. Both sides carry the tracer and metrics
    objects; the off side pays exactly what production pays without
    ``DORA_TRACING=1`` (one attribute check per hook site), so
    ``overhead_pct`` isolates the span records themselves."""
    import numpy as np

    from dora_tpu import telemetry
    from dora_tpu.metrics import ServingMetrics

    if real:
        max_seq = int(os.environ.get("DORA_MAX_SEQ", "512"))
        page_size, chunk, plen, max_new = 16, 64, 64, 32
    else:
        max_seq, page_size, chunk, plen, max_new = 64, 8, 8, 4, 8

    cfg, params = qwen2.load(path, max_seq=max_seq)
    os.environ.setdefault("DORA_INT8_DECODE", "1")
    params = qwen2.quantize_decode(params, cfg)
    rng = np.random.default_rng(7)

    def prompts(n: int) -> list[list[int]]:
        return [
            rng.integers(0, cfg.vocab, size=plen).tolist() for _ in range(n)
        ]

    engine = qwen2.make_paged_engine(
        params, cfg, max_slots=16, page_size=page_size, chunk=chunk
    )
    engine.serving_metrics = ServingMetrics("paged")
    tracer = telemetry.ServingTracer()
    engine.tracer = tracer
    _serve(engine, prompts(16), max_new)  # warmup: compiles only
    trials = int(os.environ.get("DORA_BENCH_TRIALS", "5"))
    walls: dict[str, list[float]] = {"off": [], "on": []}
    span_events = 0
    for _ in range(trials):
        for mode in ("off", "on"):
            on = mode == "on"
            telemetry.TRACING.active = on
            telemetry.FLIGHT.enabled = on
            telemetry.FLIGHT.clear()
            for i in range(16):
                tracer.begin(str(i))
            _tokens, wall, _ = _serve(engine, prompts(16), max_new)
            for i in range(16):
                tracer.finish(str(i))
            if on:
                span_events = len(telemetry.FLIGHT.events())
            walls[mode].append(wall)
    telemetry.TRACING.active = False
    telemetry.FLIGHT.enabled = False
    off_w = statistics.median(walls["off"])
    on_w = statistics.median(walls["on"])
    return {
        "off_wall_s": round(off_w, 4),
        "on_wall_s": round(on_w, 4),
        "overhead_pct": (
            round((on_w - off_w) / off_w * 100, 2) if off_w else None
        ),
        "span_events_per_run": span_events,
        "trials": trials,
    }


def _serve_tokens(engine, prompts, max_new: int):
    """Like :func:`_serve` for paged engines, but keeps each stream's
    emitted token sequence — the quant A/B compares greedy tokens
    per position, not just counts."""
    backlog = deque(enumerate(prompts))
    seqs: dict[int, list[int]] = {i: [] for i in range(len(prompts))}
    active: set[int] = set()
    t0 = time.perf_counter()
    ttft: dict[int, float] = {}
    while backlog or active:
        while backlog and engine.can_admit(len(backlog[0][1]), max_new):
            rid, ids = backlog.popleft()
            active.add(rid)
            engine.submit(str(rid), ids, max_new)
        for key, token, done in engine.step():
            rid = int(key)
            seqs[rid].append(int(token))
            ttft.setdefault(rid, time.perf_counter() - t0)
            if done:
                active.discard(rid)
    return seqs, time.perf_counter() - t0, list(ttft.values())


def _quant_ab(qwen2, path: str, real: bool) -> dict:
    """Quantized-serving A/B behind ``--quant-ab``: throughput + greedy
    token agreement for fp-KV vs int8-KV vs int8-KV + int4-weight
    engines on the identical prompt set, then a capacity leg counting
    concurrent admissions into the SAME pool byte budget (the int8
    pool is auto-resized into the fp pool's HBM bytes by
    ``make_paged_engine``; per-page scale planes are part of the
    footprint). Agreement is a per-position token match fraction vs
    the fp leg — 1.0 for the int8-KV leg on the tiny CI model,
    expected slightly below on real models with near-tie continuations
    (KNOWN_ISSUES round 18). The w4 leg's agreement measures the
    *weight* quantization (int4 weights are a different model, so low
    agreement there is expected and not a KV-error signal)."""
    import jax
    import numpy as np

    if real:
        max_seq = int(os.environ.get("DORA_MAX_SEQ", "512"))
        page_size, chunk, plen, max_new = 16, 64, 64, 64
    else:
        max_seq, page_size, chunk, plen, max_new = 64, 8, 8, 4, 24

    cfg, params = qwen2.load(path, max_seq=max_seq)
    os.environ.setdefault("DORA_INT8_DECODE", "1")
    params8 = qwen2.quantize_decode(params, cfg)
    prev = os.environ.get("DORA_WEIGHT_BITS")
    os.environ["DORA_WEIGHT_BITS"] = "4"
    try:
        params4 = qwen2.quantize_decode(params, cfg)
    finally:
        if prev is None:
            del os.environ["DORA_WEIGHT_BITS"]
        else:
            os.environ["DORA_WEIGHT_BITS"] = prev
    rng = np.random.default_rng(11)
    work = [
        rng.integers(0, cfg.vocab, size=plen).tolist() for _ in range(4)
    ]

    out: dict = {
        "backend": jax.default_backend(),
        "model": "checkpoint" if real else "tiny-random",
        "plen": plen,
        "max_new": max_new,
        "streams": 4,
    }
    seqs_by_leg: dict[str, dict[int, list[int]]] = {}
    for name, leg_params, kv8 in (
        ("fp", params8, False),
        ("kv_int8", params8, True),
        ("kv_int8_w4", params4, True),
    ):
        engine = qwen2.make_paged_engine(
            leg_params, cfg, max_slots=4, page_size=page_size,
            chunk=chunk, kv_int8=kv8,
        )
        _serve_tokens(engine, work, 4)  # warmup: compiles only
        seqs, wall, ttfts = _serve_tokens(engine, work, max_new)
        tokens = sum(len(s) for s in seqs.values())
        stats = _stats(tokens, wall, ttfts)
        stats["kv_dtype"] = engine.kv_dtype
        stats["pool_bytes"] = sum(
            int(x.nbytes) for x in jax.tree.leaves(engine.pools)
        )
        out[name] = stats
        seqs_by_leg[name] = seqs

    def agree(ref: dict, other: dict):
        total = match = 0
        for rid, ref_seq in ref.items():
            for a, b in zip(ref_seq, other.get(rid, [])):
                total += 1
                match += int(a == b)
        return round(match / total, 4) if total else None

    out["greedy_agreement_vs_fp"] = {
        "kv_int8": agree(seqs_by_leg["fp"], seqs_by_leg["kv_int8"]),
        "kv_int8_w4": agree(seqs_by_leg["fp"], seqs_by_leg["kv_int8_w4"]),
    }

    # Capacity leg: admission-path head count. Both engines get the
    # default pool BYTE budget (int8 auto-resizes page count into it);
    # streams are admitted through the real can_admit/submit page
    # granting until the pool refuses. No step() runs — admission is
    # host-side bookkeeping, so the leg holds zero compiles.
    cap: dict[str, dict] = {}
    for name, kv8 in (("fp", False), ("int8", True)):
        engine = qwen2.make_paged_engine(
            params8, cfg, max_slots=512, page_size=page_size,
            chunk=chunk, kv_int8=kv8,
        )
        n = 0
        while n < 512 and engine.can_admit(plen, max_new):
            engine.submit(f"cap{n}", work[0], max_new)
            n += 1
        cap[name] = {
            "streams": n,
            "pool_bytes": sum(
                int(x.nbytes) for x in jax.tree.leaves(engine.pools)
            ),
            "usable_pages": engine.allocator.num_pages - 1,
        }
    out["capacity"] = {
        "fp": cap["fp"],
        "int8": cap["int8"],
        "pool_budget_ratio": round(
            cap["int8"]["pool_bytes"] / cap["fp"]["pool_bytes"], 3
        ),
        # The acceptance headline: concurrent streams admitted into the
        # same HBM footprint, int8 vs fp (gate: >= 1.8).
        "int8_capacity_ratio": round(
            cap["int8"]["streams"] / cap["fp"]["streams"], 2
        ),
    }

    # Spec-acceptance leg: the round-18 guidance is that under int8 KV
    # the SIGNAL is the acceptance counters, not token identity — a
    # near-tie continuation that flips under rounding shows up as a
    # drafted-token rejection long before it shows up in quality evals.
    # Run the identical workload with speculation on for fp vs int8 KV
    # and report the acceptance fraction per leg; bench_trend watches
    # ``spec.spec_acceptance`` (the int8 leg) for downward drift.
    from dora_tpu.metrics import ServingMetrics

    spec: dict = {}
    for name, kv8 in (("fp", False), ("int8", True)):
        engine = qwen2.make_paged_engine(
            params8, cfg, max_slots=4, page_size=page_size,
            chunk=chunk, kv_int8=kv8, spec_k=2,
        )
        _serve_tokens(engine, work, 4)  # warmup: compiles only
        engine.serving_metrics = ServingMetrics(engine="paged")
        _serve_tokens(engine, work, max_new)
        sm = engine.serving_metrics
        spec[f"acceptance_{name}"] = (
            round(sm.spec_accepted / sm.spec_drafted, 4)
            if sm.spec_drafted else None
        )
        spec[f"drafted_{name}"] = sm.spec_drafted
    spec["spec_acceptance"] = spec["acceptance_int8"]
    out["spec"] = spec
    return out


def _lora_ab() -> dict:
    """Multi-tenant LoRA A/B behind ``--lora-ab``: aggregate tokens/s
    of ONE paged engine serving N adapter tenants vs N separate
    engines splitting the same HBM budget (pages and slots divided
    N ways), identical per-tenant workload. The separate engines run
    to completion back to back and their walls sum — the timesharing
    model of N single-tenant engines on one host. The shared engine
    amortizes every fused K-window dispatch across all tenants'
    streams, which is the whole perf claim: the acceptance headline is
    ``lora_aggregate_ratio`` >= 1.5.

    A churn leg rides along: with a resident budget of 2 slots, 6
    tenants rotate through admission/eviction while the XLA compile
    listener counts backend compiles — the adapter id is traced DATA,
    so steady-state churn must hold ZERO compiles
    (``churn.steady_state_compiles``)."""
    from dora_tpu import telemetry
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    tenants, per_tenant, max_new = 4, 2, 64
    max_seq, page_size, chunk, pages = 128, 8, 16, 64
    # Every engine pays this per window dispatch: the decode window on
    # real hardware is weight-streaming-bound, so its cost is ~flat in
    # active slots — which is exactly what the multi-tenant claim
    # amortizes. The bare CPU stub's ~free step would instead measure
    # host token bookkeeping (identical on both sides) and bury the
    # dispatch-count difference the A/B exists to show.
    step_cost_s = 0.002
    names = [f"tenant-{i}" for i in range(tenants)]
    prompts = {n: [[3 + i], [11 + i]] for i, n in enumerate(names)}

    def serve_tenants(engine, work):
        """(key, ids, adapter) triples, pushed at t0, drained."""
        backlog = deque(work)
        active: set[str] = set()
        tokens = 0
        t0 = time.perf_counter()
        while backlog or active:
            while backlog and engine.can_admit(
                len(backlog[0][1]), max_new, backlog[0][2]
            ):
                key, ids, ad = backlog.popleft()
                active.add(key)
                engine.submit(key, ids, max_new, adapter=ad)
            for key, _tok, done in engine.step():
                tokens += 1
                if done:
                    active.discard(key)
        return tokens, time.perf_counter() - t0

    out: dict = {
        "tenants": tenants,
        "streams_per_tenant": per_tenant,
        "max_new": max_new,
        "pool_pages": pages,
    }

    # Shared: one engine, all tenants resident, every stream concurrent.
    shared = make_stub_paged_engine(
        max_slots=tenants * per_tenant, max_seq=max_seq,
        page_size=page_size, chunk=chunk, num_pages=pages,
        lora_max_resident=tenants, tick_sleep_s=step_cost_s,
    )
    work = [
        (f"{n}/{j}", ids, n)
        for n in names for j, ids in enumerate(prompts[n])
    ]
    serve_tenants(shared, work)  # warmup: compiles only
    tokens, wall = serve_tenants(shared, work)
    out["shared"] = {
        "tokens": tokens,
        "wall_s": round(wall, 3),
        "tok_s": round(tokens / wall, 1),
    }

    # Separate: N plain engines, each with 1/N of the pages and slots
    # (same total HBM), each serving only its own tenant's streams.
    sep_tokens = sep_wall = 0.0
    engines = [
        make_stub_paged_engine(
            max_slots=per_tenant, max_seq=max_seq, page_size=page_size,
            chunk=chunk, num_pages=max(2, pages // tenants),
            tick_sleep_s=step_cost_s,
        )
        for _ in names
    ]
    for engine, n in zip(engines, names):
        serve_tenants(
            engine, [(f"{n}/w{j}", ids, None)
                     for j, ids in enumerate(prompts[n])]
        )  # warmup
    for engine, n in zip(engines, names):
        t, w = serve_tenants(
            engine, [(f"{n}/{j}", ids, None)
                     for j, ids in enumerate(prompts[n])]
        )
        sep_tokens += t
        sep_wall += w
    out["separate"] = {
        "tokens": int(sep_tokens),
        "wall_s": round(sep_wall, 3),
        "tok_s": round(sep_tokens / sep_wall, 1),
        "pages_each": max(2, pages // tenants),
    }
    # The acceptance headline: aggregate throughput, one multi-tenant
    # engine vs N single-tenant engines in the same byte budget
    # (gate: >= 1.5).
    out["lora_aggregate_ratio"] = round(
        out["shared"]["tok_s"] / out["separate"]["tok_s"], 2
    )

    # Churn leg: 6 tenants through a 2-slot resident budget. Adapter
    # ids are traced data and the stacked pool has a fixed shape, so
    # once the window shapes are warm, admission/eviction churn must
    # not recompile anything.
    churn = make_stub_paged_engine(
        max_slots=2, max_seq=max_seq, page_size=page_size, chunk=chunk,
        num_pages=pages, lora_max_resident=2,
    )
    churn_names = [f"churn-{i}" for i in range(6)]
    serve_tenants(
        churn, [(f"warm/{n}", [5], n) for n in churn_names[:2]]
    )  # warmup: compile the lora window shapes
    telemetry.install_compile_listener()
    c0 = telemetry.compile_count()
    for cycle in range(2):
        for n in churn_names:
            serve_tenants(churn, [(f"{cycle}/{n}", [7], n)])
    out["churn"] = {
        "adapters": len(churn_names),
        "resident_budget": 2,
        "loads": churn.lora.loads,
        "evictions": churn.lora.evictions,
        "steady_state_compiles": telemetry.compile_count() - c0,
    }
    return out


def _spec_ab() -> dict:
    """Speculative decoding A/B behind ``--spec-ab``: acceptance rate x
    tokens-per-dispatch, spec_k in {0, 2, 4} crossed with window K in
    {1, 8}, on the stub paged engine — the REAL spec window program
    (ngram lookup, k+1-row verify, ragged emission) over a weight-free
    token rule, so ACCEPTANCE is controlled by construction instead of
    depending on what a tiny random model happens to repeat:

    * ``repetitive`` — the period-4 cycle rule, prompt-lookup's best
      case (looping/templated text): drafts come true, every verify
      accepts, dispatches collapse.
    * ``random`` — the affine full-period rule: a trailing ngram's
      continuation never repeats, ~0% acceptance, every dispatch pays
      the k+1-row verify for one token — the worst-case overhead leg.

    Tokens-per-dispatch reads host counter deltas around the measured
    run (warmup leg compiles the shapes), the same methodology as the
    ``--multistep`` sweep — counts, not clocks."""
    from dora_tpu.metrics import ServingMetrics
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    max_seq, page_size, chunk, max_new, streams = 128, 8, 16, 96, 4
    prompts = [[5], [6], [7], [8]]
    out: dict = {
        "max_new": max_new,
        "streams": streams,
        "legs": {},
    }
    for leg, cycle in (("repetitive", 4), ("random", None)):
        leg_out: dict = {}
        for K in (1, 8):
            for k in (0, 2, 4):
                engine = make_stub_paged_engine(
                    max_slots=streams, max_seq=max_seq,
                    page_size=page_size, chunk=chunk, window=K,
                    spec_k=k, cycle=cycle,
                )
                _serve(engine, prompts, 4)  # warmup: compile only
                engine.serving_metrics = ServingMetrics(engine="paged")
                d0 = engine.dispatches
                tokens, _wall, _ = _serve(engine, prompts, max_new)
                sm = engine.serving_metrics
                leg_out[f"k{k}_K{K}"] = {
                    "tokens": tokens,
                    "dispatches": engine.dispatches - d0,
                    "tokens_per_dispatch": round(
                        tokens / (engine.dispatches - d0), 2
                    ),
                    "acceptance": (
                        round(sm.spec_accepted / sm.spec_drafted, 3)
                        if sm.spec_drafted
                        else None
                    ),
                }
        out["legs"][leg] = leg_out
    # Acceptance headlines: spec-on vs spec-off at the shipped window
    # (K=8) — the >=1.5x repetitive gate and the <=10% random-leg
    # regression bound.
    rep, rnd = out["legs"]["repetitive"], out["legs"]["random"]
    out["rep_k4_vs_k0_tpd_at_k8"] = round(
        rep["k4_K8"]["tokens_per_dispatch"]
        / rep["k0_K8"]["tokens_per_dispatch"], 2
    )
    out["rand_k4_vs_k0_tpd_at_k8"] = round(
        rnd["k4_K8"]["tokens_per_dispatch"]
        / rnd["k0_K8"]["tokens_per_dispatch"], 2
    )
    return out


def _profiling_ab() -> dict:
    """Device-monitor A/B behind ``--profiling-ab``: the round-16
    utilization plane (per-window ``block_until_ready`` attribution +
    FLOPs ledger) on vs off at 16 streams on the stub paged engine,
    trials interleaved — the ``_trace_ab`` methodology applied to the
    monitor flag. ONE engine serves both sides with
    ``engine.device_monitor`` toggled between serves (exactly what
    ``DORA_DEVICE_MONITOR`` controls): a fresh engine per side measures
    construction variance — allocator layout, first-touch page faults,
    build-order bias worth ~3-5% on a run this short — instead of the
    monitor. The estimator is the **median of per-trial paired ratios**:
    each trial's off/on serves run back-to-back (~tens of ms apart), so
    slow ambient drift — a busy CI host speeding up or bogging down over
    the run — hits both legs of a pair equally and divides out, where a
    pooled off-median vs on-median comparison would charge it to
    whichever side the drift happened to land on. The gate is <= 3%
    wall-clock overhead — same bar as the serving-trace recorder,
    because the plane is default-on."""
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    max_seq, page_size, chunk, max_new, streams = 256, 8, 16, 192, 16
    prompts = [[i + 5] for i in range(streams)]
    trials = int(os.environ.get("DORA_BENCH_TRIALS", "14"))
    engine = make_stub_paged_engine(
        max_slots=streams, max_seq=max_seq, page_size=page_size,
        chunk=chunk, window=8,
    )
    _serve(engine, prompts, 4)  # warmup: compile only
    walls: dict[str, list[float]] = {"off": [], "on": []}
    for i in range(trials):
        # Alternate pair order so first-in-pair warmth cancels instead
        # of biasing one side.
        for mode in (("off", "on") if i % 2 == 0 else ("on", "off")):
            engine.device_monitor = mode == "on"
            _, wall, _ = _serve(engine, prompts, max_new)
            walls[mode].append(wall)
    engine.device_monitor = True
    ratios = [
        on / off
        for off, on in zip(walls["off"], walls["on"])
        if off > 0
    ]
    overhead = (statistics.median(ratios) - 1.0) * 100.0 if ratios else 0.0
    return {
        "streams": streams,
        "max_new": max_new,
        "trials": trials,
        "monitor_off_wall_s": round(statistics.median(walls["off"]), 4),
        "monitor_on_wall_s": round(statistics.median(walls["on"]), 4),
        "overhead_pct": round(overhead, 2),
        "gate_pct": 3.0,
        "pass": overhead <= 3.0,
    }


def _serve_ticked(engine, prompts, max_new: int, tick) -> tuple[int, float]:
    """The ``_serve`` drain loop with a per-iteration ``tick()`` hook —
    where the production serving loop would tick its fleet digest
    publisher. Both A/B arms run THIS loop so the hook's call overhead
    is common-mode; only the publish work differs."""
    backlog = deque(enumerate(prompts))
    t0 = time.perf_counter()
    tokens = 0
    active_keys: set[int] = set()
    while backlog or active_keys:
        tick()
        while backlog and engine.can_admit(len(backlog[0][1]), max_new):
            rid, ids = backlog.popleft()
            active_keys.add(rid)
            res = engine.submit(str(rid), ids, max_new)
            if res is not None:
                tokens += 1
                if res[1]:
                    active_keys.discard(rid)
        for key, _token, done in engine.step():
            tokens += 1
            if done:
                active_keys.discard(int(key))
    return tokens, time.perf_counter() - t0


def _fleet_digest_ab() -> dict:
    """Fleet-digest A/B behind ``--fleet-digest-ab``: the engine-state
    exporter (dora_tpu/fleet.py build_digest — radix-tree top-N walk,
    fits()-derived capacity, fingerprint) publishing at an aggressive
    0.5 s cadence vs off, on the 16-stream stub serving leg, trials
    interleaved with the ``_profiling_ab`` paired-ratio methodology
    (median of per-trial on/off ratios; ambient drift divides out).
    The cadence is 4x the shipped default (DORA_FLEET_DIGEST_S=2), so
    the gate bounds a worst-plausible config, not the default. Gate:
    <= 3% wall-clock overhead — same bar as the other default-on
    observability planes. The prefix cache is ON so every digest walks
    a populated tree (the expensive path), and the publisher sinks into
    a node fake — wire cost is the metrics plane's, already gated."""
    from dora_tpu import fleet
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    max_seq, page_size, chunk, max_new, streams = 256, 8, 16, 192, 16
    cadence_s = 0.5
    prompts = [[i + 5] for i in range(streams)]
    trials = int(os.environ.get("DORA_BENCH_TRIALS", "14"))
    engine = make_stub_paged_engine(
        max_slots=streams, max_seq=max_seq, page_size=page_size,
        chunk=chunk, window=8, prefix_cache=True,
    )
    _serve(engine, prompts, 4)  # warmup: compile + warm the radix tree

    class _Sink:
        def __init__(self):
            self.digests = 0

        def report_engine_state(self, digest):
            self.digests += 1

    published = 0
    walls: dict[str, list[float]] = {"off": [], "on": []}
    for i in range(trials):
        for mode in (("off", "on") if i % 2 == 0 else ("on", "off")):
            sink = _Sink()
            pub = fleet.DigestPublisher(
                sink, engine, model_id="stub",
                interval_s=cadence_s if mode == "on" else 0,
            )
            _, wall = _serve_ticked(engine, prompts, max_new, pub.tick)
            walls[mode].append(wall)
            published += sink.digests
    ratios = [
        on / off
        for off, on in zip(walls["off"], walls["on"])
        if off > 0
    ]
    overhead = (statistics.median(ratios) - 1.0) * 100.0 if ratios else 0.0
    return {
        "streams": streams,
        "max_new": max_new,
        "trials": trials,
        "cadence_s": cadence_s,
        "digests_published": published,
        "digest_off_wall_s": round(statistics.median(walls["off"]), 4),
        "digest_on_wall_s": round(statistics.median(walls["on"]), 4),
        "overhead_pct": round(overhead, 2),
        "gate_pct": 3.0,
        "pass": overhead <= 3.0,
    }


class _OpenLoopNode:
    """Node fake feeding serve() a pre-scheduled open-loop arrival
    trace: recv() releases an event once its arrival time has passed —
    the ARRIVALS don't slow down when the engine backs up, which is the
    property that makes overload visible (a closed loop self-throttles
    and hides it)."""

    def __init__(self, schedule):
        #: [(t_offset_s, event), ...] sorted by offset
        self._schedule = list(schedule)
        self._t0 = time.perf_counter()
        self.stream_ended = False
        self.sent: list[tuple[float, dict]] = []

    def now(self) -> float:
        return time.perf_counter() - self._t0

    def recv(self, timeout=None):
        if not self._schedule:
            self.stream_ended = True
            return None
        if self.now() >= self._schedule[0][0]:
            return self._schedule.pop(0)[1]
        return None

    def send_output(self, output_id, value, metadata=None):
        self.sent.append((self.now(), dict(metadata or {})))

    def report_serving(self, snapshot):
        pass

    def close(self):
        pass


def _qos_soak() -> dict:
    """Mixed-class Poisson overload soak behind ``--qos-soak`` (see
    module docstring). Identical seeded arrival trace both legs; the
    off leg drops the class tags and the shaping env — the pre-QoS
    single-class FIFO."""
    import numpy as np

    from dora_tpu.metrics import ServingMetrics
    from dora_tpu.models.batch_engine import make_stub_paged_engine
    from dora_tpu.nodehub.llm_server import serve

    streams = int(os.environ.get("DORA_BENCH_QOS_STREAMS", "1200"))
    max_new, tick_sleep = 8, 0.0008
    # One prefill chunk per step bounds admission to ~1/window_wall
    # streams/s; the arrival rate doubles it — a sustained overload.
    rate = 2.0 / (4 * tick_sleep)
    rng = np.random.default_rng(7)
    gaps = rng.exponential(1.0 / rate, size=streams)
    classes = rng.choice(
        ["interactive", "standard", "batch"], size=streams,
        p=[0.25, 0.35, 0.40],
    )
    arrivals = []
    t = 0.0
    for n in range(streams):
        t += float(gaps[n])
        arrivals.append((t, f"q{n}", str(classes[n])))

    qos_env = {
        "DORA_QOS_PREEMPT": "1",
        "DORA_QOS_SHED_WAIT_MS": "1500",
        "DORA_QOS_DEPTH_BATCH": "256",
    }

    def leg(shaped: bool) -> dict:
        saved = {k: os.environ.pop(k, None) for k in qos_env}
        if shaped:
            os.environ.update(qos_env)
        try:
            engine = make_stub_paged_engine(
                max_slots=8, max_seq=64, page_size=8, chunk=16,
                window=4, tick_sleep_s=tick_sleep,
            )
            schedule = [
                (at, {
                    "type": "INPUT",
                    "metadata": {
                        "request_id": rid,
                        "max_new_tokens": max_new,
                        **({"qos_class": cls} if shaped else {}),
                    },
                    "value": f"prompt {rid}".encode(),
                })
                for at, rid, cls in arrivals
            ]
            node = _OpenLoopNode(schedule)
            metrics = ServingMetrics(engine="paged")
            t0 = time.perf_counter()
            serve(
                node, engine, metrics,
                encode=lambda text: [ord(ch) % 97 + 1 for ch in text],
                decode_one=lambda tok: f" t{tok}",
                max_new_cap=max_new,
            )
            wall = time.perf_counter() - t0
            by_rid: dict[str, dict] = {}
            for ts, meta in node.sent:
                rid = meta.get("request_id")
                if rid is None:
                    continue
                s = by_rid.setdefault(rid, {"t0": ts, "finish": None})
                if meta.get("done"):
                    s["finish"] = meta.get("finish")
            ttft: dict[str, list[float]] = {
                "interactive": [], "standard": [], "batch": []
            }
            finishes: dict[str, int] = {}
            for at, rid, cls in arrivals:
                s = by_rid.get(rid)
                assert s is not None and s["finish"], (
                    f"stream {rid} silently lost"
                )
                finishes[s["finish"]] = finishes.get(s["finish"], 0) + 1
                if s["finish"] in ("stop", "length"):
                    ttft[cls].append(s["t0"] - at)

            def pct(vals, q):
                if not vals:
                    return None
                o = sorted(vals)
                return round(
                    o[min(len(o) - 1, int(len(o) * q))] * 1e3, 1
                )

            return {
                "wall_s": round(wall, 2),
                "finishes": finishes,
                "shed": metrics.shed,
                "preempted": metrics.preempted,
                "resumed": metrics.resumed,
                "ttft_ms": {
                    cls: {
                        "n": len(vals),
                        "p50": pct(vals, 0.50),
                        "p99": pct(vals, 0.99),
                    }
                    for cls, vals in ttft.items()
                },
            }
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    on, off = leg(shaped=True), leg(shaped=False)
    # Off leg is single-class: slice its TTFTs by the class the SAME
    # rid carried in the on leg — the A/B compares the same requests.
    p99_on = on["ttft_ms"]["interactive"]["p99"]
    p99_off = off["ttft_ms"]["interactive"]["p99"]
    return {
        "streams": streams,
        "arrival_rate_per_s": round(rate, 1),
        "max_new": max_new,
        "qos_on": on,
        "qos_off": off,
        "interactive_p99_on_vs_off": (
            round(p99_on / p99_off, 3)
            if p99_on is not None and p99_off
            else None
        ),
    }


def _prefix_ab() -> dict:
    """Shared-prefix cache A/B behind ``--prefix-ab``: a Zipf-popular
    template workload (few hot system prompts, many unique tails — the
    multi-tenant serving shape the radix cache targets) replayed
    open-loop against the stub paged engine twice, cache on vs off,
    same seeded arrival trace.

    ``chunk_sleep_s`` gives each prefill chunk a measurable device
    cost, so TTFT is proportional to chunks actually run — a cache hit
    skips the shared-prefix chunks and the A/B shows up in first-token
    latency, not just counters. The headline gate compares TTFT p50 of
    the on-leg's HIT requests against the SAME request ids in the off
    leg (>= 2x reduction justifies default-on); hit rate, prefill-chunk
    deltas, pool occupancy, and eviction counts ride along."""
    import numpy as np

    from dora_tpu.metrics import ServingMetrics
    from dora_tpu.models.batch_engine import make_stub_paged_engine
    from dora_tpu.nodehub.llm_server import serve

    streams = int(os.environ.get("DORA_BENCH_PREFIX_STREAMS", "120"))
    templates, prefix_len, tail_len = 8, 64, 8
    max_new, chunk_sleep = 8, 0.002
    rng = np.random.default_rng(11)
    # Zipf(1.2) popularity over the template set: template 0 dominates,
    # the tail templates are cold — hits concentrate where reuse does.
    weights = 1.0 / np.arange(1, templates + 1) ** 1.2
    weights /= weights.sum()
    picks = rng.choice(templates, size=streams, p=weights)
    # Light open-loop load: TTFT is dominated by the prefill the
    # request actually runs, not by backlog wait, so the A/B reads as
    # chunks-skipped, not queueing theory.
    gaps = rng.exponential(0.015, size=streams)
    tmpl_ids = [
        [int(t) for t in rng.integers(1, 90, size=prefix_len)]
        for _ in range(templates)
    ]
    arrivals = []
    t = 0.0
    for n in range(streams):
        t += float(gaps[n])
        tail = [int(x) for x in rng.integers(1, 90, size=tail_len)]
        arrivals.append((t, f"p{n}", tmpl_ids[picks[n]] + tail))

    def leg(cache: bool) -> dict:
        engine = make_stub_paged_engine(
            max_slots=8, max_seq=128, page_size=8, chunk=16,
            window=4, chunk_sleep_s=chunk_sleep,
            prefix_cache=cache,
        )
        hit_rids: set[str] = set()
        pc = engine.prefix_cache
        if pc is not None:
            # serve() renames streams req-N; recover the trace's rid by
            # prompt identity (tails are unique by construction).
            rid_by_prompt = {tuple(ids): rid for _at, rid, ids in arrivals}
            orig_submit = engine.submit

            def submit(key, ids, max_new):
                h0 = pc.hits
                res = orig_submit(key, ids, max_new)
                if pc.hits > h0:
                    hit_rids.add(rid_by_prompt[tuple(ids)])
                return res

            engine.submit = submit
        schedule = [
            (at, {
                "type": "INPUT",
                "metadata": {
                    "request_id": rid,
                    "max_new_tokens": max_new,
                },
                "value": " ".join(str(t) for t in ids).encode(),
            })
            for at, rid, ids in arrivals
        ]
        node = _OpenLoopNode(schedule)
        metrics = ServingMetrics(engine="paged")
        c0 = engine.chunks_run
        t0 = time.perf_counter()
        serve(
            node, engine, metrics,
            encode=lambda text: [int(t) for t in text.split()],
            decode_one=lambda tok: f" t{tok}",
            max_new_cap=max_new,
        )
        wall = time.perf_counter() - t0
        ttft_by_rid: dict[str, float] = {}
        for ts, meta in node.sent:
            rid = meta.get("request_id")
            if rid is not None and rid not in ttft_by_rid:
                ttft_by_rid[rid] = ts
        ttfts = {}
        for at, rid, _ids in arrivals:
            assert rid in ttft_by_rid, f"stream {rid} silently lost"
            ttfts[rid] = ttft_by_rid[rid] - at
        out = {
            "wall_s": round(wall, 2),
            "prefill_chunks": engine.chunks_run - c0,
            "peak_used_pages": engine.allocator.peak_in_use,
            "total_pages": engine.allocator.num_pages,
            "ttfts": ttfts,
            "hit_rids": sorted(hit_rids),
        }
        if pc is not None:
            out["cache"] = pc.stats()
        return out

    def pct(vals, q):
        if not vals:
            return None
        o = sorted(vals)
        return round(o[min(len(o) - 1, int(len(o) * q))] * 1e3, 2)

    on, off = leg(cache=True), leg(cache=False)
    hit_rids = set(on["hit_rids"])
    hit_on = [v for r, v in on["ttfts"].items() if r in hit_rids]
    hit_off = [v for r, v in off["ttfts"].items() if r in hit_rids]
    all_on = list(on["ttfts"].values())
    all_off = list(off["ttfts"].values())
    for legd in (on, off):  # raw per-rid map served its purpose
        del legd["ttfts"], legd["hit_rids"]
    cache = on.get("cache", {})
    lookups = cache.get("hits", 0) + cache.get("misses", 0)
    p50_on, p50_off = pct(hit_on, 0.50), pct(hit_off, 0.50)
    return {
        "streams": streams,
        "templates": templates,
        "prefix_len": prefix_len,
        "tail_len": tail_len,
        "hit_rate": round(cache.get("hits", 0) / lookups, 3) if lookups else None,
        "hit_requests": len(hit_rids),
        "cache_on": on,
        "cache_off": off,
        "ttft_ms": {
            "hit_on": {"p50": p50_on, "p99": pct(hit_on, 0.99)},
            "hit_rids_off": {"p50": p50_off, "p99": pct(hit_off, 0.99)},
            "all_on": {"p50": pct(all_on, 0.50), "p99": pct(all_on, 0.99)},
            "all_off": {"p50": pct(all_off, 0.50), "p99": pct(all_off, 0.99)},
        },
        # The default-on gate: hit-request TTFT p50, cache on vs the
        # same requests cache off. <= 0.5 means >= 2x faster.
        "hit_p50_on_vs_off": (
            round(p50_on / p50_off, 3) if p50_on is not None and p50_off
            else None
        ),
    }


def main() -> int:
    from dora_tpu.models.hf import qwen2

    if "--prefix-ab" in sys.argv[1:]:
        # Stub-engine leg: the cache lives in the admission plane; the
        # A/B measures chunks skipped, not model quality.
        print(json.dumps({"prefix_ab": _prefix_ab()}))
        return 0
    if "--qos-soak" in sys.argv[1:]:
        # Stub-engine leg: the QoS machinery is engine-agnostic, the
        # soak measures the ADMISSION plane, not the model.
        print(json.dumps({"qos_soak": _qos_soak()}))
        return 0
    if "--spec-ab" in sys.argv[1:]:
        # Stub-engine leg: no checkpoint needed, acceptance is shaped
        # by the token rule, not model weights.
        print(json.dumps({"spec_ab": _spec_ab()}))
        return 0
    if "--lora-ab" in sys.argv[1:]:
        # Stub-engine leg: the claim is dispatch amortization across
        # tenants plus zero-compile churn — scheduler properties,
        # independent of model weights.
        print(json.dumps({"lora_ab": _lora_ab()}))
        return 0
    if "--profiling-ab" in sys.argv[1:]:
        # Stub-engine leg: the monitor's cost is per-window host work
        # (block_until_ready + counter math), independent of weights.
        print(json.dumps({"profiling_ab": _profiling_ab()}))
        return 0
    if "--fleet-digest-ab" in sys.argv[1:]:
        # Stub-engine leg: digest cost is host-side scheduler reads
        # (radix walk, allocator counters), independent of weights.
        print(json.dumps({"fleet_digest_ab": _fleet_digest_ab()}))
        return 0
    path = os.environ.get("DORA_HF_CHECKPOINT")
    real = bool(path)
    tmp = None
    if not real:
        tmp = tempfile.mkdtemp(prefix="bench-serving-")
        path = _tiny_checkpoint(tmp)
    if "--multistep" in sys.argv[1:]:
        print(json.dumps({"multistep": _multistep_sweep(qwen2, path, real)}))
        return 0
    if "--trace-ab" in sys.argv[1:]:
        print(json.dumps({"trace_ab": _trace_ab(qwen2, path, real)}))
        return 0
    if "--quant-ab" in sys.argv[1:]:
        print(json.dumps({"quant_ab": _quant_ab(qwen2, path, real)}))
        return 0
    raise SystemExit(__doc__[__doc__.index("Usage::"):])


if __name__ == "__main__":
    sys.exit(main())
