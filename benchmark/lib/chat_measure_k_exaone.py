"""From a chat load process's raw records to metrics and ``correct``,
for an ``exaone_moe`` configuration (``chat_measure.py``'s rules, with
this model's reference and the rows its two cache kinds keep).

``correct`` is true only if (a) every stream that finished has exactly
its ``max_tokens`` tokens; (b) for a seeded sample of ``reference_sample``
(4) completed requests due inside the window, THE LONGEST COMPLETED LONG
PROMPT among them (the other three drawn from the short ones), every
emitted token lies within ``NEAR_TIE_ULPS`` bf16
steps of the top of the plain reference's teacher-forced logits at its
position (``lib/reference_k_exaone.py``, a child of its own on the free
chip); (c) the K|V rows the program's engine holds for each sampled
prompt, served again for 32 tokens beside other live streams
(``lib/cache_audit_k_exaone.py``), lie within stated limits of the
reference's, as rms error over rms: ``RING_ROWS_FIRST`` at layer 0 (the
ring: the prompt's last rows and the rows the decode ticks wrote, by ``p
% 128``), ``KV_ROWS_GLOBAL_FIRST`` at the first global layer (every
prompt position, from the pool), ``KV_ROWS_DEEP`` at the last window
layer and the last layer, and at least one decode-written ring row was
looked at; (d) the server that served the window says a cached token
costs at most ``KV_BYTES_PER_TOKEN`` (pages for the global layers alone);
(e) each of three controls, computed in every run, FAILS a limit that
the program passes (``controls_refused``): the reference with every
layer full (no band mask) on the longest sample, the reference with
rotary on the global layers on the two shortest, the program's layer-0
rows through 8 bits. The limits and their two readings (the program's
largest, a faulty program's smallest) are beside the constants and in
``PERF.md`` section 6 (PR 41). ``verdict`` is the whole comparison, apart
from the records it reads.
"""

from __future__ import annotations

import json
import random
import subprocess
import sys
from pathlib import Path

import model_bytes_swa_moe as mb
import stats
from checkpoint import code_tokens

#: each limit with its two readings over 15 runs, 60 samples (my chip runs,
#: PR 41; PERF.md section 6): the program's largest, a faulty program's
#: smallest. bf16 steps below the reference's top: the program 5.3-27.1, the
#: reference with every layer full 333.4-386.5 on the 12,524-row prompt
#: (rotary on the global layers reads 12.8-33.5: the tokens cannot see it,
#: the rows below can)
NEAR_TIE_ULPS = 90
#: layer 0's ring rows: the program 0.00247-0.00249 (three bf16 roundings),
#: the same rows through 8 bits 0.00955-0.00994
RING_ROWS_FIRST = 0.005
#: the first global layer's pages: the program 0.0139-0.0257, the reference
#: with rotary on the global layers 0.354-0.472
KV_ROWS_GLOBAL_FIRST = 0.08
#: the last window layer's ring and the last layer's pages: the program
#: 0.0207-0.0423, the reference with every layer full 1.364-1.369
KV_ROWS_DEEP = 0.2
KV_BYTES_PER_TOKEN = 8192
CONTROLS = ("full_everywhere", "rope_on_global", "rows_8bit")
HERE = Path(__file__).resolve().parent


def sample_requests(done: list[dict], plan: dict, seed: int, n: int) -> list[dict]:
    """``n`` of the completed requests, seeded, the longest completed
    LONG prompt among them (the global layers' error grows with the
    context they are read over, and only a long prompt wraps the ring
    under a band that matters)."""
    if not done:
        return []
    longs = [r for r in done if plan["requests"][r["i"]].get("long")]
    longest = max(longs or done, key=lambda r: (r["prompt_tokens"], -r["i"]))
    # the others from the short ones while there are enough: every further
    # long sample costs the float32 reference some 20 s of the run's 340
    short = [r for r in done if r is not longest and r not in longs]
    rest = short if len(short) >= n - 1 else [r for r in done if r is not longest]
    picked = [longest] + random.Random(seed).sample(rest, min(n - 1, len(rest)))
    return sorted(picked, key=lambda r: r["i"])


def measure(ctx, run: dict, plan: dict) -> dict:
    raw = json.loads((ctx.workdir / "load_result.json").read_text())
    t0, t1 = raw["t0"], raw["t1"]
    reqs = raw["requests"]
    for r in reqs:
        try:
            r["tokens"] = code_tokens(r.pop("text"))
        except (ValueError, KeyError) as e:
            r["tokens"], r["error"] = [], r.get("error") or repr(e)
    m = stats.chat_metrics(reqs, t0, t1)
    before, serving = run.get("serving_before"), run.get("serving_after") or {}
    due = [r for r in reqs if stats.in_window(r["due"], t0, t1)]
    is_long = [bool(plan["requests"][r["i"]].get("long")) for r in due]
    c = run.get("compiles") or {}
    lines = [{"window": {
        "seconds": t1 - t0, "requests_due": m["attempted"], "failed": m["failed"],
        "long_requests_due": sum(is_long),
        "prompt_tokens_due": sum(r["prompt_tokens"] for r in due),
        "completed_in_window": m["completed_in_window"],
        "requests_per_s": m["requests_per_s"],
        "ttft_p50_ms": m.get("ttft_p50_ms"), "tpot_p50_ms": m.get("tpot_p50_ms"),
        "ttft_p95_ms": m.get("ttft_p95_ms"), "tpot_p95_ms": m.get("tpot_p95_ms"),
        "tokens_per_s": m["tokens_per_s"],
        "ttft_p50_ms_short": _median_ttft(due, is_long, False),
        "ttft_p50_ms_long": _median_ttft(due, is_long, True),
        "generator_lateness_ms": stats.lateness_ms(reqs, t0, t1),
        "delta_stalls": stats.stalls(reqs, t0, t1),
        "generator_pauses": stats.pauses_in_window(raw.get("generator_pauses", []), t0, t1),
        "dispatch_gap_us": stats.hist_delta(before, serving, "dispatch_gap_us"),
        "compiles_in_window": (
            None if None in (c.get("before"), c.get("after"))
            else c["after"] - c["before"]),
        "plan_exhausted": raw["plan_exhausted"],
        "errors": sorted({str(r["error"])[:120] for r in reqs if r.get("error")})[:5],
        # the program's counters, as the other cells' window lines print theirs
        **{k: serving.get(k) for k in (
            "moe_tokens", "moe_local_pairs", "moe_expert_tokens", "moe_experts_touched",
            "swa_decode_ticks", "swa_row_ticks", "swa_ring_rows_read", "global_kv_rows_read",
            "global_kv_rows_swept", "swa_chunks", "swa_chunk_rows", "swa_chunk_positions",
            "kv_bytes_per_token", "kv_pool_bytes", "kv_pages_free", "swa_ring_bytes")},
        "live_rows_a_tick_in_window": mb.per(before, serving, "swa_row_ticks",
                                             "swa_decode_ticks"),
        "global_rows_read_a_tick_in_window": mb.per(before, serving, "global_kv_rows_read",
                                                    "swa_decode_ticks"),
        "global_swept_over_read_in_window": mb.per(before, serving, "global_kv_rows_swept",
                                                   "global_kv_rows_read"),
        "chunk_position_in_window": mb.per(before, serving, "swa_chunk_positions",
                                           "swa_chunks"),
        "backlog_wait_us": stats.hist_delta(before, serving, "backlog_wait_us"),
    }}]
    warm = [r for r in reqs if r["due"] < t0 and r.get("first") is not None]
    if warm:
        start = min(r["sent"] for r in warm)
        lines.append({"before_the_window": {
            "requests": len(warm), "first_token_after_s": min(r["first"] for r in warm) - start,
            "window_opened_after_s": t0 - start,
        }})
    # (a) finished streams hold exactly what was asked for
    short = [r["i"] for r in reqs
             if r.get("finish") is not None and not r.get("error")
             and len(r["tokens"]) != r["max_tokens"]]
    # (b), (c) the plain reference and the cache audit on a seeded sample
    done = sorted(
        (r for r in due if stats.ok(r) and r["i"] >= ctx.traffic["callers"]),
        key=lambda r: r["i"],
    )
    sample = sample_requests(done, plan, ctx.seed, ctx.traffic.get("reference_sample", 4))
    ref = None
    if sample:
        ref = reference(ctx, [
            {"i": r["i"], "prompt": plan["requests"][r["i"]]["ids"], "emitted": r["tokens"]}
            for r in sample
        ])
        lines.append({"reference": ref and {
            **ref, "long": {r["i"]: bool(plan["requests"][r["i"]].get("long")) for r in sample},
            "limit_bf16_ulps": NEAR_TIE_ULPS, "limit_ring_rows_first": RING_ROWS_FIRST,
            "limit_kv_rows_global_first": KV_ROWS_GLOBAL_FIRST,
            "limit_kv_rows_deep": KV_ROWS_DEEP}})
    longs = sum(bool(plan["requests"][r["i"]].get("long")) for r in sample)
    compared, holds = verdict(ref, len(short), m["attempted"], longs,
                              serving.get("kv_bytes_per_token"))
    # the cell reports the end-to-end metrics whose lists in the manifest name it
    manifest = json.loads((ctx.root / "BENCHMARK.json").read_text())
    mine = {e["name"]: e["unit"] for e in manifest["end_to_end"]
            if ctx.cell["name"] in e.get("workloads", ())}
    metrics = {key: {"value": m[key], "unit": unit}
               for key, unit in mine.items() if key in m}
    return {
        "metrics": metrics, "attempted": m["attempted"], "failed": m["failed"],
        "correct": holds and not raw["plan_exhausted"],
        "lines": lines, "reference_device": ref and ref["device"], "compared": compared,
    }


def _median_ttft(due: list[dict], is_long: list[bool], long_: bool):
    waits = [(r["first"] - r["due"]) * 1e3 for r, flag in zip(due, is_long)
             if flag == long_ and r.get("first") is not None]
    return stats.median(waits) if waits else None


def verdict(ref: dict | None, short: int, attempted: int, long_samples: int,
            kv_bytes_per_token: int | None) -> tuple[dict, bool]:
    """Every number ``correct`` rests on beside its limit, and whether
    all hold. ``ref`` is the reference child's last line, or None."""
    samples = ref["samples"] if ref else []
    what_if = (ref or {}).get("what_if") or {}
    rows = ((ref or {}).get("cache") or {}).get("rows") or []

    def worst(key, of=None):
        return max((r[key] for r in (rows if of is None else of)
                    if r.get(key) is not None), default=None)

    deficit = max((s["max_deficit_bf16_ulps"] for s in samples), default=None)
    compared = {
        "short_streams": stats.compared(short, 0),
        "requests_due": stats.compared(attempted, 1, at_most=False),
        "reference_samples": stats.compared(len(samples), 1, at_most=False),
        "long_reference_samples": stats.compared(long_samples, 1, at_most=False),
        "max_deficit_bf16_ulps": stats.compared(deficit, NEAR_TIE_ULPS),
        "ring_rows_first_rel_err": stats.compared(worst("first"), RING_ROWS_FIRST),
        "ring_decode_rows_audited": stats.compared(
            sum(r["first_decode_rows"] for r in rows) if rows else None, 1, at_most=False),
        "kv_rows_global_first_rel_err": stats.compared(
            worst("global_first"), KV_ROWS_GLOBAL_FIRST),
        "kv_rows_deep_rel_err": stats.compared(worst("deep"), KV_ROWS_DEEP),
        "kv_bytes_per_token": stats.compared(kv_bytes_per_token, KV_BYTES_PER_TOKEN),
    }
    # a control is refused where one of its readings (the least over the
    # samples that ran it) breaks a limit that the program passes
    def least(key):
        return min((r[key] for r in rows if r.get(key) is not None), default=None)

    def breaks(value, limit):
        return value is not None and value > limit

    refused = {
        "full_everywhere": breaks(
            (what_if.get("full_everywhere") or {}).get("least_deficit_bf16_ulps"),
            NEAR_TIE_ULPS) or breaks(least("deep_full_everywhere"), KV_ROWS_DEEP),
        "rope_on_global": breaks(
            (what_if.get("rope_on_global") or {}).get("least_deficit_bf16_ulps"),
            NEAR_TIE_ULPS) or breaks(
            least("global_first_rope_on_global"), KV_ROWS_GLOBAL_FIRST),
        "rows_8bit": breaks(least("first_8bit"), RING_ROWS_FIRST),
    }
    compared["controls_refused"] = stats.compared(
        sum(refused.values()) if ref else None, len(CONTROLS), at_most=False)
    return compared, all(c["holds"] for c in compared.values())


def reference(ctx, samples: list[dict]) -> dict | None:
    cfg = ctx.config["reference"]
    env = ctx.config["node_env"]["llm"]
    spec = ctx.workdir / "reference_in.json"
    spec.write_text(json.dumps({
        "checkpoint": str(ctx.workdir / "checkpoint"), "pads": cfg["pads"],
        "q_block": cfg["q_block"], "max_new": int(env["DORA_MAX_NEW_TOKENS"]),
        "audit_decode": cfg["audit_decode"], "samples": samples,
        "ep_rank": int(env.get("DORA_EP_RANK", 0)), "audit": env,
    }))
    proc = subprocess.run(
        [sys.executable, str(HERE / "reference_k_exaone.py"), str(spec)],
        stdout=subprocess.PIPE, text=True, cwd=str(ctx.root), timeout=cfg["timeout_s"],
    )
    out = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    if proc.returncode != 0 or not out:
        print(f"benchmark: reference child exited {proc.returncode}", file=sys.stderr)
        return None
    return json.loads(out[-1])
