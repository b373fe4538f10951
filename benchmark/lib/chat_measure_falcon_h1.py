"""From a chat load process's raw records to metrics and ``correct``,
for a ``falcon_h1`` configuration (``chat_measure.py``'s rules, with
this model's reference and its recurrent state).

``correct`` is true only if (a) every stream that finished has exactly
its ``max_tokens`` tokens; (b) for a seeded sample of ``reference_sample``
(4) completed requests due inside the window, the longest completed
prompt among them, every emitted token lies within ``NEAR_TIE_ULPS``
bf16 steps of the top of the plain reference's teacher-forced logits at
its position (``lib/reference_falcon_h1.py``, a child of its own on the
free chip); (c) twin prompts whose requests were in flight together
agree on at least ``MIN_AGREE`` tokens (or on all of the shorter one),
or else both twins go through (b) as well, up to ``TWIN_SAMPLE`` pairs.
There is no prefix cache for this model, so a twin is prefilled again
into another slot beside 15 other streams: twins that part would show
one slot's recurrent state leaking into another's; (d) the SSM state
of EVERY layer, as the program's engine holds it after it has served
each sampled prompt again for 64 tokens beside other live streams
(chunked prefill, full windows, ``ssm_state_step``:
``lib/state_audit_falcon_h1.py``), lies within ``STATE_REL_ERR`` of the
reference's float32 state after the same tokens, as rms error over rms,
and every token that engine emitted passes (b) too.

The limits and their two readings are in ``PERF.md`` section 6 (PR 33):
(b) the program's largest deficit over its runs against what the
comparison reads for a program without the convolution's tail, without
``D`` or without the mixer (``what_if``, printed in every run); (d) the
program's largest error against the reference's own recurrence with its
state rounded to bfloat16 after every token (printed in every run as
``state.rel_err_bf16_state``): a state kept in bfloat16 is not correct,
by (d) alone (``what_if.bf16_state`` is what (b) reads for it).
``verdict`` is the whole comparison, apart from the records it reads, so
that a test can put the control in the program's place.
"""

from __future__ import annotations

import json
import random
import subprocess
import sys
from pathlib import Path

import model_bytes_falcon_h1
import stats
from checkpoint import code_tokens

MIN_AGREE = 8
NEAR_TIE_ULPS = 6
STATE_REL_ERR = 0.0015
TWIN_SAMPLE = 2
HERE = Path(__file__).resolve().parent


def sample_requests(done: list[dict], seed: int, n: int) -> list[dict]:
    """``n`` of the completed requests, seeded, the longest prompt among
    them (the state audit's control grows with the prompt)."""
    if not done:
        return []
    longest = max(done, key=lambda r: (r["prompt_tokens"], -r["i"]))
    rest = [r for r in done if r is not longest]
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
    serving = run.get("serving_after") or {}
    lines = [{"window": {
        "seconds": t1 - t0, "requests_due": m["attempted"], "failed": m["failed"],
        "completed_in_window": m["completed_in_window"],
        "requests_per_s": m["requests_per_s"],
        "ttft_p50_ms": m.get("ttft_p50_ms"), "tpot_p50_ms": m.get("tpot_p50_ms"),
        "ttft_p95_ms": m.get("ttft_p95_ms"), "tpot_p95_ms": m.get("tpot_p95_ms"),
        "tokens_per_s": m["tokens_per_s"],
        "generator_lateness_ms": stats.lateness_ms(reqs, t0, t1),
        "delta_stalls": stats.stalls(reqs, t0, t1),
        "generator_pauses": stats.pauses_in_window(raw.get("generator_pauses", []), t0, t1),
        "dispatch_gap_us": stats.hist_delta(
            run.get("serving_before"), serving, "dispatch_gap_us"),
        "plan_exhausted": raw["plan_exhausted"],
        "errors": sorted({str(r["error"])[:120] for r in reqs if r.get("error")})[:5],
    }}]
    lines.append({"recurrent_state": {
        "ssm_row_ticks": serving.get("ssm_row_ticks"),
        "ssm_decode_ticks": serving.get("ssm_decode_ticks"),
        "ssm_chunk_rows": serving.get("ssm_chunk_rows"),
        "ssm_zero_starts": serving.get("ssm_zero_starts"),
        "ssm_state_bytes": serving.get("ssm_state_bytes"),
        "ssm_slots_live": serving.get("ssm_slots_live"),
        "live_rows_a_tick_in_window": model_bytes_falcon_h1.live_rows_a_tick(
            run.get("serving_before"), serving),
        "capture_edges": sorted((run.get("serving_traced") or {}).get("capture_counters") or {}),
        "live_rows_a_tick_in_capture": model_bytes_falcon_h1.live_rows_in_capture(run),
        "prefix_hit_tokens": serving.get("prefix_hit_tokens"),
    }})

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
    # (c) twins in flight together
    by_i = {r["i"]: r for r in reqs if r["i"] >= 0}
    twins = []
    for i, r in sorted(by_i.items()):
        j = plan["requests"][i].get("twin_of")
        o = by_i.get(j) if j is not None else None
        if o is None or not (stats.ok(r) and stats.ok(o)):
            continue
        together = r["sent"] < o["done"] and o["sent"] < r["done"]
        n = min(len(r["tokens"]), len(o["tokens"]))
        twins.append({"i": i, "of": j, "agreed": stats.agreed(r["tokens"], o["tokens"]),
                      "of_n": n, "together": together})
    parted = [t for t in twins if t["together"] and t["agreed"] < min(MIN_AGREE, t["of_n"])]
    lines.append({"twins": {
        "pairs": len(twins), "in_flight_together": sum(t["together"] for t in twins),
        "fully_identical": sum(t["agreed"] == t["of_n"] for t in twins),
        "shortest_agreed": min((t["agreed"] for t in twins), default=None),
        "parted_before_min_agree": parted,
    }})
    # (b), (d) the plain reference on a seeded sample, and on the twins that parted
    done = sorted(
        (r for r in reqs if r["i"] >= ctx.traffic["callers"] and stats.ok(r)
         and stats.in_window(r["due"], t0, t1)),
        key=lambda r: r["i"],
    )
    sample = sample_requests(done, ctx.seed, ctx.traffic.get("reference_sample", 4))
    extra = [by_i[k] for t in parted[:TWIN_SAMPLE] for k in (t["i"], t["of"])]
    sample += [r for r in extra if r["i"] not in {x["i"] for x in sample}]
    ref = None
    if sample:
        ref = reference(ctx, [
            {"i": r["i"], "prompt": plan["requests"][r["i"]]["ids"], "emitted": r["tokens"]}
            for r in sample
        ])
        lines.append({"reference": ref and {
            **ref, "limit_bf16_ulps": NEAR_TIE_ULPS, "limit_state_rel_err": STATE_REL_ERR}})
    compared, holds = verdict(ref, len(short), m["attempted"])
    metrics = {
        "tokens_per_s": {"value": m["tokens_per_s"], "unit": "tokens/s"},
    }
    for key in ("ttft_p95_ms", "tpot_p50_ms", "tpot_p95_ms"):
        if key in m:
            metrics[key] = {"value": m[key], "unit": "ms"}
    return {
        "metrics": metrics, "attempted": m["attempted"], "failed": m["failed"],
        "correct": holds and not raw["plan_exhausted"],
        "lines": lines, "reference_device": ref and ref["device"], "compared": compared,
    }


def verdict(ref: dict | None, short: int, attempted: int) -> tuple[dict, bool]:
    """Every number ``correct`` rests on beside its limit, and whether
    all hold. ``ref`` is the reference child's last line, or None."""
    samples = ref["samples"] if ref else []
    state = (ref or {}).get("state") or {}
    deficit = max((s["max_deficit_bf16_ulps"] for s in samples), default=None)
    audit_deficit = max(
        (s["max_deficit_bf16_ulps"] for s in state.get("samples") or []), default=None)
    state_err = max((e for row in state.get("rel_err") or [] for e in row), default=None)
    compared = {
        "short_streams": stats.compared(short, 0),
        "requests_due": stats.compared(attempted, 1, at_most=False),
        "reference_samples": stats.compared(len(samples), 1, at_most=False),
        "max_deficit_bf16_ulps": stats.compared(deficit, NEAR_TIE_ULPS),
        "audit_max_deficit_bf16_ulps": stats.compared(audit_deficit, NEAR_TIE_ULPS),
        "state_rel_err": stats.compared(state_err, STATE_REL_ERR),
    }
    return compared, all(c["holds"] for c in compared.values())


def reference(ctx, samples: list[dict]) -> dict | None:
    cfg = ctx.config["reference"]
    env = ctx.config["node_env"]["llm"]
    spec = ctx.workdir / "reference_in.json"
    spec.write_text(json.dumps({
        "checkpoint": str(ctx.workdir / "checkpoint"), "pad_to": cfg["pad_to"],
        "q_block": cfg["q_block"], "max_new": int(env["DORA_MAX_NEW_TOKENS"]),
        "samples": samples, "audit": env,
    }))
    proc = subprocess.run(
        [sys.executable, str(HERE / "reference_falcon_h1.py"), str(spec)],
        stdout=subprocess.PIPE, text=True, cwd=str(ctx.root), timeout=cfg["timeout_s"],
    )
    out = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    if proc.returncode != 0 or not out:
        print(f"benchmark: reference child exited {proc.returncode}", file=sys.stderr)
        return None
    return json.loads(out[-1])
