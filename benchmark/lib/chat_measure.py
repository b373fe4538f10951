"""From a chat load process's raw records to metrics and ``correct``.

``correct`` is true only if (a) every stream that finished has exactly
its ``max_tokens`` tokens; (b) for a seeded sample of
``REFERENCE_SAMPLE`` completed requests, every emitted token lies within
``NEAR_TIE_ULPS`` bf16 steps of the top of the plain reference's
teacher-forced logits at its position (``lib/reference.py``, a child of
its own on the free chip); (c) twin prompts whose requests were in
flight together agree on at least ``MIN_AGREE`` tokens (or on all of the
shorter one), or else both twins go through (b) as well, up to
``TWIN_SAMPLE`` pairs. The first chip run of this benchmark showed why
(c) needs its second half: a repeat is served from the prefix cache, so
its prompt's tail runs through the chunk program at another offset than
its twin's did, and the two round differently: 7 of 13 pairs were
identical, two parted at tokens 0 and 2, each where the reference's own
logits are within a few steps of a tie. The constants and their reasons
are ``chip_smoke.py``'s (PR 21): measured partings of two correct
programs sit at 0-7 steps, a wrong token some 240 steps down.
"""

from __future__ import annotations

import json
import random
import subprocess
import sys
from pathlib import Path

import stats
from checkpoint import code_tokens

MIN_AGREE = 8
NEAR_TIE_ULPS = 24
REFERENCE_SAMPLE = 4
TWIN_SAMPLE = 2
HERE = Path(__file__).resolve().parent


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
            run.get("serving_before"), run.get("serving_after"), "dispatch_gap_us"),
        "plan_exhausted": raw["plan_exhausted"],
        "errors": sorted({str(r["error"])[:120] for r in reqs if r.get("error")})[:5],
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
    # twins in flight together
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
    # the plain reference on a seeded sample, and on the twins that parted
    done = sorted(
        (r for r in reqs if r["i"] >= 0 and stats.ok(r) and stats.in_window(r["due"], t0, t1)),
        key=lambda r: r["i"],
    )
    sample = random.Random(ctx.seed).sample(done, min(REFERENCE_SAMPLE, len(done)))
    extra = [by_i[k] for t in parted[:TWIN_SAMPLE] for k in (t["i"], t["of"])]
    sample += [r for r in extra if r["i"] not in {x["i"] for x in sample}]
    ref_ok, ref = bool(sample), None
    if sample:
        ref = reference(ctx, [
            {"i": r["i"], "prompt": plan["requests"][r["i"]]["ids"], "emitted": r["tokens"]}
            for r in sample
        ])
        lines.append({"reference": ref})
        ref_ok = ref is not None and all(
            s["max_deficit_bf16_ulps"] <= NEAR_TIE_ULPS for s in ref["samples"]
        )
    compared = {
        "short_streams": stats.compared(len(short), 0),
        "requests_due": stats.compared(m["attempted"], 1, at_most=False),
        "reference_samples": stats.compared(len(ref["samples"]) if ref else 0, 1, at_most=False),
        "max_deficit_bf16_ulps": stats.compared(
            max((s["max_deficit_bf16_ulps"] for s in ref["samples"]), default=None)
            if ref else None, NEAR_TIE_ULPS),
    }
    metrics = {
        "tokens_per_s": {"value": m["tokens_per_s"], "unit": "tokens/s"},
    }
    for key in ("ttft_p95_ms", "tpot_p50_ms", "tpot_p95_ms"):
        if key in m:
            metrics[key] = {"value": m[key], "unit": "ms"}
    return {
        "metrics": metrics, "attempted": m["attempted"], "failed": m["failed"],
        "correct": (not short and ref_ok and m["attempted"] > 0
                    and not raw["plan_exhausted"]),
        "lines": lines, "reference_device": ref and ref["device"], "compared": compared,
    }


def reference(ctx, samples: list[dict]) -> dict | None:
    spec = ctx.workdir / "reference_in.json"
    spec.write_text(json.dumps({
        "checkpoint": str(ctx.workdir / "checkpoint"),
        "max_seq": int(ctx.config["node_env"]["llm"]["DORA_MAX_SEQ"]),
        "pad_to": ctx.config["reference"]["pad_to"],
        "max_new": int(ctx.config["node_env"]["llm"]["DORA_MAX_NEW_TOKENS"]),
        "samples": samples,
    }))
    proc = subprocess.run(
        [sys.executable, str(HERE / "reference.py"), str(spec)],
        stdout=subprocess.PIPE, text=True, cwd=str(ctx.root),
        timeout=ctx.config["reference"]["timeout_s"],
    )
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    if proc.returncode != 0 or not lines:
        print(f"benchmark: reference child exited {proc.returncode}", file=sys.stderr)
        return None
    return json.loads(lines[-1])
