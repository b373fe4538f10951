"""From a chat load process's raw records to metrics and ``correct``,
for an ``ouro`` configuration (``chat_measure.py``'s rules, with this
model's reference and the rows its passes keep).

``correct`` is true only if (a) every stream that finished has exactly
its ``max_tokens`` tokens; (b) for a seeded sample of ``reference_sample``
(4) completed requests due inside the window, the longest completed
prompt among them, every emitted token lies within ``NEAR_TIE_ULPS``
bf16 steps of the top of the plain reference's teacher-forced logits at
its position (``lib/reference_ouro.py``, a child of its own on the free
chip); (c) twin prompts whose requests were in flight together agree on
at least ``MIN_AGREE`` tokens (or on all of the shorter one), or else
both twins go through (b) as well, up to ``TWIN_SAMPLE`` pairs (a repeat
is served from the prefix cache, so its prompt's tail runs through the
chunk program at another offset than its twin's did and the two round
differently); (d) the K rows the program's engine holds for each sampled
prompt, served again for 32 tokens beside other live streams
(``lib/cache_audit_ouro.py``), are held to the reference's rows at the
precision the configuration states (``as_stated``: a product's operands
in bfloat16, sums and the residual stream float32), as rms error over
rms: within ``K_ROWS_FIRST`` at (pass 0, layer 0); at (pass 0, layer 1),
two adds down the stream, at least ``K_ROWS_SAME`` of the prompts' first
32 rows within ``cache_audit_ouro.SAME_ROW`` of it, i.e. computed as that
precision computes them (at bfloat16 a row is reproduced to the bit or,
after one rounding that fell the other way, not at all: PERF.md section
6); within ``K_ROWS_DEEP`` at (pass 0, the last layer), 95 sublayers
down; the program's own rows of pass 0 and of the last pass in the last
layer at least ``PASSES_APART`` from each other; and every token that
engine emitted passes (b) too; (e) the program counted no token whose
running exit sum reached the threshold before the last pass
(``loop_exit_before_last``, 0 at the published threshold of 1), and the
reference counted none either.

The limits and their two readings are in ``PERF.md`` section 6 (PR 35):
(b) the program's largest deficit over its runs against what the
comparison reads for three passes in place of four, for every pass on
pass 0's rows, without the post-norms, without the per-pass final norm
(``what_if``, printed in every run); (d) against two controls computed
in every run and put in the program's place by ``verdict``'s callers
(``benchmark/tests/test_cache_audit_ouro.py``): the reference's own rows
with the residual stream held to bfloat16 (``bf16_residual``: none of
its rows at layer 1 is the stated precision's, and at the last layer it
lies twice as far from it as the program does), and the program's rows
at (pass 0, layer 0) held to 8 bits with a scale a row and head
(``first_8bit``: an int8 K/V pool). ``verdict`` is the whole comparison,
apart from the records it reads.
"""

from __future__ import annotations

import copy
import json
import random
import subprocess
import sys
from pathlib import Path

import model_bytes_ouro
import stats
from checkpoint import code_tokens

MIN_AGREE = 8
#: read on the chip over 25 runs (PERF.md section 6, PR 35): the program
#: 10.0-34.3, a faulty program (``what_if``) 253-471
NEAR_TIE_ULPS = 64
#: against ``as_stated`` (PERF.md section 6, PR 35, 103 audited streams):
#: the program 0.000012-0.00012, the same rows through 8 bits 0.0064-0.0067
K_ROWS_FIRST = 0.0009
#: of a run's 112-256 looked-at rows the program 0.33-0.575 (one stream
#: 0.062-0.78), the bf16-stream reference none (its nearest row 0.0027)
K_ROWS_SAME = 0.05
#: the program 0.0083-0.0105 (the shortest prompts read highest), the
#: bf16-stream reference 0.0176-0.0210: twice as far, no more (PERF.md)
K_ROWS_DEEP = 0.0137
#: a pool that shared rows between passes reads 0
PASSES_APART = 1.0
TWIN_SAMPLE = 2
#: a control's readings (``cache_audit_ouro.compare``) and the program's
#: that they take the place of
CONTROLS = {
    "bf16_residual": {"lead_rows_same": "lead_rows_same_bf16_residual",
                      "deep": "deep_bf16_residual"},
    "first_8bit": {"first": "first_8bit"},
}
HERE = Path(__file__).resolve().parent


def sample_requests(done: list[dict], seed: int, n: int) -> list[dict]:
    """``n`` of the completed requests, seeded, the longest prompt among
    them (the rows' error grows with the context they are read over)."""
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
    before = run.get("serving_before")
    passes = ctx.config["model"]["total_ut_steps"]
    edges = model_bytes_ouro.capture_edges(run)
    lines.append({"loop": {
        **{k: serving.get(k) for k in (
            "loop_passes", "loop_kv_rows_read", "loop_decode_ticks", "loop_chunk_rows",
            "loop_chunks", "loop_exit_before_last", "kv_bytes_per_token",
            "kv_pool_bytes", "kv_pages_free")},
        "live_rows_a_tick_in_window": model_bytes_ouro.live_rows_a_tick(
            before, serving, passes),
        "kv_rows_read_a_tick_in_window": model_bytes_ouro.rows_read_a_tick(before, serving),
        "kv_rows_read_a_tick_in_capture": (
            model_bytes_ouro.rows_read_a_tick(*edges) if edges else None),
        "chunk_position_in_capture": (
            model_bytes_ouro.chunk_position(*edges) if edges else None),
        "backlog_wait_us": stats.hist_delta(before, serving, "backlog_wait_us"),
        "prefix_hits": serving.get("prefix_hits"),
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
            **ref, "limit_bf16_ulps": NEAR_TIE_ULPS, "limit_k_rows_first": K_ROWS_FIRST,
            "limit_k_rows_same": K_ROWS_SAME, "limit_k_rows_deep": K_ROWS_DEEP,
            "limit_passes_apart": PASSES_APART}})
    compared, holds = verdict(ref, len(short), m["attempted"],
                              serving.get("loop_exit_before_last"))
    # the cell reports the end-to-end metrics whose lists in the manifest
    # name it (one whose sets spread too widely is left off a list)
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


def verdict(ref: dict | None, short: int, attempted: int,
            exit_before_last: int | None, controls: bool = True) -> tuple[dict, bool]:
    """Every number ``correct`` rests on beside its limit, and whether
    all hold. ``ref`` is the reference child's last line, or None;
    ``exit_before_last`` the serving program's own counter. The last
    number is how many of ``CONTROLS`` this run's comparison refuses."""
    samples = ref["samples"] if ref else []
    cache = (ref or {}).get("cache") or {}
    rows = cache.get("rows") or []

    def worst(key, pick=max):
        return pick((r[key] for r in rows), default=None)

    deficit = max((s["max_deficit_bf16_ulps"] for s in samples), default=None)
    audit_deficit = max(
        (s["max_deficit_bf16_ulps"] for s in cache.get("samples") or []), default=None)
    looked = sum(r["lead_rows"] for r in rows)
    same = sum(r["lead_rows_same"] for r in rows) / looked if looked else None
    left_early = None
    if exit_before_last is not None and ref:
        left_early = (exit_before_last + (cache.get("loop_exit_before_last") or 0)
                      + ref["would_leave_before_last"])
    compared = {
        "short_streams": stats.compared(short, 0),
        "requests_due": stats.compared(attempted, 1, at_most=False),
        "reference_samples": stats.compared(len(samples), 1, at_most=False),
        "max_deficit_bf16_ulps": stats.compared(deficit, NEAR_TIE_ULPS),
        "audit_max_deficit_bf16_ulps": stats.compared(audit_deficit, NEAR_TIE_ULPS),
        "k_rows_first_rel_err": stats.compared(worst("first"), K_ROWS_FIRST),
        "k_rows_same_as_stated_share": stats.compared(same, K_ROWS_SAME, at_most=False),
        "k_rows_deep_rel_err": stats.compared(worst("deep"), K_ROWS_DEEP),
        "passes_apart": stats.compared(worst("passes_apart", min), PASSES_APART,
                                       at_most=False),
        "exit_before_last": stats.compared(left_early, 0),
    }
    if controls:
        told = [c for c in CONTROLS if rows and refused(
            ref, c, compared, short, attempted, exit_before_last)]
        compared["controls_refused"] = stats.compared(
            len(told) if rows else None, len(CONTROLS), at_most=False)
    return compared, all(c["holds"] for c in compared.values())


def refused(ref: dict, control: str, compared: dict, *counts) -> list[str]:
    """The limits that hold for the program (``compared``) and not with
    a control's rows in its place: none means that this comparison
    cannot tell the two apart."""
    swapped = copy.deepcopy(ref)
    for row in swapped["cache"]["rows"]:
        row.update({key: row[source] for key, source in CONTROLS[control].items()})
    return [k for k, c in verdict(swapped, *counts, controls=False)[0].items()
            if compared[k]["holds"] and not c["holds"]]


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
        [sys.executable, str(HERE / "reference_ouro.py"), str(spec)],
        stdout=subprocess.PIPE, text=True, cwd=str(ctx.root), timeout=cfg["timeout_s"],
    )
    out = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    if proc.returncode != 0 or not out:
        print(f"benchmark: reference child exited {proc.returncode}", file=sys.stderr)
        return None
    return json.loads(out[-1])
