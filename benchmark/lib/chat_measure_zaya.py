"""From a chat load process's raw records to metrics and ``correct``,
for a ``zaya`` configuration (``chat_measure.py``'s rules, with this
model's reference and what its pages and tails keep and its router
picks).

``correct`` is true only if (a) every stream that finished has exactly
its ``max_tokens`` tokens; (b) for a seeded sample of ``reference_sample``
(4) completed requests due inside the window, THE LONGEST COMPLETED among
them, every emitted token lies within ``NEAR_TIE_ULPS`` bf16 steps of the
top of the plain reference's teacher-forced logits at its position
(``lib/reference_zaya.py``, a child of its own on the free chip), the
reference routing each row by its OWN router wherever its two best biased
probabilities lie ``cache_audit_zaya.PICK_MARGIN`` apart and following the
program's pick only inside that margin; (c) what the program's engine holds and picks for each sample,
put through it again beside other live streams — prompt + timed tokens
through the chunk program, then 32 tokens of its own through the decode
window (``lib/cache_audit_zaya.py``; the served programs, which leave
every layer's pick in ``engine.selection``) — lies within stated limits of the
reference's, as rms error over rms: ``KV_ROWS_FIRST`` / ``KV_ROWS_LAST``
at the pages of layer 0 and of the last layer, ``TAIL_FIRST`` /
``TAIL_LAST`` at the slot's convolution and value-shift tails there after
the last tick; (d) at EVERY layer the expert the program picked is the
reference's own wherever the reference is clear of a tie, but for at most
``PICKS_DIFFER`` of a sample's rows (the largest share over the layers is
judged, every layer's is printed); (e) the
server that served the window says a cached token costs at most
``KV_BYTES_PER_TOKEN`` and that every routed token landed on a held expert
(``moe_local_pairs`` = ``moe_tokens``); (f) each of five controls,
computed in every run, FAILS a limit that the program passes
(``controls_refused``; its readings: PERF.md section 6): the reference
without the convolutions on the shortest sample (tokens, layer 0's pages),
with both value heads from the row's own position on the second shortest
(tokens, layer 0's pages), with every fourth row of the MIDDLE layer sent
to the expert after its own on the third shortest (that layer's picks),
the program's layer-0 rows through 8 bits, and the pick of a router that
starts every layer from zeros (the last layer's). The limits and their two readings (the program's largest, a
faulty program's smallest) are beside the constants and in ``PERF.md``
section 6 (PR 52). ``verdict`` is the whole comparison, apart from the
records it reads.
"""

from __future__ import annotations

import json
import random
import subprocess
import sys
from pathlib import Path

import model_bytes_cca_moe as mb
import stats
from checkpoint import code_tokens

#: each limit with its two readings: the program's largest, a faulty
#: program's smallest (my chip runs, PR 52: calls r1-r4 after the review, 76
#: samples of 1,527-5,808 rows of 19 runs on 19 seeds, the reference routing
#: itself outside the margin; ``forced`` = the first version's reading, every
#: row of every layer sent where the program sent it, 36 samples beside them;
#: PERF.md section 6).
#: bf16 steps below the reference's top: the program 6.6-48.9 (forced
#: 6.7-40.3); the reference without the convolutions 207.6-367.7 (with both
#: value heads from the row's own position 82.5-198.9: the tokens cannot
#: always tell it, layer 0's rows below do; with a wrong pick planted in the
#: middle layer 13.8-42.8: the tokens cannot tell it at all, that layer's
#: picks do)
NEAR_TIE_ULPS = 100
#: layer 0's pages, a few bf16 roundings: the program 0.0027-0.0030; the
#: same rows through 8 bits 0.0097-0.0107, against the reference without the
#: value shift 0.22-0.29, without the convolutions 0.83-0.89
KV_ROWS_FIRST = 0.006
#: the last layer's pages, the residual stream's bf16 noise, the weight
#: p[e]'s and the rows the two routers sent apart: the program 0.041-0.098
#: (forced 0.041-0.080); against a control's rows 0.63-1.30
KV_ROWS_LAST = 0.2
#: the tails after the last tick: layer 0's are a projection's own rows (the
#: program 0.0022-0.0024; through 8 bits 0.0082-0.0120; a tail one position
#: stale some 1.4); the last layer's carry the stream's noise (0.017-0.046,
#: forced 0.017-0.038; against a control's 0.55-1.19)
TAIL_FIRST = 0.006
TAIL_LAST = 0.15
#: the share of a sample's rows whose pick is not the reference's own although
#: the reference is clear of a tie (0.05), the LARGEST over the 20 layers: the
#: program 0.0023-0.0277 (median 0.0094; 0.0 at layers 0-2, the largest at
#: layers 5-19; forced 0.0017-0.0190); a wrong pick planted on every fourth
#: row of the middle layer 0.156-0.181 (call r4, two runs; with every eighth
#: row, calls r1-r3, 17 runs: 0.068-0.101, so 0.14-0.20), a router without carry
#: 0.27-0.66 at the last layer (the largest of a run's four samples, as the
#: program is read; single samples 0.074-0.66)
PICKS_DIFFER = 0.05
KV_BYTES_PER_TOKEN = 20480
CONTROLS = ("no_conv", "no_value_shift", "wrong_pick", "rows_8bit", "no_router_carry")
HERE = Path(__file__).resolve().parent


def sample_requests(done: list[dict], seed: int, n: int) -> list[dict]:
    """``n`` of the completed requests, seeded, the longest completed
    among them (prompt + emitted: the pages' and the stream's error grow
    with the context they are read over)."""
    if not done:
        return []
    longest = max(done, key=lambda r: (r["prompt_tokens"] + len(r["tokens"]), -r["i"]))
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
    before, serving = run.get("serving_before"), run.get("serving_after") or {}
    due = [r for r in reqs if stats.in_window(r["due"], t0, t1)]
    c = run.get("compiles") or {}
    lines = [{"window": {
        "seconds": t1 - t0, "requests_due": m["attempted"], "failed": m["failed"],
        "prompt_tokens_due": sum(r["prompt_tokens"] for r in due),
        "completed_in_window": m["completed_in_window"],
        "requests_per_s": m["requests_per_s"],
        "ttft_p50_ms": m.get("ttft_p50_ms"), "tpot_p50_ms": m.get("tpot_p50_ms"),
        "ttft_p95_ms": m.get("ttft_p95_ms"), "tpot_p95_ms": m.get("tpot_p95_ms"),
        "tokens_per_s": m["tokens_per_s"],
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
            "cca_decode_ticks", "cca_row_ticks", "cca_kv_rows_read", "cca_kv_rows_swept",
            "cca_tail_steps", "cca_zero_starts", "cca_chunks", "cca_chunk_rows",
            "cca_chunk_positions", "kv_bytes_per_token", "kv_pool_bytes", "kv_pages_free",
            "cca_tail_bytes")},
        "live_rows_a_tick_in_window": _per_layer(
            ctx, mb.per(before, serving, "cca_row_ticks", "cca_decode_ticks")),
        "kv_rows_read_a_tick_in_window": _per_layer(
            ctx, mb.per(before, serving, "cca_kv_rows_read", "cca_decode_ticks")),
        "kv_swept_over_read_in_window": mb.per(before, serving, "cca_kv_rows_swept",
                                               "cca_kv_rows_read"),
        "experts_touched_a_layer_tick_in_window": _per_layer(
            ctx, mb.per(before, serving, "moe_touched", "cca_decode_ticks")),
        "chunk_position_in_window": mb.per(before, serving, "cca_chunk_positions",
                                           "cca_chunks"),
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
    # (b)-(d) the plain reference and the cache audit on a seeded sample
    done = sorted(
        (r for r in due if stats.ok(r) and r["i"] >= ctx.traffic["callers"]),
        key=lambda r: r["i"],
    )
    sample = sample_requests(done, ctx.seed, ctx.traffic.get("reference_sample", 4))
    ref = None
    if sample:
        ref = reference(ctx, [
            {"i": r["i"], "prompt": plan["requests"][r["i"]]["ids"], "emitted": r["tokens"]}
            for r in sample
        ])
        lines.append({"reference": ref and {
            **ref, "limit_bf16_ulps": NEAR_TIE_ULPS,
            "limit_kv_rows_first": KV_ROWS_FIRST, "limit_kv_rows_last": KV_ROWS_LAST,
            "limit_tail_first": TAIL_FIRST, "limit_tail_last": TAIL_LAST,
            "limit_picks_differ": PICKS_DIFFER}})
    compared, holds = verdict(ref, len(short), m["attempted"], serving)
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


def _per_layer(ctx, value):
    return None if value is None else value / ctx.config["model"]["num_hidden_layers"]


def verdict(ref: dict | None, short: int, attempted: int,
            serving: dict) -> tuple[dict, bool]:
    """Every number ``correct`` rests on beside its limit, and whether
    all hold. ``ref`` is the reference child's last line, or None;
    ``serving`` the served engine's last snapshot."""
    samples = ref["samples"] if ref else []
    what_if = (ref or {}).get("what_if") or {}
    rows = ((ref or {}).get("cache") or {}).get("rows") or []

    def worst(*keys):
        return max((r[k] for r in rows for k in keys if r.get(k) is not None),
                   default=None)

    def least(key):
        return min((r[key] for r in rows if r.get(key) is not None), default=None)

    tokens, pairs = serving.get("moe_tokens"), serving.get("moe_local_pairs")
    deficit = max((s["max_deficit_bf16_ulps"] for s in samples), default=None)
    compared = {
        "short_streams": stats.compared(short, 0),
        "requests_due": stats.compared(attempted, 1, at_most=False),
        "reference_samples": stats.compared(len(samples), 1, at_most=False),
        "max_deficit_bf16_ulps": stats.compared(deficit, NEAR_TIE_ULPS),
        "kv_rows_first_rel_err": stats.compared(worst("kv_rows_first"), KV_ROWS_FIRST),
        "kv_rows_last_rel_err": stats.compared(worst("kv_rows_last"), KV_ROWS_LAST),
        "tail_first_rel_err": stats.compared(worst("tail_first"), TAIL_FIRST),
        "tail_last_rel_err": stats.compared(worst("tail_last"), TAIL_LAST),
        "decode_rows_audited": stats.compared(
            sum(r["rows"] - r["prompt_rows"] for r in rows) if rows else None, 1,
            at_most=False),
        "picks_differ_clear": stats.compared(worst("picks_differ_clear"), PICKS_DIFFER),
        "kv_bytes_per_token": stats.compared(
            serving.get("kv_bytes_per_token"), KV_BYTES_PER_TOKEN),
        "moe_pairs_not_landed": stats.compared(
            None if None in (tokens, pairs) else tokens - pairs, 0),
    }

    # a control is refused where one of its readings (the least over the
    # samples that ran it) breaks a limit that the program passes
    def breaks(value, limit):
        return value is not None and value > limit

    def tokens_break(variant):
        return breaks((what_if.get(variant) or {}).get("least_deficit_bf16_ulps"),
                      NEAR_TIE_ULPS)

    refused = {
        "no_conv": tokens_break("no_conv") or breaks(
            least("kv_rows_first_no_conv"), KV_ROWS_FIRST),
        "no_value_shift": tokens_break("no_value_shift") or breaks(
            least("kv_rows_first_no_value_shift"), KV_ROWS_FIRST),
        "wrong_pick": breaks(least("picks_differ_clear_wrong_pick"), PICKS_DIFFER),
        "rows_8bit": breaks(least("kv_rows_first_8bit"), KV_ROWS_FIRST),
        # a share of one sample's rows: the program is held to its LARGEST
        # over the samples, so a router without carry fails where its
        # largest breaks the limit (single samples read 0.074-0.66)
        "no_router_carry": breaks(
            worst("picks_differ_clear_no_router_carry"), PICKS_DIFFER),
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
        "audit_decode": cfg["audit_decode"], "samples": samples, "audit": env,
    }))
    proc = subprocess.run(
        [sys.executable, str(HERE / "reference_zaya.py"), str(spec)],
        stdout=subprocess.PIPE, text=True, cwd=str(ctx.root), timeout=cfg["timeout_s"],
    )
    out = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    if proc.returncode != 0 or not out:
        print(f"benchmark: reference child exited {proc.returncode}", file=sys.stderr)
        return None
    return json.loads(out[-1])
