"""From a chat load process's raw records to metrics and ``correct``,
for a ``KeyeVL2`` configuration (``chat_measure.py``'s rules, with this
model's reference and what its two-leaf pages keep and its indexer picks).

``correct`` is true only if (a) every stream that finished has exactly
its ``max_tokens`` tokens; (b) for a seeded sample of ``reference_sample``
(4) completed requests due inside the window, THE LONGEST COMPLETED
PROMPT among them, every emitted token lies within ``NEAR_TIE_ULPS`` bf16
steps of the top of the plain reference's teacher-forced logits at its
position (``lib/reference_keye_vl2.py``, a child of its own on the free
chip), the reference attending at every layer the positions the PROGRAM
picked; (c) what the program's engine holds and picks for each sample,
put through it again beside other live streams — prompt + timed tokens
through the chunk program, then 32 tokens of its own through the decode
window (``lib/cache_audit_keye_vl2.py``; the served programs with a look
at the selection as two results more a layer) — lies within stated limits
of the reference's, as rms error over rms: ``KV_ROWS_FIRST`` /
``IK_ROWS_FIRST`` at layer 0's pages, ``KV_ROWS_LAST`` / ``IK_ROWS_LAST``
at the last layer's, ``ATTENDED_ROWS`` at the last layer's sublayer
output for the chunk rows that select and, apart, for the decode ticks;
(d) the positions picked at the last layer differ from the reference's
own top-2,048 in at most ``PICKED_DIFFER`` of a sample's chunk rows'
picks and ``PICKED_DIFFER_DECODE`` of its decode ticks' (how near a tie
the worst of them was is printed: ``picked_score_gap``,
``picked_rank_gap``); (e) the server that served the window says a cached
token costs at most ``KV_BYTES_PER_TOKEN`` and that its decode selected
(``dsa_rows_picked`` / ``dsa_rows_in_context`` at most ``PICKED_SHARE``);
(f) each of four controls, computed in every run, FAILS a limit that the
program passes (``controls_refused``; its readings: PERF.md section 6): the
reference attending every row on the longest sample (tokens, the last
layer's output at chunk rows and at decode ticks, the last layer's pages);
the program's layer-0 rows through 8 bits (K|V and indexer keys); the
reference without QK-norm on the shortest (tokens, K|V rows of both
layers); a picker that never scored (chunk rows and decode ticks). The
limits and their two readings (the program's largest, a faulty program's
smallest) are beside the constants and in ``PERF.md`` section 6 (PR 49).
``verdict`` is the whole comparison, apart from the records it reads.
"""

from __future__ import annotations

import json
import random
import subprocess
import sys
from pathlib import Path

import model_bytes_gqa_dsa as mb
import stats
from checkpoint import code_tokens

#: each limit with its two readings: the program's largest, a faulty
#: program's smallest (my chip runs, PR 49: calls a3-a4, nine runs of the
#: final tree on nine seeds, 30 samples of 4,868-12,617 rows; calls a1-a2,
#: four runs of the first version, read inside the same ranges but for the
#: steps below the top, at most 6.65 there; PERF.md section 6).
#: bf16 steps below the reference's top: the program 0.0-5.2; the reference
#: without QK-norm 71.5-165.9 (the shortest sample); attending every row
#: 0.0-33.4 (the longest: tokens cannot tell it, the last layer's output
#: and pages below do)
NEAR_TIE_ULPS = 12
#: layer 0's K|V rows and indexer keys: the program 0.00257-0.00262 and
#: 0.00278-0.00295; its rows through 8 bits 0.0082-0.0090 and 0.0066-0.0070;
#: K|V against the reference without QK-norm 0.043-0.047
KV_ROWS_FIRST = 0.005
IK_ROWS_FIRST = 0.0045
#: the last layer's: the program 0.0095-0.0127 and 0.0096-0.0135; against
#: the reference attending every row 0.070-0.182 and 0.069-0.229, without
#: QK-norm 0.67-0.77 and 0.67-0.83
KV_ROWS_LAST = 0.03
IK_ROWS_LAST = 0.03
#: the last layer's sublayer output at the rows that select (a few rows
#: carry each softmax, so bf16 inputs show): the program 0.0039-0.0076 at
#: chunk rows and 0.0039-0.0071 at decode ticks; against the reference
#: attending every row 0.047-0.185 and 0.080-0.301
ATTENDED_ROWS = 0.02
#: the share of the last layer's picked positions that differ from the
#: reference's own top-k: the program 0.0055-0.0116 at chunk rows and
#: 0.0059-0.0172 at decode ticks (the last 32 positions of a stream); a
#: picker that never scored (the first 2,048 positions) 0.301-0.698 and
#: 0.122-0.923
PICKED_DIFFER = 0.06
PICKED_DIFFER_DECODE = 0.06
#: rows attended over rows in context, decode, in the window: the selection acts
PICKED_SHARE = 0.6
KV_BYTES_PER_TOKEN = 26112
CONTROLS = ("no_selection", "rows_8bit", "no_qk_norm", "unscored_picks")
COUNTERS = (
    "moe_tokens", "moe_local_pairs", "moe_expert_tokens", "moe_experts_touched",
    "dsa_decode_ticks", "dsa_row_ticks", "dsa_chunk_rows",
    "dsa_rows_in_context", "dsa_rows_picked", "dsa_rows_fetched", "dsa_index_rows_scored",
    "dsa_row_ticks_selecting", "dsa_chunk_rows_in_context", "dsa_chunk_rows_picked",
    "dsa_chunk_rows_fetched", "dsa_chunk_index_rows_scored", "dsa_chunk_rows_selecting",
    "kv_bytes_per_token", "kv_pool_bytes", "kv_pages_free")
HERE = Path(__file__).resolve().parent


def sample_requests(done: list[dict], seed: int, n: int) -> list[dict]:
    """``n`` of the completed requests, seeded, the longest completed
    prompt among them (the selection's and the state's error grow with
    the context they are read over)."""
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
    before, serving = run.get("serving_before"), run.get("serving_after") or {}
    due = [r for r in reqs if stats.in_window(r["due"], t0, t1)]
    c = run.get("compiles") or {}
    picked_share = mb.per(before, serving, "dsa_rows_picked", "dsa_rows_in_context")
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
        **{k: serving.get(k) for k in COUNTERS},
        "live_rows_a_tick_in_window": mb.per(
            before, serving, "dsa_row_ticks", "dsa_decode_ticks"),
        "context_rows_a_live_row_in_window": _per_layer(
            ctx, mb.per(before, serving, "dsa_rows_in_context", "dsa_row_ticks")),
        # the engagement reading: 1.0 would mean the cell never selects
        "dsa_rows_picked_over_in_context_in_window": picked_share,
        "dsa_rows_fetched_over_picked_in_window": mb.per(
            before, serving, "dsa_rows_fetched", "dsa_rows_picked"),
        "selecting_share_of_row_ticks_in_window": mb.per(
            before, serving, "dsa_row_ticks_selecting", "dsa_row_ticks"),
        "chunk_context_in_window": _per_layer(
            ctx, mb.per(before, serving, "dsa_chunk_rows_in_context", "dsa_chunk_rows")),
        "chunk_fetched_over_picked_in_window": mb.per(
            before, serving, "dsa_chunk_rows_fetched", "dsa_chunk_rows_picked"),
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
            **ref, "limit_bf16_ulps": NEAR_TIE_ULPS, "limit_kv_rows_first": KV_ROWS_FIRST,
            "limit_ik_rows_first": IK_ROWS_FIRST, "limit_kv_rows_last": KV_ROWS_LAST,
            "limit_ik_rows_last": IK_ROWS_LAST, "limit_attended_rows": ATTENDED_ROWS,
            "limit_picked_differ": PICKED_DIFFER,
            "limit_picked_differ_decode": PICKED_DIFFER_DECODE}})
    compared, holds = verdict(ref, len(short), m["attempted"],
                              serving.get("kv_bytes_per_token"), picked_share)
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
    """A counter summed over the layers, a layer."""
    return None if value is None else value / ctx.config["model"]["num_hidden_layers"]


def verdict(ref: dict | None, short: int, attempted: int,
            kv_bytes_per_token: int | None, picked_share: float | None
            ) -> tuple[dict, bool]:
    """Every number ``correct`` rests on beside its limit, and whether
    all hold. ``ref`` is the reference child's last line, or None."""
    samples = ref["samples"] if ref else []
    what_if = (ref or {}).get("what_if") or {}
    rows = ((ref or {}).get("cache") or {}).get("rows") or []

    def worst(key):
        return max((r[key] for r in rows if r.get(key) is not None), default=None)

    def least(key):
        return min((r[key] for r in rows if r.get(key) is not None), default=None)

    deficit = max((s["max_deficit_bf16_ulps"] for s in samples), default=None)
    compared = {
        "short_streams": stats.compared(short, 0),
        "requests_due": stats.compared(attempted, 1, at_most=False),
        "reference_samples": stats.compared(len(samples), 1, at_most=False),
        "max_deficit_bf16_ulps": stats.compared(deficit, NEAR_TIE_ULPS),
        "kv_rows_first_rel_err": stats.compared(worst("kv_rows_first"), KV_ROWS_FIRST),
        "ik_rows_first_rel_err": stats.compared(worst("ik_rows_first"), IK_ROWS_FIRST),
        "kv_rows_last_rel_err": stats.compared(worst("kv_rows_last"), KV_ROWS_LAST),
        "ik_rows_last_rel_err": stats.compared(worst("ik_rows_last"), IK_ROWS_LAST),
        "attended_rows_rel_err": stats.compared(worst("attended_rows"), ATTENDED_ROWS),
        "attended_rows_decode_rel_err": stats.compared(
            worst("attended_rows_decode"), ATTENDED_ROWS),
        "picked_rows_compared": stats.compared(
            sum(r["picked_rows"] for r in rows) if rows else None, 1, at_most=False),
        "picked_differ_share": stats.compared(worst("picked_differ"), PICKED_DIFFER),
        # the decode tick's own selection: every sample's ticks select
        "picked_rows_decode_compared": stats.compared(
            min((r["picked_rows_decode"] for r in rows), default=None), 1, at_most=False),
        "picked_differ_decode_share": stats.compared(
            worst("picked_differ_decode"), PICKED_DIFFER_DECODE),
        "kv_bytes_per_token": stats.compared(kv_bytes_per_token, KV_BYTES_PER_TOKEN),
        "dsa_rows_picked_over_in_context": stats.compared(picked_share, PICKED_SHARE),
    }

    # a control is refused where one of its readings (the least over the
    # samples that ran it) breaks a limit that the program passes
    def breaks(value, limit):
        return value is not None and value > limit

    def deficit_of(name):
        return (what_if.get(name) or {}).get("least_deficit_bf16_ulps")

    refused = {
        # on the longest sample: what a program that ignored its indexer
        # would put out and cache there, chunk rows and decode ticks
        "no_selection": breaks(deficit_of("no_selection"), NEAR_TIE_ULPS)
        or breaks(least("attended_rows_no_selection"), ATTENDED_ROWS)
        or breaks(least("attended_rows_decode_no_selection"), ATTENDED_ROWS)
        or breaks(least("kv_rows_last_no_selection"), KV_ROWS_LAST)
        or breaks(least("ik_rows_last_no_selection"), IK_ROWS_LAST),
        # what an int8 page would hold, at the layer where nothing else has
        # been rounded yet
        "rows_8bit": breaks(least("kv_rows_first_8bit"), KV_ROWS_FIRST)
        or breaks(least("ik_rows_first_8bit"), IK_ROWS_FIRST),
        "no_qk_norm": breaks(deficit_of("no_qk_norm"), NEAR_TIE_ULPS)
        or breaks(least("kv_rows_first_no_qk_norm"), KV_ROWS_FIRST)
        or breaks(least("kv_rows_last_no_qk_norm"), KV_ROWS_LAST),
        # a picker that never scored (the first 2,048 positions), in the
        # chunk rows and in the decode ticks
        "unscored_picks": breaks(least("picked_differ_unscored"), PICKED_DIFFER)
        or breaks(least("picked_differ_unscored_decode"), PICKED_DIFFER_DECODE),
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
        [sys.executable, str(HERE / "reference_keye_vl2.py"), str(spec)],
        stdout=subprocess.PIPE, text=True, cwd=str(ctx.root), timeout=cfg["timeout_s"],
    )
    out = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    if proc.returncode != 0 or not out:
        print(f"benchmark: reference child exited {proc.returncode}", file=sys.stderr)
        return None
    return json.loads(out[-1])
