"""From a chat load process's raw records to metrics and ``correct``,
for a ``glm5_next_text`` configuration (``chat_measure.py``'s rules, with
this model's reference and what its three cache kinds keep).

``correct`` is true only if (a) every stream that finished has exactly
its ``max_tokens`` tokens; (b) for a seeded sample of ``reference_sample``
(4) completed requests due inside the window, THE LONGEST COMPLETED
PROMPT among them, every emitted token lies within ``NEAR_TIE_ULPS`` bf16
steps of the top of the plain reference's teacher-forced logits at its
position (``lib/reference_glm5_next.py``, a child of its own on the free
chip), the reference attending the blocks the PROGRAM picked; (c) what the
program's engine holds and picks for each sample, put through it again
beside other live streams — prompt + timed tokens through the chunk
program, then 64 tokens of its own through the decode window
(``lib/cache_audit_glm5_next.py``; the served programs with a look at the
selection as two results more) — lies within stated limits of the
reference's, as rms error over rms: ``STATE_FIRST`` at layer 0's
delta-rule state, ``STATE_DEEP`` at the last delta-rule layer's,
``LATENT_ROWS`` and ``INDEX_ROWS`` at the sparse-latent layer's pages,
``ATTENDED_ROWS`` at that layer's output for the chunk rows that select
and, apart, for the decode ticks; (d) the blocks picked differ from the
reference's own top-k in at most ``PICKED_DIFFER`` of a sample's chunk
rows' blocks and ``PICKED_DIFFER_DECODE`` of its decode ticks' (how near a
tie the worst of them was is printed: ``picked_score_gap``,
``picked_rank_gap``); (e) the server that served the window says a cached
token costs at most ``KV_BYTES_PER_TOKEN`` (pages for the sparse-latent
layer alone) and that its decode selected (``dsa_rows_picked`` /
``dsa_rows_in_context`` at most ``PICKED_SHARE``); (f) each of seven
controls, computed in every run, FAILS a limit that the program passes
(``controls_refused``): the reference attending every row on the longest
sample, by its chunk rows and by its decode ticks apart; the reference
with †3's other gate and the reference with one residual stream on the
shortest; the program's states through bf16 (seen by their bit patterns:
``STATE_2BYTE_SHARE``); a picker that never scored, in the chunk rows and
in the decode ticks of the longest sample. The limits and their two
readings (the program's largest, a faulty program's smallest) are beside
the constants and in ``PERF.md`` section 6 (PR 43). ``verdict`` is the
whole comparison, apart from the records it reads.
"""

from __future__ import annotations

import json
import random
import subprocess
import sys
from pathlib import Path

import model_bytes_kda_dsa as mb
import stats
from checkpoint import code_tokens

#: each limit with its two readings: the program's largest, a faulty program's
#: smallest (my chip runs, PR 43, call g7 = chip call 92: seven runs on seven
#: seeds of the tree as the review left it, 28 samples of 3,012-12,149 rows;
#: call g6 = chip call 91, 24 samples of the tree before the review, whose
#: served programs differ by two results, read inside the same ranges but for
#: the steps below the top, at most 77.2 there; PERF.md section 6).
#: bf16 steps below the reference's top: the program 31.5-90.1; the reference
#: with one residual stream 145.0-226.8, with the other gate 329.6-390.0 (the
#: reference attending every row reads 56.4-107.1: tokens cannot tell it, the
#: sparse-latent sublayer's output below does)
NEAR_TIE_ULPS = 110
#: layer 0's delta-rule state at the stream's last position: the program
#: 0.00417-0.00446; the reference with one residual stream 0.0168-0.0183,
#: with the other gate 0.666-0.736
STATE_FIRST = 0.012
#: the last delta-rule layer's state: the program 0.034-0.142; the reference
#: with one residual stream 0.561-0.701, with the other gate 1.12-1.21
STATE_DEEP = 0.3
#: the share of the two states' float32 values that bf16 could hold (low 16
#: bits zero): the program 0.00003-0.00006 (exact zeros and chance), the
#: program's states through bf16 1.0. No error limit can carry this control:
#: the reference with its state and the sums read from it held to bf16 at every
#: step, in the program's place, reads 0.00029-0.00035 at layer 0 and
#: 0.019-0.087 at the last delta-rule layer and its tokens 36.1-59.1 steps
#: (call g7, a sample a run), all INSIDE the limits: the program's own bf16
#: inputs leave more than a 2-byte state would
STATE_2BYTE_SHARE = 0.1
#: the sparse-latent layer's latent rows and pooled indexer rows: the program
#: 0.069-0.086 and 0.069-0.087; against the rows of the reference with one
#: residual stream 0.46-0.58, with the other gate 1.16-1.25
LATENT_ROWS = 0.2
INDEX_ROWS = 0.2
#: the sparse-latent sublayer's output at the rows that select (a few rows
#: carry each softmax, so bf16 inputs show). Chunk rows: the program
#: 0.188-0.239, against the reference attending every row 0.843-0.978. Decode
#: ticks (64 a sample, the audit's own): 0.172-0.264 against 1.086-1.254
ATTENDED_ROWS = 0.45
#: the share of picked blocks that differ from the reference's own top-k (it
#: grows with the context). Chunk rows: the program 0.0150-0.0597; a picker
#: that never scored (the first 512 blocks) 0.17 on the shortest samples,
#: 0.524-0.638 on the longest of each run (7,860-12,149 rows), which is where
#: it is judged
PICKED_DIFFER = 0.15
#: the same of the decode ticks, which are a stream's LAST 64 positions, where
#: the share is largest: the program 0.022-0.100 (0.074-0.100 at 12,149 rows);
#: a tick that never scored 0.31 on the shortest samples, 0.740-0.833 on the
#: longest of each run
PICKED_DIFFER_DECODE = 0.3
#: rows attended over rows in context, decode, in the window: the selection acts
PICKED_SHARE = 0.7
KV_BYTES_PER_TOKEN = 1088
CONTROLS = ("no_selection", "no_selection_decode", "softplus_gate", "one_stream",
            "state_bf16", "unscored_picks", "unscored_picks_decode")
COUNTERS = (
    "moe_tokens", "moe_local_pairs", "moe_expert_tokens", "moe_experts_touched",
    "kda_decode_ticks", "kda_row_ticks", "kda_chunks", "kda_chunk_rows",
    "dsa_rows_in_context", "dsa_rows_picked", "dsa_rows_fetched", "dsa_index_rows_scored",
    "dsa_row_ticks_selecting", "dsa_chunk_rows_in_context", "dsa_chunk_rows_picked",
    "dsa_chunk_rows_fetched", "dsa_chunk_index_rows_scored", "dsa_chunk_rows_selecting",
    "kv_bytes_per_token", "kv_pool_bytes", "kv_pages_free", "kda_state_bytes")
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
        "live_rows_a_tick_in_window": _per_layer(
            ctx, mb.per(before, serving, "kda_row_ticks", "kda_decode_ticks"), mb.kda_layers),
        "context_rows_a_live_row_in_window": _per_layer(
            ctx, mb.per(before, serving, "dsa_rows_in_context", "kda_row_ticks"),
            lambda cfg: 1.0 / mb.kda_layers(cfg)),
        # the engagement reading: 1.0 would mean the cell never selects
        "dsa_rows_picked_over_in_context_in_window": picked_share,
        "dsa_rows_fetched_over_picked_in_window": mb.per(
            before, serving, "dsa_rows_fetched", "dsa_rows_picked"),
        "selecting_share_of_row_ticks_in_window": _per_layer(
            ctx, mb.per(before, serving, "dsa_row_ticks_selecting", "kda_row_ticks"),
            lambda cfg: 1.0 / mb.kda_layers(cfg)),
        "chunk_context_in_window": mb.per(
            before, serving, "dsa_chunk_rows_in_context", "kda_chunk_rows"),
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
            **ref, "limit_bf16_ulps": NEAR_TIE_ULPS, "limit_state_first": STATE_FIRST,
            "limit_state_deep": STATE_DEEP, "limit_state_2byte_share": STATE_2BYTE_SHARE,
            "limit_latent_rows": LATENT_ROWS,
            "limit_index_rows": INDEX_ROWS, "limit_attended_rows": ATTENDED_ROWS,
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


def _per_layer(ctx, value, layers):
    return None if value is None else value / layers(ctx.config["model"])


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
        "state_first_rel_err": stats.compared(worst("state_first"), STATE_FIRST),
        "state_deep_rel_err": stats.compared(worst("state_deep"), STATE_DEEP),
        "state_2byte_share": stats.compared(worst("state_2byte_share"), STATE_2BYTE_SHARE),
        "latent_rows_rel_err": stats.compared(worst("latent_rows"), LATENT_ROWS),
        "index_rows_rel_err": stats.compared(worst("index_rows"), INDEX_ROWS),
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
        "no_selection": breaks(deficit_of("no_selection"), NEAR_TIE_ULPS)
        or breaks(least("attended_rows_no_selection"), ATTENDED_ROWS),
        # a decode tick that ignored its indexer: the ticks' rows alone
        "no_selection_decode": breaks(
            least("attended_rows_decode_no_selection"), ATTENDED_ROWS),
        "softplus_gate": breaks(deficit_of("softplus_gate"), NEAR_TIE_ULPS)
        or breaks(least("state_first_softplus_gate"), STATE_FIRST),
        "one_stream": breaks(deficit_of("one_stream"), NEAR_TIE_ULPS)
        or breaks(least("state_deep_one_stream"), STATE_DEEP),
        "state_bf16": breaks(least("state_2byte_share_bf16"), STATE_2BYTE_SHARE),
        # on the longest sample: the more blocks there are, the more a picker
        # that never scored misses
        "unscored_picks": breaks(worst("picked_differ_unscored"), PICKED_DIFFER),
        "unscored_picks_decode": breaks(
            worst("picked_differ_unscored_decode"), PICKED_DIFFER_DECODE),
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
        [sys.executable, str(HERE / "reference_glm5_next.py"), str(spec)],
        stdout=subprocess.PIPE, text=True, cwd=str(ctx.root), timeout=cfg["timeout_s"],
    )
    out = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    if proc.returncode != 0 or not out:
        print(f"benchmark: reference child exited {proc.returncode}", file=sys.stderr)
        return None
    return json.loads(out[-1])
