"""From an agents load process's raw records to metrics and ``correct``,
for a ``kimi_linear`` configuration (``chat_measure.py``'s rules, with this
model's reference and what its two cache kinds and the snapshot between
them keep).

``correct`` is true only if (a) every stream that finished has exactly its
``max_tokens`` tokens; (b) for a seeded sample of ``reference_sample``
completed requests due inside the window, THE LONGEST PROMPT first (every
request in the window was served from its prefix's branch snapshot + the
re-prefilled rows + decode beside 63 other rows), every emitted token lies
within ``NEAR_TIE_ULPS`` bf16 steps of the top of the plain reference's
teacher-forced logits at its position, the reference running ONE forward
pass over the whole sequence at the published widths
(``lib/reference_kimi_linear.py``, a child of its own on the free chip);
(c) that first sample, replayed through the program's engine as the timed
run met it (``lib/cache_audit_kimi_linear.py``: the two warm prompts of its
prefix, then the sample), is granted from the BRANCH snapshot at the last
chunk edge inside the shared pages (``snapshot_granted_samples``), the copy
is a copy (``restore_bits_differ`` 0, ``snapshot_2byte_share``) OF THAT
DEPTH'S STATE (the slot as the restore left it against the reference's
state at the grant's boundary: ``restored_state_*``; the decays forget a
boundary within a few hundred rows, so the end's state could not tell) and
what its slot holds at the end lies within stated limits of the
reference's, as rms error over rms: ``STATE_FIRST`` / ``STATE_DEEP`` at the
first and the last KDA layer's float32 state, ``LATENT_ROWS`` at the first
and the last latent layer's pages, with at most ``STATE_2BYTE_SHARE`` of
those states' values representable in bfloat16; the program's router, on
the audit's rows, chooses the float32 reference's experts
(``ROUTER_ROWS_DIFFER``); (d) the server that served the window says a
cached token costs at most ``KV_BYTES_PER_TOKEN``, saved at least one
branch snapshot, granted at least ``HIT_TOKENS_SHARE`` of the admitted
prompt tokens and kept at least ``LIVE_ROWS`` rows live a tick; (e) each
control, computed in every run, FAILS a limit that the program passes
(``controls_refused``): the reference that lost its state at the grant's
boundary (``zero_state``: a wrong restored state; by the state it leaves
and by its tokens), the reference whose state went through bfloat16
(``state_bf16``: by its bit patterns), the reference whose router's scores
went through bfloat16 (``router_bf16``: by its tokens). The limits and
their two readings are beside the constants and in ``PERF.md`` section 6
(PR 58). ``verdict`` is the whole comparison, apart from the records it
reads.
"""

from __future__ import annotations

import json
import random
import subprocess
import sys
from pathlib import Path

import model_bytes_kda_mla as mb
import stats
from checkpoint import code_tokens

#: each limit with its two readings: the program's largest over its seeds, a
#: faulty program's smallest (my chip runs, PR 58, calls c2, c3 and f: 13 runs
#: on 13 seeds up to 4,294,967,291, 39 samples of 6,294-12,314 prompt rows,
#: every first sample a request of prefix 2 granted 9,984 of its 10,428 rows
#: from the branch snapshot; PERF.md section 6).
#: bf16 steps below the reference's top: the program 57.6-101.4 (half of a
#: run's tokens are off the top: eight of 256 experts a row, chosen again in
#: each of eight layers, part a bf16 program from a float32 reference at the
#: router's near-ties: 9 % of the rows of the reference that merely lost its
#: state choose otherwise); the reference under GLM-5.3-Flash's gate 362-427,
#: every token off the top. The references that lost their state (61.5-121.8)
#: or held it to bfloat16 (64.4-111.5) read as the program does: tokens cannot
#: tell them, the readings below do
NEAR_TIE_ULPS = 200
#: the first KDA layer's float32 state, at the grant's boundary as restored
#: (0.0035-0.0036) and after the audited request's last row (0.0035-0.0036); a
#: grant without its snapshot restores zeros: 1.0; a row of another depth
#: some 1.4 (two states that share nothing)
STATE_FIRST = 0.05
#: the last KDA layer's: as restored 0.285-0.403, at the end 0.282-0.376 (its
#: inputs went through six bf16 layers and their routers); zeros 1.0
STATE_DEEP = 0.6
#: the first and the last latent layer's cached rows, and the last layer's
#: rows past the grant alone: the program 0.070-0.074, 0.207-0.218 and
#: 0.200-0.220; the reference that lost its state at the boundary, past the
#: grant, 0.407-0.421
LATENT_ROWS = 0.3
#: the share of float32 KDA state values that bf16 could hold (low 16 bits
#: zero): the program at most 0.0001 (pool row, restored slot, slot at the
#: end), the bfloat16 reference 1.0
STATE_2BYTE_SHARE = 0.1
#: the share of the audit's 512 rows whose eight experts are not the float32
#: reference's on the same rows: the program 0.0 in every run; a router whose
#: scores went through bfloat16 0.844-0.902
ROUTER_ROWS_DIFFER = 0.1
KV_BYTES_PER_TOKEN = 2560
#: prefix_hit_tokens gained over prompt tokens admitted, in the window:
#: 0.9698-0.9706 (the schedule gives 0.97: a grant ends within a chunk and a
#: page of the prefix); the control run without the branch rule 0.943 (by
#: then a request with a short tail had left a row inside each prefix)
HIT_TOKENS_SHARE = 0.85
#: mean live rows a decode tick in the window, of 64 slots: 61.5-62.1; the
#: control run without the branch rule 47.4
LIVE_ROWS = 48
CONTROLS = ("zero_state", "state_bf16", "router_bf16", "bounded_gate")
COUNTERS = (
    "kda_decode_ticks", "kda_row_ticks", "kda_chunks", "kda_chunk_rows",
    "mla_rows_in_context", "mla_rows_swept", "mla_chunk_rows_in_context",
    "moe_tokens", "moe_local_pairs", "moe_experts_touched",
    "state_snapshots_saved", "state_snapshots_branch_saved",
    "state_snapshots_restored", "state_snapshots_evicted",
    "state_snapshot_bytes_copied", "state_snapshots_held",
    "state_snapshot_pool_bytes", "kda_state_bytes", "kv_bytes_per_token",
    "kv_pool_bytes", "kv_pages_free", "prefix_hits", "prefix_misses",
    "prefix_hit_tokens", "prefix_cached_pages", "prefix_evictions")
HERE = Path(__file__).resolve().parent


def gained(before: dict | None, after: dict | None, key: str):
    a, b = (before or {}).get(key), (after or {}).get(key)
    return None if a is None or b is None else b - a


def prompt_of(plan: dict, r: dict) -> list[int]:
    """The ids the run sent for record ``r`` (``caller`` -1: the warm wave)."""
    request = (plan["warm"][r["k"]] if r["caller"] < 0
               else plan["requests"][r["caller"]][r["k"]])
    return plan["prefixes"][request["prefix"]] + request["tail_ids"]


def sample_requests(done: list[dict], seed: int, n: int,
                    branching: set[int]) -> list[dict]:
    """``n`` of the completed requests, seeded, THE LONGEST PROMPT OF A
    PREFIX THAT BRANCHED first (the audited one: its grant came from a
    branch snapshot, and the state was carried furthest; of any prefix
    where none branched), the others in the order they were sent."""
    if not done:
        return []
    first = [r for r in done if r["prefix"] in branching] or done
    longest = max(first, key=lambda r: (r["prompt_tokens"], -r["i"]))
    rest = [r for r in done if r is not longest]
    picked = random.Random(seed).sample(rest, min(n - 1, len(rest)))
    return [longest] + sorted(picked, key=lambda r: r["i"])


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
    model = ctx.config["model"]
    hit_tokens = gained(before, serving, "prefix_hit_tokens")
    prefilled = gained(before, serving, "kda_chunk_rows")
    hit_share = (None if None in (hit_tokens, prefilled) or hit_tokens + prefilled <= 0
                 else hit_tokens / (hit_tokens + prefilled))
    live_rows = _over(mb.per(before, serving, "kda_row_ticks", "kda_decode_ticks"),
                      mb.kda_layers(model))
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
        "live_rows_a_tick_in_window": live_rows,
        "context_rows_a_live_row_in_window": _over(
            mb.per(before, serving, "mla_rows_in_context", "kda_row_ticks"),
            mb.mla_layers(model) / max(mb.kda_layers(model), 1)),
        "latent_kv_swept_over_read_in_window": mb.per(
            before, serving, "mla_rows_swept", "mla_rows_in_context"),
        "experts_touched_a_layer_a_tick_in_window": _over(
            mb.per(before, serving, "moe_touched", "kda_decode_ticks"),
            mb.expert_layers(model)),
        "chunks_in_window": gained(before, serving, "kda_chunks"),
        # the engagement readings: near 0 would mean the cell measures cold prefill
        "prefix_hits_in_window": gained(before, serving, "prefix_hits"),
        "prefix_hit_tokens_in_window": hit_tokens,
        "prompt_rows_prefilled_in_window": prefilled,
        "prefix_hit_tokens_share_in_window": hit_share,
        "snapshots_saved_in_window": gained(before, serving, "state_snapshots_saved"),
        "branch_snapshots_saved_in_window": gained(
            before, serving, "state_snapshots_branch_saved"),
        "snapshots_restored_in_window": gained(before, serving, "state_snapshots_restored"),
        "snapshots_evicted_in_window": gained(before, serving, "state_snapshots_evicted"),
        "backlog_wait_us": stats.hist_delta(before, serving, "backlog_wait_us"),
    }}]
    warm = [r for r in reqs if r["due"] < t0 and r.get("first") is not None]
    if warm:
        start = min(r["sent"] for r in warm)
        wave = [r for r in warm if r["caller"] < 0]
        lines.append({"before_the_window": {
            "requests": len(warm), "warm_wave_requests": len(wave),
            "warm_wave_s": max(r["done"] for r in wave) - start if wave else None,
            "window_opened_after_s": t0 - start,
        }})
    # (a) finished streams hold exactly what was asked for
    short = [r["i"] for r in reqs
             if r.get("finish") is not None and not r.get("error")
             and len(r["tokens"]) != r["max_tokens"]]
    # (b)-(c) the plain reference and the cache audit on a seeded sample
    done = sorted((r for r in due if stats.ok(r) and r["caller"] >= 0),
                  key=lambda r: r["i"])
    import cache_audit_kimi_linear as audit

    env = ctx.config["node_env"]["llm"]
    chunk, page = int(env.get("DORA_PREFILL_CHUNK", 256)), int(env.get("DORA_PAGE_SIZE", 16))
    groups = len(plan["prefixes"])
    warm = {g: [prompt_of(plan, {"caller": -1, "k": j * groups + g}) for j in range(2)]
            for g in range(groups)}
    # a prefix BRANCHES where its cold warm prompt's own snapshot (its last
    # full chunk edge) lies past what the second one shares with it
    edges = {g: audit.branch_edge(b, page, chunk) for g, b in warm.items()}
    branching = {g for g, b in warm.items() if len(b[0]) // chunk * chunk > edges[g]}
    sample = sample_requests(done, ctx.seed, ctx.traffic.get("reference_sample", 3),
                             branching)
    ref = None
    if sample:
        specs = [{
            "i": r["i"], "caller": r["caller"], "prefix": r["prefix"],
            "prompt": prompt_of(plan, r), "emitted": r["tokens"],
            "befores": warm[r["prefix"]] if n == 0 else None,
            "branch_expected": int(r["prefix"] in branching),
            "granted_expected": edges[r["prefix"]],
        } for n, r in enumerate(sample)]
        ref = reference(ctx, specs, chunk)
        lines.append({"reference": ref and {
            **ref, "limit_bf16_ulps": NEAR_TIE_ULPS, "limit_state_first": STATE_FIRST,
            "limit_state_deep": STATE_DEEP, "limit_latent_rows": LATENT_ROWS,
            "limit_state_2byte_share": STATE_2BYTE_SHARE,
            "granted_expected": specs[0]["granted_expected"],
            "prefixes_that_branch": sorted(branching)}})
    compared, holds = verdict(
        ref, len(short), m["attempted"], serving.get("kv_bytes_per_token"),
        serving.get("state_snapshots_branch_saved"), hit_share, live_rows,
        sample and specs[0]["granted_expected"])
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


def _over(value, by):
    return None if value is None or not by else value / by


def verdict(ref: dict | None, short: int, attempted: int,
            kv_bytes_per_token: int | None, branch_saved: int | None,
            hit_share: float | None, live_rows: float | None,
            granted_expected: int | None) -> tuple[dict, bool]:
    """Every number ``correct`` rests on beside its limit, and whether all
    hold. ``ref`` is the reference child's last line, or None."""
    samples = ref["samples"] if ref else []
    what_if = (ref or {}).get("what_if") or {}
    cache = (ref or {}).get("cache") or {}
    deficit = max((s["max_deficit_bf16_ulps"] for s in samples), default=None)

    def worse(*keys):
        got = [cache.get(k) for k in keys]
        return None if any(v is None for v in got) else max(got)

    compared = {
        "short_streams": stats.compared(short, 0),
        "requests_due": stats.compared(attempted, 1, at_most=False),
        "reference_samples": stats.compared(len(samples), 1, at_most=False),
        # the program's engine grants the first sample from the branch
        # snapshot, at the last chunk edge inside the shared pages
        "snapshot_granted_samples": stats.compared(
            int(bool(cache.get("granted_from_snapshot"))
                and bool(granted_expected)
                and cache.get("granted_tokens") == granted_expected) if ref else None,
            1, at_most=False),
        "max_deficit_bf16_ulps": stats.compared(deficit, NEAR_TIE_ULPS),
        "state_first_rel_err": stats.compared(cache.get("state_first"), STATE_FIRST),
        "state_deep_rel_err": stats.compared(cache.get("state_last"), STATE_DEEP),
        "state_2byte_share": stats.compared(cache.get("state_2byte_share"),
                                            STATE_2BYTE_SHARE),
        # the copy where it stands: the pool's row, the slot it was restored to
        "snapshot_2byte_share": stats.compared(cache.get("snapshot_2byte_share"),
                                               STATE_2BYTE_SHARE),
        "restore_bits_differ": stats.compared(cache.get("restore_bits_differ"), 0),
        # ... of the state at THAT depth: the reference's at the boundary
        "restored_state_first_rel_err": stats.compared(
            cache.get("restored_state_first"), STATE_FIRST),
        "restored_state_deep_rel_err": stats.compared(
            cache.get("restored_state_last"), STATE_DEEP),
        "router_rows_differ": stats.compared(cache.get("router_rows_differ"),
                                             ROUTER_ROWS_DIFFER),
        "latent_rows_rel_err": stats.compared(
            worse("latent_rows_first", "latent_rows_last", "latent_rows_past_grant_last"),
            LATENT_ROWS),
        "latent_row_padding": stats.compared(cache.get("latent_row_padding"), 0),
        "kv_bytes_per_token": stats.compared(kv_bytes_per_token, KV_BYTES_PER_TOKEN),
        "branch_snapshots_saved": stats.compared(branch_saved, 1, at_most=False),
        "prefix_hit_tokens_share": stats.compared(hit_share, HIT_TOKENS_SHARE,
                                                  at_most=False),
        "live_rows_a_tick": stats.compared(live_rows, LIVE_ROWS, at_most=False),
    }

    def breaks(value, limit):
        return value is not None and value > limit

    def least(name):
        return (what_if.get(name) or {}).get("least_deficit_bf16_ulps")

    refused = {
        # a grant without its snapshot restores zeros (1.0 against the boundary's
        # state), and the rows it writes past the grant part from the reference's
        "zero_state": breaks(cache.get("restored_state_zero_state"), STATE_DEEP)
        and breaks(cache.get("latent_rows_past_grant_last_zero_state"), LATENT_ROWS),
        # a state through bfloat16: seen by its bit patterns
        "state_bf16": breaks(cache.get("state_2byte_share_bf16"), STATE_2BYTE_SHARE),
        # a router whose scores went through bfloat16: other experts
        "router_bf16": breaks(cache.get("router_rows_differ_bf16"), ROUTER_ROWS_DIFFER),
        # another gate under the same weights: other tokens
        "bounded_gate": breaks(least("bounded_gate"), NEAR_TIE_ULPS),
    }
    compared["controls_refused"] = stats.compared(
        sum(refused.values()) if ref else None, len(CONTROLS), at_most=False)
    return compared, all(c["holds"] for c in compared.values())


def reference(ctx, samples: list[dict], chunk: int) -> dict | None:
    cfg = ctx.config["reference"]
    env = ctx.config["node_env"]["llm"]
    spec = ctx.workdir / "reference_in.json"
    spec.write_text(json.dumps({
        "checkpoint": str(ctx.workdir / "checkpoint"), "pads": cfg["pads"],
        "q_block": cfg["q_block"], "max_new": int(env["DORA_MAX_NEW_TOKENS"]),
        "audit_decode": cfg["audit_decode"], "samples": samples, "audit": env,
        "chunk": chunk, "ep_rank": int(env.get("DORA_EP_RANK", 0)),
    }))
    proc = subprocess.run(
        [sys.executable, str(HERE / "reference_kimi_linear.py"), str(spec)],
        stdout=subprocess.PIPE, text=True, cwd=str(ctx.root), timeout=cfg["timeout_s"],
    )
    out = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    if proc.returncode != 0 or not out:
        print(f"benchmark: reference child exited {proc.returncode}", file=sys.stderr)
        return None
    return json.loads(out[-1])
