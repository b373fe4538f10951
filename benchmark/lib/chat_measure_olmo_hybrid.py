"""From a session load process's raw records to metrics and ``correct``,
for an ``olmo_hybrid`` configuration (``chat_measure.py``'s rules, with
this model's reference and what its two cache kinds and the snapshot
between them keep).

``correct`` is true only if (a) every stream that finished has exactly
its ``max_tokens`` tokens; (b) for a seeded sample of ``reference_sample``
completed requests due inside the window, THE LONGEST COMPLETED FOLLOW-UP
TURN first (a turn whose earlier turn completed: the timed run served it
from a state snapshot + the re-prefilled rows + decode), every emitted
token lies within ``NEAR_TIE_ULPS`` bf16 steps of the top of the plain
reference's teacher-forced logits at its position, the reference running
ONE forward pass over the whole history (``lib/reference_olmo_hybrid.py``,
a child of its own on the free chip); (c) that follow-up turn, replayed
through the program's engine as the timed run met it
(``lib/cache_audit_olmo_hybrid.py``), is granted from a snapshot at the
depth the earlier turn's last full chunk left (``granted_from_snapshot``),
the copy is a copy (the slot as the restore left it equals the snapshot
pool's row bit for bit, every leaf: ``restore_bits_differ`` 0; at most
``STATE_2BYTE_SHARE`` of the delta-rule states' float32 values in the
pool's row and in the restored slot representable in bfloat16:
``snapshot_2byte_share``) and what its slot holds at the end lies within
stated limits of the reference's, as rms error over rms: ``STATE_FIRST`` /
``STATE_DEEP`` at the first and the last linear layer's float32 state,
``KV_ROWS`` at the first and the last full layer's pages, with at most
``STATE_2BYTE_SHARE`` of those states' values representable in bfloat16
(``state_2byte_share``: a slot state the programs themselves round; the
low bits of a snapshot through bfloat16 are refilled by then, which is why
the copy is read where it stands); (d) the server that served the window
says a cached token costs at most ``KV_BYTES_PER_TOKEN``, granted at least
``GRANTED_TURNS`` of the follow-up turns due in the window a prefix and at
least ``HIT_TOKENS_SHARE`` of the admitted prompt tokens; (e) each of two
controls, computed in every run, FAILS a limit that the program passes
(``controls_refused``): the reference that lost its state at the grant's
boundary (``zero_state``: by its tokens), the reference whose state went
through bfloat16 (``state_bf16``: by its bit patterns). The limits and
their two readings are beside the constants and in ``PERF.md`` section 6
(PR 56). ``verdict`` is the whole comparison, apart from the records it
reads.
"""

from __future__ import annotations

import json
import random
import subprocess
import sys
from pathlib import Path

import model_bytes_gdn_hybrid as mb
import stats
from checkpoint import code_tokens

#: each limit with its two readings: the program's largest over its seeds, a
#: faulty program's smallest (my chip runs, PR 56, calls r1, r2, s6, k2, s6b,
#: f1, f2 and f3: 33 runs on 25 seeds up to 4,200,003,506, 99 samples of
#: 1,875-7,826 rows, every first sample a turn granted 7,168 of its 8,185
#: rows from a snapshot; a seed's first sample reads the same in every run of
#: it; PERF.md section 6).
#: bf16 steps below the reference's top: the program 7.7-23.1; the reference
#: that lost its state at the grant's boundary, 658 rows before the first
#: compared token, 37.6-81.8 (thin room on that side decides nothing: that
#: control is refused by its state too, in every run; the reference whose
#: state went through bfloat16 reads 9.0-27.1: tokens cannot tell it, the
#: state's bit patterns below do)
NEAR_TIE_ULPS = 34
#: the first linear layer's float32 state after the granted turn's last row:
#: the program 0.00286-0.00308; against the reference that lost its state
#: 0.0053-0.0190
STATE_FIRST = 0.004
#: the last linear layer's: the program 0.188-0.248; against the reference
#: that lost its state 0.591-0.764 (the bfloat16 one 0.047-0.068: inside)
STATE_DEEP = 0.4
#: the first and the last full layer's K/V rows: the program 0.0170-0.0189 and
#: 0.0926-0.1046; the last layer's against the reference that lost its state
#: 0.259-0.297
KV_ROWS = 0.16
#: the share of float32 delta-rule state values that bf16 could hold (low 16
#: bits zero), read in three places: the snapshot pool's row and the slot as
#: the restore left it, the program 0.00005-0.00008 (call f2), a save or a
#: restore through bfloat16 1.0 (benchmark/tests/test_cache_audit_olmo_hybrid
#: .py rounds each on the engine's own copy: bit patterns are the same on any
#: device); the slot at the end, the program 0.00003-0.00006, the bfloat16
#: reference 1.0
STATE_2BYTE_SHARE = 0.1
KV_BYTES_PER_TOKEN = 61440
#: prefix hits gained over follow-up turns due, in the window: 0.990-1.010 (a
#: hit is counted at admission, a turn where it was due); 0 without the cache
GRANTED_TURNS = 0.8
#: prefix_hit_tokens gained over prompt tokens admitted, in the window:
#: 0.8046-0.8133 (0.78 predicted from the schedule); 0 for cold prefill
HIT_TOKENS_SHARE = 0.4
CONTROLS = ("zero_state", "state_bf16")
COUNTERS = (
    "gdn_decode_ticks", "gdn_row_ticks", "gdn_chunks", "gdn_chunk_rows",
    "gdn_zero_starts", "gdn_chunk_positions", "global_kv_rows_read",
    "global_kv_rows_swept", "state_snapshots_saved", "state_snapshots_restored",
    "state_snapshots_evicted", "state_snapshot_bytes_copied", "state_snapshots_held",
    "state_snapshot_pool_bytes", "gdn_state_bytes", "kv_bytes_per_token",
    "kv_pool_bytes", "kv_pages_free", "prefix_hits", "prefix_misses",
    "prefix_hit_tokens", "prefix_cached_pages", "prefix_evictions")
HERE = Path(__file__).resolve().parent


def gained(before: dict | None, after: dict | None, key: str):
    a, b = (before or {}).get(key), (after or {}).get(key)
    return None if a is None or b is None else b - a


def histories(plan: dict, reqs: list[dict]) -> dict:
    """``(caller, conversation) -> {turn: record}`` of the requests that
    ended well."""
    out: dict = {}
    for r in reqs:
        if stats.ok(r):
            out.setdefault((r["caller"], r["conversation"]), {})[r["turn"]] = r
    return out


def prompt_of(plan: dict, told: dict, r: dict) -> list[int] | None:
    """The ids the run sent for ``r``: the plan's first prompt and messages
    and the answers the run received for the earlier turns; None where an
    earlier turn did not end well."""
    conv = plan["sessions"][r["caller"]][r["conversation"]]
    earlier = told.get((r["caller"], r["conversation"]), {})
    if any(j not in earlier for j in range(r["turn"])):
        return None
    ids = list(conv["first_ids"])
    for j in range(1, r["turn"] + 1):
        ids += earlier[j - 1]["tokens"] + conv["turns"][j]["message_ids"]
    return ids


def sample_requests(done: list[dict], seed: int, n: int) -> list[dict]:
    """``n`` of the completed requests, seeded, THE LONGEST FOLLOW-UP TURN
    first (the audited one: the state's error grows with the rows it was
    carried over), the others in the order they were sent."""
    follow = [r for r in done if r["turn"] > 0]
    if not follow:
        return []
    longest = max(follow, key=lambda r: (r["prompt_tokens"], -r["i"]))
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
    follow_ups = sum(1 for r in due if r["turn"] > 0)
    hits = gained(before, serving, "prefix_hits")
    hit_tokens = gained(before, serving, "prefix_hit_tokens")
    prefilled = gained(before, serving, "gdn_chunk_rows")
    granted_turns = None if hits is None or not follow_ups else hits / follow_ups
    hit_share = (None if None in (hit_tokens, prefilled) or hit_tokens + prefilled <= 0
                 else hit_tokens / (hit_tokens + prefilled))
    layers = mb.linear_layers(ctx.config["model"])
    lines = [{"window": {
        "seconds": t1 - t0, "requests_due": m["attempted"], "failed": m["failed"],
        "follow_up_turns_due": follow_ups,
        "first_turns_due": m["attempted"] - follow_ups,
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
        "live_rows_a_tick_in_window": _over(
            mb.per(before, serving, "gdn_row_ticks", "gdn_decode_ticks"), layers),
        "context_rows_a_live_row_in_window": _over(
            mb.per(before, serving, "global_kv_rows_read", "gdn_row_ticks"),
            mb.full_layers(ctx.config["model"]) / max(layers, 1)),
        "global_kv_swept_over_read_in_window": mb.per(
            before, serving, "global_kv_rows_swept", "global_kv_rows_read"),
        "chunk_context_in_window": mb.per(
            before, serving, "gdn_chunk_positions", "gdn_chunk_rows"),
        "chunks_in_window": gained(before, serving, "gdn_chunks"),
        "zero_starts_in_window": gained(before, serving, "gdn_zero_starts"),
        # the engagement readings: near 0 would mean the cell measures cold prefill
        "prefix_hits_in_window": hits,
        "prefix_hit_tokens_in_window": hit_tokens,
        "prompt_rows_prefilled_in_window": prefilled,
        "granted_turns_over_follow_ups_in_window": granted_turns,
        "prefix_hit_tokens_share_in_window": hit_share,
        "snapshots_saved_in_window": gained(before, serving, "state_snapshots_saved"),
        "snapshots_restored_in_window": gained(before, serving, "state_snapshots_restored"),
        "snapshots_evicted_in_window": gained(before, serving, "state_snapshots_evicted"),
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
    # (b)-(c) the plain reference and the cache audit on a seeded sample
    told = histories(plan, reqs)
    done = sorted((r for r in due if stats.ok(r) and prompt_of(plan, told, r) is not None),
                  key=lambda r: r["i"])
    sample = sample_requests(done, ctx.seed, ctx.traffic.get("reference_sample", 3))
    chunk = int(ctx.config["node_env"]["llm"].get("DORA_PREFILL_CHUNK", 256))
    ref = None
    if sample:
        specs = []
        for n, r in enumerate(sample):
            earlier = told[(r["caller"], r["conversation"])].get(r["turn"] - 1)
            before_ids = prompt_of(plan, told, earlier) if earlier else None
            specs.append({
                "i": r["i"], "turn": r["turn"], "prompt": prompt_of(plan, told, r),
                "emitted": r["tokens"],
                "before": before_ids if n == 0 else None,
                "granted_expected": len(before_ids) // chunk * chunk if before_ids else 0,
            })
        ref = reference(ctx, specs, chunk)
        lines.append({"reference": ref and {
            **ref, "limit_bf16_ulps": NEAR_TIE_ULPS, "limit_state_first": STATE_FIRST,
            "limit_state_deep": STATE_DEEP, "limit_kv_rows": KV_ROWS,
            "limit_state_2byte_share": STATE_2BYTE_SHARE}})
    compared, holds = verdict(ref, len(short), m["attempted"],
                              serving.get("kv_bytes_per_token"), granted_turns, hit_share)
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
            kv_bytes_per_token: int | None, granted_turns: float | None,
            hit_share: float | None) -> tuple[dict, bool]:
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
        # the first sample is a follow-up turn, and the program's engine
        # grants it from a snapshot at the depth the earlier turn left
        "snapshot_granted_samples": stats.compared(
            int(bool(cache.get("granted_from_snapshot"))
                and cache.get("granted_tokens") == ref.get("cut")) if ref else None,
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
        "kv_rows_rel_err": stats.compared(worse("kv_rows_first", "kv_rows_last"), KV_ROWS),
        "kv_bytes_per_token": stats.compared(kv_bytes_per_token, KV_BYTES_PER_TOKEN),
        "granted_turns_over_follow_ups": stats.compared(
            granted_turns, GRANTED_TURNS, at_most=False),
        "prefix_hit_tokens_share": stats.compared(hit_share, HIT_TOKENS_SHARE,
                                                  at_most=False),
    }

    def breaks(value, limit):
        return value is not None and value > limit

    refused = {
        # a grant without its snapshot: the tokens past the boundary part
        "zero_state": breaks(
            (what_if.get("zero_state") or {}).get("least_deficit_bf16_ulps"),
            NEAR_TIE_ULPS)
        or breaks(cache.get("state_last_zero_state"), STATE_DEEP),
        # a state through bfloat16: seen by its bit patterns
        "state_bf16": breaks(cache.get("state_2byte_share_bf16"), STATE_2BYTE_SHARE),
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
        "chunk": chunk,
    }))
    proc = subprocess.run(
        [sys.executable, str(HERE / "reference_olmo_hybrid.py"), str(spec)],
        stdout=subprocess.PIPE, text=True, cwd=str(ctx.root), timeout=cfg["timeout_s"],
    )
    out = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    if proc.returncode != 0 or not out:
        print(f"benchmark: reference child exited {proc.returncode}", file=sys.stderr)
        return None
    return json.loads(out[-1])
