"""From the document-QA load process's raw records to metrics and
``correct``, for a ``kimi_k2`` configuration.

``correct`` is true only if (a) every stream that finished has exactly
its ``max_tokens`` tokens; (b) for a seeded sample of ``reference_sample``
(4) completed requests due inside the window, at least one a first ask
of its document and one a repeat ask (served from cached latent pages),
the plain reference (``lib/reference_kimi_k2.py``, a child of its own on
the free chip) teacher-forced over prompt + emitted tokens finds every
emitted token within ``NEAR_TIE_ULPS`` bf16 steps of the top of its own
logits at that position, and at most ``OFF_TOP_SHARE`` of the sample's
emitted tokens (pooled) anywhere but at the top; (c) the cache rows
``(c_kv, k_pe)`` that the program's engine writes for each sampled
prompt (``lib/cache_audit_kimi_k2.py``: same checkpoint, same node
environment, read from the pool's pages) lie within
``LATENT_ROW_REL_ERR`` of the reference's float32 rows at layer 0, as
rms error over rms; (d) the server that served the window holds at
least ``CACHE_BYTES_PER_VALUE`` bytes for each of a row's
``kv_lora_rank + qk_rope_head_dim`` values a layer (``kv_pool_bytes``
over the pool's rows, its own gauges: a tie of (c) to that process, not
a comparison). There are no twins here: two asks of a document end in
different questions.

The limits and their readings (my chip runs, PR 27; ``PERF.md`` section
6). (b): over 35 runs on 28 seeds, four of them traced, and a calibration
run (some 110 samples) the program's largest deficit was 29.5 steps and
its pooled off-top share 0.029-0.103. Three faulty programs served the same three requests (2k
cold, 2k from cached pages, 8k cold; 64 tokens each): without the roped
key's term every token is off the top, 293-297 steps down; without the
routed experts 26.6-38.2 steps, which the program's own near-ties reach,
but 25-33 % of the tokens are off the top (pooled 0.281); with the
latent rows rounded to 8 bits (per-row scale) 2.3-6.3 steps and 0.094
off the top: the same as the program, because 8 bits a row is about the
step of bf16 itself once a softmax has averaged it. So ``NEAR_TIE_ULPS``
= 90 is three times the program's 29.5 and far under 293,
``OFF_TOP_SHARE`` = 0.18 lies between 0.103 and 0.281, and no limit on
tokens sees an 8-bit cache. (c) does: over 17 runs on 14 seeds (68
prompts of 553-8,245 tokens) the program's layer-0 rows read
0.00280-0.00284 (three bf16 roundings: the normed input, the
projection's output, the stored row) and the same rows through 8 bits
0.00969-0.00986, so ``LATENT_ROW_REL_ERR`` = 0.005 lies between with a
factor of 1.8 to the one and 1.9 to the other; both repeat to three
digits. By layer 7 the two read 0.032 and 0.034: the residual stream's
own noise hides the step, which is why the verdict is taken at layer 0.

The cell's end-to-end metric is ``ttft_p95_ms`` (and ``setup_s``): the
time a cold 8k document's 33 chunks take behind the other callers'
chunks, one chunk and one decode window a dispatch, which repeats to 1 %
(eight runs on eight seeds, my chip runs, PR 27: sigma 1.06 %; half its
bound is 5 %). ``tokens_per_s``, ``tpot_p50_ms`` and ``tpot_p95_ms`` are
printed in the ``window`` line and are no metrics of the cell: over the
same runs their sigma was 0.87 % and 0.96 %, and the driver admits a new
cell only if the middle half of six runs spreads by less than half a
bound (1 % and 1.5 %), which by simulation wants sigma under 0.6 of
that limit. The driver refused ``tpot_p95_ms`` at 1.55 % once. Why they
swing: some 200 requests a window whose work differs 16-fold, output in
bursts after each cold document, 16 threads that race for the next plan
slot when several streams finish in one window; the 95th percentile is
the 10th largest.

Before the result line this also prints what the cell exists to
exercise: the share of prompt tokens served from the prefix cache, the
share of routed (token, expert) pairs that landed on this chip's
experts, the peak of pages in use and the pool's rows.
"""

from __future__ import annotations

import json
import random
import subprocess
import sys
from pathlib import Path

import stats
from checkpoint import code_tokens

NEAR_TIE_ULPS = 90
OFF_TOP_SHARE = 0.18
LATENT_ROW_REL_ERR = 0.005
CACHE_BYTES_PER_VALUE = 2
HERE = Path(__file__).resolve().parent


def sample_requests(done: list[dict], plan: dict, seed: int, n: int) -> list[dict]:
    """``n`` of the completed requests, seeded, with a first ask and a
    repeat ask among them where both kinds completed."""
    rng = random.Random(seed)
    first = [r for r in done if plan["requests"][r["i"]].get("ask") == 0]
    later = [r for r in done if plan["requests"][r["i"]].get("ask", 0) > 0]
    picked = [rng.choice(kind) for kind in (first, later) if kind]
    rest = [r for r in done if r not in picked]
    picked += rng.sample(rest, min(max(n - len(picked), 0), len(rest)))
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
    due = [r for r in reqs if stats.in_window(r["due"], t0, t1)]
    c = run.get("compiles") or {}
    lines = [{"window": {
        "seconds": t1 - t0, "requests_due": m["attempted"], "failed": m["failed"],
        "completed_in_window": m["completed_in_window"],
        "requests_per_s": m["requests_per_s"],
        "first_asks_due": sum(plan["requests"][r["i"]].get("ask") == 0 for r in due),
        "prompt_tokens_due": sum(r["prompt_tokens"] for r in due),
        "tokens_per_s": m["tokens_per_s"],
        "ttft_p50_ms": m.get("ttft_p50_ms"), "tpot_p50_ms": m.get("tpot_p50_ms"),
        "tpot_p95_ms": m.get("tpot_p95_ms"),
        "generator_lateness_ms": stats.lateness_ms(reqs, t0, t1),
        "compiles_in_window": (
            None if None in (c.get("before"), c.get("after"))
            else c["after"] - c["before"]),
        "plan_exhausted": raw["plan_exhausted"],
        "errors": sorted({str(r["error"])[:120] for r in reqs if r.get("error")})[:5],
    }}]
    serving = run.get("serving_after") or {}
    sent_tokens = sum(r["prompt_tokens"] for r in reqs if r.get("sent") is not None)
    moe_tokens = serving.get("moe_tokens")
    page = int(ctx.config["node_env"]["llm"].get("DORA_PAGE_SIZE", 16))
    model = ctx.config["model"]
    rows = ((serving.get("total_pages") or 0) + 1) * page  # the null page too
    row_bytes = (serving.get("kv_pool_bytes") or 0) / rows / model["num_hidden_layers"]
    bytes_ok = row_bytes >= CACHE_BYTES_PER_VALUE * (
        model["kv_lora_rank"] + model["qk_rope_head_dim"])
    lines.append({"cache_and_routing": {
        "prefix_hit_tokens": serving.get("prefix_hit_tokens"),
        "prompt_tokens_sent": sent_tokens,
        "prefix_hit_share": (serving.get("prefix_hit_tokens") or 0) / max(sent_tokens, 1),
        "moe_tokens": moe_tokens, "moe_local_pairs": serving.get("moe_local_pairs"),
        "local_pairs_per_token": (
            serving["moe_local_pairs"] / moe_tokens if moe_tokens else None),
        "moe_expert_tokens": serving.get("moe_expert_tokens"),
        "moe_experts_touched": serving.get("moe_experts_touched"),
        "peak_used_pages": serving.get("peak_used_pages"),
        "pool_rows": (serving.get("total_pages") or 0) * page,
        "latent_pool_bytes": serving.get("latent_pool_bytes"),
        "cache_bytes_a_row_a_layer": row_bytes, "cache_bytes_as_stated": bytes_ok,
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
    # (b) the plain reference on a seeded sample
    done = sorted(
        (r for r in due if stats.ok(r) and r["i"] >= ctx.traffic["callers"]),
        key=lambda r: r["i"],
    )
    sample = sample_requests(done, plan, ctx.seed, ctx.traffic.get("reference_sample", 4))
    ref_ok, ref = bool(sample), None
    if sample:
        ref = reference(ctx, [
            {"i": r["i"], "prompt": plan["requests"][r["i"]]["ids"], "emitted": r["tokens"]}
            for r in sample
        ])
        off_top = ref and (
            sum(s["tokens_off_top"] for s in ref["samples"])
            / max(sum(s["emitted"] for s in ref["samples"]), 1))
        cache = (ref or {}).get("cache")
        first_layer = cache and [layers[0] for layers in cache["rel_err"]]
        lines.append({"reference": ref and {
            **ref, "asks": {r["i"]: plan["requests"][r["i"]]["ask"] for r in sample},
            "off_top_share": off_top, "limit_bf16_ulps": NEAR_TIE_ULPS,
            "limit_off_top_share": OFF_TOP_SHARE,
            "cache_rel_err_layer0": first_layer,
            "cache_rel_err_layer0_8bit_control": cache and [
                layers[0] for layers in cache["rel_err_8bit"]],
            "limit_cache_rel_err_layer0": LATENT_ROW_REL_ERR,
        }})
        ref_ok = ref is not None and off_top <= OFF_TOP_SHARE and all(
            s["max_deficit_bf16_ulps"] <= NEAR_TIE_ULPS for s in ref["samples"]
        ) and bool(first_layer) and max(first_layer) <= LATENT_ROW_REL_ERR
    samples = ref["samples"] if ref else []
    compared = {
        "short_streams": stats.compared(len(short), 0),
        "requests_due": stats.compared(m["attempted"], 1, at_most=False),
        "reference_samples": stats.compared(len(samples), 1, at_most=False),
        "max_deficit_bf16_ulps": stats.compared(
            max((s["max_deficit_bf16_ulps"] for s in samples), default=None), NEAR_TIE_ULPS),
        "off_top_share": stats.compared(off_top if samples else None, OFF_TOP_SHARE),
        "cache_rel_err_layer0": stats.compared(
            max(first_layer) if samples and first_layer else None, LATENT_ROW_REL_ERR),
        "cache_bytes_a_value": stats.compared(
            row_bytes / (model["kv_lora_rank"] + model["qk_rope_head_dim"]),
            CACHE_BYTES_PER_VALUE, at_most=False),
    }
    metrics = (
        {"ttft_p95_ms": {"value": m["ttft_p95_ms"], "unit": "ms"}}
        if "ttft_p95_ms" in m else {}
    )
    return {
        "metrics": metrics, "attempted": m["attempted"], "failed": m["failed"],
        "correct": (not short and ref_ok and bytes_ok and m["attempted"] > 0
                    and not raw["plan_exhausted"]),
        "lines": lines, "reference_device": ref and ref["device"], "compared": compared,
    }


def reference(ctx, samples: list[dict]) -> dict | None:
    t, cfg = ctx.traffic, ctx.config["reference"]
    max_new = int(ctx.config["node_env"]["llm"]["DORA_MAX_NEW_TOKENS"])
    longest = t["document_tokens"]["max"] + t["question_tokens"]["max"] + max_new
    spec = ctx.workdir / "reference_in.json"
    env = ctx.config["node_env"]["llm"]
    spec.write_text(json.dumps({
        "checkpoint": str(ctx.workdir / "checkpoint"),
        "pad_to": -(-longest // cfg["q_block"]) * cfg["q_block"],
        "q_block": cfg["q_block"], "max_new": max_new, "samples": samples,
        "ep_rank": int(env.get("DORA_EP_RANK", 0)), "audit": env,
    }))
    proc = subprocess.run(
        [sys.executable, str(HERE / "reference_kimi_k2.py"), str(spec)],
        stdout=subprocess.PIPE, text=True, cwd=str(ctx.root),
        timeout=cfg["timeout_s"],
    )
    out = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    if proc.returncode != 0 or not out:
        print(f"benchmark: reference child exited {proc.returncode}", file=sys.stderr)
        return None
    return json.loads(out[-1])
