"""The plain reference's verdict on a sample of requests served by an
``exaone_moe`` (K-EXAONE) checkpoint: the benchmark's own copy.

Run as a child of its own after the dataflow has exited and the chip is
free: ``python benchmark/lib/reference_k_exaone.py <in.json>``. For each
sampled request it computes the model's forward pass teacher-forced over
prompt + emitted tokens and reports, for every emitted token, how many
bf16 steps it lies below the top of the reference's own logits at its
position (sampled tokens are not compared: two correct programs part
within a few tokens at bf16 with random weights). It keeps the K and V
rows of every position at the audited layers (``cache_audit_k_exaone
.entries``: layer 0, the first global layer, the last window layer, the
last layer) and, when its own arrays are dropped, has
``cache_audit_k_exaone.serve`` (same process: one claim on the chip)
serve the sampled prompts once more through the program's engine and
compares what that engine holds in its pages and rings.

The mathematics is written here, in float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``, and shares no code with the
program: pre-norm, RMSNorm of q and k over the head, rotate-half rotary
on the sliding layers only, dense masks (a band of ``sliding_window``
rows, or the causal triangle) over the whole sequence a block of query
rows at a time, the sigmoid router over all experts with the bias in the
choice only, a loop over the experts this rank holds (each
applied to every row, weighted by its normalised unbiased score where it
was chosen and by 0 elsewhere), the shared expert. No cache, no ring, no
paging, no batching. What the absent experts would add is left out, as
in the program. Every matrix is held to the program's int8 weights alone
(symmetric, per output channel, ``max|w| / 127``), so the comparison
measures the program's bf16 activations, caches and arithmetic, not the
quantization; embedding, routers and norms are the checkpoint's bf16.

Controls, computed in every run, each of which must FAIL the limits the
program passes (``chat_measure_k_exaone.verdict``): ``full_everywhere``
(no band mask: every layer attends causally over everything) on the
longest sample, ``rope_on_global`` (rotary on the global layers too) on
the two shortest, and the program's own layer-0 rows through 8 bits
(``cache_audit_k_exaone.compare``).

To fit a 16k-token sample beside float32 weights: one layer's weights at
a time (read from the checkpoint, used for every sample, dropped), every
sample padded to the smallest of ``pads`` that holds it (one compiled
program a layer kind and pad), scores a block of queries at a time. The
last stdout line is the result.
"""

from __future__ import annotations

import json
import math
import sys
import time
from pathlib import Path

VARIANTS = ("as_served", "full_everywhere", "rope_on_global")


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from safetensors import safe_open

    import cache_audit_k_exaone as audit  # beside this file
    from dora_tpu import backend

    spec = json.load(open(sys.argv[1]))
    backend.init_compile_cache()
    device = backend.require_accelerator("benchmark reference")
    ckpt = Path(spec["checkpoint"])
    hf = json.loads((ckpt / "config.json").read_text())
    pads, max_new, q_block = sorted(spec["pads"]), spec["max_new"], spec["q_block"]
    f32 = jnp.float32
    heads, kvh, hd = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    q_w, kv_w = heads * hd, kvh * hd
    eps, top_k, window = hf["rms_norm_eps"], hf["num_experts_per_tok"], hf["sliding_window"]
    layers = hf["num_hidden_layers"]
    sliding = [k == "sliding_attention" for k in hf["layer_types"]]
    held = hf["num_experts"] // hf["ep_size"]
    first = spec.get("ep_rank", 0) * held
    kept_layers = audit.entries(hf["layer_types"])
    theta = hf["rope_parameters"]["rope_theta"]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)

    where = json.loads((ckpt / "model.safetensors.index.json").read_text())["weight_map"]
    files = {f: safe_open(str(ckpt / f), framework="np") for f in set(where.values())}

    def raw(name):
        return jnp.asarray(files[where[name]].get_tensor(name)).astype(f32)

    @jax.jit
    def as_served(w):
        """HF [out, in] -> [in, out], held to int8 per output channel."""
        w = w.T
        s = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0, 1e-12)
        return jnp.clip(jnp.round(w / s), -127, 127) * s

    def matrix(name):
        return as_served(raw(name))

    def norm(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def rotate(x, c, s):
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)

    def swiglu(w, x):
        return (jax.nn.silu(x @ w["gate"]) * (x @ w["up"])) @ w["down"]

    def qkv_rows(w, u, positions, rope):
        """Normed rows -> q [T, H, hd], k, v [T, KV, hd] at ``positions``;
        ``rope`` is 1.0 or 0.0 (traced: angles of 0 rotate nothing)."""
        t = u.shape[0]
        q = norm((u @ w["q"]).reshape(t, heads, hd), w["q_norm"])
        k = norm((u @ w["k"]).reshape(t, kvh, hd), w["k_norm"])
        v = (u @ w["v"]).reshape(t, kvh, hd)
        angles = positions.astype(f32)[:, None] * jnp.asarray(inv, f32)[None] * rope
        c, s = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
        return rotate(q, c, s), rotate(k, c, s), v

    def attention(w, u, band, rope):
        """``band``: how many rows back a row sees (traced: the window,
        or the whole length for a causal layer)."""
        t = u.shape[0]
        q, k, v = qkv_rows(w, u, jnp.arange(t), rope)
        kr, vr = (jnp.repeat(a, heads // kvh, axis=1) for a in (k, v))

        def block(a):
            qa = jax.lax.dynamic_slice_in_dim(q, a, q_block)
            s = jnp.einsum("qhd,khd->hqk", qa, kr) / math.sqrt(hd)
            back = (a + jnp.arange(q_block))[:, None] - jnp.arange(t)[None]
            seen = (back >= 0) & (back < band)
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khd->qhd", p, vr)

        out = jax.lax.map(block, jnp.arange(0, t, q_block))
        rows = jnp.concatenate([k.reshape(t, kv_w), v.reshape(t, kv_w)], -1)
        return out.reshape(t, q_w) @ w["o"], rows

    def moe(w, x):
        scores = jax.nn.sigmoid(x @ w["router"])
        _, ids = jax.lax.top_k(scores + w["bias"], top_k)
        chosen = jnp.take_along_axis(scores, ids, -1)
        if hf.get("norm_topk_prob", True):
            chosen = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
        chosen = chosen * hf["routed_scaling_factor"]

        def one(y, expert):  # the experts held here, one after another
            number, weights = expert
            w_e = (chosen * (ids == number)).sum(-1)
            return y + swiglu(weights, x) * w_e[:, None], None

        y, _ = jax.lax.scan(one, swiglu(w["shared"], x),
                            (first + jnp.arange(held), w["experts"]))
        return y

    @jax.jit
    def layer(w, x, band, rope):
        """One layer (a program a kind of MLP and a length) -> (its
        output, its K|V rows [pad, 2 * KV * hd])."""
        with jax.default_matmul_precision("highest"):
            a, rows = attention(w, norm(x, w["attn_norm"]), band, rope)
            x = x + a
            h = norm(x, w["ffn_norm"])
            return x + (swiglu(w["dense"], h) if "dense" in w else moe(w, h)), rows

    @jax.jit
    def score(x, out_norm, head, start, emitted):
        with jax.default_matmul_precision("highest"):
            rows = norm(x[start - 1 + jnp.arange(max_new)], out_norm) @ head
        chosen = jnp.take_along_axis(rows, emitted[:, None], axis=1)[:, 0]
        return rows.max(-1), chosen

    def ffn(prefix):
        return {k: matrix(f"{prefix}{k}_proj.weight") for k in ("gate", "up", "down")}

    def attention_weights(i):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        return {
            "attn_norm": raw(p + "input_layernorm.weight"),
            "q": matrix(a + "q_proj.weight"), "k": matrix(a + "k_proj.weight"),
            "v": matrix(a + "v_proj.weight"), "o": matrix(a + "o_proj.weight"),
            "q_norm": raw(a + "q_norm.weight"), "k_norm": raw(a + "k_norm.weight"),
        }

    def layer_weights(i):
        p = f"model.layers.{i}."
        m = p + "mlp."
        w = {**attention_weights(i),
             "ffn_norm": raw(p + "post_attention_layernorm.weight")}
        if hf["mlp_layer_types"][i] == "dense":
            w["dense"] = ffn(m)
        else:
            w["router"] = raw(m + "gate.weight").T
            w["bias"] = raw(m + "gate.e_score_correction_bias")
            w["shared"] = ffn(m + "shared_experts.")
            each = [ffn(f"{m}experts.{e}.") for e in range(first, first + held)]
            w["experts"] = {k: jnp.stack([e[k] for e in each]) for k in each[0]}
        return w

    t0 = time.perf_counter()

    def said(what):
        print(f"reference: {what} at {time.perf_counter() - t0:.1f}s", file=sys.stderr,
              flush=True)

    samples = spec["samples"]
    lengths = [len(s["prompt"]) + len(s["emitted"]) for s in samples]
    order = sorted(range(len(samples)), key=lambda j: lengths[j])
    # which samples run which control: the longest, and the two shortest
    runs_control = {"full_everywhere": set(order[-1:]), "rope_on_global": set(order[:2])}
    embed = raw("model.embed_tokens.weight")
    states = []  # one a sample: {variant: x}
    for sample, n in zip(samples, lengths):
        pad = next((p for p in pads if p >= n), None)
        if pad is None or len(sample["emitted"]) > max_new or pad % q_block:
            raise ValueError(f"sample of {n} tokens, pads {pads}, q_block {q_block}")
        ids = np.zeros((pad,), np.int32)
        ids[:n] = sample["prompt"] + sample["emitted"]
        states.append({"as_served": embed[jnp.asarray(ids)]})
    # rows[j][variant][layer]: K|V [n, 2 * KV * hd] float32 on the host
    rows = [{v: {} for v in VARIANTS} for _ in samples]
    for i in range(layers):
        w = layer_weights(i)
        for j, n in enumerate(lengths):
            x = states[j]
            # a control parts from the reference where its first layer differs
            if j in runs_control["full_everywhere"] and sliding[i]:
                x.setdefault("full_everywhere", x["as_served"])
            if j in runs_control["rope_on_global"] and not sliding[i]:
                x.setdefault("rope_on_global", x["as_served"])
            for v in list(x):
                band = window if sliding[i] and v != "full_everywhere" else len(x[v])
                rope = float(sliding[i] or v == "rope_on_global")
                x[v], kv = layer(w, x[v], jnp.int32(band), jnp.float32(rope))
                if i in kept_layers:
                    rows[j][v][i] = np.asarray(kv[:n])
        del w, kv
        said(f"layer {i}")
    out_norm, head = raw("model.norm.weight"), matrix("lm_head.weight")

    def verdict(sample, x):
        emitted = sample["emitted"]
        em = np.zeros((max_new,), np.int32)
        em[: len(emitted)] = emitted
        top, chosen = jax.device_get(score(
            x, out_norm, head, jnp.asarray(len(sample["prompt"]), jnp.int32), jnp.asarray(em)))
        deficits = []
        for k in range(len(emitted)):
            t = float(top[k])
            ulp = 2.0 ** (math.floor(math.log2(abs(t))) - 7) if t else 1.0
            deficits.append((t - float(chosen[k])) / ulp)
        return {
            "i": sample["i"], "prompt_tokens": len(sample["prompt"]),
            "emitted": len(emitted), "max_deficit_bf16_ulps": max(deficits),
            "tokens_off_top": sum(gap > 0 for gap in deficits),
            "worst_position": int(np.argmax(deficits)),
        }

    verdicts = {v: [verdict(s, x[v]) for s, x in zip(samples, states) if v in x]
                for v in VARIANTS}
    what_if = {
        v: {"max_deficit_bf16_ulps": max(r["max_deficit_bf16_ulps"] for r in found),
            "least_deficit_bf16_ulps": min(r["max_deficit_bf16_ulps"] for r in found),
            "tokens_off_top": sum(r["tokens_off_top"] for r in found),
            "emitted": sum(r["emitted"] for r in found),
            "prompt_tokens": [r["prompt_tokens"] for r in found]}
        for v, found in verdicts.items() if v != "as_served" and found
    }
    seconds = time.perf_counter() - t0
    said("tokens scored")
    del states, out_norm, head

    cache = None
    if spec.get("audit") is not None:
        # layer 0's K|V row of a position depends on its token alone: the
        # reference of the rows the audit engine's decode ticks write
        w0 = attention_weights(0)

        @jax.jit
        def first_rows(w0, x, positions):
            with jax.default_matmul_precision("highest"):
                _, k, v = qkv_rows(w0, norm(x, w0["attn_norm"]), positions,
                                   float(sliding[0]))
            t = x.shape[0]
            return jnp.concatenate([k.reshape(t, kv_w), v.reshape(t, kv_w)], -1)

        served = audit.serve(
            spec["checkpoint"], spec["audit"], [s["prompt"] for s in samples],
            min(spec["audit_decode"], max_new))
        compared = []
        for j, (sample, got) in enumerate(zip(samples, served["streams"])):
            t = len(sample["prompt"])
            tail = np.asarray(got["emitted"][:-1], np.int32)  # inputs of the ticks run
            decode0 = np.asarray(first_rows(
                w0, embed[jnp.asarray(tail)],
                jnp.asarray(t + np.arange(len(tail)), jnp.int32)))
            compared.append(audit.compare(
                got, {v: r for v, r in rows[j].items() if r}, decode0, t,
                hf["layer_types"], window))
        said("engine audited")
        cache = {"rows": compared, "layers": kept_layers,
                 **{k: v for k, v in served.items() if k != "streams"}}
    print(json.dumps({"device": device, "samples": verdicts["as_served"],
                      "what_if": what_if, "cache": cache, "seconds": seconds}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
