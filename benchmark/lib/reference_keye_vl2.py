"""The plain reference's verdict on a sample of requests served by a
``KeyeVL2`` (Keye-VL-2.0's language model) checkpoint: the benchmark's
own copy.

Run as a child of its own after the dataflow has exited and the chip is
free: ``python benchmark/lib/reference_keye_vl2.py <in.json>``. First
``cache_audit_keye_vl2.serve`` (same process: one claim on the chip) puts
each sampled request's prompt + emitted tokens through the program's
engine once more and decodes a few tokens beyond them, and what that
engine holds is kept on the host; the program's arrays are dropped. Then,
for each sample, the model's forward pass teacher-forced over prompt +
emitted tokens (+ the audit's own decode tokens) is computed here and
reports, for every token the TIMED run emitted, how many bf16 steps it
lies below the top of the reference's own logits at its position (sampled
tokens are not compared: two correct programs part within a few tokens at
bf16 with random weights). ``top_k`` over near-ties is discontinuous, so
at EVERY layer the reference attends the positions the PROGRAM picked
(the audit's chunks over the same tokens, then its decode ticks:
``make_paged_engine(picks=True)``) and at the last layer the picked sets
are compared apart, a row at a time on the device (the share of positions
that differ from the reference's own top-k, and how near a tie each one
was: ``cache_audit_keye_vl2.picked_summary``).

The mathematics is written here, in float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``, and shares no code with the
program: RMSNorm, grouped-query attention with per-head QK-norm and
rotate-half rotary (ids alone: the three M-RoPE components are the
position) under a dense picked mask a block of query rows at a time, the
indexer's scores (LayerNorm on its key, rotary, relu, head weights) over
every earlier position, the softmax router over all experts renormalised
over the chosen, a loop over the experts this rank holds. No cache, no
paging, no batching. What the absent experts would add is left out, as in
the program. Every matrix is held to the program's int8 weights alone
(symmetric, per output channel, ``max|w| / 127``), so the comparison
measures the program's bf16 activations, caches and arithmetic, not the
quantization; embedding, routers and norms are the checkpoint's bf16.

Controls, computed in every run, each of which must FAIL a limit the
program passes (``chat_measure_keye_vl2.verdict``): ``no_selection``
(every row attends all of ``0..t``) on the longest sample, ``no_qk_norm``
on the shortest, and the program's own layer-0 rows through 8 bits
(``cache_audit_keye_vl2.compare``).

To fit a 16k-token sample beside float32 weights: one layer's weights at
a time (read from the checkpoint, used for every sample, dropped), a
sample's rows on the host between layers, scores a block of queries at a
time. Every sample is padded to the smallest of ``pads`` that holds it;
the cell gives ONE pad (16,384), so that every run uses the same programs
(a layer as served with and without the last layer's comparison, and the
two that a control changes) and none is compiled after a checkout's first
run. The last stdout line is the result.
"""

from __future__ import annotations

import gc
import json
import math
import sys
import time
from functools import partial
from pathlib import Path

VARIANTS = ("as_served", "no_selection", "no_qk_norm")
EXPERT_ROWS = 512  # rows of one block of an expert's rows (divides every pad)
INDEX_NORM_EPS = 1e-6  # the indexer's LayerNorm


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from safetensors import safe_open

    import cache_audit_keye_vl2 as audit  # beside this file
    from dora_tpu import backend

    spec = json.load(open(sys.argv[1]))
    backend.init_compile_cache()
    device = backend.require_accelerator("benchmark reference")
    ckpt = Path(spec["checkpoint"])
    hf = json.loads((ckpt / "config.json").read_text())
    pads, max_new, q_block = sorted(spec["pads"]), spec["max_new"], spec["q_block"]
    f32 = jnp.float32
    t0 = time.perf_counter()

    def said(what):
        print(f"reference: {what} at {time.perf_counter() - t0:.1f}s", file=sys.stderr,
              flush=True)

    # -- the program first: its pages and its picked positions, to the host ---
    samples = spec["samples"]
    timed = [s["prompt"] + s["emitted"] for s in samples]
    served = audit.serve(spec["checkpoint"], spec["audit"], timed,
                         min(spec["audit_decode"], max_new))
    # the engine's closures refer to one another: free its weights and pools
    # now, not when the collector next runs
    held_bytes = sum(a.nbytes for a in jax.live_arrays())
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    said(f"engine served the samples again ({held_bytes / 1e9:.3f} GB on the device, "
         f"{live / 1e9:.3f} after collecting)")
    sequences = [t + got["emitted"][:-1] for t, got in zip(timed, served["streams"])]
    lengths = [len(s) for s in sequences]

    d, heads, kv_heads = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"]
    hd, eps, theta = hf["head_dim"], hf["rms_norm_eps"], float(hf["rope_theta"])
    sa = hf["sa_config"]
    ih, idim, topk = sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]
    top_k, layers = hf["num_experts_per_tok"], hf["num_hidden_layers"]
    held = hf["num_experts"] // hf["ep_size"]
    first = spec.get("ep_rank", 0) * held
    last_layer = layers - 1

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

    def rotate(x, cos, sin):
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def angles(t, width):
        inv = 1.0 / theta ** (jnp.arange(0, width, 2, dtype=f32) / width)
        a = jnp.arange(t, dtype=f32)[:, None] * inv[None]
        return jnp.cos(a), jnp.sin(a)

    def swiglu(w, x):
        return (jax.nn.silu(x @ w["gate"]) * (x @ w["up"])) @ w["down"]

    def attention(w, x, n, given, switch, compare):
        """Picked grouped-query attention over ``x [T, d]`` (normed):
        rows below ``n`` attend the positions ``given [T, topk]`` (the
        program's), the padding past them everything before them. ->
        (output, the K|V rows as cached, the indexer's keys, and with
        ``compare`` a row's comparison of the given picks with the
        reference's own top-k)."""
        t = x.shape[0]
        g = heads // kv_heads
        q = (x @ w["q"]).reshape(t, heads, hd)
        k = (x @ w["k"]).reshape(t, kv_heads, hd)
        v = (x @ w["v"]).reshape(t, kv_heads, hd)
        if switch != "no_qk_norm":
            q, k = norm(q, w["q_norm"]), norm(k, w["k_norm"])
        cos, sin = angles(t, hd)
        q, k = rotate(q, cos[:, None], sin[:, None]), rotate(k, cos[:, None], sin[:, None])
        ki = x @ w["ik"]
        ki = ki - ki.mean(-1, keepdims=True)
        ki = ki * jax.lax.rsqrt(jnp.mean(ki * ki, -1, keepdims=True) + INDEX_NORM_EPS)
        ki = ki * w["i_norm_w"] + w["i_norm_b"]
        icos, isin = angles(t, idim)
        ki = rotate(ki, icos, isin)
        qi = rotate((x @ w["iq"]).reshape(t, ih, idim), icos[:, None], isin[:, None])
        wi = (x @ w["iw"]) * (ih ** -0.5 * idim ** -0.5)
        pos = jnp.arange(t)
        q = q.reshape(t, kv_heads, g, hd)

        def block(a):
            # a block of padding rows attends nothing: nobody reads its output
            return jax.lax.cond(
                a < n, attend, lambda a: (jnp.zeros((q_block, heads * hd), f32),
                                          jnp.zeros((q_block, 4), f32)), a)

        def attend(a):
            rows = a + jnp.arange(q_block)
            causal = pos[None, :] <= rows[:, None]
            mine = jax.lax.dynamic_slice_in_dim(given, a, q_block)
            stats = jnp.zeros((q_block, 4), f32)
            if compare:
                qa = jax.lax.dynamic_slice_in_dim(qi, a, q_block)
                wa = jax.lax.dynamic_slice_in_dim(wi, a, q_block)
                s = (jax.nn.relu(jnp.einsum("qjd,nd->qjn", qa, ki)) * wa[..., None]).sum(1)
                s = jnp.where(causal, s, -jnp.inf)
                kept, own = jax.lax.top_k(s, topk)
                theirs = jnp.take_along_axis(s, mine, axis=1)
                extra = theirs < kept[:, -1:]  # under the last kept score: not the reference's
                worst = jnp.where(extra, theirs, jnp.inf).min(-1)
                spread = jnp.maximum(kept[:, 0] - kept[:, -1], 1e-30)
                any_extra = extra.any(-1)
                rank = ((s > worst[:, None]).sum(-1) + 1 - topk) / topk
                stats = jnp.stack([
                    extra.sum(-1).astype(f32),
                    jnp.where(any_extra, rank, 0.0),
                    jnp.where(any_extra, (kept[:, -1] - worst) / spread, 0.0),
                    (own >= topk).sum(-1).astype(f32)], -1)
                stats = jnp.where(((rows >= topk) & (rows < n))[:, None], stats, 0.0)
            seen = causal
            if switch != "no_selection":
                sel = jnp.zeros((q_block, t), bool).at[
                    jnp.arange(q_block)[:, None], mine].set(True)
                dense = (rows < topk) | (rows >= n)
                seen = causal & (dense[:, None] | sel)
            qa = jax.lax.dynamic_slice_in_dim(q, a, q_block)
            sc = jnp.einsum("qkgd,tkd->kgqt", qa, k) * hd ** -0.5
            p = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), axis=-1)
            return jnp.einsum("kgqt,tkd->qkgd", p, v).reshape(q_block, heads * hd), stats

        ctx, stats = jax.lax.map(block, jnp.arange(0, t, q_block))
        out = ctx.reshape(t, heads * hd) @ w["o"]
        rows = jnp.concatenate([k.reshape(t, -1), v.reshape(t, -1)], -1)
        return out, rows, ki, stats.reshape(t, 4)

    def moe(w, x):
        p = jax.nn.softmax(x @ w["router"], axis=-1)
        chosen, ids = jax.lax.top_k(p, top_k)
        if hf.get("norm_topk_prob", True):
            chosen = chosen / chosen.sum(-1, keepdims=True)

        def one(y, expert):
            """An expert held here on the rows that chose it, ``EXPERT_ROWS``
            of them at a time (rows past the last are weighted 0)."""
            number, weights = expert
            mine = (ids == number).any(-1)
            w_e = (chosen * (ids == number)).sum(-1)
            order = jnp.argsort(~mine)  # stable: the expert's rows first, in order
            n_e, size = mine.sum(), min(EXPERT_ROWS, x.shape[0])

            def rows_block(j, y):
                rows = jax.lax.dynamic_slice_in_dim(order, j * size, size)
                valid = j * size + jnp.arange(size) < n_e
                out = swiglu(weights, x[rows]) * (w_e[rows] * valid)[:, None]
                return y.at[rows].add(out)

            return jax.lax.fori_loop(0, (n_e + size - 1) // size, rows_block, y), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                            (first + jnp.arange(held), w["experts"]))
        return y

    @partial(jax.jit, static_argnames=("switch", "compare"), donate_argnums=(1,))
    def one_layer(w, x, n, given, *, switch, compare):
        with jax.default_matmul_precision("highest"):
            a, rows, ki, stats = attention(
                w, norm(x, w["attn_norm"]), n, given, switch, compare)
            x = x + a
            x = x + moe(w, norm(x, w["ffn_norm"]))
        return x, (rows, ki, a, stats)

    @jax.jit
    def score(x, out_norm, head, start, emitted):
        with jax.default_matmul_precision("highest"):
            rows = norm(x[start - 1 + jnp.arange(max_new)], out_norm) @ head
        chosen = jnp.take_along_axis(rows, emitted[:, None], axis=1)[:, 0]
        return rows.max(-1), chosen

    def layer_weights(i):
        p = f"model.layers.{i}."
        a, m = p + "self_attn.", p + "mlp."
        return {
            "attn_norm": raw(p + "input_layernorm.weight"),
            "ffn_norm": raw(p + "post_attention_layernorm.weight"),
            **{x: matrix(a + f"{x}_proj.weight") for x in "qkvo"},
            "q_norm": raw(a + "q_norm.weight"), "k_norm": raw(a + "k_norm.weight"),
            "iq": matrix(a + "indexer.wq.weight"), "ik": matrix(a + "indexer.wk.weight"),
            "iw": matrix(a + "indexer.weights_proj.weight"),
            "i_norm_w": raw(a + "indexer.k_norm.weight"),
            "i_norm_b": raw(a + "indexer.k_norm.bias"),
            "router": raw(m + "gate.weight").T,
            "experts": {
                k: jnp.stack([matrix(f"{m}experts.{e}.{k}_proj.weight")
                              for e in range(first, first + held)])
                for k in ("gate", "up", "down")},
        }

    order = sorted(range(len(samples)), key=lambda j: lengths[j])
    # which samples run which control: the longest, and the shortest
    runs_control = {"no_selection": set(order[-1:]), "no_qk_norm": set(order[:1])}
    embed = np.asarray(raw("model.embed_tokens.weight"))
    # one a sample: {variant: rows}, kept on the HOST between layers, and
    # every layer's picks of the program, [L, pad, topk]
    states, given = [], []
    for j, (seq, n, got) in enumerate(zip(sequences, lengths, served["streams"])):
        pad = next((p for p in pads if p >= n), None)
        if pad is None or pad % q_block or pad < topk:
            raise ValueError(f"sample of {n} tokens, pads {pads}, q_block {q_block}")
        ids = np.zeros((pad,), np.int32)
        ids[:n] = seq
        x = embed[ids]
        states.append({"as_served": x})
        picks = np.zeros((layers, pad, topk), np.int16)
        picks[:, :n] = np.concatenate([got["picked"], got["picked_decode"]], 1)
        given.append(picks)
        for v in VARIANTS[1:]:
            if j in runs_control[v]:
                states[j][v] = x
    # found[j][variant]: what the audited layers would cache, on the host
    found = [{v: {} for v in VARIANTS} for _ in samples]
    for i in range(layers):
        w = layer_weights(i)
        for j, n in enumerate(lengths):
            x = states[j]
            mine = jnp.asarray(given[j][i].astype(np.int32))
            for v in list(x):
                compare = i == last_layer and v == "as_served"
                out, (rows, ki, a, stats) = one_layer(
                    w, jnp.asarray(x[v]), jnp.int32(n), mine,
                    switch=None if v == "as_served" else v, compare=compare)
                x[v] = np.asarray(out)
                del out
                name = {0: "first", last_layer: "last"}.get(i)
                if name:
                    found[j][v][f"kv_{name}"] = np.asarray(rows[:n])
                    found[j][v][f"ik_{name}"] = np.asarray(ki[:n])
                if i == last_layer and v != "no_qk_norm":
                    found[j][v]["attended"] = np.asarray(a[:n])
                if compare:
                    found[j][v]["per_row"] = np.asarray(stats[:n])
                del rows, ki, a, stats
        del w
        said(f"layer {i}")
    out_norm, head = raw("model.norm.weight"), matrix("lm_head.weight")

    def verdict(sample, x):
        emitted = sample["emitted"]
        em = np.zeros((max_new,), np.int32)
        em[: len(emitted)] = emitted
        top, chosen = jax.device_get(score(
            jnp.asarray(x), out_norm, head, jnp.asarray(len(sample["prompt"]), jnp.int32),
            jnp.asarray(em)))
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
        v: {"max_deficit_bf16_ulps": max(r["max_deficit_bf16_ulps"] for r in got),
            "least_deficit_bf16_ulps": min(r["max_deficit_bf16_ulps"] for r in got),
            "tokens_off_top": sum(r["tokens_off_top"] for r in got),
            "emitted": sum(r["emitted"] for r in got),
            "prompt_tokens": [r["prompt_tokens"] for r in got]}
        for v, got in verdicts.items() if v != "as_served" and got
    }
    seconds = time.perf_counter() - t0
    said("tokens scored")
    compared = [
        audit.compare(got, found[j]["as_served"],
                      {v: found[j][v] for v in VARIANTS[1:] if found[j][v]}, topk)
        for j, got in enumerate(served["streams"])
    ]
    cache = {"rows": compared,
             **{k: v for k, v in served.items() if k != "streams"}}
    print(json.dumps({"device": device, "samples": verdicts["as_served"],
                      "what_if": what_if, "cache": cache, "seconds": seconds}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
