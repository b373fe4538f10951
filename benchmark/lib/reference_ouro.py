"""The plain reference's verdict on a sample of requests served by an
``ouro`` checkpoint: the benchmark's own copy.

Run as a child of its own after the dataflow has exited and the chip is
free: ``python benchmark/lib/reference_ouro.py <in.json>``. For each
sampled request it computes the model's forward pass teacher-forced over
prompt + emitted tokens and reports, for every emitted token, how many
bf16 steps it lies below the top of the reference's own logits at its
position (sampled tokens are not compared: two correct programs part
within a few tokens at bf16 with random weights).

Before that, while the chip's memory is still free, it has
``cache_audit_ouro.serve`` (same process: one claim on the chip) serve
the sampled prompts once more through the program's engine, together
and beside fillers, and keeps what that engine emitted and the K rows it
held at pass 0's first two layers and at the last layer of every pass.
Those sequences are then teacher-forced here too, three times over
(``ROWS_OF``), and the rows compared (``cache_audit_ouro.compare``).

The mathematics is written here, in float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``, and shares no code with the
program: sandwich norms, rotate-half rotary, whole softmax a block of
query rows at a time, the loop over the passes with the final norm
after each, the exit gate on the normed state. No cache, no paging, no
batching: a pass attends to the keys and values it has just computed
for the whole sequence. Every matrix is held to the program's int8
weights alone (symmetric, per output channel, ``max|w| / 127``; kept on
the device as int8 with its scales and multiplied out one layer at a
time, never the whole model in float32), so the comparison measures the
program's bf16 activations, its cache and its arithmetic, not the
quantization; embedding, norms and gate are the checkpoint's bf16.

``what_if``: the same verdict against four other references —
``three_passes`` (the state after the last pass but one, through the
head), ``shared_rows`` (every pass attends to pass 0's keys and
values), ``no_post_norms`` (the two norms after the sublayers left
out), ``no_pass_norm`` (the final norm once, before the head) — i.e.
what the comparison of tokens would read for a program that did that:
each has to fail it.

The rows are held to precision, which the tokens cannot see. An audited
sequence is computed (a) in float32 throughout, (b) ``as_stated``: at
the precision the configuration's ``assumed.quantization`` states, i.e.
every product takes operands rounded to the device's compute dtype (the
stream as a sublayer reads it, the normed rows, q, k and v after the
rotary, the softmax's weights before the mix, the mixed rows, the MLP's
hidden rows) and adds up in float32, while the residual stream, the
norms and the softmax stay float32; (c) ``bf16_residual``: (b) with the
residual stream rounded to bfloat16 after every add and at the end of a
pass, the nearest precision below the stated one. The program's rows
are compared with (b), and (c)'s rows, put in the program's place, have
to fail that comparison (``cache_audit_ouro.compare``, where the
readings are described; all three are computed in every run). Every
sample is padded to one length. The last stdout line is the result.
"""

from __future__ import annotations

import json
import math
import sys
import time
from pathlib import Path

VARIANTS = ("as_published", "shared_rows", "no_post_norms", "no_pass_norm")
#: what an audited sequence is computed as: float32, the stated
#: precision, and the control one precision below it
ROWS_OF = ("as_published", "as_stated", "bf16_residual")
BF16_MANTISSA = 7
AUDIT_DECODE = 32  # tokens an audited stream decodes: 4 windows of K = 8


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from safetensors import safe_open

    from dora_tpu import backend

    spec = json.load(open(sys.argv[1]))
    backend.init_compile_cache()
    device = backend.require_accelerator("benchmark reference")
    ckpt = Path(spec["checkpoint"])
    hf = json.loads((ckpt / "config.json").read_text())
    pad, max_new, q_block = spec["pad_to"], spec["max_new"], spec["q_block"]
    f32 = jnp.float32
    eps = hf["rms_norm_eps"]
    heads, kvh, hd = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    layers, passes = hf["num_hidden_layers"], hf["total_ut_steps"]
    threshold = float(hf["early_exit_threshold"])
    # the mantissa the program's products see: 7 bits on the chip; on the
    # CPU the program computes in float32 and ``as_stated`` rounds nothing
    stated_bits = int(jnp.finfo(backend.compute_dtype()).nmant)

    served = None
    if spec.get("audit") is not None:
        import cache_audit_ouro  # beside this file

        served = cache_audit_ouro.serve(
            spec["checkpoint"], spec["audit"], [s["prompt"] for s in spec["samples"]],
            min(AUDIT_DECODE, max_new))
        kept_entries = [tuple(e) for e in served["entries"]]
    else:
        kept_entries = []

    t0 = time.perf_counter()
    inv = 1.0 / hf["rope_theta"] ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    angles = np.outer(np.arange(pad), inv)
    cos, sin = jnp.asarray(np.cos(angles), f32)[:, None], jnp.asarray(np.sin(angles), f32)[:, None]

    where = json.loads((ckpt / "model.safetensors.index.json").read_text())["weight_map"]
    files = {f: safe_open(str(ckpt / f), framework="np") for f in set(where.values())}

    def raw(name):
        return jnp.asarray(files[where[name]].get_tensor(name)).astype(f32)

    @jax.jit
    def as_served(w):
        """HF [out, in] -> ([in, out] int8, its scales): the program's
        weights, kept in 8 bits until a layer needs them."""
        w = w.astype(f32).T
        s = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0, 1e-12)
        return jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8), s

    def matrix(name):
        return as_served(jnp.asarray(files[where[name]].get_tensor(name)))

    def full(m):
        return m[0].astype(f32) * m[1]

    def norm(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def rotate(x):
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def rounder(bits):
        """float32 -> the nearest value with ``bits`` of mantissa, still
        float32 (a cast there and back is optimised away)."""
        if bits >= 23:
            return lambda x: x
        return lambda x: jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=bits)

    def attention(w, u, shared, r):
        q = r(rotate((u @ full(w["q"])).reshape(pad, heads, hd)))
        k = r(rotate((u @ full(w["k"])).reshape(pad, kvh, hd)))
        v = r((u @ full(w["v"])).reshape(pad, kvh, hd))
        mine = (k, v)
        if shared is not None:
            k, v = shared
        kr, vr = (jnp.repeat(t, heads // kvh, axis=1) for t in (k, v))

        def block(start):
            qa = jax.lax.dynamic_slice_in_dim(q, start, q_block)
            s = jnp.einsum("qhd,khd->hqk", qa, kr) / math.sqrt(hd)
            seen = (start + jnp.arange(q_block))[:, None] >= jnp.arange(pad)[None]
            s = jnp.where(seen, s, -jnp.inf)
            p = jnp.exp(s - s.max(-1, keepdims=True))  # the softmax, its division last
            mixed = jnp.einsum("hqk,khd->qhd", r(p), vr)
            return mixed / p.sum(-1).T[:, :, None]

        out = jax.lax.map(block, jnp.arange(0, pad, q_block))
        return r(out.reshape(pad, heads * hd)) @ full(w["o"]), mine

    def layer(post: bool, product_bits: int = 23, stream_bits: int = 23):
        """One layer; a product's operands hold ``product_bits`` of
        mantissa, the residual stream ``stream_bits`` after every add."""
        r, kept = rounder(product_bits), rounder(stream_bits)

        @jax.jit
        def run(w, x, shared):
            with jax.default_matmul_precision("highest"):
                a, mine = attention(w, r(norm(r(x), w["n1"])), shared, r)
                x = kept(x + (norm(a, w["n2"]) if post else a))
                u = r(norm(r(x), w["n3"]))
                m = r(jax.nn.silu(u @ full(w["gate"])) * (u @ full(w["up"]))) @ full(w["down"])
                return kept(x + (norm(m, w["n4"]) if post else m)), mine
        return run

    plain = layer(True)
    layer_of = {"as_published": plain, "shared_rows": plain,
                "no_post_norms": layer(False), "no_pass_norm": plain,
                "as_stated": layer(True, stated_bits),
                "bf16_residual": layer(True, stated_bits, BF16_MANTISSA)}
    to_bf16 = jax.jit(rounder(BF16_MANTISSA))

    @jax.jit
    def end_of_pass(x, out_norm, gate_w, gate_b):
        h = norm(x, out_norm)
        with jax.default_matmul_precision("highest"):
            return h, jax.nn.sigmoid(h @ gate_w + gate_b)

    @jax.jit
    def score(h, head, start, emitted):
        with jax.default_matmul_precision("highest"):
            rows = h[start - 1 + jnp.arange(max_new)] @ full(head)
        chosen = jnp.take_along_axis(rows, emitted[:, None], axis=1)[:, 0]
        return rows.max(-1), chosen

    def layer_weights(i):
        p = f"model.layers.{i}."
        a, ff = p + "self_attn.", p + "mlp."
        return {
            "n1": raw(p + "input_layernorm.weight"),
            "n2": raw(p + "input_layernorm_2.weight"),
            "n3": raw(p + "post_attention_layernorm.weight"),
            "n4": raw(p + "post_attention_layernorm_2.weight"),
            "q": matrix(a + "q_proj.weight"), "k": matrix(a + "k_proj.weight"),
            "v": matrix(a + "v_proj.weight"), "o": matrix(a + "o_proj.weight"),
            "gate": matrix(ff + "gate_proj.weight"), "up": matrix(ff + "up_proj.weight"),
            "down": matrix(ff + "down_proj.weight"),
        }

    weights = [layer_weights(i) for i in range(layers)]  # int8: 1 byte a parameter
    embed = raw("model.embed_tokens.weight")
    out_norm, head = raw("model.norm.weight"), matrix("lm_head.weight")
    gate_w = raw("model.early_exit_gate.weight").reshape(-1)
    gate_b = raw("model.early_exit_gate.bias").reshape(())

    # (sample, the variants it runs, whether its K rows are kept)
    runs = [(s, VARIANTS, False) for s in spec["samples"]]
    if served is not None:
        for s, emitted in zip(spec["samples"], served["emitted"]):
            runs.append(({**s, "emitted": emitted}, ROWS_OF, True))
    states = []  # one a run: {variant: h}
    for sample, variants, _ in runs:
        seq = sample["prompt"] + sample["emitted"]
        if len(seq) > pad or len(sample["emitted"]) > max_new or pad % q_block:
            raise ValueError(f"sample of {len(seq)} tokens, pad_to {pad}, q_block {q_block}")
        ids = np.zeros((pad,), np.int32)
        ids[: len(seq)] = seq
        first = embed[jnp.asarray(ids)]
        states.append({v: first for v in variants})
    del embed, first
    pass0 = [{} for _ in runs]  # shared_rows: layer -> pass 0's (k, v)
    rows = [{v: {} for v in ROWS_OF} for _ in runs]  # audited: (pass, layer) -> K [pad, KV, hd]
    before_last = [None] * len(runs)  # as_published: h after the last pass but one
    survive = [jnp.ones((pad,), f32) for _ in runs]
    cdfs = [jnp.zeros((pad,), f32) for _ in runs]
    would_leave = 0  # positions whose running exit sum reached the threshold early
    for step in range(passes):
        for i, w in enumerate(weights):
            for j, (_, variants, keep) in enumerate(runs):
                for v in variants:
                    shared = pass0[j].get(i) if v == "shared_rows" and step else None
                    states[j][v], mine = layer_of[v](w, states[j][v], shared)
                    if v == "shared_rows" and step == 0:
                        pass0[j][i] = mine
                    if keep and (step, i) in kept_entries:
                        rows[j][v][step, i] = np.asarray(mine[0])
        for j, (sample, variants, _) in enumerate(runs):
            n = len(sample["prompt"]) + len(sample["emitted"])
            for v in variants:
                h, lam = end_of_pass(states[j][v], out_norm, gate_w, gate_b)
                if v != "no_pass_norm" or step == passes - 1:
                    states[j][v] = to_bf16(h) if v == "bf16_residual" else h
                if v == "as_published":
                    if step == passes - 2:
                        before_last[j] = h
                    cdfs[j] = cdfs[j] + lam * survive[j]
                    survive[j] = survive[j] * (1.0 - lam)
                    if step < passes - 1:
                        would_leave += int(np.asarray(cdfs[j][:n] >= threshold).sum())
    del pass0

    def verdict(sample, h):
        emitted = sample["emitted"]
        em = np.zeros((max_new,), np.int32)
        em[: len(emitted)] = emitted
        top, chosen = jax.device_get(score(
            h, head, jnp.asarray(len(sample["prompt"]), jnp.int32), jnp.asarray(em)))
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

    n_served = len(spec["samples"])
    verdicts = {v: [verdict(sample, x[v]) for (sample, _, _), x in
                    zip(runs[:n_served], states)] for v in VARIANTS}
    verdicts["three_passes"] = [
        verdict(sample, h) for (sample, _, _), h in zip(runs[:n_served], before_last)
        if h is not None]
    audited = [verdict(sample, x["as_published"]) for (sample, _, _), x in
               zip(runs[n_served:], states[n_served:])]
    seconds = time.perf_counter() - t0
    what_if = {
        v: {"max_deficit_bf16_ulps": max(r["max_deficit_bf16_ulps"] for r in found),
            "least_deficit_bf16_ulps": min(r["max_deficit_bf16_ulps"] for r in found),
            "tokens_off_top": sum(r["tokens_off_top"] for r in found),
            "emitted": sum(r["emitted"] for r in found)}
        for v, found in verdicts.items() if v != "as_published" and found
    }
    cache = None
    if served is not None:
        cache = {
            "rows": [cache_audit_ouro.compare(
                got, {v: [rows[n_served + j][v][e] for e in kept_entries] for v in ROWS_OF},
                kept_entries, len(spec["samples"][j]["prompt"]))
                for j, got in enumerate(served["rows"])],
            "entries": served["entries"], "samples": audited,
            **{k: v for k, v in served.items() if k not in ("rows", "emitted", "entries")},
        }
    print(json.dumps({"device": device, "samples": verdicts["as_published"],
                      "what_if": what_if, "cache": cache,
                      "would_leave_before_last": would_leave,
                      "seconds": seconds}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
