"""The plain reference's verdict on a sample of requests served by a
``falcon_h1`` checkpoint: the benchmark's own copy.

Run as a child of its own after the dataflow has exited and the chip is
free: ``python benchmark/lib/reference_falcon_h1.py <in.json>``. For each
sampled request it computes the model's forward pass teacher-forced over
prompt + emitted tokens and reports, for every emitted token, how many
bf16 steps it lies below the top of the reference's own logits at its
position (sampled tokens are not compared: two correct programs part
within a few tokens at bf16 with random weights).

Before that, while the chip's memory is still free, it has
``state_audit_falcon_h1.serve`` (same process: one claim on the chip)
serve the sampled prompts once more through the program's engine, each
for ``AUDIT_DECODE`` tokens beside other live streams, and keeps what
that engine emitted and every layer's SSM state as each stream's slot
held it at the end. Those sequences are then teacher-forced here too,
and every layer's state after the last token a decode tick consumed is
compared with the program's: in float32 and, as the control, from the
same recurrence with the state rounded to bfloat16 after every token
in every layer (``bf16_state``).

The mathematics is written here, in float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``, and shares no code with the
program: every multiplier of the published config where the forward
pass applies it, rotate-half rotary, whole softmax a block of query rows
at a time, the depthwise causal convolution over the whole sequence, and
the state-space recurrence TOKEN BY TOKEN (``lax.scan``; the program's
prefill uses the chunked form). No cache, no paging, no batching. Every
matrix is held to the program's int8 weights alone (symmetric, per
output channel, ``max|w| / 127``), so the comparison measures the
program's bf16 activations, its caches and its arithmetic, not the
quantization; embedding, norms, convolution, ``A_log``, ``dt_bias`` and
``D`` are the checkpoint's bf16.

``what_if``: the same verdict against four other references (the
convolution without its tail: every token convolved alone; ``D`` = 0;
the mixer branch left out; the state kept in bfloat16), i.e. what the
comparison of tokens would read for a program that did that: the first
three fail it, the last does not (the gated norm hides the state's
precision from the tokens), which is why the state is audited. One layer's weights at a time, every
sample padded to one length. The last stdout line is the result.
"""

from __future__ import annotations

import json
import math
import sys
import time
from pathlib import Path

VARIANTS = ("as_published", "no_conv_tail", "no_D", "no_mixer", "bf16_state")
AUDITED = ("as_published", "bf16_state")  # what an audited sequence runs
AUDIT_DECODE = 64  # tokens an audited stream decodes: 8 windows of K = 8


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
    d, eps = hf["hidden_size"], hf["rms_norm_eps"]
    heads, kvh, hd = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    mh, mp = hf["mamba_n_heads"], hf["mamba_d_head"]
    g, n, taps, d_ssm = (hf["mamba_n_groups"], hf["mamba_d_state"], hf["mamba_d_conv"],
                         hf["mamba_d_ssm"])
    gn = g * n
    m = hf["ssm_multipliers"]
    mup = jnp.concatenate([
        jnp.full((d_ssm,), m[0], f32), jnp.full((d_ssm,), m[1], f32),
        jnp.full((gn,), m[2], f32), jnp.full((gn,), m[3], f32), jnp.full((mh,), m[4], f32),
    ])
    inv = 1.0 / hf["rope_theta"] ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    angles = np.outer(np.arange(pad), inv)
    cos, sin = jnp.asarray(np.cos(angles), f32)[:, None], jnp.asarray(np.sin(angles), f32)[:, None]

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

    def rotate(x):
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def attention(w, a):
        q = rotate((a @ w["q"]).reshape(pad, heads, hd))
        k = rotate((a @ w["k"]).reshape(pad, kvh, hd) * hf["key_multiplier"])
        v = (a @ w["v"]).reshape(pad, kvh, hd)
        k, v = (jnp.repeat(t, heads // kvh, axis=1) for t in (k, v))

        def block(start):
            qa = jax.lax.dynamic_slice_in_dim(q, start, q_block)
            s = jnp.einsum("qhd,khd->hqk", qa, k) / math.sqrt(hd)
            seen = (start + jnp.arange(q_block))[:, None] >= jnp.arange(pad)[None]
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khd->qhd", p, v)

        out = jax.lax.map(block, jnp.arange(0, pad, q_block))
        return out.reshape(pad, heads * hd) @ w["o"]

    def mixer(w, u, last, variant):
        """-> (out [pad, d], the state after token ``last`` [H, P, N])."""
        proj = ((u * hf["ssm_in_multiplier"]) @ w["in"]) * mup
        z, xbc, dt = proj[:, :d_ssm], proj[:, d_ssm : 2 * d_ssm + 2 * gn], proj[:, -mh:]
        kernel = w["conv_w"][:, 0, :]  # [conv_dim, taps]
        if variant == "no_conv_tail":
            conv = xbc * kernel[:, -1]
        else:
            padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), f32), xbc], 0)
            conv = sum(padded[k : k + pad] * kernel[:, k] for k in range(taps))
        conv = jax.nn.silu(conv + w["conv_b"])
        x = conv[:, :d_ssm].reshape(pad, mh, mp)
        bm = jnp.repeat(conv[:, d_ssm : d_ssm + gn].reshape(pad, g, n), mh // g, axis=1)
        cm = jnp.repeat(conv[:, d_ssm + gn :].reshape(pad, g, n), mh // g, axis=1)
        dt = jax.nn.softplus(dt + w["dt_bias"])
        a = -jnp.exp(w["A_log"])
        skip = w["D"] * (0.0 if variant == "no_D" else 1.0)

        def token(carry, inp):
            s, kept = carry
            t, x_t, b_t, c_t, dt_t = inp  # [H, P], [H, N], [H, N], [H]
            s = jnp.exp(dt_t * a)[:, None, None] * s + (
                dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
            if variant == "bf16_state":  # a cast there and back is optimised away
                s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
            y = jnp.einsum("hpn,hn->hp", s, c_t) + skip[:, None] * x_t
            return (s, jnp.where(t == last, s, kept)), y

        zero = jnp.zeros((mh, mp, n), f32)
        (_, kept), y = jax.lax.scan(
            token, (zero, zero), (jnp.arange(pad), x, bm, cm, dt))
        y = y.reshape(pad, d_ssm) * jax.nn.silu(z)
        y = y.reshape(pad, g, d_ssm // g)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
        return (y.reshape(pad, d_ssm) * w["ssm_norm"]) @ w["out"], kept

    def layer(variant):
        @jax.jit
        def run(w, x, last):
            with jax.default_matmul_precision("highest"):
                u = norm(x, w["attn_norm"])
                mix, kept = mixer(w, u, last, variant)
                x = x + hf["attention_out_multiplier"] * attention(
                    w, u * hf["attention_in_multiplier"])
                if variant != "no_mixer":
                    x = x + hf["ssm_out_multiplier"] * mix
                v = norm(x, w["ffn_norm"])
                m0, m1 = hf["mlp_multipliers"]
                gate = jax.nn.silu((v @ w["gate"]) * m0)
                return x + ((gate * (v @ w["up"])) @ w["down"]) * m1, kept
        return run

    layers = {v: layer(v) for v in VARIANTS}

    @jax.jit
    def score(x, out_norm, head, start, emitted):
        with jax.default_matmul_precision("highest"):
            rows = (norm(x[start - 1 + jnp.arange(max_new)], out_norm) @ head
                    ) * hf["lm_head_multiplier"]
        chosen = jnp.take_along_axis(rows, emitted[:, None], axis=1)[:, 0]
        return rows.max(-1), chosen

    def layer_weights(i):
        p = f"model.layers.{i}."
        a, mm, ff = p + "self_attn.", p + "mamba.", p + "feed_forward."
        return {
            "attn_norm": raw(p + "input_layernorm.weight"),
            "ffn_norm": raw(p + "pre_ff_layernorm.weight"),
            "q": matrix(a + "q_proj.weight"), "k": matrix(a + "k_proj.weight"),
            "v": matrix(a + "v_proj.weight"), "o": matrix(a + "o_proj.weight"),
            "in": matrix(mm + "in_proj.weight"), "out": matrix(mm + "out_proj.weight"),
            "conv_w": raw(mm + "conv1d.weight"), "conv_b": raw(mm + "conv1d.bias"),
            "dt_bias": raw(mm + "dt_bias"), "A_log": raw(mm + "A_log"), "D": raw(mm + "D"),
            "ssm_norm": raw(mm + "norm.weight"),
            "gate": matrix(ff + "gate_proj.weight"), "up": matrix(ff + "up_proj.weight"),
            "down": matrix(ff + "down_proj.weight"),
        }

    served = None
    if spec.get("audit") is not None:
        import state_audit_falcon_h1  # beside this file

        served = state_audit_falcon_h1.serve(
            spec["checkpoint"], spec["audit"], [s["prompt"] for s in spec["samples"]],
            min(AUDIT_DECODE, max_new))

    t0 = time.perf_counter()
    # (sample, the variants it runs, the token whose state is kept)
    runs = [(sample, VARIANTS, 0) for sample in spec["samples"]]
    if served is not None:
        for sample, emitted in zip(spec["samples"], served["emitted"]):
            # the slot's state stands after every token but the last emitted
            runs.append(({**sample, "emitted": emitted}, AUDITED,
                         len(sample["prompt"]) + len(emitted) - 2))
    embed = raw("model.embed_tokens.weight")
    xs = []
    for sample, variants, _ in runs:
        seq = sample["prompt"] + sample["emitted"]
        if len(seq) > pad or len(sample["emitted"]) > max_new or pad % q_block:
            raise ValueError(f"sample of {len(seq)} tokens, pad_to {pad}, q_block {q_block}")
        ids = np.zeros((pad,), np.int32)
        ids[: len(seq)] = seq
        first = embed[jnp.asarray(ids)] * hf["embedding_multiplier"]
        xs.append({v: first for v in variants})
    del embed, first
    n_served = len(spec["samples"])
    errs = [[] for _ in runs[n_served:]]  # [audited sequence][layer]
    errs_bf16 = [[] for _ in runs[n_served:]]
    for i in range(hf["num_hidden_layers"]):
        w = layer_weights(i)
        for j, (_, variants, last) in enumerate(runs):
            kept = {}
            for v in variants:
                xs[j][v], kept[v] = layers[v](w, xs[j][v], jnp.asarray(last, jnp.int32))
            if j >= n_served:
                want = np.asarray(kept["as_published"])
                errs[j - n_served].append(state_audit_falcon_h1.rel_err(
                    served["states"][j - n_served][i], want))
                errs_bf16[j - n_served].append(state_audit_falcon_h1.rel_err(
                    np.asarray(kept["bf16_state"]), want))
        del w, kept
    out_norm, head = raw("model.final_layernorm.weight"), matrix("lm_head.weight")

    def verdict(sample, x):
        emitted = sample["emitted"]
        em = np.zeros((max_new,), np.int32)
        em[: len(emitted)] = emitted
        top, chosen = jax.device_get(score(
            x, out_norm, head, jnp.asarray(len(sample["prompt"]), jnp.int32),
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

    verdicts = {v: [verdict(sample, x[v]) for (sample, _, _), x in
                    zip(runs[:n_served], xs)] for v in VARIANTS}
    audited = [verdict(sample, x["as_published"]) for (sample, _, _), x in
               zip(runs[n_served:], xs[n_served:])]
    seconds = time.perf_counter() - t0
    what_if = {
        v: {"max_deficit_bf16_ulps": max(r["max_deficit_bf16_ulps"] for r in rows),
            "tokens_off_top": sum(r["tokens_off_top"] for r in rows),
            "emitted": sum(r["emitted"] for r in rows)}
        for v, rows in verdicts.items() if v != "as_published"
    }
    state = None
    if served is not None:
        state = {
            "rel_err": errs, "rel_err_bf16_state": errs_bf16,
            "layers": hf["num_hidden_layers"], "samples": audited,
            **{k: v for k, v in served.items() if k not in ("states", "emitted")},
        }
    print(json.dumps({"device": device, "samples": verdicts["as_published"],
                      "what_if": what_if, "state": state, "seconds": seconds}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
