"""The plain reference's verdict on a sample of requests served by a
``kimi_k2`` (DeepSeek-V3 block) checkpoint: the benchmark's own copy.

Run as a child of its own after the dataflow has exited and the chip is
free: ``python benchmark/lib/reference_kimi_k2.py <in.json>``. For each
sampled request it computes the model's forward pass teacher-forced over
prompt + emitted tokens and reports, for every emitted token, how many
bf16 steps it lies below the top of the reference's own logits at its
position. Sampled tokens are not compared (two correct programs part
within a few tokens at bf16 with random weights). It also keeps every
layer's cache rows ``(c_kv, k_pe)`` of each prompt, as it computes them
in float32, and hands them to ``cache_audit_kimi_k2.audit`` (same
process, after this file's arrays are dropped: one claim on the chip),
which compares them with the rows the program's engine wrote.

The mathematics is written here, in float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``, and shares no code with the
program: EXPANDED multi-head latent attention (``kv_b_proj`` applied to
every position's latent, 64 heads of keys and values, one roped key
shared by the heads), whole softmax, the sigmoid router over all experts
with the bias in the choice only, a Python loop over the experts this
rank holds (each applied to every row, weighted by its normalised
unbiased score where it was chosen and by 0 elsewhere), the shared
expert, YaRN rotary in HF's pair order. No cache, no paging, no batching.
What the absent experts would add is left out, as in the program. Every
matrix is held to the program's int8 weights alone (symmetric, per
output channel, ``max|w| / 127``), so the comparison measures the
program's bf16 activations, cache and arithmetic, not the quantization;
embedding, router and norms are the checkpoint's bf16.

To fit 8,400-token samples beside float32 weights: one layer's weights
at a time (read from the checkpoint, used for every sample, dropped),
every sample padded to one length (one compiled program a layer kind),
scores a block of queries at a time. The last stdout line is the result.
"""

from __future__ import annotations

import json
import math
import sys
import time
from pathlib import Path


def yarn_inv_freq(dim, base, rs):
    import numpy as np

    factor, original = rs["factor"], rs["original_max_position_embeddings"]

    def correction(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction(rs["beta_fast"])), 0)
    high = min(math.ceil(correction(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    plain = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


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
    d, heads = hf["hidden_size"], hf["num_attention_heads"]
    nope, rope, vdim = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"]
    kv_rank, eps, top_k = hf["kv_lora_rank"], hf["rms_norm_eps"], hf["num_experts_per_tok"]
    held = hf["n_routed_experts"] // hf["ep_size"]
    first = spec.get("ep_rank", 0) * held
    rs = hf["rope_scaling"]
    scale = (nope + rope) ** -0.5
    if rs.get("mscale_all_dim"):
        scale *= mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    ratio = mscale(rs["factor"], rs.get("mscale", 1)) / mscale(rs["factor"], rs.get("mscale_all_dim", 0))
    angles = np.outer(np.arange(pad), yarn_inv_freq(rope, hf["rope_theta"], rs))
    cos, sin = jnp.asarray(np.cos(angles) * ratio, f32), jnp.asarray(np.sin(angles) * ratio, f32)

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
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)

    def swiglu(w, x):
        return (jax.nn.silu(x @ w["gate"]) * (x @ w["up"])) @ w["down"]

    def attention(w, x):
        t = x.shape[0]
        q = (norm(x @ w["q_a"], w["q_norm"]) @ w["q_b"]).reshape(t, heads, nope + rope)
        kv_a = x @ w["kv_a"]
        c_kv = norm(kv_a[:, :kv_rank], w["kv_norm"])
        k_pe = rotate(kv_a[:, kv_rank:], cos, sin)
        kv = (c_kv @ w["kv_b"]).reshape(t, heads, nope + vdim)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe[:, None], (t, heads, rope))], -1)
        q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], cos[:, None], sin[:, None])], -1)
        v = kv[..., nope:]

        def block(a):
            qa = jax.lax.dynamic_slice_in_dim(q, a, q_block)
            s = jnp.einsum("qhd,khd->hqk", qa, k) * scale
            seen = (a + jnp.arange(q_block))[:, None] >= jnp.arange(t)[None]
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khd->qhd", p, v)

        out = jax.lax.map(block, jnp.arange(0, t, q_block))
        return out.reshape(t, heads * vdim) @ w["o"], jnp.concatenate([c_kv, k_pe], -1)

    def moe(w, x):
        scores = jax.nn.sigmoid(x @ w["router"])
        _, ids = jax.lax.top_k(scores + w["bias"], top_k)
        chosen = jnp.take_along_axis(scores, ids, -1)
        if hf.get("norm_topk_prob", True):
            chosen = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
        chosen = chosen * hf["routed_scaling_factor"]
        y = swiglu(w["shared"], x)
        for j, expert in enumerate(w["experts"]):  # the experts held here
            w_e = (chosen * (ids == first + j)).sum(-1)
            y = y + swiglu(expert, x) * w_e[:, None]
        return y

    @jax.jit
    def layer(w, x):
        """-> (the layer's output, its cache rows [pad, kv_rank + rope])."""
        with jax.default_matmul_precision("highest"):
            a, cached = attention(w, norm(x, w["attn_norm"]))
            x = x + a
            h = norm(x, w["ffn_norm"])
            return x + (swiglu(w["dense"], h) if "dense" in w else moe(w, h)), cached

    @jax.jit
    def score(x, out_norm, head, start, emitted):
        with jax.default_matmul_precision("highest"):
            rows = norm(x[start - 1 + jnp.arange(max_new)], out_norm) @ head
        chosen = jnp.take_along_axis(rows, emitted[:, None], axis=1)[:, 0]
        return rows.max(-1), chosen

    def ffn(prefix):
        return {k: matrix(f"{prefix}{k}_proj.weight") for k in ("gate", "up", "down")}

    def layer_weights(i):
        p = f"model.layers.{i}."
        a, m = p + "self_attn.", p + "mlp."
        w = {
            "attn_norm": raw(p + "input_layernorm.weight"),
            "ffn_norm": raw(p + "post_attention_layernorm.weight"),
            "q_a": matrix(a + "q_a_proj.weight"), "q_norm": raw(a + "q_a_layernorm.weight"),
            "q_b": matrix(a + "q_b_proj.weight"),
            "kv_a": matrix(a + "kv_a_proj_with_mqa.weight"),
            "kv_norm": raw(a + "kv_a_layernorm.weight"),
            "kv_b": matrix(a + "kv_b_proj.weight"), "o": matrix(a + "o_proj.weight"),
        }
        if i < hf["first_k_dense_replace"]:
            w["dense"] = ffn(m)
        else:
            w["router"] = raw(m + "gate.weight").T
            w["bias"] = raw(m + "gate.e_score_correction_bias")
            w["shared"] = ffn(m + "shared_experts.")
            w["experts"] = [ffn(f"{m}experts.{e}.") for e in range(first, first + held)]
        return w

    t0 = time.perf_counter()
    embed = raw("model.embed_tokens.weight")
    xs = []
    for sample in spec["samples"]:
        seq = sample["prompt"] + sample["emitted"]
        if len(seq) > pad or len(sample["emitted"]) > max_new or pad % q_block:
            raise ValueError(f"sample of {len(seq)} tokens, pad_to {pad}, q_block {q_block}")
        ids = np.zeros((pad,), np.int32)
        ids[: len(seq)] = seq
        xs.append(embed[jnp.asarray(ids)])
    del embed
    latents = [[] for _ in xs]  # per sample, per layer: the prompt's rows
    for i in range(hf["num_hidden_layers"]):
        w = layer_weights(i)
        for j, sample in enumerate(spec["samples"]):
            xs[j], cached = layer(w, xs[j])
            latents[j].append(np.asarray(cached[: len(sample["prompt"])]))
        del w, cached
    out_norm, head = raw("model.norm.weight"), matrix("lm_head.weight")
    rows = []
    for sample, x in zip(spec["samples"], xs):
        emitted = sample["emitted"]
        em = np.zeros((max_new,), np.int32)
        em[: len(emitted)] = emitted
        top, chosen = jax.device_get(score(
            x, out_norm, head, jnp.asarray(len(sample["prompt"]), jnp.int32), jnp.asarray(em)
        ))
        deficits = []
        for k in range(len(emitted)):
            t = float(top[k])
            ulp = 2.0 ** (math.floor(math.log2(abs(t))) - 7) if t else 1.0
            deficits.append((t - float(chosen[k])) / ulp)
        rows.append({
            "i": sample["i"], "prompt_tokens": len(sample["prompt"]),
            "emitted": len(emitted), "max_deficit_bf16_ulps": max(deficits),
            "tokens_off_top": sum(gap > 0 for gap in deficits),
            "worst_position": int(np.argmax(deficits)),
        })
    seconds = time.perf_counter() - t0
    del xs, x, out_norm, head, files
    cache = None
    if spec.get("audit"):
        import cache_audit_kimi_k2  # beside this file

        cache = cache_audit_kimi_k2.audit(
            spec["checkpoint"], spec["audit"],
            [s["prompt"] for s in spec["samples"]], latents, hf["kv_lora_rank"] + rope)
    print(json.dumps({"device": device, "samples": rows, "cache": cache,
                      "seconds": seconds}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
