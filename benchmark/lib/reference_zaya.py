"""The plain reference's verdict on a sample of requests served by a
``zaya`` (ZAYA1) checkpoint: the benchmark's own copy.

Run as a child of its own after the dataflow has exited and the chip is
free: ``python benchmark/lib/reference_zaya.py <in.json>``. First
``cache_audit_zaya.serve`` (same process: one claim on the chip) puts each
sampled request's prompt + emitted tokens through the program's engine
once more and decodes a few tokens beyond them, and what that engine
holds is kept on the host; the program's arrays are dropped. Then, for
each sample, the model's forward pass teacher-forced over prompt +
emitted tokens (+ the audit's own decode tokens) is computed here and
reports, for every token the TIMED run emitted, how many bf16 steps it
lies below the top of the reference's own logits at its position (sampled
tokens are not compared: two correct programs part within a few tokens at
bf16 with random weights).

**The reference routes itself.** At every layer a row goes to the expert
the reference's OWN router picks, ``argmax(p + bias)``, wherever its two
best biased probabilities lie at least ``cache_audit_zaya.PICK_MARGIN``
apart. Only inside that margin, where bf16's noise in the router's input
decides a near-tie either way and a row sent to the other expert leaves
the layer as another row (top-1 is discontinuous), does it follow the pick
of the PROGRAM (the audit's chunks over the same tokens, then its decode
ticks: ``engine.selection``), weighted by the reference's own probability
of it. So a wrong bias, argmax or carry at ANY layer, away from a near-tie,
parts the program from the reference there and in every row downstream,
and shows twice: in ``picks_differ_clear`` of that layer (every layer's is
kept and held to the limit) and in the tokens, pages and tails. Two
variants beside it say what the rule is worth: ``forced`` (every row of
every layer sent where the program sent it: what the first version of this
file did, printed for the longest sample and judged nowhere) and ``wrong_pick``
(a control: at the MIDDLE layer the reference sends every fourth row to
the expert after its own; the program must then fail that layer's limit).

The mathematics is written here, in float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``, and shares no code with the
program: RMSNorm; the query and key latents; the two causal convolutions
as sums over shifted copies of the whole sequence (the input padded once
with zeros); the q-k mean; the L2 norms with the key's ``exp(temp)``;
rotate-half rotary over the first half of each head; the value's second
head from the position before; causal attention a block of query rows at
a time; the residual scaling; the router (its state carried from layer to
layer beside the rows) as an MLP of two exact-GELU layers in float32; a
loop over the 16 experts, each on the rows sent to it. No cache, no
tails, no paging, no batching. Every matrix is held to the program's int8
weights alone (symmetric, per output channel, ``max|w| / 127``; the head
is the embedding's), so the comparison measures the program's bf16
activations, caches and arithmetic, not the quantization; embedding,
convolutions, routers, vectors and norms are the checkpoint's bf16.

Controls, computed in every run, each of which must FAIL a limit the
program passes (``chat_measure_zaya.verdict``): ``no_conv`` (``d_t =
c_t``) on the shortest sample, ``no_value_shift`` (both value heads from
the row's own position) on the second shortest, ``wrong_pick`` on the
third shortest (from the middle layer on), the program's own layer-0 rows
through 8 bits, and the pick of a router without carry
(``cache_audit_zaya.compare``).

To fit a 7k-token sample beside float32 weights: one layer's weights at a
time (read from the checkpoint, used for every sample, dropped), a
sample's rows on the host between layers, scores a block of queries at a
time, the head a block of rows at a time. Every sample is padded to the
smallest of ``pads`` that holds it; the cell gives ONE pad (8,192), so
that every run uses the same programs and none is compiled after a
checkout's first run. The last stdout line is the result.
"""

from __future__ import annotations

import gc
import json
import math
import sys
import time
from functools import partial
from pathlib import Path

VARIANTS = ("as_served", "forced", "no_conv", "no_value_shift", "wrong_pick")
PLANT_EVERY = 4  # ``wrong_pick`` moves every fourth row of the middle layer
EXPERT_ROWS = 512  # rows of one block of an expert's rows (divides every pad)
SCORE_ROWS = 256  # rows of one block of the head's logits


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from safetensors import safe_open

    import cache_audit_zaya as audit  # beside this file
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

    # -- the program first: its pages, its tails and its picks, to the host ---
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

    heads, kv_heads, hd = (hf["num_attention_heads"], hf["num_key_value_heads"],
                           hf["head_dim"])
    g, q_w, kv_w = heads // kv_heads, heads * hd, kv_heads * hd
    groups = heads + kv_heads
    eps, layers, experts = hf["rms_norm_eps"], hf["num_hidden_layers"], hf["num_experts"]
    r_hidden = hf["router_hidden_size"]
    rope = hf["rope_parameters"]["hybrid"]
    rot = int(hd * rope["partial_rotary_factor"])
    theta = float(rope["rope_theta"])
    last_layer, middle_layer = layers - 1, layers // 2
    pick_margin = audit.PICK_MARGIN

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

    def matrix(*names):
        """One matrix, or several quantized as the one the program fuses."""
        return as_served(jnp.concatenate([raw(n) for n in names], 0))

    def norm(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def rotate(x, cos, sin):
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def swiglu(w, x):
        return (jax.nn.silu(x @ w["gate"]) * (x @ w["up"])) @ w["down"]

    def later(x):
        """``out[t] = x[t - 1]``, zeros at 0."""
        return jnp.pad(x, ((1, 0), (0, 0)))[:-1]

    def attention(w, h, no_conv, no_value_shift):
        """Normed rows ``h [T, d]`` -> (the sublayer's output, the K|V rows
        as cached [T, 512], the pre-convolution rows c [T, 1280], Wv2 h
        [T, 128]). The two controls are traced flags: one program."""
        t = h.shape[0]
        p = h @ w["qkv"]
        c, v1, v2 = p[:, : q_w + kv_w], p[:, q_w + kv_w : q_w + kv_w + hd], p[:, -hd:]
        a = w["conv0_w"][0] * later(c) + w["conv0_w"][1] * c + w["conv0_b"]
        # a_{-1}: the padded input's first two rows are zeros
        before = jnp.concatenate([w["conv0_b"][None], a[:-1]], 0)
        d = (jnp.einsum("tgi,gio->tgo", before.reshape(t, groups, hd), w["conv1_w"][0])
             + jnp.einsum("tgi,gio->tgo", a.reshape(t, groups, hd), w["conv1_w"][1])
             ).reshape(t, -1) + w["conv1_b"]
        d = jnp.where(no_conv, c, d)

        def split(x):
            return x[:, :q_w].reshape(t, kv_heads, g, hd), x[:, q_w:].reshape(t, kv_heads, hd)

        (qt, kt), (dq, dk) = split(c), split(d)
        m = (qt + kt[:, :, None]) / 2

        def l2(x):
            return math.sqrt(hd) * x / jnp.linalg.norm(x, axis=-1, keepdims=True)

        q, k = l2(dq + m), l2(dk + m.mean(2)) * jnp.exp(w["tau"])[None, :, None]
        inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=f32) / rot)
        angles = jnp.arange(t, dtype=f32)[:, None] * inv[None]
        cos, sin = jnp.cos(angles), jnp.sin(angles)
        q = jnp.concatenate([rotate(q[..., :rot], cos[:, None, None], sin[:, None, None]),
                             q[..., rot:]], -1)
        k = jnp.concatenate([rotate(k[..., :rot], cos[:, None], sin[:, None]),
                             k[..., rot:]], -1)
        v = jnp.stack([v1, jnp.where(no_value_shift, v2, later(v2))], 1)
        pos = jnp.arange(t)

        def block(a):
            qa = jax.lax.dynamic_slice_in_dim(q, a, q_block)
            sc = jnp.einsum("qkgd,tkd->kgqt", qa, k) * hd ** -0.5
            seen = pos[None, :] <= (a + jnp.arange(q_block))[:, None]
            pr = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), axis=-1)
            return jnp.einsum("kgqt,tkd->qkgd", pr, v).reshape(q_block, q_w)

        ctx = jax.lax.map(block, jnp.arange(0, t, q_block))
        rows = jnp.concatenate([k.reshape(t, kv_w), v.reshape(t, kv_w)], -1)
        return ctx.reshape(t, q_w) @ w["o"], rows, c, v2

    def probabilities(w, h, s):
        """The router on normed rows: (p + bias [T, experts], p, the state
        for the next layer)."""
        s = h @ w["down"] + w["down_b"] + w["gamma"] * s
        u = norm(s, w["r_norm"])
        z = jax.nn.gelu(u @ w["w1"] + w["b1"], approximate=False)
        z = jax.nn.gelu(z @ w["w2"] + w["b2"], approximate=False)
        p = jax.nn.softmax(z @ w["w3"], axis=-1)
        return p + w["bias"], p, s

    def moe(w, x, weight, given):
        """Each row's ONE expert, ``given [T]``, times ``weight [T]``: an
        expert on the rows sent to it, ``EXPERT_ROWS`` of them at a time
        (rows past the last are weighted 0)."""
        def one(y, expert):
            number, weights = expert
            mine = given == number
            order = jnp.argsort(~mine)  # stable: the expert's rows first, in order
            n_e, size = mine.sum(), min(EXPERT_ROWS, x.shape[0])

            def rows_block(j, y):
                rows = jax.lax.dynamic_slice_in_dim(order, j * size, size)
                valid = j * size + jnp.arange(size) < n_e
                out = swiglu(weights, x[rows]) * (weight[rows] * valid)[:, None]
                return y.at[rows].add(out)

            return jax.lax.fori_loop(0, (n_e + size - 1) // size, rows_block, y), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(x), (jnp.arange(experts), w["experts"]))
        return y

    def scaled(res, x, y):
        return (x + res["rb"]) * res["rs"] + (y + res["hb"]) * res["hs"]

    @partial(jax.jit, donate_argnums=(1, 2))
    def one_layer(w, x, s, given, no_conv, no_value_shift, forced, plant):
        """One layer, the ONE program of this file that is large: the
        controls and the two routing variants are traced flags, and every
        layer's picks are compared (a second pass of the router's MLP over
        ``[T, 256]``), so nothing else is compiled for a control or for an
        audited layer. ``given [T]``: the program's picks. -> (rows,
        router state, (the K|V rows as cached, c, Wv2 h, per row: [the
        program's pick is not the reference's own, the gap between the
        reference's two best biased probabilities, how far its
        probability of the program's pick lies under its best, its own
        pick is not that of a router without carry]))."""
        with jax.default_matmul_precision("highest"):
            a, rows, c, v2 = attention(w, norm(x, w["attn_norm"]), no_conv, no_value_shift)
            x = scaled(w["attn_res"], x, a)
            h = norm(x, w["ffn_norm"])
            # what a router that starts every layer from zeros would pick
            lone = jnp.argmax(probabilities(w, h, jnp.zeros_like(s))[0], -1)
            biased, p, s = probabilities(w, h, s)
            best = jax.lax.top_k(biased, 2)[0]
            margin = best[:, 0] - best[:, 1]
            own = jnp.argmax(biased, -1)
            # the control: every PLANT_EVERY-th row to the expert after its own
            moved = plant & (jnp.arange(x.shape[0]) % PLANT_EVERY == 0)
            own = jnp.where(moved, (own + 1) % experts, own)
            # its own pick wherever it is clear of a tie, the program's inside
            # the margin (or everywhere: ``forced``)
            to = jnp.where((forced | (margin < pick_margin)) & ~moved, given, own)
            weight = jnp.take_along_axis(p, to[:, None], -1)[:, 0]
            x = scaled(w["ffn_res"], x, moe(w, h, weight, to))
            stats = jnp.stack([
                (own != given).astype(f32), margin,
                best[:, 0] - jnp.take_along_axis(biased, given[:, None], -1)[:, 0],
                (own != lone).astype(f32)], -1)
        return x, s, (rows, c, v2, stats)

    @jax.jit
    def score(x, out_norm, head, start, emitted):
        def block(a):
            with jax.default_matmul_precision("highest"):
                rows = norm(x[start - 1 + a + jnp.arange(SCORE_ROWS)], out_norm) @ head
            em = jax.lax.dynamic_slice_in_dim(emitted, a, SCORE_ROWS)
            return rows.max(-1), jnp.take_along_axis(rows, em[:, None], axis=1)[:, 0]

        top, chosen = jax.lax.map(block, jnp.arange(0, max_new_pad, SCORE_ROWS))
        return top.reshape(-1), chosen.reshape(-1)

    max_new_pad = -(-max_new // SCORE_ROWS) * SCORE_ROWS

    def layer_weights(i):
        p = f"model.layers.{i}."
        a, m = p + "self_attn.", p + "mlp."
        r = m + "router."
        conv1 = raw(a + "conv_qk.1.weight").reshape(groups, hd, hd, 2)  # g, out, in, tap

        def residual(at):
            return {k: raw(f"{p}{at}_residual.{name}") for k, name in (
                ("rb", "residual_bias"), ("rs", "residual_scale"),
                ("hb", "hidden_bias"), ("hs", "hidden_scale"))}

        return {
            "attn_norm": raw(p + "input_layernorm.weight"),
            "ffn_norm": raw(p + "post_attention_layernorm.weight"),
            "qkv": matrix(a + "q_proj.weight", a + "k_proj.weight",
                          a + "v_proj1.weight", a + "v_proj2.weight"),
            "o": matrix(a + "o_proj.weight"),
            "conv0_w": raw(a + "conv_qk.0.weight")[:, 0, :].T,
            "conv0_b": raw(a + "conv_qk.0.bias"),
            "conv1_w": conv1.transpose(3, 0, 2, 1), "conv1_b": raw(a + "conv_qk.1.bias"),
            "tau": raw(a + "temp"),
            "attn_res": residual("attn"), "ffn_res": residual("mlp"),
            "down": raw(r + "down_proj.weight").T, "down_b": raw(r + "down_proj.bias"),
            "gamma": raw(r + "state_scale"), "r_norm": raw(r + "norm.weight"),
            "w1": raw(r + "mlp.0.weight").T, "b1": raw(r + "mlp.0.bias"),
            "w2": raw(r + "mlp.1.weight").T, "b2": raw(r + "mlp.1.bias"),
            "w3": raw(r + "mlp.2.weight").T, "bias": raw(r + "balancing_bias"),
            "experts": {
                k: jnp.stack([matrix(f"{m}experts.{e}.{k}_proj.weight")
                              for e in range(experts)])
                for k in ("gate", "up", "down")},
        }

    order = sorted(range(len(samples)), key=lambda j: lengths[j])
    # which samples run which variant: the shortest, the second and the third
    # shortest (``wrong_pick`` parts from ``as_served`` at the middle layer),
    # and the longest the one that is printed and not judged
    runs_variant = {"no_conv": set(order[:1]), "no_value_shift": set(order[1:2] or order[:1]),
                    "wrong_pick": set(order[2:3] or order[:1]), "forced": set(order[-1:])}
    embed = np.asarray(raw("model.embed_tokens.weight"))
    # one a sample: {variant: (rows, router state)}, kept on the HOST between
    # layers, and every layer's picks of the program, [L, pad]
    states, given = [], []
    for j, (seq, n, got) in enumerate(zip(sequences, lengths, served["streams"])):
        pad = next((p for p in pads if p >= n), None)
        if pad is None or pad % q_block or pad % min(EXPERT_ROWS, pad):
            raise ValueError(f"sample of {n} tokens, pads {pads}, q_block {q_block}")
        ids = np.zeros((pad,), np.int32)
        ids[:n] = seq
        start = (embed[ids], np.zeros((pad, r_hidden), np.float32))
        states.append({"as_served": start})
        picks = np.zeros((layers, pad), np.int32)
        picks[:, :n] = got["picked"]
        given.append(picks)
        for v in ("forced", "no_conv", "no_value_shift"):
            if j in runs_variant[v]:
                states[j][v] = start
    # found[j][variant]: what the audited layers would cache and every
    # layer's comparison of the picks, on the host
    found = [{v: {"per_row": []} for v in VARIANTS} for _ in samples]
    for i in range(layers):
        w = layer_weights(i)
        name = {0: "first", last_layer: "last"}.get(i)
        for j, n in enumerate(lengths):
            x = states[j]
            if i == middle_layer and j in runs_variant["wrong_pick"]:
                x["wrong_pick"] = x["as_served"]
                found[j]["wrong_pick"]["per_row"] = list(found[j]["as_served"]["per_row"])
            mine = jnp.asarray(given[j][i])
            for v in list(x):
                out, s, (rows, c, v2, stats) = one_layer(
                    w, jnp.asarray(x[v][0]), jnp.asarray(x[v][1]), mine,
                    v == "no_conv", v == "no_value_shift", v == "forced",
                    v == "wrong_pick" and i == middle_layer)
                x[v] = (np.asarray(out), np.asarray(s))
                del out, s
                found[j][v]["per_row"].append(np.asarray(stats[:n]))
                if name:
                    found[j][v][f"kv_{name}"] = np.asarray(rows[:n])
                    found[j][v][f"c_{name}"] = np.asarray(c[:n])
                    found[j][v][f"v2_{name}"] = np.asarray(v2[:n])
                del rows, c, v2, stats
        del w
        said(f"layer {i}")
    out_norm, head = raw("model.norm.weight"), as_served(jnp.asarray(embed))

    def verdict(sample, x, per_row):
        """A sample's emitted tokens against the top of the reference's
        logits at their rows; ``per_row [L, n, 4]``: a row PARTED where, at
        some layer, the program's pick is not the reference's own although
        the reference is clear of a tie (its tokens are judged like any
        other's; how many there are is printed)."""
        emitted = sample["emitted"]
        em = np.zeros((max_new_pad,), np.int32)
        em[: len(emitted)] = emitted
        padded = np.concatenate([x, np.zeros((max_new_pad, x.shape[1]), x.dtype)])
        top, chosen = jax.device_get(score(
            jnp.asarray(padded), out_norm, head,
            jnp.asarray(len(sample["prompt"]), jnp.int32), jnp.asarray(em)))
        deficits = []
        for k in range(len(emitted)):
            t = float(top[k])
            ulp = 2.0 ** (math.floor(math.log2(abs(t))) - 7) if t else 1.0
            deficits.append((t - float(chosen[k])) / ulp)
        per_row = np.stack(per_row)
        parted = ((per_row[..., 0] > 0) & (per_row[..., 1] >= pick_margin)).any(0)
        first = len(sample["prompt"]) - 1  # the row whose logits chose emitted[0]
        return {
            "i": sample["i"], "prompt_tokens": len(sample["prompt"]),
            "emitted": len(emitted), "max_deficit_bf16_ulps": max(deficits),
            "tokens_off_top": sum(gap > 0 for gap in deficits),
            "worst_position": int(np.argmax(deficits)),
            "rows_parted": float(parted.mean()),
            "tokens_parted": int(parted[first : first + len(emitted)].sum()),
        }

    verdicts = {v: [verdict(s, x[v][0], found[j][v]["per_row"])
                    for j, (s, x) in enumerate(zip(samples, states)) if v in x]
                for v in VARIANTS}
    what_if = {
        v: {"max_deficit_bf16_ulps": max(r["max_deficit_bf16_ulps"] for r in got),
            "least_deficit_bf16_ulps": min(r["max_deficit_bf16_ulps"] for r in got),
            "tokens_off_top": sum(r["tokens_off_top"] for r in got),
            "tokens_parted": sum(r["tokens_parted"] for r in got),
            "emitted": sum(r["emitted"] for r in got),
            "prompt_tokens": [r["prompt_tokens"] for r in got]}
        for v, got in verdicts.items() if v != "as_served" and got
    }
    seconds = time.perf_counter() - t0
    said("tokens scored")
    compared = [
        audit.compare(got, found[j]["as_served"],
                      {v: found[j][v] for v in VARIANTS[1:] if v in states[j]})
        for j, got in enumerate(served["streams"])
    ]
    cache = {"rows": compared, "pick_margin": audit.PICK_MARGIN,
             **{k: v for k, v in served.items() if k != "streams"}}
    print(json.dumps({"device": device, "samples": verdicts["as_served"],
                      "what_if": what_if, "cache": cache, "seconds": seconds}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
