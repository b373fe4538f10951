"""The plain reference's verdict on a sample of requests served by a
``glm5_next_text`` (GLM-5.3-Flash) checkpoint: the benchmark's own copy.

Run as a child of its own after the dataflow has exited and the chip is
free: ``python benchmark/lib/reference_glm5_next.py <in.json>``. First
``cache_audit_glm5_next.serve`` (same process: one claim on the chip)
puts each sampled request's prompt + emitted tokens through the program's
engine once more and decodes a few tokens beyond them, and what that
engine holds is kept on the host; the program's arrays are dropped. Then,
for each sample, the model's forward pass teacher-forced over prompt +
emitted tokens (+ the audit's own decode tokens) is computed here and
reports, for every token the TIMED run emitted, how many bf16 steps it
lies below the top of the reference's own logits at its position (sampled
tokens are not compared: two correct programs part within a few tokens at
bf16 with random weights). ``top_k`` over near-ties is discontinuous, so
at the sparse-latent layer the reference attends the blocks the PROGRAM
picked (the audit's chunks over the same tokens, then its decode ticks:
``make_paged_engine(picks=True)``) and the picked sets are compared apart
(``cache_audit_glm5_next.picked_against``: the share of blocks that
differ from the reference's own top-k, and how near a tie each one was).

The mathematics is written here, in float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``, and shares no code with the
program: the residual streams and their Sinkhorn maps, the delta rule a
token at a time (``lax.scan``), latent attention unabsorbed under a dense
picked mask a block of query rows at a time, the indexer's scores over
mean-pooled keys, the sigmoid router over all experts with the bias in
the choice only, a loop over the experts this rank holds, the shared
expert, the clamp on every SwiGLU. No cache, no paging, no batching. What
the absent experts would add is left out, as in the program. Every matrix
is held to the program's int8 weights alone (symmetric, per output
channel, ``max|w| / 127``), so the comparison measures the program's bf16
activations, caches and arithmetic, not the quantization; embedding,
routers, norms and residual maps are the checkpoint's bf16.

Controls, computed in every run, each of which must FAIL the limits the
program passes (``chat_measure_glm5_next.verdict``): ``no_selection``
(every row attends all of ``0..t``) on the longest sample,
``softplus_gate`` (†3's other gate) and ``one_stream`` (``hc_mult`` 1: a
plain residual) on the shortest, and the program's own states through
bf16 (``cache_audit_glm5_next.compare``: seen by their bit patterns; this
reference with its state and the sums read from it held to bf16 at every
step lies INSIDE every limit, PERF.md section 6, PR 43, so no run computes
it).

To fit a 16k-token sample beside float32 weights: one layer's weights at
a time (read from the checkpoint, used for every sample, dropped), a
sample's streams on the host between layers, scores a block of queries at
a time. Every sample is padded to the smallest of ``pads`` that holds it;
the cell gives ONE pad (16,384), so that every run uses the same nine
programs (three layer kinds as served, and the six that a control
changes) and none is compiled after a checkout's first run. The last
stdout line is the result.
"""

from __future__ import annotations

import gc
import json
import math
import sys
import time
from functools import partial
from pathlib import Path

VARIANTS = ("as_served", "no_selection", "softplus_gate", "one_stream")
EXPERT_ROWS = 512  # rows of one block of an expert's rows (divides every pad)
NORM_EPS_SMALL = 1e-6  # the indexer's LayerNorm and the l2 norms


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from safetensors import safe_open

    import cache_audit_glm5_next as audit  # beside this file
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

    # -- the program first: its caches and its picked blocks, to the host ------
    samples = spec["samples"]
    timed = [s["prompt"] + s["emitted"] for s in samples]
    served = audit.serve(spec["checkpoint"], spec["audit"], timed,
                         min(spec["audit_decode"], max_new))
    # the engine's closures refer to one another: free its weights, pools and
    # states now, not when the collector next runs
    held = sum(a.nbytes for a in jax.live_arrays())
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    said(f"engine served the samples again ({held / 1e9:.3f} GB on the device, "
         f"{live / 1e9:.3f} after collecting)")
    sequences = [t + got["emitted"][:-1] for t, got in zip(timed, served["streams"])]
    lengths = [len(s) for s in sequences]

    d, heads = hf["hidden_size"], hf["num_attention_heads"]
    lin = hf["linear_attn_config"]
    kh, kd, taps = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    lower = float(lin["gate_lower_bound"])
    nope, v_dim, kv_rank = hf["qk_nope_head_dim"], hf["v_head_dim"], hf["kv_lora_rank"]
    ih, idim = hf["index_n_heads"], hf["index_head_dim"]
    topk, kpool = hf["index_topk"], hf["index_kpool"]
    n_picked = topk // kpool
    n_hc, hc_eps, hc_iters = hf["hc_mult"], hf["hc_eps"], hf["hc_sinkhorn_iters"]
    eps, top_k, limit = hf["rms_norm_eps"], hf["num_experts_per_tok"], hf.get("swiglu_limit")
    layers = hf["num_hidden_layers"]
    linear = [k == "linear_attention" for k in hf["layer_types"]]
    held = hf["n_routed_experts"] // hf["ep_size"]
    first = spec.get("ep_rank", 0) * held
    kept = audit.audited_layers(hf["layer_types"])

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

    def swiglu(w, x):
        gate, up = x @ w["gate"], x @ w["up"]
        if limit is not None:
            gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
        return (jax.nn.silu(gate) * up) @ w["down"]

    def sinkhorn(m):
        for _ in range(hc_iters):
            m = m / (m.sum(-1, keepdims=True) + hc_eps)
            m = m / (m.sum(-2, keepdims=True) + hc_eps)
        return m

    def sublayer(hc, streams, norm_w, fn, one_stream):
        """One sublayer around the residual streams ``[T, n, d]`` (or the
        plain residual ``[T, d]`` of the ``one_stream`` control)."""
        if one_stream:
            return streams + fn(norm(streams, norm_w))
        z = streams.reshape(streams.shape[0], n_hc * d)
        z = z * jax.lax.rsqrt(jnp.mean(z * z, -1, keepdims=True) + hc_eps)
        m = z @ hc["fn"]
        a, b = hc["scale"], hc["base"]
        pre = jax.nn.sigmoid(a[0] * m[:, :n_hc] + b[:n_hc])
        post = 2.0 * jax.nn.sigmoid(a[1] * m[:, n_hc : 2 * n_hc] + b[n_hc : 2 * n_hc])
        res = sinkhorn(jnp.exp(a[2] * m[:, 2 * n_hc :].reshape(-1, n_hc, n_hc)
                               + b[2 * n_hc :].reshape(n_hc, n_hc)))
        y = fn(norm(jnp.einsum("ti,tid->td", pre, streams), norm_w))
        return jnp.einsum("tij,tjd->tid", res, streams) + post[:, :, None] * y[:, None]

    def kda(w, x, n, softplus_gate):
        """The delta-rule mixer over ``x [T, d]``; rows ``n..`` are padding
        and leave the state alone. -> (output, the state after row n-1)."""
        t = x.shape[0]

        def convolved(name, at):  # one projection at a time: 0.5 GB at 16k rows
            pre = jnp.concatenate([jnp.zeros((taps - 1, kh * kd), f32), x @ w[name]], 0)
            taps_w = w["conv"][:, at * kh * kd : (at + 1) * kh * kd]
            conv = sum(pre[j : j + t] * taps_w[j] for j in range(taps))
            return jax.nn.silu(conv).reshape(t, kh, kd)

        q, k, v = (convolved(name, at) for at, name in enumerate("qkv"))

        def l2(a):
            return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + NORM_EPS_SMALL)

        q, k = l2(q) * kd ** -0.5, l2(k)
        r = ((x @ w["fa"]) @ w["fb"]).reshape(t, kh, kd) + w["dt_bias"]
        if softplus_gate:
            g = -w["a"][:, None] * jax.nn.softplus(r)
        else:
            g = lower * jax.nn.sigmoid(w["a"][:, None] * r)
        beta = jax.nn.sigmoid(x @ w["b"])
        live = jnp.arange(t) < n
        g = jnp.where(live[:, None, None], g, 0.0)
        beta = jnp.where(live[:, None], beta, 0.0)

        def step(s, inp):
            q_t, k_t, v_t, g_t, b_t = inp
            s = s * jnp.exp(g_t)[..., None]
            pred = jnp.einsum("hkv,hk->hv", s, k_t)
            s = s + (b_t[:, None] * k_t)[..., None] * (v_t - pred)[:, None, :]
            return s, jnp.einsum("hkv,hk->hv", s, q_t)

        s, o = jax.lax.scan(step, jnp.zeros((kh, kd, kd), f32), (q, k, v, g, beta))
        gate = jax.nn.sigmoid((x @ w["ga"]) @ w["gb"]).reshape(t, kh, kd)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
        return (o * w["o_norm"] * gate).reshape(t, kh * kd) @ w["o"], s

    def dsa(w, x, given, given_rows, no_selection):
        """Latent attention under the indexer over ``x [T, d]``: rows below
        ``given_rows`` attend the blocks ``given [T, n_picked]`` (the
        program's: every row of a sample), the padding past them the
        reference's own top-k, which keeps the mask behind the scores in the
        program's schedule: without that the layer's temporaries grow past
        what is free beside 36 experts in float32 (my chip run, PR 43, call
        g8). -> (output, the latent rows, the pooled keys, the scores, the
        own picks)."""
        t = x.shape[0]
        c_q = norm(x @ w["qa"], w["q_norm"])
        c = norm(x @ w["kva"], w["kv_norm"])
        q = (c_q @ w["qb"]).reshape(t, heads, nope)
        ki = x @ w["ik"]
        ki = ki - ki.mean(-1, keepdims=True)
        ki = ki * jax.lax.rsqrt(jnp.mean(ki * ki, -1, keepdims=True) + NORM_EPS_SMALL)
        ki = ki * w["i_norm_w"] + w["i_norm_b"]
        nb = t // kpool
        pooled = ki.reshape(nb, kpool, idim).mean(1)
        qi = (c_q @ w["iq"]).reshape(t, ih, idim)
        wi = (x @ w["iw"]) * (ih ** -0.5 * idim ** -0.5)
        pos = jnp.arange(t)

        def score_block(a):
            qa = jax.lax.dynamic_slice_in_dim(qi, a, q_block)
            wa = jax.lax.dynamic_slice_in_dim(wi, a, q_block)
            s = (jax.nn.relu(jnp.einsum("qjd,nd->qjn", qa, pooled)) * wa[..., None]).sum(1)
            may = jnp.arange(nb)[None] < ((a + jnp.arange(q_block)) // kpool)[:, None]
            return jnp.where(may, s, -jnp.inf)

        scores = jax.lax.map(score_block, jnp.arange(0, t, q_block)).reshape(t, nb)
        _, own = jax.lax.top_k(scores, n_picked)
        ids = jnp.where((pos < given_rows)[:, None], given, own)
        sel = jnp.zeros((t, nb), bool).at[pos[:, None], ids].set(True)
        k = jnp.einsum("tc,hjc->thj", c, w["kb"])
        v = jnp.einsum("tc,hcj->thj", c, w["vb"])

        def block(a):
            rows = a + jnp.arange(q_block)
            qa = jax.lax.dynamic_slice_in_dim(q, a, q_block)
            s = jnp.einsum("qhj,khj->hqk", qa, k) * nope ** -0.5
            seen = pos[None, :] <= rows[:, None]
            if not no_selection:
                mine = jnp.repeat(jax.lax.dynamic_slice_in_dim(sel, a, q_block), kpool, 1)
                tail = pos[None, :] >= (rows // kpool * kpool)[:, None]
                seen = seen & ((rows < topk)[:, None] | mine | tail)
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khj->qhj", p, v)

        ctx = jax.lax.map(block, jnp.arange(0, t, q_block)).reshape(t, heads * v_dim)
        out = ctx @ w["o"]
        return out, c, pooled, scores, own, out

    def moe(w, x):
        scores = jax.nn.sigmoid(x @ w["router"])
        _, ids = jax.lax.top_k(scores + w["bias"], top_k)
        chosen = jnp.take_along_axis(scores, ids, -1)
        if hf.get("norm_topk_prob", True):
            chosen = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
        chosen = chosen * hf["routed_scaling_factor"]

        def one(y, expert):
            """An expert held here on the rows that chose it, ``EXPERT_ROWS``
            of them at a time (rows past the last are weighted 0): applying
            every expert to every row is 36 times the products."""
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

        y, _ = jax.lax.scan(one, swiglu(w["shared"], x),
                            (first + jnp.arange(held), w["experts"]))
        return y

    @partial(jax.jit, static_argnames=("is_linear", "switch"), donate_argnums=(1,))
    def one_layer(w, streams, n, given, given_rows, *, is_linear, switch):
        one_stream = switch == "one_stream"
        cache = []
        with jax.default_matmul_precision("highest"):
            def mixer(h):
                if is_linear:
                    out, s = kda(w, h, n, switch == "softplus_gate")
                    cache.append(s)
                else:
                    out, *rest = dsa(w, h, given, given_rows, switch == "no_selection")
                    cache.extend(rest)
                return out

            def ffn(h):
                return swiglu(w["dense"], h) if "dense" in w else moe(w, h)

            streams = sublayer(w["hc_attn"], streams, w["attn_norm"], mixer, one_stream)
            streams = sublayer(w["hc_ffn"], streams, w["ffn_norm"], ffn, one_stream)
        return streams, tuple(cache)

    #: which variants change which kind of layer (a variant that does not
    #: change a layer runs the reference's own program of it: no compile)
    changes = {True: ("softplus_gate", "one_stream"),
               False: ("no_selection", "one_stream")}

    def layer(w, streams, n, given, given_rows, *, is_linear, variant):
        """One layer (a program a kind, a length and a variant that changes
        it) -> (its output, what it would cache: the state, or the
        sparse-latent layer's rows, pooled keys, scores and own picks)."""
        switch = variant if variant in changes[is_linear] else None
        return one_layer(w, streams, n, given, given_rows, is_linear=is_linear,
                         switch=switch)

    @jax.jit
    def score(x, out_norm, head, start, emitted):
        with jax.default_matmul_precision("highest"):
            rows = norm(x[start - 1 + jnp.arange(max_new)], out_norm) @ head
        chosen = jnp.take_along_axis(rows, emitted[:, None], axis=1)[:, 0]
        return rows.max(-1), chosen

    def ffn_weights(prefix):
        return {k: matrix(f"{prefix}{k}_proj.weight") for k in ("gate", "up", "down")}

    def layer_weights(i):
        p = f"model.layers.{i}."
        a, m = p + "self_attn.", p + "mlp."
        w = {"attn_norm": raw(p + "input_layernorm.weight"),
             "ffn_norm": raw(p + "post_attention_layernorm.weight")}
        for sub in ("attn", "ffn"):
            w[f"hc_{sub}"] = {"fn": raw(p + f"hc_{sub}_fn").T,
                              "base": raw(p + f"hc_{sub}_base"),
                              "scale": raw(p + f"hc_{sub}_scale")}
        if linear[i]:
            w.update({x: matrix(a + f"{x}_proj.weight") for x in "qkvo"})
            w["conv"] = jnp.concatenate(
                [raw(a + f"{x}_conv1d.weight").reshape(kh * kd, taps) for x in "qkv"], 0).T
            w.update(fa=matrix(a + "f_a_proj.weight"), fb=matrix(a + "f_b_proj.weight"),
                     ga=matrix(a + "g_a_proj.weight"), gb=matrix(a + "g_b_proj.weight"),
                     b=matrix(a + "b_proj.weight"), a=jnp.exp(raw(a + "A_log")),
                     dt_bias=raw(a + "dt_bias").reshape(kh, kd),
                     o_norm=raw(a + "o_norm.weight"))
        else:
            kvb = matrix(a + "kv_b_proj.weight").reshape(kv_rank, heads, nope + v_dim)
            w.update(qa=matrix(a + "q_a_proj.weight"), q_norm=raw(a + "q_a_layernorm.weight"),
                     qb=matrix(a + "q_b_proj.weight"),
                     kva=matrix(a + "kv_a_proj_with_mqa.weight"),
                     kv_norm=raw(a + "kv_a_layernorm.weight"),
                     kb=jnp.transpose(kvb[:, :, :nope], (1, 2, 0)),
                     vb=jnp.transpose(kvb[:, :, nope:], (1, 0, 2)),
                     o=matrix(a + "o_proj.weight"),
                     iq=matrix(a + "indexer.wq_b.weight"), ik=matrix(a + "indexer.wk.weight"),
                     iw=matrix(a + "indexer.weights_proj.weight"),
                     i_norm_w=raw(a + "indexer.k_norm.weight"),
                     i_norm_b=raw(a + "indexer.k_norm.bias"))
        if hf["mlp_layer_types"][i] == "dense":
            w["dense"] = ffn_weights(m)
        else:
            w["router"] = raw(m + "gate.weight").T
            w["bias"] = raw(m + "gate.e_score_correction_bias")
            w["shared"] = ffn_weights(m + "shared_experts.")
            # one kind of matrix at a time: 36 of them are 1.2 GB in float32
            w["experts"] = {
                k: jnp.stack([matrix(f"{m}experts.{e}.{k}_proj.weight")
                              for e in range(first, first + held)])
                for k in ("gate", "up", "down")}
        return w

    order = sorted(range(len(samples)), key=lambda j: lengths[j])
    # which samples run which control: the longest, and the shortest
    runs_control = {"no_selection": set(order[-1:]), "softplus_gate": set(order[:1]),
                    "one_stream": set(order[:1])}
    embed = np.asarray(raw("model.embed_tokens.weight"))
    # one a sample: {variant: streams}, kept on the HOST between layers (a
    # 16k-row sample's streams are 1 GB in float32), and the program's picks
    states, given = [], []
    for seq, n, got in zip(sequences, lengths, served["streams"]):
        pad = next((p for p in pads if p >= n), None)
        if pad is None or pad % q_block or pad % kpool:
            raise ValueError(f"sample of {n} tokens, pads {pads}, q_block {q_block}")
        ids = np.zeros((pad,), np.int32)
        ids[:n] = seq
        x = embed[ids]
        states.append({"as_served": np.broadcast_to(x[:, None], (pad, n_hc, d))})
        picks = np.zeros((pad, n_picked), np.int32)
        picks[:n] = np.concatenate([got["picked"], got["picked_decode"]])
        given.append(jnp.asarray(picks))
        j = len(states) - 1
        if j in runs_control["softplus_gate"]:
            states[j]["softplus_gate"] = states[j]["as_served"]
        if j in runs_control["one_stream"]:
            states[j]["one_stream"] = x
    # found[j][variant]: what the audited layers would cache, on the host
    found = [{v: {} for v in VARIANTS} for _ in samples]
    for i in range(layers):
        w = layer_weights(i)
        for j, n in enumerate(lengths):
            x = states[j]
            # the control without selection parts from the reference at the
            # first sparse-latent layer
            if j in runs_control["no_selection"] and not linear[i]:
                x.setdefault("no_selection", x["as_served"])
            for v in list(x):
                out, cache = layer(w, jnp.asarray(x[v]), jnp.int32(n), given[j],
                                   jnp.int32(n), is_linear=linear[i], variant=v)
                x[v] = np.asarray(out)
                del out
                if linear[i] and i == kept["state_first"]:
                    found[j][v]["state_first"] = np.asarray(cache[0])
                if linear[i] and i == kept["state_deep"]:
                    found[j][v]["state_deep"] = np.asarray(cache[0])
                if not linear[i] and i == kept["pages"] and v == "as_served":
                    c, pooled, scores, own, out = cache
                    found[j][v].update(
                        latent=np.asarray(c[:n]), index=np.asarray(pooled[: n // kpool]),
                        scores=np.asarray(scores[:n]), picked=np.asarray(own[:n]),
                        attended=np.asarray(out[:n]))
                if not linear[i] and i == kept["pages"] and v == "no_selection":
                    found[j][v]["attended"] = np.asarray(cache[-1][:n])
                if not linear[i] and i == kept["pages"] and v in ("softplus_gate",
                                                                  "one_stream"):
                    found[j][v].update(latent=np.asarray(cache[0][:n]),
                                       index=np.asarray(cache[1][: n // kpool]))
        del w, cache
        said(f"layer {i}")
    out_norm, head = raw("model.norm.weight"), matrix("lm_head.weight")

    def verdict(sample, x):
        if x.ndim == 3:  # the exit: the streams' sum
            x = x.sum(1)
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
