"""The plain reference's verdict on a sample of requests served by a
``kimi_linear`` (Kimi Linear) checkpoint: the benchmark's own copy.

Run as a child of its own after the dataflow has exited and the chip is
free: ``python benchmark/lib/reference_kimi_linear.py <in.json>``. First
``cache_audit_kimi_linear.serve`` (same process: one claim on the chip)
replays the FIRST sample through the program's engine as the timed run met
it (the two warm prompts of its prefix, then the sample, granted from the
branch snapshot the second one left) and what that engine holds is kept on
the host; the program's arrays are dropped. Then, for each sample, the
model's forward pass teacher-forced over the WHOLE sequence — prompt +
emitted tokens (+ the audit's own decode tokens), from row 0, nothing
cached — is computed here and reports, for every token the TIMED run
emitted, how many bf16 steps it lies below the top of the reference's own
logits at its position (sampled tokens are not compared: two correct
programs part within a few tokens at bf16 with random weights). The timed
run produced a request's tokens from a snapshot + the re-prefilled rows +
decode at 64 slots; the reference from one forward pass.

The mathematics is written here, in float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``, and shares no code with the
program: every sublayer ``x + F(RMSNorm(x))``; KDA: the convolution as a
sum over shifted copies of the whole sequence (zeros before row 0), SiLU,
the L2 norms, ``g = -exp(A_log) softplus((u Wfa) Wfb + dt_bias)`` a key
channel, ``beta = sigmoid(u Wb)``, the delta rule ONE TOKEN AT A TIME
(``lax.scan``; the state float32), the head-wise RMSNorm under
``sigmoid((u Wga) Wgb)``; the latent layer as full (unabsorbed) multi-head
attention, ``k_h = [Wkvb^K c | k_s]`` with the 64 shared columns not
rotated, a block of query rows at a time over every earlier row; the
expert layer given the same share (the router over all 256, the sum over
chosen-and-held, an expert on its own rows), the shared expert. No cache,
no tails, no paging, no batching. Every matrix is held to the program's
int8 weights alone (symmetric, per output channel, ``max|w| / 127``), so
the comparison measures the program's bf16 activations, caches and
arithmetic, not the quantization; embedding, routers, convolution, vectors
and norms are the checkpoint's bf16.

Controls, computed in every run on the first sample
(``chat_measure_kimi_linear.verdict`` says which limit each must FAIL):
``zero_state`` (the KDA state and the convolution's memory zeroed at the
grant's boundary: what a grant without its snapshot would compute),
``state_bf16`` (the state rounded to bfloat16 after every token: the
precision below the stated one), ``bounded_gate`` (GLM-5.3-Flash's decay in
place of the published one: another model under the same weights, which
the tokens must tell) and, apart from the forward passes, the router of
the first expert layer on the audit's rows with its scores held to
bfloat16 before the choice (``router_rows_differ_bf16``).

To fit a 14k-row sample beside float32 weights: one layer's weights at a
time (read from the checkpoint, used for every sample, dropped), a
sample's rows on the host between layers, the MLP and the head a block of
rows at a time. Every sample is padded to the smallest of ``pads`` that
holds it; the cell gives ONE pad, so that every run uses the same programs
and none is compiled after a checkout's first run. The last stdout line is
the result.
"""

from __future__ import annotations

import gc
import json
import math
import sys
import time
from functools import partial
from pathlib import Path

VARIANTS = ("as_served", "zero_state", "state_bf16", "bounded_gate")
GATE_LOWER = -5.0  # GLM-5.3-Flash's gate_lower_bound: the other gate's
EXPERT_ROWS = 512  # rows of one block of an expert's rows (divides every pad)
MLP_ROWS = 1024  # rows of one block of a dense or shared SwiGLU
SCORE_ROWS = 128  # rows of one block of the head's logits
L2_EPS = 1e-6


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from safetensors import safe_open

    import cache_audit_kimi_linear as audit  # beside this file
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

    # -- the program first: what it holds for the granted request, to the host --
    samples = spec["samples"]
    timed = [s["prompt"] + s["emitted"] for s in samples]
    first = samples[0]
    served = None
    if first.get("befores"):
        served = audit.serve(spec["checkpoint"], spec["audit"], first["befores"],
                             timed[0], min(spec["audit_decode"], max_new),
                             first.get("branch_expected", 1))
        held_bytes = sum(a.nbytes for a in jax.live_arrays())
        gc.collect()
        live = sum(a.nbytes for a in jax.live_arrays())
        said(f"engine served the granted request again ({held_bytes / 1e9:.3f} GB on the "
             f"device, {live / 1e9:.3f} after collecting; granted "
             f"{served['granted_tokens']} of {len(timed[0])} rows)")
    sequences = list(timed)
    if served:
        sequences[0] = timed[0] + served["emitted"][:-1]
    lengths = [len(s) for s in sequences]
    #: the grant's boundary of the first sample: where ``zero_state`` cuts
    cut = (served or {}).get("granted_tokens") or first.get("granted_expected") or 0

    d, heads = hf["hidden_size"], hf["num_attention_heads"]
    lin = hf["linear_attn_config"]
    kh, kd, taps = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    nope, shared, v_dim = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"]
    kv_rank, eps, top_k = hf["kv_lora_rank"], hf["rms_norm_eps"], hf["num_experts_per_token"]
    layers = hf["num_hidden_layers"]
    is_kda = [i + 1 in lin["kda_layers"] for i in range(layers)]
    is_sparse = [i >= hf["first_k_dense_replace"] and i % hf.get("moe_layer_freq", 1) == 0
                 for i in range(layers)]
    held = hf["num_experts"] // hf["ep_size"]
    first_expert = spec.get("ep_rank", 0) * held
    (kda_first, kda_last), (mla_first, mla_last) = audit.first_and_last(hf)

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
        return (jax.nn.silu(x @ w["gate"]) * (x @ w["up"])) @ w["down"]

    def in_blocks(w, x):
        size = min(MLP_ROWS, x.shape[0])
        return jax.lax.map(partial(swiglu, w),
                           x.reshape(-1, size, x.shape[1])).reshape(x.shape)

    def kda(w, x, n, cut_at, cutting, rounding, bounded):
        """The KDA mixer over ``x [T, d]``; rows ``n..`` are padding and
        leave the state alone; with ``cutting`` the state and the
        convolution's memory are zeros at row ``cut_at``. -> (output, the
        state after row n - 1, the convolution's inputs [T, 3 H d_k], the
        state after row ``cut_at`` - 1)."""
        t = x.shape[0]
        at = jnp.arange(t)[:, None]
        pre = jnp.concatenate([x @ w["q"], x @ w["k"], x @ w["v"]], -1)
        conv = 0.0
        for j in range(taps):
            back = taps - 1 - j
            rows = jnp.pad(pre, ((back, 0), (0, 0)))[:t]
            lost = cutting & (at >= cut_at) & (at - back < cut_at)
            conv = conv + w["conv"][j] * jnp.where(lost, 0.0, rows)
        q, k, v = (a.reshape(t, kh, kd) for a in jnp.split(jax.nn.silu(conv), 3, axis=-1))

        def l2(a):
            return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + L2_EPS)

        q, k = l2(q) * kd ** -0.5, l2(k)
        r = ((x @ w["fa"]) @ w["fb"]).reshape(t, kh, kd) + w["dt_bias"]
        live = jnp.arange(t) < n
        g = jnp.where(bounded, GATE_LOWER * jax.nn.sigmoid(w["a"][:, None] * r),
                      -w["a"][:, None] * jax.nn.softplus(r))
        g = jnp.where(live[:, None, None], g, 0.0)
        beta = jnp.where(live[:, None], jax.nn.sigmoid(x @ w["b"]), 0.0)

        def step(carry, inp):
            s, at_cut = carry
            q_t, k_t, v_t, g_t, b_t, number = inp
            # the state after the grant's last row, before anything cuts it
            at_cut = jnp.where(number == cut_at, s, at_cut)
            s = jnp.where(cutting & (number == cut_at), 0.0, s)
            s = s * jnp.exp(g_t)[..., None]
            pred = jnp.einsum("hkv,hk->hv", s, k_t)
            s = s + (b_t[:, None] * k_t)[..., None] * (v_t - pred)[:, None, :]
            # reduce_precision, not a cast there and back: the chip's compiler
            # drops a convert pair as excess precision it is allowed to keep
            s = jnp.where(rounding, jax.lax.reduce_precision(s, 8, 7), s)
            return (s, at_cut), jnp.einsum("hkv,hk->hv", s, q_t)

        zeros = jnp.zeros((kh, kd, kd), f32)
        (s, at_cut), o = jax.lax.scan(step, (zeros, zeros),
                                      (q, k, v, g, beta, jnp.arange(t)))
        gate = jax.nn.sigmoid((x @ w["ga"]) @ w["gb"]).reshape(t, kh, kd)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
        return (o * w["o_norm"] * gate).reshape(t, kh * kd) @ w["o"], s, pre, at_cut

    def mla(w, x):
        """-> (output [T, d], the rows a cache holds [T, kv_rank + shared])."""
        t = x.shape[0]
        q = (x @ w["q"]).reshape(t, heads, nope + shared)
        c = norm(x @ w["c"], w["kv_norm"])
        k_s = x @ w["s"]
        k = jnp.einsum("tc,hjc->thj", c, w["kb"])
        v = jnp.einsum("tc,hcj->thj", c, w["vb"])
        pos = jnp.arange(t)

        def block(a):
            qa = jax.lax.dynamic_slice_in_dim(q, a, q_block)
            sc = (jnp.einsum("qhj,khj->hqk", qa[..., :nope], k)
                  + jnp.einsum("qhj,kj->hqk", qa[..., nope:], k_s)) * (nope + shared) ** -0.5
            seen = pos[None, :] <= (a + jnp.arange(q_block))[:, None]
            pr = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khj->qhj", pr, v).reshape(q_block, heads * v_dim)

        ctx = jax.lax.map(block, jnp.arange(0, t, q_block))
        return ctx.reshape(t, heads * v_dim) @ w["o"], jnp.concatenate([c, k_s], -1)

    def route(w, x, scores_bf16=False):
        scores = jax.nn.sigmoid(x @ w["router"])
        # reduce_precision, not a cast there and back (see the state's)
        scores = jnp.where(scores_bf16, jax.lax.reduce_precision(scores, 8, 7), scores)
        _, ids = jax.lax.top_k(scores + w["bias"], top_k)
        return scores, ids

    def moe(w, x):
        scores, ids = route(w, x)
        chosen = jnp.take_along_axis(scores, ids, -1)
        if hf.get("moe_renormalize", True):
            chosen = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
        chosen = chosen * hf["routed_scaling_factor"]

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

        y, _ = jax.lax.scan(one, in_blocks(w["shared"], x),
                            (first_expert + jnp.arange(held), w["experts"]))
        return y, ids

    @partial(jax.jit, static_argnames=("linear", "sparse"), donate_argnums=(1,))
    def layer(w, x, n, cut_at, cutting, rounding, bounded, *, linear, sparse):
        with jax.default_matmul_precision("highest"):
            u = norm(x, w["attn_norm"])
            if linear:
                a, s, pre, at_cut = kda(w, u, n, cut_at, cutting, rounding, bounded)
                cache = (s, pre, at_cut)
            else:
                a, rows = mla(w, u)
                cache = (rows,)
            x = x + a
            u = norm(x, w["ffn_norm"])
            if sparse:
                y, ids = moe(w, u)
            else:
                y, ids = in_blocks(w["dense"], u), None
        return x + y, cache, ids

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

    def ffn_weights(prefix, names=("gate_proj", "up_proj", "down_proj")):
        return {k: matrix(f"{prefix}{n}.weight") for k, n in zip(("gate", "up", "down"), names)}

    def layer_weights(i):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        w = {"attn_norm": raw(p + "input_layernorm.weight"),
             "ffn_norm": raw(p + "post_attention_layernorm.weight")}
        if is_kda[i]:
            w.update({x: matrix(a + f"{x}_proj.weight") for x in "qkvo"})
            w["conv"] = jnp.concatenate(
                [raw(a + f"{x}_conv1d.weight").reshape(kh * kd, taps) for x in "qkv"], 0).T
            w.update(fa=matrix(a + "f_a_proj.weight"), fb=matrix(a + "f_b_proj.weight"),
                     ga=matrix(a + "g_a_proj.weight"), gb=matrix(a + "g_b_proj.weight"),
                     b=matrix(a + "b_proj.weight"), a=jnp.exp(raw(a + "A_log")),
                     dt_bias=raw(a + "dt_bias").reshape(kh, kd),
                     o_norm=raw(a + "o_norm.weight"))
        else:
            kva = matrix(a + "kv_a_proj_with_mqa.weight")
            kvb = matrix(a + "kv_b_proj.weight").reshape(kv_rank, heads, nope + v_dim)
            w.update(q=matrix(a + "q_proj.weight"), c=kva[:, :kv_rank], s=kva[:, kv_rank:],
                     kv_norm=raw(a + "kv_a_layernorm.weight"),
                     kb=jnp.transpose(kvb[:, :, :nope], (1, 2, 0)),
                     vb=jnp.transpose(kvb[:, :, nope:], (1, 0, 2)),
                     o=matrix(a + "o_proj.weight"))
        if not is_sparse[i]:
            w["dense"] = ffn_weights(p + "mlp.")
            return w
        m = p + "block_sparse_moe."
        w["router"] = raw(m + "gate.weight").T
        w["bias"] = raw(m + "gate.e_score_correction_bias")
        w["shared"] = ffn_weights(m + "shared_experts.")
        # one kind of matrix at a time: 64 of them are 0.6 GB in float32
        w["experts"] = {
            k: jnp.stack([matrix(f"{m}experts.{e}.{n}.weight")
                          for e in range(first_expert, first_expert + held)])
            for k, n in (("gate", "w1"), ("up", "w3"), ("down", "w2"))}
        return w

    embed = np.asarray(raw("model.embed_tokens.weight"))
    # one a sample: {variant: rows}, kept on the HOST between layers
    states = []
    for j, (seq, n) in enumerate(zip(sequences, lengths)):
        pad = next((p for p in pads if p >= n), None)
        if pad is None or pad % q_block or pad % min(MLP_ROWS, pad) or pad % min(
                EXPERT_ROWS, pad):
            raise ValueError(f"sample of {n} tokens, pads {pads}, q_block {q_block}")
        ids = np.zeros((pad,), np.int32)
        ids[:n] = seq
        start = embed[ids]
        states.append({"as_served": start})
        if j == 0:
            states[0].update(state_bf16=start, bounded_gate=start)
            if cut:
                states[0]["zero_state"] = start
    # found[variant]: what the audited layers of the FIRST sample would hold
    found = {v: {} for v in VARIANTS}
    pairs = {v: [] for v in VARIANTS}  # the first sample's routed choices, a layer each
    for i in range(layers):
        w = layer_weights(i)
        for j, n in enumerate(lengths):
            for v in list(states[j]):
                out, cache, ids = layer(
                    w, jnp.asarray(states[j][v]), jnp.asarray(n, jnp.int32),
                    jnp.asarray(cut, jnp.int32), v == "zero_state", v == "state_bf16",
                    v == "bounded_gate", linear=is_kda[i], sparse=is_sparse[i])
                if j == 0:
                    for name, at in (("first", kda_first), ("last", kda_last)):
                        if is_kda[i] and at == i:
                            found[v][f"state_{name}"] = np.asarray(cache[0])
                            found[v][f"c_{name}"] = np.asarray(cache[1][:n])
                            found[v][f"cut_{name}"] = np.asarray(cache[2])
                    for name, at in (("first", mla_first), ("last", mla_last)):
                        if not is_kda[i] and at == i:
                            found[v][f"kv_{name}"] = np.asarray(cache[0][:n])
                    if ids is not None:
                        pairs[v].append(np.sort(np.asarray(ids[:n]), -1))
                states[j][v] = np.asarray(out)
                del out, cache, ids
        del w
        said(f"layer {i}")
    out_norm, head = raw("model.norm.weight"), matrix("lm_head.weight")
    # the router's audit: the program's choices on these rows came with
    # ``served``; the float32 ones and the bfloat16-score ones are made here
    router_bf16_differ = None
    if served and served.get("router"):
        at = served["router"]
        m = f"model.layers.{at['layer']}.block_sparse_moe."
        w = {"router": raw(m + "gate.weight").T, "bias": raw(m + "gate.e_score_correction_bias")}
        rows = jnp.asarray(embed[np.asarray(at["tokens"], np.int32)])
        with jax.default_matmul_precision("highest"):
            own = np.sort(np.asarray(route(w, rows)[1]), -1)
            low = np.sort(np.asarray(route(w, rows, True)[1]), -1)
        found["as_served"]["router_ids"] = own
        router_bf16_differ = float((low != own).any(-1).mean())

    def verdict(sample, x):
        """A sample's emitted tokens against the top of the reference's
        logits at their rows."""
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
        return {
            "i": sample["i"], "caller": sample.get("caller"),
            "prompt_tokens": len(sample["prompt"]), "emitted": len(emitted),
            "rows_past_the_grant": (len(sample["prompt"]) - sample["granted_expected"]
                                    if sample.get("granted_expected") else None),
            "max_deficit_bf16_ulps": max(deficits),
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
            "prompt_tokens": [r["prompt_tokens"] for r in got],
            # the share of the first sample's (row, expert) choices that are
            # not the float32 reference's own
            "routed_pairs_differ": (float(np.mean([
                (a != b).any(-1).mean() for a, b in zip(pairs[v], pairs["as_served"])]))
                if pairs[v] else None)}
        for v, got in verdicts.items() if v != "as_served" and got
    }
    seconds = time.perf_counter() - t0
    said("tokens scored")
    cache = None
    if served:
        cache = {
            **audit.compare(served, found["as_served"], found.get("state_bf16") or None),
            # against the reference that lost its state at the grant's boundary
            **({f"{key}_zero_state": audit.rel_err(
                    served[kept], found["zero_state"][kept][: served["rows"]])
                for key, kept in (("state_first", "state_first"),
                                  ("state_last", "state_last"),
                                  ("latent_rows_last", "kv_last"))}
               if found["zero_state"] else {"state_last_zero_state": None}),
            # past the grant alone, where a lost state still shows
            "latent_rows_past_grant_last_zero_state": (
                audit.rel_err(served["kv_last"][cut:],
                              found["zero_state"]["kv_last"][cut : served["rows"]])
                if found["zero_state"] and cut else None),
            # what a grant WITHOUT its snapshot restores: zeros
            "restored_state_zero_state": audit.rel_err(
                np.zeros_like(found["as_served"]["cut_first"]),
                found["as_served"]["cut_first"]) if cut else None,
            "router_rows_differ_bf16": router_bf16_differ,
            **{k: served[k] for k in (
                "granted_pages", "snapshots_saved", "branch_saved", "branch_expected",
                "snapshots_restored",
                "before_rows", "slots", "pool_pages", "snapshot_rows", "snapshot_bytes",
                "kv_bytes_per_token", "state_snapshot_pool_bytes", "chunks_run",
                "load_seconds", "seconds")},
        }
    print(json.dumps({"device": device, "samples": verdicts["as_served"],
                      "what_if": what_if, "cache": cache, "cut": cut,
                      "seconds": seconds}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
