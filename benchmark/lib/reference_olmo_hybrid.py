"""The plain reference's verdict on a sample of requests served by an
``olmo_hybrid`` (Olmo-Hybrid) checkpoint: the benchmark's own copy.

Run as a child of its own after the dataflow has exited and the chip is
free: ``python benchmark/lib/reference_olmo_hybrid.py <in.json>``. First
``cache_audit_olmo_hybrid.serve`` (same process: one claim on the chip)
replays the FIRST sample, a follow-up turn, through the program's engine
as the timed run met it (the turn before it, then the sample, granted from
the snapshot that turn left) and what that engine holds is kept on the
host; the program's arrays are dropped. Then, for each sample, the model's
forward pass teacher-forced over the WHOLE history — prompt + emitted
tokens (+ the audit's own decode tokens), from row 0, nothing cached — is
computed here and reports, for every token the TIMED run emitted, how many
bf16 steps it lies below the top of the reference's own logits at its
position (sampled tokens are not compared: two correct programs part
within a few tokens at bf16 with random weights). The timed run produced a
follow-up turn's tokens from a snapshot + the re-prefilled rows + decode;
the reference from one forward pass.

The mathematics is written here, in float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``, and shares no code with the
program: every sublayer ``x + RMSNorm(F(x))``; the convolution as a sum
over shifted copies of the whole sequence (zeros before row 0); SiLU; the
L2 norms; ``alpha = exp(-exp(A_log) softplus(W_a x + dt_bias))``, ``beta =
2 sigmoid(W_b x)``; the delta rule IN BLOCKS of 64 rows by the Gated
DeltaNet paper's ``W`` / ``U`` form (``T = (I + tril(diag(beta) (K K^T) *
D, -1))^-1`` by a triangular solve, ``W = T (beta e^gamma K)``, ``U = T
(beta V)``, the block's new values ``U - W S``, ``O = (Q e^gamma) S + ((Q
K^T) * D) (U - W S)``, ``S' = e^gamma_end S + (K e^(gamma_end -
gamma))^T (U - W S)``; ``D[t, s] = e^(gamma_t - gamma_s)``, ``gamma`` the
running sum of ``log alpha`` inside the block), the state carried between
blocks in float32; the output RMSNorm over each head, ``silu(W_g x)``;
full attention with RMSNorm over the whole q and k projections, no
rotary, a block of query rows at a time; SwiGLU. No cache, no tails, no
paging, no batching. Every matrix is held to the program's int8 weights
alone (symmetric, per output channel, ``max|w| / 127``), so the comparison
measures the program's bf16 activations, caches and arithmetic, not the
quantization; embedding, convolution, vectors and norms are the
checkpoint's bf16.

Controls, computed in every run on the first sample, each of which must
FAIL a limit the program passes (``chat_measure_olmo_hybrid.verdict``):
``zero_state`` (the delta-rule state and the convolution's memory zeroed
at the grant's boundary: what a grant without its snapshot would
compute) and ``state_bf16`` (the state rounded to bfloat16 after every
block: what a snapshot pool, or a state, of a narrower dtype would hold).

To fit an 8k-row sample beside float32 weights: one layer's weights at a
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

VARIANTS = ("as_served", "zero_state", "state_bf16")
BLOCK_ROWS = 64  # rows of one block of the delta rule (a tiny chunk's, where shorter)
MLP_ROWS = 1024  # rows of one block of the SwiGLU
SCORE_ROWS = 128  # rows of one block of the head's logits


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from safetensors import safe_open

    import cache_audit_olmo_hybrid as audit  # beside this file
    from dora_tpu import backend

    spec = json.load(open(sys.argv[1]))
    backend.init_compile_cache()
    device = backend.require_accelerator("benchmark reference")
    ckpt = Path(spec["checkpoint"])
    hf = json.loads((ckpt / "config.json").read_text())
    pads, max_new, q_block = sorted(spec["pads"]), spec["max_new"], spec["q_block"]
    f32 = jnp.float32
    BLOCK = math.gcd(BLOCK_ROWS, spec["chunk"])
    t0 = time.perf_counter()

    def said(what):
        print(f"reference: {what} at {time.perf_counter() - t0:.1f}s", file=sys.stderr,
              flush=True)

    # -- the program first: what it holds for the granted turn, to the host ---
    samples = spec["samples"]
    timed = [s["prompt"] + s["emitted"] for s in samples]
    first = samples[0]
    served = None
    if first.get("before"):
        served = audit.serve(spec["checkpoint"], spec["audit"], first["before"],
                             timed[0], min(spec["audit_decode"], max_new))
        held_bytes = sum(a.nbytes for a in jax.live_arrays())
        gc.collect()
        live = sum(a.nbytes for a in jax.live_arrays())
        said(f"engine served the granted turn again ({held_bytes / 1e9:.3f} GB on the "
             f"device, {live / 1e9:.3f} after collecting; granted "
             f"{served['granted_tokens']} of {len(timed[0])} rows)")
    sequences = list(timed)
    if served:
        sequences[0] = timed[0] + served["emitted"][:-1]
    lengths = [len(s) for s in sequences]
    #: the grant's boundary of the first sample: where ``zero_state`` cuts
    cut = (served or {}).get("granted_tokens") or (
        len(first.get("before") or []) // spec["chunk"] * spec["chunk"])

    d, heads = hf["hidden_size"], hf["num_attention_heads"]
    kv_heads = hf["num_key_value_heads"]
    hd = d // heads
    g, q_w, kv_w = heads // kv_heads, heads * hd, kv_heads * hd
    h, dk, dv = (hf["linear_num_value_heads"], hf["linear_key_head_dim"],
                 hf["linear_value_head_dim"])
    kw, vw, taps = h * dk, h * dv, hf["linear_conv_kernel_dim"]
    eps, layers, kinds = hf["rms_norm_eps"], hf["num_hidden_layers"], hf["layer_types"]
    doubled = 2.0 if hf.get("linear_allow_neg_eigval") else 1.0
    (lin_first, lin_last), (full_first, full_last) = audit.linear_and_full(kinds)

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

    def mlp(w, x):
        def block(rows):
            return (jax.nn.silu(rows @ w["gate"]) * (rows @ w["up"])) @ w["down"]

        size = min(MLP_ROWS, x.shape[0])
        return jax.lax.map(block, x.reshape(-1, size, x.shape[1])).reshape(x.shape)

    def convolution(w, c, cut_at, cutting):
        """The causal depthwise convolution of ``taps`` rows, tap 0 the
        oldest, zeros before row 0; with ``cutting`` also zeros before row
        ``cut_at`` for the rows from there on."""
        t = c.shape[0]
        at = jnp.arange(t)[:, None]
        out = 0.0
        for j in range(taps):
            back = taps - 1 - j
            rows = jnp.pad(c, ((back, 0), (0, 0)))[:t]
            lost = cutting & (at >= cut_at) & (at - back < cut_at)
            out = out + w[j] * jnp.where(lost, 0.0, rows)
        return out

    def delta_rule(q, k, v, log_alpha, beta, cut_at, cutting, rounding):
        """In blocks of ``BLOCK`` rows (the module's docstring). -> (o [T,
        H, d_v], the state after the last row [H, d_k, d_v])."""
        t = q.shape[0]
        nb = t // BLOCK

        def blocks(a):
            return a.reshape(nb, BLOCK, *a.shape[1:])

        idx = jnp.arange(BLOCK)
        strictly, upto = idx[:, None] > idx[None, :], idx[:, None] >= idx[None, :]
        eye = jnp.eye(BLOCK, dtype=f32)

        def one(s, inp):
            qb, kb, vb, gb, bb, number = inp  # [Q, H, ...]
            s = jnp.where(cutting & (number * BLOCK == cut_at), 0.0, s)
            gamma = jnp.cumsum(gb, 0)  # [Q, H]
            gh = gamma.T  # [H, Q]
            dmat = jnp.exp(jnp.where(upto, gh[:, :, None] - gh[:, None, :], -jnp.inf))
            kh, qh, vh = (jnp.moveaxis(a, 1, 0) for a in (kb, qb, vb))  # [H, Q, .]
            bh = bb.T[:, :, None]  # [H, Q, 1]
            a_mat = jnp.where(strictly, bh * (kh @ jnp.swapaxes(kh, 1, 2)) * dmat, 0.0)
            t_mat = jax.lax.linalg.triangular_solve(
                eye + a_mat, jnp.broadcast_to(eye, a_mat.shape), left_side=True,
                lower=True, unit_diagonal=True)
            w_mat = t_mat @ (bh * jnp.exp(gh)[:, :, None] * kh)  # [H, Q, d_k]
            u_mat = t_mat @ (bh * vh)  # [H, Q, d_v]
            new = u_mat - w_mat @ s
            o = (qh * jnp.exp(gh)[:, :, None]) @ s + (
                (qh @ jnp.swapaxes(kh, 1, 2)) * dmat) @ new
            to_end = jnp.exp(gh[:, -1:] - gh)[:, :, None]
            s = jnp.exp(gh[:, -1])[:, None, None] * s + jnp.swapaxes(kh * to_end, 1, 2) @ new
            # reduce_precision, not a cast there and back: the chip's compiler
            # drops a convert pair as excess precision it is allowed to keep
            s = jnp.where(rounding, jax.lax.reduce_precision(s, 8, 7), s)
            return s, jnp.moveaxis(o, 0, 1)

        s0 = jnp.zeros((h, dk, dv), f32)
        s, o = jax.lax.scan(one, s0, (*map(blocks, (q, k, v, log_alpha, beta)),
                                      jnp.arange(nb)))
        return o.reshape(t, h, dv), s

    def linear_attention(w, x, n, cut_at, cutting, rounding):
        t = x.shape[0]
        c = jnp.concatenate([x @ w["q"], x @ w["k"], x @ w["v"]], -1)
        act = jax.nn.silu(convolution(w["conv"], c, cut_at, cutting))
        q = act[:, :kw].reshape(t, h, dk)
        k = act[:, kw : 2 * kw].reshape(t, h, dk)
        v = act[:, 2 * kw :].reshape(t, h, dv)

        def l2(a):
            return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

        q, k = l2(q) * dk ** -0.5, l2(k)
        # rows past the sequence's end are padding: they leave the state alone
        valid = (jnp.arange(t) < n)[:, None]
        beta = jnp.where(valid, doubled * jax.nn.sigmoid(x @ w["b"]), 0.0)
        log_alpha = jnp.where(
            valid, -w["a_exp"] * jax.nn.softplus(x @ w["a"] + w["dt_bias"]), 0.0)
        o, s = delta_rule(q, k, v, log_alpha, beta, cut_at, cutting, rounding)
        o = norm(o, w["o_norm"]) * jax.nn.silu(x @ w["g"]).reshape(o.shape)
        return o.reshape(t, vw) @ w["o"], s, c

    def full_attention(w, x):
        t = x.shape[0]
        q = norm(x @ w["q"], w["q_norm"]).reshape(t, kv_heads, g, hd)
        k = norm(x @ w["k"], w["k_norm"]).reshape(t, kv_heads, hd)
        v = (x @ w["v"]).reshape(t, kv_heads, hd)
        pos = jnp.arange(t)

        def block(a):
            qa = jax.lax.dynamic_slice_in_dim(q, a, q_block)
            sc = jnp.einsum("qkgd,tkd->kgqt", qa, k) * hd ** -0.5
            seen = pos[None, :] <= (a + jnp.arange(q_block))[:, None]
            pr = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), axis=-1)
            return jnp.einsum("kgqt,tkd->qkgd", pr, v).reshape(q_block, q_w)

        ctx = jax.lax.map(block, jnp.arange(0, t, q_block))
        rows = jnp.concatenate([k.reshape(t, kv_w), v.reshape(t, kv_w)], -1)
        return ctx.reshape(t, q_w) @ w["o"], rows

    @partial(jax.jit, donate_argnums=(1,))
    def linear_layer(w, x, n, cut_at, cutting, rounding):
        with jax.default_matmul_precision("highest"):
            a, s, c = linear_attention(w, x, n, cut_at, cutting, rounding)
            x = x + norm(a, w["attn_norm"])
            x = x + norm(mlp(w, x), w["ffn_norm"])
        return x, (s, c)

    @partial(jax.jit, donate_argnums=(1,))
    def full_layer(w, x):
        with jax.default_matmul_precision("highest"):
            a, rows = full_attention(w, x)
            x = x + norm(a, w["attn_norm"])
            x = x + norm(mlp(w, x), w["ffn_norm"])
        return x, rows

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
        w = {
            "attn_norm": raw(p + "post_attention_layernorm.weight"),
            "ffn_norm": raw(p + "post_feedforward_layernorm.weight"),
            "gate": matrix(p + "mlp.gate_proj.weight"),
            "up": matrix(p + "mlp.up_proj.weight"),
            "down": matrix(p + "mlp.down_proj.weight"),
        }
        if kinds[i] == "linear_attention":
            a = p + "linear_attn."
            conv = jnp.concatenate(
                [raw(a + f"{n}_conv1d.weight").reshape(-1, taps) for n in "qkv"], 0).T
            w.update({n: matrix(a + f"{n}_proj.weight") for n in "qkvgabo"})
            w.update(conv=conv, a_exp=jnp.exp(raw(a + "A_log")),
                     dt_bias=raw(a + "dt_bias"), o_norm=raw(a + "o_norm.weight"))
        else:
            a = p + "self_attn."
            w.update({n: matrix(a + f"{n}_proj.weight") for n in "qkvo"})
            w.update(q_norm=raw(a + "q_norm.weight"), k_norm=raw(a + "k_norm.weight"))
        return w

    embed = np.asarray(raw("model.embed_tokens.weight"))
    # one a sample: {variant: rows}, kept on the HOST between layers
    states = []
    for j, (seq, n) in enumerate(zip(sequences, lengths)):
        pad = next((p for p in pads if p >= n), None)
        if pad is None or pad % q_block or pad % BLOCK or pad % min(MLP_ROWS, pad):
            raise ValueError(f"sample of {n} tokens, pads {pads}, q_block {q_block}")
        if j == 0 and cut % BLOCK:
            raise ValueError(f"the grant's boundary {cut} is no multiple of {BLOCK}")
        ids = np.zeros((pad,), np.int32)
        ids[:n] = seq
        start = embed[ids]
        states.append({"as_served": start})
        if j == 0 and cut:
            states[0].update(zero_state=start, state_bf16=start)
    # found[variant]: what the audited layers of the FIRST sample would hold
    found = {v: {} for v in VARIANTS}
    names = (("first", lin_first), ("last", lin_last))
    full_names = (("first", full_first), ("last", full_last))
    for i in range(layers):
        w = layer_weights(i)
        for j, n in enumerate(lengths):
            for v in list(states[j]):
                x = jnp.asarray(states[j][v])
                if kinds[i] == "linear_attention":
                    out, (s, c) = linear_layer(
                        w, x, jnp.asarray(n, jnp.int32), jnp.asarray(cut, jnp.int32),
                        v == "zero_state", v == "state_bf16")
                    for name, layer in names if j == 0 else ():
                        if layer == i:
                            found[v][f"state_{name}"] = np.asarray(s)
                            found[v][f"c_{name}"] = np.asarray(c[:n])
                    del s, c
                else:
                    out, rows = full_layer(w, x)
                    for name, layer in full_names if j == 0 else ():
                        if layer == i:
                            found[v][f"kv_{name}"] = np.asarray(rows[:n])
                    del rows
                states[j][v] = np.asarray(out)
                del out, x
        del w
        said(f"layer {i}")
    out_norm, head = raw("model.norm.weight"), matrix("lm_head.weight")

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
            "i": sample["i"], "turn": sample.get("turn"),
            "prompt_tokens": len(sample["prompt"]), "emitted": len(emitted),
            "rows_past_the_grant": (
                len(sample["prompt"]) - sample["granted_expected"]
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
            "prompt_tokens": [r["prompt_tokens"] for r in got]}
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
                                  ("state_last", "state_last"), ("kv_rows_last", "kv_last"))}
               if found["zero_state"] else {"state_last_zero_state": None}),
            **{k: served[k] for k in (
                "granted_pages", "snapshots_saved", "snapshots_restored", "before_rows",
                "pool_pages", "snapshot_rows", "snapshot_bytes", "kv_bytes_per_token",
                "state_snapshot_pool_bytes", "chunks_run", "load_seconds", "seconds")},
        }
    print(json.dumps({"device": device, "samples": verdicts["as_served"],
                      "what_if": what_if, "cache": cache, "cut": cut,
                      "seconds": seconds}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
