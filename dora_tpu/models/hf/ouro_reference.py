"""The plain reference of the Ouro looped LM: float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``, one full forward pass over
a whole sequence. No cache, no paging, no batching, no kernels, and no
code shared with ``ouro.py``: it reads the checkpoint's tensors under
their HF names itself.

Every matrix is held to the program's int8 weights alone (symmetric,
per output channel, ``max|w| / 127``), so a comparison with the serving
path measures its activations, its cache and its arithmetic, not the
quantization. That is the one departure from the published code in the
numbers; the others, in form, are noted at their lines.

The model (``config`` is the checkpoint's ``config.json``):

    a = x + N2(Attn(N1(x)))                 input_layernorm, input_layernorm_2
    y = a + N4(MLP(N3(a)))                  post_attention_layernorm, ..._2
    h_0 = Embed(ids); h_{t+1} = Norm_f(L_{n-1}(... L_0(h_t)))
    logits = W_head h_T;  lambda_t = sigmoid(w_g . h_{t+1} + b_g)

``what_if`` names a departure ON PURPOSE, for the tests that must see a
wrong program fail: ``three_passes`` (one pass fewer), ``shared_rows``
(every pass attends to pass 0's keys and values), ``no_post_norms``
(N2 and N4 left out), ``no_pass_norm`` (the final norm once, after the
last pass, not after each).
"""

from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp

WHAT_IFS = ("three_passes", "shared_rows", "no_post_norms", "no_pass_norm")


def as_served(w):
    """HF ``[out, in]`` -> ``[in, out]`` float32, held to int8 per
    output channel."""
    w = jnp.asarray(w, jnp.float32).T
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0, 1e-12)
    return jnp.clip(jnp.round(w / s), -127, 127) * s


def load(model_dir: str | Path) -> tuple[dict, dict]:
    """(config, weights): every tensor of the checkpoint in float32
    under its HF name less ``model.``, the projection matrices and the
    head transposed and held to int8 (the gate's one row is not a
    matrix the program quantizes)."""
    from safetensors import safe_open

    model_dir = Path(model_dir)
    config = json.loads((model_dir / "config.json").read_text())
    out = {}
    for f in sorted(model_dir.glob("*.safetensors")):
        with safe_open(str(f), framework="np") as h:
            for name in h.keys():
                t = jnp.asarray(h.get_tensor(name)).astype(jnp.float32)
                matrix = name.endswith("_proj.weight") or name == "lm_head.weight"
                out[name.removeprefix("model.")] = as_served(t) if matrix else t
    return config, out


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def rotate(x, cos, sin):
    """Rotate-half rotary: ``x [T, H, hd]``, ``cos/sin [T, 1, hd/2]``."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def keys_values(config, w, i, u, cos, sin):
    """This layer's roped keys and values ``[T, KV, hd]`` of normed rows."""
    p = f"layers.{i}.self_attn."
    t = u.shape[0]
    kvh = config.get("num_key_value_heads", config["num_attention_heads"])
    hd = config["head_dim"]
    k = rotate((u @ w[p + "k_proj.weight"]).reshape(t, kvh, hd), cos, sin)
    return k, (u @ w[p + "v_proj.weight"]).reshape(t, kvh, hd)


def attention(config, w, i, u, k, v, cos, sin):
    """Causal softmax attention of normed rows ``u [T, D]`` over keys
    and values ``[T, KV, hd]``, whole sequence at once."""
    p = f"layers.{i}.self_attn."
    t = u.shape[0]
    heads, hd = config["num_attention_heads"], config["head_dim"]
    q = rotate((u @ w[p + "q_proj.weight"]).reshape(t, heads, hd), cos, sin)
    k, v = (jnp.repeat(x, heads // k.shape[1], axis=1) for x in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k) / hd ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return ctx.reshape(t, heads * hd) @ w[p + "o_proj.weight"]


def mlp(w, i, u):
    p = f"layers.{i}.mlp."
    gate = jax.nn.silu(u @ w[p + "gate_proj.weight"])
    return (gate * (u @ w[p + "up_proj.weight"])) @ w[p + "down_proj.weight"]


def forward(config: dict, w: dict, ids, what_if: str | None = None,
            keep_rows: bool = False):
    """``ids [T]`` -> (logits ``[T, vocab]``, lambdas ``[passes, T]``),
    and with ``keep_rows`` also the roped keys ``{(pass, layer): [T, KV,
    hd]}`` every pass wrote (what a cache of the program must hold)."""
    assert what_if is None or what_if in WHAT_IFS, what_if
    eps = config.get("rms_norm_eps", 1e-6)
    hd = config["head_dim"]
    layers, passes = config["num_hidden_layers"], config.get("total_ut_steps", 1)
    if what_if == "three_passes":
        passes -= 1
    t = ids.shape[0]
    inv = 1.0 / config["rope_theta"] ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    post = what_if != "no_post_norms"
    with jax.default_matmul_precision("highest"):
        h = w["embed_tokens.weight"][ids]
        lambdas, rows, first = [], {}, {}
        for step in range(passes):
            for i in range(layers):
                p = f"layers.{i}."
                u = rms_norm(h, w[p + "input_layernorm.weight"], eps)
                k, v = keys_values(config, w, i, u, cos, sin)
                # the published code appends k, v to cache entry
                # i + layers * step and attends over that entry; with no
                # cache a whole sequence attends over its own pass's rows
                first.setdefault(i, (k, v))
                rows[step, i] = k
                if what_if == "shared_rows":
                    k, v = first[i]
                a = attention(config, w, i, u, k, v, cos, sin)
                if post:
                    a = rms_norm(a, w[p + "input_layernorm_2.weight"], eps)
                h = h + a
                u = rms_norm(h, w[p + "post_attention_layernorm.weight"], eps)
                m = mlp(w, i, u)
                if post:
                    m = rms_norm(m, w[p + "post_attention_layernorm_2.weight"], eps)
                h = h + m
            # the published code norms the state at the end of every
            # pass, and the gate is Linear(hidden, 1) on the normed state
            normed = rms_norm(h, w["norm.weight"], eps)
            if what_if != "no_pass_norm":
                h = normed
            lambdas.append(jax.nn.sigmoid(
                normed @ w["early_exit_gate.weight"].reshape(-1)
                + w["early_exit_gate.bias"].reshape(())))
        h = normed  # no_pass_norm: the one norm, before the head
        # the published code mixes the passes' logits by the exit
        # distribution only while training; at inference with
        # early_exit_threshold 1 it returns the last pass's
        logits = h @ w["lm_head.weight"] if "lm_head.weight" in w else (
            h @ w["embed_tokens.weight"].T)
    out = (logits, jnp.stack(lambdas))
    return (*out, rows) if keep_rows else out


def exit_step(lambdas, threshold: float):
    """The pass after which each token would leave: the first ``t``
    whose running sum of ``p_t = lambda_t prod_{j<t} (1 - lambda_j)``
    reaches ``threshold``, the last pass taking the rest. ``lambdas
    [passes, T]`` -> ``[T]`` ints; ``passes - 1`` everywhere at the
    published threshold of 1."""
    passes = lambdas.shape[0]
    before = jnp.concatenate(
        [jnp.ones_like(lambdas[:1]), jnp.cumprod(1.0 - lambdas, axis=0)[:-1]])
    reached = jnp.cumsum(lambdas * before, axis=0)[:-1] >= threshold
    return jnp.where(reached.any(0), jnp.argmax(reached, axis=0), passes - 1)
