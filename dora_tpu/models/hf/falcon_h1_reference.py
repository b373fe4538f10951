"""The plain reference of the Falcon-H1 block: float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``, every multiplier of the
published ``config.json`` written where the forward pass applies it,
whole-sequence causal attention, and the state-space recurrence as a
TOKEN-BY-TOKEN ``lax.scan`` (not the chunked form the program's prefill
uses). No cache, no paging, no batching, no kernels, and no code shared
with ``falcon_h1.py``: it reads the checkpoint's tensors under their HF
names itself.

Every matrix is held to the program's int8 weights alone (symmetric,
per output channel, ``max|w| / 127``, of the UNSCALED matrix: the
program folds the multipliers into the scales afterwards), so a
comparison with the serving path measures its activations, its caches
and its arithmetic, not the quantization.

The layer (``config`` is the checkpoint's ``config.json``):

    u = RMSNorm(x; input_layernorm)
    x = x + ssm_out_multiplier * Mixer(u)
          + attention_out_multiplier * Attn(u * attention_in_multiplier)
    x = x + MLP(RMSNorm(x; pre_ff_layernorm))
"""

from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp


def as_served(w):
    """HF ``[out, in]`` -> ``[in, out]`` float32, held to int8 per
    output channel."""
    w = jnp.asarray(w, jnp.float32).T
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0, 1e-12)
    return jnp.clip(jnp.round(w / s), -127, 127) * s


def load(model_dir: str | Path) -> tuple[dict, dict]:
    """(config, weights): every tensor of the checkpoint in float32,
    the matrices transposed and held to int8."""
    from safetensors import safe_open

    model_dir = Path(model_dir)
    config = json.loads((model_dir / "config.json").read_text())
    tensors = {}
    for f in sorted(model_dir.glob("*.safetensors")):
        with safe_open(str(f), framework="np") as h:
            for name in h.keys():
                tensors[name] = jnp.asarray(h.get_tensor(name)).astype(jnp.float32)
    out = {}
    for name, t in tensors.items():
        matrix = t.ndim == 2 and "embed_tokens" not in name
        out[name.removeprefix("model.")] = as_served(t) if matrix else t
    return config, out


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def rotate_half(x, cos, sin):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(w, config, p: str, a):
    """``a [T, dim]`` (normed, times ``attention_in_multiplier``)."""
    t = a.shape[0]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["head_dim"]
    q = (a @ w[p + "q_proj.weight"]).reshape(t, heads, hd)
    k = (a @ w[p + "k_proj.weight"]).reshape(t, kv, hd) * config["key_multiplier"]
    v = (a @ w[p + "v_proj.weight"]).reshape(t, kv, hd)
    inv = 1.0 / config["rope_theta"] ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    q, k = rotate_half(q, cos, sin), rotate_half(k, cos, sin)
    k, v = (jnp.repeat(x, heads // kv, axis=1) for x in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k) / hd ** 0.5
    seen = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    pr = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", pr, v).reshape(t, heads * hd) @ w[p + "o_proj.weight"]


def mixer(w, config, p: str, u, drop: str | None = None):
    """``u [T, dim]`` (normed). Returns (out [T, dim], the SSM state
    after the last token [H, P, N], the last ``d_conv - 1`` rows of the
    convolution's input [d_conv-1, conv_dim]). ``drop`` leaves a piece
    out, for the what-if figures: ``"conv_tail"`` (each token convolved
    alone, as if no earlier rows existed) or ``"D"``."""
    t = u.shape[0]
    h, hd = config["mamba_n_heads"], config["mamba_d_head"]
    g, n, taps = config["mamba_n_groups"], config["mamba_d_state"], config["mamba_d_conv"]
    d_ssm = config["mamba_d_ssm"]
    m = config["ssm_multipliers"]
    mup = jnp.concatenate([
        jnp.full((d_ssm,), m[0]), jnp.full((d_ssm,), m[1]),
        jnp.full((g * n,), m[2]), jnp.full((g * n,), m[3]), jnp.full((h,), m[4]),
    ]).astype(jnp.float32)
    proj = ((u * config["ssm_in_multiplier"]) @ w[p + "in_proj.weight"]) * mup
    z, xbc, dt = proj[:, :d_ssm], proj[:, d_ssm : 2 * d_ssm + 2 * g * n], proj[:, -h:]
    kernel = w[p + "conv1d.weight"][:, 0, :]  # [conv_dim, taps]
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1])), xbc], 0)
    if drop == "conv_tail":
        conv = xbc * kernel[:, -1]
    else:
        conv = sum(padded[k : k + t] * kernel[:, k] for k in range(taps))
    conv = jax.nn.silu(conv + w[p + "conv1d.bias"])
    x = conv[:, :d_ssm].reshape(t, h, hd)
    bm = jnp.repeat(conv[:, d_ssm : d_ssm + g * n].reshape(t, g, n), h // g, axis=1)
    cm = jnp.repeat(conv[:, d_ssm + g * n :].reshape(t, g, n), h // g, axis=1)
    dt = jax.nn.softplus(dt + w[p + "dt_bias"])
    a = -jnp.exp(w[p + "A_log"])
    skip = w[p + "D"] * (0.0 if drop == "D" else 1.0)

    def token(s, inp):
        x_t, b_t, c_t, dt_t = inp  # [H, P], [H, N], [H, N], [H]
        s = jnp.exp(dt_t * a)[:, None, None] * s + (
            dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_t) + skip[:, None] * x_t

    s, y = jax.lax.scan(token, jnp.zeros((h, hd, n)), (x, bm, cm, dt))
    y = y.reshape(t, d_ssm) * jax.nn.silu(z)
    y = y.reshape(t, g, d_ssm // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + config["rms_norm_eps"])
    y = y.reshape(t, d_ssm) * w[p + "norm.weight"]
    return y @ w[p + "out_proj.weight"], s, padded[t:]


def mlp(w, config, p: str, v):
    m0, m1 = config["mlp_multipliers"]
    gate = jax.nn.silu((v @ w[p + "gate_proj.weight"]) * m0)
    return ((gate * (v @ w[p + "up_proj.weight"])) @ w[p + "down_proj.weight"]) * m1


def layer(w, config, i: int, x, drop: str | None = None):
    """-> (the layer's output [T, dim], its SSM state and convolution
    tail after the last token). ``drop`` as :func:`mixer`'s, or
    ``"mixer"``: the whole branch left out."""
    p = f"layers.{i}."
    eps = config["rms_norm_eps"]
    u = rms_norm(x, w[p + "input_layernorm.weight"], eps)
    m, s, tail = mixer(w, config, p + "mamba.", u, drop)
    att = attention(w, config, p + "self_attn.", u * config["attention_in_multiplier"])
    x = x + config["attention_out_multiplier"] * att
    if drop != "mixer":
        x = x + config["ssm_out_multiplier"] * m
    x = x + mlp(w, config, p + "feed_forward.",
                rms_norm(x, w[p + "pre_ff_layernorm.weight"], eps))
    return x, s, tail


def forward(w, config, tokens, drop: str | None = None):
    """tokens ``[T]`` -> (logits ``[T, vocab]`` float32, per layer the
    SSM state ``[H, P, N]`` and the convolution tail after token T)."""
    with jax.default_matmul_precision("highest"):
        x = w["embed_tokens.weight"][tokens] * config["embedding_multiplier"]
        states = []
        for i in range(config["num_hidden_layers"]):
            x, s, tail = layer(w, config, i, x, drop)
            states.append((s, tail))
        x = rms_norm(x, w["final_layernorm.weight"], config["rms_norm_eps"])
        return (x @ w["lm_head.weight"]) * config["lm_head_multiplier"], states
