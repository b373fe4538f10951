"""VITS / MMS-TTS serving pretrained HF checkpoints — real text-to-speech.

Faithful to transformers' `VitsModel` inference graph (facebook/mms-tts-*
and kakao-enterprise/vits-* checkpoints):

* text encoder: windowed-relative-position attention + conv feed-forward,
  projecting to per-phoneme prior (mean, log-variance);
* duration: either the plain conv predictor or the stochastic one
  (dilated depth-separable convs + rational-quadratic spline flows run
  in reverse);
* length regulation: ceil(exp(log_dur)) repeats of each phoneme prior;
* flow: residual-coupling stack (WaveNet gated convs) inverted to map
  the prior to latents;
* decoder: HiFiGAN (transposed-conv upsampling + multi-kernel residual
  stacks) from latents to the waveform.

Deterministic serving: both noise scales default to the checkpoint
config; parity tests pin them to 0 so torch and JAX agree exactly.
Numeric parity with torch is asserted in tests/test_hf_parity.py.

Reference parity: node-hub/dora-parler serves TTS through torch/CUDA
(dora_parler/main.py:34-60); this is the TPU-native pretrained TTS
path (the self-contained trainable stack lives in models/tts.py).

Shape note: text length and output frame count are data-dependent, so
synthesis runs as three jits (encode, duration, decode) with the
expansion matrix built host-side. Serving uses ``synthesize_bucketed``:
inputs pad to bucket edges with the true length threaded through masked
graphs, so compilation count is bounded by the bucket grid (the TTS
operator in nodehub/ops.py routes through it).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from dora_tpu.models.hf.loader import read_config, read_safetensors


@dataclass(frozen=True)
class VitsConfig:
    vocab: int
    dim: int
    layers: int
    heads: int
    ffn: int
    ffn_kernel: int
    window_size: int
    flow_size: int
    spectrogram_bins: int
    duration_kernel: int
    duration_filters: int
    use_stochastic_duration: bool
    duration_num_flows: int
    duration_flow_bins: int
    duration_tail_bound: float
    depth_separable_layers: int
    depth_separable_channels: int
    prior_num_flows: int
    prior_wavenet_layers: int
    wavenet_kernel: int
    wavenet_dilation: int
    upsample_initial: int
    upsample_rates: tuple[int, ...]
    upsample_kernels: tuple[int, ...]
    resblock_kernels: tuple[int, ...]
    resblock_dilations: tuple[tuple[int, ...], ...]
    leaky_relu_slope: float
    norm_eps: float
    speaking_rate: float
    noise_scale: float
    noise_scale_duration: float
    num_speakers: int
    speaker_embed_size: int
    sampling_rate: int

    @property
    def head_dim(self) -> int:
        return self.dim // self.heads

    @classmethod
    def from_hf(cls, c: dict) -> "VitsConfig":
        return cls(
            vocab=c["vocab_size"],
            dim=c["hidden_size"],
            layers=c["num_hidden_layers"],
            heads=c["num_attention_heads"],
            ffn=c["ffn_dim"],
            ffn_kernel=c.get("ffn_kernel_size", 3),
            window_size=c.get("window_size", 4),
            flow_size=c.get("flow_size", 192),
            spectrogram_bins=c.get("spectrogram_bins", 513),
            duration_kernel=c.get("duration_predictor_kernel_size", 3),
            duration_filters=c.get("duration_predictor_filter_channels", 256),
            use_stochastic_duration=c.get(
                "use_stochastic_duration_prediction", True
            ),
            duration_num_flows=c.get("duration_predictor_num_flows", 4),
            duration_flow_bins=c.get("duration_predictor_flow_bins", 10),
            duration_tail_bound=c.get("duration_predictor_tail_bound", 5.0),
            depth_separable_layers=c.get("depth_separable_num_layers", 3),
            depth_separable_channels=c.get("depth_separable_channels", 2),
            prior_num_flows=c.get("prior_encoder_num_flows", 4),
            prior_wavenet_layers=c.get("prior_encoder_num_wavenet_layers", 4),
            wavenet_kernel=c.get("wavenet_kernel_size", 5),
            wavenet_dilation=c.get("wavenet_dilation_rate", 1),
            upsample_initial=c.get("upsample_initial_channel", 512),
            upsample_rates=tuple(c.get("upsample_rates", [8, 8, 2, 2])),
            upsample_kernels=tuple(c.get("upsample_kernel_sizes", [16, 16, 4, 4])),
            resblock_kernels=tuple(c.get("resblock_kernel_sizes", [3, 7, 11])),
            resblock_dilations=tuple(
                tuple(d) for d in c.get(
                    "resblock_dilation_sizes",
                    [[1, 3, 5], [1, 3, 5], [1, 3, 5]],
                )
            ),
            leaky_relu_slope=c.get("leaky_relu_slope", 0.1),
            norm_eps=c.get("layer_norm_eps", 1e-5),
            speaking_rate=c.get("speaking_rate", 1.0),
            noise_scale=c.get("noise_scale", 0.667),
            noise_scale_duration=c.get("noise_scale_duration", 0.8),
            num_speakers=c.get("num_speakers", 1),
            speaker_embed_size=c.get("speaker_embedding_size", 0),
            sampling_rate=c.get("sampling_rate", 16000),
        )


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------


def load(model_dir: str | Path):
    hf_config = read_config(model_dir)
    cfg = VitsConfig.from_hf(hf_config)
    tensors = read_safetensors(model_dir)
    return cfg, map_params(tensors, cfg)


def _conv_weight(tensors: dict, name: str) -> np.ndarray:
    """Conv weight, materializing torch weight-norm parametrizations
    (``parametrizations.weight.original0/1`` = g, v → g·v/||v||) when the
    checkpoint stores them; plain ``weight`` otherwise."""
    g_name = name + ".parametrizations.weight.original0"
    if g_name in tensors:
        g = tensors[g_name].astype(np.float64)
        v = tensors[name + ".parametrizations.weight.original1"].astype(np.float64)
        norm = np.sqrt((v**2).sum(axis=(1, 2), keepdims=True))
        return (g * v / np.maximum(norm, 1e-12)).astype(np.float32)
    if name + ".weight_g" in tensors:  # legacy weight-norm layout
        g = tensors[name + ".weight_g"].astype(np.float64)
        v = tensors[name + ".weight_v"].astype(np.float64)
        norm = np.sqrt((v**2).sum(axis=(1, 2), keepdims=True))
        return (g * v / np.maximum(norm, 1e-12)).astype(np.float32)
    return tensors[name + ".weight"]


def _conv(tensors: dict, name: str) -> dict:
    out = {"w": _conv_weight(tensors, name)}
    if name + ".bias" in tensors:
        out["b"] = tensors[name + ".bias"]
    return out


def _dds(tensors: dict, prefix: str, n_layers: int) -> dict:
    return {
        str(i): {
            "dilated": _conv(tensors, f"{prefix}.convs_dilated.{i}"),
            "pointwise": _conv(tensors, f"{prefix}.convs_pointwise.{i}"),
            "norm1": tensors[f"{prefix}.norms_1.{i}.weight"],
            "norm1_b": tensors[f"{prefix}.norms_1.{i}.bias"],
            "norm2": tensors[f"{prefix}.norms_2.{i}.weight"],
            "norm2_b": tensors[f"{prefix}.norms_2.{i}.bias"],
        }
        for i in range(n_layers)
    }


def _wavenet(tensors: dict, prefix: str, n_layers: int) -> dict:
    return {
        "in": {
            str(i): _conv(tensors, f"{prefix}.in_layers.{i}")
            for i in range(n_layers)
        },
        "res_skip": {
            str(i): _conv(tensors, f"{prefix}.res_skip_layers.{i}")
            for i in range(n_layers)
        },
    }


def map_params(tensors: dict, cfg: VitsConfig) -> dict:
    params: dict[str, Any] = {
        "embed": tensors["text_encoder.embed_tokens.weight"],
        "project": _conv(tensors, "text_encoder.project"),
        "enc_blocks": {},
    }
    for i in range(cfg.layers):
        lp = f"text_encoder.encoder.layers.{i}."
        params["enc_blocks"][str(i)] = {
            "wq": tensors[lp + "attention.q_proj.weight"].T.copy(),
            "bq": tensors[lp + "attention.q_proj.bias"],
            "wk": tensors[lp + "attention.k_proj.weight"].T.copy(),
            "bk": tensors[lp + "attention.k_proj.bias"],
            "wv": tensors[lp + "attention.v_proj.weight"].T.copy(),
            "bv": tensors[lp + "attention.v_proj.bias"],
            "wo": tensors[lp + "attention.out_proj.weight"].T.copy(),
            "bo": tensors[lp + "attention.out_proj.bias"],
            "rel_k": tensors[lp + "attention.emb_rel_k"][0],
            "rel_v": tensors[lp + "attention.emb_rel_v"][0],
            "ln1": tensors[lp + "layer_norm.weight"],
            "ln1_b": tensors[lp + "layer_norm.bias"],
            "fc1": _conv(tensors, lp + "feed_forward.conv_1"),
            "fc2": _conv(tensors, lp + "feed_forward.conv_2"),
            "ln2": tensors[lp + "final_layer_norm.weight"],
            "ln2_b": tensors[lp + "final_layer_norm.bias"],
        }

    dp = "duration_predictor."
    if cfg.use_stochastic_duration:
        duration: dict[str, Any] = {
            "conv_pre": _conv(tensors, dp + "conv_pre"),
            "conv_proj": _conv(tensors, dp + "conv_proj"),
            "dds": _dds(tensors, dp + "conv_dds", cfg.depth_separable_layers),
            "flows": {},
        }
        # flows.0 is the elementwise affine; 1..N the conv flows.
        duration["flows"]["affine"] = {
            "translate": tensors[dp + "flows.0.translate"],
            "log_scale": tensors[dp + "flows.0.log_scale"],
        }
        for i in range(1, cfg.duration_num_flows + 1):
            fp = f"{dp}flows.{i}."
            duration["flows"][str(i)] = {
                "conv_pre": _conv(tensors, fp + "conv_pre"),
                "dds": _dds(tensors, fp + "conv_dds",
                            cfg.depth_separable_layers),
                "conv_proj": _conv(tensors, fp + "conv_proj"),
            }
    else:
        duration = {
            "conv1": _conv(tensors, dp + "conv_1"),
            "norm1": tensors[dp + "norm_1.weight"],
            "norm1_b": tensors[dp + "norm_1.bias"],
            "conv2": _conv(tensors, dp + "conv_2"),
            "norm2": tensors[dp + "norm_2.weight"],
            "norm2_b": tensors[dp + "norm_2.bias"],
            "proj": _conv(tensors, dp + "proj"),
        }
    params["duration"] = duration

    params["flow"] = {
        str(i): {
            "conv_pre": _conv(tensors, f"flow.flows.{i}.conv_pre"),
            "wavenet": _wavenet(
                tensors, f"flow.flows.{i}.wavenet", cfg.prior_wavenet_layers
            ),
            "conv_post": _conv(tensors, f"flow.flows.{i}.conv_post"),
        }
        for i in range(cfg.prior_num_flows)
    }

    dec = {
        "conv_pre": _conv(tensors, "decoder.conv_pre"),
        "conv_post": _conv(tensors, "decoder.conv_post"),
        "up": {
            str(i): _conv(tensors, f"decoder.upsampler.{i}")
            for i in range(len(cfg.upsample_rates))
        },
        "res": {},
    }
    n_kernels = len(cfg.resblock_kernels)
    for i in range(len(cfg.upsample_rates) * n_kernels):
        rp = f"decoder.resblocks.{i}."
        dec["res"][str(i)] = {
            "convs1": {
                str(j): _conv(tensors, f"{rp}convs1.{j}")
                for j in range(len(cfg.resblock_dilations[i % n_kernels]))
            },
            "convs2": {
                str(j): _conv(tensors, f"{rp}convs2.{j}")
                for j in range(len(cfg.resblock_dilations[i % n_kernels]))
            },
        }
    params["decoder"] = dec
    if "embed_speaker.weight" in tensors:
        params["embed_speaker"] = tensors["embed_speaker.weight"]
    return jax.tree.map(jnp.asarray, params)


# ---------------------------------------------------------------------------
# primitives ([B, C, T] layout, matching the torch graph)
# ---------------------------------------------------------------------------


def conv1d(x, p: dict, *, stride=1, dilation=1, padding=0, groups=1):
    out = jax.lax.conv_general_dilated(
        x, p["w"].astype(x.dtype),
        window_strides=(stride,),
        padding=[(padding, padding)],
        rhs_dilation=(dilation,),
        dimension_numbers=("NCH", "OIH", "NCH"),
        feature_group_count=groups,
    )
    if "b" in p:
        out = out + p["b"].astype(x.dtype)[None, :, None]
    return out


def conv_transpose1d(x, p: dict, *, stride, padding):
    """torch ConvTranspose1d as its fractionally-strided-conv identity:
    input dilated by ``stride``, kernel ([in, out, k]) swapped to
    [out, in, k] and spatially flipped, padding k-1-p each side."""
    w = p["w"].astype(x.dtype)
    k = w.shape[-1]
    w_fwd = jnp.flip(w.transpose(1, 0, 2), axis=-1)
    out = jax.lax.conv_general_dilated(
        x, w_fwd,
        window_strides=(1,),
        padding=[(k - 1 - padding, k - 1 - padding)],
        lhs_dilation=(stride,),
        dimension_numbers=("NCH", "OIH", "NCH"),
    )
    if "b" in p:
        out = out + p["b"].astype(x.dtype)[None, :, None]
    return out


def _length_mask(b: int, t: int, length, dtype):
    """[B, 1, T] {0,1} mask of real positions (< ``length``). ``length``
    is a traced scalar so one compilation serves a whole bucket."""
    idx = jax.lax.broadcasted_iota(jnp.int32, (b, 1, t), 2)
    return (idx < length).astype(dtype)


def _ln_channels(x, w, b, eps):
    """LayerNorm over the channel dim of [B, C, T]."""
    mean = jnp.mean(x, axis=1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=1, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    return x * w[None, :, None] + b[None, :, None]


def _ln_last(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


# ---------------------------------------------------------------------------
# text encoder (windowed relative attention)
# ---------------------------------------------------------------------------


def _relative_embeddings(table, length: int, window: int):
    """[2*window+1, head_dim] -> [2*length-1, head_dim] (pad or slice)."""
    pad = max(length - (window + 1), 0)
    if pad > 0:
        table = jnp.pad(table, ((pad, pad), (0, 0)))
    start = max((window + 1) - length, 0)
    return table[start : start + 2 * length - 1]


def _relative_to_absolute(x):
    """[BH, L, 2L-1] relative logits -> [BH, L, L] absolute."""
    bh, length, _ = x.shape
    x = jnp.pad(x, ((0, 0), (0, 0), (0, 1)))
    x = x.reshape(bh, length * 2 * length)
    x = jnp.pad(x, ((0, 0), (0, length - 1)))
    x = x.reshape(bh, length + 1, 2 * length - 1)
    return x[:, :length, length - 1 :]


def _absolute_to_relative(x):
    """[BH, L, L] -> [BH, L, 2L-1]."""
    bh, length, _ = x.shape
    x = jnp.pad(x, ((0, 0), (0, 0), (0, length - 1)))
    x = x.reshape(bh, length * (2 * length - 1))
    x = jnp.pad(x, ((0, 0), (length, 0)))
    return x.reshape(bh, length, 2 * length)[:, :, 1:]


def _encoder_attention(block, x, cfg: VitsConfig, key_mask=None):
    b, t, _ = x.shape
    h, hd = cfg.heads, cfg.head_dim
    scale = hd**-0.5
    q = (x @ block["wq"] + block["bq"]) * scale
    k = x @ block["wk"] + block["bk"]
    v = x @ block["wv"] + block["bv"]
    q, k, v = (
        z.reshape(b, t, h, hd).transpose(0, 2, 1, 3).reshape(b * h, t, hd)
        for z in (q, k, v)
    )
    weights = q @ k.transpose(0, 2, 1)  # [BH, T, T]
    rel_k = _relative_embeddings(block["rel_k"], t, cfg.window_size)
    weights = weights + _relative_to_absolute(q @ rel_k.T)
    if key_mask is not None:  # [B, 1, T] — bucketed padding never attends
        km = jnp.repeat(key_mask > 0, h, axis=0)  # [BH, 1, T]
        weights = jnp.where(km, weights, jnp.finfo(weights.dtype).min)
    probs = jax.nn.softmax(weights, axis=-1)
    out = probs @ v
    rel_v = _relative_embeddings(block["rel_v"], t, cfg.window_size)
    out = out + _absolute_to_relative(probs) @ rel_v
    out = out.reshape(b, h, t, hd).transpose(0, 2, 1, 3).reshape(b, t, h * hd)
    return out @ block["wo"] + block["bo"]


def _encoder_ffn(block, x, cfg: VitsConfig, mask=None):
    h = x.transpose(0, 2, 1)  # [B, C, T]
    if mask is not None:
        h = h * mask
    pad_l = (cfg.ffn_kernel - 1) // 2
    pad_r = cfg.ffn_kernel // 2
    h = jnp.pad(h, ((0, 0), (0, 0), (pad_l, pad_r)))
    h = jax.nn.relu(conv1d(h, block["fc1"]))
    if mask is not None:  # bias re-fills padding; zero it before fc2 reads
        h = h * mask
    h = jnp.pad(h, ((0, 0), (0, 0), (pad_l, pad_r)))
    h = conv1d(h, block["fc2"])
    return h.transpose(0, 2, 1)


@partial(jax.jit, static_argnums=(1,))
def encode_text(params, cfg: VitsConfig, input_ids, length=None):
    """input_ids [B, T] -> (hidden [B, dim, T], prior_means [B, T, flow],
    prior_log_var [B, T, flow]).

    ``length`` (traced scalar) marks the real prefix of a
    bucket-padded batch: padding is masked out of attention and zeroed
    around every conv, so the real positions compute exactly what an
    unpadded run computes (see synthesize_bucketed).
    """
    b, t = input_ids.shape
    mask = None if length is None else _length_mask(
        b, t, length, params["embed"].dtype
    )
    x = params["embed"][input_ids] * math.sqrt(cfg.dim)  # [B, T, dim]
    if mask is not None:
        x = x * mask.transpose(0, 2, 1)
    for i in range(cfg.layers):
        block = params["enc_blocks"][str(i)]
        x = _ln_last(
            x + _encoder_attention(block, x, cfg, key_mask=mask),
            block["ln1"], block["ln1_b"], cfg.norm_eps,
        )
        x = _ln_last(
            x + _encoder_ffn(block, x, cfg, mask=mask), block["ln2"],
            block["ln2_b"], cfg.norm_eps,
        )
    h = x.transpose(0, 2, 1)
    if mask is not None:
        h = h * mask
    stats = conv1d(h, params["project"]).transpose(0, 2, 1)
    means, log_var = jnp.split(stats, 2, axis=-1)
    return h, means, log_var


# ---------------------------------------------------------------------------
# duration prediction
# ---------------------------------------------------------------------------


def _dds_forward(dds_params, x, cfg: VitsConfig, cond=None, mask=None):
    if cond is not None:
        x = x + cond
    k = cfg.duration_kernel
    for i in range(cfg.depth_separable_layers):
        layer = dds_params[str(i)]
        dilation = k**i
        padding = (k * dilation - dilation) // 2
        if mask is not None:  # keep padding zero under the dilated taps
            x = x * mask
        h = conv1d(x, layer["dilated"], dilation=dilation, padding=padding,
                   groups=cfg.dim)
        h = _ln_channels(h, layer["norm1"], layer["norm1_b"], cfg.norm_eps)
        h = jax.nn.gelu(h, approximate=False)
        h = conv1d(h, layer["pointwise"])
        h = _ln_channels(h, layer["norm2"], layer["norm2_b"], cfg.norm_eps)
        h = jax.nn.gelu(h, approximate=False)
        x = x + h
    if mask is not None:
        x = x * mask
    return x


def _spline_inverse(inputs, uw, uh, ud, cfg: VitsConfig):
    """Inverse unconstrained rational-quadratic spline (the torch
    `_unconstrained_rational_quadratic_spline` with reverse=True),
    vectorized over [B, C, T]."""
    bound = cfg.duration_tail_bound
    n_bins = cfg.duration_flow_bins
    min_w = min_h = min_d = 1e-3
    constant = math.log(math.exp(1 - min_d) - 1)
    ud = jnp.pad(ud, ((0, 0), (0, 0), (0, 0), (1, 1)),
                 constant_values=constant)

    inside = (inputs >= -bound) & (inputs <= bound)
    # Clamp so the spline math stays finite for outside entries (masked
    # back to identity at the end).
    x = jnp.clip(inputs, -bound, bound)

    widths = jax.nn.softmax(uw, axis=-1)
    widths = min_w + (1 - min_w * n_bins) * widths
    cumw = jnp.cumsum(widths, axis=-1)
    cumw = jnp.pad(cumw, ((0, 0), (0, 0), (0, 0), (1, 0)))
    cumw = 2 * bound * cumw - bound
    cumw = cumw.at[..., 0].set(-bound).at[..., -1].set(bound)
    widths = cumw[..., 1:] - cumw[..., :-1]

    derivs = min_d + jax.nn.softplus(ud)

    heights = jax.nn.softmax(uh, axis=-1)
    heights = min_h + (1 - min_h * n_bins) * heights
    cumh = jnp.cumsum(heights, axis=-1)
    cumh = jnp.pad(cumh, ((0, 0), (0, 0), (0, 0), (1, 0)))
    cumh = 2 * bound * cumh - bound
    cumh = cumh.at[..., 0].set(-bound).at[..., -1].set(bound)
    heights = cumh[..., 1:] - cumh[..., :-1]

    locations = cumh.at[..., -1].add(1e-6)  # reverse: bin by heights
    bin_idx = jnp.sum((x[..., None] >= locations).astype(jnp.int32), axis=-1) - 1
    bin_idx = jnp.clip(bin_idx, 0, n_bins - 1)[..., None]

    def take(a):
        return jnp.take_along_axis(a, bin_idx, axis=-1)[..., 0]

    in_cumw = take(cumw[..., :-1])
    in_w = take(widths)
    in_cumh = take(cumh[..., :-1])
    delta = heights / widths
    in_delta = take(delta)
    in_d = take(derivs[..., :-1])
    in_d1 = take(derivs[..., 1:])
    in_h = take(heights)

    inter1 = in_d + in_d1 - 2 * in_delta
    inter2 = x - in_cumh
    inter3 = inter2 * inter1
    a = in_h * (in_delta - in_d) + inter3
    b = in_h * in_d - inter3
    c = -in_delta * inter2
    disc = b**2 - 4 * a * c
    root = (2 * c) / (-b - jnp.sqrt(jnp.maximum(disc, 0.0)))
    out = root * in_w + in_cumw
    return jnp.where(inside, out, inputs)


def _conv_flow_reverse(flow, x, cfg: VitsConfig, cond, mask=None):
    half = cfg.depth_separable_channels // 2
    first, second = x[:, :half], x[:, half:]
    h = conv1d(first, flow["conv_pre"])
    h = _dds_forward(flow["dds"], h, cfg, cond=cond, mask=mask)
    h = conv1d(h, flow["conv_proj"])
    b, _, t = first.shape
    h = h.reshape(b, half, -1, t).transpose(0, 1, 3, 2)  # [B, half, T, 3bins-1]
    n_bins = cfg.duration_flow_bins
    scale = math.sqrt(cfg.dim)
    uw = h[..., :n_bins] / scale
    uh = h[..., n_bins : 2 * n_bins] / scale
    ud = h[..., 2 * n_bins :]
    second = _spline_inverse(second, uw, uh, ud, cfg)
    return jnp.concatenate([first, second], axis=1)


@partial(jax.jit, static_argnums=(1,), static_argnames=("noise_scale",))
def predict_log_duration(params, cfg: VitsConfig, hidden, noise_scale=None,
                         length=None):
    """hidden [B, dim, T] -> log durations [B, 1, T]. ``length`` masks a
    bucket-padded batch (see encode_text); padded positions are
    meaningless — the caller slices to the real prefix."""
    dp = params["duration"]
    b, _, t = hidden.shape
    mask = None if length is None else _length_mask(
        b, t, length, hidden.dtype
    )
    if not cfg.use_stochastic_duration:
        k = cfg.duration_kernel
        h = conv1d(hidden, dp["conv1"], padding=k // 2)
        h = jax.nn.relu(h)
        h = _ln_channels(h, dp["norm1"], dp["norm1_b"], cfg.norm_eps)
        if mask is not None:
            h = h * mask
        h = conv1d(h, dp["conv2"], padding=k // 2)
        h = jax.nn.relu(h)
        h = _ln_channels(h, dp["norm2"], dp["norm2_b"], cfg.norm_eps)
        return conv1d(h, dp["proj"])

    if noise_scale is None:
        noise_scale = cfg.noise_scale_duration
    h = conv1d(hidden, dp["conv_pre"])
    h = _dds_forward(dp["dds"], h, cfg, mask=mask)
    cond = conv1d(h, dp["conv_proj"])

    b, _, t = hidden.shape
    # Deterministic serving: zeros scaled by noise_scale (the torch graph
    # draws randn * noise_scale; parity tests pin noise_scale=0).
    latents = jnp.zeros((b, cfg.depth_separable_channels, t), hidden.dtype)
    latents = latents * noise_scale
    # torch runs reversed(flows) minus the "useless vflow": conv flows
    # N..2, then the elementwise affine — each preceded by a channel
    # flip (modeling_vits.py:798-805).
    order = [str(i) for i in range(cfg.duration_num_flows, 1, -1)]
    order.append("affine")
    affine = dp["flows"]["affine"]
    for name in order:
        latents = jnp.flip(latents, axis=1)
        if name == "affine":
            latents = (latents - affine["translate"]) * jnp.exp(
                -affine["log_scale"]
            )
        else:
            latents = _conv_flow_reverse(
                dp["flows"][name], latents, cfg, cond, mask=mask
            )
    return latents[:, :1]


# ---------------------------------------------------------------------------
# flow + decoder
# ---------------------------------------------------------------------------


def _wavenet_forward(wn, x, cfg: VitsConfig, mask=None):
    outputs = jnp.zeros_like(x)
    half = cfg.dim
    for i in range(cfg.prior_wavenet_layers):
        dilation = cfg.wavenet_dilation**i
        padding = (cfg.wavenet_kernel * dilation - dilation) // 2
        if mask is not None:  # residual carries conv bias into padding
            x = x * mask
        h = conv1d(x, wn["in"][str(i)], dilation=dilation, padding=padding)
        t_act = jnp.tanh(h[:, :half])
        s_act = jax.nn.sigmoid(h[:, half:])
        acts = t_act * s_act
        res_skip = conv1d(acts, wn["res_skip"][str(i)])
        if i < cfg.prior_wavenet_layers - 1:
            x = x + res_skip[:, :half]
            outputs = outputs + res_skip[:, half:]
        else:
            outputs = outputs + res_skip
    return outputs


@partial(jax.jit, static_argnums=(1,))
def flow_inverse(params, cfg: VitsConfig, latents, length=None):
    """Residual-coupling stack in reverse: prior latents -> decoder
    latents. latents [B, flow_size, T]; ``length`` masks a frame-bucket
    padded batch (real prefix computes exactly the unpadded result)."""
    half = cfg.flow_size // 2
    b, _, t = latents.shape
    mask = None if length is None else _length_mask(
        b, t, length, latents.dtype
    )
    x = latents
    for i in reversed(range(cfg.prior_num_flows)):
        x = jnp.flip(x, axis=1)
        flow = params["flow"][str(i)]
        first, second = x[:, :half], x[:, half:]
        h = conv1d(first, flow["conv_pre"])
        h = _wavenet_forward(flow["wavenet"], h, cfg, mask=mask)
        mean = conv1d(h, flow["conv_post"])
        second = second - mean
        x = jnp.concatenate([first, second], axis=1)
        if mask is not None:
            x = x * mask
    return x


@partial(jax.jit, static_argnums=(1,))
def hifigan(params, cfg: VitsConfig, latents, length=None):
    """latents [B, flow_size, T] -> waveform [B, samples]. ``length``
    (frames) masks a frame-bucket padded batch at every stage — the
    mask upsamples with the signal, so no padded activation ever leaks
    into a real sample's conv window."""
    dec = params["decoder"]
    slope = cfg.leaky_relu_slope
    b, _, t = latents.shape
    cur_len = length
    mask = None if length is None else _length_mask(
        b, t, cur_len, latents.dtype
    )
    h = conv1d(latents, dec["conv_pre"], padding=3)
    n_kernels = len(cfg.resblock_kernels)
    for i, (rate, kernel) in enumerate(
        zip(cfg.upsample_rates, cfg.upsample_kernels)
    ):
        if mask is not None:
            h = h * mask
        h = jax.nn.leaky_relu(h, slope)
        h = conv_transpose1d(
            h, dec["up"][str(i)], stride=rate, padding=(kernel - rate) // 2
        )
        if mask is not None:
            cur_len = cur_len * rate
            mask = _length_mask(b, h.shape[-1], cur_len, h.dtype)
            h = h * mask
        acc = None
        for j in range(n_kernels):
            rb = dec["res"][str(i * n_kernels + j)]
            k = cfg.resblock_kernels[j]
            r = h
            for d_idx, dilation in enumerate(cfg.resblock_dilations[j]):
                s = jax.nn.leaky_relu(r, slope)
                s = conv1d(
                    s, rb["convs1"][str(d_idx)], dilation=dilation,
                    padding=(k * dilation - dilation) // 2,
                )
                if mask is not None:
                    s = s * mask
                s = jax.nn.leaky_relu(s, slope)
                s = conv1d(s, rb["convs2"][str(d_idx)], padding=(k - 1) // 2)
                if mask is not None:
                    s = s * mask
                r = r + s
            acc = r if acc is None else acc + r
        h = acc / n_kernels
    h = jax.nn.leaky_relu(h)  # torch default slope 0.01 here
    h = conv1d(h, dec["conv_post"], padding=3)
    return jnp.tanh(h)[:, 0]


# ---------------------------------------------------------------------------
# synthesis
# ---------------------------------------------------------------------------


def synthesize(params, cfg: VitsConfig, input_ids, noise_scale=None,
               noise_scale_duration=None, speaking_rate=None):
    """input_ids [B, T] (B=1) -> waveform [B, samples] float32.

    Host-orchestrated: encode + duration jits produce durations, the
    phoneme→frame expansion is built host-side (data-dependent length),
    then flow+HiFiGAN jits decode. ``noise_scale=0`` makes the output
    deterministic (the parity-test configuration)."""
    if noise_scale is None:
        noise_scale = cfg.noise_scale
    if speaking_rate is None:
        speaking_rate = cfg.speaking_rate
    hidden, means, log_var = encode_text(params, cfg, jnp.asarray(input_ids))
    log_dur = predict_log_duration(
        params, cfg, hidden, noise_scale=noise_scale_duration
    )
    duration = np.ceil(np.exp(np.asarray(log_dur[:, 0])) / speaking_rate)
    repeats = duration.astype(np.int64)  # [B, T]

    waveforms = []
    rng = np.random.default_rng()
    for b in range(input_ids.shape[0]):
        prior_mean = np.repeat(np.asarray(means[b]), repeats[b], axis=0)
        prior_logv = np.repeat(np.asarray(log_var[b]), repeats[b], axis=0)
        latents = prior_mean
        if noise_scale:
            latents = prior_mean + rng.standard_normal(
                prior_mean.shape
            ).astype(prior_mean.dtype) * np.exp(prior_logv) * noise_scale
        z = flow_inverse(
            params, cfg, jnp.asarray(latents.T[None])
        )
        waveforms.append(np.asarray(hifigan(params, cfg, z)[0]))
    max_len = max(w.shape[0] for w in waveforms)
    out = np.zeros((len(waveforms), max_len), np.float32)
    for b, w in enumerate(waveforms):
        out[b, : w.shape[0]] = w
    return out


# ---------------------------------------------------------------------------
# bucketed synthesis (bounded recompiles)
# ---------------------------------------------------------------------------

#: Default serving buckets. Text lengths and frame counts are padded up
#: to the nearest edge, so the four jits compile at most once per edge
#: ever used instead of once per distinct input length (a TTS node fed
#: varying sentences would otherwise recompile on nearly every tick).
TEXT_BUCKETS = (32, 64, 128, 256, 512)
FRAME_BUCKETS = (128, 256, 512, 1024, 2048, 4096)


def _bucket(n: int, buckets) -> int:
    for edge in buckets:
        if n <= edge:
            return edge
    last = buckets[-1]
    return (n + last - 1) // last * last  # oversize: multiples of the top


def upsample_factor(cfg: VitsConfig) -> int:
    f = 1
    for r in cfg.upsample_rates:
        f *= r
    return f


@partial(jax.jit, static_argnums=(1,), static_argnames=("noise_scale_duration",))
def _duration_stage(params, cfg: VitsConfig, padded_ids, length,
                    speaking_rate, noise_scale_duration=None):
    """Stage 1 of bucketed synthesis, entirely on device: text encode +
    duration prediction + the token→frame cumulative map. Nothing is
    fetched — the caller pulls ONE scalar (total frames) to pick the
    frame bucket. Returns (frames, cum [TB] int32, means [TB, C],
    log_var [TB, C])."""
    hidden, means, log_var = encode_text(
        params, cfg, padded_ids, length=length
    )
    log_dur = predict_log_duration(
        params, cfg, hidden, noise_scale=noise_scale_duration, length=length
    )
    tb = padded_ids.shape[1]
    live = jnp.arange(tb) < length
    dur = jnp.where(
        live, jnp.ceil(jnp.exp(log_dur[0, 0]) / speaking_rate), 0
    ).astype(jnp.int32)
    cum = jnp.cumsum(dur)
    return cum[-1], cum, means[0], log_var[0]


@partial(jax.jit, static_argnums=(1,), static_argnames=("fb",))
def _render_stage(params, cfg: VitsConfig, cum, means, log_var, frames,
                  key, noise_scale, *, fb: int):
    """Stage 2, entirely on device: the frame-alignment gather (the
    np.repeat of the host-orchestrated path becomes a searchsorted-style
    comparison gather), prior sampling, flow inverse and HiFiGAN. The
    caller fetches only the waveform."""
    tb = cum.shape[0]
    j = jnp.arange(fb)
    # frame j belongs to the token k with cum[k-1] <= j < cum[k]:
    # count how many cumulative edges are <= j.
    idx = jnp.clip(jnp.sum(cum[None, :] <= j[:, None], axis=1), 0, tb - 1)
    live = (j < frames)[:, None]
    pm = jnp.where(live, means[idx], 0.0)
    noise = jax.random.normal(key, pm.shape, pm.dtype)
    latents = pm + noise * jnp.exp(log_var[idx]) * noise_scale
    latents = jnp.where(live, latents, 0.0)
    z = flow_inverse(params, cfg, latents.T[None], length=frames)
    return hifigan(params, cfg, z, length=frames)


def synthesize_bucketed(params, cfg: VitsConfig, input_ids,
                        noise_scale=None, noise_scale_duration=None,
                        speaking_rate=None, text_buckets=TEXT_BUCKETS,
                        frame_buckets=FRAME_BUCKETS, key=None):
    """Bucket-padded :func:`synthesize` (B=1): pads text to a bucket
    edge and frames to a frame bucket, threading the real lengths
    through the masked graphs — compilation count is bounded by the
    bucket grid while the real-prefix output matches the unpadded run
    to float tolerance (asserted in tests/test_hf_parity.py).

    Round 5: the whole synthesis is TWO host round trips — stage 1 stays
    on device and only the total-frame scalar is fetched (it picks the
    static frame bucket), stage 2 does the alignment gather on device
    and only the waveform is fetched. The round-4 path paid ~5
    transfers (durations, means, log_var down; latents up; wav down),
    which dominated warm per-sentence latency. Returns (waveform [1, samples], sliced to
    the true length)."""
    if noise_scale is None:
        noise_scale = cfg.noise_scale
    if speaking_rate is None:
        speaking_rate = cfg.speaking_rate
    ids = np.asarray(input_ids)
    assert ids.shape[0] == 1, "bucketed synthesis is batch-1 serving"
    t = ids.shape[1]
    tb = _bucket(t, text_buckets)
    padded = np.zeros((1, tb), ids.dtype)
    padded[0, :t] = ids[0]
    frames_dev, cum, means0, logv0 = _duration_stage(
        params, cfg, jnp.asarray(padded), jnp.asarray(t, jnp.int32),
        jnp.asarray(speaking_rate, jnp.float32),
        noise_scale_duration=noise_scale_duration,
    )
    frames = int(frames_dev)  # round trip 1: one scalar
    fb = _bucket(frames, frame_buckets)
    if key is None:
        key = jax.random.PRNGKey(np.random.default_rng().integers(2**31))
    wav = _render_stage(
        params, cfg, cum, means0, logv0, frames_dev, key,
        jnp.asarray(noise_scale, jnp.float32), fb=fb,
    )
    # round trip 2: the waveform itself
    return np.asarray(wav[:, : frames * upsample_factor(cfg)])
