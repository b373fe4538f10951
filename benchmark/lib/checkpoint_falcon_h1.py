"""Seeded random checkpoint of a ``falcon_h1`` configuration (a Mamba-2
mixer beside attention in every layer): bf16 safetensors under the HF
tensor names, one file a layer and one for the two ends, ``config.json``
as the configuration file's top level stands, and the synthetic
tokenizer of ``lib/checkpoint.py``.

Matrices: uniform with standard deviation 0.02. Norms 1. The mixer's own
parameters in the ranges Mamba-2 initialises them, so that a head's
decay ``exp(dt A)`` is neither 0 nor 1 and the recurrent state holds a
mix of fast and slow heads: ``A`` uniform in 1..16, ``dt`` log-uniform
in 0.001..0.1 (``dt_bias`` its inverse softplus), ``D`` = 1; the
convolution's 4 taps and its bias uniform in +-0.5 (PyTorch's default).
"""

from __future__ import annotations

import json
import os
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

from checkpoint import _bf16, _levels, _save_safetensors, token_code

INITIALIZER_RANGE = 0.02
CONV_AMPLITUDE = 0.5
A_RANGE, DT_RANGE = (1.0, 16.0), (0.001, 0.1)


def widths(config: dict) -> dict:
    heads = config["mamba_n_heads"]
    d_ssm = config["mamba_d_ssm"]
    conv = d_ssm + 2 * config["mamba_n_groups"] * config["mamba_d_state"]
    return {"heads": heads, "d_ssm": d_ssm, "conv": conv, "in": d_ssm + conv + heads,
            "q": config["num_attention_heads"] * config["head_dim"],
            "kv": config["num_key_value_heads"] * config["head_dim"]}


def layer_shapes(config: dict, i: int) -> dict[str, tuple]:
    """HF tensor names of layer ``i`` -> shapes (matrices ``[out, in]``)."""
    d, ffn, w = config["hidden_size"], config["intermediate_size"], widths(config)
    p = f"model.layers.{i}."
    return {
        p + "input_layernorm.weight": (d,),
        p + "pre_ff_layernorm.weight": (d,),
        p + "self_attn.q_proj.weight": (w["q"], d),
        p + "self_attn.k_proj.weight": (w["kv"], d),
        p + "self_attn.v_proj.weight": (w["kv"], d),
        p + "self_attn.o_proj.weight": (d, w["q"]),
        p + "mamba.in_proj.weight": (w["in"], d),
        p + "mamba.conv1d.weight": (w["conv"], 1, config["mamba_d_conv"]),
        p + "mamba.conv1d.bias": (w["conv"],),
        p + "mamba.dt_bias": (w["heads"],),
        p + "mamba.A_log": (w["heads"],),
        p + "mamba.D": (w["heads"],),
        p + "mamba.norm.weight": (w["d_ssm"],),
        p + "mamba.out_proj.weight": (d, w["d_ssm"]),
        p + "feed_forward.gate_proj.weight": (ffn, d),
        p + "feed_forward.up_proj.weight": (ffn, d),
        p + "feed_forward.down_proj.weight": (d, ffn),
    }


def write_checkpoint(path: Path, config: dict, seed: int) -> dict:
    """Everything drawn from ``seed`` (a stream a file). Returns the
    seconds the drawing and the writing took."""
    import ml_dtypes
    import numpy as np

    t0 = time.perf_counter()
    path.mkdir(parents=True, exist_ok=True)
    layers = config["num_hidden_layers"]
    d, vocab = config["hidden_size"], config["vocab_size"]
    levels = _levels(INITIALIZER_RANGE * 3 ** 0.5)
    conv_levels = _levels(CONV_AMPLITUDE)

    def draw(rng, shape, name):
        tail = name.rsplit(".", 2)[-2:]
        if tail == ["mamba", "A_log"]:
            return _bf16(np.log(rng.uniform(*A_RANGE, shape)).astype(np.float32))
        if tail == ["mamba", "dt_bias"]:
            dt = np.exp(rng.uniform(np.log(DT_RANGE[0]), np.log(DT_RANGE[1]), shape))
            return _bf16((dt + np.log(-np.expm1(-dt))).astype(np.float32))
        if tail[0] == "conv1d":
            picks = rng.integers(0, 65536, size=shape, dtype=np.uint16)
            return conv_levels[picks].view(ml_dtypes.bfloat16)
        if len(shape) == 1:  # norms and D
            return _bf16(np.ones(shape, np.float32))
        picks = rng.integers(0, 65536, size=shape, dtype=np.uint16)
        return levels[picks].view(ml_dtypes.bfloat16)

    seqs = np.random.SeedSequence(seed).spawn(layers + 1)
    files = [
        (f"model-{i:05d}.safetensors", layer_shapes(config, i), seqs[i])
        for i in range(layers)
    ]
    files.append(("model-ends.safetensors", {
        "model.embed_tokens.weight": (vocab, d),
        "model.final_layernorm.weight": (d,),
        "lm_head.weight": (vocab, d),
    }, seqs[layers]))

    def one(job) -> float:
        name, shapes, seq = job
        rng = np.random.default_rng(seq)
        tensors = {n: draw(rng, s, n) for n, s in shapes.items()}
        t = time.perf_counter()
        _save_safetensors(tensors, path / name)
        return time.perf_counter() - t

    with ThreadPoolExecutor(max_workers=min(len(files), os.cpu_count() or 1)) as pool:
        wrote = sum(pool.map(one, files))
    (path / "model.safetensors.index.json").write_text(json.dumps({
        "metadata": {"stage": 0},
        "weight_map": {n: name for name, shapes, _ in files for n in shapes},
    }))
    (path / "config.json").write_text(json.dumps(config, indent=1))
    (path / "tokenizer.json").write_text(json.dumps({
        "version": "1.0",
        "added_tokens": [],
        "pre_tokenizer": {
            "type": "Split", "pattern": {"Regex": "[0-9A-Za-z]{3}"},
            "behavior": "Isolated", "invert": False,
        },
        "model": {
            "type": "BPE", "ignore_merges": True, "merges": [],
            "vocab": {token_code(i): i for i in range(vocab)},
        },
    }))
    return {"total_s": time.perf_counter() - t0, "write_thread_s": wrote,
            "files": len(files)}
