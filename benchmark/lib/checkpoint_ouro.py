"""Seeded random checkpoint of an ``ouro`` configuration (a stack of
sandwich-norm layers run ``total_ut_steps`` times, an exit gate): bf16
safetensors under the HF tensor names, one file a layer and one for the
two ends, ``config.json`` as the configuration file's top level stands,
and the synthetic tokenizer of ``lib/checkpoint.py``.

Matrices, the gate's row among them: uniform with standard deviation
0.02 (the family's ``initializer_range``; the catalog row drops the
key). The four norms of a layer and the final norm: 1. The gate's bias:
0.
"""

from __future__ import annotations

import json
import os
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

from checkpoint import _bf16, _levels, _save_safetensors, token_code

INITIALIZER_RANGE = 0.02
LAYER_NORMS = ("input_layernorm", "input_layernorm_2",
               "post_attention_layernorm", "post_attention_layernorm_2")


def layer_shapes(config: dict, i: int) -> dict[str, tuple]:
    """HF tensor names of layer ``i`` -> shapes (matrices ``[out, in]``)."""
    d, ffn, hd = config["hidden_size"], config["intermediate_size"], config["head_dim"]
    q, kv = config["num_attention_heads"] * hd, config["num_key_value_heads"] * hd
    p = f"model.layers.{i}."
    return {
        **{p + name + ".weight": (d,) for name in LAYER_NORMS},
        p + "self_attn.q_proj.weight": (q, d),
        p + "self_attn.k_proj.weight": (kv, d),
        p + "self_attn.v_proj.weight": (kv, d),
        p + "self_attn.o_proj.weight": (d, q),
        p + "mlp.gate_proj.weight": (ffn, d),
        p + "mlp.up_proj.weight": (ffn, d),
        p + "mlp.down_proj.weight": (d, ffn),
    }


def end_shapes(config: dict) -> dict[str, tuple]:
    d, vocab = config["hidden_size"], config["vocab_size"]
    return {
        "model.embed_tokens.weight": (vocab, d),
        "model.norm.weight": (d,),
        "model.early_exit_gate.weight": (1, d),
        "model.early_exit_gate.bias": (1,),
        "lm_head.weight": (vocab, d),
    }


def write_checkpoint(path: Path, config: dict, seed: int) -> dict:
    """Everything drawn from ``seed`` (a stream a file). Returns the
    seconds the drawing and the writing took."""
    import ml_dtypes
    import numpy as np

    t0 = time.perf_counter()
    path.mkdir(parents=True, exist_ok=True)
    layers = config["num_hidden_layers"]
    levels = _levels(INITIALIZER_RANGE * 3 ** 0.5)

    def draw(rng, shape, name):
        if name.endswith("early_exit_gate.bias"):
            return _bf16(np.zeros(shape, np.float32))
        if len(shape) == 1:  # norms
            return _bf16(np.ones(shape, np.float32))
        picks = rng.integers(0, 65536, size=shape, dtype=np.uint16)
        return levels[picks].view(ml_dtypes.bfloat16)

    seqs = np.random.SeedSequence(seed).spawn(layers + 1)
    files = [
        (f"model-{i:05d}.safetensors", layer_shapes(config, i), seqs[i])
        for i in range(layers)
    ]
    files.append(("model-ends.safetensors", end_shapes(config), seqs[layers]))

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
        "metadata": {},
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
            "vocab": {token_code(i): i for i in range(config["vocab_size"])},
        },
    }))
    return {"total_s": time.perf_counter() - t0, "write_thread_s": wrote,
            "files": len(files)}
