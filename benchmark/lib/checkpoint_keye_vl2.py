"""Seeded random rank-0 checkpoint of a ``KeyeVL2`` (Keye-VL-2.0's
language model) configuration: bf16 safetensors under the HF tensor names
(Qwen3-MoE's for attention, norms and the expert layer, DeepSeek-V3.2's
for the indexer with ``wq`` in place of ``wq_b``: an assumption,
``assumed.tensor_names``), one file a layer, the experts of ONE rank only
(a shard checkpoint, as a launcher of an expert group would hand each
chip), ``config.json`` and the synthetic tokenizer of ``lib/checkpoint.py``.

``config`` is the benchmark's configuration file's top level, where
``num_experts`` counts the experts HELD here; the ``config.json`` written
restores HF's meaning (``num_experts`` = ``num_local_experts`` = held x
``ep_size``, the router's width). Which rank a process is, its launcher
says (``DORA_EP_RANK``); the index's metadata names the rank of this
shard.

Every matrix ``[out, in]`` is uniform with standard deviation ``1 /
sqrt(in)`` (a unit-rms row in, a unit-rms row out, at the published widths
and the tiny ones alike), norms 1. ``GAINS``: the attention's and the
indexer's query projections are drawn 3 x wider (GLM-5.3-Flash's gain), so
scores have a standard deviation near 3, a few rows carry each softmax as
in a trained model, and which rows were picked shows in what the layer
puts out. ``indexer.k_norm.bias`` is uniform in +-0.01.
"""

from __future__ import annotations

import json
import os
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

from checkpoint import _bf16, _levels, _save_safetensors, token_code

GAINS = {"self_attn.q_proj.weight": 3.0, "indexer.wq.weight": 3.0}
BIAS_AMPLITUDE = 0.01


def hf_config(config: dict) -> dict:
    """The checkpoint's config.json: HF's meaning of the expert keys."""
    experts = config["num_experts"] * config["ep_size"]
    return {**config, "num_experts": experts, "num_local_experts": experts}


def layer_shapes(config: dict, i: int, rank: int = 0) -> dict[str, tuple]:
    """HF tensor names of layer ``i`` -> shapes (``[out, in]``), with the
    experts ``rank`` holds under their GLOBAL numbers."""
    d, hd = config["hidden_size"], config["head_dim"]
    q, kv = config["num_attention_heads"] * hd, config["num_key_value_heads"] * hd
    sa = config["sa_config"]
    ih, idim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    p = f"model.layers.{i}."
    a, m = p + "self_attn.", p + "mlp."
    held = config["num_experts"]
    t = {
        p + "input_layernorm.weight": (d,),
        p + "post_attention_layernorm.weight": (d,),
        a + "q_proj.weight": (q, d), a + "k_proj.weight": (kv, d),
        a + "v_proj.weight": (kv, d), a + "o_proj.weight": (d, q),
        a + "q_norm.weight": (hd,), a + "k_norm.weight": (hd,),
        a + "indexer.wq.weight": (ih * idim, d),
        a + "indexer.wk.weight": (idim, d),
        a + "indexer.k_norm.weight": (idim,), a + "indexer.k_norm.bias": (idim,),
        a + "indexer.weights_proj.weight": (ih, d),
        m + "gate.weight": (held * config["ep_size"], d),
    }
    for e in range(rank * held, (rank + 1) * held):
        width = config["moe_intermediate_size"]
        t[f"{m}experts.{e}.gate_proj.weight"] = (width, d)
        t[f"{m}experts.{e}.up_proj.weight"] = (width, d)
        t[f"{m}experts.{e}.down_proj.weight"] = (d, width)
    return t


def draw(rng, shape: tuple, name: str):
    """One tensor, bf16: see the module docstring."""
    import ml_dtypes
    import numpy as np

    if len(shape) == 1 and not name.endswith("bias"):
        return _bf16(np.ones(shape, np.float32))
    gain = next((g for suffix, g in GAINS.items() if name.endswith(suffix)), 1.0)
    half = BIAS_AMPLITUDE if name.endswith("bias") else gain * (3.0 / shape[-1]) ** 0.5
    picks = rng.integers(0, 65536, size=shape, dtype=np.uint16)
    return _levels(half)[picks].view(ml_dtypes.bfloat16)


def write_checkpoint(path: Path, config: dict, seed: int, rank: int = 0) -> dict:
    """All drawn from ``seed`` (a stream a file). Returns the seconds the
    drawing and the writing took."""
    import numpy as np

    t0 = time.perf_counter()
    path.mkdir(parents=True, exist_ok=True)
    layers = config["num_hidden_layers"]
    d, vocab = config["hidden_size"], config["vocab_size"]
    seqs = np.random.SeedSequence(seed).spawn(layers + 1)
    files = [
        (f"model-{i:05d}.safetensors", layer_shapes(config, i, rank), seqs[i])
        for i in range(layers)
    ]
    files.append(("model-ends.safetensors", {
        "model.embed_tokens.weight": (vocab, d), "model.norm.weight": (d,),
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
        "metadata": {"rank": rank, "ep_size": config["ep_size"]},
        "weight_map": {n: name for name, shapes, _ in files for n in shapes},
    }))
    (path / "config.json").write_text(json.dumps(hf_config(config), indent=1))
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
    total = time.perf_counter() - t0
    return {"total_s": total, "write_thread_s": wrote, "files": len(files)}
