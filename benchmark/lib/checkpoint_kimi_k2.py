"""Seeded random rank-0 checkpoint of a ``kimi_k2`` (DeepSeek-V3 block)
configuration: bf16 safetensors under the HF tensor names, one file a
layer, the experts of ONE rank only (a shard checkpoint, as a launcher
of an expert group would hand each chip), ``config.json`` and the
synthetic tokenizer of ``lib/checkpoint.py``.

``config`` is the benchmark's configuration file's top level, where
``n_routed_experts`` counts the experts HELD here; the ``config.json``
written restores HF's meaning (``n_routed_experts`` = held x ``ep_size``,
the router's width). Which rank a process is, its launcher says
(``DORA_EP_RANK``); the index's metadata names the rank of this shard.
"""

from __future__ import annotations

import json
import os
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

from checkpoint import _bf16, _levels, _save_safetensors, token_code

INITIALIZER_RANGE = 0.02  # DeepSeek-V3's; the catalog row drops the key
BIAS_AMPLITUDE = 0.01


def hf_config(config: dict) -> dict:
    """The checkpoint's config.json: HF's meaning of the expert keys."""
    return {**config, "n_routed_experts": config["n_routed_experts"] * config["ep_size"]}


def layer_shapes(config: dict, i: int, rank: int = 0) -> dict[str, tuple]:
    """HF tensor names of layer ``i`` -> shapes (``[out, in]``), with the
    experts ``rank`` holds under their GLOBAL numbers."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    nope, rope, v = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"])
    q_rank, kv_rank = config["q_lora_rank"], config["kv_lora_rank"]
    p = f"model.layers.{i}."
    t = {
        p + "input_layernorm.weight": (d,),
        p + "post_attention_layernorm.weight": (d,),
        p + "self_attn.q_a_proj.weight": (q_rank, d),
        p + "self_attn.q_a_layernorm.weight": (q_rank,),
        p + "self_attn.q_b_proj.weight": (h * (nope + rope), q_rank),
        p + "self_attn.kv_a_proj_with_mqa.weight": (kv_rank + rope, d),
        p + "self_attn.kv_a_layernorm.weight": (kv_rank,),
        p + "self_attn.kv_b_proj.weight": (h * (nope + v), kv_rank),
        p + "self_attn.o_proj.weight": (d, h * v),
    }

    def ffn(prefix: str, width: int) -> None:
        t[prefix + "gate_proj.weight"] = (width, d)
        t[prefix + "up_proj.weight"] = (width, d)
        t[prefix + "down_proj.weight"] = (d, width)

    if i < config["first_k_dense_replace"]:
        ffn(p + "mlp.", config["intermediate_size"])
        return t
    held = config["n_routed_experts"]
    t[p + "mlp.gate.weight"] = (held * config["ep_size"], d)
    t[p + "mlp.gate.e_score_correction_bias"] = (held * config["ep_size"],)
    ffn(p + "mlp.shared_experts.",
        config["moe_intermediate_size"] * config["n_shared_experts"])
    for e in range(rank * held, (rank + 1) * held):
        ffn(f"{p}mlp.experts.{e}.", config["moe_intermediate_size"])
    return t


def write_checkpoint(path: Path, config: dict, seed: int, rank: int = 0) -> dict:
    """Every matrix uniform with standard deviation 0.02, norms 1, the
    routing bias uniform in +-0.01, all drawn from ``seed`` (a stream a
    file). Returns the seconds the drawing and the writing took."""
    import ml_dtypes
    import numpy as np

    t0 = time.perf_counter()
    path.mkdir(parents=True, exist_ok=True)
    layers = config["num_hidden_layers"]
    d, vocab = config["hidden_size"], config["vocab_size"]
    levels = _levels(INITIALIZER_RANGE * 3 ** 0.5)
    small = _levels(BIAS_AMPLITUDE)

    def draw(rng, shape, name):
        if len(shape) == 1 and not name.endswith("bias"):
            return _bf16(np.ones(shape, np.float32))
        picks = rng.integers(0, 65536, size=shape, dtype=np.uint16)
        table = small if name.endswith("bias") else levels
        return table[picks].view(ml_dtypes.bfloat16)

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
    return {"total_s": total, "write_thread_s": wrote,
            "files": len(files)}
