"""Seeded random rank-0 checkpoint of a ``kimi_linear`` (Kimi Linear)
configuration: bf16 safetensors under Kimi Linear's tensor names
(``self_attn.`` for the KDA mixer and for the latent layer, ``mlp.`` for
the dense layer, ``block_sparse_moe.`` with experts ``w1`` / ``w3`` / ``w2``
for the expert layer: ``assumed.tensor_names``), one file a layer, the
experts of ONE rank only (a shard checkpoint, as a launcher of an expert
group would hand each chip), ``config.json`` and the synthetic tokenizer of
``lib/checkpoint.py``.

``config`` is the benchmark's configuration file's top level, where
``num_experts`` counts the experts HELD here; the ``config.json`` written
restores HF's meaning (``num_experts`` = held x ``ep_size``, the router's
width). Which rank a process is, its launcher says (``DORA_EP_RANK``); the
index's metadata names the rank of this shard.

What is drawn how (``assumed.weights``; the rules ``checkpoint_glm5_next``
found, ``KNOWN_ISSUES.md`` "PR 52"): every matrix uniform with standard
deviation ``1 / sqrt(inputs)`` (a row of unit rms in, a row of unit rms
out), the latent layer's queries 3 times that (:data:`QUERY_GAIN`: scores
of deviation near 3, so a softmax has a few carriers and WHICH rows are
attended shows in the logits), norms 1, and the vectors that set a gate or
a decay from ranges of their own (:data:`RANGES`).
"""

from __future__ import annotations

import json
import os
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

from checkpoint import _bf16, _levels, _save_safetensors, token_code

QUERY_GAIN = 3.0
#: name suffix -> (centre, half width) of a uniform draw
#: (the first suffix that fits is taken: ``dt_bias`` before ``bias``)
RANGES = {
    "A_log": (0.0, 0.5),          # exp(A_log) in 0.6 .. 1.65
    # g = -exp(A_log) softplus(r + dt_bias) with r of deviation 1: softplus
    # of -5.5 .. -0.5 is 0.004 .. 0.47, so a channel forgets (1 / |g|) in
    # one to a few hundred positions
    "dt_bias": (-3.0, 2.5),
    # the short convolutions' taps: q's and k's small, so that silu works in
    # its linear part and q and k have no common positive component that
    # would reach the router as a common mode (checkpoint_glm5_next, PR 43);
    # v's output is of its input's size
    "q_conv1d.weight": (0.0, 0.05),
    "k_conv1d.weight": (0.0, 0.05),
    "v_conv1d.weight": (0.0, 0.5),
    "bias": (0.0, 0.01),          # e_score_correction_bias: a centred router
}


def hf_config(config: dict) -> dict:
    """The checkpoint's config.json: HF's meaning of the expert key."""
    return {**config, "num_experts": config["num_experts"] * config["ep_size"]}


def is_kda(config: dict, i: int) -> bool:
    """Layer ``i`` (from 0); the published lists number the layers from 1."""
    return i + 1 in config["linear_attn_config"]["kda_layers"]


def is_sparse(config: dict, i: int) -> bool:
    return (i >= config["first_k_dense_replace"]
            and i % config.get("moe_layer_freq", 1) == 0)


def layer_shapes(config: dict, i: int, rank: int = 0) -> dict[str, tuple]:
    """HF tensor names of layer ``i`` -> shapes (``[out, in]``), with the
    experts ``rank`` holds under their GLOBAL numbers."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    p = f"model.layers.{i}."
    a = p + "self_attn."
    t = {
        p + "input_layernorm.weight": (d,),
        p + "post_attention_layernorm.weight": (d,),
    }
    if is_kda(config, i):
        lin = config["linear_attn_config"]
        hk, r = lin["num_heads"] * lin["head_dim"], lin["head_dim"]
        for x in "qkv":
            t[a + f"{x}_proj.weight"] = (hk, d)
            t[a + f"{x}_conv1d.weight"] = (hk, 1, lin["short_conv_kernel_size"])
        t.update({
            a + "f_a_proj.weight": (r, d), a + "f_b_proj.weight": (hk, r),
            a + "g_a_proj.weight": (r, d), a + "g_b_proj.weight": (hk, r),
            a + "b_proj.weight": (lin["num_heads"], d),
            a + "A_log": (lin["num_heads"],), a + "dt_bias": (hk,),
            a + "o_norm.weight": (r,), a + "o_proj.weight": (d, hk),
        })
    else:
        nope, shared, v = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                           config["v_head_dim"])
        kv_rank = config["kv_lora_rank"]
        t.update({
            a + "q_proj.weight": (h * (nope + shared), d),
            a + "kv_a_proj_with_mqa.weight": (kv_rank + shared, d),
            a + "kv_a_layernorm.weight": (kv_rank,),
            a + "kv_b_proj.weight": (h * (nope + v), kv_rank),
            a + "o_proj.weight": (d, h * v),
        })

    def ffn(prefix: str, width: int, names=("gate_proj", "up_proj", "down_proj")) -> None:
        t[f"{prefix}{names[0]}.weight"] = (width, d)
        t[f"{prefix}{names[1]}.weight"] = (width, d)
        t[f"{prefix}{names[2]}.weight"] = (d, width)

    if not is_sparse(config, i):
        ffn(p + "mlp.", config["intermediate_size"])
        return t
    m = p + "block_sparse_moe."
    held = config["num_experts"]
    t[m + "gate.weight"] = (held * config["ep_size"], d)
    t[m + "gate.e_score_correction_bias"] = (held * config["ep_size"],)
    ffn(m + "shared_experts.",
        config["moe_intermediate_size"] * config["num_shared_experts"])
    for e in range(rank * held, (rank + 1) * held):
        ffn(f"{m}experts.{e}.", config["moe_intermediate_size"], ("w1", "w3", "w2"))
    return t


def draw(rng, shape: tuple, name: str, gain: float = 1.0):
    """One tensor, bf16: see the module docstring."""
    import ml_dtypes
    import numpy as np

    def uniform(centre: float, half: float):
        picks = rng.integers(0, 65536, size=shape, dtype=np.uint16)
        if centre == 0.0:
            return _levels(half)[picks].view(ml_dtypes.bfloat16)
        values = _levels(half).view(ml_dtypes.bfloat16).astype(np.float32)[picks]
        return _bf16(values + np.float32(centre))

    for suffix, (centre, half) in RANGES.items():
        if name.endswith(suffix):
            return uniform(centre, half)
    if len(shape) == 1:
        return _bf16(np.ones(shape, np.float32))
    return uniform(0.0, gain * (3.0 / shape[-1]) ** 0.5)


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
        (f"model-{i:05d}.safetensors", layer_shapes(config, i, rank), seqs[i],
         None if is_kda(config, i) else f"model.layers.{i}.self_attn.q_proj.weight")
        for i in range(layers)
    ]
    files.append(("model-ends.safetensors", {
        "model.embed_tokens.weight": (vocab, d), "model.norm.weight": (d,),
        "lm_head.weight": (vocab, d),
    }, seqs[layers], None))

    def one(job) -> float:
        name, shapes, seq, queries = job
        rng = np.random.default_rng(seq)
        tensors = {n: draw(rng, s, n, QUERY_GAIN if n == queries else 1.0)
                   for n, s in shapes.items()}
        t = time.perf_counter()
        _save_safetensors(tensors, path / name)
        return time.perf_counter() - t

    with ThreadPoolExecutor(max_workers=min(len(files), os.cpu_count() or 1)) as pool:
        wrote = sum(pool.map(one, files))
    (path / "model.safetensors.index.json").write_text(json.dumps({
        "metadata": {"rank": rank, "ep_size": config["ep_size"]},
        "weight_map": {n: name for name, shapes, _, _ in files for n in shapes},
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
