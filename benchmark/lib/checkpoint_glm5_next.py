"""Seeded random rank-0 checkpoint of a ``glm5_next_text`` (GLM-5.3-Flash)
configuration: bf16 safetensors under the HF tensor names (Kimi Linear's
for the delta-rule mixer, DeepSeek-V3.2's for the latent layer and its
indexer, DeepSeek-V3's for the expert layer, ``hc_{attn,ffn}_{fn,base,
scale}`` for the residual maps: an assumption, ``assumed.tensor_names``),
one file a layer, the experts of ONE rank only (a shard checkpoint, as a
launcher of an expert group would hand each chip), ``config.json`` and
the synthetic tokenizer of ``lib/checkpoint.py``.

``config`` is the benchmark's configuration file's top level, where
``n_routed_experts`` counts the experts HELD here; the ``config.json``
written restores HF's meaning (``n_routed_experts`` = held x ``ep_size``,
the router's width). Which rank a process is, its launcher says
(``DORA_EP_RANK``); the index's metadata names the rank of this shard.

What is drawn how (``assumed.weights``): every matrix uniform with
standard deviation ``1 / sqrt(inputs)`` (:data:`GAINS` for the one
exception), norms 1, and the vectors that set a gate, a decay or the
Sinkhorn input from ranges of their own (:data:`RANGES`).
"""

from __future__ import annotations

import json
import os
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

from checkpoint import _bf16, _levels, _save_safetensors, token_code

#: a matrix ``[out, in]`` is uniform with standard deviation ``gain / sqrt(in)``
#: (a row of unit rms in, a row of rms ``gain`` out, at the published widths
#: and at the tiny ones alike: 0.0156 at 4,096 inputs). The query's heads get
#: 3: the latent layer's scores then have a standard deviation near 3, a few
#: rows carry a softmax (as in a trained model), and WHICH rows are attended
#: shows in the logits: with scores of deviation 0.35 (what 0.02 everywhere
#: gives) attention is an average of thousands of random rows, 1 % of the
#: residual, and no token could tell a selection from none.
GAINS = {"self_attn.q_b_proj.weight": 3.0}
#: name suffix -> (centre, half width) of a uniform draw
#: (the first suffix that fits is taken: ``dt_bias`` before ``bias``)
RANGES = {
    "A_log": (0.0, 0.5),          # exp(A_log) in 0.6 .. 1.65
    "dt_bias": (-3.5, 2.5),       # g = -5 sigmoid(.) in about -2 .. -0.01 a step
    # the short convolutions' taps. q's and k's are small, so that silu works in
    # its linear part and q and k have no common positive component: with one
    # (12 % of k's energy at taps of 0.5), every stream's state holds the same
    # rank-1 part, the mixer's output the same vector for every token (16 % of
    # its energy), the router's input a common mode, and which experts are
    # popular, and so how many of this rank's 36 a tick touches, swings with
    # the seed (tpot_p50_ms 8.47-8.82 over three seeds: my chip runs, PR 43);
    # v's output is of its input's size
    "q_conv1d.weight": (0.0, 0.05),
    "k_conv1d.weight": (0.0, 0.05),
    "v_conv1d.weight": (0.0, 0.5),
    "_scale": (1.0, 0.5),         # a_pre, a_post, a_res: map logits of deviation 1 stay near 1
    "bias": (0.0, 0.01),          # e_score_correction_bias, indexer.k_norm.bias
}
#: hc_*_base: b_pre, b_post uniform in +-0.5; b_res +2 on the diagonal
BASE_AMPLITUDE, RES_DIAGONAL = 0.5, 2.0


def hf_config(config: dict) -> dict:
    """The checkpoint's config.json: HF's meaning of the expert keys."""
    return {**config, "n_routed_experts": config["n_routed_experts"] * config["ep_size"]}


def layer_shapes(config: dict, i: int, rank: int = 0) -> dict[str, tuple]:
    """HF tensor names of layer ``i`` -> shapes (``[out, in]``), with the
    experts ``rank`` holds under their GLOBAL numbers."""
    d, h, n = config["hidden_size"], config["num_attention_heads"], config["hc_mult"]
    p = f"model.layers.{i}."
    a = p + "self_attn."
    t = {
        p + "input_layernorm.weight": (d,),
        p + "post_attention_layernorm.weight": (d,),
    }
    for sub in ("attn", "ffn"):
        t[p + f"hc_{sub}_fn"] = (2 * n + n * n, n * d)
        t[p + f"hc_{sub}_base"] = (2 * n + n * n,)
        t[p + f"hc_{sub}_scale"] = (3,)
    if config["layer_types"][i] == "linear_attention":
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
        nope, v = config["qk_nope_head_dim"], config["v_head_dim"]
        q_rank, kv_rank = config["q_lora_rank"], config["kv_lora_rank"]
        ih, idim = config["index_n_heads"], config["index_head_dim"]
        t.update({
            a + "q_a_proj.weight": (q_rank, d),
            a + "q_a_layernorm.weight": (q_rank,),
            a + "q_b_proj.weight": (h * nope, q_rank),
            a + "kv_a_proj_with_mqa.weight": (kv_rank, d),
            a + "kv_a_layernorm.weight": (kv_rank,),
            a + "kv_b_proj.weight": (h * (nope + v), kv_rank),
            a + "o_proj.weight": (d, h * v),
            a + "indexer.wq_b.weight": (ih * idim, q_rank),
            a + "indexer.wk.weight": (idim, d),
            a + "indexer.k_norm.weight": (idim,),
            a + "indexer.k_norm.bias": (idim,),
            a + "indexer.weights_proj.weight": (ih, d),
        })

    def ffn(prefix: str, width: int) -> None:
        t[prefix + "gate_proj.weight"] = (width, d)
        t[prefix + "up_proj.weight"] = (width, d)
        t[prefix + "down_proj.weight"] = (d, width)

    if config["mlp_layer_types"][i] == "dense":
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


def draw(rng, shape: tuple, name: str, n_streams: int):
    """One tensor, bf16: see the module docstring."""
    import ml_dtypes
    import numpy as np

    def uniform(centre: float, half: float):
        picks = rng.integers(0, 65536, size=shape, dtype=np.uint16)
        if centre == 0.0:
            return _levels(half)[picks].view(ml_dtypes.bfloat16)
        values = _levels(half).view(ml_dtypes.bfloat16).astype(np.float32)[picks]
        return _bf16(values + np.float32(centre))

    if name.endswith("_base"):
        n = n_streams
        base = uniform(0.0, BASE_AMPLITUDE).astype(np.float32)
        base[2 * n :] += (RES_DIAGONAL * np.eye(n, dtype=np.float32)).reshape(-1)
        return _bf16(base)
    for suffix, (centre, half) in RANGES.items():
        if name.endswith(suffix):
            return uniform(centre, half)
    if len(shape) == 1:
        return _bf16(np.ones(shape, np.float32))
    gain = next((g for suffix, g in GAINS.items() if name.endswith(suffix)), 1.0)
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
        tensors = {n: draw(rng, s, n, config["hc_mult"]) for n, s in shapes.items()}
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
