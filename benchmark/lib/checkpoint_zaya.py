"""Seeded random checkpoint of a ``zaya`` (ZAYA1) configuration: bf16
safetensors under the tensor names ``dora_tpu/models/hf/zaya.py`` reads
(an assumption, ``assumed.tensor_names``: no real checkpoint is in the
repository), one file a layer, EVERY expert (``ep_size`` 1: the layer's
output is the model's), ``config.json`` as the configuration file's top
level gives it and the synthetic tokenizer of ``lib/checkpoint.py``. The
embedding is the head's (``tie_word_embeddings``): no ``lm_head.weight``
is written.

Every matrix ``[out, in]`` is uniform with standard deviation ``gain /
sqrt(in)`` (a unit-rms row in, a unit-rms row out, at the published
widths and the tiny ones alike; a convolution's ``in`` is its taps x its
channels a group), norms 1. :data:`GAINS`: the query projection is drawn
3 x wider (GLM-5.3-Flash's and Keye-VL-2.0's gain; the queries are
L2-normed after the convolutions, so here it weights the query latent in
the q-k mean and sharpens no softmax); the router's last matrix 4 x, so
that ``p``'s largest entry is some 0.3 and clear of the second by 0.05 in
seven rows of ten, and its last two matrices with rows that sum to zero
(:data:`CENTRED`), so that the seeded routers spread the tokens evenly
over the experts, as a trained router's balancing does. (A sharper router
is a worse-conditioned model, and no better a test: top-1 is
discontinuous, bf16's noise decides a near-tie either way at any
sharpness, and what a row loses by it is the chosen expert's whole output
times ``p``. At 12 x, ``p`` near 1, two correct programs part on three
rows in four by the last layer: ``PERF.md`` section 6, PR 52.)
:data:`VECTORS`: ``uniform(centre - half, centre + half)`` for the learned
vectors that are no norm weight: the key temperature ``temp`` near ln 3
(THAT sharpens the softmax: scores of standard deviation 3, a few rows
carry each, as in a trained model), the router's ``state_scale`` near
0.5, the four residual-scaling vectors near 1 and 0 but off them (a
program that left them out would not match), the balancing bias within
+-0.01, every other bias within +-0.05.
"""

from __future__ import annotations

import json
import math
import os
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

from checkpoint import _bf16, _levels, _save_safetensors, token_code

GAINS = {"self_attn.q_proj.weight": 3.0, "router.mlp.2.weight": 4.0}
#: matrices whose rows (an output's weights over its inputs) are drawn to sum
#: to zero: what a GELU layer hands on has a mean, the same for every row,
#: and an output that weighs it would favour its expert in every row
CENTRED = ("router.mlp.1.weight", "router.mlp.2.weight")
#: name suffix -> (centre, half width), the first that matches
VECTORS = (
    ("layernorm.weight", (1.0, 0.0)), ("norm.weight", (1.0, 0.0)),
    ("self_attn.temp", (math.log(3.0), 0.1)),
    ("router.state_scale", (0.5, 0.1)),
    ("residual_scale", (1.0, 0.1)), ("hidden_scale", (1.0, 0.1)),
    ("residual_bias", (0.0, 0.05)), ("hidden_bias", (0.0, 0.05)),
    ("balancing_bias", (0.0, 0.01)),
    ("bias", (0.0, 0.05)),
)
RESIDUALS = ("residual_bias", "residual_scale", "hidden_bias", "hidden_scale")


def hf_config(config: dict) -> dict:
    """The checkpoint's config.json: the configuration file's top level
    (``num_experts`` is the model's and the chip's alike)."""
    return dict(config)


def layer_shapes(config: dict, i: int, rank: int = 0) -> dict[str, tuple]:
    """Tensor names of layer ``i`` -> shapes (matrices ``[out, in]``, the
    convolutions torch's ``Conv1d`` ``[out, in / groups, taps]``)."""
    d, hd = config["hidden_size"], config["head_dim"]
    q, kv = config["num_attention_heads"] * hd, config["num_key_value_heads"] * hd
    r, e, width = config["router_hidden_size"], config["num_experts"], q + kv
    p = f"model.layers.{i}."
    a, m = p + "self_attn.", p + "mlp."
    t = {
        p + "input_layernorm.weight": (d,),
        p + "post_attention_layernorm.weight": (d,),
        a + "q_proj.weight": (q, d), a + "k_proj.weight": (kv, d),
        a + "v_proj1.weight": (hd, d), a + "v_proj2.weight": (hd, d),
        a + "o_proj.weight": (d, q),
        a + "conv_qk.0.weight": (width, 1, config["cca_time0"]),
        a + "conv_qk.0.bias": (width,),
        a + "conv_qk.1.weight": (width, hd, config["cca_time1"]),
        a + "conv_qk.1.bias": (width,),
        a + "temp": (config["num_key_value_heads"],),
        m + "router.down_proj.weight": (r, d), m + "router.down_proj.bias": (r,),
        m + "router.state_scale": (r,), m + "router.norm.weight": (r,),
        m + "router.mlp.0.weight": (r, r), m + "router.mlp.0.bias": (r,),
        m + "router.mlp.1.weight": (r, r), m + "router.mlp.1.bias": (r,),
        m + "router.mlp.2.weight": (e, r),
        m + "router.balancing_bias": (e,),
    }
    for at in ("attn", "mlp"):
        for name in RESIDUALS:
            t[f"{p}{at}_residual.{name}"] = (d,)
    held = e // int(config.get("ep_size") or 1)
    for n in range(rank * held, (rank + 1) * held):
        width = config["moe_intermediate_size"]
        t[f"{m}experts.{n}.gate_proj.weight"] = (width, d)
        t[f"{m}experts.{n}.up_proj.weight"] = (width, d)
        t[f"{m}experts.{n}.down_proj.weight"] = (d, width)
    return t


def draw(rng, shape: tuple, name: str):
    """One tensor, bf16: see the module docstring."""
    import ml_dtypes
    import numpy as np

    if len(shape) == 1:
        centre, half = next(v for suffix, v in VECTORS if name.endswith(suffix))
        return _bf16((centre + half * rng.uniform(-1.0, 1.0, shape)).astype(np.float32))
    gain = next((g for suffix, g in GAINS.items() if name.endswith(suffix)), 1.0)
    inputs = math.prod(shape[1:])  # a convolution's: channels a group x taps
    picks = rng.integers(0, 65536, size=shape, dtype=np.uint16)
    drawn = _levels(gain * (3.0 / inputs) ** 0.5)[picks].view(ml_dtypes.bfloat16)
    if name.endswith(CENTRED):
        rows = drawn.astype(np.float32)
        return _bf16(rows - rows.mean(-1, keepdims=True))
    return drawn


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
        "metadata": {"rank": rank, "ep_size": int(config.get("ep_size") or 1)},
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
