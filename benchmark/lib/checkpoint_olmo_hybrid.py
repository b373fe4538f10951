"""Seeded random checkpoint of an ``olmo_hybrid`` configuration: bf16
safetensors under the tensor names ``dora_tpu/models/hf/olmo_hybrid.py``
reads (an assumption, ``assumed.tensor_names``: no real checkpoint is in
the repository), one file a layer, ``config.json`` as the configuration
file's top level gives it and the synthetic tokenizer of
``lib/checkpoint.py``. Embedding and head are two matrices
(``tie_word_embeddings`` false).

Every matrix ``[out, in]`` is uniform with standard deviation ``gain /
sqrt(in)`` (a unit-rms row in, a unit-rms row out, at the published
widths and the tiny ones alike; a convolution's ``in`` is its 4 taps),
sublayer norms 1. :data:`VECTORS`, ``uniform(centre - half, centre +
half)``, for the learned vectors that are no sublayer norm:

* ``q_norm`` and ``k_norm`` near sqrt(3): the norms run over the whole
  projection, so a head's score has the standard deviation of their
  product, 3 (GLM-5.3-Flash's, Keye-VL-2.0's and ZAYA's sharpness: a few
  rows carry each softmax, as in a trained model, where weights of 1 would
  average thousands of rows and hide a wrong page);
* ``A_log`` in ln 0.25 .. ln 4 and ``dt_bias`` with ``softplus(dt_bias)``
  log-uniform in 0.001 .. 0.1 (Mamba-2's and the Gated DeltaNet's
  initialisers' ranges): a head's decay a token runs from exp(-0.00025)
  to exp(-0.4), so some heads of every layer remember thousands of rows
  (what a grant without its snapshot loses shows at any distance) and some
  a few (what a dropped convolution tap or a stale tail loses shows at
  once). ``dt_bias`` is written as the inverse softplus of the draw;
* ``o_norm`` 1 +- 0.1 (off 1, so that leaving it out fails).

``b_proj`` is drawn 2 x wider: beta = 2 sigmoid(.) then spreads over (0.2,
1.8) and not around 1, where ``I - beta k k^T`` would forget a key's
direction whatever the gate (and ``linear_allow_neg_eigval`` would have
nothing to allow).
"""

from __future__ import annotations

import json
import math
import os
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

from checkpoint import _bf16, _levels, _save_safetensors, token_code

GAINS = {"linear_attn.b_proj.weight": 2.0}
#: name suffix -> (centre, half width), the first that matches
VECTORS = (
    ("self_attn.q_norm.weight", (math.sqrt(3.0), 0.1)),
    ("self_attn.k_norm.weight", (math.sqrt(3.0), 0.1)),
    ("linear_attn.o_norm.weight", (1.0, 0.1)),
    ("linear_attn.A_log", (0.0, math.log(4.0))),
    ("layernorm.weight", (1.0, 0.0)), ("norm.weight", (1.0, 0.0)),
)
#: softplus(dt_bias) is log-uniform between these
DT_RANGE = (0.001, 0.1)


def hf_config(config: dict) -> dict:
    """The checkpoint's config.json: the configuration file's top level."""
    return dict(config)


def layer_shapes(config: dict, i: int) -> dict[str, tuple]:
    """Tensor names of layer ``i`` -> shapes (matrices ``[out, in]``, the
    convolutions torch's depthwise ``Conv1d`` ``[channels, 1, taps]``)."""
    d, ffn = config["hidden_size"], config["intermediate_size"]
    p = f"model.layers.{i}."
    t = {
        p + "post_attention_layernorm.weight": (d,),
        p + "post_feedforward_layernorm.weight": (d,),
        p + "mlp.gate_proj.weight": (ffn, d), p + "mlp.up_proj.weight": (ffn, d),
        p + "mlp.down_proj.weight": (d, ffn),
    }
    if config["layer_types"][i] == "linear_attention":
        a = p + "linear_attn."
        h, taps = config["linear_num_value_heads"], config["linear_conv_kernel_dim"]
        kw = config["linear_num_key_heads"] * config["linear_key_head_dim"]
        vw = h * config["linear_value_head_dim"]
        for name, width in (("q", kw), ("k", kw), ("v", vw)):
            t[a + f"{name}_proj.weight"] = (width, d)
            t[a + f"{name}_conv1d.weight"] = (width, 1, taps)
        t.update({
            a + "g_proj.weight": (vw, d), a + "a_proj.weight": (h, d),
            a + "b_proj.weight": (h, d), a + "A_log": (h,), a + "dt_bias": (h,),
            a + "o_norm.weight": (config["linear_value_head_dim"],),
            a + "o_proj.weight": (d, vw),
        })
    else:
        a = p + "self_attn."
        hd = d // config["num_attention_heads"]
        kv = config["num_key_value_heads"] * hd
        t.update({
            a + "q_proj.weight": (d, d), a + "k_proj.weight": (kv, d),
            a + "v_proj.weight": (kv, d), a + "o_proj.weight": (d, d),
            a + "q_norm.weight": (d,), a + "k_norm.weight": (kv,),
        })
    return t


def draw(rng, shape: tuple, name: str):
    """One tensor, bf16: see the module docstring."""
    import ml_dtypes
    import numpy as np

    if name.endswith("dt_bias"):
        lo, hi = (math.log(v) for v in DT_RANGE)
        dt = np.exp(rng.uniform(lo, hi, shape))
        return _bf16(np.log(np.expm1(dt)).astype(np.float32))  # softplus^-1
    if len(shape) == 1:
        centre, half = next(v for suffix, v in VECTORS if name.endswith(suffix))
        return _bf16((centre + half * rng.uniform(-1.0, 1.0, shape)).astype(np.float32))
    gain = next((g for suffix, g in GAINS.items() if name.endswith(suffix)), 1.0)
    inputs = math.prod(shape[1:])  # a convolution's: its taps
    picks = rng.integers(0, 65536, size=shape, dtype=np.uint16)
    return _levels(gain * (3.0 / inputs) ** 0.5)[picks].view(ml_dtypes.bfloat16)


def write_checkpoint(path: Path, config: dict, seed: int) -> dict:
    """All drawn from ``seed`` (a stream a file). Returns the seconds the
    drawing and the writing took."""
    import numpy as np

    t0 = time.perf_counter()
    path.mkdir(parents=True, exist_ok=True)
    layers = config["num_hidden_layers"]
    d, vocab = config["hidden_size"], config["vocab_size"]
    seqs = np.random.SeedSequence(seed).spawn(layers + 2)
    files = [
        (f"model-{i:05d}.safetensors", layer_shapes(config, i), seqs[i])
        for i in range(layers)
    ]
    files.append(("model-embed.safetensors", {
        "model.embed_tokens.weight": (vocab, d), "model.norm.weight": (d,),
    }, seqs[layers]))
    files.append(("model-head.safetensors", {"lm_head.weight": (vocab, d)},
                  seqs[layers + 1]))

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
        "metadata": {}, "weight_map": {n: name for name, shapes, _ in files for n in shapes},
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
