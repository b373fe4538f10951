"""Seeded random Qwen2 checkpoint and the fixed-width synthetic tokenizer.

Copied from ``chip_smoke.py`` (PR 21: ``write_checkpoint``, ``token_code``,
``code_tokens``) so that the yardstick does not move when the program's
own smoke does. The tokenizer maps every id to one 3-character code, so a
prompt's text IS its ids and a stream's text reads back as the ids the
engine emitted.
"""

from __future__ import annotations

import json
import os
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

_ALNUM = "0123456789ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz"
_INDEX = {c: i for i, c in enumerate(_ALNUM)}


def token_code(i: int) -> str:
    return _ALNUM[i // 3844] + _ALNUM[i // 62 % 62] + _ALNUM[i % 62]


def code_tokens(text: str) -> list[int]:
    if len(text) % 3:
        raise ValueError(f"text of {len(text)} characters is no run of codes")
    return [
        _INDEX[text[j]] * 3844 + _INDEX[text[j + 1]] * 62 + _INDEX[text[j + 2]]
        for j in range(0, len(text), 3)
    ]


def _bf16(x):
    """float32 -> bfloat16, round-to-nearest-even, by bit arithmetic
    (ml_dtypes' astype is ~10x slower at these sizes)."""
    import ml_dtypes
    import numpy as np

    u = x.view(np.uint32)
    u = (u + 0x7FFF + ((u >> 16) & 1)) >> 16
    return u.astype(np.uint16).view(ml_dtypes.bfloat16)


def _save_safetensors(tensors: dict, path: Path) -> None:
    """The safetensors layout (8-byte header length, JSON header, raw
    little-endian tensors) written straight from the arrays: the
    library's ``save_file`` first copies all 3 GB into one buffer."""
    import struct

    header, offset = {}, 0
    names = sorted(tensors)
    for name in names:
        t = tensors[name]
        header[name] = {"dtype": "BF16", "shape": list(t.shape),
                        "data_offsets": [offset, offset + t.nbytes]}
        offset += t.nbytes
    blob = json.dumps(header, separators=(",", ":")).encode()
    blob += b" " * (-len(blob) % 8)
    with open(path, "wb") as f:
        f.write(struct.pack("<Q", len(blob)))
        f.write(blob)
        for name in names:
            f.write(memoryview(tensors[name].view("uint16")).cast("B"))


def _levels(amp: float):
    """65,536 evenly spaced levels of [-amp, amp) as bf16 bit patterns: a
    weight is one of them, picked by 16 random bits (seven times faster
    than drawing floats and rounding them)."""
    import numpy as np

    x = ((np.arange(65536, dtype=np.float32) + 0.5) / 65536 - 0.5) * (2 * amp)
    return _bf16(x).view(np.uint16)


def write_checkpoint(path: Path, config: dict, seed: int) -> dict:
    """bf16 safetensors under HF names + config.json + tokenizer.json,
    every weight uniform with the published initializer's standard
    deviation, drawn from ``seed``. Returns the seconds each part took."""
    import ml_dtypes
    import numpy as np

    t0 = time.perf_counter()
    path.mkdir(parents=True, exist_ok=True)
    dim = config["hidden_size"]
    ffn = config["intermediate_size"]
    hd = dim // config["num_attention_heads"]
    kv = config["num_key_value_heads"] * hd
    layers = config["num_hidden_layers"]
    vocab = config["vocab_size"]
    levels = _levels(config.get("initializer_range", 0.02) * 3 ** 0.5)
    ones = _bf16(np.ones((dim,), np.float32))

    def rand(rng, *shape):
        picks = rng.integers(0, 65536, size=shape, dtype=np.uint16)
        return levels[picks].view(ml_dtypes.bfloat16)

    def layer(args):
        i, seq = args
        rng = np.random.default_rng(seq)
        p = f"model.layers.{i}."
        return {
            p + "input_layernorm.weight": ones,
            p + "post_attention_layernorm.weight": ones,
            p + "self_attn.q_proj.weight": rand(rng, dim, dim),
            p + "self_attn.q_proj.bias": rand(rng, dim),
            p + "self_attn.k_proj.weight": rand(rng, kv, dim),
            p + "self_attn.k_proj.bias": rand(rng, kv),
            p + "self_attn.v_proj.weight": rand(rng, kv, dim),
            p + "self_attn.v_proj.bias": rand(rng, kv),
            p + "self_attn.o_proj.weight": rand(rng, dim, dim),
            p + "mlp.gate_proj.weight": rand(rng, ffn, dim),
            p + "mlp.up_proj.weight": rand(rng, ffn, dim),
            p + "mlp.down_proj.weight": rand(rng, dim, ffn),
        }

    parts = 8  # the embedding is a sixth of the weights: drawn in row blocks
    seqs = np.random.SeedSequence(seed).spawn(layers + parts)
    rows = [vocab * k // parts for k in range(parts + 1)]

    def embed(args):
        k, seq = args
        return rand(np.random.default_rng(seq), rows[k + 1] - rows[k], dim)

    tensors = {"model.norm.weight": ones}
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        blocks = pool.map(embed, enumerate(seqs[layers:]))
        for part in pool.map(layer, enumerate(seqs[:layers])):
            tensors.update(part)
        tensors["model.embed_tokens.weight"] = np.concatenate(list(blocks))
    t_drawn = time.perf_counter()
    _save_safetensors(tensors, path / "model.safetensors")
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
    done = time.perf_counter()
    return {"draw_s": t_drawn - t0, "write_s": done - t_drawn}
