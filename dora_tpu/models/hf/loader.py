"""Checkpoint directory reading: safetensors (single file or sharded) and
config.json, as numpy arrays — no torch required on the load path.

Reference parity: the reference loads checkpoints through torch
`from_pretrained` (node-hub/dora-qwenvl/dora_qwenvl/main.py:24-33); here
the tensors go straight from the memory-mapped safetensors file into JAX
arrays (cast to the requested dtype on device_put).
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np


def read_config(model_dir: str | Path) -> dict:
    return json.loads((Path(model_dir) / "config.json").read_text())


class TensorFiles:
    """The safetensors files of a checkpoint directory, read one tensor
    at a time by name (``model.safetensors``, a sharded index, or any
    ``*.safetensors``): nothing is read that is not asked for."""

    def __init__(self, model_dir: str | Path):
        from safetensors import safe_open

        model_dir = Path(model_dir)
        index = model_dir / "model.safetensors.index.json"
        if index.exists():
            weight_map = json.loads(index.read_text())["weight_map"]
            files = sorted(set(weight_map.values()))
        else:
            files = sorted(p.name for p in model_dir.glob("*.safetensors"))
        if not files:
            raise FileNotFoundError(f"no safetensors files under {model_dir}")
        self._open = {
            f: safe_open(str(model_dir / f), framework="np") for f in files
        }
        self.where = {
            name: f for f, h in self._open.items() for name in h.keys()
        }

    def __contains__(self, name: str) -> bool:
        return name in self.where

    def get(self, name: str):
        return self._open[self.where[name]].get_tensor(name)


def read_safetensors(model_dir: str | Path) -> dict[str, np.ndarray]:
    """All tensors of a checkpoint dir keyed by their checkpoint names.

    Handles both single-file ``model.safetensors`` and sharded
    ``model.safetensors.index.json`` layouts.
    """
    from safetensors.numpy import load_file

    model_dir = Path(model_dir)
    index = model_dir / "model.safetensors.index.json"
    tensors: dict[str, np.ndarray] = {}
    if index.exists():
        weight_map = json.loads(index.read_text())["weight_map"]
        for shard in sorted(set(weight_map.values())):
            tensors.update(load_file(model_dir / shard))
        return tensors
    single = model_dir / "model.safetensors"
    if single.exists():
        return load_file(single)
    candidates = sorted(model_dir.glob("*.safetensors"))
    if not candidates:
        raise FileNotFoundError(f"no safetensors files under {model_dir}")
    for path in candidates:
        tensors.update(load_file(path))
    return tensors


def linear(tensors: dict, name: str) -> np.ndarray:
    """HF nn.Linear weight [out, in] → matmul layout [in, out]."""
    return np.ascontiguousarray(tensors[name].T)


def maybe_bias(params: dict, key: str, tensors: dict, name: str) -> None:
    """Attach a bias parameter when the checkpoint has one."""
    if name in tensors:
        params[key] = tensors[name]
