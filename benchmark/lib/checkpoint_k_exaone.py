"""Seeded random rank-0 checkpoint of an ``exaone_moe`` (K-EXAONE)
configuration: bf16 safetensors under the HF tensor names (EXAONE-4's
for attention and norms, DeepSeek-V3's for the expert layer: an
assumption, ``assumed.tensor_names``), one file a layer, the experts of
ONE rank only (a shard checkpoint, as a launcher of an expert group
would hand each chip), ``config.json`` and the synthetic tokenizer of
``lib/checkpoint.py``.

``config`` is the benchmark's configuration file's top level, where
``num_experts`` counts the experts HELD here; the ``config.json``
written restores HF's meaning (``num_experts`` = held x ``ep_size``, the
router's width). Which rank a process is, its launcher says
(``DORA_EP_RANK``); the index's metadata names the rank of this shard.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path


def hf_config(config: dict) -> dict:
    """The checkpoint's config.json: HF's meaning of the expert keys."""
    return {**config, "num_experts": config["num_experts"] * config["ep_size"]}


def layer_shapes(config: dict, i: int, rank: int = 0) -> dict[str, tuple]:
    """HF tensor names of layer ``i`` -> shapes (``[out, in]``), with the
    experts ``rank`` holds under their GLOBAL numbers."""
    d, hd = config["hidden_size"], config["head_dim"]
    q, kv = config["num_attention_heads"] * hd, config["num_key_value_heads"] * hd
    p = f"model.layers.{i}."
    t = {
        p + "input_layernorm.weight": (d,),
        p + "post_attention_layernorm.weight": (d,),
        p + "self_attn.q_proj.weight": (q, d),
        p + "self_attn.k_proj.weight": (kv, d),
        p + "self_attn.v_proj.weight": (kv, d),
        p + "self_attn.o_proj.weight": (d, q),
        p + "self_attn.q_norm.weight": (hd,),
        p + "self_attn.k_norm.weight": (hd,),
    }

    def ffn(prefix: str, width: int) -> None:
        t[prefix + "gate_proj.weight"] = (width, d)
        t[prefix + "up_proj.weight"] = (width, d)
        t[prefix + "down_proj.weight"] = (d, width)

    if config["mlp_layer_types"][i] == "dense":
        ffn(p + "mlp.", config["intermediate_size"])
        return t
    held = config["num_experts"]
    t[p + "mlp.gate.weight"] = (held * config["ep_size"], d)
    t[p + "mlp.gate.e_score_correction_bias"] = (held * config["ep_size"],)
    ffn(p + "mlp.shared_experts.",
        config["moe_intermediate_size"] * config["num_shared_experts"])
    for e in range(rank * held, (rank + 1) * held):
        ffn(f"{p}mlp.experts.{e}.", config["moe_intermediate_size"])
    return t


# ``checkpoint_kimi_k2.write_checkpoint`` (every matrix uniform with standard
# deviation 0.02, norms 1, the routing bias uniform in +-0.01, all drawn from
# the seed, a stream and a file a layer, the index, config.json and the
# tokenizer) over this module's shapes and config.json: a private copy of
# that module with the two functions that know the model put in
_spec = importlib.util.spec_from_file_location(
    "bench_checkpoint_kimi_k2_for_k_exaone",
    Path(__file__).with_name("checkpoint_kimi_k2.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)
_base.layer_shapes, _base.hf_config = layer_shapes, hf_config
write_checkpoint = _base.write_checkpoint
