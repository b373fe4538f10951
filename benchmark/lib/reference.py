"""The plain reference's verdict on a sample of served requests.

Run as a child of its own after the dataflow has exited and the chip is
free: ``python benchmark/lib/reference.py <in.json>``. For each sampled
request it runs the program's plain forward pass (``qwen2.forward``: no
cache, no paging, no batching) teacher-forced over prompt + emitted
tokens, padded to one length so that it is one cached program, from the
same checkpoint held to the int8 weights alone as ``chip_smoke.py``'s
reference child does, and reports for every emitted token how many bf16
steps it lies below the top of the reference's own logits at its
position. Sampled tokens are not compared: two correct programs part
every 4-10 tokens at bf16 with random weights (PR 21). The last stdout
line is the result.
"""

from __future__ import annotations

import json
import math
import os
import sys


def main() -> int:
    os.environ["DORA_INT8_DECODE"] = "1"  # as llm_server.main does
    os.environ["DORA_INT8_PURE"] = "1"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dora_tpu import backend
    from dora_tpu.models.hf import qwen2

    spec = json.load(open(sys.argv[1]))
    backend.init_compile_cache()
    device = backend.require_accelerator("benchmark reference")
    cfg, params = qwen2.load(spec["checkpoint"], max_seq=spec["max_seq"])
    params = qwen2.quantize_decode(params, cfg)
    pad, max_new = spec["pad_to"], spec["max_new"]

    @jax.jit
    def score(params, padded, start, emitted):
        logits = qwen2.forward(params, cfg, padded[None])[0]
        rows = logits[start - 1 + jnp.arange(max_new)]
        chosen = jnp.take_along_axis(rows, emitted[:, None], axis=1)[:, 0]
        return rows.max(-1), chosen

    rows = []
    for sample in spec["samples"]:
        prompt, emitted = sample["prompt"], sample["emitted"]
        seq = prompt + emitted
        if len(seq) > pad or len(emitted) > max_new:
            raise ValueError(f"sample of {len(seq)} tokens passes pad_to {pad}")
        padded = np.zeros((pad,), np.int32)
        padded[: len(seq)] = seq
        em = np.zeros((max_new,), np.int32)
        em[: len(emitted)] = emitted
        top, chosen = jax.device_get(score(
            params, jnp.asarray(padded), jnp.asarray(len(prompt), jnp.int32),
            jnp.asarray(em),
        ))
        deficits = []
        for k in range(len(emitted)):
            t = float(top[k])
            ulp = 2.0 ** (math.floor(math.log2(abs(t))) - 7) if t else 1.0
            deficits.append((t - float(chosen[k])) / ulp)
        rows.append({
            "i": sample["i"], "prompt_tokens": len(prompt),
            "emitted": len(emitted),
            "max_deficit_bf16_ulps": max(deficits),
            "tokens_off_top": sum(d > 0 for d in deficits),
            "worst_position": int(np.argmax(deficits)),
        })
    print(json.dumps({"device": device, "samples": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
