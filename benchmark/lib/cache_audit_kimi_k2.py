"""What the program keeps in its latent cache, against the reference's
rows: the part of ``correct`` that the cache's precision moves.

No emitted token shows whether the cache holds 16 bits or 8 (an 8-bit
row with a scale of its own is off by about bf16's own step once the
softmax has averaged over a context), so the rows themselves are
compared. Called by ``reference_kimi_k2.py`` in its own process, after
the dataflow has exited: the program's engine — ``llm_server``'s choice
of module, its ``make_engine`` under the cell's node environment, the
same checkpoint, the module's own default pool, the prefix cache on —
prefills each sampled prompt through ``PagedBatchEngine``; when the
stream's first token is out, its pages are read from the pool, layer
by layer, through the stream's block table, and compared with the
``(c_kv, k_pe)`` rows that the float32 reference computed for the same
prompt. A prompt whose document an earlier sample left in the prefix
cache is read from those cached pages. This audits the program (module,
loader, engine, environment), not the memory of the server that served
the window; ``docs_measure`` holds that server to its own byte count.

For each sample and layer: ``rel_err`` = rms(program - reference) /
rms(reference) over the prompt's rows. ``rel_err_8bit`` is the control,
computed in every run: the same program rows rounded to 8 bits with a
scale a row (``max|row| / 127``) and back to bf16 — what an int8 latent
cache would hold. Layer 0 is where the verdict is taken: there the
program's own error is two or three bf16 roundings, and the 8-bit
step stands clear of it; deeper layers inherit the residual stream's
bf16 noise, which hides it.
"""

from __future__ import annotations

import os
import time


def rel_err(got, want) -> float:
    import numpy as np

    want = want.astype(np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def through_8_bits(rows):
    """Rows [T, n] rounded to int8 with one scale a row, then to bf16."""
    import ml_dtypes
    import numpy as np

    scale = np.maximum(np.abs(rows).max(-1, keepdims=True) / 127.0, 1e-30)
    back = np.clip(np.round(rows / scale), -127, 127) * scale
    return back.astype(ml_dtypes.bfloat16).astype(np.float32)


def audit(checkpoint: str, env: dict, prompts: list[list[int]],
          reference_rows: list[list], latent: int) -> dict:
    """``reference_rows[sample][layer]`` is ``[len(prompt), latent]``
    float32. Returns the two error tables ``[sample][layer]``."""
    import jax.numpy as jnp
    import numpy as np

    t0 = time.perf_counter()
    os.environ.update({k: str(v) for k, v in env.items()})  # the rank too
    os.environ.setdefault("DORA_INT8_DECODE", "1")  # as llm_server.main does
    from dora_tpu.models.hf.loader import read_config
    from dora_tpu.nodehub import llm_server

    module = llm_server.model_module(read_config(checkpoint).get("model_type"))
    cfg, params = module.load(checkpoint, max_seq=int(os.environ.get("DORA_MAX_SEQ", "2048")))
    engine = llm_server.make_engine(module.quantize_decode(params, cfg), cfg, module=module)
    page = engine.page_size
    errs, control = [], []
    for i, (prompt, want) in enumerate(zip(prompts, reference_rows)):
        rid = f"audit-{i}"
        engine.submit(rid, prompt, 2 * engine.window + 2)  # alive past its first token
        for _ in range(len(prompt) // engine.chunk + 2):
            if any(r == rid for r, _, _ in engine.step()):
                break
        else:
            raise RuntimeError(f"{rid}: no first token after its chunks")
        slot = next(s for s in engine.slots if s is not None and s.request_id == rid)
        pages = jnp.asarray(slot.pages[: -(-len(prompt) // page)], jnp.int32)
        errs.append([])
        control.append([])
        for layer, ref in enumerate(want):
            held = np.asarray(engine.pools[str(layer)]["kv"][pages].astype(jnp.float32))
            rows = held.reshape(-1, held.shape[-1])[: len(prompt), :latent]
            errs[-1].append(rel_err(rows, ref))
            control[-1].append(rel_err(through_8_bits(rows), ref))
        engine.preempt(rid)
    return {
        "rel_err": errs, "rel_err_8bit": control,
        "prefix_hits": engine.prefix_cache.hits if engine.prefix_cache else None,
        "pool_pages": engine.allocator.num_pages,
        "seconds": time.perf_counter() - t0,
    }
