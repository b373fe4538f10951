"""What the program keeps in its two cache kinds, against the reference's
K and V rows: the global layers' pages and the window layers' rings.

No emitted token shows where a row lies in a ring or whether it holds 16
bits or 8, so the rows themselves are compared. Called by
``reference_k_exaone.py`` in its own process, after the dataflow has
exited and the reference's arrays are dropped: the program's engine —
``llm_server``'s choice of module, its ``make_engine`` under the cell's
node environment, the same checkpoint, the module's own default pool —
serves each sampled prompt again for ``decode`` tokens, all of them at
once beside filler streams (chunked prefill between windows, windows
with a dozen live rows, short rows beside the long one). When an audited
stream has emitted its tokens and is still alive, its rows are read at
the audited layers (:func:`entries`: layer 0, the first global layer,
the last window layer, the last layer): a global layer's rows of every
prompt position from the pool through the stream's block table, a
window layer's whole ring of its slot, whose row ``j`` holds the last
position written that is ``j`` modulo the window.

A reading is rms(program - reference) / rms(reference) over rows of K|V
(:func:`compare`). The reference's rows of a PROMPT position do not
depend on what follows, so they are the ones its pass over prompt +
the timed run's tokens computed. Rows the audit engine's own decode
ticks wrote are compared at layer 0 alone, where a row depends on its
token and position and on nothing before it (``decode0``): that is the
tick's ring write (row ``p % window``, rotary at ``p``, the active bit),
read back. What the ticks READ from ring and pages is held by the
tokens.

* ``first`` at layer 0 (a window layer: the ring), prompt and decode
  rows together: embedding, one norm, the projection, the head norm, the
  rotary. ``first_8bit`` is the control for the rows' own width: the
  program's rows through 8 bits with one scale a row (what an int8
  cache would hold).
* ``global_first`` at the first global layer, every prompt position from
  the pool. ``global_first_rope_on_global``: the reference with rotary on
  the global layers in the program's place (where it ran that control).
* ``deep``: the largest reading of the remaining audited layers (the
  last window layer's ring, the last layer). ``deep_full_everywhere``:
  the reference with no band mask in the program's place at the last
  layer (where it ran that control).
"""

from __future__ import annotations

import os
import time

# beside this file: rms(got - want) / rms(want); rows through int8 with one
# scale a row and back
from cache_audit_kimi_k2 import rel_err, through_8_bits

FILLERS = 8


def entries(layer_types: list[str]) -> list[int]:
    """The audited layers: layer 0, the first global layer, the last
    window layer, the last layer."""
    full = [i for i, k in enumerate(layer_types) if k == "full_attention"]
    ring = [i for i, k in enumerate(layer_types) if k == "sliding_attention"]
    return sorted({0, len(layer_types) - 1, *full[:1], *ring[-1:]})


def fillers(prompts: list[list[int]], n: int, decode: int) -> list[tuple[list[int], int]]:
    """``n`` short (prompt, max_new) pairs made of the sampled prompts'
    ids (rotated: no two alike)."""
    out = []
    for k in range(n):
        base = prompts[k % len(prompts)]
        turn = (7 * k + 3) % len(base)
        rotated = (base[turn:] + base[:turn])[: 16 + 24 * (k % 4)]
        out.append((rotated, max(1, decode * (1 + k % 4) // 2)))
    return out


def held(engine, layer_types: list[str], slot_index: int, slot, prompt_rows: int) -> dict:
    """A live slot's rows at the audited layers, float32: its whole ring
    ``[window, 2 * KV * hd]`` of a window layer, its first
    ``prompt_rows`` positions ``[n, 2 * KV * hd]`` of a global layer."""
    import jax.numpy as jnp
    import numpy as np

    pages = jnp.asarray(slot.pages[: -(-prompt_rows // engine.page_size)], jnp.int32)
    rings, paged = {}, {}
    for layer in entries(layer_types):
        if layer_types[layer] == "sliding_attention":
            ring = engine.slot_state[str(layer)]["kv"][slot_index]
            rings[layer] = np.asarray(ring.astype(jnp.float32))
        else:
            rows = engine.pools[str(layer)]["kv"][pages].astype(jnp.float32)
            paged[layer] = np.asarray(rows).reshape(-1, rows.shape[-1])[:prompt_rows]
    return {"rings": rings, "pages": paged}


def serve(checkpoint: str, env: dict, prompts: list[list[int]], decode: int) -> dict:
    """Serve ``prompts`` for at least ``decode`` tokens each, together,
    beside fillers. -> ``{"streams": [{"emitted", "rings", "pages"} a
    prompt], ...}``: ``emitted`` is EVERY token the stream had emitted
    when its rows were read, so its ticks wrote positions ``len(prompt)
    .. len(prompt) + len(emitted) - 2``."""
    t0 = time.perf_counter()
    os.environ.update({k: str(v) for k, v in env.items()})  # the rank too
    os.environ.setdefault("DORA_INT8_DECODE", "1")  # as llm_server.main does
    from dora_tpu.models.hf.loader import read_config
    from dora_tpu.nodehub import llm_server

    config = read_config(checkpoint)
    module = llm_server.model_module(config.get("model_type"))
    cfg, params = module.load(checkpoint, max_seq=int(os.environ.get("DORA_MAX_SEQ", "2048")))
    engine = llm_server.make_engine(module.quantize_decode(params, cfg), cfg, module=module)
    spare = 2 * engine.window + 2  # alive past its last audited token
    queue = []
    for k, (prompt, max_new) in enumerate(fillers(prompts, FILLERS, decode)):
        queue.append((f"filler-{k}", prompt, max_new))
        if k < len(prompts):
            queue.append((f"audit-{k}", prompts[k], decode + spare))
    emitted: dict[str, list[int]] = {}
    streams: dict[str, dict] = {}
    in_slots = windows = 0
    audited = [f"audit-{k}" for k in range(len(prompts))]
    chunks = sum(-(-len(p) // engine.chunk) for p in prompts)
    while len(streams) < len(audited):
        while queue and engine.can_admit(len(queue[0][1]), queue[0][2]):
            rid, prompt, max_new = queue.pop(0)
            engine.submit(rid, prompt, max_new)
            emitted[rid] = []
        in_slots += engine.active
        windows += 1
        for rid, token, _done in engine.step():
            emitted[rid].append(token)
        for k, rid in enumerate(audited):
            if rid not in streams and len(emitted.get(rid, ())) >= decode:
                b, slot = next((b, s) for b, s in enumerate(engine.slots)
                               if s is not None and s.request_id == rid)
                streams[rid] = {"emitted": list(emitted[rid]),
                                **held(engine, config["layer_types"], b, slot, len(prompts[k]))}
                engine.preempt(rid)
        if windows > chunks + 64 * (len(audited) + FILLERS):
            raise RuntimeError(f"audit: {sorted(set(audited) - set(streams))} never got there")
    counters = engine.model_counters()
    out = {
        "streams": [streams[rid] for rid in audited],
        "served": len(emitted), "windows": windows,
        "streams_in_slots_a_window": in_slots / max(windows, 1),
        "pool_pages": engine.allocator.num_pages,
        "pool_layers": sorted(int(k) for k in engine.pools),
        "ring_layers": sorted(int(k) for k in engine.slot_state),
        "kv_bytes_per_token": counters.get("kv_bytes_per_token"),
    }
    del engine, params
    out["seconds"] = time.perf_counter() - t0
    return out


def ring_positions(last: int, window: int):
    """The position each ring row holds once ``last`` was written
    (negative: not this stream's)."""
    import numpy as np

    j = np.arange(window)
    return last - (last - j) % window


def compare(got: dict, reference_rows: dict, decode0, prompt_rows: int,
            layer_types: list[str], window: int) -> dict:
    """``got``: one audited stream of :func:`serve`. ``reference_rows
    [variant][layer]``: the reference's K|V rows ``[>= prompt_rows, 2 *
    KV * hd]`` (``as_served`` always; a control where it ran).
    ``decode0``: layer 0's reference rows of the positions the stream's
    ticks wrote. -> the stream's readings and the controls."""
    import numpy as np

    want = reference_rows["as_served"]
    last = prompt_rows + len(got["emitted"]) - 2
    at = ring_positions(last, window)
    prompt, decoded = (at >= 0) & (at < prompt_rows), at >= prompt_rows
    read = {}
    for layer, ring in got["rings"].items():
        layer = int(layer)
        rows, ref = [ring[prompt]], [want[layer][at[prompt]]]
        if layer == 0:
            rows.append(ring[decoded])
            ref.append(decode0[at[decoded] - prompt_rows])
        read[layer] = (np.concatenate(rows), np.concatenate(ref))
    for layer, rows in got["pages"].items():
        read[int(layer)] = (rows, want[int(layer)][:prompt_rows])
    errs = {layer: rel_err(a, b) for layer, (a, b) in read.items() if len(a)}
    first_global = next(i for i, k in enumerate(layer_types) if k == "full_attention")
    last_layer = len(layer_types) - 1
    rest = [e for layer, e in errs.items() if layer not in (0, first_global)]

    def control(variant: str, layer: int):
        rows = reference_rows.get(variant, {}).get(layer)
        if rows is None:
            return None
        return rel_err(rows[:prompt_rows], want[layer][:prompt_rows])

    return {
        "prompt_tokens": prompt_rows, "emitted": len(got["emitted"]),
        "by_layer": {str(layer): e for layer, e in sorted(errs.items())},
        "first": errs.get(0),
        "first_8bit": rel_err(through_8_bits(read[0][0]), read[0][1]),
        "first_rows": int(len(read[0][0])), "first_decode_rows": int(decoded.sum()),
        "global_first": errs.get(first_global),
        "global_first_rope_on_global": control("rope_on_global", first_global),
        "deep": max(rest) if rest else None,
        "deep_full_everywhere": control("full_everywhere", last_layer),
    }
