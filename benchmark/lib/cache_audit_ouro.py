"""What the program keeps in its looped K/V cache, against the
reference's rows: the part of ``correct`` that the cache's layout and
its precision move.

No emitted token shows whether pass 3 read rows of its own or whether a
row holds 16 bits or 8, so the rows themselves are compared. Called by
``reference_ouro.py`` in its own process, after the dataflow has exited
and while the chip's memory is still free: the program's engine —
``llm_server``'s choice of module, its ``make_engine`` under the cell's
node environment, the same checkpoint, the module's own default pool,
the prefix cache on — serves each sampled prompt again for ``decode``
tokens, all of them at once beside filler streams (chunked prefill
between windows, windows with a dozen live rows). When an audited
stream has emitted its tokens and is still alive, the K rows of every
position it holds are read from the pool through its block table at
pass 0's first two layers and at the last layer of every pass.
``reference_ouro.py`` then teacher-forces what that engine emitted and
compares. This audits the program (module, loader, engine, environment,
both programs, the pool's layout), not the memory of the server that
served the window.

A reading is rms(a - b) / rms(b) over rows (:func:`compare`). The
reference gives each entry three times: float32 throughout,
``as_stated`` (the configuration's precision: a product's operands in
the compute dtype, sums and the residual stream float32) and
``bf16_residual`` (the same with the stream held to bfloat16, the
control). What the chip showed (PERF.md section 6, PR 35): at bfloat16
the computation is reproduced to the bit or not at all. One rounding
that falls the other way, from a sum taken in another order, moves the
next roundings with a likelihood that grows with the distance, so within
two or three products a row is as far from the reference at the same
precision as from float32. The readings are chosen for that:

* ``first`` at (pass 0, layer 0), against ``as_stated``: the embedding,
  one norm, ``k_proj`` and the rotary, reproduced to a rounding in some
  ten thousand. ``first_8bit`` is the control for the rows' own width:
  the program's rows rounded to 8 bits with a scale a row and head
  (``max|row| / 127``, what ``ops.decode_block.kv_quant_rows`` would
  store) and back, i.e. what an int8 K/V pool would hold.
* ``lead_rows_same`` at (pass 0, layer 1), two adds down the stream: of
  the prompt's first ``LEAD_ROWS`` rows (few rows before them to inherit
  a stray rounding from, all from the first chunk), how many lie within
  ``SAME_ROW`` of ``as_stated``, i.e. were computed as that precision
  computes them. ``lead_rows_same_bf16_residual`` is the control in the
  program's place: with the stream rounded after each add no row is.
* ``deep`` at (pass 0, the last layer), against ``as_stated``: 95
  sublayers down, where every row has long gone its own way and the
  reading is the size of bfloat16's noise; ``deep_bf16_residual``, the
  control's, is twice that, which is all the room this depth gives.
* ``by_pass``: the last layer's readings pass by pass, printed and not
  held: the program from float32 and from ``as_stated``, the control
  from ``as_stated``, and ``as_stated`` from float32, which is the
  cause shown (bfloat16 operands alone, in the reference's own
  arithmetic, lie as far from float32 as the program does, and the
  distance doubles from pass to pass).
* ``passes_apart``: the distance between the program's own rows of pass
  0 and of the last pass in the last layer: a pass's own rows are as far
  from pass 0's as from anything (about 1.4), a pool that shared them
  would read 0.
"""

from __future__ import annotations

import os
import time

from cache_audit_kimi_k2 import rel_err  # beside this file: rms(got - want) / rms(want)

FILLERS = 8
#: at (pass 0, layer 1): the rows of a prompt's first positions that are
#: looked at, and how near a row lies that the program computed as the
#: stated precision computes it (rms over rms)
LEAD_ROWS = 32
SAME_ROW = 1e-3


def through_8_bits(rows):
    """Rows ``[T, KV, hd]`` rounded to int8 with one scale a row and
    head, then back to float32."""
    import numpy as np

    scale = np.maximum(np.abs(rows).max(-1, keepdims=True) / 127.0, 1e-30)
    return (np.clip(np.round(rows / scale), -127, 127) * scale).astype(np.float32)


def entries(passes: int, layers: int) -> list[tuple[int, int]]:
    """The audited cache entries, as (pass, layer): pass 0's first two
    layers, then the last layer's of every pass."""
    last = layers - 1
    return sorted({(0, 0), (0, min(1, last))} | {(t, last) for t in range(passes)})


def fillers(prompts: list[list[int]], n: int, decode: int) -> list[tuple[list[int], int]]:
    """``n`` short (prompt, max_new) pairs made of the sampled prompts'
    ids (rotated: no two alike, none a prefix of an audited prompt)."""
    out = []
    for k in range(n):
        base = prompts[k % len(prompts)]
        turn = (7 * k + 3) % len(base)
        rotated = (base[turn:] + base[:turn])[: 16 + 24 * (k % 4)]
        out.append((rotated, max(1, decode * (1 + k % 4) // 2)))
    return out


def held_rows(module, engine, cfg, slot, n: int) -> list:
    """The K rows ``[n, KV, hd]`` float32 of a live slot's first ``n``
    positions at each audited entry."""
    import jax.numpy as jnp
    import numpy as np

    pages = slot.pages[: -(-n // engine.page_size)]
    out = []
    for t, layer in entries(cfg.passes, cfg.layers):
        k, _ = module.entry_pages(engine.pools, cfg, t, layer, pages)
        k = np.asarray(k.astype(jnp.float32))  # [pages, KV, page, hd]
        out.append(k.transpose(0, 2, 1, 3).reshape(-1, *k.shape[1::2])[:n])
    return out


def serve(checkpoint: str, env: dict, prompts: list[list[int]], decode: int) -> dict:
    """Serve ``prompts`` for ``decode`` tokens each, together, beside
    fillers. -> ``{"emitted": [tokens a prompt], "rows": [[K rows an
    entry] a prompt], ...}``; ``rows[j][e]`` covers the prompt and every
    emitted token but the last (whose row no tick has written yet)."""
    t0 = time.perf_counter()
    os.environ.update({k: str(v) for k, v in env.items()})
    os.environ.setdefault("DORA_INT8_DECODE", "1")  # as llm_server.main does
    from dora_tpu.models.hf.loader import read_config
    from dora_tpu.nodehub import llm_server

    module = llm_server.model_module(read_config(checkpoint).get("model_type"))
    cfg, params = module.load(checkpoint, max_seq=int(os.environ.get("DORA_MAX_SEQ", "2048")))
    engine = llm_server.make_engine(module.quantize_decode(params, cfg), cfg, module=module)
    # alive past its last audited token: the rows are read through the
    # slot's own block table
    spare = 2 * engine.window + 2
    queue = []
    for k, (prompt, max_new) in enumerate(fillers(prompts, FILLERS, decode)):
        queue.append((f"filler-{k}", prompt, max_new))
        if k < len(prompts):
            queue.append((f"audit-{k}", prompts[k], decode + spare))
    emitted: dict[str, list[int]] = {}
    rows: dict[str, list] = {}
    in_slots = windows = 0
    audited = [f"audit-{k}" for k in range(len(prompts))]
    while len(rows) < len(audited):
        while queue and engine.can_admit(len(queue[0][1]), queue[0][2]):
            rid, prompt, max_new = queue.pop(0)
            engine.submit(rid, prompt, max_new)
            emitted[rid] = []
        in_slots += engine.active
        windows += 1
        for rid, token, _done in engine.step():
            emitted[rid].append(token)
        for k, rid in enumerate(audited):
            if rid not in rows and len(emitted.get(rid, ())) >= decode:
                slot = next(s for s in engine.slots
                            if s is not None and s.request_id == rid)
                emitted[rid] = emitted[rid][:decode]
                rows[rid] = held_rows(module, engine, cfg, slot,
                                      len(prompts[k]) + decode - 1)
                engine.preempt(rid)
        if windows > 64 * (len(audited) + FILLERS):
            raise RuntimeError(f"audit: {sorted(set(audited) - set(rows))} never got there")
    counters = engine.model_counters()
    out = {
        "emitted": [emitted[rid] for rid in audited],
        "rows": [rows[rid] for rid in audited],
        "entries": entries(cfg.passes, cfg.layers),
        "streams": len(emitted), "windows": windows,
        "streams_in_slots_a_window": in_slots / max(windows, 1),
        "pool_pages": engine.allocator.num_pages,
        "prefix_hits": engine.prefix_cache.hits if engine.prefix_cache else None,
        "loop_exit_before_last": counters.get("loop_exit_before_last"),
    }
    del engine, params
    out["seconds"] = time.perf_counter() - t0
    return out


def row_errs(got, want):
    """rms(got - want) / rms(want) of every row ``[n, KV, hd]`` -> ``[n]``."""
    import numpy as np

    got, want = (np.asarray(a, np.float64).reshape(len(a), -1) for a in (got, want))
    return np.sqrt(((got - want) ** 2).mean(-1) / np.maximum((want ** 2).mean(-1), 1e-300))


def compare(served_rows: list, reference_rows: dict, at: list, prompt_rows: int) -> dict:
    """``served_rows[e]``: one sample's K rows ``[n, KV, hd]`` at each
    audited entry ``at[e]`` (:func:`entries`); ``reference_rows[variant][e]``
    the reference's (``reference_ouro.ROWS_OF``), which may hold more
    positions; ``prompt_rows``: how many of the rows are the prompt's. ->
    the sample's readings and the controls."""
    want = {v: [r[: len(s)] for s, r in zip(served_rows, rows)]
            for v, rows in reference_rows.items()}
    exact, stated, control = (want[v] for v in ("as_published", "as_stated", "bf16_residual"))
    read = {tuple(entry): {
        "entry": list(entry),
        "float32": rel_err(served_rows[e], exact[e]),
        "as_stated": rel_err(served_rows[e], stated[e]),
        "bf16_residual": rel_err(control[e], stated[e]),
        "stated_from_float32": rel_err(stated[e], exact[e]),
    } for e, entry in enumerate(at)}
    where = {tuple(entry): e for e, entry in enumerate(at)}
    passes, last = 1 + max(t for t, _ in read), max(layer for _, layer in read)
    second = where[0, min(1, last)]
    lead = min(LEAD_ROWS, prompt_rows)
    near = row_errs(served_rows[second][:lead], stated[second][:lead])
    near_control = row_errs(control[second][:lead], stated[second][:lead])
    return {
        "first": read[0, 0]["as_stated"],
        "first_float32": read[0, 0]["float32"],
        "first_8bit": rel_err(through_8_bits(served_rows[0]), stated[0]),
        "lead_rows": lead,
        "lead_rows_same": int((near < SAME_ROW).sum()),
        "lead_rows_same_bf16_residual": int((near_control < SAME_ROW).sum()),
        "lead_row_least_bf16_residual": float(near_control.min()),
        "lead_row_errs": sorted(round(float(x), 6) for x in near),
        "second": read[0, min(1, last)],
        "deep": read[0, last]["as_stated"],
        "deep_bf16_residual": read[0, last]["bf16_residual"],
        "by_pass": [read[t, last] for t in range(passes)],
        "rows": len(served_rows[0]),
        "passes_apart": rel_err(served_rows[where[0, last]],
                                served_rows[where[passes - 1, last]]),
    }
