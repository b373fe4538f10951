"""What the program keeps in its two-leaf pages and what its indexer
picks, against the reference's: the K|V rows and the indexer's keys of
the first and the last layer, and the positions the last layer's rows
picked, in the chunk program AND in the decode tick.

No emitted token shows whether a cached row is held in bf16 or which
rows a tick picked, so they are compared themselves. Called by
``reference_keye_vl2.py`` in its own process, after the dataflow has
exited and BEFORE the reference's arrays exist: the program's engine —
``llm_server``'s choice of module, its ``make_engine`` under the cell's
node environment (the prefix cache off: an audit's engine has none), the
same checkpoint, the module's own default pool, its two programs built
with a look at the selection (``make_paged_engine(picks=True)``: the
served programs with two results more a layer) — is handed each sampled
request's prompt AND the tokens the timed run emitted for it as one
prompt (the chunk program, teacher-forced over the very positions whose
tokens are judged), decodes ``decode`` tokens more of its own (the window
program), all samples at once beside filler streams. While an audited
stream is still seated its pages are read through its block table; what
was kept behind every chunk and every window: the positions every layer's
chunk rows and decode ticks picked (``top_k``'s own ids: the reference
attends THEM, at every layer, so that a near-tie decided the other way is
not counted against the arithmetic) and the last layer's sublayer output
there.

A reading is rms(program - reference) / rms(reference) (:func:`compare`):

* ``kv_rows_first`` / ``kv_rows_last``, ``ik_rows_first`` /
  ``ik_rows_last``: the pages of layer 0 and of the last layer;
  ``kv_rows_first_8bit`` / ``ik_rows_first_8bit``: layer 0's rows through
  8 bits (a control: what an int8 page would hold); ``*_no_qk_norm`` /
  ``*_no_selection``: the same pages against the rows of the reference
  without QK-norm, or attending every row, where that control ran;
* ``attended_rows`` / ``attended_rows_decode``: the last layer's sublayer
  OUTPUT at every eighth chunk row at or past ``topk`` and at every decode
  tick there, against the reference attending the positions the program
  picked; ``*_no_selection``: the same rows against the reference that
  attends every row;
* ``picked_differ`` / ``picked_differ_decode``: the share of the positions
  the program's chunk rows / decode ticks picked at the last layer that
  the reference's own top-k there does not hold; ``picked_rank_gap``: how
  many ranks past the last kept one the worst such position lies under the
  reference's scores, as a share of ``topk``; ``picked_score_gap``: how far
  its score lies under the reference's last kept score, as a share of the
  spread of the row's kept scores (0 = a tie);
  ``picked_differ_unscored``: the share for a picker that never scored
  (the first ``topk`` positions). The reference computes these a row on
  the device (``reference_keye_vl2``: a row's scores are 64 KB).
"""

from __future__ import annotations

import os
import time

# beside this file: the short filler streams made of the samples' ids,
# rms(got - want) / rms(want), and rows through 8 bits
from cache_audit_k_exaone import FILLERS, fillers
from cache_audit_kimi_k2 import rel_err, through_8_bits

#: the sublayer's output is kept for one chunk row in this many (a chunk
#: starts at a multiple of it, so the rows kept are the positions that are
#: multiples of it)
ATTENDED_EVERY = 8


def held(engine, layers: tuple, slot, rows: int) -> dict:
    """A live slot's pages at the audited ``layers`` (first, last),
    float32: the first ``rows`` K|V rows and indexer keys through its
    block table."""
    import jax.numpy as jnp
    import numpy as np

    pages = jnp.asarray(slot.pages[: -(-rows // engine.page_size)], jnp.int32)
    out = {}
    for name, layer in zip(("first", "last"), layers):
        pool = engine.pools[str(layer)]
        kv = np.asarray(pool["kv"][pages].astype(jnp.float32))
        ik = np.asarray(pool["ik"][pages].astype(jnp.float32))
        width = ik.shape[-1] * ik.shape[-2] // engine.page_size  # keys share rows
        out[f"kv_{name}"] = kv.reshape(-1, kv.shape[-1])[:rows]
        out[f"ik_{name}"] = ik.reshape(-1, width)[:rows]
    return out


def ticks(windows: list, first_row: int, rows: int) -> dict:
    """A slot's windows ``[(the first tick's position, picked [L, K, n],
    attended [K, dim])]`` -> the decode ticks that wrote positions
    ``first_row..rows - 1``, in order: ``{"picked_decode" [L, rows -
    first_row, n], "attended_decode" [rows - first_row, dim]}``. A row
    that is missing is the audit's fault, and raises."""
    import numpy as np

    by_row = {first + j: (p[:, j], a[j])
              for first, p, a in windows for j in range(p.shape[1])}
    kept = [by_row[t] for t in range(first_row, rows)]
    return {"picked_decode": np.stack([p for p, _ in kept], 1),
            "attended_decode": np.stack([a for _, a in kept])}


def serve(checkpoint: str, env: dict, samples: list[list[int]], decode: int) -> dict:
    """Prefill each of ``samples`` (a timed request's prompt + its emitted
    tokens) and decode at least ``decode`` tokens more, together, beside
    fillers. -> ``{"streams": [{"emitted", "picked", "kv_first", ...} a
    sample], ...}``: ``emitted`` is EVERY token the stream had emitted
    when its pages were read, so its ticks wrote positions ``len(sample)
    .. len(sample) + len(emitted) - 2``; ``picked [L, len(sample), topk]``
    int16 are the positions every layer's chunk rows picked, ``attended``
    the last layer's sublayer output at every eighth of them,
    ``picked_decode`` / ``attended_decode`` the same of every such tick
    (:func:`ticks`)."""
    import numpy as np

    t0 = time.perf_counter()
    os.environ.update({k: str(v) for k, v in env.items()})  # the rank too
    os.environ.setdefault("DORA_INT8_DECODE", "1")  # as llm_server.main does
    os.environ["DORA_PREFIX_CACHE"] = "0"  # picks and a prefix cache: refused
    from dora_tpu.models.hf.loader import read_config
    from dora_tpu.nodehub import llm_server

    config = read_config(checkpoint)
    module = llm_server.model_module(config.get("model_type"))
    cfg, params = module.load(checkpoint, max_seq=int(os.environ.get("DORA_MAX_SEQ", "2048")))
    engine = llm_server.make_engine(module.quantize_decode(params, cfg), cfg,
                                    module=module, picks=True)
    layers = (0, config["num_hidden_layers"] - 1)
    look, chunk_program, window_program = (
        engine.selection, engine.chunk_prefill, engine.window_step)
    picked: dict[int, list] = {}
    attended: dict[int, list] = {}
    ticked: dict[int, list] = {}

    def every_layer(kind):
        return np.stack([np.asarray(one["picked"]) for one in look[kind]]).astype(np.int16)

    def chunk_prefill(ids, pools, position, bt, valid, slot, state):
        out = chunk_program(ids, pools, position, bt, valid, slot, state)
        picked.setdefault(int(slot), []).append(every_layer("chunk")[:, : int(valid)])
        attended.setdefault(int(slot), []).append(np.asarray(
            look["chunk"][-1]["attended"][: int(valid) : ATTENDED_EVERY].astype("float32")))
        return out

    def window_step(tokens, pools, positions, bts, active, *rest):
        first, live = np.asarray(positions), np.asarray(active)
        out = window_program(tokens, pools, positions, bts, active, *rest)
        ids = every_layer("window")  # [L, K, B, topk]
        outs = np.asarray(look["window"][-1]["attended"])
        for b in np.flatnonzero(live):
            ticked.setdefault(int(b), []).append(
                (int(first[b]), ids[:, :, b], outs[:, b]))
        return out

    engine.chunk_prefill, engine.window_step = chunk_prefill, window_step
    spare = 2 * engine.window + 2  # alive past its last audited token
    queue = []
    for k, (prompt, max_new) in enumerate(fillers(samples, FILLERS, decode)):
        queue.append((f"filler-{k}", prompt, max_new))
        if k < len(samples):
            queue.append((f"audit-{k}", samples[k], decode + spare))
    emitted: dict[str, list[int]] = {}
    streams: dict[str, dict] = {}
    in_slots = windows = 0
    audited = [f"audit-{k}" for k in range(len(samples))]
    chunks = sum(-(-len(p) // engine.chunk) for p in samples)
    while len(streams) < len(audited):
        while queue and engine.can_admit(len(queue[0][1]), queue[0][2]):
            rid, prompt, max_new = queue.pop(0)
            engine.submit(rid, prompt, max_new)
            emitted[rid] = []
            b = next(b for b, s in enumerate(engine.slots)
                     if s is not None and s.request_id == rid)
            # this slot's chunks and ticks are this stream's now
            picked[b], attended[b], ticked[b] = [], [], []
        in_slots += engine.active
        windows += 1
        for rid, token, _done in engine.step():
            emitted[rid].append(token)
        for k, rid in enumerate(audited):
            if rid not in streams and len(emitted.get(rid, ())) >= decode:
                b, slot = next((b, s) for b, s in enumerate(engine.slots)
                               if s is not None and s.request_id == rid)
                rows = len(samples[k]) + len(emitted[rid]) - 1
                streams[rid] = {"emitted": list(emitted[rid]),
                                "prompt_rows": len(samples[k]),
                                "picked": np.concatenate(picked[b], 1),
                                "attended": np.concatenate(attended[b]),
                                **ticks(ticked[b], len(samples[k]), rows),
                                **held(engine, layers, slot, rows)}
                engine.preempt(rid)
        if windows > chunks + 64 * (len(audited) + FILLERS):
            raise RuntimeError(f"audit: {sorted(set(audited) - set(streams))} never got there")
    report = engine.model_counters()
    out = {
        "streams": [streams[rid] for rid in audited],
        "served": len(emitted), "windows": windows,
        "streams_in_slots_a_window": in_slots / max(windows, 1),
        "pool_pages": engine.allocator.num_pages,
        "pool_leaves": sorted(engine.pools["0"]),
        "kv_bytes_per_token": report.get("kv_bytes_per_token"),
        "layers": list(layers),
    }
    del engine, params
    out["seconds"] = time.perf_counter() - t0
    return out


def picked_summary(per_row, first_row: int, rows: int, topk: int) -> dict:
    """The reference's per-row comparison of the program's picks with its
    own top-k (``per_row [T, 4]``: positions that differ, the worst one's
    rank gap and score gap, positions a picker that never scored would
    miss) over rows ``first_row..rows - 1``."""
    import numpy as np

    rows = max(rows, first_row)
    mine = np.asarray(per_row[first_row:rows], np.float64)
    total = (rows - first_row) * topk
    if not total:
        return {"picked_rows": 0, "picked_differ": None, "picked_differ_unscored": None,
                "picked_rank_gap": None, "picked_score_gap": None}
    return {"picked_rows": rows - first_row,
            "picked_differ": float(mine[:, 0].sum() / total),
            "picked_rank_gap": float(mine[:, 1].max()),
            "picked_score_gap": float(mine[:, 2].max()),
            "picked_differ_unscored": float(mine[:, 3].sum() / total)}


def compare(got: dict, ref: dict, controls: dict, topk: int) -> dict:
    """``got``: one audited stream of :func:`serve`. ``ref``: the
    reference's ``{"kv_first", "ik_first", "kv_last", "ik_last",
    "attended", "per_row"}`` over the same tokens. ``controls``: ``{name:
    {...}}`` of the reference's other variants where they ran on this
    sample. -> the stream's readings and the controls'."""
    import numpy as np

    rows, chunk_rows = len(got["kv_first"]), got["prompt_rows"]
    ticks_from = max(chunk_rows, topk)  # the first tick that selects
    out = {"rows": rows, "emitted": len(got["emitted"])}
    for key in ("kv_first", "ik_first", "kv_last", "ik_last"):
        name = key.replace("_", "_rows_")
        out[name] = rel_err(got[key], ref[key][:rows])
    for key in ("kv_first", "ik_first"):
        out[key.replace("_", "_rows_") + "_8bit"] = rel_err(
            through_8_bits(got[key]), ref[key][:rows])
    out.update(picked_summary(ref["per_row"], topk, chunk_rows, topk))
    out.update({f"{k}_decode": v for k, v in picked_summary(
        ref["per_row"], ticks_from, rows, topk).items()})
    # the sublayer's output where a row selects: every ATTENDED_EVERY-th
    # chunk row, every decode tick
    first = -(-topk // ATTENDED_EVERY)
    mine = got["attended"][first:]
    at = (first + np.arange(len(mine))) * ATTENDED_EVERY
    ticks_at = np.arange(ticks_from, rows)
    for key, theirs in (("", ref), ("_no_selection", controls.get("no_selection"))):
        if theirs is None:
            continue
        if len(mine):
            out[f"attended_rows{key}"] = rel_err(mine, theirs["attended"][at])
        if len(ticks_at):
            out[f"attended_rows_decode{key}"] = rel_err(
                got["attended_decode"][ticks_from - chunk_rows :], theirs["attended"][ticks_at])
    # the pages against a control's: what a program with that fault would hold
    for name, other in controls.items():
        for key in ("kv_first", "ik_first", "kv_last", "ik_last"):
            if other.get(key) is not None:
                out[f"{key.replace('_', '_rows_')}_{name}"] = rel_err(
                    got[key], other[key][:rows])
    return out
