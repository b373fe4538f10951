"""What the program keeps in its three cache kinds, against the
reference's: the delta-rule layers' float32 states, the sparse-latent
layer's latent rows and its pooled indexer rows, and the blocks its
indexer picked, in the chunk program AND in the decode tick.

No emitted token shows whether a state is held in float32, whether a
pooled row is the mean of its block or which blocks a row picked, so they
are compared themselves. Called by ``reference_glm5_next.py`` in its own
process, after the dataflow has exited and BEFORE the reference's arrays
exist: the program's engine — ``llm_server``'s choice of module, its
``make_engine`` under the cell's node environment, the same checkpoint,
the module's own default pool, its two programs built with a look at the
selection (``make_paged_engine(picks=True)``: the served programs with two
results more a sparse-latent layer) — is handed each sampled request's
prompt AND the tokens the timed run emitted for it as one prompt (the
chunk program, teacher-forced over the very positions whose tokens are
judged), decodes ``decode`` tokens more of its own (the window program),
all samples at once beside filler streams (chunked prefill between
windows, windows with a dozen live rows). While an audited stream is
still seated, its caches are read: the states of the first and the last
delta-rule layer at its last position, the last sparse-latent layer's
latent rows and pooled indexer rows of every position written; and what
was kept behind every chunk and every window: the blocks each chunk row
and each decode tick picked (``top_k``'s own result) and the
sparse-latent sublayer's output there (``engine.selection``).

The reference then runs over the same tokens (timed prompt + timed tokens
+ the audit's own decode tokens), every row attending the blocks the
PROGRAM picked at it, so its state at the last position is what a
faultless program would hold there, through chunks and ticks alike. A
reading is rms(program - reference) / rms(reference) (:func:`compare`):

* ``state_first`` / ``state_deep``: layer 0's and the last delta-rule
  layer's state; ``state_*_<control>``: the same against a control of the
  reference (the other gate, one residual stream),
  ``state_*_<control>_alone``: that control against the reference, which
  is what it would read in the program's place. No error limit can tell a
  2-byte state: the reference with its state and the sums read from it
  held to bf16 at EVERY step reads 0.0003 at layer 0 and 0.02-0.09 at the
  last delta-rule layer against the reference (the decays forget a
  rounding within a few steps), under the program's own 0.0042-0.0045 and
  0.03-0.14, which its bf16 inputs leave (my chip run, PR 43, call g7: 7
  samples of 3,012-3,937 rows). The bit patterns can:
  ``state_2byte_share`` is the share of the two states' float32 values
  that bf16 could hold (low 16 bits zero), near 0 for the program, 1.0 for
  its states through bf16 (``state_2byte_share_bf16``; ``state_first_bf16``
  is layer 0's error through that ONE rounding).
* ``latent_rows`` / ``index_rows``: the last sparse-latent layer's pages.
* ``attended_rows``: that layer's OUTPUT at every eighth chunk row at or
  past ``index_topk``, ``attended_rows_decode``: at every decode tick
  there, against the reference attending the blocks the program picked.
  ``attended_rows_no_selection`` / ``attended_rows_decode_no_selection``:
  the same rows against the reference that attends every row: what a
  chunk or a tick that ignored its indexer would hold. (The sparse-latent
  layer is the last one, so nothing it computes is cached downstream, and
  with thousands of rows in a softmax the logits barely feel which were
  attended: its output is read itself.)
* ``picked_differ`` / ``picked_differ_decode``: the share of the blocks
  the program's chunk rows / decode ticks picked that the reference's own
  top-k does not hold, over rows at or past ``index_topk``.
  ``picked_differ_unscored`` (``_decode``): the same share for a picker
  that never scored (the first ``index_topk / index_kpool`` blocks, what
  the program attends below ``index_topk``): the faulty reading, taken on
  the longest sample. Printed beside them, with no limit:
  ``picked_score_gap`` (for a block that differs, how far the reference's
  score of it lies under the reference's own last kept score, as a share
  of the spread of the row's kept scores; 0 = a tie) and
  ``picked_rank_gap`` (how many ranks past the last kept one the worst
  such block lies under the reference's scores, as a share of the blocks
  kept).
"""

from __future__ import annotations

import os
import time

# beside this file: the short filler streams made of the samples' ids, and
# rms(got - want) / rms(want)
from cache_audit_k_exaone import FILLERS, fillers
from cache_audit_kimi_k2 import rel_err

#: the sparse-latent sublayer's output is kept for one chunk row in this many
#: (a chunk starts at a multiple of it, so the rows kept are the positions
#: that are multiples of it)
ATTENDED_EVERY = 8


def audited_layers(layer_types: list[str]) -> dict:
    """The audited layers: the first and the last delta-rule layer, the
    last sparse-latent layer."""
    kda = [i for i, k in enumerate(layer_types) if k == "linear_attention"]
    dsa = [i for i, k in enumerate(layer_types) if k == "deepseek_sparse_attention"]
    return {"state_first": kda[0], "state_deep": kda[-1], "pages": dsa[-1]}


def held(engine, layers: dict, slot_index: int, slot, rows: int, kpool: int) -> dict:
    """A live slot's caches at the audited layers, float32: the two
    states, the first ``rows`` latent rows and the first ``rows // kpool``
    pooled indexer rows through its block table."""
    import jax.numpy as jnp
    import numpy as np

    pages = jnp.asarray(slot.pages[: -(-rows // engine.page_size)], jnp.int32)
    pool = engine.pools[str(layers["pages"])]
    kv = np.asarray(pool["kv"][pages].astype(jnp.float32))
    ik = np.asarray(pool["ik"][pages].astype(jnp.float32))
    return {
        "state_first": np.asarray(
            engine.slot_state[str(layers["state_first"])]["s"][slot_index]),
        "state_deep": np.asarray(
            engine.slot_state[str(layers["state_deep"])]["s"][slot_index]),
        "latent": kv.reshape(-1, kv.shape[-1])[:rows],
        "index": ik.reshape(-1, ik.shape[-1])[: rows // kpool],
    }


def ticks(windows: list, first_row: int, rows: int) -> dict:
    """A slot's windows ``[(the first tick's position, picked [K, n],
    attended [K, dim])]`` -> the decode ticks that wrote positions
    ``first_row..rows - 1``, in order: ``{"picked_decode" [rows - first_row,
    n], "attended_decode" [rows - first_row, dim]}``. A row that is missing
    is the audit's fault, and raises."""
    import numpy as np

    by_row = {first + j: (p[j], a[j]) for first, p, a in windows for j in range(len(p))}
    kept = [by_row[t] for t in range(first_row, rows)]
    return {"picked_decode": np.stack([p for p, _ in kept]),
            "attended_decode": np.stack([a for _, a in kept])}


def serve(checkpoint: str, env: dict, samples: list[list[int]], decode: int) -> dict:
    """Prefill each of ``samples`` (a timed request's prompt + its emitted
    tokens) and decode at least ``decode`` tokens more, together, beside
    fillers. -> ``{"streams": [{"emitted", "picked", "state_first", ...} a
    sample], ...}``: ``emitted`` is EVERY token the stream had emitted
    when its caches were read, so its ticks wrote positions ``len(sample)
    .. len(sample) + len(emitted) - 2``; ``picked [len(sample),
    picked_blocks]`` are the blocks its chunk rows picked, ``attended``
    the sparse-latent sublayer's output at every eighth of them,
    ``picked_decode`` / ``attended_decode`` the same of every such tick
    (:func:`ticks`)."""
    import numpy as np

    t0 = time.perf_counter()
    os.environ.update({k: str(v) for k, v in env.items()})  # the rank too
    os.environ.setdefault("DORA_INT8_DECODE", "1")  # as llm_server.main does
    from dora_tpu.models.hf.loader import read_config
    from dora_tpu.nodehub import llm_server

    config = read_config(checkpoint)
    module = llm_server.model_module(config.get("model_type"))
    cfg, params = module.load(checkpoint, max_seq=int(os.environ.get("DORA_MAX_SEQ", "2048")))
    # the server's engine, its two programs built with a look at what each
    # sparse-latent layer picked and put out (``make_paged_engine(picks=True)``)
    engine = llm_server.make_engine(module.quantize_decode(params, cfg), cfg,
                                    module=module, picks=True)
    layers = audited_layers(config["layer_types"])
    kpool = config["index_kpool"]
    pages_layer = config["layer_types"][: layers["pages"] + 1].count(
        "deepseek_sparse_attention") - 1
    # by the slot it was for: every chunk's picked blocks and every
    # ATTENDED_EVERY-th of its output rows; every window's first position and
    # its ticks' picked blocks and output rows
    look, chunk_program, window_program = (
        engine.selection, engine.chunk_prefill, engine.window_step)
    picked: dict[int, list] = {}
    attended: dict[int, list] = {}
    ticked: dict[int, list] = {}

    def chunk_prefill(ids, pools, position, bt, valid, slot, state):
        out = chunk_program(ids, pools, position, bt, valid, slot, state)
        mine = look["chunk"][pages_layer]
        picked.setdefault(int(slot), []).append(
            np.asarray(mine["picked"])[: int(valid)])
        attended.setdefault(int(slot), []).append(np.asarray(
            mine["attended"][: int(valid) : ATTENDED_EVERY].astype("float32")))
        return out

    def window_step(tokens, pools, positions, bts, active, *rest):
        first, live = np.asarray(positions), np.asarray(active)
        out = window_program(tokens, pools, positions, bts, active, *rest)
        mine = {k: np.asarray(v) for k, v in look["window"][pages_layer].items()}
        for b in np.flatnonzero(live):
            ticked.setdefault(int(b), []).append(
                (int(first[b]), mine["picked"][:, b], mine["attended"][:, b]))
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
                                "picked": np.concatenate(picked[b]),
                                "attended": np.concatenate(attended[b]),
                                **ticks(ticked[b], len(samples[k]), rows),
                                **held(engine, layers, b, slot, rows, kpool)}
                engine.preempt(rid)
        if windows > chunks + 64 * (len(audited) + FILLERS):
            raise RuntimeError(f"audit: {sorted(set(audited) - set(streams))} never got there")
    report = engine.model_counters()
    out = {
        "streams": [streams[rid] for rid in audited],
        "served": len(emitted), "windows": windows,
        "streams_in_slots_a_window": in_slots / max(windows, 1),
        "pool_pages": engine.allocator.num_pages,
        "pool_layers": sorted(int(k) for k in engine.pools),
        "state_leaves": {k: sorted(v) for k, v in engine.slot_state.items()},
        "kv_bytes_per_token": report.get("kv_bytes_per_token"),
        "layers": layers,
    }
    del engine, params
    out["seconds"] = time.perf_counter() - t0
    return out


def through_bf16(x):
    """float32 -> bfloat16 -> float32: what a 2-byte state would hold."""
    import ml_dtypes
    import numpy as np

    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def two_byte_share(*states) -> float:
    """The share of float32 values whose low 16 bits are zero: what bf16
    can hold. Near 0 for a state kept in float32 (1 in 65,536 by chance,
    and exact zeros), 1.0 for one kept in 2 bytes."""
    import numpy as np

    bits = np.concatenate([np.ascontiguousarray(s, np.float32).view(np.uint32).ravel()
                           for s in states])
    return float(np.mean((bits & 0xFFFF) == 0))


def picked_against(own_scores, own_picked, program_picked, first_row: int,
                   rows: int | None = None) -> dict:
    """The program's picked blocks of rows ``first_row..rows - 1`` (to the
    last without ``rows``) against the reference's own top-k of the same
    rows: ``own_scores [T, N]`` (-inf where a block may not be scored),
    ``own_picked`` / ``program_picked [T, k]``."""
    import numpy as np

    differ = unscored = total = 0
    gap, rank_gap = 0.0, 0.0
    k = program_picked.shape[1]
    rows = len(program_picked) if rows is None else rows
    for t in range(first_row, rows):
        mine, theirs = set(own_picked[t].tolist()), set(program_picked[t].tolist())
        total += len(theirs)
        # a picker that never scored takes the first blocks, as below index_topk
        unscored += len(set(range(k)) - mine)
        extra = sorted(theirs - mine)
        if not extra:
            continue
        differ += len(extra)
        row = own_scores[t]
        kept = row[own_picked[t]]
        worst = float(row[extra].min())
        spread = float(kept.max() - kept.min()) or 1.0
        gap = max(gap, float(kept.min() - worst) / spread)
        rank_gap = max(rank_gap, (int((row > worst).sum()) + 1 - k) / k)
    return {"picked_rows": max(rows - first_row, 0),
            "picked_differ": differ / total if total else None,
            "picked_differ_unscored": unscored / total if total else None,
            "picked_score_gap": gap if total else None,
            "picked_rank_gap": rank_gap if total else None}


def compare(got: dict, ref: dict, controls: dict, index_topk: int) -> dict:
    """``got``: one audited stream of :func:`serve`. ``ref``: the
    reference's ``{"state_first", "state_deep", "latent", "index",
    "scores", "picked", "attended"}`` over the same tokens. ``controls``: ``{name:
    {"state_first": ..., "state_deep": ...}}`` of the reference's other
    variants where they ran on this sample. -> the stream's readings and
    the controls'."""
    import numpy as np

    rows, chunk_rows = len(got["latent"]), got["prompt_rows"]
    ticks_from = max(chunk_rows, index_topk)  # the first tick that selects
    # every row's picks, the chunks' then the ticks', by position
    every = np.concatenate([got["picked"], got["picked_decode"]])
    out = {
        "rows": rows, "emitted": len(got["emitted"]),
        "state_first": rel_err(got["state_first"], ref["state_first"]),
        "state_deep": rel_err(got["state_deep"], ref["state_deep"]),
        "state_first_bf16": rel_err(through_bf16(got["state_first"]), ref["state_first"]),
        "state_2byte_share": two_byte_share(got["state_first"], got["state_deep"]),
        "state_2byte_share_bf16": two_byte_share(
            through_bf16(got["state_first"]), through_bf16(got["state_deep"])),
        "latent_rows": rel_err(got["latent"], ref["latent"][:rows]),
        "index_rows": rel_err(got["index"], ref["index"][: len(got["index"])]),
        **picked_against(ref["scores"], ref["picked"], every, index_topk, chunk_rows),
        **{f"{k}_decode": v for k, v in picked_against(
            ref["scores"], ref["picked"], every, ticks_from, rows).items()},
    }
    # the sublayer's output where a row selects: every ATTENDED_EVERY-th
    # chunk row, every decode tick
    first = -(-index_topk // ATTENDED_EVERY)
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
    for name, other in controls.items():
        for key in ("state_first", "state_deep"):
            if other.get(key) is not None:
                out[f"{key}_{name}"] = rel_err(got[key], other[key])
                # the control put in the program's place
                out[f"{key}_{name}_alone"] = rel_err(other[key], ref[key])
        if other.get("latent") is not None:
            out[f"latent_rows_{name}"] = rel_err(got["latent"], other["latent"][:rows])
            out[f"index_rows_{name}"] = rel_err(
                got["index"], other["index"][: len(got["index"])])
    return out
