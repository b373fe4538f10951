"""What the program keeps in its pages and in its tails and which expert
its router picks, against the reference's: the K|V rows and the
convolution and value-shift tails of the first and the last layer, and
EVERY layer's top-1 pick, in the chunk program AND in the decode tick.

No emitted token shows whether a cached row is held in bf16, whether a
tail was stepped by the row it belongs to, or which of two nearly equal
experts a row was sent to, so they are compared themselves. Called by
``reference_zaya.py`` in its own process, after the dataflow has exited
and BEFORE the reference's arrays exist: the program's engine —
``llm_server``'s choice of module, its ``make_engine`` under the cell's
node environment, the same checkpoint, the module's own default pool, the
server's own two programs (they leave every layer's pick in
``engine.selection``) — is handed each sampled request's prompt AND the
tokens the timed run emitted for it as one prompt (the chunk program,
teacher-forced over the very positions whose tokens are judged), decodes
``decode`` tokens more of its own (the window program), all samples at
once beside filler streams. While an audited stream is still seated its
pages are read through its block table and its slot's tails as they
stand; what was kept behind every chunk and every window: the expert
every layer's router picked for every row. The reference routes ITSELF
wherever it is clear of a tie, and follows these picks only inside
``PICK_MARGIN`` (``reference_zaya.py``: top-1 is discontinuous, so a
near-tie decided the other way is not counted against the arithmetic).

**The engine's two programs are first called as the server calls them**,
through ``engine.step()`` with nothing of this file between the engine
and the program: a Mosaic kernel's serialized module holds the Python
frames of its trace (the ten innermost, ``jax_traceback_in_locations_limit``),
and they are part of the compile cache's key. A wrapper's frame there —
how the first version read the picks — gave the audit's two programs keys
of their own: 73 s of compiles a checkout's first run, which that run did
not have (``PERF.md`` section 7, PR 52). The picks are read behind
wrappers put in AFTER that first step, when both programs are traced.

A reading is rms(program - reference) / rms(reference) (:func:`compare`):

* ``kv_rows_first`` / ``kv_rows_last``: the pages of layer 0 and of the
  last layer, every position written (the chunks' and the ticks');
  ``kv_rows_first_8bit``: layer 0's rows through 8 bits (a control: what
  an int8 page would hold); ``kv_rows_first_no_conv`` /
  ``kv_rows_first_no_value_shift``: the same pages against the rows of
  the reference without the convolutions, or with both value heads from
  the row's own position, where that control ran;
* ``tail_first`` / ``tail_last``: the slot's two ``c`` rows and ``Wv2 h``
  after the last tick run, against the reference's rows of the last two
  positions written: a tail that a tick did not step, or that a chunk
  left after a padding row, holds other positions' rows;
  ``tail_first_8bit``: layer 0's tail through 8 bits; ``kv_rows_last_*``
  / ``tail_last_*``: the last layer's pages and tail against a control's;
* ``picks_differ_clear``: the share of a layer's rows whose pick is not
  the reference's own although the reference's two best biased
  probabilities lie at least ``PICK_MARGIN`` apart, the LARGEST over all
  layers (``picks_differ_clear_by_layer`` holds every layer's,
  ``picks_differ_clear_at`` the layer of the largest; bf16's noise in the
  router's input decides a near-tie either way, and moves a clear row
  across only where the stream itself is far off, or where an earlier
  layer sent it elsewhere: the reference follows the program only inside
  the margin); ``picks_differ`` / ``rows_inside_margin``: the share over
  all rows and the share of near-ties, at the layer of the largest;
  ``pick_gap``: the largest amount, over rows and layers, by which the
  reference's probability of the expert the PROGRAM picked lies under its
  best one (printed, not judged: a maximum over thousands of rows);
  ``picks_differ_clear_no_router_carry``: the share for the pick of a
  router that starts every layer from zeros (a control, the last layer's:
  layer 0 has no carry); ``picks_differ_clear_wrong_pick``: the same
  largest share against the reference that moved every fourth row of the
  middle layer to another expert (a control, where it ran), and
  ``picks_differ_clear_at_wrong_pick`` its layer. The reference computes
  the per-row readings a layer on the device.
"""

from __future__ import annotations

import os
import time

# beside this file: the short filler streams made of the samples' ids,
# rms(got - want) / rms(want), and rows through 8 bits
from cache_audit_k_exaone import FILLERS, fillers
from cache_audit_kimi_k2 import rel_err, through_8_bits

#: two biased probabilities closer than this count as a near-tie
PICK_MARGIN = 0.05


def held(engine, layers: tuple, slot_index: int, slot, rows: int) -> dict:
    """A live slot's pages and tails at the audited ``layers`` (first,
    last), float32: the first ``rows`` K|V rows through its block table,
    its two ``c`` rows and its ``Wv2 h``."""
    import jax.numpy as jnp
    import numpy as np

    pages = jnp.asarray(slot.pages[: -(-rows // engine.page_size)], jnp.int32)
    out = {}
    for name, layer in zip(("first", "last"), layers):
        kv = np.asarray(engine.pools[str(layer)]["kv"][pages].astype(jnp.float32))
        out[f"kv_{name}"] = kv.reshape(-1, kv.shape[-1])[:rows]
        tail = engine.slot_state[str(layer)]
        out[f"tail_{name}"] = np.concatenate([
            np.asarray(tail["c"][slot_index].astype(jnp.float32)).reshape(-1),
            np.asarray(tail["v"][slot_index].astype(jnp.float32))])
    return out


def ticks(windows: list, first_row: int, rows: int):
    """A slot's windows ``[(the first tick's position, expert [K, L])]`` ->
    the picks of the decode ticks that wrote positions ``first_row..rows -
    1``, in order: ``[L, rows - first_row]``. A row that is missing is the
    audit's fault, and raises."""
    import numpy as np

    by_row = {first + j: e[j] for first, e in windows for j in range(e.shape[0])}
    return np.stack([by_row[t] for t in range(first_row, rows)], 1)


def serve(checkpoint: str, env: dict, samples: list[list[int]], decode: int) -> dict:
    """Prefill each of ``samples`` (a timed request's prompt + its emitted
    tokens) and decode at least ``decode`` tokens more, together, beside
    fillers. -> ``{"streams": [{"emitted", "prompt_rows", "picked",
    "kv_first", "kv_last", "tail_first", "tail_last"} a sample], ...}``:
    ``emitted`` is EVERY token the stream had emitted when its pages were
    read, so its ticks wrote positions ``len(sample) .. len(sample) +
    len(emitted) - 2``; ``picked [L, rows]`` int8 is the expert every
    layer's router picked for every position written, the chunks' rows
    then the ticks'."""
    import numpy as np

    t0 = time.perf_counter()
    os.environ.update({k: str(v) for k, v in env.items()})
    os.environ.setdefault("DORA_INT8_DECODE", "1")  # as llm_server.main does
    from dora_tpu.models.hf.loader import read_config
    from dora_tpu.nodehub import llm_server

    config = read_config(checkpoint)
    module = llm_server.model_module(config.get("model_type"))
    cfg, params = module.load(checkpoint, max_seq=int(os.environ.get("DORA_MAX_SEQ", "2048")))
    engine = llm_server.make_engine(module.quantize_decode(params, cfg), cfg,
                                    module=module)
    layers = (0, config["num_hidden_layers"] - 1)
    look, chunk_program, window_program = (
        engine.selection, engine.chunk_prefill, engine.window_step)
    picked: dict[int, list] = {}
    ticked: dict[int, list] = {}

    def chunk_prefill(ids, pools, position, bt, valid, slot, state):
        out = chunk_program(ids, pools, position, bt, valid, slot, state)
        picked.setdefault(int(slot), []).append(
            np.asarray(look["chunk"]["expert"])[:, : int(valid)].astype(np.int8))
        return out

    def window_step(tokens, pools, positions, bts, active, *rest):
        first, live = np.asarray(positions), np.asarray(active)
        out = window_program(tokens, pools, positions, bts, active, *rest)
        experts = np.asarray(look["window"]["expert"]).astype(np.int8)  # [K, L, B]
        for b in np.flatnonzero(live):
            ticked.setdefault(int(b), []).append((int(first[b]), experts[:, :, b]))
        return out

    spare = 2 * engine.window + 2  # alive past its last audited token
    queue = []
    for k, (prompt, max_new) in enumerate(fillers(samples, FILLERS, decode)):
        queue.append((f"filler-{k}", prompt, max_new))
        if k < len(samples):
            queue.append((f"audit-{k}", samples[k], decode + spare))
    emitted: dict[str, list[int]] = {}
    streams: dict[str, dict] = {}
    # the first filler's one chunk and the window behind it: both programs'
    # FIRST calls, from the engine's own frames (the module's docstring)
    rid, prompt, max_new = queue.pop(0)
    engine.submit(rid, prompt, max_new)
    emitted[rid] = [token for _rid, token, _done in engine.step()]
    if engine.chunks_run != 1 or not emitted[rid]:
        raise RuntimeError("audit: the first step did not run a chunk and a window")
    engine.chunk_prefill, engine.window_step = chunk_prefill, window_step
    in_slots = windows = 1
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
            picked[b], ticked[b] = [], []
        in_slots += engine.active
        windows += 1
        for rid, token, _done in engine.step():
            emitted[rid].append(token)
        for k, rid in enumerate(audited):
            if rid not in streams and len(emitted.get(rid, ())) >= decode:
                b, slot = next((b, s) for b, s in enumerate(engine.slots)
                               if s is not None and s.request_id == rid)
                rows = len(samples[k]) + len(emitted[rid]) - 1
                streams[rid] = {
                    "emitted": list(emitted[rid]), "prompt_rows": len(samples[k]),
                    "picked": np.concatenate(
                        picked[b] + [ticks(ticked[b], len(samples[k]), rows)], 1),
                    **held(engine, layers, b, slot, rows)}
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
        "tail_leaves": sorted(engine.slot_state["0"]),
        "kv_bytes_per_token": report.get("kv_bytes_per_token"),
        "moe_tokens": report.get("moe_tokens"),
        "moe_local_pairs": report.get("moe_local_pairs"),
        "layers": list(layers),
    }
    del engine, params
    out["seconds"] = time.perf_counter() - t0
    return out


def pick_summary(per_row, rows: int) -> dict:
    """The reference's per-row comparison of the program's pick with its
    own at ONE layer (``per_row [T, 4]``: the picks differ, the gap
    between its two best biased probabilities, how far its probability of
    the program's pick lies under its best, the pick of a router without
    carry differs) over rows ``0..rows - 1``."""
    import numpy as np

    mine = np.asarray(per_row[:rows], np.float64)
    clear = mine[:, 1] >= PICK_MARGIN
    return {"picks_differ": float(mine[:, 0].mean()),
            "picks_differ_clear": float((mine[:, 0] * clear).mean()),
            "rows_inside_margin": float(1.0 - clear.mean()),
            "pick_gap": float(mine[:, 2].max()),
            "picks_differ_clear_no_router_carry": float((mine[:, 3] * clear).mean())}


def picks_over_layers(per_row: list, rows: int) -> dict:
    """Every layer's :func:`pick_summary` -> the layer whose
    ``picks_differ_clear`` is the largest (its summary, and which layer it
    is), every layer's share, the widest ``pick_gap`` of all, and the last
    layer's share for a router without carry."""
    layers = [pick_summary(one, rows) for one in per_row]
    by_layer = [one["picks_differ_clear"] for one in layers]
    at = max(range(len(layers)), key=lambda i: by_layer[i])
    return {**layers[at], "picks_differ_clear_at": at,
            "picks_differ_clear_by_layer": by_layer,
            "pick_gap": max(one["pick_gap"] for one in layers),
            "picks_differ_clear_no_router_carry":
                layers[-1]["picks_differ_clear_no_router_carry"]}


def compare(got: dict, ref: dict, controls: dict) -> dict:
    """``got``: one audited stream of :func:`serve`. ``ref``: the
    reference's ``{"kv_first", "kv_last" [n, 512], "c_first", "c_last"
    [n, 1280], "v2_first", "v2_last" [n, 128], "per_row": [n, 4] a
    layer}`` over the same tokens.
    ``controls``: ``{name: {"kv_first": ...}}`` of the reference's other
    variants where they ran on this sample. -> the stream's readings and
    the controls'."""
    import numpy as np

    rows = len(got["kv_first"])
    out = {"rows": rows, "prompt_rows": got["prompt_rows"], "emitted": len(got["emitted"])}

    def tail_of(c, v2):
        """The tail after position rows - 1: c of the last two positions,
        oldest first, then Wv2 h of the last."""
        return np.concatenate([c[rows - 2], c[rows - 1], v2[rows - 1]])

    first_tail = tail_of(ref["c_first"], ref["v2_first"])
    for name in ("first", "last"):
        out[f"kv_rows_{name}"] = rel_err(got[f"kv_{name}"], ref[f"kv_{name}"][:rows])
        out[f"tail_{name}"] = rel_err(
            got[f"tail_{name}"], tail_of(ref[f"c_{name}"], ref[f"v2_{name}"]))
    out.update(picks_over_layers(ref["per_row"], rows))
    out["kv_rows_first_8bit"] = rel_err(
        through_8_bits(got["kv_first"]), ref["kv_first"][:rows])
    out["tail_first_8bit"] = rel_err(through_8_bits(got["tail_first"][None])[0], first_tail)
    # the pages and the last layer's tail against a control's: what a
    # program with that fault would hold
    for variant, other in controls.items():
        if variant in ("forced", "wrong_pick"):
            over = picks_over_layers(other["per_row"], rows)
            for key in ("picks_differ_clear", "picks_differ_clear_at"):
                out[f"{key}_{variant}"] = over[key]
        for name in ("first", "last"):
            if other.get(f"kv_{name}") is not None:
                out[f"kv_rows_{name}_{variant}"] = rel_err(
                    got[f"kv_{name}"], other[f"kv_{name}"][:rows])
        if other.get("c_last") is not None:
            out[f"tail_last_{variant}"] = rel_err(
                got["tail_last"], tail_of(other["c_last"], other["v2_last"]))
    return out
