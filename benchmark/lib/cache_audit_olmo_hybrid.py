"""What the program's engine holds for a follow-up turn that it was
GRANTED FROM A STATE SNAPSHOT, read back and compared with the plain
reference's: the audit of an ``olmo_hybrid`` configuration's two cache
kinds and of the copy between them.

:func:`serve` builds the server's own engine (``llm_server.make_engine``
under the cell's ``node_env``: the prefix cache on, the snapshot pool
beside it) in the reference child, after the dataflow has exited, and
replays ONE sampled pair as the timed run met it: first the turn before
the sample (its prompt, one token), which leaves the snapshot that turn
left; then the sample's prompt + the tokens the timed run emitted, as one
prompt, which the engine must grant from that snapshot (``granted_tokens``:
the depth its first chunk started at, read from the slot at admission, and
``snapshots_restored``: the copy was made), and ``decode`` tokens more.
The copy itself is read where it stands, on the audit's own engine: the
snapshot pool's row that the turn before left (the node the sample was
promised, read after ``submit`` and before any step) and the slot's row as
the restore copy left it (the slots' state that the engine hands the
sample's FIRST chunk, read before that chunk runs), every leaf in its own
dtype: the two must be equal bit for bit (``restore_bits_differ`` 0), and
the float32 delta-rule states of both must not be values that bfloat16
could hold (``snapshot_2byte_share``). What the slot holds at the end went
snapshot -> slot -> the chunks past the grant -> the decode ticks, and is
read back: the float32 delta-rule state and the convolution tail of the
first and the last linear layer, and the first and the last full layer's
K/V rows (those under the grant are the EARLIER turn's pages, shared). Both
programs are called for the first time through ``engine.step()``, with no
frame of this module's between, so that they find the server's
compile-cache entries (the wrapper that reads the restored slot stands
around the sample's first chunk only, after the turn before has run the
chunk program).

:func:`compare` holds them to the reference's (``rms(got - want) /
rms(want)``) and reads the share of float32 values whose low 16 bits are
zero: 1.0 for values that went through bfloat16, some 0.0001 for float32
ones, where no error limit could tell them apart (the program's bf16
INPUTS leave more error than a 2-byte state would: ``PERF.md`` section 6,
PR 43). WHERE the share is read decides what it can see. On the pool's row
and on the restored slot (``snapshot_2byte_share``): a snapshot pool of a
narrower dtype, a save or a restore that casts. On the slot at the end
(``state_2byte_share``): a slot state that the two programs themselves
round on every chunk and tick, up to the last; NOT a snapshot through
bfloat16, whose low bits the float32 chunks and ticks past the grant
refill (658 rows and 8 ticks in the cell's audited turn).
"""

from __future__ import annotations

import os
import time

from cache_audit_kimi_k2 import rel_err  # beside this file


def linear_and_full(layer_types: list[str]) -> tuple[tuple[int, int], tuple[int, int]]:
    """(first, last) linear layer and (first, last) full layer."""
    linear = [i for i, k in enumerate(layer_types) if k == "linear_attention"]
    full = [i for i, k in enumerate(layer_types) if k == "full_attention"]
    return (linear[0], linear[-1]), (full[0], full[-1])


def two_byte_share(state) -> float:
    """The share of float32 values whose low 16 bits are zero (exact zeros
    aside, which count): 1.0 for values that bfloat16 could hold."""
    import numpy as np

    bits = np.ascontiguousarray(state, np.float32).view(np.uint32)
    return float(((bits & 0xFFFF) == 0).mean())


def row_of(tree, index: int) -> list:
    """Row ``index`` of every leaf, on the host, each in its own dtype."""
    import jax
    import numpy as np

    return [np.asarray(leaf[index]) for leaf in jax.tree.leaves(tree)]


def bits_differ(a: list, b: list) -> int:
    """How many values of two rows (:func:`row_of`) differ in their bits;
    a leaf whose dtype or shape differs counts whole."""
    import numpy as np

    n = 0
    for x, y in zip(a, b, strict=True):
        if x.dtype != y.dtype or x.shape != y.shape:
            n += max(x.size, y.size)
            continue
        if not x.size:
            continue
        raw = [np.ascontiguousarray(v).reshape(-1).view(np.uint8).reshape(v.size, -1)
               for v in (x, y)]
        n += int((raw[0] != raw[1]).any(axis=1).sum())
    return n


def states_share(tree, index: int, layer_types: list[str]) -> float:
    """The larger :func:`two_byte_share` of the first and the last linear
    layer's delta-rule state in row ``index`` of ``tree`` (the slots' state
    or the snapshot pool); a leaf that is not float32 reads 1.0."""
    import numpy as np

    linear, _ = linear_and_full(layer_types)
    shares = []
    for layer in linear:
        state = tree[str(layer)]["s"]
        shares.append(1.0 if state.dtype != np.float32
                      else two_byte_share(np.asarray(state[index])))
    return max(shares)


def held(engine, layer_types: list[str], slot_index: int, slot, rows: int) -> dict:
    """A live slot's state, tails and pages at the audited layers, float32
    on the host."""
    import jax.numpy as jnp
    import numpy as np

    linear, full = linear_and_full(layer_types)
    pages = jnp.asarray(slot.pages[: -(-rows // engine.page_size)], jnp.int32)
    out = {}
    for name, layer in zip(("first", "last"), linear):
        st = engine.slot_state[str(layer)]
        assert st["s"].dtype == jnp.float32, st["s"].dtype
        out[f"state_{name}"] = np.asarray(st["s"][slot_index])
        out[f"tail_{name}"] = np.asarray(st["conv"][slot_index].astype(jnp.float32))
    for name, layer in zip(("first", "last"), full):
        kv = np.asarray(engine.pools[str(layer)]["kv"][pages].astype(jnp.float32))
        out[f"kv_{name}"] = kv.reshape(-1, kv.shape[-1])[:rows]
    return out


def serve(checkpoint: str, env: dict, before: list[int], sample: list[int],
          decode: int) -> dict:
    """``before``: the prompt of the turn before the sample; ``sample``:
    the sample's prompt + its timed tokens (it begins with ``before``).
    -> ``{"granted_tokens", "snapshots_saved", "snapshots_restored",
    "snapshot_row_2byte_share", "restored_slot_2byte_share",
    "restore_bits_differ", "emitted", "rows", "state_first", "state_last",
    "tail_first", "tail_last", "kv_first", "kv_last", ...}`` (the three
    readings of the copy are None where nothing was granted): ``emitted``
    is every token the stream had emitted when its slot was read, so the
    slot holds rows ``0 .. rows - 1`` with ``rows = len(sample) +
    len(emitted) - 1``."""
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
    loaded = time.perf_counter() - t0
    if sample[: len(before)] != before:
        raise ValueError("audit: the sample does not begin with the turn before it")
    engine.submit("before", before, 1)
    for _ in range(-(-len(before) // engine.chunk) + 4):
        if any(done for _rid, _tok, done in engine.step()):
            break
    else:
        raise RuntimeError("audit: the turn before the sample never finished")
    saved = engine.snapshots_saved
    spare = 2 * engine.window + 2  # alive past its last audited token
    engine.submit("audit", sample, decode + spare)
    slot_index, slot = next((b, s) for b, s in enumerate(engine.slots) if s is not None)
    granted, shared = slot.chunk_base, slot.shared
    copy = {"snapshot_row_2byte_share": None, "restored_slot_2byte_share": None,
            "restore_bits_differ": None}
    if slot.snap_from is not None:
        # the pool's row as the turn before left it, and the slot as the
        # engine's own restore leaves it, read from the operand of the
        # sample's first chunk (the slots' state goes last) before it runs
        layer_types, at = config["layer_types"], slot.snap_from.snap
        kept = row_of(engine.snapshot_pool, at)
        copy["snapshot_row_2byte_share"] = states_share(
            engine.snapshot_pool, at, layer_types)
        run_chunk = engine.chunk_prefill

        def first_chunk(*operands):
            engine.chunk_prefill = run_chunk
            copy["restored_slot_2byte_share"] = states_share(
                operands[-1], slot_index, layer_types)
            copy["restore_bits_differ"] = bits_differ(
                kept, row_of(operands[-1], slot_index))
            return run_chunk(*operands)

        engine.chunk_prefill = first_chunk
    emitted: list[int] = []
    for _ in range(-(-len(sample) // engine.chunk) + decode + 8):
        emitted += [tok for _rid, tok, _done in engine.step()]
        if len(emitted) >= decode:
            break
    else:
        raise RuntimeError("audit: the sample never decoded")
    rows = len(sample) + len(emitted) - 1
    engine.check_invariants()
    report = engine.model_counters()
    out = {
        "granted_tokens": granted, "granted_pages": shared,
        "snapshots_saved": saved, "snapshots_restored": engine.snapshots_restored,
        **copy, "emitted": emitted, "rows": rows, "before_rows": len(before),
        **held(engine, config["layer_types"], slot_index, slot, rows),
        "pool_pages": engine.allocator.num_pages,
        "snapshot_rows": engine.prefix_cache.snapshots,
        "snapshot_bytes": engine.snapshot_bytes,
        "kv_bytes_per_token": report.get("kv_bytes_per_token"),
        "state_snapshot_pool_bytes": report.get("state_snapshot_pool_bytes"),
        "chunks_run": engine.chunks_run,
        "load_seconds": loaded,
    }
    del engine, params
    out["seconds"] = time.perf_counter() - t0
    return out


def compare(got: dict, want: dict, bf16: dict | None = None) -> dict:
    """``got``: :func:`serve`'s; ``want``: the reference's ``{"state_first",
    "state_last" [H, d_k, d_v], "c_first", "c_last" [T, channels] (the
    pre-convolution rows), "kv_first", "kv_last" [T, 2 KV hd]}`` over the
    same rows; ``bf16``: the same of the reference whose state was held to
    bfloat16 (a control). -> the readings ``chat_measure_olmo_hybrid``
    judges."""
    rows = got["rows"]
    out = {
        "rows": rows, "granted_tokens": got["granted_tokens"],
        "granted_from_snapshot": bool(
            got["granted_tokens"] and got["snapshots_restored"] >= 1),
    }
    for name in ("first", "last"):
        out[f"state_{name}"] = rel_err(got[f"state_{name}"], want[f"state_{name}"])
        out[f"tail_{name}"] = rel_err(got[f"tail_{name}"], want[f"c_{name}"][rows - 3 : rows])
        out[f"kv_rows_{name}"] = rel_err(got[f"kv_{name}"], want[f"kv_{name}"][:rows])
        # the rows under the grant are the earlier turn's pages
        under = got["granted_tokens"]
        if under:
            out[f"kv_rows_granted_{name}"] = rel_err(
                got[f"kv_{name}"][:under], want[f"kv_{name}"][:under])
    out["state_2byte_share"] = max(
        two_byte_share(got["state_first"]), two_byte_share(got["state_last"]))
    # the copy, where it stands: the pool's row and the restored slot
    shares = [got.get("snapshot_row_2byte_share"), got.get("restored_slot_2byte_share")]
    out["snapshot_2byte_share"] = None if None in shares else max(shares)
    out["snapshot_row_2byte_share"], out["restored_slot_2byte_share"] = shares
    out["restore_bits_differ"] = got.get("restore_bits_differ")
    if bf16 is not None:
        out["state_2byte_share_bf16"] = min(
            two_byte_share(bf16["state_first"]), two_byte_share(bf16["state_last"]))
        out["state_last_bf16"] = rel_err(bf16["state_last"], want["state_last"])
    return out
