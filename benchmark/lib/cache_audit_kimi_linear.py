"""What the program's engine holds for a request whose shared prefix it was
GRANTED FROM A BRANCH SNAPSHOT, read back and compared with the plain
reference's: the audit of a ``kimi_linear`` configuration's two cache kinds
(per-slot KDA state, latent pages) and of the copy between the snapshot
pool and a slot.

:func:`serve` builds the server's own engine (``llm_server.make_engine``
under the cell's ``node_env``: 64 slots, the prefix cache on, the snapshot
pool beside it) in the reference child, after the dataflow has exited, and
replays ONE sampled request as the timed run met it: first the two prompts
of the warm wave that share its prefix (one token each): the first leaves
the prefix's pages, the second leaves the tree after the shared part and
saves the BRANCH snapshot (``branch_saved`` 1) at the last chunk edge
inside it; then the sample's prompt + the tokens the timed run emitted, as
one prompt, which the engine must grant from that snapshot
(``granted_tokens``: the depth its first chunk started at, read from the
slot at admission; ``snapshots_restored``: the copy was made), and
``decode`` tokens more. The copy itself is read where it stands, on the
audit's own engine: the snapshot pool's row (the node the sample was
promised, read after ``submit`` and before any step) and the slot's row as
the restore copy left it (the slots' state that the engine hands the
sample's FIRST chunk, read before that chunk runs), every leaf in its own
dtype: the two must be equal bit for bit (``restore_bits_differ`` 0), and
the float32 states of both must not be values that bfloat16 could hold
(``snapshot_2byte_share``). The restored slot's state and tail are kept
too (``restored_*``): the reference's state AT THE GRANT'S BOUNDARY is what
a snapshot of the right depth holds, and a faithful copy of another
depth's row does not (the decays forget a boundary within a few hundred
rows, so the state at the END cannot tell). What the slot holds at the end went snapshot ->
slot -> the chunks past the grant -> the decode ticks, and is read back:
the float32 state and the convolution tail of the first and the last KDA
layer, and the first and the last latent layer's rows (those under the
grant are the FIRST warm prompt's pages, shared). Both programs are called
for the first time through ``engine.step()``, with no frame of this
module's between, so that they find the server's compile-cache entries.

The router is audited apart (:func:`routed`): the program's own
``moe.route`` of the first expert layer on the sample's first embedding
rows, whose choices the reference repeats in float32 on the same rows; a
router whose scores went through bfloat16 chooses other experts for every
second row, where a float32 one parts on a tie in a thousand.

:func:`compare` holds them to the reference's (``rms(got - want) /
rms(want)``) and reads the share of float32 values whose low 16 bits are
zero: 1.0 for values that went through bfloat16, some 0.0001 for float32
ones (``cache_audit_olmo_hybrid`` says where each share can see what).
"""

from __future__ import annotations

import os
import time

from cache_audit_kimi_k2 import rel_err  # beside this file
from cache_audit_olmo_hybrid import bits_differ, row_of, two_byte_share


def first_and_last(config: dict) -> tuple[tuple[int, int], tuple[int, int]]:
    """(first, last) KDA layer and (first, last) latent layer, from 0 (the
    published lists number the layers from 1)."""
    lin = config["linear_attn_config"]
    kda = sorted(i - 1 for i in lin["kda_layers"])
    mla = sorted(i - 1 for i in lin["full_attn_layers"])
    return (kda[0], kda[-1]), (mla[0], mla[-1])


def branch_edge(befores: list[list[int]], page: int, chunk: int) -> int:
    """Where the engine must start a later request of the prefix: the last
    chunk edge inside the whole pages that the second warm prompt shared
    with the first (a branch snapshot's depth, or the first prompt's own
    last full chunk edge where that lies inside the shared part)."""
    a, b = befores
    same = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), min(len(a), len(b)))
    matched = min(same // page * page, (len(b) - 1) // page * page)
    return matched // chunk * chunk


def states_share(tree, index: int, layers) -> float:
    """The larger :func:`two_byte_share` of the given KDA layers' state in
    row ``index`` of ``tree`` (the slots' state or the snapshot pool); a
    leaf that is not float32 reads 1.0."""
    import numpy as np

    shares = []
    for layer in layers:
        state = tree[str(layer)]["s"]
        shares.append(1.0 if state.dtype != np.float32
                      else two_byte_share(np.asarray(state[index])))
    return max(shares)


def held(engine, config: dict, slot_index: int, slot, rows: int) -> dict:
    """A live slot's state, tails and latent rows at the audited layers,
    float32 on the host."""
    import jax.numpy as jnp
    import numpy as np

    kda, mla = first_and_last(config)
    pages = jnp.asarray(slot.pages[: -(-rows // engine.page_size)], jnp.int32)
    width = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    out = {}
    for name, layer in zip(("first", "last"), kda):
        st = engine.slot_state[str(layer)]
        assert st["s"].dtype == jnp.float32, st["s"].dtype
        out[f"state_{name}"] = np.asarray(st["s"][slot_index])
        out[f"tail_{name}"] = np.asarray(st["conv"][slot_index].astype(jnp.float32))
    for name, layer in zip(("first", "last"), mla):
        kv = np.asarray(engine.pools[str(layer)]["kv"][pages].astype(jnp.float32))
        kv = kv.reshape(-1, kv.shape[-1])[:rows]
        out[f"kv_{name}"] = kv[:, :width]
        out[f"kv_pad_{name}"] = float(np.abs(kv[:, width:]).max()) if kv.shape[1] > width else 0.0
    return out


ROUTER_ROWS = 512  # rows of the router's audit: the sample's first tokens


def routed(params, cfg, config: dict, sample: list[int]) -> dict:
    """The program's router (``models/moe.route``) of the FIRST expert
    layer on the embedding rows of the sample's first ``ROUTER_ROWS``
    tokens: ``{"layer", "tokens", "ids" [N, k] sorted a row}``."""
    import jax.numpy as jnp
    import numpy as np

    from dora_tpu.models import moe

    layer = next(i for i in range(config["num_hidden_layers"])
                 if "router" in params["blocks"][str(i)])
    tokens = sample[:ROUTER_ROWS]
    rows = params["embed"][jnp.asarray(tokens, jnp.int32)]
    ids, _weights = moe.route(params["blocks"][str(layer)], cfg, rows)
    return {"layer": layer, "tokens": tokens, "ids": np.sort(np.asarray(ids), -1)}


def serve(checkpoint: str, env: dict, befores: list[list[int]], sample: list[int],
          decode: int, branch_expected: int = 1) -> dict:
    """``befores``: the two warm prompts that share the sample's prefix;
    ``sample``: the sample's prompt + its timed tokens. -> the readings
    :func:`compare` takes (the three readings of the copy are None where
    nothing was granted): ``emitted`` is every token the stream had emitted
    when its slot was read, so the slot holds rows ``0 .. rows - 1`` with
    ``rows = len(sample) + len(emitted) - 1``."""
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
    loaded = time.perf_counter() - t0
    router = routed(params, cfg, config, sample)
    for n, before in enumerate(befores):
        engine.submit(f"before{n}", before, 1)
        for _ in range(-(-len(before) // engine.chunk) + 4):
            if any(done for _rid, _tok, done in engine.step()):
                break
        else:
            raise RuntimeError("audit: a warm prompt never finished")
    saved, branch_saved = engine.snapshots_saved, engine.snapshots_branch_saved
    spare = 2 * engine.window + 2  # alive past its last audited token
    engine.submit("audit", sample, decode + spare)
    slot_index, slot = next((b, s) for b, s in enumerate(engine.slots) if s is not None)
    granted, shared = slot.chunk_base, slot.shared
    kda, _ = first_and_last(config)
    copy = {"snapshot_row_2byte_share": None, "restored_slot_2byte_share": None,
            "restore_bits_differ": None, "restored": None}
    if slot.snap_from is not None:
        at = slot.snap_from.snap
        kept = row_of(engine.snapshot_pool, at)
        copy["snapshot_row_2byte_share"] = states_share(engine.snapshot_pool, at, kda)
        run_chunk = engine.chunk_prefill

        def first_chunk(*operands):
            engine.chunk_prefill = run_chunk
            copy["restored_slot_2byte_share"] = states_share(operands[-1], slot_index, kda)
            copy["restore_bits_differ"] = bits_differ(
                kept, row_of(operands[-1], slot_index))
            # the state and the tail a snapshot of THIS depth must hold
            copy["restored"] = {
                f"{leaf}_{name}": np.asarray(
                    operands[-1][str(layer)][key][slot_index]).astype(np.float32)
                for name, layer in zip(("first", "last"), kda)
                for leaf, key in (("state", "s"), ("tail", "conv"))}
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
        "snapshots_saved": saved, "branch_saved": branch_saved,
        "branch_expected": branch_expected,
        "snapshots_restored": engine.snapshots_restored,
        **copy, "router": router, "emitted": emitted, "rows": rows,
        "before_rows": [len(b) for b in befores],
        **held(engine, config, slot_index, slot, rows),
        "slots": engine.max_slots, "pool_pages": engine.allocator.num_pages,
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
    "state_last" [H, d_k, d_v], "c_first", "c_last" [T, 3 H d_k] (the
    pre-convolution rows), "kv_first", "kv_last" [T, kv_rank + shared]}``
    over the same rows; ``bf16``: the same of the reference whose state
    was held to bfloat16 (a control). -> the readings
    ``chat_measure_kimi_linear`` judges."""
    import numpy as np

    rows = got["rows"]
    out = {
        "rows": rows, "granted_tokens": got["granted_tokens"],
        "granted_from_snapshot": bool(
            got["granted_tokens"] and got["snapshots_restored"] >= 1
            and got["branch_saved"] >= got["branch_expected"]),
    }
    for name in ("first", "last"):
        out[f"state_{name}"] = rel_err(got[f"state_{name}"], want[f"state_{name}"])
        out[f"tail_{name}"] = rel_err(got[f"tail_{name}"], want[f"c_{name}"][rows - 3 : rows])
        out[f"latent_rows_{name}"] = rel_err(got[f"kv_{name}"], want[f"kv_{name}"][:rows])
        under = got["granted_tokens"]
        if under:  # the rows under the grant are the first warm prompt's pages
            out[f"latent_rows_granted_{name}"] = rel_err(
                got[f"kv_{name}"][:under], want[f"kv_{name}"][:under])
            out[f"latent_rows_past_grant_{name}"] = rel_err(
                got[f"kv_{name}"][under:], want[f"kv_{name}"][under:rows])
        restored = got.get("restored")
        if restored and f"cut_{name}" in want:
            # the slot as the restore left it, against the reference's state
            # and convolution inputs at the grant's boundary
            out[f"restored_state_{name}"] = rel_err(
                restored[f"state_{name}"], want[f"cut_{name}"])
            out[f"restored_tail_{name}"] = rel_err(
                restored[f"tail_{name}"], want[f"c_{name}"][under - 3 : under])
    if got.get("router") is not None and "router_ids" in want:
        out["router_rows_differ"] = float(
            (got["router"]["ids"] != np.asarray(want["router_ids"])).any(-1).mean())
    out["latent_row_padding"] = max(got["kv_pad_first"], got["kv_pad_last"])
    out["state_2byte_share"] = max(
        two_byte_share(got["state_first"]), two_byte_share(got["state_last"]))
    shares = [got.get("snapshot_row_2byte_share"), got.get("restored_slot_2byte_share")]
    out["snapshot_2byte_share"] = None if None in shares else max(shares)
    out["snapshot_row_2byte_share"], out["restored_slot_2byte_share"] = shares
    out["restore_bits_differ"] = got.get("restore_bits_differ")
    if bf16 is not None:
        out["state_2byte_share_bf16"] = min(
            two_byte_share(bf16["state_first"]), two_byte_share(bf16["state_last"]))
        out["state_last_bf16"] = rel_err(bf16["state_last"], want["state_last"])
    return out
