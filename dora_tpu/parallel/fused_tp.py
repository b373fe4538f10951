"""Tensor-parallel fused decode: the Pallas kernel tier over a tp mesh.

Round-4 seam: the fused decode kernels (ops/decode_block.py)
were batch-1 AND single-device — "fastest" and "multi-chip" were disjoint
paths. This module composes them: the same three kernels run per tp rank
on weight shards, with one f32 ``psum`` per sublayer stitching the
Megatron column/row-parallel partials back together, and a pmax/pmin pair
turning per-rank lm_head argmax winners into the global greedy token.

Layout (one-time host-side prep, :func:`prepare_decode_params`):

* ``wqkv`` [D, (H+2KV)*hd] — columns permuted into rank-block order
  (rank r holds ``[q_r | k_r | v_r]``) then sharded ``P(None, 'tp')``;
  the contiguous shard_map slice per rank is exactly the fused qkv
  weight of its local heads. Same permutation rides on scales + bias.
* ``wo`` [H*hd, D] — rows are head-major, so rank r's rows ARE its
  heads: natural ``P('tp', None)``, partial output psummed.
* ``w_gateup`` [D, 2F] — ``[gate | up]`` permuted to rank blocks
  ``[gate_r | up_r]``; ``w_down`` [F, D] row-sharded to match (rank r
  owns ffn rows ``r*F/tp..``), partial down-projection psummed.
* ``lm_head`` [D, V] — vocab-sharded ``P(None, 'tp')``; each rank's
  kernel returns (argmax, max) over its shard and the global winner is
  ``pmin`` of global indices among ``pmax``-achievers — preserving
  jnp.argmax's first-index tie-break exactly.
* KV caches — sharded over the kv-head axis; the in-place cache update
  stays per-rank and never crosses the interconnect.

Exactness: kernels run with ``residual=False`` so per-rank partials are
raw f32 deltas; the psum and residual-add happen in f32, mirroring the
single-device kernels' f32 accumulate — asserted token-identical on the
virtual mesh (tests/test_fused_tp.py, __graft_entry__ serving dryrun).

Reference parity: none — the reference (torch/CUDA eager, NCCL data
plane) has no tensor-parallel serving at all. This is the TPU-first
completeness axis: XLA collectives over ICI via shard_map.
"""

from __future__ import annotations

from functools import partial

import numpy as np

AXIS = "tp"


def tp_degree(mesh) -> int:
    if mesh is None:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(AXIS, 1)


def tp_compatible(tp: int, *, heads: int, kv_heads: int, ffn: int,
                  vocab: int) -> bool:
    """True when the fused kernel tier can shard over ``tp`` ranks:
    every partitioned dimension must tile. (kv_heads caps tp for GQA
    models — Qwen2-VL-2B's kv_heads=2 serves fused-tp at tp<=2; wider
    meshes fall back to the unfused XLA path, which replicates KV.)"""
    return (
        tp > 1
        and heads % tp == 0
        and kv_heads % tp == 0
        and ffn % tp == 0
        and vocab % tp == 0
    )


# ---------------------------------------------------------------------------
# column permutations (rank-block order)
# ---------------------------------------------------------------------------


def _perm_qkv(heads: int, kv_heads: int, head_dim: int, tp: int):
    """Column permutation [q|k|v] -> [q_0|k_0|v_0 | q_1|k_1|v_1 | ...]."""
    hl, kvl = heads // tp, kv_heads // tp
    q0, k0 = 0, heads * head_dim
    v0 = k0 + kv_heads * head_dim
    idx = []
    for r in range(tp):
        idx.append(np.arange(q0 + r * hl * head_dim, q0 + (r + 1) * hl * head_dim))
        idx.append(np.arange(k0 + r * kvl * head_dim, k0 + (r + 1) * kvl * head_dim))
        idx.append(np.arange(v0 + r * kvl * head_dim, v0 + (r + 1) * kvl * head_dim))
    return np.concatenate(idx)


def _perm_gateup(ffn: int, tp: int):
    """[gate|up] -> [gate_0|up_0 | gate_1|up_1 | ...]."""
    fl = ffn // tp
    idx = []
    for r in range(tp):
        idx.append(np.arange(r * fl, (r + 1) * fl))
        idx.append(np.arange(ffn + r * fl, ffn + (r + 1) * fl))
    return np.concatenate(idx)


# ---------------------------------------------------------------------------
# parameter prep
# ---------------------------------------------------------------------------


def _qw(d: dict):
    if "int4" in d:
        return d["int4"], d["gscale"]
    return d["int8"], d["scale"]


def _check_row_groups(w, s, tp: int, what: str) -> None:
    """int4 row-sharding must slice whole nibble-pack groups."""
    if w.dtype == np.uint8 or str(w.dtype) == "uint8":
        k = 2 * w.shape[0]
        group = k // s.shape[0]
        if (k // tp) % group:
            raise ValueError(
                f"{what}: K={k} over tp={tp} does not tile int4 "
                f"groups of {group}"
            )


def prepare_decode_params(params, mesh, *, heads: int, kv_heads: int,
                          head_dim: int, layers: int, eps: float = 1e-6):
    """Quantized fused-layout params -> the tp decode tree, placed.

    Input is the quantize_decode tree (fused wqkv/w_gateup dicts, int8
    or int4). Output is a flat-per-block tree of plain arrays (the _qw
    dispatch resolved) with columns permuted into rank-block order and
    every leaf device_put with its tp sharding. bf16 prefill sidecars
    are NOT carried — prefill rides the original tree.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    tp = tp_degree(mesh)

    def put(arr, *spec):
        # A replicated put may keep the source's own buffer as the shard
        # on the device it already lives on. The source tree rides in the
        # same operator state (nodehub/ops.make_vlm), the executor
        # donates that state, and the chip refuses to be handed one
        # buffer twice ("Attempt to donate the same buffer twice"): give
        # every replicated leaf a buffer of its own.
        if not any(spec):
            arr = jnp.copy(arr)
        return jax.device_put(arr, NamedSharding(mesh, P(*spec)))

    pq = _perm_qkv(heads, kv_heads, head_dim, tp)
    out = {"blocks": {}}
    for i in range(layers):
        blk = params["blocks"][str(i)]
        wqkv, sqkv = _qw(blk["wqkv"])
        wo, swo = _qw(blk["wo"])
        wgu, sgu = _qw(blk["w_gateup"])
        wd, sd = _qw(blk["w_down"])
        _check_row_groups(wo, swo, tp, f"blocks.{i}.wo")
        _check_row_groups(wd, sd, tp, f"blocks.{i}.w_down")
        ffn = wd.shape[0] * (2 if "int4" in blk["w_down"] else 1)
        pgu = _perm_gateup(ffn, tp)
        n_qkv = (heads + 2 * kv_heads) * head_dim
        bqkv = blk.get("bqkv")
        if bqkv is None:
            bqkv = jnp.zeros((n_qkv,), jnp.float32)
        bgu = blk.get("b_gateup")
        if bgu is None:
            bgu = jnp.zeros((2 * ffn,), jnp.float32)
        out["blocks"][str(i)] = {
            "attn_norm": put(blk["attn_norm"], ),
            "wqkv": put(jnp.asarray(wqkv)[:, pq], None, AXIS),
            "sqkv": put(jnp.asarray(sqkv)[:, pq], None, AXIS),
            "bqkv": put(jnp.asarray(bqkv)[pq], AXIS),
            "wo": put(wo, AXIS, None),
            "swo": put(swo, AXIS, None) if swo.shape[0] > 1 else put(swo),
            "ffn_norm": put(blk["ffn_norm"]),
            "wgu": put(jnp.asarray(wgu)[:, pgu], None, AXIS),
            "sgu": put(jnp.asarray(sgu)[:, pgu], None, AXIS),
            "bgu": put(jnp.asarray(bgu)[pgu], AXIS),
            "wd": put(wd, AXIS, None),
            "sd": put(sd, AXIS, None) if sd.shape[0] > 1 else put(sd),
        }
    wh, sh = _qw(params["lm_head"])
    out["out_norm"] = put(params["out_norm"])
    out["wh"] = put(wh, None, AXIS)
    out["sh"] = put(sh, None, AXIS)
    return out


def _specs(params_tp, layers: int):
    """The in_specs pytree mirroring prepare_decode_params placement."""
    from jax.sharding import PartitionSpec as P

    col, row, rep = P(None, AXIS), P(AXIS, None), P()
    blocks = {}
    for i in range(layers):
        blk = params_tp["blocks"][str(i)]
        blocks[str(i)] = {
            "attn_norm": rep, "wqkv": col, "sqkv": col, "bqkv": P(AXIS),
            "wo": row, "swo": row if blk["swo"].shape[0] > 1 else rep,
            "ffn_norm": rep, "wgu": col, "sgu": col, "bgu": P(AXIS),
            "wd": row, "sd": row if blk["sd"].shape[0] > 1 else rep,
        }
    return {"blocks": blocks, "out_norm": rep, "wh": col, "sh": col}


def cache_spec():
    """KV caches shard over the kv-head axis: [B, KV, S, hd]."""
    from jax.sharding import PartitionSpec as P

    return P(None, AXIS, None, None)


def shard_caches(caches, mesh):
    """Place a freshly prefetched cache tree on the tp mesh (inside jit
    this is a resharding constraint; outside, a device_put)."""
    import jax
    from jax.sharding import NamedSharding

    sharding = NamedSharding(mesh, cache_spec())

    def place(x):
        if isinstance(x, jax.core.Tracer):
            return jax.lax.with_sharding_constraint(x, sharding)
        return jax.device_put(x, sharding)

    return jax.tree.map(place, caches)


# ---------------------------------------------------------------------------
# the tp pass
# ---------------------------------------------------------------------------


def decode_pass_tp(params_tp, x, caches, position, cos_rows, sin_rows, *,
                   heads: int, kv_heads: int, head_dim: int, layers: int,
                   mesh, eps: float = 1e-6):
    """M-row fused greedy pass over the tp mesh (shard_map).

    Mirrors models/vlm.fused_decode_pass: x [M, D] embedded rows,
    cos/sin [M, hd] rope rows, caches [1, KV, S, hd] per layer (sharded
    over KV). Returns (greedy [M] int32 — replicated — and the
    in-place-updated sharded caches).
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from dora_tpu.ops import decode_block as DB

    tp = tp_degree(mesh)
    heads_l, kv_l = heads // tp, kv_heads // tp
    vocab_l = params_tp["wh"].shape[1] // tp
    m = x.shape[0]
    attn = DB.attention_step if m == 1 else DB.attention_chunk_step
    rep = P()

    def body(params, x, caches, pos, cos, sin):
        r = jax.lax.axis_index(AXIS)
        new_caches = {}
        for i in range(layers):
            blk = params["blocks"][str(i)]
            kc = caches[str(i)]["k"][0]  # [KV_l, S, hd]
            vc = caches[str(i)]["v"][0]
            o, kc, vc = attn(
                x, blk["attn_norm"], blk["wqkv"], blk["sqkv"], blk["bqkv"],
                cos, sin, kc, vc, blk["wo"], blk["swo"], pos,
                heads=heads_l, kv_heads=kv_l, head_dim=head_dim, eps=eps,
                residual=False,
            )
            o = jax.lax.psum(o, AXIS)
            x = (x.astype(jnp.float32) + o).astype(x.dtype)
            new_caches[str(i)] = {"k": kc[None], "v": vc[None]}
            a = DB.mlp_step(
                x, blk["ffn_norm"], blk["wgu"], blk["sgu"], blk["bgu"],
                blk["wd"], blk["sd"], eps=eps, residual=False,
            )
            a = jax.lax.psum(a, AXIS)
            x = (x.astype(jnp.float32) + a).astype(x.dtype)
        idx, val = DB.lm_head_argmax(
            x, params["out_norm"], params["wh"], params["sh"], eps=eps,
            return_val=True,
        )
        # Global argmax with jnp.argmax's first-index tie-break: among
        # ranks achieving the global max, the smallest global index wins.
        gmax = jax.lax.pmax(val, AXIS)
        cand = jnp.where(
            val >= gmax, idx + r * vocab_l, jnp.int32(2**31 - 1)
        )
        gidx = jax.lax.pmin(cand, AXIS)
        return gidx, new_caches

    cspec = {str(i): {"k": cache_spec(), "v": cache_spec()}
             for i in range(layers)}
    return shard_map(
        partial(body),
        mesh=mesh,
        in_specs=(_specs(params_tp, layers), rep, cspec, rep, rep, rep),
        out_specs=(rep, cspec),
        check_vma=False,
    )(params_tp, x, caches, position, cos_rows, sin_rows)
