"""Int8 weight-only dequant-matmul as a Pallas TPU kernel.

Batch-1 decode is HBM-bandwidth-bound: every generated token streams the
full LM weight set from HBM once, so tokens/s is capped at
``peak_bandwidth / weight_bytes``. Storing weights in int8 halves the
bytes vs bf16 — but only if the dequantize happens *at the MXU edge*:
a naive ``(q * scale).astype(bf16)`` materializes the full bf16 weight
in HBM first and wins nothing (measured, round 2). This
kernel streams int8 blocks HBM→VMEM, converts to the compute dtype
in-register, runs the MXU dot, and applies the per-output-channel scale
once on the f32 accumulator — HBM traffic is the int8 bytes, nothing
else.

Quantization is symmetric per output channel (axis=-1 of the [K, N]
weight): ``w ≈ q * scale[None, :]`` with q ∈ [-127, 127]. Because the
scale is per-column it commutes with the matmul —
``x @ (q·s) == (x @ q) · s`` exactly — so applying it on the
accumulator is not an approximation.

Reference parity: the reference serves its models through torch/CUDA
with no quantized path (node-hub/dora-qwenvl/dora_qwenvl/main.py); this
is a TPU-native extension targeting the decode MBU ceiling.

On non-TPU backends the kernel runs through the Pallas interpreter;
tests assert parity against the plain-JAX dequantized matmul on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dora_tpu.backend import interpret as _interpret

_SUBLANE = 16  # bf16 sublane; f32's 8 divides it
_LANE = 128

# Block sizing is the whole game: each grid step carries fixed overhead
# (measured ~0.5 us on v5e), so 64 KB blocks cap the sweep at ~130 GB/s
# while ~2-4 MB blocks reach HBM speed. A block that would need the
# weight padded costs a copy of the whole weight in every call, so the
# blocks are fitted to the stored array: the K block divides K, and an
# N block that does not divide N ends in a ragged last block.
_TARGET_BYTES = 4 << 20
#: Above this K the weight panel would not fit VMEM at a useful BN and
#: the kernel falls back to a sequential K sweep with an accumulator.
_MAX_BLOCK_K = 16384


def quantize_int8(w, keep_bf16: bool = False) -> dict:
    """[K, N] float -> {"int8": [K, N] int8, "scale": [1, N] f32}.

    Symmetric per-output-channel; returned as a dict so quantized
    weights flow through parameter pytrees (layers.matmul dispatches on
    the dict). With ``keep_bf16`` the original weight rides along in
    bf16: matvec-shaped calls (decode — weight-bandwidth-bound) take the
    int8 kernel, larger-M calls (prefill/training — MXU-bound, where
    XLA's plain bf16 matmul is faster than dequant-in-kernel) take the
    sidecar. Costs 2 extra bytes/param of HBM; drop it where memory is
    tighter than prefill latency.
    """
    wf = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(wf), axis=0, keepdims=True) / 127.0  # [1, N]
    scale = jnp.maximum(scale, jnp.float32(1e-12))
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    out = {"int8": q, "scale": scale}
    if keep_bf16:
        out["bf16"] = w.astype(jnp.bfloat16)
    return out


@jax.jit
def quantize_int8_t(*weights) -> dict:
    """HF ``[out, in]`` weights -> one int8 ``[in, sum(out)]`` matrix
    with per-output-channel scales (transposed and joined on the
    device): what a loader that quantizes layer by layer calls."""
    return quantize_int8(jnp.concatenate([w.T for w in weights], axis=1))


def dequantize(wq: dict, dtype=jnp.float32):
    return (wq["int8"].astype(jnp.float32) * wq["scale"]).astype(dtype)


def _kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, nk: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]  # [M, BK] compute dtype
    w = q_ref[...].astype(x.dtype)  # int8 -> compute dtype, in VMEM
    acc_ref[...] += jax.lax.dot(
        x, w, preferred_element_type=jnp.float32
    )

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[...] = (
            acc_ref[...] * s_ref[...].astype(jnp.float32)
        ).astype(o_ref.dtype)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _best_bk(k: int, whole: int) -> int:
    """K whole when it is at most ``whole`` (a block that spans the
    array's dimension needs no lane alignment), else the largest lane
    multiple <= 2048 that divides ``round_up(k, 128)``: 7168 -> 1792,
    18432 -> 2048."""
    if k <= whole:
        return k
    lanes = pl.cdiv(k, _LANE)
    return _LANE * max(
        d for d in range(1, 2048 // _LANE + 1) if lanes % d == 0
    )


def _best_bn(n: int, bn_cap: int) -> int:
    """Lane-multiple N block <= bn_cap over the stored N columns: the
    largest that divides ``round_up(n, 128)`` if that fills half the
    cap, else the fewest blocks of even width, the last one ragged."""
    lanes = pl.cdiv(n, _LANE)
    cap = bn_cap // _LANE
    if lanes <= cap:
        return lanes * _LANE
    div = max(d for d in range(1, cap + 1) if lanes % d == 0)
    if 2 * div >= cap:
        return div * _LANE
    return pl.cdiv(lanes, pl.cdiv(lanes, cap)) * _LANE


def _pick_blocks(m_pad: int, k: int, n: int) -> tuple[int, int, int]:
    """(block_m, block_k, block_n) for x [M, K] @ q [K, N] int8.

    Matvec regime (decode, M <= 32): the kernel is HBM-bound on the
    weight sweep — K kept whole when it fits (no accumulator sweep),
    BN targets ~_TARGET_BYTES of int8 per block to amortize the
    per-grid-step overhead.

    Compute-bound regime (prefill/training, larger M): weight traffic
    amortizes over M rows, so fixed MXU-friendly blocks are used and
    sized to the scoped-VMEM budget (~16 MB with double buffering)
    instead of chasing bandwidth.

    In both, block_k is K itself or divides ``round_up(k, 128)``, so
    only a K that is no lane multiple and too long for one block is
    ever padded.
    """
    if m_pad <= 32:
        bk = _best_bk(k, _MAX_BLOCK_K)
        return m_pad, bk, _best_bn(n, max(_TARGET_BYTES // bk, _LANE))
    bm = min(m_pad, 256)
    bk = _best_bk(k, 2048)
    # double-buffered VMEM: 2*(x + w + out) + scratch, bytes
    budget = 10 << 20
    fixed = 2 * (bm * bk * 2)
    per_bn = 2 * (bk * 1 + bm * 2) + bm * 4
    bn_cap = max((budget - fixed) // per_bn // _LANE * _LANE, _LANE)
    return bm, bk, _best_bn(n, bn_cap)


@jax.jit
def int8_matmul(x, q, scale):
    """``x @ dequantize(q, scale)`` with int8-only HBM traffic.

    x: [..., K] float; q: [K, N] int8; scale: [1, N] f32.
    Returns [..., N] in x.dtype (accumulation in f32).

    ``q`` and ``scale`` go to the kernel as stored whenever K is a lane
    multiple (every model width) or fits one block: columns past N in a
    ragged last block hold anything and only reach output columns that
    are cut. Only a K that is neither keeps a zero pad of ``x`` and
    ``q`` up to the next lane multiple, since rows past K would enter
    every sum.
    """
    *lead, k = x.shape
    kq, n = q.shape
    assert k == kq, (x.shape, q.shape)
    m = 1
    for d in lead:
        m *= d
    x2 = x.reshape(m, k)

    m_pad = _round_up(max(m, _SUBLANE), _SUBLANE)
    block_m, block_k, block_n = _pick_blocks(m_pad, k, n)
    m_pad = _round_up(m_pad, block_m)
    k_pad = _round_up(k, block_k)
    n_pad = _round_up(n, block_n)
    if m_pad != m or k_pad != k:
        x2 = jnp.pad(x2, ((0, m_pad - m), (0, k_pad - k)))
    if k_pad != k:
        q = jnp.pad(q, ((0, k_pad - k), (0, 0)))

    nm = m_pad // block_m
    nn = n_pad // block_n
    nk = k_pad // block_k
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((block_k, block_n), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((1, block_n), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec(
            (block_m, block_n), lambda mi, ni, ki: (mi, ni)
        ),
        out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
    )(x2, q, scale)

    return out[:m, :n].reshape(*lead, n)


@jax.jit
def int8_matmul_grouped(x, q, scale, ids, n_groups):
    """:func:`int8_matmul` for the first ``n_groups`` of ``G`` groups,
    group ``g`` against matrix ``ids[g]`` of a stack: the same kernel on
    the same blocks under a leading grid axis whose bound is
    ``n_groups``, a value of the program.

    x: [G, M, K] float, or [M, K] that every group reads; q: [E, K, N]
    int8; scale: [E, 1, N] f32; ids: [G] int32; n_groups: int32 scalar.
    Returns [G, M, N] in x.dtype; groups at or past ``n_groups`` are
    never written and hold anything (mask them).

    The kernel fetches the matrices named by ``ids[:n_groups]`` and no
    other: the grid has no step for a group past them (measured against
    a static grid whose spare steps compute nothing and name the last
    block again: 0.06-0.08 us a spare step on a v5e, 17 us a call of 36
    groups with one running).
    """
    _, kq, n = q.shape
    *g_dim, m, k = x.shape
    g = ids.shape[0]
    assert k == kq and g_dim in ([], [g]), (x.shape, q.shape, ids.shape)

    m_pad = _round_up(max(m, _SUBLANE), _SUBLANE)
    block_m, block_k, block_n = _pick_blocks(m_pad, k, n)
    m_pad = _round_up(m_pad, block_m)
    k_pad = _round_up(k, block_k)
    n_pad = _round_up(n, block_n)
    if m_pad != m or k_pad != k:
        x = jnp.pad(x, [(0, 0)] * len(g_dim) + [(0, m_pad - m), (0, k_pad - k)])
    if k_pad != k:
        q = jnp.pad(q, ((0, 0), (0, k_pad - k), (0, 0)))

    nm = m_pad // block_m
    nn = n_pad // block_n
    nk = k_pad // block_k

    def x_map(i, ni, ki, ids_ref):
        return (i // nm, i % nm, ki) if g_dim else (i % nm, ki)

    out = pl.pallas_call(
        # the scalar-prefetched ids are the index maps' to read
        lambda ids_ref, *refs: _kernel(*refs, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(jnp.asarray(n_groups, jnp.int32) * nm, nn, nk),
            in_specs=[
                pl.BlockSpec(
                    (None,) * len(g_dim) + (block_m, block_k), x_map),
                pl.BlockSpec(
                    (None, block_k, block_n),
                    lambda i, ni, ki, ids_ref: (ids_ref[i // nm], ki, ni)),
                pl.BlockSpec(
                    (None, 1, block_n),
                    lambda i, ni, ki, ids_ref: (ids_ref[i // nm], 0, ni)),
            ],
            out_specs=pl.BlockSpec(
                (None, block_m, block_n),
                lambda i, ni, ki, ids_ref: (i // nm, i % nm, ni)),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((g, m_pad, n_pad), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=_interpret(),
    )(ids.astype(jnp.int32), x, q, scale)

    return out[:, :m, :n]


# ---------------------------------------------------------------------------
# parameter-tree quantization
# ---------------------------------------------------------------------------

#: Weight leaves worth quantizing in a decode path: the per-token matmul
#: set. Norms, biases, position tables, and the embedding gather stay in
#: their serving dtype (they are O(dim) reads, not O(dim^2)).
DECODE_WEIGHTS = frozenset(
    {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head"}
)


def _fusable(params, names) -> bool:
    return all(
        n in params
        and not isinstance(params[n], dict)
        and getattr(params[n], "ndim", 0) == 2
        for n in names
    )


def _fuse(params, out, w_names, b_names, w_key, b_key, keep_bf16,
          quantizer):
    """Concatenate the named projections along N into one quantized
    weight (one kernel sweep instead of len(w_names)); biases concatenate
    with zero fill for absent segments."""
    ws = [params[n] for n in w_names]
    out[w_key] = quantizer(
        jnp.concatenate([jnp.asarray(w) for w in ws], axis=1), keep_bf16
    )
    if any(b in params for b in b_names):
        out[b_key] = jnp.concatenate(
            [
                jnp.asarray(params[b])
                if b in params
                else jnp.zeros((w.shape[1],), jnp.float32)
                for b, w in zip(b_names, ws)
            ]
        )


def quantize_tree(params, names=DECODE_WEIGHTS, keep_bf16: bool = True,
                  fuse: bool = True, quantizer=quantize_int8):
    """Replace named 2-D weight leaves with quantized dicts.

    Walks nested dicts; a leaf is quantized when its key is in ``names``
    and it is a rank-2 float array. Everything else is returned as-is;
    already-quantized dicts pass through untouched. With ``fuse``,
    co-resident q/k/v and gate/up projections are concatenated into
    single ``wqkv`` / ``w_gateup`` weights (layers.attention_sublayer /
    mlp_sublayer split after the matmul) — decode is kernel-launch-bound
    at ~100+ calls/token, so halving the call count is worth real
    tokens/s. ``keep_bf16`` rides the original weights along for the
    MXU-bound large-M paths (see quantize_int8). ``quantizer`` selects
    the weight format — quantize_int8 (default) or ops.int4's
    quantize_int4 — the whole fusion/recursion machinery is shared.

    Note: fused/quantized leaves fall outside the Megatron tp sharding
    rules (layers.tp_rules matches leaf names) — quantized decode is a
    single-chip serving configuration.
    """
    if not isinstance(params, dict):
        return params
    if "int8" in params or "int4" in params:
        return params
    out = {}
    skip: set[str] = set()
    if fuse and {"wq", "wk", "wv"} <= names and _fusable(params, ("wq", "wk", "wv")):
        _fuse(params, out, ("wq", "wk", "wv"), ("bq", "bk", "bv"),
              "wqkv", "bqkv", keep_bf16, quantizer)
        skip |= {"wq", "wk", "wv", "bq", "bk", "bv"}
    if fuse and {"w_gate", "w_up"} <= names and _fusable(params, ("w_gate", "w_up")):
        _fuse(params, out, ("w_gate", "w_up"), ("b_gate", "b_up"),
              "w_gateup", "b_gateup", keep_bf16, quantizer)
        skip |= {"w_gate", "w_up", "b_gate", "b_up"}
    for key, value in params.items():
        if key in skip:
            continue
        if (
            key in names
            and not isinstance(value, dict)
            and getattr(value, "ndim", 0) == 2
            and jnp.issubdtype(value.dtype, jnp.floating)
        ):
            out[key] = quantizer(value, keep_bf16)
        else:
            out[key] = quantize_tree(value, names, keep_bf16, fuse, quantizer)
    return out
