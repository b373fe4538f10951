"""Grouped (multi-tenant) LoRA delta as a Pallas TPU gather-matmul.

Multi-tenant serving batches streams that belong to DIFFERENT adapters
into one fused decode window. The per-row low-rank delta

    y[i] = (x[i] @ A[g[i]]) @ B[g[i]]

must therefore gather each row's adapter factors out of a resident
stack ``A: [S, D, r]`` / ``B: [S, r, N]`` by the row's adapter id
``g: [R] int32`` — a ragged/grouped matmul (punica's BGMV shape). Done
naively (``A[g]`` then einsum) XLA materializes an [R, D, r] gather in
HBM per call; this kernel instead prefetches the ids as scalars and
lets the BlockSpec index maps steer each grid step's DMA straight at
the row's adapter slab — HBM traffic is one A/B slab per row, nothing
is materialized.

Slot 0 of the stack is all-zeros by contract (models/lora_pool): base
(adapter-less) rows ride the same kernel and get an exact zero delta,
so a mixed batch of base and tenant rows shares ONE program — the
engine's zero-steady-state-compile discipline extends to adapter
churn because admission/eviction only rewrites stack CONTENTS, never
shapes.

Rank limits: r and N are zero-padded to the 128-lane tile, so ranks
up to 128 cost the same kernel time — the resident stack is
homogeneous in (D, r, N) and adapters of smaller rank are zero-padded
into it (see KNOWN_ISSUES round 19).

On non-TPU backends the kernel runs through the Pallas interpreter;
tests assert parity against the eager per-stream reference
(:func:`lora_gather_matmul_ref`) on CPU — the ``decode_block.py``
discipline.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dora_tpu.backend import interpret as _interpret

_LANE = 128


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _kernel(g_ref, x_ref, a_ref, b_ref, o_ref):
    # g_ref is consumed by the BlockSpec index maps (scalar prefetch);
    # the body sees the row's own pre-gathered A/B slabs.
    del g_ref
    x = x_ref[0].astype(jnp.float32)  # [1, D]
    a = a_ref[0].astype(jnp.float32)  # [D, r]
    t = jax.lax.dot(x, a, preferred_element_type=jnp.float32)  # [1, r]
    b = b_ref[0].astype(jnp.float32)  # [r, N]
    o_ref[0] = jax.lax.dot(
        t, b, preferred_element_type=jnp.float32
    ).astype(o_ref.dtype)


@jax.jit
def lora_gather_matmul(x, groups, a_stack, b_stack):
    """``(x[i] @ A[g_i]) @ B[g_i]`` per row, gathered by adapter id.

    x: [R, D] float; groups: [R] int32 in [0, S); a_stack: [S, D, r];
    b_stack: [S, r, N]. Returns [R, N] in x.dtype (f32 accumulation).
    Row id 0 must be the all-zeros base slot for exact no-op deltas.
    """
    r_rows, d = x.shape
    s, da, rank = a_stack.shape
    sb, rb, n = b_stack.shape
    assert d == da and rank == rb and s == sb, (
        x.shape, a_stack.shape, b_stack.shape
    )

    d_pad = _round_up(d, _LANE)
    r_pad = _round_up(rank, _LANE)
    n_pad = _round_up(n, _LANE)
    x2 = x if d_pad == d else jnp.pad(x, ((0, 0), (0, d_pad - d)))
    a2 = a_stack
    if (d_pad, r_pad) != (d, rank):
        a2 = jnp.pad(a2, ((0, 0), (0, d_pad - d), (0, r_pad - rank)))
    b2 = b_stack
    if (r_pad, n_pad) != (rank, n):
        b2 = jnp.pad(b2, ((0, 0), (0, r_pad - rank), (0, n_pad - n)))

    # One row per grid step. x/out ride as [R, 1, D] so the row block's
    # last two dims (1, D) equal the array's: Mosaic refuses a (1, D)
    # block of an [R, D] array (second-minor must be 8-aligned or full).
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r_rows,),
        in_specs=[
            pl.BlockSpec((1, 1, d_pad), lambda i, g: (i, 0, 0)),
            pl.BlockSpec((1, d_pad, r_pad), lambda i, g: (g[i], 0, 0)),
            pl.BlockSpec((1, r_pad, n_pad), lambda i, g: (g[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, n_pad), lambda i, g: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r_rows, 1, n_pad), x.dtype),
        interpret=_interpret(),
    )(groups.astype(jnp.int32), x2[:, None, :], a2, b2)
    return out[:, 0, :n]


def lora_gather_matmul_ref(x, groups, a_stack, b_stack):
    """Eager per-stream reference: one plain two-step matmul per row,
    indexing the stack on host — the parity oracle for the kernel."""
    rows = []
    groups = jnp.asarray(groups)
    for i in range(x.shape[0]):
        g = int(groups[i])
        t = x[i : i + 1].astype(jnp.float32) @ a_stack[g].astype(
            jnp.float32
        )
        rows.append(t @ b_stack[g].astype(jnp.float32))
    return jnp.concatenate(rows, axis=0).astype(x.dtype)
