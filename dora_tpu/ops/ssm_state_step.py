"""One decode token of a selective state-space (Mamba-2) recurrence, for
every live row of a batch, with the recurrent state left in HBM.

Per row ``r`` and head ``h`` the state is ``S [P, N]`` float32 (head
width x state size: 128 x 256 = 128 KB for Falcon-H1) and one token does

    S  <- decay * S + (dt * x)[:, None] * B[None, :]
    y   = S @ C + D * x

with ``decay = exp(dt * A)`` a scalar per (row, head), ``x`` the head's
``P`` inputs, ``B`` and ``C`` the ``N``-vectors of the head's group.
That is a read and a write of the whole state for ``2 P N`` useful
multiply-adds twice over: pure HBM traffic (8.4 MB a row a layer at the
Falcon-H1 widths), so the kernel is a pipeline of state blocks through
VMEM, the update applied in passing.

What XLA cannot do for this and the kernel does: **a row whose
``active`` bit is off moves no state**. The state array is aliased in
and out, a grid step's block is chosen by scalar-prefetched tables, and
an inactive row's steps name the block the pipeline already holds (the
last block of the last active row before it, or the first block of the
first active row when none came before), so Pallas schedules no copy in
and no copy out for them and the body leaves the buffers alone. A
``jnp.where(active, new, old)`` over the whole array would read and
write every row's state every tick.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dora_tpu.backend import interpret as _interpret

#: bytes of state one grid step holds (in and out, double-buffered: four
#: of these in VMEM): 1 MB is 8 heads of 128 x 256 float32.
_BLOCK_BYTES = 1 << 20

_STEP, _COPY = 1, 2


def head_block(heads_per_group: int, head_dim: int, d_state: int) -> int:
    """Heads a grid step: the largest divisor of a group's heads whose
    state fits ``_BLOCK_BYTES`` (a block never spans two groups, so it
    reads one ``B`` and one ``C``), and a multiple of 8 sublanes where
    the group allows it."""
    cap = max(_BLOCK_BYTES // (head_dim * d_state * 4), 1)
    return max(d for d in range(1, heads_per_group + 1)
               if heads_per_group % d == 0 and d <= cap)


def _column(row, n: int):
    """``row [1, n]`` -> ``[n, 1]`` with selects and a lane reduction
    only (no relayout that Mosaic might refuse)."""
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(row, (n, n)), 0.0),
                   axis=1, keepdims=True)


def _row(col, n: int):
    """``col [n, 1]`` -> ``[1, n]``, the same way."""
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(col, (n, n)), 0.0),
                   axis=0, keepdims=True)


def _kernel(src_row, src_blk, mode, decay_ref, dt_ref, d_ref, x_ref, b_ref,
            c_ref, s_ref, y_ref, o_ref, *, hb: int):
    del src_row, src_blk
    r, j = pl.program_id(0), pl.program_id(1)
    p = x_ref.shape[-1]

    @pl.when(mode[r] == _STEP)
    def _step():
        bvec = b_ref[0]  # [1, N]
        cvec = c_ref[0]
        for i in range(hb):
            head = j * hb + i
            xcol = _column(x_ref[0, i : i + 1, :], p)  # [P, 1]
            s = s_ref[0, i] * decay_ref[r, head] + (
                xcol * dt_ref[r, head]) * bvec  # [P, N]
            o_ref[0, i] = s
            ycol = jnp.sum(s * cvec, axis=1, keepdims=True) + (
                xcol * d_ref[head])
            y_ref[0, i : i + 1, :] = _row(ycol, p)

    # No row is active: the one block the grid names goes back as it came.
    @pl.when((mode[r] == _COPY) & (j == 0))
    def _copy():
        o_ref[...] = s_ref[...]


def schedule(active, blocks: int):
    """The three scalar tables of the grid, from the rows' ``active``
    bits: for row ``r``, the row and (for an inactive row) the block its
    steps name, and what its steps do."""
    rows = active.shape[0]
    idx = jnp.arange(rows, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(active, idx, -1))  # last active <= r
    first = jnp.argmax(active).astype(jnp.int32)  # 0 when none is
    src_row = jnp.where(before >= 0, before, first)
    src_blk = jnp.where(before >= 0, blocks - 1, 0).astype(jnp.int32)
    mode = active.astype(jnp.int32) * _STEP
    mode = mode.at[0].set(jnp.where(active.any(), mode[0], _COPY))
    return src_row, src_blk, mode


@jax.jit
def ssm_state_step(state, x, dt, a, bmat, cmat, d, active):
    """One token of the recurrence for the active rows.

    state ``[R, H, P, N]`` float32, aliased in and out (donate it: rows
    with ``active`` off are neither read nor written); x ``[R, H, P]``;
    dt ``[R, H]`` (after the softplus); a ``[H]`` (negative); bmat, cmat
    ``[R, G, N]`` (``H // G`` consecutive heads share a group); d
    ``[H]``; active ``[R]`` bool. Returns (y ``[R, H, P]`` float32 —
    zeros for inactive rows — and the state).
    """
    rows, heads, p, n = state.shape
    groups = bmat.shape[1]
    per_group = heads // groups
    hb = head_block(per_group, p, n)
    blocks = heads // hb
    f32 = jnp.float32
    dt = dt.astype(f32)
    decay = jnp.exp(dt * a.astype(f32)[None, :])
    src_row, src_blk, mode = schedule(active, blocks)

    def blk(r, j, src_row, src_blk, mode):
        return jnp.where(mode[r] == _STEP, j, src_blk[r])

    def per_head(r, j, src_row, src_blk, mode):
        return (src_row[r], blk(r, j, src_row, src_blk, mode), 0)

    def per_state(r, j, src_row, src_blk, mode):
        return (src_row[r], blk(r, j, src_row, src_blk, mode), 0, 0)

    def per_group_vec(r, j, src_row, src_blk, mode):
        g = blk(r, j, src_row, src_blk, mode) * hb // per_group
        return (src_row[r] * groups + g, 0, 0)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    y, state = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(rows, blocks),
            in_specs=[
                smem, smem, smem,  # decay [R, H], dt [R, H], d [H]
                pl.BlockSpec((1, hb, p), per_head),  # x
                pl.BlockSpec((1, 1, n), per_group_vec),  # B
                pl.BlockSpec((1, 1, n), per_group_vec),  # C
                pl.BlockSpec((1, hb, p, n), per_state),  # state
            ],
            out_specs=[
                pl.BlockSpec((1, hb, p), per_head),
                pl.BlockSpec((1, hb, p, n), per_state),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((rows, heads, p), f32),
            jax.ShapeDtypeStruct(state.shape, f32),
        ],
        # operand 9 (the three tables included) is the state -> output 1
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=_interpret(),
    )(
        src_row, src_blk, mode, decay, dt, d.astype(f32), x.astype(f32),
        bmat.astype(f32).reshape(rows * groups, 1, n),
        cmat.astype(f32).reshape(rows * groups, 1, n),
        state,
    )
    return jnp.where(active[:, None, None], y, 0.0), state


def ssm_state_step_reference(state, x, dt, a, bmat, cmat, d, active):
    """The same update in plain ``jax.numpy`` over the whole array (what
    the kernel is tested against; it reads and writes every row)."""
    f32 = jnp.float32
    rows, heads, p, n = state.shape
    per_group = heads // bmat.shape[1]
    bh = jnp.repeat(bmat.astype(f32), per_group, axis=1)  # [R, H, N]
    ch = jnp.repeat(cmat.astype(f32), per_group, axis=1)
    dt = dt.astype(f32)
    decay = jnp.exp(dt * a.astype(f32)[None, :])[..., None, None]
    x = x.astype(f32)
    new = state * decay + (x * dt[..., None])[..., None] * bh[:, :, None, :]
    y = jnp.sum(new * ch[:, :, None, :], -1) + x * d.astype(f32)[None, :, None]
    on = active[:, None, None]
    return jnp.where(on, y, 0.0), jnp.where(on[..., None], new, state)
