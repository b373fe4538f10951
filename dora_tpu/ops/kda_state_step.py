"""One decode token of the gated delta rule (KDA), for every live row of
a batch, in one pass over the recurrent state, which stays in HBM.

Per row ``r`` and head ``h`` the state is ``S [d_k, d_v]`` float32 (128 x
128 = 64 KB for GLM-5.3-Flash) and one token does

    S~   = S * exp(g)[:, None]                      # a decay a key channel
    pred = sum over d_k of S~ * k[:, None]          # what the state holds for k
    S'   = S~ + (beta * k)[:, None] * (v - pred)[None, :]
    o    = sum over d_k of S' * q[:, None]

``pred`` is a reduction over the whole head that the update needs, so
plain XLA sweeps the state three times (read for ``pred``, read for the
update, write) and, under a ``jnp.where(active, new, old)``, for every
row whether it is live or not. Here a grid step holds a block of heads
in VMEM: the block is read from HBM once and written once, the two
reductions are sublane sums over what is already on chip, and, as in
``ops/ssm_state_step`` (whose ``schedule`` this reuses), **a row whose
``active`` bit is off moves no state**: its steps name the block the
pipeline already holds, so no copy in and none out is scheduled.

``d_k`` lies on sublanes and ``d_v`` on lanes. The four per-key-channel
vectors of a head (``exp(g)``, ``k``, ``beta * k``, ``q``) must be
columns; they come in as ``[R, blocks, d_k, 4 * hb]`` (a small transpose
in XLA), so that a head's column is a static lane of the block and the
kernel transposes nothing. Products and sums are float32 on the vector
unit: no bf16 operand and no MXU pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dora_tpu.backend import interpret as _interpret
from dora_tpu.ops.ssm_state_step import _COPY, _STEP, schedule

#: bytes of state one grid step holds (in and out, double-buffered: four
#: of these in VMEM): 1 MB is 16 heads of 128 x 128 float32.
_BLOCK_BYTES = 1 << 20

#: the columns of a head, in the order they are packed on lanes
_DECAY, _K, _BETA_K, _Q = range(4)


def head_block(heads: int, d_k: int, d_v: int) -> int:
    """Heads a grid step: the largest divisor of ``heads`` whose state
    fits ``_BLOCK_BYTES``, a multiple of 8 (the rows of ``v`` and ``o``
    are sublanes of their blocks) unless it is all the heads."""
    cap = max(_BLOCK_BYTES // (d_k * d_v * 4), 1)
    return max((d for d in range(1, heads + 1)
                if heads % d == 0 and d <= cap and (d % 8 == 0 or d == heads)),
               default=heads)


def _kernel(src_row, src_blk, mode, cols_ref, v_ref, s_ref, o_ref, out_ref,
            *, hb: int):
    del src_row, src_blk
    r, j = pl.program_id(0), pl.program_id(1)

    @pl.when(mode[r] == _STEP)
    def _step():
        def col(which, i):
            lane = which * hb + i
            return cols_ref[0, 0, :, lane : lane + 1]  # [d_k, 1]

        for i in range(hb):
            decayed = s_ref[0, i] * col(_DECAY, i)  # [d_k, d_v]
            pred = jnp.sum(decayed * col(_K, i), axis=0, keepdims=True)
            new = decayed + col(_BETA_K, i) * (v_ref[0, i : i + 1, :] - pred)
            out_ref[0, i] = new
            o_ref[0, i : i + 1, :] = jnp.sum(
                new * col(_Q, i), axis=0, keepdims=True)

    # No row is active: the one block the grid names goes back as it came.
    @pl.when((mode[r] == _COPY) & (j == 0))
    def _copy():
        out_ref[...] = s_ref[...]


@jax.jit
def kda_state_step(state, g, k, q, v, beta, active):
    """One token of the gated delta rule for the active rows.

    state ``[R, H, d_k, d_v]`` float32, aliased in and out (donate it:
    rows with ``active`` off are neither read nor written); g ``[R, H,
    d_k]`` (log decays, <= 0), k, q ``[R, H, d_k]``; v ``[R, H, d_v]``;
    beta ``[R, H]``; active ``[R]`` bool. Returns (o ``[R, H, d_v]``
    float32 — zeros for inactive rows — and the state).
    """
    rows, heads, dk, dv = state.shape
    hb = head_block(heads, dk, dv)
    blocks = heads // hb
    f32 = jnp.float32
    k = k.astype(f32)
    cols = jnp.stack(
        [jnp.exp(g.astype(f32)), k, beta.astype(f32)[..., None] * k,
         q.astype(f32)], axis=1)  # [R, 4, H, d_k]
    cols = cols.reshape(rows, 4, blocks, hb, dk)
    cols = cols.transpose(0, 2, 4, 1, 3).reshape(rows, blocks, dk, 4 * hb)
    src_row, src_blk, mode = schedule(active, blocks)

    def blk(r, j, src_row, src_blk, mode):
        return jnp.where(mode[r] == _STEP, j, src_blk[r])

    def per_head(r, j, src_row, src_blk, mode):
        return (src_row[r], blk(r, j, src_row, src_blk, mode), 0)

    def per_block(r, j, src_row, src_blk, mode):
        return (src_row[r], blk(r, j, src_row, src_blk, mode), 0, 0)

    o, state = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(rows, blocks),
            in_specs=[
                pl.BlockSpec((1, 1, dk, 4 * hb), per_block),  # columns
                pl.BlockSpec((1, hb, dv), per_head),  # v
                pl.BlockSpec((1, hb, dk, dv), per_block),  # state
            ],
            out_specs=[
                pl.BlockSpec((1, hb, dv), per_head),
                pl.BlockSpec((1, hb, dk, dv), per_block),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((rows, heads, dv), f32),
            jax.ShapeDtypeStruct(state.shape, f32),
        ],
        # operand 5 (the three tables included) is the state -> output 1
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        name="kda_state_step",
        interpret=_interpret(),
    )(src_row, src_blk, mode, cols, v.astype(f32), state)
    return jnp.where(active[:, None, None], o, 0.0), state


def kda_state_step_reference(state, g, k, q, v, beta, active):
    """The same update in plain ``jax.numpy`` over the whole array (what
    the kernel is tested against; it reads every row's state twice and
    writes it once)."""
    decayed = state * jnp.exp(g)[..., None]
    pred = (decayed * k[..., None]).sum(-2)  # S~^T k  [R, H, d_v]
    new = decayed + (beta[..., None] * k)[..., None] * (v - pred)[..., None, :]
    o = (new * q[..., None]).sum(-2)
    on = active[:, None, None]
    return jnp.where(on, o, 0.0), jnp.where(on[..., None], new, state)
