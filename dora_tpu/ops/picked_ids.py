"""The positions of a row's ``k`` largest scores, without a sort: the one
step of a decode tick's selection between its scores and its row gather.

``lax.top_k`` of ``[4, 16384]`` -> 2,048 is a bitonic sort of 16,384
key/index pairs on the chip, some 105 compare-exchange stages whatever
the leading dimension: 85 us for 4 rows and for 8, 167 for 16, to put in
order 2,048 ids that the gather, the attention and the counters take as
a SET. Here the same set, position for position (equal scores to the
lower positions, ``-0.0`` below ``+0.0`` as the sort's total order has
them), in ascending position, in ONE kernel over the group's scores,
which sit in VMEM whole (256 KB): 12.4 us for 4 rows, 24.3 for 8, 48.3
for 16 (``PERF.md`` section 6, PR 57). Two steps:

1. **The k-th largest by counts.** The floats' bit patterns are put in
   their order as integer keys, and the k-th largest key is found
   ``_KEY_BITS`` bits a pass: a pass counts the keys at or above each of
   the 16 trial values under the prefix found so far, and keeps the
   largest that still has ``k``. A count is 16 compares and adds of a
   row's 16 vregs, then two small products that sum sublanes and lanes
   (0/1 sums up to 256 are exact in bf16 x bf16 -> f32), so a prefix is a
   lane-replicated row and nothing is a scalar. 2 bits a pass read the
   same (11.7 us); as plain XLA both steps read 32 us at 4 bits a pass
   and 99 at 6; the chunk's ``keye_vl2.kth_largest`` over ``[256,
   16384]``, a bit a pass, reads 167 us against 600 at 4 bits: there a
   pass is its compares, here it is latency.
2. **Mask -> ids by compare-and-sum.** The mask is the keys above the
   k-th and the level's lower positions up to ``k`` (a running count: one
   product with a triangle inside a block of 128 lanes, one over the
   blocks). Its set positions are named slot by slot: a slot's block is
   how many blocks END at or before it (a compare and a sum over the
   blocks' running counts, the sum a product), its rank inside is the slot
   less what those blocks hold, that block's row of running counts comes
   by a one-hot product, and its offset is how many of the row's lanes
   count at most the rank. No scatter, no ``nonzero``, no gather of
   scalars: XLA:TPU runs those an element at a time.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dora_tpu.backend import interpret as _interpret

#: bits of the k-th key that one pass of counts settles
_KEY_BITS = 4
#: positions of one block of the mask: a lane row
_LANES = 128
#: rows of one grid step: their passes interleave (17.5 us for 4 rows a
#: step at a time, 11.8 together)
_ROWS = 4
#: blocks of one count: a trial's partial sums stay exact in bf16
_COUNT_BLOCKS = 256


def _kernel(s_ref, out_ref, keys_ref, *, k: int):
    rows, b, w = s_ref.shape
    kp = out_ref.shape[-1]
    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    trials = 1 << _KEY_BITS
    top = jnp.int32(-(2 ** 31))
    iota = jax.lax.broadcasted_iota

    def dot(a, c):  # 0/1 and counts up to 256: exact
        return jnp.dot(a.astype(bf16), c.astype(bf16),
                       preferred_element_type=f32)

    def dot_t(a, c):  # a [m, x] . c [n, x] -> [m, n]
        return jax.lax.dot_general(
            a.astype(bf16), c.astype(bf16), (((1,), (1,)), ((), ())),
            preferred_element_type=f32)

    ones_w = jnp.ones((w, w), bf16)
    ones_8w = jnp.ones((8, w), bf16)
    ones_8b = jnp.ones((8, b), bf16)
    upto = iota(i32, (w, w), 0) <= iota(i32, (w, w), 1)
    downto = iota(i32, (w, w), 0) >= iota(i32, (w, w), 1)
    earlier = iota(i32, (b, b), 1) < iota(i32, (b, b), 0)
    # the 8 sublanes of each trial's partial counts, summed by a product
    group = (iota(i32, (trials, trials * 8), 1) // 8
             == iota(i32, (trials, trials * 8), 0))

    for r in range(rows):
        v = pltpu.bitcast(s_ref[r], i32)
        keys_ref[r] = v ^ ((v >> 31) & 0x7FFFFFFF)  # the floats' order, signed

    def a_pass(p, found):
        """``found [1, w]`` a row, every lane alike: the k-th key's bits
        above ``low`` (counted from 0 = the lowest key) -> with the next
        ``_KEY_BITS``."""
        low = 32 - _KEY_BITS * (p + 1)
        new = []
        for r in range(rows):
            count = jnp.zeros((trials, w), f32)
            for at in range(0, b, _COUNT_BLOCKS):
                key = keys_ref[r, at : at + _COUNT_BLOCKS]
                parts = []
                for j in range(trials):
                    trial = (found[r] | jnp.left_shift(jnp.int32(j), low)) ^ top
                    ge = (key >= trial).astype(f32)
                    parts.append(ge.reshape(-1, 8, w).sum(0))
                per = dot(group, jnp.concatenate(parts, 0))  # [trials, w]
                count += dot(per, ones_w)  # every lane: the total
            # counts fall as trials rise (trial 0 always has k): how many
            # have enough, less one, IS the digit
            digit = (count >= k).astype(i32).sum(0, keepdims=True) - 1
            new.append(found[r] | jnp.left_shift(digit, low))
        return tuple(new)

    found = jax.lax.fori_loop(
        0, 32 // _KEY_BITS, a_pass,
        tuple(jnp.zeros((1, w), i32) for _ in range(rows)))

    slot = iota(i32, (1, kp), 1).astype(f32)
    blocks = iota(i32, (b, 1), 0).astype(f32)
    for r in range(rows):
        key, kth = keys_ref[r], found[r] ^ top
        above, level = key > kth, key == kth
        # [b, w] below: a block's own number in every lane
        room = k - dot(ones_8b, dot(above, ones_w))[:1]
        count = dot(level, ones_w)
        fits = dot(level, upto) + dot(earlier, count) <= room
        sel = above | (level & fits)
        count = dot(sel, ones_w)
        ends = dot(earlier, count) + count
        at = ends[:, :1] <= slot  # [b, kp]: the blocks that end before a slot
        both = dot(jnp.concatenate([ones_8b, dot_t(ones_8w, sel)], 0), at)
        block, rank = both[:1], slot - both[8:9]  # [1, kp]
        row = dot(dot_t(downto, sel), blocks == block)  # [w, kp]
        offset = dot(ones_8w, row <= rank)[:1]
        out_ref[r] = (block * w + offset).astype(i32)


@functools.partial(jax.jit, static_argnames=("k",))
def picked_ids(s, k: int):
    """The positions of the ``k`` largest of each row of float32 scores
    ``s [R, N]`` (``-inf`` where a row may not look): the SET
    ``lax.top_k(s, k)[1]`` holds, equal scores to the lower positions, in
    ascending position, ``[R, k]`` int32. Rows are independent; ``_ROWS``
    of them (or what of it divides ``R``) share a grid step."""
    r, n = s.shape
    if not 0 < k <= n:
        raise ValueError(f"picked_ids: {k} of {n} scores")
    if s.dtype != jnp.float32:
        raise ValueError(f"picked_ids: scores are float32, not {s.dtype}")
    w = _LANES
    # whole vregs of blocks; what is added holds the lowest key at the
    # highest positions, so it is picked last: never, since k <= n
    s = jnp.pad(s, ((0, 0), (0, -n % (8 * w))), constant_values=-jnp.inf)
    b = s.shape[1] // w
    rows = math.gcd(r, _ROWS)
    kp = k + -k % w
    out = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=(r // rows,),
        in_specs=[pl.BlockSpec((rows, b, w), lambda g: (g, 0, 0))],
        out_specs=pl.BlockSpec((rows, 1, kp), lambda g: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1, kp), jnp.int32),
        scratch_shapes=[pltpu.VMEM((rows, b, w), jnp.int32)],
        interpret=_interpret(),
        name="picked_ids",
    )(s.reshape(r, b, w))
    return out[:, 0, :k]
