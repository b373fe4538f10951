"""Rows that a selection picked, fetched from a paged pool and attended:
the two steps around XLA's row gather that a decode tick needs, in the
form that is cheap on the chip. For R = 4 rows x 2,048 picked positions
of 2,048 B on a v5e (``PERF.md`` section 6, PR 50): addresses by
``take_along_axis`` 108 us, by :func:`pool_rows` 21; the gather itself
131 (16 ns a row); keys and values split out of the gathered rows and
attended 39, :func:`attend_rows` 22; the whole 290 -> 158.

There is no kernel here, and the measurements say why. Mosaic slices a
tiled dimension of an HBM ref by whole tiles, so one position's row of a
``[P, page, width]`` leaf is no copy's source
(``tests/test_chip_compile.py`` keeps the refusal); over a leaf whose row
IS a tile (``[P, page, 8, 128]`` bf16) a kernel's scalar core issues a
row copy every 21-22 ns, unrolled 8-16 times: slower than XLA's gather
before anything is multiplied.

Both functions are jitted so that a model's layers lower one function
each (a program's first launch is Python tracing and lowering:
``setup_s``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnames=("page",))
def pool_rows(block_tables, ids, page: int):
    """Positions ``ids [R, n]`` -> their rows of a pool leaf seen flat
    (``[P * page, ...]``) through ``block_tables [R, max_pages]``: the
    page by a compare and a sum over the table's row, exact for any page
    number (XLA:TPU gathers scalars one at a time)."""
    at = (ids // page)[..., None] == jnp.arange(block_tables.shape[1])
    return (jnp.where(at, block_tables[:, None, :], 0).sum(-1) * page
            + ids % page)


@jax.jit
def attend_rows(q, held, seen):
    """``q [R, KV, G, hd]`` over gathered rows ``held [R, n, 2 * KV * hd]``
    (a position's keys, then its values), those ``seen [R, n]``, a head at
    a time: its keys and values are LANE slices of the rows (splitting
    them into ``[2, KV, hd]`` relays every row out: 33 us for 4 x 2,048).
    Scores and sums float32, probabilities in the rows' dtype. Returns the
    context ``[R, KV, G, hd]`` float32, zeros for a row that sees nothing."""
    f32, (_, kv, _, hd) = jnp.float32, q.shape
    seen, out = seen[:, None, :], []
    for h in range(kv):
        keys, values = (held[..., i * hd : (i + 1) * hd] for i in (h, kv + h))
        s = jnp.einsum("bgd,bnd->bgn", q[:, h], keys,
                       preferred_element_type=f32)
        s = jnp.where(seen, s * hd ** -0.5, -1e30)
        p = jnp.where(seen, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
        mix = jnp.einsum("bgn,bnd->bgd", p.astype(values.dtype), values,
                         preferred_element_type=f32)
        out.append(mix / jnp.maximum(p.sum(-1), 1e-30)[..., None])
    return jnp.stack(out, 1)
