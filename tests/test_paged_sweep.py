"""The paged decode kernel's pipelined sweep against plain attention.

``ops.decode_block.attention_paged_batch_step`` walks every live row's
pages as one flat schedule of page GROUPS (8 pages = 128 cache rows at
page 16), several groups' copies in flight. What that could get wrong is
exactly what the token-identity tests of ``test_paged_engine.py`` are too
coarse to pin down: a group's tail beyond the context, a group that ends
on the context's last row, a row that has no group at all, a buffer slot
reused before it was read, a page id taken from the wrong row. So this
file holds the kernel, under the Pallas interpreter, to a per-row
attention written in numpy over the same pool, at the positions where
those cases live, with page ids shuffled over the pool, live and frozen
rows mixed, and loud stale content everywhere the context is not.

The kernel takes ``wqkv`` and ``wo`` from HBM by column tiles through a
ring of buffers, the query columns' tiles before the sweep and the K and
V columns' between its steps. At these widths one tile would hold a
whole weight, so the cases below run with the tile cut to one lane tile
of columns (``narrow_tiles``): Ouro's 16/16 heads then take two query
tiles, four K|V tiles under the sweep (or after it, where the schedule
is shorter) and a ``wo`` tile narrower than the buffer; 4/4 heads a
last ``wqkv`` tile of half the width; int4 weights their group scales'
columns beside the nibbles'.

``attention_paged_rows_step`` is the same sweep without the projections,
over a pool that keeps K and V of a position as ONE row (``[P, page,
2 * KV * hd]``): its cases below hold it to the same numpy attention at
the same positions, and a row that attends nothing to exact zeros.
"""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from dora_tpu.models import layers as L
from dora_tpu.ops import decode_block as DB

D, HD, PAGE = 64, 16, 16
#: (query heads, K/V heads): Qwen's grouping, two query rows a K/V head,
#: and no grouping at all (Ouro), where a K/V head serves ONE query row
#: and the sweep steps all heads of a group in one vector pass; then both
#: models' own head counts, whose ``wqkv`` is 2 and 6 tiles wide here
HEADS = pytest.mark.parametrize(
    "heads", [(4, 2), (4, 4), (12, 2), (16, 16)],
    ids=["grouped_4_2", "ungrouped_4_4", "qwen_12_2", "ouro_16_16"])
MAX_PAGES = 20  # not a multiple of the group: the last group is short
SEQ = MAX_PAGES * PAGE
GROUP = DB.sweep_group_rows(PAGE, MAX_PAGES)  # cache rows a step covers
#: 0 = a frozen row's position; the last one fills every page of a row
POSITIONS = (0, 1, PAGE - 1, PAGE, GROUP - 1, GROUP, GROUP + 1, SEQ - 1)
#: what a page holds where no context was written: finite and far from it
STALE = 40.0


def test_group_is_one_lane_tile_of_cache_rows():
    assert GROUP == 128 and DB._sweep_pages(16, 128) == 8
    assert DB._sweep_pages(8, 8) == 8       # 64 rows: all a table holds
    assert DB._sweep_pages(256, 8) == 1     # a page wider than a tile
    assert DB._SWEEP_SLOTS >= 2             # one group ahead at least


@pytest.fixture(autouse=True, scope="module")
def narrow_tiles():
    """A weight tile of one lane tile of columns, so that these widths
    span several tiles; the constant is read when the kernel is traced,
    so no trace made under it may be met outside this file, nor one
    from outside in it."""
    was, DB._WEIGHT_TILE_BYTES = DB._WEIGHT_TILE_BYTES, 1
    DB.attention_paged_batch_step.clear_cache()
    yield
    DB._WEIGHT_TILE_BYTES = was
    DB.attention_paged_batch_step.clear_cache()


def _weights(rng, heads, int4=False):
    from dora_tpu.ops.int4 import quantize_int4
    from dora_tpu.ops.int8_matmul import quantize_int8

    H, KV = heads
    quantize = quantize_int4 if int4 else quantize_int8
    nw = jnp.asarray(rng.standard_normal(D), jnp.float32)
    wqkv = quantize(jnp.asarray(
        rng.standard_normal((D, (H + 2 * KV) * HD)) * 0.2, jnp.float32))
    wo = quantize(jnp.asarray(
        rng.standard_normal((H * HD, D)) * 0.2, jnp.float32))
    bqkv = jnp.asarray(rng.standard_normal((H + 2 * KV) * HD), jnp.float32)
    return nw, wqkv, bqkv, wo


def _operands(w):
    """A quantized weight as the kernel takes it: (values, scales)."""
    return (w["int4"], w["gscale"]) if "int4" in w else (w["int8"], w["scale"])


def _dense(w):
    """The same weight as one float64 matrix."""
    if "int4" in w:
        from dora_tpu.ops.int4 import dequantize_int4

        return np.asarray(dequantize_int4(w), np.float64)
    return np.asarray(w["int8"], np.float64) * np.asarray(w["scale"], np.float64)


def _setup(rng, positions, active, kv_int8, KV):
    """Pools whose every row is stale except the live rows' contexts,
    block tables over shuffled page ids, and the operands of one tick."""
    batch = len(positions)
    pages = 1 + batch * MAX_PAGES
    ids = 1 + rng.permutation(pages - 1).astype(np.int32)
    bt = ids.reshape(batch, MAX_PAGES)  # every row owns MAX_PAGES ids
    sign = rng.choice([-1.0, 1.0], size=(2, pages, KV, PAGE, HD))
    kf, vf = (STALE * sign).astype(np.float32)
    for b, pos in enumerate(positions):
        for idx in range(pos):
            pg, off = bt[b, idx // PAGE], idx % PAGE
            kf[pg, :, off] = rng.standard_normal((KV, HD))
            vf[pg, :, off] = rng.standard_normal((KV, HD))
    if kv_int8:
        kq, ks = DB.kv_quant_rows(jnp.asarray(kf))
        vq, vs = DB.kv_quant_rows(jnp.asarray(vf))
        pools = (kq, vq, ks, vs)
        kf = np.asarray(DB.kv_dequant(kq, ks, jnp.float32))
        vf = np.asarray(DB.kv_dequant(vq, vs, jnp.float32))
    else:
        pools = (jnp.asarray(kf), jnp.asarray(vf))
    x = jnp.asarray(rng.standard_normal((batch, D)), jnp.float32)
    pos_in, bt_in = DB.freeze_inactive(
        jnp.asarray(positions, jnp.int32), jnp.asarray(bt),
        jnp.asarray(active),
    )
    return x, pools, (kf, vf), pos_in, bt_in


def _softmax_mix(q, keys, vals):
    """One query row over its gathered context, plain softmax."""
    s = keys @ q / np.sqrt(HD)
    p = np.exp(s - s.max())
    return p @ vals / p.sum()


def _reference(x, weights, kf, vf, positions, bt, heads):
    """Per-row attention in float64 numpy: the kernel's own projection
    formulas, then a plain softmax over the row's gathered context and
    its current token. Returns (x_out, k_new, v_new)."""
    H, KV = heads
    nw, wqkv, bqkv, wo = weights
    x = np.asarray(x, np.float64)
    h = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * np.asarray(nw)
    h = h.astype(np.float32).astype(np.float64)
    qkv = h @ _dense(wqkv) + np.asarray(bqkv)
    cos_t, sin_t = L.rope_table(SEQ, HD)
    cos, sin = (np.asarray(t, np.float64) for t in DB.rope_rows_at(
        cos_t, sin_t, jnp.asarray(positions, jnp.int32)))

    def rot(t):  # [B, n, HD]
        swapped = np.concatenate([t[..., HD // 2:], t[..., : HD // 2]], -1)
        return t * cos[:, None] + swapped * sin[:, None]

    batch = x.shape[0]
    q = rot(qkv[:, : H * HD].reshape(batch, H, HD))
    k_new = rot(qkv[:, H * HD: (H + KV) * HD].reshape(batch, KV, HD))
    v_new = qkv[:, (H + KV) * HD:].reshape(batch, KV, HD)
    attn = np.zeros((batch, H, HD))
    for b, pos in enumerate(positions):
        idx = np.arange(pos)
        pg, off = bt[b, idx // PAGE], idx % PAGE
        for head in range(H):
            g = head // (H // KV)
            keys = np.concatenate([kf[pg, g, off], k_new[b, g][None]])
            vals = np.concatenate([vf[pg, g, off], v_new[b, g][None]])
            attn[b, head] = _softmax_mix(q[b, head], keys, vals)
    attn = attn.reshape(batch, H * HD).astype(np.float32).astype(np.float64)
    return x + attn @ _dense(wo), k_new, v_new


def _run(seed, positions, active, kv_int8, heads, int4=False, exact=False):
    """One seeded tick through the kernel: (its results, what it was
    given, for the reference). ``exact``: rows of +-1 under a norm
    weight in eighths and ``eps`` 0, so that the normed rows are eighths
    and every partial sum of the ``wqkv`` product is a float32: the
    product is then the same to the bit in whatever order the host's
    matmul takes its columns and its sums."""
    H, KV = heads
    rng = np.random.default_rng(seed)
    weights = _weights(rng, heads, int4)
    nw, wqkv, bqkv, wo = weights
    x, pools, (kf, vf), pos_in, bt_in = _setup(
        rng, positions, active, kv_int8, KV)
    eps = 1e-6
    if exact:
        x, nw, eps = jnp.sign(x), jnp.round(nw * 8) / 8, 0.0
        weights = (nw, wqkv, bqkv, wo)
    cos_t, sin_t = L.rope_table(SEQ, HD)
    cosr, sinr = DB.rope_rows_at(cos_t, sin_t, pos_in)
    out = DB.attention_paged_batch_step(
        x, nw, *_operands(wqkv), bqkv, cosr, sinr,
        pools[0], pools[1], *_operands(wo), pos_in, bt_in,
        *pools[2:], heads=H, kv_heads=KV, head_dim=HD, eps=eps,
    )
    return out, (x, weights, pools, kf, vf, pos_in, bt_in)


def _check(seed, positions, active, kv_int8, heads, int4=False):
    out, (x, weights, pools, kf, vf, pos_in, bt_in) = _run(
        seed, positions, active, kv_int8, heads, int4)
    pos_np, bt_np = np.asarray(pos_in), np.asarray(bt_in)
    want, k_new, v_new = _reference(
        x, weights, kf, vf, pos_np, bt_np, heads)
    got = np.asarray(out[0])
    assert np.isfinite(got).all()
    # float32 sums in another order; one stale row let in moves it by 1e-1
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    # the pools: one row written per batch row, every other bit as it was
    # (frozen rows all write row 0 of the null page: any of them may win)
    new = [np.asarray(a) for a in out[1:]]
    old = [np.asarray(a).copy() for a in pools]
    dumped = int((bt_np[np.arange(len(pos_np)), pos_np // PAGE] == 0).sum())
    for b, pos in enumerate(pos_np):
        pg, off = bt_np[b, pos // PAGE], pos % PAGE
        if kv_int8:
            rows = (*DB.kv_quant_rows(jnp.asarray(k_new[b], jnp.float32)),
                    *DB.kv_quant_rows(jnp.asarray(v_new[b], jnp.float32)))
            rows = [np.asarray(rows[i]) for i in (0, 2, 1, 3)]
        else:
            rows = [k_new[b], v_new[b]]
        for plane, was, row in zip(new, old, rows):
            if pg == 0 and dumped > 1:
                assert pos == 0
                was[pg, :, off] = plane[pg, :, off]
                continue
            if plane.dtype == np.int8:  # round-to-nearest of a near-tie
                assert np.abs(plane[pg, :, off].astype(int) - row).max() <= 1
            else:
                np.testing.assert_allclose(
                    plane[pg, :, off], row, rtol=1e-5, atol=1e-5)
            was[pg, :, off] = plane[pg, :, off]
    for plane, was in zip(new, old):
        np.testing.assert_array_equal(plane, was)


KV_KINDS = pytest.mark.parametrize(
    "kv_int8", [False, True], ids=["fp_kv", "int8_kv"])


@HEADS
@KV_KINDS
@pytest.mark.parametrize("pos", POSITIONS)
def test_one_row_matches_plain_attention(pos, kv_int8, heads):
    _check(100 + pos, [pos], [pos > 0], kv_int8, heads)


def _sixteen_rows(seed):
    """Every boundary position twice over 16 rows in a seeded order, five
    of them frozen mid-life."""
    rng = np.random.default_rng(seed)
    positions = rng.permutation(np.repeat(POSITIONS, 2)).tolist()
    active = np.ones(16, bool)
    active[rng.choice(16, size=5, replace=False)] = False
    return positions, active.tolist()


@HEADS
@KV_KINDS
@pytest.mark.parametrize("seed,int4", [
    (0, False), (1, False), (2, False), (0, True),
], ids=["seed_0", "seed_1", "seed_2", "seed_0_int4"])
def test_sixteen_rows_live_and_frozen_match_plain_attention(
        seed, int4, kv_int8, heads):
    """Every boundary position twice over 16 rows in a seeded order, five
    of them frozen mid-life (their positions and tables zeroed by
    ``freeze_inactive``, as a decode window does the tick they finish):
    a live row's first group follows a frozen row's none, short rows sit
    between long ones, and every slot is reused across row boundaries;
    once over int4 weights, whose tiles take their group scales' columns."""
    _check(seed, *_sixteen_rows(seed), kv_int8, heads, int4)


# -- the same float32 sums as the whole-weight kernel ---------------------------

#: (heads, int8 pages, int4 weights) of the seeded ticks below
EXACT_CASES = (((16, 16), False, False), ((12, 2), True, False),
               ((16, 16), False, True))
#: sha256 over every result (rows out, then the pools) of those ticks from
#: the kernel as it stood before it streamed its weights (commit de633c4:
#: ``wqkv`` and ``wo`` whole in VMEM, one product each, the insert before
#: the sweep), computed by ``exact_ticks_sha256`` under the same flag
WHOLE_WEIGHT_SHA256 = (
    "c0f524e32a2594177031c49a6106fdaa675fe77c4c1a9e2adb80c98433c4c824")


def exact_ticks_sha256():
    """For a process of its own (it narrows the tiles as ``narrow_tiles``
    does and leaves them so)."""
    import hashlib

    DB._WEIGHT_TILE_BYTES = 1
    digest = hashlib.sha256()
    for heads, kv_int8, int4 in EXACT_CASES:
        out, _ = _run(0, *_sixteen_rows(0), kv_int8, heads, int4, exact=True)
        for result in out:
            digest.update(np.asarray(result).tobytes())
    return digest.hexdigest()


def test_streamed_tiles_give_the_whole_weight_kernels_bits():
    """Column tiles leave every output column's sum over D as it was, the
    K and V columns' products move between the sweep's steps and the
    insert behind it: the same operands in the same float32 sums, so
    sixteen rows at Ouro's and Qwen's head counts come out as they did
    from the whole-weight kernel, to the bit, int8 pages and int4
    weights included. Two things are the host's and are taken out: its
    matmul takes a sum's terms in an order that depends on how many
    columns it is given (``exact`` makes every partial sum of the
    ``wqkv`` product a float32), and its compiler contracts ``a * b + c``
    differently in two programs (a child process runs the interpreter
    with the backend's optimizations off)."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    flags = os.environ.get("XLA_FLAGS", "")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"{flags} --xla_backend_optimization_level=0"}
    done = subprocess.run(
        [sys.executable, "-c", "from tests import test_paged_sweep as T; "
                               "print(T.exact_ticks_sha256())"],
        cwd=Path(__file__).resolve().parent.parent, env=env,
        capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.split()[-1] == WHOLE_WEIGHT_SHA256


# -- the projection-free entry: K and V of a position as one pool row ----------

#: query rows a K/V head serves: the least the MXU form takes, K-EXAONE's
ROWS = pytest.mark.parametrize("rows", [2, 8], ids=["rows_2", "rows_8"])
KV_JOINED = 2


def _check_joined(seed, positions, active, rows):
    """``positions[b]`` is where row b's tick stands: its K/V row is in
    the pool already (the caller's scatter), so a live row attends
    ``positions[b] + 1`` rows and a frozen one none."""
    rng = np.random.default_rng(seed)
    batch, KV = len(positions), KV_JOINED
    pages = 1 + batch * MAX_PAGES
    bt = (1 + rng.permutation(pages - 1).astype(np.int32)).reshape(
        batch, MAX_PAGES)
    pool = (STALE * rng.choice([-1.0, 1.0], size=(pages, PAGE, 2 * KV * HD))
            ).astype(np.float32)
    counts = np.where(active, np.asarray(positions) + 1, 0)
    for b, n in enumerate(counts):
        idx = np.arange(n)
        pool[bt[b, idx // PAGE], idx % PAGE] = rng.standard_normal(
            (n, 2 * KV * HD))
    q = rng.standard_normal((batch, KV, rows, HD)).astype(np.float32)
    _, bt_in = DB.freeze_inactive(
        jnp.asarray(positions, jnp.int32), jnp.asarray(bt),
        jnp.asarray(active))
    got = np.asarray(DB.attention_paged_rows_step(
        jnp.asarray(q), jnp.asarray(pool), jnp.asarray(counts, jnp.int32),
        bt_in))
    assert got.shape == q.shape and got.dtype == np.float32
    assert np.isfinite(got).all()
    want = np.zeros(q.shape)
    for b, n in enumerate(counts):
        idx = np.arange(n)
        ctx = pool[bt[b, idx // PAGE], idx % PAGE].astype(np.float64)
        ctx = ctx.reshape(n, 2, KV, HD)
        for h in range(KV if n else 0):
            for r in range(rows):
                want[b, h, r] = _softmax_mix(
                    q[b, h, r].astype(np.float64), ctx[:, 0, h], ctx[:, 1, h])
    # a row that attends nothing: zeros, whatever the null page holds
    assert (got[counts == 0] == 0.0).all()
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@ROWS
@pytest.mark.parametrize("active", [True, False], ids=["live", "frozen"])
@pytest.mark.parametrize("pos", POSITIONS)
def test_one_row_of_joined_pages_matches_plain_attention(pos, active, rows):
    _check_joined(200 + pos, [pos], [active], rows)


@ROWS
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sixteen_rows_of_joined_pages_live_and_frozen_match_plain_attention(
        seed, rows):
    """The sixteen-row case above for the projection-free entry: every
    boundary position twice in a seeded order, five rows frozen with
    their tables zeroed, page ids shuffled over the pool."""
    rng = np.random.default_rng(seed)
    positions = rng.permutation(np.repeat(POSITIONS, 2)).tolist()
    active = np.ones(16, bool)
    active[rng.choice(16, size=5, replace=False)] = False
    _check_joined(seed, positions, active.tolist(), rows)
