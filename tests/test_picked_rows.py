"""The decode tick's fetch of the picked rows and their attention
(``ops/picked_rows``: ``pool_rows`` + the flat row gather + ``attend_rows``)
against a plain ``jax.numpy`` gather-and-softmax, the tick's ids without a
sort (``ops/picked_ids``) against ``lax.top_k``'s set, and ``dsa_decode``
through all of them against the XLA form they replaced (PR 49's with its
``lax.top_k``, kept below line for line as the reference), on the CPU in
float32.

No kernel is under test: the picked-rows kernel this file was asked for
cannot be written over a ``[P, page, 2 * KV * hd]`` leaf (Mosaic slices a
tiled dimension by whole tiles of 8 rows, never by one:
``tests/test_chip_compile.py`` keeps that refusal as a test), and over a
leaf whose row is one tile it read 250 us where these three steps read
158 (``PERF.md`` section 6, PR 50).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dora_tpu.models.hf import keye_vl2 as K
from dora_tpu.ops.picked_ids import picked_ids
from dora_tpu.ops.picked_rows import attend_rows, pool_rows
from tests.test_keye_vl2 import (  # noqa: F401  (ckpt, model: fixtures)
    MAX_SEQ, PAGE, TOPK, ckpt, model)

HD = 16


def case(rows: int, g: int, kv: int, topk: int, counts, page: int = 8,
         max_pages: int = 12, seed: int = 0):
    """A pool of ``rows * max_pages + 1`` pages behind shuffled block
    tables (no page twice), ``topk`` distinct positions a row in no order
    (so neighbours in ``ids`` lie pages apart), queries, counts."""
    rng = np.random.default_rng(seed)
    pages = rows * max_pages + 1
    pool = rng.standard_normal((pages, page, 2 * kv * HD)).astype(np.float32)
    bt = (rng.permutation(pages - 1)[: rows * max_pages] + 1).reshape(
        rows, max_pages).astype(np.int32)
    ids = np.stack([rng.permutation(page * max_pages)[:topk]
                    for _ in range(rows)]).astype(np.int32)
    q = rng.standard_normal((rows, kv, g, HD)).astype(np.float32)
    return q, pool, ids, np.asarray(counts, np.int32), bt


def plain(q, pool, ids, counts, bt):
    """Row by row, head by head: the first ``counts[r]`` picked positions
    through the block table, a whole softmax."""
    rows, kv, g, hd = q.shape
    page = pool.shape[1]
    out = np.zeros(q.shape, np.float32)
    for r in range(rows):
        at = ids[r, : counts[r]]
        if not len(at):
            continue
        held = pool[bt[r, at // page], at % page].reshape(len(at), 2, kv, hd)
        for h in range(kv):
            s = q[r, h] @ held[:, 0, h].T / np.sqrt(hd)
            p = np.exp(s - s.max(-1, keepdims=True))
            out[r, h] = (p / p.sum(-1, keepdims=True)) @ held[:, 1, h]
    return out


def through_the_program(q, pool, ids, counts, bt):
    page = pool.shape[1]
    flat = jnp.asarray(pool).reshape(-1, pool.shape[-1])
    rows_at = pool_rows(jnp.asarray(bt), jnp.asarray(ids), page)
    seen = jnp.arange(ids.shape[1])[None, :] < jnp.asarray(counts)[:, None]
    return rows_at, attend_rows(jnp.asarray(q), flat[rows_at], seen)


@pytest.mark.parametrize("rows,g,kv,topk,counts", [
    (4, 8, 2, 16, [16, 16, 16, 16]),   # every row selects: count = topk
    (4, 8, 2, 16, [16, 5, 1, 11]),     # rows below topk: a prefix of the ids
    (4, 8, 2, 16, [16, 0, 9, 0]),      # frozen rows and spare entries: zeros
    (4, 2, 4, 24, [24, 17, 0, 3]),     # G = 2, four K/V heads
    (1, 8, 2, 16, [16]),               # R = 1
    (1, 2, 1, 40, [33]),               # one K/V head, a count inside the row
    (4, 8, 4, 96, [96, 95, 64, 1]),    # every position of the table picked
], ids=["selecting", "prefix", "zero-counts", "g2-kv4", "r1", "kv1", "all"])
def test_picked_rows_fetched_and_attended_against_a_plain_gather(
        rows, g, kv, topk, counts):
    q, pool, ids, counts, bt = case(rows, g, kv, topk, counts)
    rows_at, got = through_the_program(q, pool, ids, counts, bt)
    # the addresses are take_along_axis's, to the row
    page = pool.shape[1]
    np.testing.assert_array_equal(
        rows_at, np.take_along_axis(bt, ids // page, 1) * page + ids % page)
    # picks that lie next to each other in ids cross pages, under a
    # shuffled table
    assert (np.diff(np.asarray(rows_at) // page, axis=1) != 0).mean() > 0.8
    want = plain(q, pool, ids, counts, bt)
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert not np.asarray(got)[counts == 0].any()


def test_a_row_that_is_not_seen_moves_nothing():
    """What lies past a row's count is gathered (the shapes are fixed) and
    must not show: another pool row there, the same context."""
    q, pool, ids, counts, bt = case(4, 8, 2, 16, [16, 5, 0, 11])
    _, want = through_the_program(q, pool, ids, counts, bt)
    other = pool.copy()
    page = pool.shape[1]
    for r in range(4):
        at = ids[r, counts[r]:]
        other[bt[r, at // page], at % page] = 1e4
    _, got = through_the_program(q, other, ids, counts, bt)
    np.testing.assert_array_equal(got, want)


def test_page_numbers_past_sixteen_bits_come_through():
    bt = jnp.asarray([[70_001, 3, 1_000_003], [5, 2_000_000, 9]], jnp.int32)
    ids = jnp.asarray([[17, 0, 9], [23, 8, 15]], jnp.int32)
    np.testing.assert_array_equal(
        pool_rows(bt, ids, 8),
        [[1_000_003 * 8 + 1, 70_001 * 8, 3 * 8 + 1],
         [9 * 8 + 7, 2_000_000 * 8, 2_000_000 * 8 + 7]])


# ---------------------------------------------------------------------------
# the ids without a sort
# ---------------------------------------------------------------------------


def _random(rng, r, n, k):
    return rng.standard_normal((r, n))


def _integers(rng, r, n, k):
    """A handful of levels: the k-th largest is shared, and its level
    holds more positions than have room."""
    return rng.integers(-2, 3, (r, n))


def _level_fits(rng, r, n, k):
    """Ties across the k-th that do not overflow: k - 2 distinct scores on
    top, then exactly two at the level, the rest below."""
    s = np.tile(np.arange(n, 0, -1.0), (r, 1))
    s[:, k - 2 : k] = s[:, k - 1 : k]
    return np.stack([rng.permutation(row) for row in s])


def _both_zeros(rng, r, n, k):
    """``+0.0`` beside ``-0.0`` across the k-th: ``lax.top_k`` holds the
    first above the second (the sort's total order)."""
    s = np.where(rng.random((r, n)) < 0.5, 0.0, -0.0)
    s[:, : k // 2] = rng.standard_normal((r, k // 2))
    return s


def _tail(finite):
    def make(rng, r, n, k):
        s = rng.standard_normal((r, n))
        s[:, k + finite:] = -np.inf
        return s
    return make


def _all_equal(rng, r, n, k):
    return np.full((r, n), 0.25)


@pytest.mark.parametrize("rows,n,k", [
    (1, 32, 8), (4, 32, 8), (16, 32, 8), (4, 16384, 2048),  # the cell's group
], ids=["1x32-8", "4x32-8", "16x32-8", "4x16384-2048"])
@pytest.mark.parametrize("scores", [
    _random, _integers, _level_fits, _both_zeros, _tail(0), _tail(1),
    _all_equal,
], ids=["random", "integers", "level-fits", "both-zeros", "k-finite",
        "k-plus-1-finite", "all-equal"])
def test_picked_ids_are_top_ks_set_in_ascending_position(scores, rows, n, k):
    rng = np.random.default_rng(rows * n + k)
    s = jnp.asarray(scores(rng, rows, n, k), jnp.float32)
    ids = np.asarray(picked_ids(s, k))
    want = np.asarray(jax.lax.top_k(s, k)[1])
    assert ids.shape == (rows, k) and ids.dtype == np.int32
    for r in range(rows):
        assert (np.diff(ids[r]) > 0).all(), r  # ascending, so distinct
        assert ids[r].tolist() == sorted(want[r].tolist()), r


@pytest.mark.parametrize("k,n,dtype,match", [
    (0, 32, jnp.float32, "0 of 32"), (33, 32, jnp.float32, "33 of 32"),
    (8, 32, jnp.bfloat16, "float32"),
])
def test_picked_ids_refuses_what_it_cannot_pick(k, n, dtype, match):
    with pytest.raises(ValueError, match=match):
        picked_ids(jnp.zeros((2, n), dtype), k)


def test_picked_ids_counts_past_256_blocks_exactly():
    """Over 32,768 scores a trial's partial counts pass what bf16 holds:
    they are taken 256 blocks at a time."""
    rng = np.random.default_rng(9)
    s = jnp.asarray(np.round(rng.standard_normal((2, 40000)) * 2), jnp.float32)
    want = np.asarray(jax.lax.top_k(s, 3000)[1])
    assert np.asarray(picked_ids(s, 3000)).tolist() == np.sort(want, -1).tolist()


# ---------------------------------------------------------------------------
# dsa_decode against the form it replaced
# ---------------------------------------------------------------------------


def dsa_decode_pr49(blk, cfg, u, pool, positions, block_tables, live, rope,
                    block):
    """``keye_vl2.dsa_decode`` as PR 49 left it: the ids by ``lax.top_k``
    (in descending score), the addresses by ``take_along_axis``, the rows
    split into keys and values of every head (``_split_rows``), two einsums
    over all heads."""
    f32 = jnp.float32
    kvp, ikp = pool["kv"], pool["ik"]
    page, k_ = kvp.shape[1], cfg.idx_topk
    b = u.shape[0]
    rows, t = jnp.arange(b), positions
    q, k, v, qi, ki, wi = K.project(blk, cfg, u, rope)
    pages = block_tables[rows, t // page]
    kvp = kvp.at[pages, t % page].set(K.L.kv_rows(cfg, k, v).astype(kvp.dtype))
    pack = ikp.shape[2] // cfg.idx_dim
    at = (t % page) // pack
    lane = jnp.arange(pack * cfg.idx_dim) // cfg.idx_dim
    ikp = ikp.at[pages, at].set(jnp.where(
        lane[None, :] == (t % pack)[:, None],
        jnp.tile(ki.astype(ikp.dtype), (1, pack)), ikp[pages, at]))
    order, n_live = live
    r = K.decode_group(b)
    first = jnp.broadcast_to(jnp.arange(k_), (r, k_))
    per = block // page
    flat = kvp.reshape(-1, kvp.shape[-1])

    def group(g, carry):
        ctx, seen_rows, picked = carry
        mine = jax.lax.dynamic_slice_in_dim(order, g * r, r)
        ok = g * r + jnp.arange(r) < n_live
        t_g, bt = t[mine], block_tables[mine]
        selecting = ok & (t_g >= k_)

        def scored(_):
            def keys_of(j):
                ids = jax.lax.dynamic_slice_in_dim(bt, j * per, per, 1)
                return ikp[ids].reshape(r, block, cfg.idx_dim)

            s = K.index_scores(
                cfg, qi[mine], wi[mine], keys_of,
                jnp.where(selecting, t_g + 1, 0),
                jnp.where(selecting, t_g, 0).max() // block + 1, block)
            return jax.lax.top_k(s, k_)[1]

        ids = jax.lax.cond(selecting.any(), scored, lambda _: first, None)
        ids = jnp.where(selecting[:, None], ids, first)
        seen = (selecting[:, None] | (ids <= t_g[:, None])) & ok[:, None]
        held = flat[jnp.take_along_axis(bt, ids // page, 1) * page
                    + ids % page]
        keys, values = K._split_rows(cfg, held)  # [R, topk, KV, hd]
        s = jnp.einsum("bkgd,bnkd->bkgn", q[mine], keys,
                       preferred_element_type=f32)
        s = jnp.where(seen[:, None, None, :], s * cfg.head_dim ** -0.5,
                      -1e30)
        p = jnp.where(seen[:, None, None, :],
                      jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
        mix = jnp.einsum("bkgn,bnkd->bkgd", p.astype(values.dtype),
                         values, preferred_element_type=f32)
        mix = mix / jnp.maximum(p.sum(-1), 1e-30)[..., None]
        return (ctx.at[mine].set(mix),
                seen_rows.at[mine].set(seen.sum(-1, dtype=jnp.int32)),
                picked.at[mine].set(ids))

    ctx, seen_rows, picked = jax.lax.fori_loop(
        0, (n_live + r - 1) // r, group,
        (jnp.zeros(q.shape, f32), jnp.zeros((b,), jnp.int32),
         jnp.broadcast_to(jnp.arange(k_), (b, k_))))
    out = K._out(blk, cfg, ctx, u.dtype)
    return out, {"kv": kvp, "ik": ikp}, {
        "rows": seen_rows, "picked": picked, "attended": out}


@pytest.mark.parametrize("slots", [3, 6, 8], ids=["r1", "r2-short", "r4"])
def test_dsa_decode_against_the_form_it_replaced(model, slots):
    """A tick of every layer's sublayer on the tiny model, rows below and
    past ``topk`` beside frozen ones, over pages that a long run would
    have written (random, the same for both): the same picked SETS (the
    tick names them in ascending position, ``lax.top_k`` in descending
    score), the same rows attended, the same pool, the output to float32
    summation order."""
    cfg, params, _ = model
    max_pages = MAX_SEQ // PAGE
    rng = np.random.default_rng(slots)
    positions = np.asarray([37, 5, 0, 90, TOPK - 1, TOPK, 0, 64][:slots])
    active = np.asarray([1, 1, 0, 1, 1, 1, 0, 1][:slots], bool)
    bt = (rng.permutation(slots * max_pages) + 1).reshape(
        slots, max_pages) * active[:, None]
    positions, bt = jnp.asarray(positions * active), jnp.asarray(bt, jnp.int32)
    live = jnp.argsort(~jnp.asarray(active), stable=True), int(active.sum())
    pool = jax.tree.map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
        K.init_page_pool(cfg, slots * max_pages + 1, PAGE)["0"])
    u = jnp.asarray(rng.standard_normal((slots, cfg.dim)), jnp.float32)
    rope = K.rope_rows(cfg, positions)
    for i in range(cfg.layers):
        args = (params["blocks"][str(i)], cfg, u, pool, positions, bt, live,
                rope, 16)
        out, pool_new, look = jax.jit(K.dsa_decode, static_argnums=(1, 8))(*args)
        ref_out, ref_pool, ref_look = jax.jit(
            dsa_decode_pr49, static_argnums=(1, 8))(*args)
        np.testing.assert_array_equal(
            look["picked"], np.sort(ref_look["picked"], -1))
        np.testing.assert_array_equal(look["rows"], ref_look["rows"])
        assert np.asarray(look["rows"])[np.asarray(active)].tolist() == [
            min(int(t) + 1, TOPK) for t in np.asarray(positions)[active]]
        jax.tree.map(np.testing.assert_array_equal, pool_new, ref_pool)
        np.testing.assert_allclose(look["attended"], ref_look["attended"],
                                   atol=2e-5)
        assert not np.asarray(out)[~active].any()
