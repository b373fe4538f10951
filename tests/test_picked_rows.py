"""The decode tick's fetch of the picked rows and their attention
(``ops/picked_rows``: ``pool_rows`` + the flat row gather + ``attend_rows``)
against a plain ``jax.numpy`` gather-and-softmax, and ``dsa_decode``
through them against the XLA form they replaced (PR 49's, kept below
line for line as the reference), on the CPU in float32.

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
# dsa_decode against the form it replaced
# ---------------------------------------------------------------------------


def dsa_decode_pr49(blk, cfg, u, pool, positions, block_tables, live, rope,
                    block):
    """``keye_vl2.dsa_decode`` as PR 49 left it: the addresses by
    ``take_along_axis``, the rows split into keys and values of every head
    (``_split_rows``), two einsums over all heads."""
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
    have written (random, the same for both): the same picks, the same
    rows attended, the same pool, the output to float32 summation order."""
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
        np.testing.assert_array_equal(look["picked"], ref_look["picked"])
        np.testing.assert_array_equal(look["rows"], ref_look["rows"])
        assert np.asarray(look["rows"])[np.asarray(active)].tolist() == [
            min(int(t) + 1, TOPK) for t in np.asarray(positions)[active]]
        jax.tree.map(np.testing.assert_array_equal, pool_new, ref_pool)
        np.testing.assert_allclose(look["attended"], ref_look["attended"],
                                   atol=2e-5)
        assert not np.asarray(out)[~active].any()
