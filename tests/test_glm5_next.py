"""GLM-5.3-Flash on the paged serving path (models/hf/glm5_next.py:
delta-rule layers as per-slot state, the sparse-latent layer's latent
rows and pooled indexer rows in pages, the residual streams, ``kimi_k2``'s
expert layer under the clamp) against its plain float32 reference
(models/hf/glm5_next_reference.py: whole sequence, the delta rule a token
at a time, dense picked masks, no cache), at tiny widths on the CPU, from
seeded weights. Logits are compared, not sampled tokens.

Tiny: hidden 64, 4 heads, ``index_topk`` 16 over ``index_kpool`` 4 (a row
picks 4 blocks), page 8, chunk 32, 8 experts of which 2 are held, 5
layers ``L | L L L D`` with layer 0 dense, 4 residual streams. On the CPU
the serving path computes in float32 too, so ``TOL`` is float32 summation
order (the blocked delta rule against the recurrence, the blocks' running
softmax against a whole one, the int8 scales applied after the product
or before): 3e-4 absolute on logits of magnitude 4. Measured: 8e-6 to
2.4e-5 on most prompts and 1.5e-4 on one row of one (an ill-conditioned
row: the float32 reference itself lies 1.5e-5 from a float64 one there,
three times its usual), far under what a flipped † switch or a missing
selection moves (1.3 to 5.2: each asserted below). ``hc_eps`` is 1e-2 here (1e-6
published) so that †1 — where the eps enters the Sinkhorn sums — moves
the logits past float32 noise; test (f) holds the published value.
"""

from __future__ import annotations

import copy
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dora_tpu.models import paged_model as PM
from dora_tpu.models.hf import glm5_next as G
from dora_tpu.models.hf import glm5_next_reference as R
from tests.glm5_next_tiny import (  # noqa: F401  (ckpt, model: fixtures)
    BLOCK, CHUNK, KINDS, KPOOL, MAX_SEQ, PAGE, SLOTS, TINY, TOL, TOPK, Served,
    ckpt, held_of, make_engine, model, prompt_ids, reference_logits,
)




# -- (a) state, pages and pooled rows against the whole forward pass -----------


@pytest.mark.parametrize("n,chunk", [
    (5, CHUNK),    # below index_topk: nothing selects
    (16, CHUNK),   # the prompt ends at index_topk: the first decode row selects
    (37, CHUNK),   # a ragged second chunk that ends inside a pooled block
    (64, CHUNK),   # the chunks' edges and the prompt's end on a page and a block
    (75, CHUNK),   # several times index_topk, a ragged third chunk
    (45, 8),       # chunks of one page: every chunk edge is a page's
    (58, 16),      # the prompt ends two rows into a block
])
def test_chunked_prefill_then_decode_matches_the_reference(model, n, chunk):
    cfg, params, _ = model
    prompt, emitted = prompt_ids(n, seed=n), prompt_ids(11, seed=100 + n)
    got = Served(cfg, params, chunk).serve(1, prompt, emitted)
    want = reference_logits(model, prompt + emitted)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOL




# -- (b) each † switch, flipped, fails the same limit ---------------------------


@pytest.mark.parametrize("switch", R.SWITCHES)
def test_a_flipped_switch_fails_the_tolerance(model, switch):
    cfg, params, _ = model
    prompt, emitted = prompt_ids(75, seed=75), prompt_ids(11, seed=175)
    got = Served(cfg, params).serve(1, prompt, emitted)
    assert np.abs(got - reference_logits(model, prompt + emitted)).max() < TOL
    flipped = reference_logits(model, prompt + emitted, **{switch: True})
    assert np.abs(got - flipped).max() > 100 * TOL


def test_unknown_switches_are_refused(model):
    with pytest.raises(TypeError, match="no_such"):
        reference_logits(model, [1, 2, 3], no_such=True)


def test_the_reference_takes_picked_sets_from_outside(model):
    """Given the program's picked blocks the reference computes what it
    computes from its own (they are equal here), and given others it
    does not."""
    cfg, params, rp = model
    prompt = prompt_ids(60, seed=81)
    served = Served(cfg, params)
    served.prefill(0, prompt)
    picks = {4: jnp.asarray(served.picked[0])}
    own = reference_logits(model, prompt)
    given = np.asarray(R.forward(rp, cfg, jnp.asarray(prompt), held=held_of(cfg),
                                 picks=picks))
    assert np.abs(own - given).max() < TOL
    other = {4: jnp.zeros_like(picks[4])}
    moved = np.asarray(R.forward(rp, cfg, jnp.asarray(prompt), held=held_of(cfg),
                                 picks=other))
    assert np.abs(own - moved).max() > 100 * TOL


# -- (c) the shares add up ------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer(ckpt):
    """The routed parts of all ``ep_size`` shares plus the shared expert
    once equal the uncut reference's expert layer, in the program
    (``kimi_k2.mlp`` under this config and its clamp) and in the
    reference."""
    from dora_tpu.models import moe as K

    x = jnp.asarray(np.random.default_rng(3).standard_normal((24, 64)),
                    jnp.float32)
    live = jnp.ones((24,), bool)
    parts, shared = [], None
    for rank in range(4):
        cfg, params = G.load(ckpt, max_seq=MAX_SEQ, ep_rank=rank)
        blk = params["blocks"]["1"]
        both, _ = K.mlp(blk, cfg, x, live, live)
        shared = K.swiglu(blk["shared"], x)
        assert float(blk["shared"]["limit"]) == 1.0
        assert blk["experts"]["limit"].tolist() == [1.0] * cfg.experts_held
        parts.append(np.asarray(both - shared))
        rp = R.reference_params(params, cfg)["blocks"]["1"]
        with jax.default_matmul_precision("highest"):
            assert np.abs(np.asarray(R.moe(rp, cfg, x, held_of(cfg)))
                          - np.asarray(both)).max() < TOL
    whole_dir = ckpt.with_name("ckpt-ep1")
    whole_dir.mkdir(exist_ok=True)
    (whole_dir / "model.safetensors").symlink_to(ckpt / "model.safetensors")
    (whole_dir / "config.json").write_text(json.dumps({**TINY, "ep_size": 1}))
    cfg, params = G.load(whole_dir, max_seq=MAX_SEQ)
    assert (cfg.expert_first, cfg.experts_held) == (0, 8)
    rp = R.reference_params(params, cfg)["blocks"]["1"]
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(R.moe(rp, cfg, x))
        unclamped = np.asarray(R.moe(rp, cfg.__class__(**{
            **cfg.__dict__, "swiglu_limit": None}), x))
    assert np.abs(sum(parts) + np.asarray(shared) - whole).max() < TOL
    assert sum(np.abs(p).max() > 0.01 for p in parts) >= 3
    assert np.abs(whole - unclamped).max() > 0.01  # the clamp binds here


# -- (d) what must leave state, tail, accumulator and pool untouched ------------


def test_padding_rows_and_frozen_rows_leave_every_cache_untouched(model):
    cfg, params, _ = model
    served = Served(cfg, params)
    served.prefill(0, prompt_ids(20, seed=41))
    before = jax.tree.map(np.asarray, served.state)
    pool_before = jax.tree.map(np.asarray, served.pools["4"])
    # a ragged chunk into slot 1: 6 valid rows of 32, under two paddings
    prompt = prompt_ids(6, seed=42)
    served.prefill(1, prompt, pad_id=0)
    twin = Served(cfg, params)
    twin.prefill(0, prompt_ids(20, seed=41))
    twin.prefill(1, prompt, pad_id=77)
    for key, leaves in served.state.items():
        for name, leaf in leaves.items():
            leaf, other = np.asarray(leaf), np.asarray(twin.state[key][name])
            was = before[key][name]
            # the other slots as they were, bit for bit
            assert (leaf[0] == was[0]).all() and (leaf[2] == was[2]).all()
            # slot 1: written, and whatever the padding rows held
            assert (leaf[1] != was[1]).any()
            assert (leaf[1] == other[1]).all(), (key, name)
    # the accumulator holds the two valid rows of the unfinished block
    _, kept = R.forward(model[2], cfg, jnp.asarray(prompt), held=held_of(cfg),
                        rows=True)
    # slot 1's pages took the chunk; slot 0's and slot 2's did not move; the
    # prompt's rows and its one complete pooled block do not see the padding
    mine = served.bts[1]
    for name in ("kv", "ik"):
        pool = np.asarray(served.pools["4"][name])
        others = [p for p in range(1, pool.shape[0]) if p not in set(mine.tolist())]
        assert (pool[others] == pool_before[name][others]).all()
        other = np.asarray(twin.pools["4"][name])
        valid = 6 if name == "kv" else 1
        rows = pool[mine].reshape(-1, pool.shape[-1])
        assert (rows[:valid] == other[mine].reshape(rows.shape)[:valid]).all()
    assert np.abs(np.asarray(served.pools["4"]["kv"])[mine[0]][:6]
                  - np.asarray(kept[4]["c"])).max() < TOL
    assert np.abs(np.asarray(served.pools["4"]["ik"])[mine[0]][0]
                  - np.asarray(kept[4]["pooled"])[0]).max() < TOL
    # a window in which slots 0 and 2 are frozen: bit-identical leaves and pages
    state = jax.tree.map(np.asarray, served.state)
    pools = jax.tree.map(np.asarray, served.pools["4"])
    for tok in (9, 10, 11):
        served.tick({1: tok})
    for key, leaves in served.state.items():
        for name, leaf in leaves.items():
            leaf = np.asarray(leaf)
            assert (leaf[0] == state[key][name][0]).all()
            assert (leaf[2] == state[key][name][2]).all()
            assert (leaf[1] != state[key][name][1]).any()
    zero = [int(p) for p in served.bts[0]] + [int(p) for p in served.bts[2]]
    for name in ("kv", "ik"):
        assert (np.asarray(served.pools["4"][name])[zero] == pools[name][zero]).all()


def _kernels_and_state_selects(jaxpr, shape):
    """(names of the ``pallas_call`` equations, sub-programs included;
    ``select_n`` equations with an operand of ``shape``)."""
    kernels, selects = [], []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            kernels.append(eqn.params["name"])
            continue
        if eqn.primitive.name == "select_n" and any(
                getattr(v.aval, "shape", None) == shape for v in eqn.invars):
            selects.append(str(eqn))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            k, s = _kernels_and_state_selects(sub, shape)
            kernels += k
            selects += s
    return kernels, selects


@pytest.mark.parametrize("picks", [False, True], ids=["served", "audited"])
def test_a_tick_steps_the_state_in_one_kernel_a_layer_and_selects_none(model,
                                                                       picks):
    """The decode tick (the served program and the audit's) holds one
    ``kda_state_step`` a delta-rule layer and no ``jnp.where`` over a
    whole state array: a frozen row's state is left alone by the kernel's
    schedule, not selected back. The chunk program does not call the
    step."""
    cfg, params, _ = model
    served = Served(cfg, params, dirty=False)
    state_shape = served.state["0"]["s"].shape
    assert state_shape == (SLOTS, cfg.kda_heads, cfg.kda_dim, cfg.kda_dim)
    i32 = jnp.int32
    tick = jax.make_jaxpr(
        lambda p, *a: G.paged_batch_logits(p, cfg, *a, picks=picks))(
        params, jnp.zeros((SLOTS,), i32), served.pools, served.state,
        served.stats, jnp.zeros((SLOTS,), i32), jnp.asarray(served.bts),
        jnp.ones((SLOTS,), bool))
    kernels, selects = _kernels_and_state_selects(tick.jaxpr, state_shape)
    assert kernels.count("kda_state_step") == sum(cfg.linear) == 4
    assert selects == []
    chunk = jax.make_jaxpr(
        lambda p, *a: G.paged_chunk_logits(p, cfg, *a, block=BLOCK, picks=picks))(
        params, jnp.zeros((CHUNK,), i32), served.pools, served.state,
        served.stats, jnp.asarray(0, i32), jnp.asarray(served.bts[0]),
        jnp.asarray(CHUNK, i32), jnp.asarray(0, i32))
    assert "kda_state_step" not in _kernels_and_state_selects(
        chunk.jaxpr, state_shape)[0]


# -- (e) a tick selects, addresses and gathers for the live rows alone ----------

WIDE = 16  # slots: four groups of DECODE_ROWS
#: the prompt in each of the sixteen slots: below ``index_topk``, at it, past
#: it; ends inside a block, on a block's last row and on a page's
WIDE_PROMPTS = [5, 16, 19, 23, 31, 37, 40, 47, 52, 58, 63, 64, 70, 75, 81, 90]
WIDE_TICKS = 3


@pytest.fixture(scope="module")
def wide(model):
    """Sixteen slots, every one prefilled (a frozen slot has a state, a
    tail, an accumulator and pages to lose), and the reference's logits,
    picked blocks and sparse-latent output rows of each stream followed by
    its decode tokens."""
    cfg, params, rp = model
    # four pages a block of the scoring loop: up to four turns a group
    served = Served(cfg, params, slots=WIDE, index_block=4 * PAGE)
    streams, want = {}, {}
    real, outs = R.dsa, []

    def spy(*args, **kw):  # the reference keeps no sublayer output: listen
        out, cached = real(*args, **kw)
        outs.append(np.asarray(out))
        return out, cached

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(R, "dsa", spy)
        for b, n in enumerate(WIDE_PROMPTS):
            streams[b] = (prompt_ids(n, seed=300 + b),
                          prompt_ids(WIDE_TICKS, seed=400 + b))
            served.prefill(b, streams[b][0])
            tokens = jnp.asarray(streams[b][0] + streams[b][1])
            logits, kept = R.forward(rp, cfg, tokens, held=held_of(cfg), rows=True)
            want[b] = {"logits": np.asarray(logits),
                       "picked": np.asarray(kept[4]["picked"]),
                       "attended": outs.pop()}
    return served, streams, want


def _rows_attended(t: int) -> int:
    return TOPK + t % KPOOL + 1 if t >= TOPK else t + 1


@pytest.mark.parametrize("live", [
    (1, 5, 6, 11, 15),     # scattered: a whole group and a short one
    (),                    # none live: no group runs
    tuple(range(WIDE)),    # all live: four whole groups
    (0, 2, 3, 4, 9, 12),   # rows below index_topk beside selecting ones
    (7,),                  # one row: one group, three spare entries
    (3, 8, 10, 14),        # exactly one group
], ids=["scattered", "none", "all", "short-last-group", "one", "one-group"])
def test_a_tick_selects_and_gathers_for_the_live_rows_alone(wide, live):
    """Whatever slots are live, a live row picks the reference's blocks,
    attends them and its tail and nothing else, and puts out the
    reference's row; a frozen row puts out zeros and every cache,
    accumulator and state of its slot stays bit for bit; the counters
    read the groups that ran and the rows their gathers named."""
    prefilled, streams, want = wide
    served = copy.copy(prefilled)  # the arrays are immutable; ticks rebind them
    served.positions, served.ticked = prefilled.positions.copy(), {}
    cfg = served.cfg
    frozen = [b for b in range(WIDE) if b not in live]
    state = jax.tree.map(np.asarray, served.state)
    pools = jax.tree.map(np.asarray, served.pools["4"])
    stats = {k: int(v) for k, v in served.stats["kda"].items()}
    picked_rows = 0
    for k in range(WIDE_TICKS):
        t = {b: len(streams[b][0]) + k for b in live}
        rows = served.tick({b: streams[b][1][k] for b in live})
        look = served.look
        assert look["picked"].shape == (WIDE, TOPK // KPOOL)
        assert look["attended"].shape == (WIDE, cfg.dim)
        for b in live:
            if t[b] >= TOPK:
                assert set(look["picked"][b]) == set(want[b]["picked"][t[b]]), (b, k)
            assert np.abs(look["attended"][b] - want[b]["attended"][t[b]]).max() < TOL
            assert np.abs(rows[b] - want[b]["logits"][t[b]]).max() < TOL, (b, k)
            picked_rows += _rows_attended(t[b])
        assert (look["attended"][frozen] == 0).all()
    for key, leaves in served.state.items():
        for name, leaf in leaves.items():
            leaf = np.asarray(leaf)
            assert (leaf[frozen] == state[key][name][frozen]).all(), (key, name)
            for b in live:
                assert (leaf[b] != state[key][name][b]).any(), (key, name, b)
    theirs = [int(p) for b in frozen for p in served.bts[b]]
    for name in ("kv", "ik"):
        assert (np.asarray(served.pools["4"][name])[theirs]
                == pools[name][theirs]).all()
    gained = {k: int(v) - stats[k] for k, v in served.stats["kda"].items()}
    groups = -(-len(live) // G.DECODE_ROWS)
    assert G.decode_group(WIDE) == G.DECODE_ROWS == 4
    assert gained["dsa_rows_fetched"] == WIDE_TICKS * groups * 4 * (TOPK + KPOOL)
    assert G.decode_groups(cfg, WIDE, gained["dsa_rows_fetched"]) == (
        WIDE_TICKS * groups)
    assert gained["dsa_rows_picked"] == picked_rows  # the tail's rows among them
    assert gained["kda_decode_ticks"] == (WIDE_TICKS if live else 0)
    assert gained["kda_row_ticks"] == 4 * WIDE_TICKS * len(live)


def _gathers(jaxpr):
    """Every ``gather`` equation, sub-programs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _gathers(sub)
    return found


@pytest.mark.parametrize("picks", [False, True], ids=["served", "audited"])
def test_a_tick_gathers_no_address_and_a_groups_latent_rows(model, picks):
    """The decode tick (the served program and the audit's) takes no
    picked row's address by a gather of scalars through the block table
    (XLA:TPU gathers scalars one at a time: 319 us a tick at the served
    shapes): the table gives up whole rows, a group's, and the one page
    a slot's new row goes to. The latent rows come out of ONE gather a
    group, ``DECODE_ROWS x (index_topk + index_kpool)`` rows of
    ``kv_rank``, never a slot's worth for every slot."""
    cfg, params, _ = model
    served = Served(cfg, params, dirty=False, slots=WIDE)
    i32 = jnp.int32
    tick = jax.make_jaxpr(
        lambda p, *a: G.paged_batch_logits(p, cfg, *a, picks=picks))(
        params, jnp.zeros((WIDE,), i32), served.pools, served.state,
        served.stats, jnp.zeros((WIDE,), i32), jnp.asarray(served.bts),
        jnp.ones((WIDE,), bool))
    gathers = _gathers(tick.jaxpr)
    max_pages = served.bts.shape[1]
    through_table = [
        e for e in gathers
        if e.invars[0].aval.dtype == jnp.int32
        and e.invars[0].aval.shape[1:] == (max_pages,)]  # [rows, max_pages]
    scalars = [e for e in through_table
               if set(e.params["slice_sizes"]) == {1}]
    # the page each slot's new row is written to, and nothing else
    assert [e.outvars[0].aval.shape for e in scalars] == [(WIDE,)]
    assert {e.outvars[0].aval.shape for e in through_table} == {
        (WIDE,), (G.DECODE_ROWS, max_pages)}
    flat_rows = served.pools["4"]["kv"].shape[0] * served.pools["4"]["kv"].shape[1]
    latent = [e for e in gathers
              if e.invars[0].aval.shape == (flat_rows, cfg.kv_rank)]
    assert [(e.outvars[0].aval.shape, tuple(e.params["slice_sizes"]))
            for e in latent] == [
        ((G.DECODE_ROWS, TOPK + KPOOL, cfg.kv_rank), (1, cfg.kv_rank))]


# -- (f) the delta rule's blocked form, Sinkhorn, the picked sets ------------------


@pytest.mark.parametrize("lower,name", [(-5.0, "near exp(-5)"), (-1e-3, "near 1"),
                                        (-0.7, "between")])
def test_the_blocked_delta_rule_equals_the_recurrence(lower, name):
    rng = np.random.default_rng(7)
    c, h, d = 64, 3, 8
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    q, k, v = f(c, h, d), f(c, h, d), f(c, h, d)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = lower * jax.nn.sigmoid(f(c, h, d) + (4.0 if lower == -5.0 else 0.0))
    beta = jax.nn.sigmoid(f(c, h))
    s0 = f(h, d, d)
    # rows 50.. are padding: no decay, no write
    live = jnp.arange(c) < 50
    g, beta = jnp.where(live[:, None, None], g, 0.0), jnp.where(live[:, None], beta, 0.0)
    if lower == -5.0:
        assert float(g[:50].mean()) < -4.5  # 16 rows of it: exp(-72) and beyond

    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        s = s * jnp.exp(g_t)[..., None]
        pred = (s * k_t[..., None]).sum(-2)
        s = s + (b_t[:, None] * k_t)[..., None] * (v_t - pred)[:, None, :]
        return s, (s * q_t[..., None]).sum(-2)

    s_want, o_want = jax.lax.scan(step, s0, (q, k, v, g, beta))
    for block in (16, 8, 64):
        o, s = G.delta_rule_blocks(q, k, v, g, beta, s0, block)
        assert np.isfinite(np.asarray(o)).all()
        assert np.abs(np.asarray(o - o_want))[:50].max() < 2e-5, (name, block)
        assert np.abs(np.asarray(s - s_want)).max() < 2e-5, (name, block)


def test_sinkhorn_is_doubly_stochastic_and_keeps_the_streams_sum(model):
    """At the published ``hc_eps`` 1e-6: rows and columns of ``Hres`` sum
    to 1 within 1e-5, so ``Hres X`` keeps the sum of the streams."""
    cfg, params, _ = model
    cfg = cfg.__class__(**{**cfg.__dict__, "hc_eps": 1e-6})
    rng = np.random.default_rng(9)
    streams = jnp.asarray(rng.standard_normal((12, 4, 64)), jnp.float32)
    pre, post, res = G.mhc_maps(params["blocks"]["2"]["hc_attn"], cfg, streams)
    res = np.asarray(res)
    assert res.min() > 0
    assert np.abs(res.sum(-1) - 1).max() < 1e-5
    assert np.abs(res.sum(-2) - 1).max() < 1e-5
    assert 0 < float(pre.min()) and float(pre.max()) < 1 and float(post.max()) < 2
    mixed = np.einsum("tij,tjd->tid", res, np.asarray(streams))
    assert np.abs(mixed.sum(1) - np.asarray(streams).sum(1)).max() < 1e-4
    assert np.abs(res - 0.25).max() > 0.05  # not the uniform matrix: unsaturated


def test_the_picked_sets_equal_the_references_tail_included(model):
    """Chunk rows and decode ticks at ``t % 4`` = 0..3 pick the blocks the
    reference picks, and attend their positions plus the unfinished
    block's (the tail) and nothing else."""
    cfg, params, rp = model
    prompt, emitted = prompt_ids(53, seed=91), prompt_ids(8, seed=92)
    served = Served(cfg, params)
    got = served.serve(0, prompt, emitted)
    _, kept = R.forward(rp, cfg, jnp.asarray(prompt + emitted),
                        held=held_of(cfg), rows=True)
    own = np.asarray(kept[4]["picked"])
    for t in range(TOPK, len(prompt)):
        assert set(own[t]) == set(served.picked[0][t]), t
        assert (own[t] < t // KPOOL).all()
    mask = np.asarray(R.picked_rows(cfg, len(prompt) + 8, jnp.asarray(own),
                                    R.AS_SERVED))
    for t in range(len(prompt) + 8):
        want = set(range(t + 1)) if t < TOPK else (
            {KPOOL * b + j for b in own[t] for j in range(KPOOL)}
            | set(range(t // KPOOL * KPOOL, t + 1)))
        assert set(np.flatnonzero(mask[t])) == want, t
    # the decode ticks' positions cover t % 4 = 1, 2, 3, 0, ...
    assert {(len(prompt) + k) % KPOOL for k in range(8)} == {0, 1, 2, 3}
    for k, mine in enumerate(served.ticked[0]):
        assert set(own[len(prompt) + k]) == set(mine), k
    want = reference_logits(model, prompt + emitted)
    assert np.abs(got - want).max() < TOL


# -- (g) the refusals by name ----------------------------------------------------


@pytest.mark.parametrize("knob", sorted(G.NOT_OFFERED))
def test_knobs_of_the_qwen_path_are_refused_by_name(model, monkeypatch, knob):
    cfg, params, _ = model
    monkeypatch.setenv(knob, "1")
    with pytest.raises(NotImplementedError, match=knob):
        make_engine(cfg, params)


def test_no_prefix_cache_whatever_is_asked(model, caplog):
    cfg, params, _ = model
    with caplog.at_level("WARNING"):
        engine = make_engine(cfg, params, prefix_cache=True)
    assert engine.prefix_cache is None
    assert "prefix cache is off" in caplog.text


@pytest.mark.parametrize("change,error,match", [
    ({"qk_rope_head_dim": 64}, NotImplementedError, "qk_rope_head_dim 64 > 0 with mla_use_nope"),
    ({"qk_rope_head_dim": 64, "mla_use_nope": False}, NotImplementedError,
     "qk_rope_head_dim 64"),
    ({"indexer_types": ["full"] * 4 + ["shared"]}, NotImplementedError,
     "indexer_types .'shared'."),
    ({"n_group": 2}, NotImplementedError, "n_group"),
    ({"mhc": False}, NotImplementedError, "hc_mult 4 with mhc false"),
    ({"scoring_func": "softmax"}, NotImplementedError, "scoring_func 'softmax'"),
    ({"layer_types": KINDS[:4]}, ValueError, "layer_types"),
    ({"layer_types": ["full_attention"] * 5}, NotImplementedError,
     "full_attention"),
    ({"mlp_layer_types": ["dense"] * 4}, ValueError, "mlp_layer_types"),
    ({"linear_attn_config": {**TINY["linear_attn_config"], "kda_layers": [0, 1]}},
     ValueError, "kda_layers"),
    ({"index_kpool_compress": False}, NotImplementedError, "index_kpool_compress"),
    ({"index_topk": 18}, ValueError, "index_topk 18"),
    ({"attention_bias": True}, NotImplementedError, "attention_bias"),
    ({"model_type": "glm4_moe"}, ValueError, "glm4_moe"),
    ({"ep_size": 3}, ValueError, "ep_size"),
])
def test_unsupported_variants_are_refused_by_name(change, error, match):
    with pytest.raises(error, match=match):
        G.Glm5NextConfig.from_hf({**TINY, **change})


def test_a_pooled_block_that_does_not_divide_the_page_is_refused(ckpt):
    cfg = G.Glm5NextConfig.from_hf({**TINY, "index_kpool": 3, "index_topk": 18},
                                   max_seq=MAX_SEQ)
    with pytest.raises(NotImplementedError, match="index_kpool 3 does not divide"):
        G.make_paged_engine({}, cfg, page_size=8, chunk=32)


def test_first_k_dense_replace_stands_in_for_mlp_layer_types():
    config = {k: v for k, v in TINY.items() if k != "mlp_layer_types"}
    cfg = G.Glm5NextConfig.from_hf(config)
    assert cfg.sparse == (False, True, True, True, True)
    assert cfg.linear == (True, True, True, True, False)
    assert (cfg.kda_layers, cfg.dsa_layers) == ((0, 1, 2, 3), (4,))
    assert cfg.picked_blocks == TOPK // KPOOL and cfg.swiglu_limit == 1.0


def test_expert_share_rank_from_the_launcher(monkeypatch):
    monkeypatch.setenv("DORA_EP_RANK", "3")
    cfg = G.Glm5NextConfig.from_hf(TINY)
    assert (cfg.expert_first, cfg.experts_held, cfg.n_experts) == (6, 2, 8)


def test_llm_server_knows_the_family(ckpt):
    from dora_tpu.models.hf.loader import read_config
    from dora_tpu.nodehub import llm_server

    module = llm_server.model_module(read_config(ckpt)["model_type"])
    assert module is G
    with pytest.raises(RuntimeError, match="glm5_next_text") as err:
        llm_server.model_module("glm4_moe")
    assert "glm4_moe" in str(err.value)


def test_the_published_cut_in_bytes():
    """The numbers ``PERF.md`` and the configuration's file state, from
    the config class: 1,088 B a cached position at bf16, 4.19 MB of
    float32 state a slot a delta-rule layer, 285 MB of pages."""
    cfg = G.Glm5NextConfig.from_hf({
        **TINY, "hidden_size": 4096, "num_attention_heads": 64,
        "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 256,
        "v_head_dim": 256, "index_n_heads": 32, "index_head_dim": 128,
        "index_topk": 2048,
        "linear_attn_config": {**TINY["linear_attn_config"], "num_heads": 64,
                               "head_dim": 128}}, max_seq=16384)
    item = jnp.dtype(G.L.compute_dtype()).itemsize  # 4 on the CPU, 2 on the chip
    assert cfg.kv_bytes_per_token == (512 + 32) * item
    assert cfg.picked_blocks == 512
    per_slot = cfg.state_bytes_per_slot
    assert per_slot == 4 * (64 * 128 * 128 * 4 + 3 * 3 * 8192 * item) + 128 * 4
    assert 64 * 128 * 128 * 4 == 4_194_304
    limit, used = 16_909_336_064, 5_000_000_000
    page = 16 * cfg.kv_bytes_per_token
    assert PM.pages_that_fit(page, limit, used, 16, 16384, 16) == 16 * 16384 // 16 + 1
    assert PM.pages_that_fit(page, 8 << 30, 6 << 30, 16, 16384, 16) == 2 * 16384 // 16


# (h) no weight is copied or closed over in the two programs:
# tests/test_backend.py walks them ("glm5_next" in its table of engines)
