"""Olmo-Hybrid on the paged serving path (models/hf/olmo_hybrid.py): three
gated-delta-rule layers of per-slot state for every full-attention layer
of pages. At tiny widths (tests/olmo_hybrid_tiny.py), on seeded weights,
LOGITS and not tokens:

(a) chunked prefill then decode, through pages and slot state, against the
    plain reference's one forward pass over the whole sequence (float32,
    one token at a time);
(b) each † switch and each control of the reference, flipped, fails the
    same limit: a bf16 state, a ``beta`` not doubled, a gate laid over its
    axis the wrong way, a dropped convolution tap, a zeroed state;
(c) padding rows and frozen rows leave every cache untouched;
(d) the blocked delta rule with ``beta`` in (1, 2) and correlated keys:
    the solved form holds where the expansion in powers does not;
(e) the moved delta-rule code is GLM's, jaxpr for jaxpr;
(f) the refusals by name, the family in ``llm_server``, the bytes of the
    published cut.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dora_tpu.models import delta_rule as DR
from dora_tpu.models import paged_model as PM
from dora_tpu.models.hf import olmo_hybrid as O
from dora_tpu.models.hf import olmo_hybrid_reference as R
from tests.olmo_hybrid_tiny import (  # noqa: F401  (fixtures)
    CHUNK, KINDS, MAX_SEQ, PAGE, SLOTS, TINY, Served, ckpt, make_engine,
    model, prompt_ids, reference_logits, run)

#: the CPU computes in float32, so the programs and the reference differ in
#: the order of their sums alone (blocks of 64 against one row at a time, a
#: running softmax against a whole one): logits of magnitude 4 agree to 1e-4
TOL = 3e-4


# -- (a) state and pages against the whole forward pass --------------------------


@pytest.mark.parametrize("n,chunk", [
    (5, CHUNK),    # shorter than the convolution's reach plus a page
    (32, CHUNK),   # the prompt ends on the chunk's edge: decode starts from it
    (37, CHUNK),   # a ragged second chunk
    (96, CHUNK),   # three whole chunks: two blocks of 64 rows would not divide
    (75, CHUNK),   # a ragged third chunk
    (45, 8),       # chunks of one page: a block of the delta rule is the chunk
    (130, 64),     # a chunk is one block of 64
])
def test_chunked_prefill_then_decode_matches_the_reference(model, n, chunk):
    cfg, params, _ = model
    prompt, emitted = prompt_ids(n, seed=n), prompt_ids(11, seed=100 + n)
    got = Served(cfg, params, chunk).serve(1, prompt, emitted)
    want = reference_logits(model, prompt + emitted)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOL


def test_two_streams_decode_side_by_side_each_from_its_own_state(model):
    cfg, params, _ = model
    a, b = prompt_ids(41, seed=3), prompt_ids(70, seed=4)
    served = Served(cfg, params)
    served.prefill(0, a)
    served.prefill(2, b)
    toks_a, toks_b = prompt_ids(6, seed=5), prompt_ids(6, seed=6)
    rows = {0: [], 2: []}
    for ta, tb in zip(toks_a, toks_b):
        out = served.tick({0: ta, 2: tb})
        rows[0].append(out[0])
        rows[2].append(out[2])
    for slot, prompt, toks in ((0, a, toks_a), (2, b, toks_b)):
        want = reference_logits(model, prompt + toks)[len(prompt):]
        assert np.abs(np.stack(rows[slot]) - want).max() < TOL


# -- (b) each switch, flipped, fails the same limit ------------------------------


FLIPPED = {**dict.fromkeys(R.SWITCHES, True), "rope_theta": 1e4,
           "zero_state_at": 64}


@pytest.mark.parametrize("switch", R.SWITCHES)
def test_a_flipped_switch_fails_the_tolerance(model, switch):
    cfg, params, _ = model
    prompt, emitted = prompt_ids(75, seed=75), prompt_ids(11, seed=175)
    got = Served(cfg, params).serve(1, prompt, emitted)
    assert np.abs(got - reference_logits(model, prompt + emitted)).max() < TOL
    flipped = reference_logits(model, prompt + emitted,
                               **{switch: FLIPPED[switch]})
    assert np.abs(got - flipped).max() > 100 * TOL


def test_unknown_switches_are_refused(model):
    with pytest.raises(TypeError, match="no_such"):
        reference_logits(model, [1, 2, 3], no_such=True)


def test_the_state_a_chunk_leaves_is_the_references_float32(model):
    """Every linear layer's state and tail after a ragged prompt, against
    the reference's last state and its last three pre-convolution rows;
    the full layer's pages against its keys and values."""
    cfg, params, rp = model
    prompt = prompt_ids(75, seed=9)
    served = Served(cfg, params)
    served.prefill(1, prompt)
    _, kept = R.forward(rp, cfg, jnp.asarray(prompt), rows=True)
    for i in cfg.gdn_layers:
        st = served.state[str(i)]
        assert st["s"].dtype == jnp.float32
        assert np.abs(np.asarray(st["s"][1]) - np.asarray(kept[i]["s"])).max() < 1e-4
        assert np.abs(np.asarray(st["conv"][1], np.float32)
                      - np.asarray(kept[i]["c"][-3:])).max() < 1e-4
    for i in cfg.full_layers:
        pages = served.bts[1][: -(-len(prompt) // PAGE)]
        rows = np.asarray(served.pools[str(i)]["kv"])[pages].reshape(
            -1, 2 * cfg.kv_width)[: len(prompt)]
        want = np.concatenate([
            np.asarray(kept[i]["k"]).reshape(len(prompt), -1),
            np.asarray(kept[i]["v"]).reshape(len(prompt), -1)], -1)
        assert np.abs(rows - want).max() < 1e-4


# -- (c) what must not move -------------------------------------------------------


def test_padding_rows_and_frozen_rows_leave_every_cache_untouched(model):
    cfg, params, _ = model
    served = Served(cfg, params)
    served.prefill(0, prompt_ids(40, seed=11))
    before = jax.tree.map(np.asarray, served.state)
    # another stream's chunks and ticks: slot 0's rows stay bit for bit
    served.prefill(1, prompt_ids(37, seed=12), pad_id=77)
    served.tick({1: 5})
    after = jax.tree.map(np.asarray, served.state)
    for key in before:
        for leaf in before[key]:
            assert (before[key][leaf][0] == after[key][leaf][0]).all(), (key, leaf)
    # the pad token's id does not reach the logits of the prompt's rows
    a = Served(cfg, params).prefill(1, prompt_ids(37, seed=12), pad_id=77)
    b = Served(cfg, params).prefill(1, prompt_ids(37, seed=12), pad_id=3)
    assert (a == b).all()


def test_the_counters_count_rows_chunks_and_swept_groups(model):
    cfg, params, _ = model
    served = Served(cfg, params)
    served.prefill(0, prompt_ids(40, seed=13))
    served.prefill(1, prompt_ids(70, seed=14), base0=0)
    served.tick({0: 1, 1: 2})
    served.tick({1: 3})
    got = {k: int(v) for k, v in served.stats.items()}
    group = O.DB.sweep_group_rows(PAGE, MAX_SEQ // PAGE)
    swept = sum(-(-n // group) * group for n in (41, 71, 72))
    assert got == {
        "gdn_decode_ticks": 2, "gdn_row_ticks": 4 * 3, "gdn_chunks": 2 + 3,
        "gdn_chunk_rows": 110, "gdn_zero_starts": 2,
        "gdn_chunk_positions": 40 * 41 // 2 + 70 * 71 // 2,
        "global_kv_rows_read": 41 + 71 + 72, "global_kv_rows_swept": swept}


# -- (d) the blocked delta rule where beta passes 1 --------------------------------


def _delta_rule_case(corr: float, c=128, h=3, dk=8, dv=16, seed=7):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    k = f(c, h, dk) * (1 - corr) + f(1, h, dk) * corr
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    q = f(c, h, dk)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    v, s0 = f(c, h, dv), f(h, dk, dv) * 0.1
    g = -jnp.exp(f(c, h) - 3.0)
    beta = 1.0 + jax.nn.sigmoid(f(c, h))  # (1, 2)
    live = jnp.arange(c) < 100  # rows 100.. are padding: no decay, no write
    return (q, k, v, jnp.where(live[:, None], g, 0.0),
            jnp.where(live[:, None], beta, 0.0), s0)


def _recurrence(q, k, v, g, beta, s0):
    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        s = s * jnp.exp(g_t)[:, None, None]
        pred = (s * k_t[..., None]).sum(-2)
        s = s + (b_t[:, None] * k_t)[..., None] * (v_t - pred)[:, None, :]
        return s, (s * q_t[..., None]).sum(-2)

    s, o = jax.lax.scan(step, s0, (q, k, v, g, beta))
    return o, s


@pytest.mark.parametrize("corr", [0.0, 0.95], ids=["random keys", "keys alike"])
@pytest.mark.parametrize("block", [16, 64, 128])
def test_the_solved_blocked_form_equals_the_recurrence_with_beta_past_1(
        corr, block):
    q, k, v, g, beta, s0 = _delta_rule_case(corr)
    o_want, s_want = _recurrence(q, k, v, g, beta, s0)
    o, s = DR.head_gated_delta_rule_blocks(q, k, v, g, beta, s0, block)
    assert np.abs(np.asarray(o - o_want))[:100].max() < 2e-5
    assert np.abs(np.asarray(s - s_want)).max() < 2e-5


def test_the_expansion_in_powers_does_not_hold_with_beta_past_1():
    """Why the head-gated form solves: GLM's ``(I + A)^-1`` by repeated
    squaring, given the same rows with the gate spread over the key
    channels, is right for random keys and off by a thousand times the
    limit once the keys are alike and ``beta`` passes 1 (its powers of
    ``A`` grow as ``(beta * block)^j`` before they cancel)."""
    for corr, holds in ((0.0, True), (0.95, False)):
        q, k, v, g, beta, s0 = _delta_rule_case(corr)
        o_want, _ = _recurrence(q, k, v, g, beta, s0)
        o, _ = DR.delta_rule_blocks(
            q, k, v, jnp.broadcast_to(g[..., None], k.shape), beta, s0, 16)
        err = np.abs(np.asarray(o - o_want))[:100].max()
        assert (err < 2e-5) == holds, (corr, err)


# -- (e) the moved code is GLM's ---------------------------------------------------


def _parents_delta_rule_blocks(q, k, v, g, beta, s0, block: int):
    """``glm5_next.delta_rule_blocks`` as it stood in the parent commit,
    statement for statement (its home is ``models/delta_rule.py`` now)."""
    highest = jax.lax.Precision.HIGHEST
    c, h, dk = q.shape
    qn = min(block, c)
    nb = c // qn

    def blocks(t):
        return t.reshape(nb, qn, *t.shape[1:])

    qb, kb, vb, gb, bb = map(blocks, (q, k, v, g, beta))
    gsum = jnp.cumsum(gb, axis=1)
    t_idx = jnp.arange(qn)
    lower = t_idx[:, None] >= t_idx[None, :]
    pair = jnp.exp(jnp.where(
        lower[None, :, :, None, None],
        gsum[:, :, None] - gsum[:, None, :], -jnp.inf))
    kk = (kb[:, :, None] * kb[:, None, :] * pair).sum(-1)
    qk = (qb[:, :, None] * kb[:, None, :] * pair).sum(-1)
    a = jnp.where((t_idx[:, None] > t_idx[None, :])[None, :, :, None],
                  bb[:, :, None, :] * kk, 0.0)
    a = jnp.moveaxis(a, -1, 1)
    b_mat = jnp.moveaxis(qk, -1, 1)

    def mm(x, y):
        return jnp.matmul(x, y, precision=highest)

    power = -a
    inv = jnp.eye(qn, dtype=a.dtype) + power
    for _ in range(qn.bit_length() - 2):
        power = mm(power, power)
        inv = inv + mm(inv, power)
    decay = jnp.exp(gsum)
    to_end = jnp.exp(gsum[:, -1:] - gsum)

    def body(s, inp):
        q_, k_, v_, beta_, decay_, to_end_, inv_, b_ = inp
        rhs = beta_[..., None] * (v_ - jnp.einsum(
            "thk,hkv->thv", k_ * decay_, s, precision=highest))
        u = jnp.einsum("hts,shv->thv", inv_, rhs, precision=highest)
        o = jnp.einsum("thk,hkv->thv", q_ * decay_, s, precision=highest) \
            + jnp.einsum("hts,shv->thv", b_, u, precision=highest)
        s = s * decay_[-1][..., None] + jnp.einsum(
            "thk,thv->hkv", k_ * to_end_, u, precision=highest)
        return s, o

    s, o = jax.lax.scan(body, s0, (qb, kb, vb, bb, decay, to_end, inv, b_mat))
    return o.reshape(c, h, -1), s


def test_the_moved_delta_rule_traces_to_the_parents_jaxpr_for_glm():
    from dora_tpu.models.hf import glm5_next as G
    from dora_tpu.ops.kda_state_step import kda_state_step

    assert G.delta_rule_blocks is DR.delta_rule_blocks
    f32 = jnp.float32
    c, h, d = 32, 4, 16  # GLM's tiny chunk: a decay a key channel
    shapes = [jax.ShapeDtypeStruct(s, f32) for s in (
        (c, h, d), (c, h, d), (c, h, d), (c, h, d), (c, h), (h, d, d))]
    moved = jax.make_jaxpr(lambda *a: DR.delta_rule_blocks(*a, G.KDA_BLOCK))(
        *shapes)
    parents = jax.make_jaxpr(
        lambda *a: _parents_delta_rule_blocks(*a, G.KDA_BLOCK))(*shapes)
    assert str(moved) == str(parents)
    # the step: a per-channel gate goes to the kernel as it came
    rows = [jax.ShapeDtypeStruct(s, f32) for s in (
        (3, h, d, d), (3, h, d), (3, h, d), (3, h, d), (3, h, d), (3, h))]
    on = jax.ShapeDtypeStruct((3,), jnp.bool_)
    assert str(jax.make_jaxpr(DR.delta_rule_step)(*rows, on)) == str(
        jax.make_jaxpr(kda_state_step)(*rows, on))


# -- (f) the refusals by name, the family, the bytes -------------------------------


@pytest.mark.parametrize("knob", sorted(O.NOT_OFFERED))
def test_knobs_of_the_qwen_path_are_refused_by_name(model, monkeypatch, knob):
    cfg, params, _ = model
    monkeypatch.setenv(knob, "1")
    with pytest.raises(NotImplementedError, match=knob):
        make_engine(cfg, params)


@pytest.mark.parametrize("change,error,match", [
    ({"layer_types": KINDS[:4]}, ValueError, "layer_types"),
    ({"layer_types": ["sliding_attention"] * 5}, NotImplementedError,
     "sliding_attention"),
    ({"rope_parameters": {"rope_theta": 500000.0}}, NotImplementedError,
     "rope_theta 500000.0"),
    ({"attention_bias": True}, NotImplementedError, "attention_bias"),
    ({"tie_word_embeddings": True}, NotImplementedError, "tied"),
    ({"linear_num_key_heads": 2}, NotImplementedError, "linear_num_key_heads 2"),
    ({"num_attention_heads": 5}, ValueError, "num_attention_heads 5"),
    ({"model_type": "olmo3"}, ValueError, "olmo3"),
])
def test_unsupported_variants_are_refused_by_name(change, error, match):
    with pytest.raises(error, match=match):
        O.OlmoHybridConfig.from_hf({**TINY, **change})


def test_llm_server_knows_the_family(ckpt):
    from dora_tpu.models.hf.loader import read_config
    from dora_tpu.nodehub import llm_server

    module = llm_server.model_module(read_config(ckpt)["model_type"])
    assert module is O
    with pytest.raises(RuntimeError, match="olmo_hybrid") as err:
        llm_server.model_module("olmo3")
    assert "olmo3" in str(err.value)


def test_llm_server_builds_the_engine_with_the_prefix_cache_on(model, monkeypatch):
    """The front door's default (``DORA_PREFIX_CACHE`` unset = on) reaches
    this model's engine, with a snapshot pool beside it."""
    from dora_tpu.nodehub import llm_server

    cfg, params, _ = model
    for key, value in {"DORA_BATCH_SLOTS": "3", "DORA_PAGE_SIZE": "8",
                       "DORA_PREFILL_CHUNK": "32", "DORA_MULTISTEP_K": "4"}.items():
        monkeypatch.setenv(key, value)
    monkeypatch.delenv("DORA_PREFIX_CACHE", raising=False)
    engine = llm_server.make_engine(params, cfg, module=O)
    assert engine.prefix_cache is not None and engine.snapshot_pool is not None
    assert engine.prefix_cache.snapshots == 2 * 3
    monkeypatch.setenv("DORA_PREFIX_CACHE", "0")
    engine = llm_server.make_engine(params, cfg, module=O)
    assert engine.prefix_cache is None and engine.snapshot_pool is None


def test_the_published_cut_in_bytes():
    """The numbers ``PERF.md`` and the configuration's file state, from
    the config class: 61,440 B a cached position at bf16, 2,211,840 B of
    float32 state a slot a linear layer, and the two rules in bytes."""
    cfg = O.OlmoHybridConfig.from_hf({
        **TINY, "hidden_size": 3840, "num_attention_heads": 30,
        "num_key_value_heads": 30, "intermediate_size": 11008,
        "num_hidden_layers": 16, "layer_types": (KINDS[:4]) * 4,
        "vocab_size": 100352, "linear_num_key_heads": 30,
        "linear_num_value_heads": 30, "linear_key_head_dim": 96,
        "linear_value_head_dim": 192}, max_seq=12288)
    item = jnp.dtype(O.L.compute_dtype()).itemsize  # 4 on the CPU, 2 on the chip
    assert cfg.head_dim == 128 and cfg.conv_width == 11520
    assert cfg.kv_bytes_per_token == 4 * 2 * 30 * 128 * item
    assert 30 * 96 * 192 * 4 == 2_211_840
    assert cfg.state_bytes_per_slot == 12 * (2_211_840 + 3 * 11520 * item)
    # the matrices a token touches, as ISSUE 56 reckons them
    linear = 2 * 3840 * 2880 + 3 * 3840 * 5760 + 2 * 3840 * 30
    assert O.flops_per_token(cfg) == 2.0 * (
        12 * linear + 4 * 4 * 3840 * 3840 + 16 * 3 * 3840 * 11008
        + 3840 * 100352)
    snapshot = 12 * (2_211_840 + 3 * 11520 * 2)
    assert snapshot == 27_371_520  # the price of 445.5 cached tokens
    assert snapshot // 61_440 == 445
    left = 16_909_336_064 - 4_490_000_000 - 16 * snapshot - PM.POOL_HEADROOM_BYTES
    assert PM.snapshots_that_fit(snapshot, left, 16) == 35
    assert PM.snapshots_that_fit(snapshot, 1 << 30, 16) == 32   # the floor
    assert PM.snapshots_that_fit(snapshot, 64 << 30, 16) == 64  # the cap
