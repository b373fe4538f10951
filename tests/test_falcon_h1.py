"""Falcon-H1 on the paged serving path (models/hf/falcon_h1.py) against
its plain float32 reference (models/hf/falcon_h1_reference.py: the
recurrence token by token, whole-sequence attention), at tiny widths on
the CPU, from seeded weights. Logits are compared, not sampled tokens.

On the CPU the serving path computes in float32 too, so the tolerances
are float32 summation order (the chunked scan against the token-by-token
recurrence, running against whole softmax, the int8 scales folded or
not). ``TOL`` = 1e-5 absolute on logits of magnitude 0.9: 24 times what
was measured (4.2e-7), and under what a faulty program moves, each
asserted below: an SSM state kept in bfloat16 2.75e-5 (small because
the gated norm divides the mixer's output by its own size, and the
state's values are of order 0.01 at Mamba-2's ``dt``), a dropped
convolution tail 0.79, a dropped ``D`` 0.76, a dropped mixer 0.75.
``STATE_TOL`` = 1e-5 of the state's largest value: measured 4e-7, a
bfloat16 state reads 2.4e-3 to 7e-3.
"""

from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dora_tpu.models.hf import falcon_h1 as F
from dora_tpu.models.hf import falcon_h1_reference as R
from dora_tpu.ops import ssm_state_step as S

TOL = 1e-5
STATE_TOL = 1e-5
PAGE, CHUNK, BLOCK, WINDOW, SLOTS, MAX_SEQ = 8, 16, 16, 4, 3, 64

TINY = dict(
    model_type="falcon_h1", hidden_size=64, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, intermediate_size=128,
    num_hidden_layers=2, vocab_size=128, rms_norm_eps=1e-5,
    rope_theta=1e11, rope_scaling=None, max_position_embeddings=MAX_SEQ,
    tie_word_embeddings=False, attention_bias=False, mlp_bias=False,
    mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16, mamba_n_groups=2,
    mamba_d_state=32, mamba_d_conv=4, mamba_chunk_size=8, mamba_expand=2,
    mamba_conv_bias=True, mamba_proj_bias=False, mamba_rms_norm=True,
    mamba_norm_before_gate=False, projectors_bias=False,
    embedding_multiplier=5.656854249492381, lm_head_multiplier=0.25,
    attention_in_multiplier=1.0, attention_out_multiplier=0.75,
    key_multiplier=0.5, ssm_in_multiplier=0.25,
    ssm_multipliers=[0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738],
    ssm_out_multiplier=0.7, mlp_multipliers=[0.5, 0.4],
)


def write_checkpoint(path: Path, cfg: dict, seed: int = 0) -> None:
    """A float32 checkpoint under the HF names; ``A_log``, ``dt_bias``
    and ``D`` in the ranges Mamba-2 initialises them (A in 1..16, dt in
    0.001..0.1 before the softplus's inverse, D = 1), so a decay is
    neither 0 nor 1."""
    from safetensors.numpy import save_file

    rng = np.random.default_rng(seed)
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d_ssm, mh = cfg["mamba_d_ssm"], cfg["mamba_n_heads"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    conv_dim = d_ssm + 2 * gn
    t: dict[str, np.ndarray] = {}

    def w(out, inp, scale=None):
        return (rng.standard_normal((out, inp)) * (scale or inp ** -0.5)
                ).astype(np.float32)

    def norm(n):
        return (1 + 0.1 * rng.standard_normal(n)).astype(np.float32)

    t["model.embed_tokens.weight"] = w(cfg["vocab_size"], d, 1.0 / 5.6)
    t["model.final_layernorm.weight"] = norm(d)
    t["lm_head.weight"] = w(cfg["vocab_size"], d)
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        t[p + "input_layernorm.weight"] = norm(d)
        t[p + "pre_ff_layernorm.weight"] = norm(d)
        a, m, f = p + "self_attn.", p + "mamba.", p + "feed_forward."
        t[a + "q_proj.weight"] = w(h * hd, d)
        t[a + "k_proj.weight"] = w(kv * hd, d, 2 * d ** -0.5)
        t[a + "v_proj.weight"] = w(kv * hd, d)
        t[a + "o_proj.weight"] = w(d, h * hd)
        t[m + "in_proj.weight"] = w(d_ssm + conv_dim + mh, d, 4 * d ** -0.5)
        t[m + "conv1d.weight"] = (
            rng.standard_normal((conv_dim, 1, cfg["mamba_d_conv"])) * 0.5
        ).astype(np.float32)
        t[m + "conv1d.bias"] = (0.1 * rng.standard_normal(conv_dim)).astype(np.float32)
        dt = np.exp(rng.uniform(np.log(0.001), np.log(0.1), mh))
        t[m + "dt_bias"] = (dt + np.log(-np.expm1(-dt))).astype(np.float32)
        t[m + "A_log"] = np.log(rng.uniform(1, 16, mh)).astype(np.float32)
        t[m + "D"] = np.ones(mh, np.float32)
        t[m + "norm.weight"] = norm(d_ssm)
        t[m + "out_proj.weight"] = w(d, d_ssm)
        t[f + "gate_proj.weight"] = w(cfg["intermediate_size"], d)
        t[f + "up_proj.weight"] = w(cfg["intermediate_size"], d)
        t[f + "down_proj.weight"] = w(d, cfg["intermediate_size"])
    path.mkdir(parents=True, exist_ok=True)
    save_file(t, str(path / "model.safetensors"))
    (path / "config.json").write_text(json.dumps(cfg))


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory) -> Path:
    path = tmp_path_factory.mktemp("falcon") / "ckpt"
    write_checkpoint(path, TINY)
    return path


@pytest.fixture(scope="module")
def model(ckpt):
    cfg, params = F.load(ckpt, max_seq=MAX_SEQ)
    return cfg, params, R.load(ckpt)


def prompt_ids(n: int, seed: int = 1) -> list[int]:
    return np.random.default_rng(seed).integers(1, 128, size=n).tolist()


def make_engine(cfg, params, **kw):
    kw = {"max_slots": SLOTS, "page_size": PAGE, "chunk": CHUNK,
          "window": WINDOW, "attn_block": BLOCK, **kw}
    return F.make_paged_engine(params, cfg, **kw)


def run(engine, rid) -> list[int]:
    """Step until ``rid`` is done; its tokens."""
    out = []
    for _ in range(200):
        for r, tok, done in engine.step():
            if r == rid:
                out.append(tok)
                if done:
                    return out
    raise AssertionError(f"{rid} never finished")


@pytest.fixture(scope="module")
def programs(model):
    """The two programs as the engine jits them, but with logits where
    the greedy tokens would be (cfg is static)."""
    cfg = model[0]
    return (
        jax.jit(lambda p, *a: F.paged_chunk_logits(p, cfg, *a, block=BLOCK)),
        jax.jit(lambda p, *a: F.paged_batch_logits(p, cfg, *a, block=BLOCK)),
    )


def serve_logits(model, programs, prompt, emitted, slot=1, ssm_dtype=None):
    """What the engine does for one stream, by hand, keeping the logits:
    chunked prefill of ``prompt`` into ``slot`` (the last chunk ragged
    when the prompt's length says so), then one decode tick a token of
    ``emitted`` (teacher-forced), the other rows frozen. Returns (logits
    [len(prompt) + len(emitted), vocab], the slot state, the counters). ``ssm_dtype``
    rounds the SSM state through that type after every program: what a
    state kept in it would hold."""
    cfg, params, _ = model
    chunk_fn, batch_fn = programs
    pools = F.init_page_pool(cfg, SLOTS * MAX_SEQ // PAGE + 1, PAGE)
    state = F.init_slot_state(cfg, SLOTS)
    stats = F.init_counters()
    pages = MAX_SEQ // PAGE
    bt = np.zeros((SLOTS, pages), np.int32)
    bt[slot] = 1 + slot * pages + np.arange(pages)
    i32 = jnp.int32

    def rounded(state):
        if ssm_dtype is None:
            return state
        return {k: {**v, "ssm": v["ssm"].astype(ssm_dtype).astype(jnp.float32)}
                for k, v in state.items()}

    logits = []
    for base in range(0, len(prompt), CHUNK):
        piece = prompt[base : base + CHUNK]
        ids = jnp.asarray(piece + [0] * (CHUNK - len(piece)), i32)
        lg, pools, state, stats = chunk_fn(
            params, ids, pools, state, stats, jnp.asarray(base, i32),
            jnp.asarray(bt[slot]), jnp.asarray(len(piece), i32),
            jnp.asarray(slot, i32))
        state = rounded(state)
        logits.append(np.asarray(lg[: len(piece)]))
    active = jnp.arange(SLOTS) == slot
    bts = jnp.asarray(bt * np.asarray(active)[:, None])
    for j, tok in enumerate(emitted):
        tokens = jnp.zeros((SLOTS,), i32).at[slot].set(tok)
        positions = jnp.zeros((SLOTS,), i32).at[slot].set(len(prompt) + j)
        lg, pools, state, stats = batch_fn(
            params, tokens, pools, state, stats, positions, bts, active)
        state = rounded(state)
        logits.append(np.asarray(lg[slot : slot + 1]))
    return np.concatenate(logits), state, stats


def reference_logits(model, tokens, drop=None):
    _, _, (hf, w) = model
    logits, states = R.forward(w, hf, jnp.asarray(tokens, jnp.int32), drop)
    return np.asarray(logits), states


def state_error(state, layer: int, want, slot: int = 1) -> float:
    """Largest error of the slot's SSM state over the reference's
    largest value."""
    want = np.asarray(want)
    got = np.asarray(state[str(layer)]["ssm"][slot])
    return float(np.abs(got - want).max() / np.abs(want).max())


# -- (a) chunked prefill + decode against the reference, on logits -----------


def test_chunked_prefill_then_decode_matches_the_reference(model, programs):
    """41 prompt tokens are three chunks of 16 (the last holds 9), then
    2 * WINDOW decode ticks: every position's logits against the
    reference's one forward pass over the whole sequence, and the slot's
    state against the reference's after the last token."""
    prompt, emitted = prompt_ids(41), prompt_ids(2 * WINDOW, seed=2)
    want, states = reference_logits(model, prompt + emitted)
    got, state, stats = serve_logits(model, programs, prompt, emitted)
    assert np.abs(want).max() > 0.5
    assert np.abs(got - want).max() < TOL
    for i, (s, tail) in enumerate(states):
        assert state_error(state, i, s) < STATE_TOL
        assert np.abs(np.asarray(state[str(i)]["conv"][1]) - np.asarray(tail)).max() < TOL
        # the other slots' rows were never touched
        assert not np.asarray(state[str(i)]["ssm"][0]).any()
        assert not np.asarray(state[str(i)]["ssm"][2]).any()
    assert {k: int(v) for k, v in stats.items()} == {
        "row_ticks": 2 * WINDOW, "decode_ticks": 2 * WINDOW,
        "chunk_rows": 41, "zero_starts": 1,
    }


@pytest.mark.parametrize("fault,least", [
    ("bf16_state", 2 * TOL), ("conv_tail", 0.3), ("D", 0.3), ("mixer", 0.3),
])
def test_the_tolerance_sees_a_faulty_program(model, programs, fault, least):
    """What (a) must fail: an SSM state kept in bfloat16 (on the logits
    and, a hundred times over, on the state), and a reference without
    the convolution's tail, without ``D``, without the mixer branch (= a
    program that computed them where they are not)."""
    prompt, emitted = prompt_ids(41), prompt_ids(2 * WINDOW, seed=2)
    if fault == "bf16_state":
        want, states = reference_logits(model, prompt + emitted)
        got, state, _ = serve_logits(model, programs, prompt, emitted,
                                     ssm_dtype=jnp.bfloat16)
        assert all(state_error(state, i, s) > 100 * STATE_TOL
                   for i, (s, _) in enumerate(states))
    else:
        want, _ = reference_logits(model, prompt + emitted, drop=fault)
        got, _, _ = serve_logits(model, programs, prompt, emitted)
    assert np.abs(got - want).max() > least


def test_engine_tokens_are_the_programs_argmax(model, programs):
    """Through ``PagedBatchEngine`` itself (dispatch, window, collect):
    the stream's tokens are the greedy continuation of the logits above."""
    cfg, params, _ = model
    prompt = prompt_ids(41)
    engine = make_engine(cfg, params)
    engine.submit("r", prompt, 2 * WINDOW + 1)
    tokens = run(engine, "r")
    assert len(tokens) == 2 * WINDOW + 1
    got, _, _ = serve_logits(model, programs, prompt, tokens[:-1], slot=0)
    assert np.argmax(got[len(prompt) - 1 :], -1).tolist() == tokens
    report = engine.model_counters()
    assert report["ssm_chunk_rows"] == 41 and report["ssm_zero_starts"] == 1
    assert report["ssm_row_ticks"] == 2 * WINDOW
    assert report["ssm_state_bytes"] == SLOTS * cfg.state_bytes_per_slot
    assert report["ssm_slots_live"] == 0


# -- (b) the chunked scan against the token-by-token recurrence ---------------


def test_chunked_scan_matches_the_recurrence():
    """``ssd_scan`` (two blocks of 8, state carried in) against one
    ``ssm_state_step`` a token: outputs and the state after the last row;
    and rows whose ``dt`` is 0 leave the state alone."""
    cfg = F.FalconH1Config.from_hf(TINY, MAX_SEQ)
    rng = np.random.default_rng(3)
    c, h, p, g, n = 16, cfg.ssm_heads, cfg.ssm_head_dim, cfg.n_groups, cfg.d_state

    def f(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    x, bm, cm, s0 = f(c, h, p), f(c, g, n), f(c, g, n), f(h, p, n)
    dt = jax.nn.softplus(f(c, h))
    a, d = -jnp.exp(f(h)), f(h)
    y, s = F.ssd_scan(cfg, x, dt, a, bm, cm, d, s0)
    state, ys = s0[None], []
    on = jnp.ones((1,), bool)
    for t in range(c):
        yt, state = S.ssm_state_step(
            state, x[t][None], dt[t][None], a, bm[t][None], cm[t][None], d, on)
        ys.append(yt[0])
    assert np.abs(np.asarray(y) - np.asarray(jnp.stack(ys))).max() < 2e-5
    assert np.abs(np.asarray(s) - np.asarray(state[0])).max() < 2e-5
    # stopped at row 11: the state after 16 rows is the state after 11
    stop = jnp.where((jnp.arange(c) < 11)[:, None], dt, 0.0)
    _, s11 = F.ssd_scan(cfg, x, stop, a, bm, cm, d, s0)
    _, s_short = F.ssd_scan(cfg, x[:8], dt[:8], a, bm[:8], cm[:8], d, s0)
    state = s_short[None]
    for t in range(8, 11):
        _, state = S.ssm_state_step(
            state, x[t][None], dt[t][None], a, bm[t][None], cm[t][None], d, on)
    assert np.abs(np.asarray(s11) - np.asarray(state[0])).max() < 2e-5


@pytest.mark.parametrize("active", [
    [1, 1, 1, 1, 1], [0, 0, 1, 0, 1], [0, 0, 0, 0, 0], [1, 0, 0, 0, 0],
    [0, 0, 0, 0, 1],
])
def test_state_step_kernel_steps_the_active_rows_alone(active):
    """The Pallas kernel (interpret mode here) against the whole-array
    form, for every shape of the grid's tables: an inactive row's state
    comes back bit for bit and its output is zero."""
    rng = np.random.default_rng(4)
    r, h, p, g, n = 5, 8, 16, 2, 32

    def f(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    state, x, bm, cm = f(r, h, p, n), f(r, h, p), f(r, g, n), f(r, g, n)
    dt, a, d = jax.nn.softplus(f(r, h)), -jnp.exp(f(h)), f(h)
    on = jnp.asarray(active, bool)
    y, new = S.ssm_state_step(state, x, dt, a, bm, cm, d, on)
    y_ref, new_ref = S.ssm_state_step_reference(state, x, dt, a, bm, cm, d, on)
    assert np.abs(np.asarray(y) - np.asarray(y_ref)).max() < 1e-4
    assert np.abs(np.asarray(new) - np.asarray(new_ref)).max() < 1e-5
    off = ~np.asarray(on)
    assert (np.asarray(new)[off] == np.asarray(state)[off]).all()
    assert not np.asarray(y)[off].any()


# -- (c) state isolation and reset; (d) a frozen row ----------------------------


def test_twins_and_a_reused_slot_give_the_first_streams_tokens(model):
    """Two slots given the same prompt at once, and a slot taken again
    after a longer stream left its state there, all give the tokens the
    prompt gives alone: no row reads another's state, and a chunk at
    position 0 starts from zeros without the host resetting anything."""
    cfg, params, _ = model
    prompt, longer = prompt_ids(21), prompt_ids(45, seed=5)
    alone = make_engine(cfg, params)
    alone.submit("a", prompt, 9)
    want = run(alone, "a")
    engine = make_engine(cfg, params)
    engine.submit("long", longer, 14)   # slot 0
    engine.submit("t1", prompt, 9)      # slot 1
    engine.submit("t2", prompt, 9)      # slot 2
    got: dict[str, list[int]] = {"long": [], "t1": [], "t2": [], "again": []}
    resubmitted = False
    for _ in range(200):
        for r, tok, _done in engine.step():
            got[r].append(tok)
        if not resubmitted and engine.slots[0] is None:
            assert any(np.asarray(engine.slot_state["0"]["ssm"][0]).ravel())
            engine.submit("again", prompt, 9)  # slot 0, after "long"
            assert engine.slots[0].request_id == "again"
            resubmitted = True
        if resubmitted and not engine.active:
            break
    assert got["t1"] == want and got["t2"] == want and got["again"] == want
    assert len(got["long"]) == 14


def test_chunks_ahead_of_their_period_carry_the_slot_state_as_in_line(model):
    """``ahead()`` hands the next period's chunk the slots' state as the
    running window will leave it: three-chunk and two-chunk prompts
    whose chunks go behind windows that step other rows' state give the
    tokens they give alone, and a slot taken again starts from zeros."""
    cfg, params, _ = model
    prompt, longer = prompt_ids(21), prompt_ids(45, seed=5)
    alone = make_engine(cfg, params)
    alone.submit("a", prompt, 9)
    want = run(alone, "a")
    alone.submit("b", longer, 14)
    want_long = run(alone, "b")
    engine = make_engine(cfg, params)
    engine.submit("t1", prompt, 9)      # slot 0
    engine.submit("long", longer, 14)   # slot 1
    engine.submit("t2", prompt, 9)      # slot 2
    got: dict[str, list[int]] = {"long": [], "t1": [], "t2": [], "again": []}
    resubmitted = False
    for _ in range(200):
        first = engine.dispatch()
        engine.ahead()
        for r, tok, _done in first + engine.collect():
            got[r].append(tok)
        if not resubmitted and engine.slots[0] is None:
            engine.submit("again", prompt, 9)  # slot 0, after "t1"
            resubmitted = True
        if resubmitted and not engine.active:
            break
    assert got["t1"] == want and got["t2"] == want and got["again"] == want
    assert got["long"] == want_long
    assert engine.chunks_run == 2 + 3 + 2 + 2 and engine.chunks_ahead >= 6


def test_a_frozen_rows_state_is_bit_identical_across_a_window(model):
    """A stream that finishes on a window's first tick is frozen for the
    rest of it: its state is the reference's after that one token, not
    after the three ticks that followed. From then on the row is not
    active (freed, while a neighbour decodes and another slot is in
    mid-prefill), and window after window its state comes back bit for
    bit; the slot in mid-prefill takes no decode tick either."""
    cfg, params, _ = model
    prompt = prompt_ids(10)
    engine = make_engine(cfg, params)
    engine.submit("short", prompt, 2)             # slot 0: 1 token + 1 tick
    engine.submit("other", prompt_ids(12, 7), 3 * WINDOW)
    late = prompt_ids(40, 8)
    engine.submit("late", late, 2)                # slot 2: three chunks
    # slot 0's chunk, then a window whose first tick ends the stream
    tokens = [(tok, done) for r, tok, done in engine.step() if r == "short"]
    assert [done for _, done in tokens] == [False, True]
    _, one = reference_logits(model, prompt + [tokens[0][0]])
    _, two = reference_logits(model, prompt + [t for t, _ in tokens])
    for i in range(cfg.layers):
        assert state_error(engine.slot_state, i, one[i][0], slot=0) < STATE_TOL
        assert state_error(engine.slot_state, i, two[i][0], slot=0) > 100 * STATE_TOL
    held = jax.tree.map(lambda x: np.asarray(x[0]), engine.slot_state)
    engine.step()  # "other"'s chunk and its first window
    assert not np.asarray(engine.slot_state["0"]["ssm"][2]).any()
    engine.step()  # "late"'s first chunk, then a window "other" decodes in
    _, chunk1 = reference_logits(model, late[:CHUNK])
    for i in range(cfg.layers):  # the window's four ticks passed row 2 by
        assert state_error(engine.slot_state, i, chunk1[i][0], slot=2) < STATE_TOL
    engine.step()
    for layer, leaves in held.items():
        for leaf, was in leaves.items():
            assert np.array_equal(
                np.asarray(engine.slot_state[layer][leaf][0]), was)
    assert engine.slots[2].prompt is not None  # still in mid-prefill


# -- (e) checkpoint and restore ----------------------------------------------


def test_checkpoint_restore_round_trips_a_stream_in_mid_decode(model, tmp_path):
    """``checkpoint_state`` + ``save_pools`` after one window, restored
    into a fresh engine (``restore_pools`` + ``restore_state``): the
    stream goes on to the tokens it gives uninterrupted. Without the
    state rows it does not, which is what the check is worth."""
    cfg, params, _ = model
    prompt = prompt_ids(21)
    whole = make_engine(cfg, params)
    whole.submit("r", prompt, 3 * WINDOW)
    want = run(whole, "r")

    first = make_engine(cfg, params)
    first.submit("pad", prompt_ids(5, 9), 2 * WINDOW)  # so "r" sits in slot 1
    first.submit("r", prompt, 3 * WINDOW)
    head = []
    while len(head) < 1 + WINDOW:
        head += [tok for r, tok, _ in first.step() if r == "r"]
    snap = json.loads(json.dumps(first.checkpoint_state()))  # it is JSON
    assert snap["slot_state"] is True
    first.save_pools(tmp_path / "pools")

    second = make_engine(cfg, params)
    second.restore_pools(tmp_path / "pools")
    assert "r" in second.restore_state(snap)
    assert head + run(second, "r") == want

    blank = make_engine(cfg, params)
    blank.restore_pools(tmp_path / "pools")
    blank.slot_state = F.init_slot_state(cfg, SLOTS)
    blank.restore_state(snap)
    assert head + run(blank, "r") != want
    # a handoff to another slot cannot take the state along: refused by name
    with pytest.raises(RuntimeError, match="per-slot state"):
        make_engine(cfg, params).admit_streams(snap)
    # and a snapshot of one kind of engine is not restored on the other
    with pytest.raises(ValueError, match="per-slot state"):
        make_engine(cfg, params).restore_state({**snap, "slot_state": False})


def test_preempt_drops_the_state_and_resume_prefills_from_zero(model):
    cfg, params, _ = model
    prompt = prompt_ids(21)
    alone = make_engine(cfg, params)
    alone.submit("a", prompt, 2 * WINDOW)
    want = run(alone, "a")
    engine = make_engine(cfg, params)
    engine.submit("a", prompt, 2 * WINDOW)
    got = []
    while len(got) < 3:
        got += [tok for _, tok, _ in engine.step()]
    meta = engine.preempt("a")
    assert meta["emitted"] == len(got) and engine.active == 0
    engine.submit("a", prompt + got, 2 * WINDOW - len(got))
    assert got + run(engine, "a") == want


# -- (f) what is refused, by name --------------------------------------------


def test_no_prefix_cache_whatever_is_asked(model, caplog):
    cfg, params, _ = model
    with caplog.at_level("WARNING"):
        engine = make_engine(cfg, params, prefix_cache=True)
    assert engine.prefix_cache is None
    assert "prefix cache is off" in caplog.text


@pytest.mark.parametrize("knob", sorted(F.NOT_OFFERED))
def test_knobs_of_the_qwen_path_are_refused_by_name(model, monkeypatch, knob):
    cfg, params, _ = model
    monkeypatch.setenv(knob, "4" if knob == "DORA_SPEC_K" else "1")
    with pytest.raises(NotImplementedError, match=knob):
        make_engine(cfg, params)


@pytest.mark.parametrize("kw", [
    {"prefix_cache": True}, {"spec_k": 2}, {"lora_pool": object()},
])
def test_the_engine_refuses_what_cannot_follow_a_slot_state(kw):
    from dora_tpu.models.batch_engine import PagedBatchEngine

    with pytest.raises(NotImplementedError, match="slot-state"):
        PagedBatchEngine(
            init_pool=lambda n: {}, init_slot_state=lambda s: {},
            chunk_prefill=None, window_step=None, max_seq=64, page_size=8,
            chunk=16, num_pages=9, **kw)


def test_the_engine_refuses_valid_rows_beside_adapters():
    """The chunk's operands are ``valid`` or the adapter's, never both:
    no chunk program was written for that signature."""
    from dora_tpu.models.batch_engine import PagedBatchEngine

    with pytest.raises(NotImplementedError, match="chunk_valid_rows"):
        PagedBatchEngine(
            init_pool=lambda n: {}, chunk_prefill=None, window_step=None,
            max_seq=64, page_size=8, chunk=16, num_pages=9,
            chunk_valid_rows=True, lora_pool=object())


def test_llm_server_knows_the_family(ckpt):
    from dora_tpu.nodehub import llm_server

    assert llm_server.model_module("falcon_h1") is F
    with pytest.raises(RuntimeError, match="falcon_h2"):
        llm_server.model_module("falcon_h2")


# -- (g) the other two families' programs do not see the new argument ---------


@pytest.mark.parametrize("module_name,window_operands", [
    ("qwen2", 8), ("kimi_k2", 9),
])
def test_engines_without_a_slot_state_trace_as_before(
    module_name, window_operands, monkeypatch, tmp_path
):
    """Without ``init_slot_state`` the engine hands its closures the
    operands it always did (the window: parameters, tokens, pools,
    [Kimi's counters,] positions, tables, active, emitted, max_new; the
    chunk: no slot and no state) and ``make_paged_window`` carries five
    things through its scan: the programs' StableHLO is the parent's
    byte for byte (compared at both trees when this was written)."""
    from test_backend import _engine_programs

    seen = _engine_programs(module_name, monkeypatch, tmp_path)
    arities = sorted(len(shapes) for shapes, took in seen.values() if took)
    chunk_operands = {"qwen2": 5, "kimi_k2": 7}[module_name]
    assert arities == [chunk_operands, window_operands]
