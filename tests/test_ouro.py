"""Ouro on the paged serving path (models/hf/ouro.py) against its plain
float32 reference (models/hf/ouro_reference.py: one forward pass over
the whole sequence, no cache), at tiny widths on the CPU, from seeded
weights. Logits are compared, not sampled tokens.

On the CPU the serving path computes in float32 too, so the tolerance is
float32 summation order (running against whole softmax, fused against
separate products). ``TOL`` = 1e-4 absolute on logits of magnitude 3.5:
24 times what was measured (4.2e-6 at three and four passes over three
layers), and far under what a faulty program moves, each asserted
below: three passes for four 2.65, every pass on pass 0's rows 6.6, no
post-norms 3.5, no per-pass final norm 4.4. An emitted token
passes when the reference's logit for it lies within ``TOL`` of the
reference's top logit at its position (the near-tie rule: two correct
programs part where the top two logits are closer than their rounding).
"""

from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dora_tpu.models import paged_model as PM
from dora_tpu.models.hf import ouro as O
from dora_tpu.models.hf import ouro_reference as R

TOL = 1e-4
PAGE, CHUNK, WINDOW, SLOTS, MAX_SEQ = 8, 16, 8, 6, 64
PAGES_A_SLOT = MAX_SEQ // PAGE
NUM_PAGES = SLOTS * PAGES_A_SLOT + 1
VOCAB = 128

TINY = dict(
    model_type="ouro", hidden_size=64, num_attention_heads=4,
    num_key_value_heads=4, head_dim=16, intermediate_size=128,
    num_hidden_layers=3, vocab_size=VOCAB, rms_norm_eps=1e-6,
    rope_theta=1e6, rope_scaling=None, max_position_embeddings=MAX_SEQ,
    tie_word_embeddings=False, hidden_act="silu", total_ut_steps=4,
    early_exit_threshold=1, use_sliding_window=False, sliding_window=None,
    layer_types=["full_attention"] * 3, max_window_layers=3,
)


def tensors(cfg: dict, seed: int = 0) -> dict[str, np.ndarray]:
    """A float32 checkpoint's tensors under the HF names."""
    rng = np.random.default_rng(seed)
    d, hd, f = cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    t: dict[str, np.ndarray] = {}

    def w(out, inp, scale=None):
        return (rng.standard_normal((out, inp)) * (scale or inp ** -0.5)
                ).astype(np.float32)

    def norm(n):
        return (1 + 0.2 * rng.standard_normal(n)).astype(np.float32)

    t["model.embed_tokens.weight"] = w(cfg["vocab_size"], d, 1.0)
    t["model.norm.weight"] = norm(d)
    t["lm_head.weight"] = w(cfg["vocab_size"], d)
    t["model.early_exit_gate.weight"] = w(1, d)
    t["model.early_exit_gate.bias"] = np.asarray([0.3], np.float32)
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        for name in ("input_layernorm", "input_layernorm_2",
                     "post_attention_layernorm", "post_attention_layernorm_2"):
            t[p + name + ".weight"] = norm(d)
        a, m = p + "self_attn.", p + "mlp."
        t[a + "q_proj.weight"] = w(h * hd, d)
        t[a + "k_proj.weight"] = w(kv * hd, d)
        t[a + "v_proj.weight"] = w(kv * hd, d)
        t[a + "o_proj.weight"] = w(d, h * hd)
        t[m + "gate_proj.weight"] = w(f, d)
        t[m + "up_proj.weight"] = w(f, d)
        t[m + "down_proj.weight"] = w(d, f)
    return t


def write_checkpoint(path: Path, cfg: dict, seed: int = 0, drop=(),
                     extra=None) -> Path:
    from safetensors.numpy import save_file

    t = {k: v for k, v in tensors(cfg, seed).items() if k not in drop}
    t.update(extra or {})
    path.mkdir(parents=True, exist_ok=True)
    save_file(t, str(path / "model.safetensors"))
    (path / "config.json").write_text(json.dumps(cfg))
    return path


def load_both(tmp_path_factory, passes: int):
    """(serving config, serving params, the reference's (config, weights))
    of one seeded checkpoint."""
    hf = {**TINY, "total_ut_steps": passes}
    ckpt = write_checkpoint(tmp_path_factory.mktemp("ouro") / "ckpt", hf)
    cfg, params = O.load(ckpt, max_seq=MAX_SEQ)
    return cfg, params, R.load(ckpt)


@pytest.fixture(scope="module", params=[4, 3], ids=["4-passes", "3-passes"])
def model(request, tmp_path_factory):
    return load_both(tmp_path_factory, request.param)


@pytest.fixture(scope="module")
def model4(tmp_path_factory):
    return load_both(tmp_path_factory, 4)


def prompt_ids(n: int, seed: int = 1) -> list[int]:
    return np.random.default_rng(seed).integers(1, VOCAB, size=n).tolist()


def make_engine(cfg, params, **kw):
    kw = {"max_slots": SLOTS, "page_size": PAGE, "chunk": CHUNK,
          "window": WINDOW, "num_pages": NUM_PAGES, **kw}
    return O.make_paged_engine(params, cfg, **kw)


def drain(engine, want: set[str], steps: int = 400) -> dict[str, list[int]]:
    """Step until every request of ``want`` is done; tokens by request."""
    out: dict[str, list[int]] = {r: [] for r in want}
    left = set(want)
    for _ in range(steps):
        for r, tok, done in engine.step():
            out.setdefault(r, []).append(tok)
            if done:
                left.discard(r)
        if not left:
            return out
    raise AssertionError(f"{sorted(left)} never finished")


def run(engine, rid: str) -> list[int]:
    return drain(engine, {rid})[rid]


def programs_of(cfg):
    return (jax.jit(lambda p, *a: O.paged_chunk_logits(p, cfg, *a)),
            jax.jit(lambda p, *a: O.paged_batch_logits(p, cfg, *a)))


def serve_logits(cfg, params, prompt, emitted, slot=1):
    """What the engine does for one stream, by hand, keeping the logits:
    chunked prefill of ``prompt`` (the last chunk ragged when the
    prompt's length says so), then one decode tick a token of
    ``emitted`` (teacher-forced), the other rows frozen. Returns
    (logits [len(prompt) + len(emitted), vocab], lambdas [passes, the
    same], pools, the counters, the stream's pages)."""
    chunk_fn, batch_fn = programs_of(cfg)
    pools = O.init_page_pool(cfg, NUM_PAGES, PAGE)
    stats = O.init_counters()
    bt = np.zeros((SLOTS, PAGES_A_SLOT), np.int32)
    bt[slot] = 1 + slot * PAGES_A_SLOT + np.arange(PAGES_A_SLOT)
    i32 = jnp.int32
    logits, lambdas = [], []
    for base in range(0, len(prompt), CHUNK):
        piece = prompt[base : base + CHUNK]
        ids = jnp.asarray(piece + [0] * (CHUNK - len(piece)), i32)
        lg, pools, stats, lam = chunk_fn(
            params, ids, pools, stats, jnp.asarray(base, i32),
            jnp.asarray(bt[slot]), jnp.asarray(len(piece), i32))
        logits.append(np.asarray(lg[: len(piece)]))
        lambdas.append(np.asarray(lam[:, : len(piece)]))
    live = np.arange(SLOTS) == slot
    bts = jnp.asarray(bt * live[:, None])
    for j, tok in enumerate(emitted):
        tokens = jnp.zeros((SLOTS,), i32).at[slot].set(tok)
        positions = jnp.zeros((SLOTS,), i32).at[slot].set(len(prompt) + j)
        lg, pools, stats, lam = batch_fn(
            params, tokens, pools, stats, positions, bts)
        logits.append(np.asarray(lg[slot : slot + 1]))
        lambdas.append(np.asarray(lam[:, slot : slot + 1]))
    return (np.concatenate(logits), np.concatenate(lambdas, axis=1), pools,
            {k: int(v) for k, v in stats.items()}, bt[slot])


def reference(model, tokens, what_if=None, keep_rows=False):
    _, _, (hf, w) = model
    return R.forward(hf, w, jnp.asarray(tokens, jnp.int32), what_if, keep_rows)


def deficits(model, prompt, emitted) -> np.ndarray:
    """How far below the reference's top logit each emitted token's
    reference logit lies, teacher-forced over prompt + emitted."""
    logits = np.asarray(reference(model, prompt + emitted)[0])
    rows = logits[len(prompt) - 1 : len(prompt) - 1 + len(emitted)]
    return rows.max(-1) - rows[np.arange(len(emitted)), emitted]


# -- (a) chunked prefill + decode against the reference, on logits -----------


def test_chunked_prefill_then_decode_matches_the_reference(model):
    """41 prompt tokens are three chunks of 16 (the last holds 9), then
    WINDOW decode ticks: every position's logits and every pass's gate
    value against the reference's one forward pass over the whole
    sequence; and what the counters count."""
    cfg, params, _ = model
    prompt, emitted = prompt_ids(41), prompt_ids(WINDOW, seed=2)
    want, want_lam = reference(model, prompt + emitted)
    got, lam, _, stats, _ = serve_logits(cfg, params, prompt, emitted)
    assert np.abs(np.asarray(want)).max() > 0.5
    assert np.abs(got - np.asarray(want)).max() < TOL
    assert lam.shape == (cfg.passes, 41 + WINDOW)
    assert np.abs(lam - np.asarray(want_lam)).max() < TOL
    # tick j attends the prompt, the j tokens before it and itself
    rows_read = sum(41 + j + 1 for j in range(WINDOW))
    assert stats == {
        "passes": cfg.passes * WINDOW, "kv_rows_read": cfg.passes * rows_read,
        "decode_ticks": WINDOW, "chunk_rows": 41, "chunks": 3,
        "chunk_positions": 0 + 16 + 32, "exit_before_last": 0,
        # a group is all 64 rows a table holds here: one step a tick
        "sweep_groups": cfg.passes * WINDOW,
    }


def test_sweep_groups_are_the_steps_the_decode_kernel_schedules(tmp_path):
    """``loop_sweep_groups`` of one tick, counted by hand at the serving
    geometry (pages of 16, so a group is 128 cache rows): a frozen row
    holds no step, a row one step for every started 128 rows BEFORE its
    position (its current token is folded in from registers), and every
    pass walks the same schedule."""
    from dora_tpu.ops import decode_block as DB

    max_seq, page = 256, 16
    hf = {**TINY, "max_position_embeddings": max_seq}
    cfg, params = O.load(write_checkpoint(tmp_path / "ckpt", hf), max_seq=max_seq)
    pages_a_slot = max_seq // page
    assert DB.sweep_group_rows(page, pages_a_slot) == 128
    #               frozen  first  last of a group, next group's first two, full
    positions = np.asarray([0, 1, 127, 128, 129, max_seq - 1], np.int32)
    steps = [0, 1, 1, 1, 2, 2]
    bt = 1 + np.arange(SLOTS * pages_a_slot, dtype=np.int32).reshape(SLOTS, -1)
    bt[0] = 0  # freeze_inactive's zeroed table row
    pools = O.init_page_pool(cfg, SLOTS * pages_a_slot + 1, page)
    _, batch_fn = programs_of(cfg)
    *_, stats, _ = batch_fn(
        params, jnp.ones((SLOTS,), jnp.int32), pools, O.init_counters(),
        jnp.asarray(positions), jnp.asarray(bt))
    stats = {k: int(v) for k, v in stats.items()}
    assert stats["sweep_groups"] == cfg.passes * sum(steps) == 28
    assert stats["passes"] == cfg.passes * 5 and stats["decode_ticks"] == 1
    assert stats["kv_rows_read"] == cfg.passes * int((positions[1:] + 1).sum())


@pytest.mark.parametrize("what_if", R.WHAT_IFS)
def test_the_tolerance_sees_a_faulty_program(model4, what_if):
    """What (a) must fail: a reference with three passes for four, with
    every pass attending to pass 0's rows, without the post-norms,
    without the per-pass final norm (= a program that did that)."""
    cfg, params, _ = model4
    prompt, emitted = prompt_ids(41), prompt_ids(WINDOW, seed=2)
    want, _ = reference(model4, prompt + emitted, what_if)
    got, *_ = serve_logits(cfg, params, prompt, emitted)
    assert np.abs(got - np.asarray(want)).max() > 1.0


def test_one_pass_is_a_hand_written_single_pass(tmp_path):
    """``total_ut_steps`` 1: the program's logits against a plain stack
    of sandwich-norm layers, a final norm and a head, written out here."""
    hf = {**TINY, "total_ut_steps": 1}
    ckpt = write_checkpoint(tmp_path / "ckpt", hf)
    cfg, params = O.load(ckpt, max_seq=MAX_SEQ)
    assert cfg.passes == 1 and cfg.kv_entries == 3
    _, w = R.load(ckpt)
    prompt = prompt_ids(23)
    ids = jnp.asarray(prompt, jnp.int32)
    t = len(prompt)
    angles = jnp.arange(t)[:, None] * (
        1.0 / 1e6 ** (jnp.arange(0, 16, 2) / 16))[None]
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    with jax.default_matmul_precision("highest"):
        x = w["embed_tokens.weight"][ids]
        for i in range(3):
            p = f"layers.{i}."
            u = R.rms_norm(x, w[p + "input_layernorm.weight"], 1e-6)
            k, v = R.keys_values(hf, w, i, u, cos, sin)
            a = R.attention(hf, w, i, u, k, v, cos, sin)
            x = x + R.rms_norm(a, w[p + "input_layernorm_2.weight"], 1e-6)
            u = R.rms_norm(x, w[p + "post_attention_layernorm.weight"], 1e-6)
            x = x + R.rms_norm(
                R.mlp(w, i, u), w[p + "post_attention_layernorm_2.weight"], 1e-6)
        want = R.rms_norm(x, w["norm.weight"], 1e-6) @ w["lm_head.weight"]
    got, lam, *_ = serve_logits(cfg, params, prompt, [])
    assert lam.shape == (1, t)
    assert np.abs(got - np.asarray(want)).max() < TOL
    assert np.abs(np.asarray(R.forward(hf, w, ids)[0]) - np.asarray(want)).max() < TOL


# -- (b) the engine itself: windows of 8, streams side by side ----------------


def test_concurrent_streams_of_unequal_length_pass_the_reference(model):
    """Six streams at once through ``PagedBatchEngine`` (chunked prefill
    between windows, K = 8 windows, rows finishing on different ticks):
    every token every stream emitted lies within ``TOL`` of the top of
    the reference's logits at its position."""
    cfg, params, _ = model
    lengths = [(5, 12), (17, 9), (33, 20), (41, 3), (16, 17), (26, 1)]
    engine = make_engine(cfg, params)
    prompts = {}
    for n, (p_len, new) in enumerate(lengths):
        prompts[f"r{n}"] = prompt_ids(p_len, seed=10 + n)
        engine.submit(f"r{n}", prompts[f"r{n}"], new)
    got = drain(engine, set(prompts))
    for n, (_, new) in enumerate(lengths):
        tokens = got[f"r{n}"]
        assert len(tokens) == new
        assert deficits(model, prompts[f"r{n}"], tokens).max() < TOL
    report = engine.model_counters()
    assert report["loop_passes"] == cfg.passes * sum(
        new - 1 for _, new in lengths)  # the first token is the chunk's
    # a group is the whole table here: one sweep step a live row a pass
    assert report["loop_sweep_groups"] == report["loop_passes"]
    assert report["loop_chunk_rows"] == sum(p for p, _ in lengths)
    assert report["loop_exit_before_last"] == 0
    assert report["kv_bytes_per_token"] == cfg.passes * 3 * 2 * 4 * 16 * 4
    assert report["kv_pool_bytes"] == NUM_PAGES * PAGE * report["kv_bytes_per_token"]
    assert report["kv_pages_free"] == NUM_PAGES - 1


def test_engine_tokens_are_the_programs_argmax(model4):
    cfg, params, _ = model4
    prompt = prompt_ids(41)
    engine = make_engine(cfg, params)
    engine.submit("r", prompt, WINDOW + 1)
    tokens = run(engine, "r")
    got, *_ = serve_logits(cfg, params, prompt, tokens[:-1], slot=0)
    assert np.argmax(got[len(prompt) - 1 :], -1).tolist() == tokens


# -- (c) the pool: layers x passes entries a token ------------------------------


def test_every_pass_keeps_rows_of_its_own(model4):
    """The pool holds ``passes`` pools' worth of pages a layer; after a
    prefill and a window every (pass, layer) entry holds the reference's
    roped keys of that pass, and pass 0's rows of a token are not pass
    3's."""
    cfg, params, _ = model4
    pools = O.init_page_pool(cfg, NUM_PAGES, PAGE)
    assert len(pools) == cfg.layers and cfg.kv_entries == 12
    assert pools["0"]["k"].shape == (cfg.passes * NUM_PAGES, 4, PAGE, 16)
    assert O.page_pool_bytes(cfg, PAGE) == PAGE * cfg.kv_bytes_per_token
    prompt, emitted = prompt_ids(41), prompt_ids(WINDOW, seed=2)
    n = len(prompt) + len(emitted)
    *_, pools, _, pages = serve_logits(cfg, params, prompt, emitted)
    *_, rows = reference(model4, prompt + emitted, keep_rows=True)

    def held(t, layer):
        k, _ = O.entry_pages(pools, cfg, t, layer, pages)
        # [pages, KV, page, hd] -> [T, KV, hd]
        return np.asarray(k).transpose(0, 2, 1, 3).reshape(-1, 4, 16)[:n]

    for t in range(cfg.passes):
        for layer in range(cfg.layers):
            want = np.asarray(rows[t, layer])
            assert np.abs(held(t, layer) - want).max() < TOL
    apart = np.abs(held(0, 2) - held(3, 2)).max()
    assert apart > 1000 * TOL
    # and nothing was written outside the stream's pages and the null pages
    k0 = np.asarray(pools["0"]["k"]).reshape(cfg.passes, NUM_PAGES, -1)
    touched = {int(p) for p in np.nonzero(np.abs(k0).sum((0, 2)))[0]}
    assert touched <= {0, *map(int, pages)}


# -- (d) prefix cache, twins, preempt + resume, save + restore ----------------


def test_a_prefix_hit_and_a_twin_give_the_first_streams_tokens(model4):
    cfg, params, _ = model4
    prompt = prompt_ids(37)
    alone = make_engine(cfg, params)
    alone.submit("a", prompt, 11)
    want = run(alone, "a")
    engine = make_engine(cfg, params, prefix_cache=True)
    engine.submit("first", prompt, 11)
    assert run(engine, "first") == want
    # the same prompt again: its whole pages come from the prefix cache,
    # all passes' rows with them; and two twins side by side
    engine.submit("hit", prompt, 11)
    engine.submit("twin", prompt, 11)
    got = drain(engine, {"hit", "twin"})
    assert engine.prefix_cache.hits >= 2
    assert got["hit"] == want and got["twin"] == want


def test_preempt_and_resume_give_the_undisturbed_tokens(model4):
    cfg, params, _ = model4
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


def test_save_and_restore_pools_round_trip_a_stream_in_mid_decode(model4, tmp_path):
    cfg, params, _ = model4
    prompt = prompt_ids(21)
    whole = make_engine(cfg, params)
    whole.submit("r", prompt, 3 * WINDOW)
    want = run(whole, "r")
    first = make_engine(cfg, params)
    first.submit("pad", prompt_ids(5, 9), 2 * WINDOW)
    first.submit("r", prompt, 3 * WINDOW)
    head = []
    while len(head) < 1 + WINDOW:
        head += [tok for r, tok, _ in first.step() if r == "r"]
    snap = json.loads(json.dumps(first.checkpoint_state()))
    first.save_pools(tmp_path / "pools")
    second = make_engine(cfg, params)
    second.restore_pools(tmp_path / "pools")
    assert "r" in second.restore_state(snap)
    assert head + run(second, "r") == want
    # without the pages' contents the stream goes elsewhere
    blank = make_engine(cfg, params)
    blank.restore_state(snap)
    assert head + run(blank, "r") != want


# -- (e) the loader ------------------------------------------------------------


def test_the_loader_maps_every_tensor_of_the_checkpoint(tmp_path):
    ckpt = write_checkpoint(tmp_path / "ckpt", TINY)
    names = set(tensors(TINY))
    asked = []
    files = O.TensorFiles(ckpt)

    def get(name):
        asked.append(name)
        return jnp.asarray(files.get(name))

    cfg = O.OuroConfig.from_hf(TINY, MAX_SEQ)
    params = O.map_params(get, files.__contains__, cfg)
    assert set(asked) == names and len(asked) == len(names)
    blk = params["blocks"]["2"]
    assert blk["wqkv"]["int8"].shape == (64, 3 * 64)
    assert blk["wqkv"]["int8"].dtype == jnp.int8
    assert blk["w_gateup"]["int8"].shape == (64, 2 * 128)
    assert blk["w_down"]["int8"].shape == (128, 64)
    assert params["lm_head"]["int8"].shape == (64, VOCAB)
    assert params["gate_w"].shape == (64,) and params["gate_b"].shape == ()
    want = tensors(TINY)["model.layers.2.input_layernorm_2.weight"]
    assert np.array_equal(np.asarray(blk["attn_post_norm"]), want)
    # no float copy of a matrix is kept
    floats = [x for x in jax.tree.leaves(params["blocks"]) if x.ndim == 2
              and x.dtype != jnp.int8 and x.shape[0] > 1]
    assert not floats


@pytest.mark.parametrize("name", [
    "model.layers.1.input_layernorm_2.weight",
    "model.layers.0.post_attention_layernorm_2.weight",
    "model.early_exit_gate.weight", "model.early_exit_gate.bias",
    "model.norm.weight", "model.layers.2.mlp.up_proj.weight",
])
def test_the_loader_names_a_missing_tensor(tmp_path, name):
    ckpt = write_checkpoint(tmp_path / "ckpt", TINY, drop=(name,))
    with pytest.raises(KeyError, match=name.replace(".", r"\.")):
        O.load(ckpt, max_seq=MAX_SEQ)


def test_a_tied_checkpoint_takes_its_head_from_the_embedding(tmp_path):
    ckpt = write_checkpoint(tmp_path / "ckpt", TINY, drop=("lm_head.weight",))
    cfg, params = O.load(ckpt, max_seq=MAX_SEQ)
    hf, w = R.load(ckpt)
    prompt = prompt_ids(9)
    want, _ = R.forward(hf, w, jnp.asarray(prompt, jnp.int32))
    got, *_ = serve_logits(cfg, params, prompt, [])
    # the program's head is the embedding held to int8, the reference's is not
    assert np.abs(got - np.asarray(want)).max() < 0.2
    assert np.argmax(got[-1]) == np.argmax(np.asarray(want)[-1])


# -- (f) what is refused, by name ------------------------------------------------


@pytest.mark.parametrize("key,value", [
    ("early_exit_threshold", 0.9), ("early_exit_threshold", 0.5),
    ("use_sliding_window", True), ("sliding_window", 4096),
    ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("attention_bias", True), ("mlp_bias", True),
    ("layer_types", ["full_attention", "sliding_attention", "full_attention"]),
    ("hidden_act", "gelu"),
])
def test_settings_that_are_not_written_are_refused_by_name(key, value):
    with pytest.raises(NotImplementedError, match=key):
        O.OuroConfig.from_hf({**TINY, key: value}, MAX_SEQ)


def test_a_checkpoint_with_projection_biases_is_refused(tmp_path):
    bias = {"model.layers.0.self_attn.q_proj.bias": np.zeros(64, np.float32)}
    ckpt = write_checkpoint(tmp_path / "ckpt", TINY, extra=bias)
    with pytest.raises(NotImplementedError, match="q_proj.bias"):
        O.load(ckpt, max_seq=MAX_SEQ)


@pytest.mark.parametrize("knob", sorted(O.NOT_OFFERED))
def test_knobs_of_the_qwen_path_are_refused_by_name(model4, monkeypatch, knob):
    cfg, params, _ = model4
    monkeypatch.setenv(knob, "4" if knob == "DORA_SPEC_K" else "1")
    with pytest.raises(NotImplementedError, match=knob):
        make_engine(cfg, params)


def test_llm_server_knows_the_family():
    from dora_tpu.nodehub import llm_server

    assert llm_server.model_module("ouro") is O
    with pytest.raises(RuntimeError, match="ouro2"):
        llm_server.model_module("ouro2")


# -- (g) the gate, the pool's rule ------------------------------------------------


def test_no_token_leaves_before_the_last_pass_at_the_published_threshold(model4):
    _, lambdas = reference(model4, prompt_ids(30))
    lambdas = np.asarray(lambdas)
    assert lambdas.shape == (4, 30) and (0 < lambdas).all() and (lambdas < 1).all()
    assert (np.asarray(R.exit_step(jnp.asarray(lambdas), 1.0)) == 3).all()
    # a lower threshold lets some leave early: what the refusal is about
    early = np.asarray(R.exit_step(jnp.asarray(lambdas), 0.5))
    assert early.min() < 3 and early.max() <= 3
    first = lambdas[0] >= 0.5
    assert (early[first] == 0).all()


@pytest.mark.parametrize("limit,used,want", [
    # a 16 GB v5e after 2.77 GB of weights: (16.91 - 2.77 - 4.29) / 0.0252
    (16_909_336_064, 2_770_000_000, 384),
    (16_909_336_064, 2_950_000_000, 384),
    # more memory than 16 slots of 2048 rows can use: every slot to max_seq
    (64 << 30, 2_770_000_000, 2049),
    # hardly any: two streams' worth all the same
    (8 << 30, 2_770_000_000, 256),
])
def test_the_pools_default_size_is_a_rule_in_bytes(monkeypatch, limit, used, want):
    monkeypatch.setattr(O.L, "compute_dtype", lambda: jnp.bfloat16)  # the chip's
    hf = dict(TINY, hidden_size=2048, num_attention_heads=16,
              num_key_value_heads=16, head_dim=128, intermediate_size=5632,
              num_hidden_layers=48, vocab_size=49152,
              layer_types=["full_attention"] * 48)
    cfg = O.OuroConfig.from_hf(hf, 2048)
    assert cfg.kv_entries == 192 and cfg.kv_bytes_per_token == 1_572_864
    assert O.page_pool_bytes(cfg, 16) == 25_165_824
    assert PM.pages_that_fit(
        O.page_pool_bytes(cfg, 16), limit, used, 16, cfg.max_seq, 16,
        multiple=O.POOL_PAGE_MULTIPLE) == want
