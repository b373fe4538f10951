"""K-EXAONE on the paged serving path (models/hf/exaone_moe.py: window
layers as a per-slot ring, global layers in pages, ``kimi_k2``'s expert
layer) against its plain float32 reference
(models/hf/exaone_moe_reference.py: whole sequence, dense masks, no
cache), at tiny widths on the CPU, from seeded weights. Logits are
compared, not sampled tokens.

Tiny: window 8, page 8, chunk 32, 4 experts of which 2 are held, 5
layers ``LLLG L`` with layer 0 dense. On the CPU the serving path
computes in float32 too, so ``TOL`` is float32 summation order (the
ring's and the blocks' running softmax against a whole one, the int8
scales applied after the product or before): 2e-5 absolute on logits of
magnitude 4, five times what was measured (3.6e-6), and far under what
a flipped † switch or a missing mask moves (each asserted below: a
projection bias of 0.05 moves 1.44, rotary on the one global layer 1.48,
no QK-norm 2.5, post-norm 3.4, no band mask 4.0).
"""

from __future__ import annotations

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dora_tpu.models import paged_model as PM
from dora_tpu.models.hf import exaone_moe as E
from dora_tpu.models.hf import exaone_moe_reference as R
from dora_tpu.ops import decode_block as DB

TOL = 2e-5
WINDOW, PAGE, CHUNK, BLOCK, K_TICKS, SLOTS, MAX_SEQ = 8, 8, 32, 16, 4, 3, 128
KINDS = ["sliding_attention"] * 3 + ["full_attention", "sliding_attention"]

TINY = dict(
    model_type="exaone_moe", hidden_size=64, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=5, vocab_size=128,
    rms_norm_eps=1e-5, max_position_embeddings=MAX_SEQ,
    rope_parameters={"rope_theta": 1e6, "rope_type": "default"},
    layer_types=KINDS, sliding_window=WINDOW,
    mlp_layer_types=["dense"] + ["sparse"] * 4, first_k_dense_replace=1,
    num_experts=4, num_experts_per_tok=2, num_shared_experts=1,
    routed_scaling_factor=2.5, norm_topk_prob=True, scoring_func="sigmoid",
    n_group=1, topk_group=1, ep_size=2, tie_word_embeddings=False,
    num_nextn_predict_layers=0,
)


def write_checkpoint(path: Path, cfg: dict, seed: int = 0) -> None:
    """A whole (all experts) float32 checkpoint under the HF names."""
    from safetensors.numpy import save_file

    rng = np.random.default_rng(seed)
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    t: dict[str, np.ndarray] = {}

    def w(out, inp, scale=None):
        return (rng.standard_normal((out, inp)) * (scale or inp ** -0.5)
                ).astype(np.float32)

    def norm(n):
        return (1 + 0.1 * rng.standard_normal(n)).astype(np.float32)

    def ffn(prefix, width):
        t[prefix + "gate_proj.weight"] = w(width, d)
        t[prefix + "up_proj.weight"] = w(width, d)
        t[prefix + "down_proj.weight"] = w(d, width)

    t["model.embed_tokens.weight"] = w(cfg["vocab_size"], d, 1.0)
    t["model.norm.weight"] = norm(d)
    t["lm_head.weight"] = w(cfg["vocab_size"], d)
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        t[p + "input_layernorm.weight"] = norm(d)
        t[p + "post_attention_layernorm.weight"] = norm(d)
        a, m = p + "self_attn.", p + "mlp."
        t[a + "q_proj.weight"] = w(h * hd, d)
        t[a + "k_proj.weight"] = w(kv * hd, d)
        t[a + "v_proj.weight"] = w(kv * hd, d)
        t[a + "o_proj.weight"] = w(d, h * hd)
        t[a + "q_norm.weight"] = norm(hd)
        t[a + "k_norm.weight"] = norm(hd)
        if cfg["mlp_layer_types"][i] == "dense":
            ffn(m, cfg["intermediate_size"])
            continue
        t[m + "gate.weight"] = w(cfg["num_experts"], d)
        t[m + "gate.e_score_correction_bias"] = (
            0.1 * rng.standard_normal(cfg["num_experts"])).astype(np.float32)
        ffn(m + "shared_experts.", cfg["moe_intermediate_size"])
        for e in range(cfg["num_experts"]):
            ffn(f"{m}experts.{e}.", cfg["moe_intermediate_size"])
    path.mkdir(parents=True, exist_ok=True)
    save_file(t, str(path / "model.safetensors"))
    (path / "config.json").write_text(json.dumps(cfg))


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory) -> Path:
    path = tmp_path_factory.mktemp("exaone") / "ckpt"
    write_checkpoint(path, TINY)
    return path


@pytest.fixture(scope="module")
def model(ckpt):
    """Rank 0's share (experts 0-1 of 4): (cfg, params, reference params)."""
    cfg, params = E.load(ckpt, max_seq=MAX_SEQ, ep_rank=0)
    return cfg, params, R.reference_params(params, cfg)


def prompt_ids(n: int, seed: int = 1) -> list[int]:
    return np.random.default_rng(seed).integers(1, 128, size=n).tolist()


def make_engine(cfg, params, **kw):
    kw = {"max_slots": SLOTS, "page_size": PAGE, "chunk": CHUNK,
          "window": K_TICKS, "attn_block": BLOCK, **kw}
    return E.make_paged_engine(params, cfg, **kw)


def run(engine, rid) -> list[int]:
    """Step until ``rid`` is done; its tokens."""
    out = []
    for _ in range(300):
        for r, tok, done in engine.step():
            if r == rid:
                out.append(tok)
                if done:
                    return out
    raise AssertionError(f"{rid} never finished")


@functools.lru_cache(maxsize=None)
def programs(cfg):
    """The two programs as the engine jits them, but with logits where
    the greedy tokens would be (cfg is static; one trace a config)."""
    return (
        jax.jit(lambda p, *a: E.paged_chunk_logits(p, cfg, *a, block=BLOCK)),
        jax.jit(lambda p, *a: E.paged_batch_logits(p, cfg, *a)),
    )


class Served:
    """What the engine does, by hand, keeping the logits: pools, rings
    and counters of ``SLOTS`` slots, each stream with pages of its own."""

    def __init__(self, cfg, params, chunk: int = CHUNK, dirty: bool = True):
        self.cfg, self.params, self.chunk = cfg, params, chunk
        self.chunk_fn, self.tick_fn = programs(cfg)
        pages = SLOTS * MAX_SEQ // PAGE + 1
        self.pools = E.init_page_pool(cfg, pages, PAGE)
        self.state = E.init_slot_state(cfg, SLOTS)
        if dirty:  # an earlier stream's rows: no zero-start is needed
            self.state = jax.tree.map(lambda a: a + 3.0, self.state)
        self.stats = E.init_counters(cfg)
        per = MAX_SEQ // PAGE
        self.bts = np.zeros((SLOTS, per), np.int32)
        for b in range(SLOTS):
            self.bts[b] = 1 + b * per + np.arange(per)
        self.positions = np.zeros((SLOTS,), np.int32)

    def prefill(self, slot: int, prompt: list[int]):
        """Chunked prefill into ``slot``; the prompt's logits [T, vocab]."""
        out = []
        for base in range(0, len(prompt), self.chunk):
            piece = prompt[base : base + self.chunk]
            ids = piece + [0] * (self.chunk - len(piece))
            logits, self.pools, self.state, self.stats = self.chunk_fn(
                self.params, jnp.asarray(ids, jnp.int32), self.pools,
                self.state, self.stats, jnp.asarray(base, jnp.int32),
                jnp.asarray(self.bts[slot]), jnp.asarray(len(piece), jnp.int32),
                jnp.asarray(slot, jnp.int32))
            out.append(np.asarray(logits)[: len(piece)])
        self.positions[slot] = len(prompt)
        return np.concatenate(out)

    def tick(self, tokens: dict[int, int]):
        """One decode tick: ``tokens`` = slot -> its next input token;
        the other rows are frozen (position 0, zeroed table row). ->
        slot -> logits [vocab]."""
        active = np.zeros((SLOTS,), bool)
        toks = np.zeros((SLOTS,), np.int32)
        for b, tok in tokens.items():
            active[b], toks[b] = True, tok
        pos = np.where(active, self.positions, 0).astype(np.int32)
        bts = np.where(active[:, None], self.bts, 0).astype(np.int32)
        logits, self.pools, self.state, self.stats = self.tick_fn(
            self.params, jnp.asarray(toks), self.pools, self.state, self.stats,
            jnp.asarray(pos), jnp.asarray(bts), jnp.asarray(active))
        self.positions[active] += 1
        return {b: np.asarray(logits[b]) for b in tokens}

    def serve(self, slot: int, prompt: list[int], emitted: list[int]):
        """Prefill then teacher-forced decode: logits [T + E, vocab]."""
        rows = [self.prefill(slot, prompt)]
        for tok in emitted:
            rows.append(self.tick({slot: tok})[slot][None])
        return np.concatenate(rows)


def reference_logits(model, tokens, **switches):
    cfg, _, rp = model
    held = range(cfg.expert_first, cfg.expert_first + cfg.experts_held)
    return np.asarray(R.forward(rp, cfg, jnp.asarray(tokens), held=held,
                                **switches))


# -- (a) ring and pages against the whole forward pass -------------------------


@pytest.mark.parametrize("n,chunk", [
    (5, CHUNK),    # shorter than the window
    (8, CHUNK),    # exactly the window
    (37, CHUNK),   # wraps the ring four times, ends inside a revolution
    (64, CHUNK),   # the chunks' edges and the prompt's end on a wrap
    (75, CHUNK),   # nine revolutions, a ragged third chunk
    (45, 8),       # chunks of one window: every chunk replaces the ring
])
def test_chunked_prefill_then_decode_matches_the_reference(model, n, chunk):
    cfg, params, _ = model
    prompt, emitted = prompt_ids(n, seed=n), prompt_ids(11, seed=100 + n)
    got = Served(cfg, params, chunk).serve(1, prompt, emitted)
    want = reference_logits(model, prompt + emitted)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOL


def test_chunk_edges_inside_a_revolution(ckpt):
    """Window 16 over chunks of 8 rows (a page): three chunk edges in
    four fall inside a revolution of the ring, and a chunk never holds a
    whole window."""
    wide = ckpt.with_name("ckpt-w16")
    wide.mkdir(exist_ok=True)
    (wide / "model.safetensors").symlink_to(ckpt / "model.safetensors")
    (wide / "config.json").write_text(json.dumps({**TINY, "sliding_window": 16}))
    cfg, params = E.load(wide, max_seq=MAX_SEQ, ep_rank=0)
    model = (cfg, params, R.reference_params(params, cfg))
    prompt, emitted = prompt_ids(43, seed=7), prompt_ids(9, seed=8)
    got = Served(cfg, params, chunk=8).serve(2, prompt, emitted)
    assert np.abs(got - reference_logits(model, prompt + emitted)).max() < TOL


def test_a_short_and_a_long_stream_decode_in_one_window(model):
    """Rows of one tick at positions below the window and several times
    past it: the ring's mask is the row's own, the global layer's sweep
    fetches each row's own pages and none for the frozen slot."""
    cfg, params, _ = model
    short, long_ = prompt_ids(4, seed=21), prompt_ids(70, seed=22)
    follow = {0: prompt_ids(10, seed=23), 2: prompt_ids(10, seed=24)}
    served = Served(cfg, params)
    served.prefill(0, short)
    served.prefill(2, long_)
    got = {0: [], 2: []}
    for k in range(10):
        rows = served.tick({b: follow[b][k] for b in follow})
        for b in follow:
            got[b].append(rows[b])
    for b, prompt in ((0, short), (2, long_)):
        want = reference_logits(model, prompt + follow[b])[len(prompt):]
        assert np.abs(np.stack(got[b]) - want).max() < TOL
    swa = {k: int(v) for k, v in served.stats["swa"].items()}
    assert swa["swa_decode_ticks"] == 10 and swa["swa_row_ticks"] == 20
    # rows attended: sum over ticks and live rows of position + 1 (x 1
    # global layer); the ring's: min(position + 1, window) (x 4 layers)
    pos = [4 + k for k in range(10)] + [70 + k for k in range(10)]
    assert swa["global_kv_rows_read"] == sum(p + 1 for p in pos)
    assert swa["swa_ring_rows_read"] == 4 * sum(min(p + 1, WINDOW) for p in pos)
    # rows fetched: the sweep's (row, group) steps, a group of 128 cache
    # rows each (x 1 global layer): every live row's own, to its own count
    group = DB.sweep_group_rows(PAGE, MAX_SEQ // PAGE)
    assert group == 128
    assert swa["global_sweep_groups"] == sum(-(-(p + 1) // group) for p in pos)
    assert swa["global_kv_rows_swept"] == group * swa["global_sweep_groups"]
    assert (swa["global_kv_rows_swept"] / swa["global_kv_rows_read"]
            <= 1 + (group - 1) / (min(pos) + 1))
    assert swa["swa_chunks"] == 1 + 3 and swa["swa_chunk_rows"] == 74
    assert swa["swa_chunk_positions"] == 0 + 0 + 32 + 64


def test_engine_tokens_are_the_references_argmax(model):
    """Through ``PagedBatchEngine`` itself (scheduler, allocator, K-tick
    window, greedy head): every emitted token is the top of the
    reference's teacher-forced logits, or within TOL of it."""
    cfg, params, _ = model
    engine = make_engine(cfg, params)
    prompts = {"a": prompt_ids(6, 31), "b": prompt_ids(50, 32),
               "c": prompt_ids(33, 33)}
    for rid, prompt in prompts.items():
        engine.submit(rid, prompt, 13)
    out = {rid: [] for rid in prompts}
    for _ in range(200):
        for rid, tok, _done in engine.step():
            out[rid].append(tok)
        if not engine.active:
            break
    for rid, prompt in prompts.items():
        assert len(out[rid]) == 13
        want = reference_logits(model, prompt + out[rid])[len(prompt) - 1 : -1]
        chosen = want[np.arange(13), out[rid]]
        assert (want.max(-1) - chosen).max() < TOL
    report = engine.model_counters()
    assert report["kv_bytes_per_token"] == 1 * 2 * 32 * 4  # one global layer, f32
    assert report["swa_ring_bytes"] == SLOTS * 4 * WINDOW * 2 * 32 * 4
    assert report["moe_tokens"] > 0 and len(report["moe_expert_tokens"]) == 2
    assert set(engine.pools) == {"3"} and set(engine.slot_state) == set("0124")


# -- (b) each † switch, flipped, fails the same limit ---------------------------


def _with_bias(model):
    """The reference's parameters with a small seeded bias on every
    layer's projections (the program has none to add)."""
    cfg, params, rp = model
    rng = np.random.default_rng(5)
    width = cfg.q_width + 2 * cfg.kv_width
    blocks = {i: {**r, "qkv_bias": jnp.asarray(
        0.05 * rng.standard_normal(width), jnp.float32)}
        for i, r in rp["blocks"].items()}
    return cfg, params, {**rp, "blocks": blocks}


@pytest.mark.parametrize("switch,value", [
    ("post_norm", True), ("qkv_bias", True), ("qk_norm", False),
    ("rope_on_global", True), ("full_everywhere", True),
])
def test_a_flipped_switch_fails_the_tolerance(model, switch, value):
    cfg, params, _ = model
    prompt, emitted = prompt_ids(37, seed=37), prompt_ids(11, seed=137)
    got = Served(cfg, params).serve(1, prompt, emitted)
    ref = _with_bias(model) if switch == "qkv_bias" else model
    assert np.abs(got - reference_logits(ref, prompt + emitted)).max() < TOL
    flipped = reference_logits(ref, prompt + emitted, **{switch: value})
    assert np.abs(got - flipped).max() > 0.5


def test_unknown_switches_are_refused(model):
    with pytest.raises(TypeError, match="no_such"):
        reference_logits(model, [1, 2, 3], no_such=True)


# -- (c) the shares add up ------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer(ckpt):
    """The routed parts of all ``ep_size`` shares plus the shared expert
    once equal the uncut reference's expert layer, in the program
    (``kimi_k2.mlp`` under this config) and in the reference."""
    from dora_tpu.models import moe as K

    x = jnp.asarray(np.random.default_rng(3).standard_normal((24, 64)),
                    jnp.float32)
    live = jnp.ones((24,), bool)
    parts, shared = [], None
    for rank in range(2):
        cfg, params = E.load(ckpt, max_seq=MAX_SEQ, ep_rank=rank)
        blk = params["blocks"]["1"]
        both, _ = K.mlp(blk, cfg, x, live, live)
        shared = K.swiglu(blk["shared"], x)
        parts.append(np.asarray(both - shared))
        rp = R.reference_params(params, cfg)["blocks"]["1"]
        mine = range(cfg.expert_first, cfg.expert_first + cfg.experts_held)
        with jax.default_matmul_precision("highest"):
            assert np.abs(np.asarray(R.moe(rp, cfg, x, mine)) - np.asarray(both)
                          ).max() < TOL
    whole_dir = ckpt.with_name("ckpt-ep1")
    whole_dir.mkdir(exist_ok=True)
    (whole_dir / "model.safetensors").symlink_to(ckpt / "model.safetensors")
    (whole_dir / "config.json").write_text(json.dumps({**TINY, "ep_size": 1}))
    cfg, params = E.load(whole_dir, max_seq=MAX_SEQ)
    assert (cfg.expert_first, cfg.experts_held) == (0, 4)
    rp = R.reference_params(params, cfg)["blocks"]["1"]
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(R.moe(rp, cfg, x))
    assert np.abs(sum(parts) + np.asarray(shared) - whole).max() < TOL
    assert np.abs(parts[0]).max() > 0.01 and np.abs(parts[1]).max() > 0.01


# -- (d) what must leave ring and pool untouched --------------------------------


def test_padding_rows_and_frozen_rows_leave_ring_and_pool_untouched(model):
    cfg, params, _ = model
    served = Served(cfg, params)
    served.prefill(0, prompt_ids(20, seed=41))
    before_rings = jax.tree.map(np.asarray, served.state)
    before_pool = np.asarray(served.pools["3"]["kv"])
    # a ragged chunk into slot 1: 5 valid rows of 32
    served.prefill(1, prompt_ids(5, seed=42))
    for key, ring in served.state.items():
        ring = np.asarray(ring["kv"])
        was = before_rings[key]["kv"]
        assert (ring[0] == was[0]).all() and (ring[2] == was[2]).all()
        # rows 0-4 written, rows 5-7 (padding's positions) as they were
        assert (ring[1, 5:] == was[1, 5:]).all()
        assert (ring[1, :5] != was[1, :5]).any(-1).all()
    # slot 1's pages took the chunk; slot 0's and slot 2's did not move
    pool = np.asarray(served.pools["3"]["kv"])
    mine = set(served.bts[1].tolist())
    others = [p for p in range(1, pool.shape[0]) if p not in mine]
    assert (pool[others] == before_pool[others]).all()
    # a window in which slot 0 is frozen: bit-identical rings, its pages too
    rings = jax.tree.map(np.asarray, served.state)
    pool = np.asarray(served.pools["3"]["kv"])
    for tok in (9, 10, 11):
        served.tick({1: tok})
    for key, ring in served.state.items():
        ring = np.asarray(ring["kv"])
        assert (ring[0] == rings[key]["kv"][0]).all()
        assert (ring[2] == rings[key]["kv"][2]).all()
        assert (ring[1, 5:8] != rings[key]["kv"][1, 5:8]).any(-1).all()
    after = np.asarray(served.pools["3"]["kv"])
    zero = [int(p) for p in served.bts[0]] + [int(p) for p in served.bts[2]]
    assert (after[zero] == pool[zero]).all()


# -- (e) preempt, save and restore carry the ring -------------------------------


def test_preempt_and_resume_give_the_first_streams_tokens(model):
    cfg, params, _ = model
    prompt = prompt_ids(29, seed=51)
    want = run_one(make_engine(cfg, params), prompt, 14)
    engine = make_engine(cfg, params)
    engine.submit("r", prompt, 14)
    head = []
    while len(head) < 5:
        head += [tok for _r, tok, _d in engine.step()]
    engine.preempt("r")
    assert engine.active == 0
    # another stream dirties the slot's rings, then the first comes back
    assert len(run_one(engine, prompt_ids(40, seed=52), 9, "other")) == 9
    assert run_one(engine, prompt, 14) == want


def test_chunks_ahead_of_their_period_give_the_tokens_of_step(model):
    """``dispatch → ahead → collect`` against ``step()`` on the MoE
    model whose window layers keep a ring a slot: multi-chunk prompts
    whose chunks go behind windows that step other rows' rings and
    pages give the tokens they give in line, and a stream preempted
    between ``ahead()`` and its chunk's adoption comes back to them."""
    cfg, params, _ = model
    prompts = {"a": prompt_ids(21, seed=61), "long": prompt_ids(45, seed=62),
               "b": prompt_ids(9, seed=63)}
    caps = {"a": 9, "long": 14, "b": 6}

    engine = make_engine(cfg, params)

    def serve(halves: bool):
        ran, ahead = engine.chunks_run, engine.chunks_ahead
        for rid, prompt in prompts.items():
            engine.submit(rid, prompt, caps[rid])
        got = {rid: [] for rid in prompts}
        preempted = False
        for _ in range(300):
            if not engine.active:
                break
            if halves:
                out = engine.dispatch()
                engine.ahead()
                out += engine.collect()
                went = engine._ahead
                if not preempted and went is not None and went[0].request_id == "b":
                    # b's chunk is on the device and its stream goes:
                    # the chunk wrote a ring and pages nobody holds
                    assert engine.preempt("b") is not None
                    engine.check_invariants()
                    engine.submit("b", prompts["b"], caps["b"])
                    preempted = True
            else:
                out = engine.step()
            for rid, tok, _done in out:
                got[rid].append(tok)
        assert halves == preempted
        engine.check_invariants()
        return got, engine.chunks_run - ran, engine.chunks_ahead - ahead

    # the same engine, so the same two programs: in line, then ahead
    want, line_chunks, line_ahead = serve(False)
    got, chunks, ahead = serve(True)
    assert got == want and [len(got[r]) for r in caps] == list(caps.values())
    assert line_ahead == 0 and ahead >= 4
    # b's chunk ran twice: once for the stream that went, once again
    assert chunks == line_chunks + 1


def run_one(engine, prompt, max_new, rid="r") -> list[int]:
    engine.submit(rid, prompt, max_new)
    return run(engine, rid)


def test_checkpoint_restore_round_trips_a_stream_in_mid_decode(model, tmp_path):
    cfg, params, _ = model
    prompt = prompt_ids(43, seed=61)
    want = run_one(make_engine(cfg, params), prompt, 18)
    engine = make_engine(cfg, params)
    engine.submit("r", prompt, 18)
    head = []
    while len(head) < 6:
        head += [tok for _r, tok, _d in engine.step()]
    snap = engine.checkpoint_state()
    assert snap["slot_state"] is True
    engine.save_pools(tmp_path / "pools")
    fresh = make_engine(cfg, params)
    fresh.restore_pools(tmp_path / "pools")
    fresh.restore_state(snap)
    assert head + run(fresh, "r") == want
    # without the rings the stream goes elsewhere: the rings are carried
    blank = make_engine(cfg, params)
    blank.restore_pools(tmp_path / "pools")
    blank.slot_state = E.init_slot_state(cfg, SLOTS)
    blank.restore_state(snap)
    assert head + run(blank, "r") != want


# -- (f) the refusals by name ----------------------------------------------------


@pytest.mark.parametrize("knob", sorted(E.NOT_OFFERED))
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
    ({"scoring_func": "softmax"}, NotImplementedError, "scoring_func"),
    ({"n_group": 2}, NotImplementedError, "n_group"),
    ({"layer_types": KINDS[:4]}, ValueError, "layer_types"),
    ({"layer_types": ["chunked_attention"] * 5}, NotImplementedError,
     "chunked_attention"),
    ({"mlp_layer_types": ["dense"] * 4}, ValueError, "mlp_layer_types"),
    ({"attention_bias": True}, NotImplementedError, "attention_bias"),
    ({"rope_parameters": {"rope_type": "yarn", "rope_theta": 1e6}},
     NotImplementedError, "rotary"),
    ({"model_type": "exaone4"}, ValueError, "exaone4"),
    ({"ep_size": 3}, ValueError, "ep_size"),
])
def test_unsupported_variants_are_refused_by_name(change, error, match):
    with pytest.raises(error, match=match):
        E.ExaoneMoeConfig.from_hf({**TINY, **change})


def test_a_window_that_is_no_multiple_of_the_page_is_refused(model):
    cfg, params, _ = model
    with pytest.raises(NotImplementedError, match="sliding_window 8"):
        make_engine(cfg, params, page_size=16, chunk=32)


def test_first_k_dense_replace_stands_in_for_mlp_layer_types():
    config = {k: v for k, v in TINY.items() if k != "mlp_layer_types"}
    cfg = E.ExaoneMoeConfig.from_hf(config)
    assert cfg.sparse == (False, True, True, True, True)
    assert cfg.sliding == (True, True, True, False, True)
    assert (cfg.window_layers, cfg.global_layers) == ((0, 1, 2, 4), (3,))


def test_expert_share_rank_from_the_launcher(monkeypatch):
    monkeypatch.setenv("DORA_EP_RANK", "1")
    cfg = E.ExaoneMoeConfig.from_hf(TINY)
    assert (cfg.expert_first, cfg.experts_held, cfg.n_experts) == (2, 2, 4)


def test_llm_server_knows_the_family(ckpt):
    from dora_tpu.models.hf.loader import read_config
    from dora_tpu.nodehub import llm_server

    module = llm_server.model_module(read_config(ckpt)["model_type"])
    assert module is E
    with pytest.raises(RuntimeError, match="exaone_moe") as err:
        llm_server.model_module("exaone4")
    assert "exaone4" in str(err.value)


def test_the_pool_rule_in_bytes(model):
    """16 slots x 16,384 rows of 8,192 B fit a v5e beside 6.1 GB of
    weights; a smaller device caps the pool."""
    cfg, _, _ = model
    big = E.ExaoneMoeConfig.from_hf(
        {**TINY, "hidden_size": 6144, "num_attention_heads": 64,
         "num_key_value_heads": 8, "head_dim": 128,
         "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2,
         "mlp_layer_types": ["dense"] + ["sparse"] * 7,
         "num_hidden_layers": 8, "sliding_window": 128}, max_seq=16384)
    token = big.kv_bytes_per_token  # float32 on the CPU: twice bf16's 8,192
    assert token == 2 * 2 * 1024 * jnp.dtype(E.L.compute_dtype()).itemsize
    limit, used = 16_909_336_064, 6_200_000_000
    def fit(limit, used):
        return PM.pages_that_fit(E.page_pool_bytes(big, 16), limit, used, 16,
                                 big.max_seq, 16)

    assert fit(limit, used) == min(
        16 * 16384 // 16 + 1, (limit - used - (4 << 30)) // (16 * token))
    assert fit(8 << 30, 3 << 30) == (1 << 30) // (16 * token)
    assert fit(8 << 30, 6 << 30) == 2 * 16384 // 16


# (g) no weight is copied or closed over in the two programs:
# tests/test_backend.py walks them ("exaone_moe" in its table of engines)
