"""ZAYA1 on the paged serving path (models/hf/zaya.py: attention inside a
convolved, compressed latent whose convolution and value-shift tails are
slot state beside one leaf of pages a layer, under a top-1 MLP router
that carries its state from layer to layer) against its plain float32
reference (models/hf/zaya_reference.py: whole sequence, padded sums, no
cache, no tails), at tiny widths on the CPU, from seeded weights. Logits
are compared, not sampled tokens.

Tiny: 8 query / 2 K/V heads of 16 (the published 4 query heads a K/V
head), rotary on the first 8 dimensions, 16 experts of width 32, one a
token, ``router_hidden_size`` 32, ``cca_time0`` = ``cca_time1`` = 2, 3
layers, page 8, chunk 32. On the CPU the serving path computes in float32
too, so ``TOL`` is float32 summation order (the blocks' running softmax
against a whole one, the int8 scales applied after the product or
before, the convolution over a tail against one over the sequence): 2e-4
absolute on logits of standard deviation 3.5 (the largest 15), five times
the largest measured over this file's cases (4.3e-5), and far under what
a lower precision, a flipped † switch or a control moves (each asserted
below; measured on the 37 + 11 token case: the convolutions in bfloat16
move the logits by 0.16, the pages through 8 bits by 0.12, the router in
bfloat16 by 5.5 because it flips picks, the least of the switches,
``tau_linear``, by 5.5, ``no_conv`` by 13).
"""

from __future__ import annotations

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dora_tpu.models import moe
from dora_tpu.models import paged_model as PM
from dora_tpu.models.hf import zaya as Z
from dora_tpu.models.hf import zaya_reference as R
from dora_tpu.ops import decode_block as DB

TOL = 2e-4
#: two biased probabilities closer than this may be picked either way
#: (float32 summation order in the router's inputs)
PICK_MARGIN = 1e-4
PAGE, CHUNK, BLOCK, K_TICKS, SLOTS, MAX_SEQ = 8, 32, 16, 4, 3, 128

TINY = dict(
    model_type="zaya", hidden_size=128, num_attention_heads=8,
    num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
    num_hidden_layers=3, layer_types=["hybrid"] * 3, vocab_size=128,
    rms_norm_eps=1e-5, max_position_embeddings=MAX_SEQ,
    partial_rotary_factor=0.5, cca_time0=2, cca_time1=2,
    rope_parameters={
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "rope_type": "default"},
    num_experts=16, num_experts_per_tok=1, router_hidden_size=32,
    hidden_act="silu", attention_bias=False, lm_head_bias=False,
    sliding_window=None, tie_word_embeddings=True,
)


def write_checkpoint(path: Path, cfg: dict, seed: int = 0) -> None:
    """A whole (all experts) float32 checkpoint under the tensor names
    ``zaya.load_layer`` reads."""
    from safetensors.numpy import save_file

    rng = np.random.default_rng(seed)
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    r, e = cfg["router_hidden_size"], cfg["num_experts"]
    width = (h + kv) * hd
    t: dict[str, np.ndarray] = {}

    def w(*shape, scale=None):
        return (rng.standard_normal(shape) * (scale or shape[-1] ** -0.5)
                ).astype(np.float32)

    def near(value, n, spread=0.1):
        return (value + spread * rng.standard_normal(n)).astype(np.float32)

    t["model.embed_tokens.weight"] = w(cfg["vocab_size"], d, scale=0.3)
    t["model.norm.weight"] = near(1, d)
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        a, m = p + "self_attn.", p + "mlp."
        t[p + "input_layernorm.weight"] = near(1, d)
        t[p + "post_attention_layernorm.weight"] = near(1, d)
        t[a + "q_proj.weight"] = w(h * hd, d)
        t[a + "k_proj.weight"] = w(kv * hd, d)
        t[a + "v_proj1.weight"] = w(hd, d)
        t[a + "v_proj2.weight"] = w(hd, d)
        t[a + "o_proj.weight"] = w(d, h * hd)
        t[a + "conv_qk.0.weight"] = w(width, 1, 2, scale=0.7)
        t[a + "conv_qk.0.bias"] = near(0, width)
        t[a + "conv_qk.1.weight"] = w(width, hd, 2, scale=(2 * hd) ** -0.5)
        t[a + "conv_qk.1.bias"] = near(0, width)
        t[a + "temp"] = near(0, kv)
        for at in ("attn", "mlp"):
            # off 1 and 0, so that leaving them out shows
            t[f"{p}{at}_residual.residual_bias"] = near(0, d)
            t[f"{p}{at}_residual.residual_scale"] = near(1, d, 0.2)
            t[f"{p}{at}_residual.hidden_bias"] = near(0, d)
            t[f"{p}{at}_residual.hidden_scale"] = near(1, d, 0.2)
        t[m + "router.down_proj.weight"] = w(r, d)
        t[m + "router.down_proj.bias"] = near(0, r)
        t[m + "router.state_scale"] = near(0.5, r)
        t[m + "router.norm.weight"] = near(1, r)
        t[m + "router.mlp.0.weight"] = w(r, r, scale=2 * r ** -0.5)
        t[m + "router.mlp.0.bias"] = near(0, r)
        t[m + "router.mlp.1.weight"] = w(r, r, scale=2 * r ** -0.5)
        t[m + "router.mlp.1.bias"] = near(0, r)
        t[m + "router.mlp.2.weight"] = w(e, r, scale=3 * r ** -0.5)
        t[m + "router.balancing_bias"] = near(0, e, 0.01)
        for n in range(e):
            t[f"{m}experts.{n}.gate_proj.weight"] = w(cfg["moe_intermediate_size"], d)
            t[f"{m}experts.{n}.up_proj.weight"] = w(cfg["moe_intermediate_size"], d)
            t[f"{m}experts.{n}.down_proj.weight"] = w(d, cfg["moe_intermediate_size"])
    path.mkdir(parents=True, exist_ok=True)
    save_file(t, str(path / "model.safetensors"))
    (path / "config.json").write_text(json.dumps(cfg))


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory) -> Path:
    path = tmp_path_factory.mktemp("zaya") / "ckpt"
    write_checkpoint(path, TINY)
    return path


@pytest.fixture(scope="module")
def model(ckpt):
    """(cfg, params, reference params): every expert held."""
    cfg, params = Z.load(ckpt, max_seq=MAX_SEQ)
    return cfg, params, R.reference_params(params, cfg)


def prompt_ids(n: int, seed: int = 1) -> list[int]:
    return np.random.default_rng(seed).integers(1, 128, size=n).tolist()


def make_engine(cfg, params, **kw):
    kw = {"max_slots": SLOTS, "page_size": PAGE, "chunk": CHUNK,
          "window": K_TICKS, "attn_block": BLOCK, **kw}
    return Z.make_paged_engine(params, cfg, **kw)


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


def run_one(engine, prompt, max_new, rid="r") -> list[int]:
    engine.submit(rid, prompt, max_new)
    return run(engine, rid)


@functools.lru_cache(maxsize=None)
def programs(cfg):
    """The two programs as the engine jits them, but with logits where
    the greedy tokens would be (cfg is static; one trace a config)."""
    return (
        jax.jit(lambda p, *a: Z.paged_chunk_logits(p, cfg, *a, block=BLOCK)),
        jax.jit(lambda p, *a: Z.paged_batch_logits(p, cfg, *a)),
    )


class Served:
    """What the engine does, by hand, keeping the logits: pools, tails
    and counters of ``SLOTS`` slots, each stream with pages of its own.
    ``dirty``: the tails start as an earlier stream left them."""

    def __init__(self, cfg, params, chunk: int = CHUNK, dirty: bool = True):
        self.cfg, self.params, self.chunk = cfg, params, chunk
        self.chunk_fn, self.tick_fn = programs(cfg)
        pages = SLOTS * MAX_SEQ // PAGE + 1
        self.pools = Z.init_page_pool(cfg, pages, PAGE)
        self.state = Z.init_slot_state(cfg, SLOTS)
        if dirty:
            self.state = jax.tree.map(lambda a: a + 3.0, self.state)
        self.stats = Z.init_counters(cfg)
        per = MAX_SEQ // PAGE
        self.bts = np.zeros((SLOTS, per), np.int32)
        for b in range(SLOTS):
            self.bts[b] = 1 + b * per + np.arange(per)
        self.positions = np.zeros((SLOTS,), np.int32)
        self.picked: dict[int, list] = {b: [] for b in range(SLOTS)}

    def prefill(self, slot: int, prompt: list[int]):
        """Chunked prefill into ``slot``; the prompt's logits [T, vocab]."""
        out = []
        for base in range(0, len(prompt), self.chunk):
            piece = prompt[base : base + self.chunk]
            ids = piece + [0] * (self.chunk - len(piece))
            logits, self.pools, self.state, self.stats, look = self.chunk_fn(
                self.params, jnp.asarray(ids, jnp.int32), self.pools,
                self.state, self.stats, jnp.asarray(base, jnp.int32),
                jnp.asarray(self.bts[slot]), jnp.asarray(len(piece), jnp.int32),
                jnp.asarray(slot, jnp.int32))
            out.append(np.asarray(logits)[: len(piece)])
            self.picked[slot] += np.asarray(look["expert"]).T[: len(piece)].tolist()
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
        logits, self.pools, self.state, self.stats, look = self.tick_fn(
            self.params, jnp.asarray(toks), self.pools, self.state, self.stats,
            jnp.asarray(pos), jnp.asarray(bts), jnp.asarray(active))
        self.positions[active] += 1
        for b in tokens:
            self.picked[b].append(np.asarray(look["expert"])[:, b].tolist())
        return {b: np.asarray(logits[b]) for b in tokens}

    def serve(self, slot: int, prompt: list[int], emitted: list[int]):
        """Prefill then teacher-forced decode: logits [T + E, vocab]."""
        rows = [self.prefill(slot, prompt)]
        for tok in emitted:
            rows.append(self.tick({slot: tok})[slot][None])
        return np.concatenate(rows)

    def rows(self, slot: int, layer: int, n: int):
        """The first ``n`` cached K|V rows of ``slot`` at ``layer``."""
        pool = np.asarray(self.pools[str(layer)]["kv"])
        return pool[self.bts[slot]].reshape(-1, pool.shape[-1])[:n]

    def tail(self, slot: int, layer: int):
        st = self.state[str(layer)]
        return np.asarray(st["c"][slot]), np.asarray(st["v"][slot])


def reference(model, tokens, **switches):
    cfg, _, rp = model
    logits, kept = R.forward(rp, cfg, jnp.asarray(tokens), rows=True, **switches)
    return np.asarray(logits), kept


def reference_logits(model, tokens, **switches):
    return reference(model, tokens, **switches)[0]


def reference_rows(cfg, kept, layer: int):
    """The reference's K|V rows ``[T, 2 * KV * hd]`` as a page holds them."""
    k, v = kept[layer]["k"], kept[layer]["v"]
    t = k.shape[0]
    return np.concatenate([np.asarray(k).reshape(t, -1),
                           np.asarray(v).reshape(t, -1)], -1)


# -- (a) tails and pages against the whole forward pass ------------------------


@pytest.mark.parametrize("n,chunk", [
    (1, CHUNK),    # one row: both convolutions see the padding alone
    (2, CHUNK),    # the tail half padding, half prompt
    (5, CHUNK),    # inside a page
    (32, CHUNK),   # exactly a chunk: the tail is its last two rows
    (37, CHUNK),   # a ragged second chunk
    (75, CHUNK),   # three chunks, the last ragged
    (45, 8),       # chunks of one page: every chunk's tail crosses an edge
])
def test_chunked_prefill_then_decode_matches_the_reference(model, n, chunk):
    cfg, params, _ = model
    prompt, emitted = prompt_ids(n, seed=n), prompt_ids(11, seed=100 + n)
    served = Served(cfg, params, chunk)
    got = served.serve(1, prompt, emitted)
    want, kept = reference(model, prompt + emitted)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOL
    # the pages hold the reference's (k'', v) rows, the tail its c rows
    # and Wv2 h of the last position run (the last emitted token's)
    total = n + len(emitted)
    for layer in range(cfg.layers):
        assert np.abs(served.rows(1, layer, total)
                      - reference_rows(cfg, kept, layer)).max() < TOL
        c, v = served.tail(1, layer)
        want_c = np.asarray(kept[layer]["c"])
        want_c = np.concatenate([np.zeros((2, want_c.shape[1])), want_c])
        assert np.abs(c - want_c[total : total + 2]).max() < TOL
        assert np.abs(v - np.asarray(kept[layer]["v2"])[total - 1]).max() < TOL


@pytest.mark.parametrize("off", [1, 2, 3])
@pytest.mark.parametrize("side", [-1, 1])
def test_a_chunk_boundary_at_every_offset_from_a_page_edge(model, off, side):
    """Chunks of one page over a prompt that ends ``off`` rows before or
    after a page edge: the ragged chunk's tail is read after its last
    VALID row, and the decode ticks that follow step it across the edge."""
    cfg, params, _ = model
    n = 3 * PAGE + side * off
    prompt, emitted = prompt_ids(n, seed=40 + n), prompt_ids(7, seed=140 + n)
    served = Served(cfg, params, chunk=PAGE)
    got = served.serve(2, prompt, emitted)
    want, kept = reference(model, prompt + emitted)
    assert np.abs(got - want).max() < TOL
    for layer in (0, cfg.layers - 1):
        assert np.abs(served.rows(2, layer, n + 7)
                      - reference_rows(cfg, kept, layer)).max() < TOL


def test_the_tail_after_a_ragged_chunk_is_the_last_valid_rows(model):
    cfg, params, _ = model
    prompt = prompt_ids(37, seed=3)
    served = Served(cfg, params)
    served.prefill(0, prompt)
    _, kept = reference(model, prompt)
    for layer in range(cfg.layers):
        c, v = served.tail(0, layer)
        assert np.abs(c - np.asarray(kept[layer]["c"])[35:37]).max() < TOL
        assert np.abs(v - np.asarray(kept[layer]["v2"])[36]).max() < TOL


def test_streams_at_different_positions_decode_in_one_window(model):
    """Rows of one tick at position 4 and at position 70: each steps its
    own tail, the sweep fetches each row's own pages and none for the
    frozen slot; the counters count what ran."""
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
    cca = {k: int(v) for k, v in served.stats["cca"].items()}
    layers = cfg.layers
    assert cca["cca_decode_ticks"] == 10
    assert cca["cca_row_ticks"] == 20 * layers
    pos = [4 + k for k in range(10)] + [70 + k for k in range(10)]
    assert cca["cca_kv_rows_read"] == layers * sum(p + 1 for p in pos)
    group = DB.sweep_group_rows(PAGE, MAX_SEQ // PAGE)
    assert group == 128
    assert cca["cca_kv_rows_swept"] == layers * group * sum(
        -(-(p + 1) // group) for p in pos)
    # a tail is written by every live row of a tick and once by a chunk
    assert cca["cca_tail_steps"] == layers * (20 + 4)
    assert cca["cca_chunks"] == 1 + 3 and cca["cca_chunk_rows"] == 74
    assert cca["cca_chunk_positions"] == 0 + 0 + 32 + 64
    assert cca["cca_zero_starts"] == 2
    # every expert is held: every routed token lands, one pair a token
    moe_stats = served.stats["moe"]
    assert int(moe_stats["tokens"]) == int(moe_stats["local_pairs"]) == (
        layers * (74 + 20))
    assert int(np.asarray(moe_stats["expert_tokens"]).sum()) == layers * 94


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
    assert report["kv_bytes_per_token"] == 3 * 2 * 32 * 4  # 3 layers, f32
    assert report["cca_tail_bytes"] == SLOTS * 3 * (2 * 160 + 16) * 4
    assert report["moe_tokens"] == report["moe_local_pairs"] > 0
    assert len(report["moe_expert_tokens"]) == 16
    assert set(engine.pools) == set(engine.slot_state) == set("012")
    assert engine.pools["0"]["kv"].shape[1:] == (PAGE, 64)
    assert engine.slot_state["0"]["c"].shape == (SLOTS, 2, 160)
    assert engine.slot_state["0"]["v"].shape == (SLOTS, 16)
    assert engine.selection["window"]["expert"].shape == (K_TICKS, 3, SLOTS)


def test_top1_picks_are_the_references_outside_the_margin(model):
    """Chunk rows and decode ticks pick the reference's expert at every
    layer wherever the reference's two best biased probabilities differ
    by more than ``PICK_MARGIN``; most rows are outside it."""
    cfg, params, _ = model
    prompt, emitted = prompt_ids(45, seed=71), prompt_ids(9, seed=72)
    served = Served(cfg, params)
    served.serve(1, prompt, emitted)
    _, kept = reference(model, prompt + emitted)
    got = np.asarray(served.picked[1])  # [T, layers]
    clear = 0
    for layer in range(cfg.layers):
        best = np.sort(np.asarray(kept[layer]["biased"]), -1)
        decided = best[:, -1] - best[:, -2] > PICK_MARGIN
        want = np.asarray(kept[layer]["expert"])
        assert (got[decided, layer] == want[decided]).all()
        clear += decided.sum()
    assert clear >= 0.95 * got.size
    assert len(np.unique(got)) >= 8  # the seeded routers spread the tokens


# -- (b) a lower precision, each † switch and each control fail the limit -------


@pytest.mark.parametrize("switch", [
    "conv_bf16", "router_bf16",
    "tau_linear", "value_heads_swapped", "no_residual_scaling",
    "router_reads_residual", "carry_normed_state", "router_one_hidden",
    "skip_output", "pad_each_conv",
    "no_conv", "no_value_shift", "no_router_carry",
])
def test_a_flipped_switch_fails_the_tolerance(model, switch):
    cfg, params, _ = model
    prompt, emitted = prompt_ids(37, seed=37), prompt_ids(11, seed=137)
    got = Served(cfg, params).serve(1, prompt, emitted)
    assert np.abs(got - reference_logits(model, prompt + emitted)).max() < TOL
    flipped = reference_logits(model, prompt + emitted, **{switch: True})
    assert np.abs(got - flipped).max() > 100 * TOL


def test_pages_through_8_bits_fail_the_tolerance(model):
    """The program's own rows held to 8 bits (one scale a row, what an
    int8 cache would keep) between prefill and decode: the logits leave
    the tolerance, and so do the rows themselves."""
    cfg, params, _ = model
    prompt, emitted = prompt_ids(37, seed=37), prompt_ids(11, seed=137)
    served = Served(cfg, params)
    served.prefill(1, prompt)

    def through_8_bits(pool):
        scale = jnp.maximum(jnp.abs(pool).max(-1, keepdims=True) / 127.0, 1e-12)
        return jnp.round(pool / scale) * scale

    exact = served.rows(1, 0, 37)
    served.pools = jax.tree.map(through_8_bits, served.pools)
    assert np.abs(served.rows(1, 0, 37) - exact).max() > 50 * TOL  # 0.015
    got = np.stack([served.tick({1: tok})[1] for tok in emitted])
    want = reference_logits(model, prompt + emitted)[37:]
    assert np.abs(got - want).max() > 100 * TOL  # 0.12


def test_unknown_switches_are_refused(model):
    with pytest.raises(TypeError, match="no_such"):
        reference_logits(model, [1, 2, 3], no_such=True)


# -- (c) the shares add up ------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer(ckpt, model):
    """The catalog's two-chip share: with ``ep_size`` 2 each rank holds 8
    of the 16 experts, routes over all 16, and a token's one expert lands
    on one rank; the two parts of one expert layer add up to the
    ``ep_size`` 1 layer, in the program and in the reference."""
    cfg1, params1, rp1 = model
    assert (cfg1.expert_first, cfg1.experts_held) == (0, 16)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((24, 128)), jnp.float32)
    s = jnp.asarray(rng.standard_normal((24, 32)), jnp.float32)
    live = jnp.ones((24,), bool)
    whole, counters, s1, _ = Z.mlp(params1["blocks"]["1"], cfg1, x, s, live, live)
    assert int(counters[1]) == 24  # every pair lands
    halves = ckpt.with_name("ckpt-ep2")
    halves.mkdir(exist_ok=True)
    (halves / "model.safetensors").symlink_to(ckpt / "model.safetensors")
    (halves / "config.json").write_text(json.dumps({**TINY, "ep_size": 2}))
    parts, landed = [], 0
    for rank in range(2):
        cfg, params = Z.load(halves, max_seq=MAX_SEQ, ep_rank=rank)
        assert (cfg.expert_first, cfg.experts_held) == (8 * rank, 8)
        blk = params["blocks"]["1"]
        assert blk["experts"]["w_down"]["int8"].shape[0] == 8
        part, counters, s2, _ = Z.mlp(blk, cfg, x, s, live, live)
        assert np.abs(np.asarray(s2) - np.asarray(s1)).max() < TOL
        landed += int(counters[1])
        parts.append(np.asarray(part))
        rp = R.reference_params(params, cfg)["blocks"]["1"]
        mine = range(cfg.expert_first, cfg.expert_first + cfg.experts_held)
        with jax.default_matmul_precision("highest"):
            ref_part, *_ = R.moe(rp, cfg, x, s, R.AS_SERVED, mine)
        assert np.abs(np.asarray(ref_part) - parts[-1]).max() < TOL
    assert landed == 24
    assert np.abs(sum(parts) - np.asarray(whole)).max() < TOL
    with jax.default_matmul_precision("highest"):
        ref_whole, *_ = R.moe(rp1["blocks"]["1"], cfg1, x, s, R.AS_SERVED)
    assert np.abs(sum(parts) - np.asarray(ref_whole)).max() < TOL
    assert np.abs(parts[0]).max() > 0.01 and np.abs(parts[1]).max() > 0.01


def test_a_decode_tick_reads_the_touched_experts_alone(model):
    """``held_experts``' ``n <= EXPERT_BLOCK`` branch with one choice a
    row: 3 live rows touch at most 3 of 16 experts, and the layer's
    output is the chosen expert's SwiGLU times its probability."""
    cfg, params, rp = model
    assert SLOTS <= moe.EXPERT_BLOCK
    blk = params["blocks"]["0"]
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((SLOTS, 128)), jnp.float32)
    s = jnp.zeros((SLOTS, 32), jnp.float32)
    live = jnp.asarray([True, False, True])
    y, (tokens, pairs, per_expert), _, _ = Z.mlp(blk, cfg, x, s, live, live)
    assert int(tokens) == int(pairs) == 2 and int(per_expert.sum()) == 2
    with jax.default_matmul_precision("highest"):
        want, _, e, _ = R.moe(rp["blocks"]["0"], cfg, x, s, R.AS_SERVED)
    assert np.abs(np.asarray(y)[[0, 2]] - np.asarray(want)[[0, 2]]).max() < TOL
    assert np.asarray(per_expert)[np.asarray(e)[[0, 2]]].all()


# -- (d) what must leave tails and pool untouched --------------------------------


def test_padding_rows_and_frozen_rows_leave_tails_and_pool_untouched(model):
    cfg, params, _ = model
    served = Served(cfg, params)
    served.prefill(0, prompt_ids(20, seed=41))
    before = jax.tree.map(np.asarray, served.state)
    before_pool = np.asarray(served.pools["1"]["kv"])
    # a ragged chunk into slot 1: 5 valid rows of 32
    served.prefill(1, prompt_ids(5, seed=42))
    for key, tail in served.state.items():
        for leaf in ("c", "v"):
            now, was = np.asarray(tail[leaf]), before[key][leaf]
            assert (now[0] == was[0]).all() and (now[2] == was[2]).all()
            assert (now[1] != was[1]).any()
    # slot 1's pages took the chunk; slot 0's and slot 2's did not move
    pool = np.asarray(served.pools["1"]["kv"])
    mine = set(served.bts[1].tolist())
    others = [p for p in range(1, pool.shape[0]) if p not in mine]
    assert (pool[others] == before_pool[others]).all()
    # a window in which slots 0 and 2 are frozen: bit-identical tails and pages
    tails = jax.tree.map(np.asarray, served.state)
    pool = np.asarray(served.pools["1"]["kv"])
    for tok in (9, 10, 11):
        served.tick({1: tok})
    for key, tail in served.state.items():
        for leaf in ("c", "v"):
            now, was = np.asarray(tail[leaf]), tails[key][leaf]
            assert (now[0] == was[0]).all() and (now[2] == was[2]).all()
            assert (now[1] != was[1]).any()
    after = np.asarray(served.pools["1"]["kv"])
    zero = [int(p) for p in served.bts[0]] + [int(p) for p in served.bts[2]]
    assert (after[zero] == pool[zero]).all()


def test_a_slot_reused_starts_from_zeros(model):
    """A second stream in a slot whose tails an earlier one left: its
    first chunk (position 0) starts from zeros with no call from the
    host, and its logits are the reference's."""
    cfg, params, _ = model
    served = Served(cfg, params, dirty=False)
    served.serve(1, prompt_ids(40, seed=81), prompt_ids(5, seed=82))
    left = served.tail(1, 0)[0]
    assert np.abs(left).max() > 0.1
    prompt, emitted = prompt_ids(19, seed=83), prompt_ids(6, seed=84)
    got = served.serve(1, prompt, emitted)
    assert np.abs(got - reference_logits(model, prompt + emitted)).max() < TOL


# -- (e) preempt, save and restore carry the tails -------------------------------


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
    # another stream dirties the slot's tails, then the first comes back
    assert len(run_one(engine, prompt_ids(40, seed=52), 9, "other")) == 9
    assert run_one(engine, prompt, 14) == want


def test_chunks_ahead_of_their_period_give_the_tokens_of_step(model):
    """``dispatch → ahead → collect`` against ``step()``: multi-chunk
    prompts whose chunks go behind windows that step other rows' tails
    and pages give the tokens they give in line."""
    cfg, params, _ = model
    prompts = {"a": prompt_ids(21, seed=61), "long": prompt_ids(45, seed=62),
               "b": prompt_ids(9, seed=63)}
    caps = {"a": 9, "long": 14, "b": 6}
    engine = make_engine(cfg, params)

    def serve(halves: bool):
        ahead = engine.chunks_ahead
        for rid, prompt in prompts.items():
            engine.submit(rid, prompt, caps[rid])
        got = {rid: [] for rid in prompts}
        for _ in range(300):
            if not engine.active:
                break
            if halves:
                out = engine.dispatch()
                engine.ahead()
                out += engine.collect()
            else:
                out = engine.step()
            for rid, tok, _done in out:
                got[rid].append(tok)
        engine.check_invariants()
        return got, engine.chunks_ahead - ahead

    want, line_ahead = serve(False)
    got, ahead = serve(True)
    assert got == want and [len(got[r]) for r in caps] == list(caps.values())
    assert line_ahead == 0 and ahead >= 3


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


def test_the_engine_keeps_every_layers_picks(model):
    """``engine.selection`` (what a cache audit reads): the last chunk's
    and the last window's top-1 picks, every layer."""
    cfg, params, _ = model
    engine = make_engine(cfg, params)
    prompt = prompt_ids(20, seed=91)
    engine.submit("r", prompt, 6)
    out = []
    while len(out) < 5:
        out += [tok for _r, tok, _d in engine.step()]
    chunk, window = engine.selection["chunk"], engine.selection["window"]
    assert chunk["expert"].shape == (cfg.layers, CHUNK)
    assert window["expert"].shape == (K_TICKS, cfg.layers, SLOTS)
    assert set(window) == set(chunk) == {"expert"}
    _, kept = reference(model, prompt + out)
    for layer in range(cfg.layers):
        want = np.asarray(kept[layer]["expert"])[:20]
        best = np.sort(np.asarray(kept[layer]["biased"])[:20], -1)
        decided = best[:, -1] - best[:, -2] > PICK_MARGIN
        got = np.asarray(chunk["expert"])[layer, :20]
        assert (got[decided] == want[decided]).all()


# -- (f) the refusals by name ----------------------------------------------------


@pytest.mark.parametrize("knob", sorted(Z.NOT_OFFERED))
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
    assert "prefix cache is off" in caplog.text and "tails" in caplog.text


@pytest.mark.parametrize("change,error,match", [
    ({"layer_types": ["hybrid"] * 2}, ValueError, "layer_types"),
    ({"layer_types": ["hybrid", "hybrid_sliding", "hybrid"]},
     NotImplementedError, "hybrid_sliding"),
    ({"sliding_window": 4096}, NotImplementedError, "sliding_window"),
    ({"cca_time0": 4}, NotImplementedError, "cca_time0"),
    ({"cca_time1": 3}, NotImplementedError, "cca_time1"),
    ({"attention_bias": True}, NotImplementedError, "attention_bias"),
    ({"num_experts_per_tok": 2}, NotImplementedError, "num_experts_per_tok"),
    ({"router_hidden_size": 0}, NotImplementedError, "router_hidden_size"),
    ({"num_key_value_heads": 4}, NotImplementedError, "K/V heads"),
    ({"rope_parameters": {"hybrid": {"rope_type": "yarn"}}},
     NotImplementedError, "rotary"),
    ({"model_type": "zamba2"}, ValueError, "zamba2"),
    ({"ep_size": 3}, ValueError, "ep_size"),
])
def test_unsupported_variants_are_refused_by_name(change, error, match):
    with pytest.raises(error, match=match):
        Z.ZayaConfig.from_hf({**TINY, **change})


def test_the_published_keys_give_the_published_shapes():
    """The catalog row's keys: 1,280-wide pre-convolution rows, 64
    rotating dimensions, 1,024 B a token a layer at bf16, 5,376 B of tail
    a slot a layer."""
    cfg = Z.ZayaConfig.from_hf({
        **TINY, "hidden_size": 2048, "head_dim": 128,
        "moe_intermediate_size": 2048, "router_hidden_size": 256,
        "num_hidden_layers": 20, "layer_types": ["hybrid"] * 20,
        "vocab_size": 131136}, max_seq=8192)
    item = jnp.dtype(Z.L.compute_dtype()).itemsize  # 4 on the CPU, 2 on the chip
    assert (cfg.q_width, cfg.kv_width, cfg.conv_width) == (1024, 256, 1280)
    assert cfg.rotary_dim == 64 and cfg.rope_theta == 5e6
    assert cfg.kv_bytes_per_token == 20 * 512 * item
    assert cfg.tail_bytes_per_slot == 20 * (2 * 1280 + 128) * item
    assert (cfg.expert_first, cfg.experts_held, cfg.top_k) == (0, 16, 1)
    token = 20 * 1024  # bf16
    assert PM.pages_that_fit(16 * token, 16_909_336_064, 5_000_000_000, 16,
                             8192, 16) == 16 * 8192 // 16 + 1


def test_expert_share_rank_from_the_launcher(monkeypatch):
    monkeypatch.setenv("DORA_EP_RANK", "1")
    cfg = Z.ZayaConfig.from_hf({**TINY, "ep_size": 2})
    assert (cfg.expert_first, cfg.experts_held, cfg.n_experts) == (8, 8, 16)


def test_llm_server_knows_the_family(ckpt):
    from dora_tpu.models.hf.loader import read_config
    from dora_tpu.nodehub import llm_server

    module = llm_server.model_module(read_config(ckpt)["model_type"])
    assert module is Z
    with pytest.raises(RuntimeError, match="zaya") as err:
        llm_server.model_module("zamba2")
    assert "zamba2" in str(err.value)


# (g) no weight is copied or closed over in the two programs:
# tests/test_backend.py walks them ("zaya" in its table of engines)
