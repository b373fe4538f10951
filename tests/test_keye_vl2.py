"""Keye-VL-2.0's language model on the paged serving path
(models/hf/keye_vl2.py: GQA pages read through an indexer's per-row
picks, a softmax router over ``moe.held_experts``) against its plain
float32 reference (models/hf/keye_vl2_reference.py: whole sequence,
``[T, T]`` index scores, a ``top_k`` a row, no cache), at tiny widths on
the CPU, from seeded weights. Logits and picked sets are compared, not
sampled tokens.

Tiny: ``topk`` 8 (so the selection acts from row 9 of a 40-row prompt),
page 8, chunk 32, an indexer of 2 heads of 8 (a quarter of the scores
are exactly 0: ties, which go to the lower position in both), 16 experts
of which a rank holds 2, 4 a token, 3 layers. On the CPU the serving
path computes in float32 too, so ``TOL`` is float32 summation order (the
blocks' running softmax against a whole one, the int8 scales applied
after the product or before): 2e-5 absolute on logits of magnitude 4,
ten times what was measured (2.1e-6) and far under what a flipped †
switch moves (each asserted below: the indexer on the residual row 1.8,
a forced tail and sink 2.7, no selection and no QK-norm 2.9, a plain key
3.0, post-norm 3.1, shared block picks 3.8).
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
from dora_tpu.models.hf import keye_vl2 as K
from dora_tpu.models.hf import keye_vl2_reference as R

TOL = 2e-5
TOPK, PAGE, CHUNK, BLOCK, K_TICKS, SLOTS, MAX_SEQ = 8, 8, 32, 16, 4, 3, 128

TINY = dict(
    model_type="KeyeVL2", hidden_size=64, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=3, vocab_size=128,
    rms_norm_eps=1e-6, max_position_embeddings=MAX_SEQ, rope_theta=1e7,
    rope_scaling={"mrope_section": [2, 3, 3], "rope_type": "default",
                  "type": "default"},
    sa_config={"indexer_head_dim": 8, "indexer_num_heads": 2,
               "indexer_num_kv_heads": 1, "kv_chunk_size": 4,
               "q_chunk_size": 4, "topk": TOPK},
    num_experts=16, num_local_experts=16, num_experts_per_tok=4,
    norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[],
    sliding_window=None, use_sliding_window=False, attention_bias=False,
    ep_size=8, tie_word_embeddings=False,
)


def write_checkpoint(path: Path, cfg: dict, seed: int = 0) -> None:
    """A whole (all experts) float32 checkpoint under the HF names."""
    from safetensors.numpy import save_file

    rng = np.random.default_rng(seed)
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sa = cfg["sa_config"]
    t: dict[str, np.ndarray] = {}

    def w(out, inp, scale=None):
        return (rng.standard_normal((out, inp)) * (scale or inp ** -0.5)
                ).astype(np.float32)

    def norm(n):
        return (1 + 0.1 * rng.standard_normal(n)).astype(np.float32)

    t["model.embed_tokens.weight"] = w(cfg["vocab_size"], d, 1.0)
    t["model.norm.weight"] = norm(d)
    t["lm_head.weight"] = w(cfg["vocab_size"], d)
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        t[p + "input_layernorm.weight"] = norm(d)
        t[p + "post_attention_layernorm.weight"] = norm(d)
        a, m = p + "self_attn.", p + "mlp."
        # a few rows carry each softmax, so a selection shows in the logits
        t[a + "q_proj.weight"] = w(h * hd, d, 3 * d ** -0.5)
        t[a + "k_proj.weight"] = w(kv * hd, d)
        t[a + "v_proj.weight"] = w(kv * hd, d)
        t[a + "o_proj.weight"] = w(d, h * hd)
        t[a + "q_norm.weight"] = norm(hd)
        t[a + "k_norm.weight"] = norm(hd)
        width = sa["indexer_head_dim"]
        t[a + "indexer.wq.weight"] = w(sa["indexer_num_heads"] * width, d)
        t[a + "indexer.wk.weight"] = w(width, d)
        t[a + "indexer.k_norm.weight"] = norm(width)
        t[a + "indexer.k_norm.bias"] = (
            0.1 * rng.standard_normal(width)).astype(np.float32)
        t[a + "indexer.weights_proj.weight"] = w(sa["indexer_num_heads"], d)
        t[m + "gate.weight"] = w(cfg["num_experts"], d)
        for e in range(cfg["num_experts"]):
            q = f"{m}experts.{e}."
            t[q + "gate_proj.weight"] = w(cfg["moe_intermediate_size"], d)
            t[q + "up_proj.weight"] = w(cfg["moe_intermediate_size"], d)
            t[q + "down_proj.weight"] = w(d, cfg["moe_intermediate_size"])
    path.mkdir(parents=True, exist_ok=True)
    save_file(t, str(path / "model.safetensors"))
    (path / "config.json").write_text(json.dumps(cfg))


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory) -> Path:
    path = tmp_path_factory.mktemp("keye") / "ckpt"
    write_checkpoint(path, TINY)
    return path


@pytest.fixture(scope="module")
def model(ckpt):
    """Rank 0's share (experts 0-1 of 16): (cfg, params, reference params)."""
    cfg, params = K.load(ckpt, max_seq=MAX_SEQ, ep_rank=0)
    return cfg, params, R.reference_params(params, cfg)


def prompt_ids(n: int, seed: int = 1) -> list[int]:
    return np.random.default_rng(seed).integers(1, 128, size=n).tolist()


def make_engine(cfg, params, **kw):
    kw = {"max_slots": SLOTS, "page_size": PAGE, "chunk": CHUNK,
          "window": K_TICKS, "attn_block": BLOCK, **kw}
    return K.make_paged_engine(params, cfg, **kw)


def drain(engine, want: set[str]) -> dict[str, list[int]]:
    out: dict[str, list[int]] = {}
    done: set[str] = set()
    for _ in range(300):
        for rid, token, fin in engine.step():
            out.setdefault(rid, []).append(token)
            if fin:
                done.add(rid)
        if want <= done:
            return out
    raise AssertionError(f"streams never finished: {want - done}")


@functools.lru_cache(maxsize=None)
def programs(cfg):
    """The two programs as the engine jits them, but with logits where
    the greedy tokens would be (cfg is static; one trace a config)."""
    return (
        jax.jit(lambda p, *a: K.paged_chunk_logits(
            p, cfg, *a, block=BLOCK, idx_block=CHUNK)),
        jax.jit(lambda p, *a: K.paged_batch_logits(p, cfg, *a, block=CHUNK)),
    )


class Served:
    """What the engine does, by hand, keeping the logits: pools and
    counters of ``SLOTS`` slots, each stream with pages of its own."""

    def __init__(self, cfg, params, chunk: int = CHUNK):
        self.cfg, self.params, self.chunk = cfg, params, chunk
        self.chunk_fn, self.tick_fn = programs(cfg)
        pages = SLOTS * MAX_SEQ // PAGE + 1
        self.pools = K.init_page_pool(cfg, pages, PAGE)
        self.stats = K.init_counters(cfg)
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
            logits, self.pools, self.stats = self.chunk_fn(
                self.params, jnp.asarray(ids, jnp.int32), self.pools,
                self.stats, jnp.asarray(base, jnp.int32),
                jnp.asarray(self.bts[slot]), jnp.asarray(len(piece), jnp.int32))
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
        logits, self.pools, self.stats = self.tick_fn(
            self.params, jnp.asarray(toks), self.pools, self.stats,
            jnp.asarray(pos), jnp.asarray(bts))
        self.positions[active] += 1
        return {b: np.asarray(logits[b]) for b in tokens}

    def serve(self, slot: int, prompt: list[int], emitted: list[int]):
        """Prefill then teacher-forced decode: logits [T + E, vocab]."""
        rows = [self.prefill(slot, prompt)]
        for tok in emitted:
            rows.append(self.tick({slot: tok})[slot][None])
        return np.concatenate(rows)


def held_of(cfg):
    return range(cfg.expert_first, cfg.expert_first + cfg.experts_held)


def reference_logits(model, tokens, **switches):
    cfg, _, rp = model
    return np.asarray(R.forward(rp, cfg, tokens, held=held_of(cfg), **switches))


# -- (a) pages under the picks against the whole forward pass --------------------


@pytest.mark.parametrize("n,chunk", [
    (3, CHUNK),    # prompt and decode below topk: nothing selects
    (7, CHUNK),    # the prompt ends below topk; decode passes it at row 8
    (8, CHUNK),    # the prompt ends AT topk (t + 1 = 8): the first tick selects
    (9, CHUNK),    # one chunk row selects
    (37, CHUNK),   # a ragged second chunk
    (64, CHUNK),   # the chunks' edges and the prompt's end on a page
    (75, CHUNK),   # a ragged third chunk
    (45, 8),       # chunks of one page
])
def test_chunked_prefill_then_decode_matches_the_reference(model, n, chunk):
    cfg, params, _ = model
    prompt, emitted = prompt_ids(n, seed=n), prompt_ids(11, seed=100 + n)
    got = Served(cfg, params, chunk).serve(1, prompt, emitted)
    want = reference_logits(model, prompt + emitted)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOL


def test_a_frozen_row_beside_live_ones_and_what_the_counters_count(model):
    """Rows of one tick below and far past ``topk`` beside a frozen slot:
    each row's picks are its own, the frozen row scores nothing, writes
    the null page alone and is not counted."""
    cfg, params, _ = model
    short, long_ = prompt_ids(4, seed=21), prompt_ids(70, seed=22)
    follow = {0: prompt_ids(10, seed=23), 2: prompt_ids(10, seed=24)}
    served = Served(cfg, params)
    served.prefill(0, short)
    served.prefill(2, long_)
    before = jax.tree.map(np.asarray, served.pools)
    got = {0: [], 2: []}
    for k in range(10):
        rows = served.tick({b: follow[b][k] for b in follow})
        for b in follow:
            got[b].append(rows[b])
    for b, prompt in ((0, short), (2, long_)):
        want = reference_logits(model, prompt + follow[b])[len(prompt):]
        assert np.abs(np.stack(got[b]) - want).max() < TOL
    # slot 1 was frozen throughout: its pages are bit-identical
    mine = [int(p) for p in served.bts[1]]
    for key, leaves in served.pools.items():
        for leaf in ("kv", "ik"):
            assert (np.asarray(leaves[leaf])[mine] == before[key][leaf][mine]).all()
    dsa = {k: int(v) for k, v in served.stats["dsa"].items()}
    layers = cfg.layers
    pos = [4 + k for k in range(10)] + [70 + k for k in range(10)]
    selecting = [p for p in pos if p >= TOPK]
    assert dsa["dsa_decode_ticks"] == 10 and dsa["dsa_row_ticks"] == 20
    assert dsa["dsa_rows_in_context"] == layers * sum(p + 1 for p in pos)
    assert dsa["dsa_rows_picked"] == layers * sum(min(p + 1, TOPK) for p in pos)
    assert dsa["dsa_rows_fetched"] == layers * TOPK * 20  # 3 slots: a row a group
    assert dsa["dsa_index_rows_scored"] == layers * sum(p + 1 for p in selecting)
    assert dsa["dsa_row_ticks_selecting"] == len(selecting)
    # the two prompts: 4 rows of one chunk, 70 of three (32 + 32 + 6)
    rows = list(range(4)) + list(range(70))
    assert dsa["dsa_chunk_rows"] == 74
    assert dsa["dsa_chunk_rows_in_context"] == layers * sum(p + 1 for p in rows)
    assert dsa["dsa_chunk_rows_picked"] == layers * sum(
        min(p + 1, TOPK) for p in rows)
    assert dsa["dsa_chunk_rows_selecting"] == sum(p >= TOPK for p in rows)
    assert dsa["dsa_chunk_index_rows_scored"] == layers * sum(
        p + 1 for p in rows if p >= TOPK)
    # a chunk multiplies whole blocks of BLOCK rows up to its last row
    swept = 4 * 32 + 32 * 32 + 32 * 64 + 6 * 96
    assert dsa["dsa_chunk_rows_fetched"] == layers * swept
    assert int(served.stats["moe"]["tokens"]) == layers * (74 + 20)


def test_padding_rows_leave_other_streams_pages_untouched(model):
    cfg, params, _ = model
    served = Served(cfg, params)
    served.prefill(0, prompt_ids(20, seed=41))
    before = jax.tree.map(np.asarray, served.pools)
    served.prefill(1, prompt_ids(5, seed=42))  # 5 valid rows of 32
    mine = set(served.bts[1].tolist())
    others = [p for p in range(1, before["0"]["kv"].shape[0]) if p not in mine]
    for key, leaves in served.pools.items():
        for leaf in ("kv", "ik"):
            assert (np.asarray(leaves[leaf])[others] == before[key][leaf][others]).all()


# -- (b) the picked sets, of chunk rows and of decode ticks ----------------------


def test_the_picked_sets_are_the_references(model):
    """An audit's engine (``picks=True``) hands out every chunk row's and
    every tick's picked positions and sublayer output; both are the
    reference's, a layer each. A served engine hands out neither."""
    cfg, params, rp = model
    prompt = prompt_ids(45, seed=71)
    engine = make_engine(cfg, params, picks=True)
    assert engine.prefix_cache is None
    seen_chunks, seen_ticks = [], []
    chunk_program, window_program = engine.chunk_prefill, engine.window_step

    def chunk_prefill(*args):
        out = chunk_program(*args)
        seen_chunks.append(jax.tree.map(np.asarray, engine.selection["chunk"]))
        return out

    def window_step(*args):
        out = window_program(*args)
        seen_ticks.append(jax.tree.map(np.asarray, engine.selection["window"]))
        return out

    engine.chunk_prefill, engine.window_step = chunk_prefill, window_step
    engine.submit("r", prompt, 9)
    emitted = drain(engine, {"r"})["r"]
    assert len(emitted) == 9 and len(seen_chunks) == 2
    tokens = prompt + emitted[:-1]
    _, looks = R.forward(rp, cfg, tokens, held=held_of(cfg), rows=True)
    for layer in range(cfg.layers):
        want = np.asarray(looks[layer]["picked"])  # [T, T] bool
        attended = np.asarray(looks[layer]["attended"])
        # chunk rows: the first chunk is rows 0-31, the second 32-44
        for c, look in enumerate(seen_chunks):
            for r in range(CHUNK):
                t = c * CHUNK + r
                if t >= len(prompt):
                    break
                assert np.abs(look[layer]["attended"][r] - attended[t]).max() < TOL
                if t >= TOPK:
                    assert set(look[layer]["picked"][r].tolist()) == set(
                        np.flatnonzero(want[t]).tolist()), (layer, t)
        # decode ticks: tick j of window w is row len(prompt) + w * K + j
        t = len(prompt)
        for look in seen_ticks:
            for j in range(K_TICKS):
                if t >= len(tokens):
                    break
                assert set(look[layer]["picked"][j, 0].tolist()) == set(
                    np.flatnonzero(want[t]).tolist()), (layer, t)
                assert np.abs(look[layer]["attended"][j, 0] - attended[t]
                              ).max() < TOL
                t += 1
        assert t == len(tokens)
    served = make_engine(cfg, params)
    assert not hasattr(served, "selection") and served.slot_state is None


def test_equal_scores_go_to_the_lower_position():
    """``picked_mask`` holds the scores to the ``topk``-th largest and
    gives equal scores to the lower positions, as ``lax.top_k`` does."""
    cfg = K.KeyeVL2Config.from_hf(TINY, max_seq=32)
    rng = np.random.default_rng(5)
    s = rng.integers(0, 4, size=(6, 32)).astype(np.float32)  # many ties
    q_pos = np.asarray([7, 8, 12, 20, 31, 31])
    s = np.where(np.arange(32)[None, :] <= q_pos[:, None], s, -np.inf)
    sel, ids = K.picked_mask(cfg, jnp.asarray(s), jnp.asarray(q_pos), ids=True)
    sel, ids = np.asarray(sel), np.asarray(ids)
    assert not sel[0].any() and ids[0].tolist() == list(range(TOPK))
    for r in range(1, 6):
        want = np.asarray(jax.lax.top_k(jnp.asarray(s[r]), TOPK)[1])
        assert set(np.flatnonzero(sel[r]).tolist()) == set(want.tolist())
        assert ids[r].tolist() == sorted(want.tolist())  # the set, ascending


@pytest.mark.parametrize("k", [1, 5, 8, 31, 32])
def test_the_kth_largest_without_a_sort(k):
    """Negative scores, both zeros, ``-inf`` and repeats: the key found a
    bit at a time is the sort's ``k``-th."""
    rng = np.random.default_rng(k)
    s = rng.standard_normal((7, 32)).astype(np.float32)
    s[0, :10] = 0.0
    s[1, :10] = -0.0
    s[1, 10:14] = 0.0
    s[2, 5:] = -np.inf
    s[3] = np.round(s[3])  # repeats
    s[4] = -np.abs(s[4])
    keys, kth = K.kth_largest(jnp.asarray(s), k)
    want = np.sort(s, -1)[:, ::-1][:, k - 1]
    at_kth = np.asarray(keys) == np.asarray(kth)[:, None]
    assert at_kth.any(-1).all()
    for r in range(7):
        assert (s[r][at_kth[r]] == want[r]).all(), (r, s[r][at_kth[r]], want[r])
        assert (np.asarray(keys)[r] > np.asarray(kth)[r]).sum() == (s[r] > want[r]).sum()


# -- (c) through the engine: tokens, the prefix cache, preemption, restore -------


def deficits(model, prompt, emitted) -> float:
    """Largest gap between the top of the reference's teacher-forced
    logits and the logit of the token the engine emitted there."""
    ref = reference_logits(model, prompt + emitted)
    rows = ref[len(prompt) - 1: len(prompt) - 1 + len(emitted)]
    return float((rows.max(-1) - rows[np.arange(len(emitted)), emitted]).max())


@pytest.mark.parametrize("slots", [SLOTS, 6, 8])
def test_engine_tokens_are_the_references_argmax(model, slots):
    """Three streams in 3, 6 and 8 slots: a decode tick takes its live
    rows 1, 2 and 4 at a time (``DECODE_ROWS`` against the slots), the
    last group short, frozen slots between them."""
    cfg, params, _ = model
    engine = make_engine(cfg, params, max_slots=slots)
    prompts = {"a": prompt_ids(6, 31), "b": prompt_ids(50, 32),
               "c": prompt_ids(33, 33)}
    for rid, prompt in prompts.items():
        engine.submit(rid, prompt, 13)
    out = drain(engine, set(prompts))
    for rid, prompt in prompts.items():
        assert len(out[rid]) == 13
        assert deficits(model, prompt, out[rid]) < TOL
    report = engine.model_counters()
    # K, V and the indexer's key of three layers, float32 on the CPU
    assert report["kv_bytes_per_token"] == 3 * (2 * 32 + 8) * 4
    assert report["kv_pool_bytes"] == (
        engine.allocator.num_pages * PAGE * report["kv_bytes_per_token"])
    assert report["moe_tokens"] > 0 and len(report["moe_expert_tokens"]) == 2
    assert 0 < report["dsa_rows_picked"] < report["dsa_rows_in_context"]
    assert set(engine.pools["0"]) == {"kv", "ik"}


def test_a_prefix_hit_on_two_leaf_pages_a_preemption_and_a_restore_give_the_cold_tokens(
        model, tmp_path):
    """Through PagedBatchEngine, the window program and the prefix cache,
    unchanged: a cold run; the same prompt again, served from cached
    pages of BOTH leaves (its rows select among positions it never
    wrote); a stream preempted after its first window and resumed; a
    stream checkpointed mid-generation and restored into a second engine.
    Each emitted token is the reference's top at its position, and the
    three later runs emit the cold run's tokens."""
    cfg, params, _ = model
    prompt = prompt_ids(41, seed=11)
    engine = make_engine(cfg, params, num_pages=64, prefix_cache=True)
    engine.submit("cold", prompt, 12)
    cold = drain(engine, {"cold"})["cold"]
    assert len(cold) == 12 and deficits(model, prompt, cold) < TOL

    engine.submit("hit", prompt, 12)
    assert engine.prefix_cache.hits == 1
    assert engine.prefix_cache.hit_tokens == 40  # 5 whole pages of 8
    assert drain(engine, {"hit"})["hit"] == cold
    # a longer prompt over the same prefix: its own rows differ, and its
    # chunk starts on a page that is no chunk's edge
    longer = prompt + prompt_ids(23, seed=12)
    engine.submit("longer", longer, 7)
    assert engine.prefix_cache.hits == 2
    assert deficits(model, longer, drain(engine, {"longer"})["longer"]) < TOL

    engine.submit("victim", prompt, 12)
    part = []
    while len(part) < 3:
        part += [t for _, t, _ in engine.step()]
    meta = engine.preempt("victim")
    assert meta["emitted"] == len(part) and meta["was_decoding"]
    engine.submit("resumed", prompt + part, 12 - len(part))
    assert part + drain(engine, {"resumed"})["resumed"] == cold
    engine.check_invariants()

    engine.submit("saved", prompt, 12)
    first = []
    while len(first) < 3:
        first += [t for _, t, _ in engine.step()]
    state = engine.checkpoint_state()
    engine.save_pools(tmp_path / "pools")
    other = make_engine(cfg, params, num_pages=64, prefix_cache=True)
    other.restore_pools(tmp_path / "pools")
    assert other.restore_state(json.loads(json.dumps(state))) == ["saved"]
    assert first + drain(other, {"saved"})["saved"] == cold
    assert engine.kv_pool_bytes() == 64 * PAGE * cfg.kv_bytes_per_token


def test_chunks_ahead_of_their_period_give_the_tokens_of_step(model):
    """``dispatch → ahead → collect`` against ``step()``: chunks that go
    behind windows which write other rows' pages give the tokens they
    give in line."""
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


# -- (d) each † switch and each control, flipped, fails the same limit -----------


@pytest.mark.parametrize("switch", [
    "index_reads_residual", "index_plain_key", "block_picks",
    "forced_tail_and_sink", "post_norm", "no_selection", "no_qk_norm"])
def test_a_flipped_switch_fails_the_tolerance(model, switch):
    cfg, params, _ = model
    tokens = prompt_ids(60, seed=81)
    got = Served(cfg, params).prefill(0, tokens)
    assert np.abs(got - reference_logits(model, tokens)).max() < TOL
    other = reference_logits(model, tokens, chunks=(4, 4), **{switch: True})
    assert np.abs(got - other).max() > 1.0


def test_the_head_weights_scale_moves_no_pick(model):
    """†3: a positive factor on ``w`` leaves every ranking where it is."""
    tokens = prompt_ids(60, seed=82)
    same = reference_logits(model, tokens, index_unscaled=True)
    assert np.abs(same - reference_logits(model, tokens)).max() < TOL


def test_unknown_switches_are_refused(model):
    with pytest.raises(TypeError, match="no_such_switch"):
        reference_logits(model, [1, 2, 3], no_such_switch=True)


def test_picks_given_from_outside_replace_the_references_own(model):
    cfg, _, rp = model
    tokens = prompt_ids(30, seed=83)
    causal = jnp.tril(jnp.ones((30, 30), bool))
    given = R.forward(rp, cfg, tokens, held=held_of(cfg),
                      picks=[causal] * cfg.layers)
    dense = R.forward(rp, cfg, tokens, held=held_of(cfg), no_selection=True)
    assert np.abs(np.asarray(given) - np.asarray(dense)).max() == 0


# -- (e) the expert layer's shares ------------------------------------------------


def test_the_eight_shares_add_up_to_the_uncut_layer(ckpt):
    """The routed parts of all ``ep_size`` shares, each through
    ``moe.held_experts`` under this module's softmax router, add up to
    the uncut reference's expert layer; no share is empty."""
    x = jnp.asarray(np.random.default_rng(3).standard_normal((40, 64)),
                    jnp.float32)
    live = jnp.ones((40,), bool)
    parts = []
    for rank in range(8):
        cfg, params = K.load(ckpt, max_seq=MAX_SEQ, ep_rank=rank)
        assert (cfg.expert_first, cfg.experts_held) == (2 * rank, 2)
        blk = params["blocks"]["1"]
        mine, (tokens, pairs, per_expert) = K.mlp(blk, cfg, x, live, live)
        assert int(tokens) == 40 and int(pairs) == int(per_expert.sum())
        parts.append(np.asarray(mine))
        rp = R.reference_params(params, cfg)["blocks"]["1"]
        with jax.default_matmul_precision("highest"):
            assert np.abs(np.asarray(R.moe(rp, cfg, x, held_of(cfg))) - parts[-1]
                          ).max() < TOL
    whole_dir = ckpt.with_name("ckpt-ep1")
    whole_dir.mkdir(exist_ok=True)
    (whole_dir / "model.safetensors").symlink_to(ckpt / "model.safetensors")
    (whole_dir / "config.json").write_text(json.dumps({**TINY, "ep_size": 1}))
    cfg, params = K.load(whole_dir, max_seq=MAX_SEQ)
    assert (cfg.expert_first, cfg.experts_held) == (0, 16)
    rp = R.reference_params(params, cfg)["blocks"]["1"]
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(R.moe(rp, cfg, x))
    assert np.abs(sum(parts) - whole).max() < TOL
    assert all(np.abs(p).max() > 0.01 for p in parts)


def test_the_router_is_a_softmax_renormalised_over_the_chosen(model):
    cfg, params, _ = model
    blk = params["blocks"]["0"]
    x = jnp.asarray(np.random.default_rng(4).standard_normal((9, 64)),
                    jnp.float32)
    ids, w = K.route(blk, cfg, x)
    logits = np.asarray(x) @ np.asarray(blk["router"], np.float32)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.argsort(-p, -1)[:, :4]
    assert (np.asarray(ids) == want).all() and ids.shape == (9, 4)
    chosen = np.take_along_axis(p, want, -1)
    assert np.abs(np.asarray(w) - chosen / chosen.sum(-1, keepdims=True)
                  ).max() < 1e-6
    assert np.abs(np.asarray(w).sum(-1) - 1).max() < 1e-6


# -- (f) M-RoPE -------------------------------------------------------------------


def test_mrope_with_unequal_components_against_a_hand_written_rotation(model):
    """The reference rotates frequency ``i`` by the component its section
    names; with equal components that is plain rotary (what the program
    applies), with unequal ones it is not."""
    cfg, _, rp = model
    assert cfg.mrope_section == (2, 3, 3)
    t = 6
    pos3 = jnp.asarray([[0, 1, 2, 3, 4, 5], [0, 0, 1, 1, 2, 2],
                        [5, 4, 3, 2, 1, 0]])
    angles = np.asarray(R.mrope_angles(cfg, pos3))
    inv = 1.0 / 1e7 ** (np.arange(0, 16, 2) / 16)
    by_hand = np.zeros((t, 8))
    for i in range(8):
        comp = 0 if i < 2 else 1 if i < 5 else 2
        by_hand[:, i] = np.asarray(pos3)[comp] * inv[i]
    assert np.abs(angles - by_hand).max() < 1e-6
    # a vector of ones, rotated: x1 cos - x2 sin | x2 cos + x1 sin
    x = jnp.ones((t, 1, 16))
    got = np.asarray(R.rotate(x, jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]))
    want = np.concatenate([np.cos(by_hand) - np.sin(by_hand),
                           np.cos(by_hand) + np.sin(by_hand)], -1)
    assert np.abs(got[:, 0] - want).max() < 1e-6
    # equal components: the program's table; unequal: other logits
    plain = np.asarray(R.mrope_angles(cfg, jnp.broadcast_to(jnp.arange(t), (3, t))))
    (cos, _), _ = K.rope_rows(cfg, jnp.arange(t))
    assert np.abs(np.cos(plain) - np.asarray(cos)).max() < 1e-6
    tokens = prompt_ids(t, seed=91)
    moved = R.forward(rp, cfg, tokens, held=held_of(cfg), positions3=pos3)
    assert np.abs(np.asarray(moved) - reference_logits(model, tokens)).max() > 0.01


# -- (g) the refusals by name -----------------------------------------------------


@pytest.mark.parametrize("knob", sorted(K.NOT_OFFERED))
def test_knobs_of_the_qwen_path_are_refused_by_name(model, monkeypatch, knob):
    cfg, params, _ = model
    monkeypatch.setenv(knob, "1")
    with pytest.raises(NotImplementedError, match=knob):
        make_engine(cfg, params)


@pytest.mark.parametrize("change,error,match", [
    ({"sliding_window": 4096}, NotImplementedError, "sliding_window"),
    ({"use_sliding_window": True}, NotImplementedError, "sliding_window"),
    ({"mlp_only_layers": [0]}, NotImplementedError, "mlp_only_layers"),
    ({"decoder_sparse_step": 2}, NotImplementedError, "decoder_sparse_step"),
    ({"tie_word_embeddings": True}, NotImplementedError, "tied"),
    ({"attention_bias": True}, NotImplementedError, "attention_bias"),
    ({"rope_scaling": {"rope_type": "yarn", "mrope_section": [2, 3, 3]}},
     NotImplementedError, "rotary"),
    ({"rope_scaling": {"rope_type": "default", "mrope_section": [2, 3, 4]}},
     ValueError, "mrope_section"),
    ({"sa_config": None}, ValueError, "sa_config"),
    ({"sa_config": {**TINY["sa_config"], "indexer_num_kv_heads": 2}},
     NotImplementedError, "indexer_num_kv_heads"),
    ({"model_type": "KeyeVL1_5"}, ValueError, "KeyeVL1_5"),
    ({"ep_size": 3}, ValueError, "ep_size"),
])
def test_unsupported_variants_are_refused_by_name(change, error, match):
    with pytest.raises(error, match=match):
        K.KeyeVL2Config.from_hf({**TINY, **change})


def test_picks_on_an_engine_with_a_prefix_cache_is_refused(model):
    cfg, params, _ = model
    with pytest.raises(NotImplementedError, match="picks"):
        make_engine(cfg, params, picks=True, prefix_cache=True)


def test_a_context_that_can_never_select_is_refused(ckpt):
    cfg, params = K.load(ckpt, max_seq=4, ep_rank=0)
    with pytest.raises(ValueError, match="topk"):
        K.make_paged_engine(params, cfg, page_size=4, chunk=4)


def test_expert_share_rank_from_the_launcher(monkeypatch):
    monkeypatch.setenv("DORA_EP_RANK", "3")
    cfg = K.KeyeVL2Config.from_hf(TINY)
    assert (cfg.expert_first, cfg.experts_held, cfg.n_experts) == (6, 2, 16)


def test_llm_server_knows_the_family(ckpt):
    from dora_tpu.models.hf.loader import read_config
    from dora_tpu.nodehub import llm_server

    module = llm_server.model_module(read_config(ckpt)["model_type"])
    assert module is K
    with pytest.raises(RuntimeError, match="KeyeVL2") as err:
        llm_server.model_module("KeyeVL1_5")
    assert "KeyeVL1_5" in str(err.value)


def test_the_loader_reads_the_held_experts_alone(ckpt, monkeypatch):
    from dora_tpu.models.hf import loader

    asked = []
    get = loader.TensorFiles.get
    monkeypatch.setattr(loader.TensorFiles, "get",
                        lambda self, name: asked.append(name) or get(self, name))
    cfg, params = K.load(ckpt, max_seq=MAX_SEQ, ep_rank=5)
    experts = {n.split("experts.")[1].split(".")[0] for n in asked if "experts." in n}
    assert experts == {"10", "11"}
    assert not any("e_score_correction_bias" in n or "visual" in n for n in asked)
    stack = params["blocks"]["0"]["experts"]
    assert sorted(stack) == ["w_down", "w_gateup"]
    assert [v.shape[0] for v in jax.tree.leaves(stack)] == [2] * 4
    # one fused matrix: q, k, v, the indexer's q and k, its head weights
    assert params["blocks"]["0"]["wqkv"]["int8"].shape == (
        64, 64 + 32 + 32 + 16 + 8 + 128)


def test_the_pool_rule_in_bytes():
    """16 slots x 16,384 rows of 26,112 B (bf16) fit a v5e beside 1.3 GB
    of weights; a smaller device caps the pool."""
    big = K.KeyeVL2Config.from_hf(
        {**TINY, "hidden_size": 2048, "num_attention_heads": 32,
         "num_key_value_heads": 4, "head_dim": 128, "num_hidden_layers": 12,
         "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default"},
         "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                       "indexer_num_kv_heads": 1, "topk": 2048}},
        max_seq=16384)
    token = big.kv_bytes_per_token  # float32 on the CPU: twice bf16's 26,112
    assert token == 12 * (2 * 512 + 64) * jnp.dtype(K.L.compute_dtype()).itemsize
    assert 12 * (2 * 512 + 64) * 2 == 26112

    def fit(limit, used):
        return PM.pages_that_fit(16 * token, limit, used, 16, big.max_seq, 16)

    limit, used = 16_909_336_064, 1_400_000_000
    assert fit(limit, used) == min(
        16 * 16384 // 16 + 1, (limit - used - (4 << 30)) // (16 * token))
    assert fit(12 << 30, 3 << 30) == (5 << 30) // (16 * token)
    assert fit(8 << 30, 6 << 30) == 2 * 16384 // 16  # never under two streams' worth


def test_the_counters_names_are_glms_and_three_of_its_own():
    from dora_tpu.models.hf import glm5_next as G

    shared = [n for n in G.KDA_COUNTERS if n.startswith("dsa_")]
    assert set(shared) < set(K.DSA_COUNTERS)
    assert set(K.DSA_COUNTERS) - set(shared) == {
        "dsa_decode_ticks", "dsa_row_ticks", "dsa_chunk_rows"}
    assert moe.init_counters.__module__ == "dora_tpu.models.moe"
