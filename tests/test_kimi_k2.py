"""Kimi-K2 / DeepSeek-V3 on the paged serving path (models/hf/kimi_k2.py)
against its plain float32 reference (models/hf/kimi_k2_reference.py), at
tiny widths on the CPU, from seeded weights. Logits are compared, not
sampled tokens. On the CPU the serving path computes in float32 too, so
every tolerance here is float32 summation order (absorbed against
expanded attention, running against whole softmax, blocked against
whole expert sums): 2e-4 absolute on logits of magnitude ~4, a thousand
times what was measured (3e-6) and a hundred times under the smallest
term a dropped piece of the mathematics would move (the roped key, one
expert, the shared expert: > 1e-2 each, asserted below).
"""

from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dora_tpu.models import layers as L
from dora_tpu.models import moe as M
from dora_tpu.models.hf import kimi_k2 as K
from dora_tpu.models.hf import kimi_k2_reference as R

TOL = 2e-4
PAGE, CHUNK, BLOCK = 8, 16, 16

TINY = dict(
    model_type="kimi_k2", hidden_size=64, num_attention_heads=4,
    q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
    moe_intermediate_size=32, n_routed_experts=32, num_experts_per_tok=4,
    n_shared_experts=1, first_k_dense_replace=1, num_hidden_layers=3,
    vocab_size=128, rms_norm_eps=1e-5, rope_theta=50000,
    routed_scaling_factor=2.827, norm_topk_prob=True,
    scoring_func="sigmoid", topk_method="noaux_tc", n_group=1, topk_group=1,
    max_position_embeddings=128, ep_size=1, tie_word_embeddings=False,
    rope_scaling=dict(type="yarn", factor=4,
                      original_max_position_embeddings=32, beta_fast=32,
                      beta_slow=1, mscale=1, mscale_all_dim=1),
)


def write_checkpoint(path: Path, cfg: dict, seed: int = 0) -> None:
    """A whole (all experts) float32 checkpoint under the HF names."""
    from safetensors.numpy import save_file

    rng = np.random.default_rng(seed)
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
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
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        t[p + "input_layernorm.weight"] = norm(d)
        t[p + "post_attention_layernorm.weight"] = norm(d)
        a = p + "self_attn."
        t[a + "q_a_proj.weight"] = w(cfg["q_lora_rank"], d)
        t[a + "q_a_layernorm.weight"] = norm(cfg["q_lora_rank"])
        t[a + "q_b_proj.weight"] = w(h * (nope + rope), cfg["q_lora_rank"])
        t[a + "kv_a_proj_with_mqa.weight"] = w(cfg["kv_lora_rank"] + rope, d)
        t[a + "kv_a_layernorm.weight"] = norm(cfg["kv_lora_rank"])
        t[a + "kv_b_proj.weight"] = w(h * (nope + v), cfg["kv_lora_rank"])
        t[a + "o_proj.weight"] = w(d, h * v)
        m = p + "mlp."
        if i < cfg["first_k_dense_replace"]:
            ffn(m, cfg["intermediate_size"])
            continue
        t[m + "gate.weight"] = w(cfg["n_routed_experts"], d)
        t[m + "gate.e_score_correction_bias"] = (
            0.1 * rng.standard_normal(cfg["n_routed_experts"])
        ).astype(np.float32)
        ffn(m + "shared_experts.", cfg["moe_intermediate_size"])
        for e in range(cfg["n_routed_experts"]):
            ffn(f"{m}experts.{e}.", cfg["moe_intermediate_size"])
    path.mkdir(parents=True, exist_ok=True)
    save_file(t, str(path / "model.safetensors"))
    (path / "config.json").write_text(json.dumps(cfg))


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory) -> Path:
    path = tmp_path_factory.mktemp("kimi") / "ckpt"
    write_checkpoint(path, TINY)
    return path


def shared_by(ckpt: Path, ep_size: int) -> Path:
    """The checkpoint as a group of ``ep_size`` ranks shares it: the same
    tensors, ``ep_size`` in its config.json."""
    path = ckpt.with_name(f"ckpt-ep{ep_size}")
    if not path.exists():
        path.mkdir()
        (path / "model.safetensors").symlink_to(ckpt / "model.safetensors")
        (path / "config.json").write_text(
            json.dumps({**TINY, "ep_size": ep_size}))
    return path


@pytest.fixture(scope="module")
def share(ckpt):
    """Rank 1 of 4: experts 8..15 of 32, and its reference parameters."""
    cfg, params = K.load(shared_by(ckpt, 4), max_seq=128, ep_rank=1)
    return cfg, params, R.reference_params(params, cfg)


@pytest.fixture()
def small_expert_blocks(monkeypatch):
    """Chunks of 16 rows take the blocked expert path (8 rows a block)."""
    monkeypatch.setattr(M, "EXPERT_BLOCK", 8)


def prompt_ids(n: int, seed: int = 1) -> list[int]:
    return np.random.default_rng(seed).integers(1, 128, size=n).tolist()


def jitted(fn, cfg):
    """One compiled program per step function and share (cfg is static)."""
    return jax.jit(lambda params, *args: fn(params, cfg, *args, block=BLOCK))


def run_chunks(params, cfg, prompt, pools, stats, bt):
    out = []
    chunk_logits = jitted(K.paged_chunk_logits, cfg)
    for base in range(0, len(prompt), CHUNK):
        piece = prompt[base:base + CHUNK]
        valid = len(piece)
        piece = piece + [0] * (CHUNK - valid)
        logits, pools, stats = chunk_logits(
            params, jnp.asarray(piece, jnp.int32), pools, stats,
            jnp.asarray(base, jnp.int32), jnp.asarray(bt),
            jnp.asarray(valid, jnp.int32),
        )
        out.append(np.asarray(logits))
    return np.concatenate(out)[: len(prompt)], pools, stats


# -- tables and the router ----------------------------------------------------


def test_yarn_table_against_closed_form_values():
    """Kimi-K2's settings: 32 pairs, theta 50000, factor 64, original
    4096. The correction dimensions are 8.9 (32 turns) and 19.2 (1
    turn): pairs 0..8 keep the plain frequency, pairs 20.. are divided
    by 64, pair 14 is half way up the ramp. mscale = mscale_all_dim = 1,
    so the tables are unscaled (cos 0 = 1). Tolerance: float32 cos/sin
    of an angle under 100 rad, 2e-5."""
    plain = 50000.0 ** (-np.arange(0, 64, 2) / 64)
    inv = L.yarn_inv_freq(64, 50000.0, 64.0, 4096, 32, 1)
    np.testing.assert_allclose(inv[:9], plain[:9], rtol=1e-12)
    np.testing.assert_allclose(inv[20:], plain[20:] / 64, rtol=1e-12)
    np.testing.assert_allclose(inv[14], plain[14] * (0.5 + 0.5 / 64), rtol=1e-12)
    assert np.all(np.diff(inv) < 0)
    cos, sin = L.yarn_rope_table(128, 64, 50000.0, 64.0, 4096, 32, 1, 1.0, 1.0)
    assert cos.shape == (128, 32) and float(cos[0, 0]) == 1.0
    for pos, pair in ((1, 0), (100, 3), (127, 14), (90, 31)):
        assert abs(float(cos[pos, pair]) - math.cos(pos * inv[pair])) < 2e-5
        assert abs(float(sin[pos, pair]) - math.sin(pos * inv[pair])) < 2e-5
    # the ratio of the two mscales scales both tables (not 1 only if they differ)
    cos2, _ = L.yarn_rope_table(4, 64, 50000.0, 64.0, 4096, 32, 1, 1.0, 0.0)
    assert float(cos2[0, 0]) == pytest.approx(0.1 * math.log(64) + 1, rel=1e-6)


def test_softmax_scale_carries_mscale_squared(share):
    cfg = share[0]
    want = (16 + 8) ** -0.5 * (0.1 * math.log(4) + 1) ** 2
    assert cfg.softmax_scale == pytest.approx(want, rel=1e-12)
    kimi = K.KimiK2Config.from_hf({
        **TINY, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rope_scaling": {**TINY["rope_scaling"], "factor": 64},
    })
    assert kimi.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2, rel=1e-12)


@pytest.mark.parametrize("which", ["program", "reference"])
def test_router_bias_only_in_the_choice(share, which):
    """The bias decides who is chosen; the weights are the unbiased
    sigmoid scores of the chosen, normalised, times 2.827."""
    cfg, params, rp = share
    blk, r = dict(params["blocks"]["1"]), dict(rp["blocks"]["1"])
    x = jnp.asarray(np.random.default_rng(3).standard_normal((5, 64)),
                    jnp.float32)
    scores = np.asarray(jax.nn.sigmoid(x @ r["router"]))
    loser = int(scores.sum(0).argmin())  # chosen by nobody without a bias
    bias = np.zeros(32, np.float32)
    bias[loser] = 10.0
    blk["router_bias"] = r["router_bias"] = jnp.asarray(bias)
    ids, w = (M.route(blk, cfg, x) if which == "program"
              else R.route(r, cfg, x))
    ids, w = np.asarray(ids), np.asarray(w)
    assert (ids == loser).any(-1).all()
    for row in range(5):
        chosen = scores[row, ids[row]]
        np.testing.assert_allclose(
            w[row], chosen / chosen.sum() * 2.827, rtol=1e-5)
        rest = np.sort(np.delete(scores[row], loser))[::-1][:3]
        assert set(np.round(chosen, 6)) == set(
            np.round(np.append(rest, scores[row, loser]), 6))


# -- the share and the whole ----------------------------------------------------


def test_four_shares_add_up_to_the_uncut_layer(ckpt):
    """Ranks 0..3 of 4 each compute their 8 of 32 experts' part of one
    layer plus the shared expert; the four parts, with the shared expert
    counted once, are the uncut reference's layer (all 32 experts)."""
    cfg_all, p_all = K.load(ckpt, max_seq=128)
    whole = R.reference_params(p_all, cfg_all)["blocks"]["2"]
    x = jnp.asarray(np.random.default_rng(5).standard_normal((24, 64)),
                    jnp.float32)
    live = jnp.ones((24,), bool)
    want = np.asarray(R.moe(whole, cfg_all, x))
    total, pairs = np.zeros((24, 64), np.float32), 0
    for rank in range(4):
        cfg, params = K.load(shared_by(ckpt, 4), max_seq=128, ep_rank=rank)
        assert (cfg.expert_first, cfg.experts_held) == (8 * rank, 8)
        blk = params["blocks"]["2"]
        y, (tokens, landed, per_expert) = M.mlp(blk, cfg, x, live, live)
        shared = np.asarray(M.swiglu(blk["shared"], x))
        total += np.asarray(y) - (shared if rank else 0)
        pairs += int(landed)
        assert int(tokens) == 24 and int(per_expert.sum()) == int(landed)
        # the reference, given the same share, gives the same part
        part = R.moe(R.reference_params(params, cfg)["blocks"]["2"], cfg, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(part), atol=TOL)
    assert pairs == 24 * 4  # every chosen pair landed on exactly one rank
    np.testing.assert_allclose(total, want, atol=TOL)
    # and the parts matter: one rank's part alone is not the layer
    assert np.abs(np.asarray(y) - want).max() > 1e-2


def test_absorbed_attention_equals_expanded(share):
    """One layer's attention: W_kvb folded into query and output, over
    latent pages in blocks with a running softmax, against 4 heads of
    expanded keys and values with one whole softmax."""
    cfg, params, rp = share
    blk, r = params["blocks"]["0"], rp["blocks"]["0"]
    x = jnp.asarray(np.random.default_rng(7).standard_normal((32, 64)),
                    jnp.float32)
    cos_t, sin_t = K.rope_tables(cfg)
    want = np.asarray(R.attention(r, cfg, x, cos_t[:32], sin_t[:32]))
    pool = jnp.zeros((8, PAGE, cfg.row), jnp.float32)
    bt = jnp.asarray([3, 1, 4, 2] + [0] * 12, jnp.int32)
    got, pool = K.mla_chunk(blk, cfg, x, pool, jnp.asarray(0, jnp.int32), bt,
                            cos_t[:32], sin_t[:32], BLOCK)
    np.testing.assert_allclose(np.asarray(got), want, atol=TOL)
    # the cache row is (normalised c_kv, roped k_pe): without the roped
    # key's term the scores, and the output, are another model's
    q, rows = K.mla_project(blk, cfg, x, cos_t[:32], sin_t[:32])
    np.testing.assert_allclose(
        np.asarray(pool[3, 0]), np.asarray(rows[0]), atol=1e-6)
    assert cfg.row == 128 and not np.asarray(pool[..., cfg.latent:]).any()
    no_pe = pool.at[:, :, cfg.kv_rank:].set(0.0)
    ctx = L.attend_latent_blocks(
        cfg, q,
        lambda j: no_pe[jax.lax.dynamic_slice_in_dim(bt, j * 2, 2)].reshape(
            16, cfg.row),
        lambda j: ((j * 16 + jnp.arange(16))[None] <= jnp.arange(32)[:, None]
                   )[:, None, :],
        2, "qhc,tc->qht", "qht,tc->qhc",
    )
    assert np.abs(np.asarray(L.mla_output(blk, cfg, ctx)) - want).max() > 1e-2


def test_chunked_prefill_then_decode_windows_match_the_full_forward(
        share, small_expert_blocks):
    """Three chunks (the last ragged: 41 = 16 + 16 + 9) into scattered
    latent pages, then two K=8 windows' worth of decode ticks in a batch
    of 4 rows of which one is live, against the reference's full forward
    over prompt + emitted, at every position."""
    cfg, params, rp = share
    prompt = prompt_ids(41)
    pools, stats = K.init_page_pool(cfg, 40, PAGE), M.init_counters(cfg)
    bt = np.zeros((128 // PAGE,), np.int32)
    bt[:10] = np.arange(1, 11)[::-1]
    logits, pools, stats = run_chunks(params, cfg, prompt, pools, stats, bt)
    want = np.asarray(R.forward(rp, cfg, jnp.asarray(prompt)))
    np.testing.assert_allclose(logits, want, atol=TOL)

    seq, token = list(prompt), int(logits[-1].argmax())
    bts = np.zeros((4, 128 // PAGE), np.int32)
    bts[2] = bt
    got = []
    batch_logits = jitted(K.paged_batch_logits, cfg)
    for _ in range(16):
        seq.append(token)
        tokens = np.zeros(4, np.int32)
        positions = np.zeros(4, np.int32)
        tokens[2], positions[2] = token, len(seq) - 1
        step, pools, stats = batch_logits(
            params, jnp.asarray(tokens), pools, stats,
            jnp.asarray(positions), jnp.asarray(bts),
        )
        got.append(np.asarray(step)[2])
        token = int(got[-1].argmax())
    want = np.asarray(R.forward(rp, cfg, jnp.asarray(seq)))[len(prompt):]
    np.testing.assert_allclose(np.stack(got), want, atol=TOL)

    # the counters: 41 prompt rows + 16 live decode rows, over 2 expert
    # layers; the chunk's 7 pad rows and the 3 frozen rows are not counted
    moe = {k: np.asarray(v) for k, v in stats.items()}
    assert set(pools) == {"0", "1", "2"}  # the cache holds pages alone
    assert moe["tokens"] == (41 + 16) * 2 and moe["decode_ticks"] == 16
    assert moe["expert_tokens"].shape == (2, 8)
    assert moe["expert_tokens"].sum() == moe["local_pairs"]
    assert 0 < moe["touched"] <= moe["local_pairs"]


def test_dropping_the_routed_experts_or_the_shared_expert_fails_the_tolerance(
        share):
    cfg, params, rp = share
    prompt = prompt_ids(16, seed=9)
    want = np.asarray(R.forward(rp, cfg, jnp.asarray(prompt)))
    for drop in ("experts", "shared"):
        # no routed expert: a share past the model's last, so no pair lands
        cut = {**params, "blocks": {
            i: {k: v for k, v in b.items() if (k, drop) != ("shared", "shared")}
            for i, b in params["blocks"].items()}}
        cut_cfg = cfg if drop == "shared" else K.KimiK2Config(
            **{**cfg.__dict__, "expert_first": cfg.n_experts})
        pools = K.init_page_pool(cut_cfg, 8, PAGE)
        bt = np.zeros((128 // PAGE,), np.int32)
        bt[:2] = (1, 2)
        got, _, _ = run_chunks(cut, cut_cfg, prompt, pools,
                               M.init_counters(cut_cfg), bt)
        assert np.abs(got - want).max() > 50 * TOL, drop


# -- through the engine -----------------------------------------------------------


def make_engine(params, cfg, **kw):
    return K.make_paged_engine(
        params, cfg, max_slots=4, page_size=PAGE, chunk=CHUNK, num_pages=64,
        window=4, prefix_cache=True, attn_block=BLOCK, **kw)


def drain(engine, want: set[str]) -> dict[str, list[int]]:
    out: dict[str, list[int]] = {}
    done: set[str] = set()
    for _ in range(200):
        for rid, token, fin in engine.step():
            out.setdefault(rid, []).append(token)
            if fin:
                done.add(rid)
        if want <= done:
            return out
    raise AssertionError(f"streams never finished: {want - done}")


def deficits(rp, cfg, prompt, emitted) -> float:
    """Largest gap between the top of the reference's teacher-forced
    logits and the logit of the token the engine emitted there."""
    ref = np.asarray(R.forward(rp, cfg, jnp.asarray(prompt + emitted)))
    rows = ref[len(prompt) - 1: len(prompt) - 1 + len(emitted)]
    return float((rows.max(-1) - rows[np.arange(len(emitted)), emitted]).max())


def test_engine_cold_hit_preempt_and_checkpoint_give_the_cold_logits(
        share, tmp_path, small_expert_blocks):
    """Through PagedBatchEngine, the K=4 window program and the prefix
    cache, unchanged: a cold run; the same prompt again, served from
    cached latent pages; a stream preempted after its first window and
    resumed by re-submitting prompt + emitted; a stream checkpointed
    mid-generation (checkpoint_state + save_pools) and restored into a
    second engine. Each emitted token is the reference's top at its
    position (its logit within TOL of the top), and the three later
    runs emit the cold run's tokens."""
    cfg, params, rp = share
    prompt = prompt_ids(41, seed=11)
    engine = make_engine(params, cfg)
    assert engine.kv_dtype == "fp"
    engine.submit("cold", prompt, 12)
    cold = drain(engine, {"cold"})["cold"]
    assert len(cold) == 12 and deficits(rp, cfg, prompt, cold) <= TOL

    engine.submit("hit", prompt, 12)
    assert engine.prefix_cache.hits == 1
    assert engine.prefix_cache.hit_tokens == 40  # 5 whole pages of 8
    assert drain(engine, {"hit"})["hit"] == cold

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
    assert state["kv_dtype"] == "fp"
    engine.save_pools(tmp_path / "pools")
    other = make_engine(params, cfg)
    other.restore_pools(tmp_path / "pools")
    assert other.restore_state(json.loads(json.dumps(state))) == ["saved"]
    assert first + drain(other, {"saved"})["saved"] == cold

    counters = engine.model_counters()
    assert counters["moe_tokens"] > 0 and len(counters["moe_expert_tokens"]) == 8
    assert sum(counters["moe_expert_tokens"]) == counters["moe_local_pairs"]
    assert 0 < counters["moe_experts_touched"] <= 8
    assert counters["latent_pool_bytes"] == 64 * PAGE * cfg.row * 4 * 3
    again = engine.model_counters()  # differences, not re-added totals
    assert again["moe_tokens"] == counters["moe_tokens"]
    # the counters are no part of the cache: its snapshot, its restore
    # and its byte count are the pages', and the engine that restored an
    # older snapshot counted its own rows alone (the decode ticks of the
    # tokens it emitted, over 2 expert layers)
    assert set(engine.pools) == {"0", "1", "2"}
    assert engine.kv_pool_bytes() == 64 * PAGE * cfg.row * 4 * 3
    assert other.model_counters()["moe_tokens"] == (12 - len(first)) * 2


def test_counters_count_the_prompt_rows_the_engine_names(share):
    """A prompt that ends in token 0 (what the tail chunk is padded with)
    counts whole: the engine says how many rows are real."""
    cfg, params, _ = share
    engine = make_engine(params, cfg)
    engine.submit("zeros", prompt_ids(18, seed=3) + [0, 0, 0], 1)
    drain(engine, {"zeros"})
    assert engine.model_counters()["moe_tokens"] == 21 * 2


def test_engine_refuses_what_latent_pages_do_not_offer(share, monkeypatch):
    cfg, params, _ = share
    monkeypatch.setenv("DORA_KV_INT8", "1")
    with pytest.raises(NotImplementedError, match="DORA_KV_INT8.*latent pages"):
        make_engine(params, cfg)


# -- loading ------------------------------------------------------------------------


def test_loader_maps_hf_names_and_reads_only_held_experts(ckpt, monkeypatch):
    read: list[str] = []
    get = K.TensorFiles.get
    monkeypatch.setattr(
        K.TensorFiles, "get",
        lambda self, name: (read.append(name), get(self, name))[1])
    cfg, params = K.load(shared_by(ckpt, 8), max_seq=64, ep_rank=3)
    assert (cfg.expert_first, cfg.experts_held, cfg.n_experts) == (12, 4, 32)
    experts = {int(n.split(".experts.")[1].split(".")[0])
               for n in read if ".experts." in n}
    assert experts == {12, 13, 14, 15}
    assert len(read) == len(set(read))  # every tensor once
    blk = params["blocks"]["1"]
    assert blk["experts"]["w_gateup"]["int8"].shape == (4, 64, 2 * 32)
    assert blk["experts"]["w_down"]["scale"].shape == (4, 1, 64)
    assert blk["router"].shape == (64, 32)
    assert blk["w_qkv_a"]["int8"].shape == (64, 128)  # 32 + 32 + 8, padded
    assert blk["w_kv_b"]["k8"].shape == (4, 16, 32)
    assert "dense" in params["blocks"]["0"] and "router" not in params["blocks"]["0"]
    # the tensors are HF's: q_b of layer 1, dequantized, is the file's
    from safetensors.numpy import load_file

    raw = load_file(str(ckpt / "model.safetensors"))
    want = raw["model.layers.1.self_attn.q_b_proj.weight"].T
    got = np.asarray(R.reference_params(params, cfg)["blocks"]["1"]["q_b"])
    assert np.abs(got - want).max() <= np.abs(want).max() / 127  # one int8 step
    up = raw["model.layers.2.mlp.experts.13.up_proj.weight"].T
    got = np.asarray(
        R.reference_params(params, cfg)["blocks"]["2"]["experts"][13]["up"])
    assert np.abs(got - up).max() <= np.abs(up).max() / 127


def test_expert_share_size_from_the_checkpoint_rank_from_the_launcher(
        monkeypatch):
    hf = {"n_routed_experts": 384, "ep_size": 32}
    assert M.expert_share(hf) == (0, 12)
    assert M.expert_share({"n_routed_experts": 384}) == (0, 384)
    monkeypatch.setenv("DORA_EP_RANK", "7")
    assert M.expert_share(hf) == (84, 12)
    assert M.expert_share(hf, ep_rank=5) == (60, 12)  # the argument wins
    with pytest.raises(ValueError, match="do not divide"):
        M.expert_share({**hf, "ep_size": 7})
    with pytest.raises(ValueError, match="rank 32"):
        M.expert_share(hf, ep_rank=32)


def test_unsupported_variants_are_refused_by_name():
    with pytest.raises(NotImplementedError, match="q_lora_rank"):
        K.KimiK2Config.from_hf({**TINY, "q_lora_rank": None})
    with pytest.raises(NotImplementedError, match="group-limited"):
        K.KimiK2Config.from_hf({**TINY, "n_group": 8, "topk_group": 4})
    with pytest.raises(ValueError, match="model_type"):
        K.KimiK2Config.from_hf({**TINY, "model_type": "qwen2"})


# -- llm_server's choice of model ---------------------------------------------------


def test_llm_server_picks_the_module_by_model_type():
    from dora_tpu.models.hf import qwen2
    from dora_tpu.nodehub import llm_server

    assert llm_server.model_module("qwen2") is qwen2
    assert llm_server.model_module(None) is qwen2  # pre-table checkpoints
    assert llm_server.model_module("kimi_k2") is K
    assert llm_server.model_module("deepseek_v3") is K
    with pytest.raises(RuntimeError, match="'llama'"):
        llm_server.model_module("llama")


@pytest.mark.parametrize("model_type", ["qwen2", "kimi_k2"])
def test_llm_server_main_calls_the_module_as_it_called_qwen2(
        tmp_path, monkeypatch, model_type):
    """main() reads model_type, then calls the module's ``load(path,
    max_seq=)``, ``quantize_decode(params, cfg)`` and, through
    make_engine, ``make_paged_engine`` with the arguments the Qwen path
    has always had."""
    from types import SimpleNamespace

    from dora_tpu.nodehub import llm_server

    (tmp_path / "config.json").write_text(json.dumps({"model_type": model_type}))
    calls: list = []
    cfg = SimpleNamespace(vocab=128, layers=1, dim=8)
    fake = SimpleNamespace(
        load=lambda path, max_seq=None: (
            calls.append(("load", path, max_seq)), (cfg, {"w": 1}))[1],
        quantize_decode=lambda params, c: (
            calls.append(("quantize_decode", params, c)), {"q": 1})[1],
        make_paged_engine=lambda params, c, **kw: (
            calls.append(("make_paged_engine", params, c, kw)),
            SimpleNamespace(free_pages=0))[1],
    )
    monkeypatch.setattr(
        llm_server, "model_module",
        lambda mt: (calls.append(("model_module", mt)), fake)[1])
    monkeypatch.setattr(llm_server, "serve", lambda *a, **kw: calls.append(("serve",)))
    monkeypatch.setattr(llm_server, "Node", lambda: None)
    monkeypatch.setenv("DORA_HF_CHECKPOINT", str(tmp_path))
    monkeypatch.setenv("DORA_MAX_SEQ", "64")
    for knob in ("DORA_BATCH_SLOTS", "DORA_PAGE_SIZE", "DORA_PREFILL_CHUNK",
                 "DORA_MULTISTEP_K", "DORA_PREFIX_CACHE",
                 "DORA_PREFIX_CACHE_PAGES"):
        monkeypatch.delenv(knob, raising=False)
    llm_server.main()
    assert calls[0] == ("model_module", model_type)
    assert calls[1] == ("load", str(tmp_path), 64)
    assert calls[2] == ("quantize_decode", {"w": 1}, cfg)
    name, params, c, kw = calls[3]
    assert (name, params, c) == ("make_paged_engine", {"q": 1}, cfg)
    assert kw == dict(max_slots=16, eos=None, page_size=16, chunk=None,
                      window=8, prefix_cache=True, prefix_cache_pages=0)
    assert calls[4] == ("serve",)


def test_serving_metrics_carry_the_model_counters():
    from dora_tpu.metrics import ServingMetrics

    m = ServingMetrics(engine="paged")
    assert "moe_tokens" not in m.snapshot()  # models without counters
    m.model = {"moe_tokens": 7, "moe_expert_tokens": [3, 4]}
    snap = m.snapshot()
    assert snap["moe_tokens"] == 7 and snap["moe_expert_tokens"] == [3, 4]
