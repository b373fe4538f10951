"""Kimi Linear on the paged serving path (models/hf/kimi_linear.py: KDA
layers as per-slot state, the latent layer's rows in pages swept whole,
``moe.py``'s expert layer with a rank's share) against its plain float32
reference (models/hf/kimi_linear_reference.py: whole sequence, the delta
rule a token at a time, full multi-head attention, no cache), at tiny
widths on the CPU, from seeded weights (tests/kimi_linear_tiny.py). Logits
are compared, not sampled tokens. On the CPU the serving path computes in
float32 too, so ``TOL`` is float32 summation order.
"""

from __future__ import annotations

import json
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dora_tpu.models import moe
from dora_tpu.models import paged_model as PM
from dora_tpu.models.hf import glm5_next_reference as GR
from dora_tpu.models.hf import kimi_linear as K
from dora_tpu.models.hf import kimi_linear_reference as R
from tests.kimi_linear_tiny import (  # noqa: F401  (ckpt, model: fixtures)
    BLOCK, CHUNK, MAX_SEQ, PAGE, SLOTS, TINY, TOL, Served, ckpt, held_of,
    make_engine, model, prompt_ids, reference_logits, run,
)

# -- (a) state and pages against the whole forward pass ----------------------------


@pytest.mark.parametrize("n,chunk", [
    (5, CHUNK),    # under one chunk, under the convolution's reach + 2
    (37, CHUNK),   # a ragged second chunk
    (64, CHUNK),   # the chunks' edges and the prompt's end on a page
    (75, CHUNK),   # a ragged third chunk
    (45, 8),       # chunks of one page: every chunk edge is a page's
])
def test_chunked_prefill_then_decode_matches_the_reference(model, n, chunk):
    cfg, params, _ = model
    prompt, emitted = prompt_ids(n, seed=n), prompt_ids(11, seed=100 + n)
    got = Served(cfg, params, chunk).serve(1, prompt, emitted)
    want = reference_logits(model, prompt + emitted)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("slots,lengths", [
    (1, [41]),
    (3, [9, 70, 33]),
    (8, [5, 64, 17, 90, 33, 48, 75, 12]),
], ids=["1 slot", "3 slots", "8 slots"])
def test_streams_of_mixed_lengths_decode_side_by_side(model, slots, lengths):
    """Each slot prefilled in chunks, then all of them decoding in the SAME
    ticks (one frozen for the first three where there are several): every
    slot's logits are its own sequence's whole forward pass."""
    cfg, params, _ = model
    served = Served(cfg, params, slots=slots)
    prompts = [prompt_ids(n, seed=200 + n) for n in lengths]
    forced = [prompt_ids(7, seed=300 + n) for n in lengths]
    got = [[served.prefill(b, p)] for b, p in enumerate(prompts)]
    late = slots - 1 if slots > 1 else None
    for step in range(7 + 3):
        tokens = {}
        for b in range(slots):
            at = step - (3 if b == late else 0)
            if 0 <= at < 7:
                tokens[b] = forced[b][at]
        for b, row in served.tick(tokens).items():
            got[b].append(row[None])
    for b in range(slots):
        want = reference_logits(model, prompts[b] + forced[b])
        assert np.abs(np.concatenate(got[b]) - want).max() < TOL, b


# -- (b) each switch, flipped, fails the same limit ---------------------------------


@pytest.mark.parametrize("switch,moves", [
    ("bounded_gate", 100), ("drop_shared_columns", 100),
    # the controls: one precision below the stated one
    ("state_bf16", 10), ("router_bf16", 10),
])
def test_a_flipped_switch_fails_the_tolerance(model, switch, moves):
    cfg, params, _ = model
    prompt, emitted = prompt_ids(75, seed=75), prompt_ids(11, seed=175)
    got = Served(cfg, params).serve(1, prompt, emitted)
    assert np.abs(got - reference_logits(model, prompt + emitted)).max() < TOL
    flipped = reference_logits(model, prompt + emitted, **{switch: True})
    assert np.abs(got - flipped).max() > moves * TOL


def test_unknown_switches_are_refused(model):
    assert set(R.SWITCHES) == {"bounded_gate", "drop_shared_columns",
                               "state_bf16", "router_bf16"}
    with pytest.raises(TypeError, match="no_such"):
        reference_logits(model, [1, 2, 3], no_such=True)


def test_the_published_gate_is_glms_reference_switch_on_shared_inputs(model):
    """``g = -exp(A_log) softplus(W_fb W_fa u + dt_bias)``: GLM-5.3-Flash's
    reference computes it under its switch ``softplus_gate`` (never its
    served program); on the same rows and the same matrices the two
    references' KDA mixers agree, and GLM's bounded gate is this file's
    ``bounded_gate``."""
    cfg, _, rp = model
    p = rp["blocks"]["0"]
    x = jnp.asarray(np.random.default_rng(5).standard_normal((40, cfg.dim)),
                    jnp.float32)
    glm = SimpleNamespace(kda_heads=cfg.kda_heads, kda_dim=cfg.kda_dim,
                          conv=cfg.conv, norm_eps=cfg.norm_eps,
                          gate_lower=R.GATE_LOWER)
    with jax.default_matmul_precision("highest"):
        for theirs, ours in ((True, False), (False, True)):
            want, s_want, _ = GR.kda(p, glm, x, {"softplus_gate": theirs})
            got, s_got, _ = R.kda(p, cfg, x, {**R.AS_SERVED,
                                              "bounded_gate": ours})
            assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-6
            assert np.abs(np.asarray(s_got) - np.asarray(s_want)).max() < 1e-6
        a, _, _ = R.kda(p, cfg, x, R.AS_SERVED)
        b, _, _ = R.kda(p, cfg, x, {**R.AS_SERVED, "bounded_gate": True})
    assert np.abs(np.asarray(a) - np.asarray(b)).max() > 0.01


# -- (c) the shares add up -----------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer(ckpt):
    """The routed parts of all ``ep_size`` shares plus the shared expert
    once equal the uncut reference's expert layer, in the program
    (``moe.mlp`` under this config) and in the reference."""
    x = jnp.asarray(np.random.default_rng(3).standard_normal((24, 64)),
                    jnp.float32)
    live = jnp.ones((24,), bool)
    parts, shared = [], None
    for rank in range(4):
        cfg, params = K.load(ckpt, max_seq=MAX_SEQ, ep_rank=rank)
        assert (cfg.expert_first, cfg.experts_held) == (2 * rank, 2)
        blk = params["blocks"]["1"]
        both, _ = moe.mlp(blk, cfg, x, live, live)
        shared = moe.swiglu(blk["shared"], x)
        parts.append(np.asarray(both - shared))
        rp = R.reference_params(params, cfg)["blocks"]["1"]
        with jax.default_matmul_precision("highest"):
            assert np.abs(np.asarray(R.moe(rp, cfg, x, held_of(cfg)))
                          - np.asarray(both)).max() < TOL
    whole_dir = ckpt.with_name("ckpt-ep1")
    whole_dir.mkdir(exist_ok=True)
    (whole_dir / "model.safetensors").symlink_to(ckpt / "model.safetensors")
    (whole_dir / "config.json").write_text(json.dumps({**TINY, "ep_size": 1}))
    cfg, params = K.load(whole_dir, max_seq=MAX_SEQ)
    assert (cfg.expert_first, cfg.experts_held) == (0, 8)
    rp = R.reference_params(params, cfg)["blocks"]["1"]
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(R.moe(rp, cfg, x))
    assert np.abs(sum(parts) + np.asarray(shared) - whole).max() < TOL
    assert sum(np.abs(p).max() > 0.01 for p in parts) >= 3


def test_a_batch_of_64_rows_goes_to_its_experts_whole(ckpt):
    """64 slots' decode tick: the rows go to every expert they touched in
    two grouped products a layer (``moe.WHOLE_ROWS``), and give what the
    chunk's form (an expert's own rows, ``EXPERT_BLOCK`` at a time) gives
    for the same rows; a frozen row gets zeros from the routed sum."""
    cfg, params = K.load(ckpt, max_seq=MAX_SEQ, ep_rank=0)
    blk = params["blocks"]["2"]
    x = jnp.asarray(np.random.default_rng(4).standard_normal((64, 64)),
                    jnp.float32)
    live = jnp.asarray(np.arange(64) % 5 != 0)
    assert moe.EXPERT_BLOCK < 64 <= moe.WHOLE_ROWS
    ids, weights = moe.route(blk, cfg, x)
    local = ids - cfg.expert_first
    whole = moe.held_experts(blk, cfg, x, local, weights, live)
    text = str(jax.make_jaxpr(
        lambda x: moe.held_experts(blk, cfg, x, local, weights, live))(x))
    assert text.count("pallas_call") == 2 and "while" not in text
    # the chunk's form: the same rows in a batch too long to go whole
    pad = jnp.zeros((64, 64), jnp.float32)
    long = moe.held_experts(
        blk, cfg, jnp.concatenate([x, pad]),
        jnp.concatenate([local, jnp.full_like(local, -1)]),
        jnp.concatenate([weights, weights]),
        jnp.concatenate([live, jnp.zeros((64,), bool)]))
    assert np.abs(np.asarray(whole) - np.asarray(long[:64])).max() < TOL
    assert np.abs(np.asarray(whole)[::5]).max() == 0.0
    assert np.abs(np.asarray(whole)).max() > 0.01


# -- (d) what must leave state, tail and pool untouched -----------------------------


def test_padding_rows_and_frozen_rows_leave_every_cache_untouched(model):
    cfg, params, _ = model
    served = Served(cfg, params)
    served.prefill(0, prompt_ids(37, seed=31))
    before = jax.tree.map(np.asarray, (served.pools, served.state))
    # slot 2 decodes; slot 0 is frozen and its state, tail and pages stay
    served.prefill(2, prompt_ids(20, seed=32))
    mine = jax.tree.map(lambda a: np.asarray(a[0]), served.state)
    served.tick({2: 7})
    served.tick({2: 9})
    after = jax.tree.map(lambda a: np.asarray(a[0]), served.state)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(after)):
        assert (a == b).all()
    per = MAX_SEQ // PAGE
    for key in served.pools:
        kv = np.asarray(served.pools[key]["kv"])
        assert (kv[1 : 1 + per] == before[0][key]["kv"][1 : 1 + per]).all()
    # a chunk's padding rows neither decay nor write: the state after 37
    # rows in chunks of 32 is the state after 37 rows in chunks of 8
    other = Served(cfg, params, chunk=8)
    other.prefill(0, prompt_ids(37, seed=31))
    for key in cfg.kda_layers:
        a = before[1][str(key)]["s"][0]
        b = np.asarray(other.state[str(key)]["s"][0])
        assert np.abs(a - b).max() < 1e-5


def test_the_state_a_chunk_leaves_is_the_references_float32(model):
    cfg, params, rp = model
    prompt = prompt_ids(70, seed=21)
    served = Served(cfg, params)
    served.prefill(1, prompt)
    _, kept = R.forward(rp, cfg, jnp.asarray(prompt), held=held_of(cfg),
                        rows=True)
    for i in cfg.kda_layers:
        st = served.state[str(i)]
        assert st["s"].dtype == jnp.float32
        assert np.abs(np.asarray(st["s"][1]) - np.asarray(kept[i]["s"])).max() < 1e-4
        assert np.abs(np.asarray(st["conv"][1])
                      - np.asarray(kept[i]["pre"][-3:])).max() < 1e-4
    for i in cfg.mla_layers:
        pages = served.bts[1][: -(-len(prompt) // PAGE)]
        rows = np.asarray(served.pools[str(i)]["kv"][pages]).reshape(
            -1, cfg.row)[: len(prompt)]
        assert np.abs(rows[:, : cfg.latent] - np.asarray(kept[i]["kv"])).max() < 1e-4
        assert (rows[:, cfg.latent :] == 0).all()  # the stored row's padding


def test_the_counters_count_rows_chunks_and_swept_rows(model):
    cfg, params, _ = model
    engine = make_engine(cfg, params, prefix_cache=False)
    engine.submit("a", prompt_ids(70, seed=41), 9)
    engine.submit("b", prompt_ids(20, seed=42), 5)
    while engine.active:
        engine.step()
    got = engine.model_counters()
    assert got["kda_chunks"] == 3 + 1 and got["kda_chunk_rows"] == 90
    assert got["gdn_chunk_rows"] == got["kda_chunk_rows"]
    # 8 + 4 decode tokens past each stream's first, a KDA layer each
    assert got["kda_row_ticks"] == len(cfg.kda_layers) * (8 + 4)
    assert 8 <= got["kda_decode_ticks"] <= 12
    assert got["mla_rows_in_context"] == sum(range(71, 79)) + sum(range(21, 25))
    # every tick sweeps all 3 slots to the longest row's last block
    assert got["mla_rows_swept"] >= got["mla_rows_in_context"]
    assert got["mla_rows_swept"] % (SLOTS * BLOCK) == 0
    assert got["mla_chunk_rows_in_context"] == sum(range(1, 71)) + sum(range(1, 21))
    assert got["moe_tokens"] == 4 * (90 + 12)
    assert got["kv_bytes_per_token"] == cfg.kv_bytes_per_token
    assert got["kda_state_bytes"] == cfg.state_bytes_per_slot * SLOTS
    assert "state_snapshots_saved" not in got


# -- (e) the engine: preempt, the prefix cache, the front door ----------------------


def test_preempt_and_readmit_mid_decode_give_the_same_tokens(model):
    cfg, params, _ = model
    prompt = prompt_ids(45, seed=51)
    engine = make_engine(cfg, params)
    engine.submit("r", prompt, 14)
    want = run(engine, "r")
    engine = make_engine(cfg, params)
    engine.submit("r", prompt, 14)
    head = []
    while len(head) < 5:
        head += [tok for _r, tok, _d in engine.step()]
    meta = engine.preempt("r")
    assert engine.active == 0 and meta["was_decoding"]
    # another stream dirties the slot's state, then the first comes back as
    # a server resumes it: prompt + what it had emitted, the rest to come
    engine.submit("other", prompt_ids(40, seed=52), 6)
    assert len(run(engine, "other")) == 6
    engine.submit("r", prompt + head, 14 - len(head))
    assert head + run(engine, "r") == want
    engine.check_invariants()


def test_a_shared_system_prompt_is_granted_from_its_branch_snapshot(model):
    """This model's engine with the prefix cache on: the third request that
    shares a system prompt starts at the branch edge and emits what a cold
    engine emits."""
    cfg, params, _ = model
    system = prompt_ids(80, seed=61)
    prompts = [system + prompt_ids(21, seed=62 + i) for i in range(3)]
    cold = make_engine(cfg, params, prefix_cache=False)
    want = []
    for i, p in enumerate(prompts):
        cold.submit(f"c{i}", p, 6)
        want.append(run(cold, f"c{i}"))
    engine = make_engine(cfg, params, prefix_cache=True)
    got = []
    for i, p in enumerate(prompts):
        engine.submit(f"w{i}", p, 6)
        slot = next(s for s in engine.slots if s is not None)
        assert slot.chunk_base == (64 if i == 2 else 0)
        got.append(run(engine, f"w{i}"))
        engine.check_invariants()
    assert got == want
    stats = engine.model_counters()
    assert stats["state_snapshots_branch_saved"] == 1
    assert stats["state_snapshots_restored"] == 1
    assert stats["state_snapshot_pool_bytes"] == (
        cfg.state_bytes_per_slot * 2 * SLOTS)


@pytest.mark.parametrize("knob", sorted(K.NOT_OFFERED))
def test_knobs_of_the_qwen_path_are_refused_by_name(model, monkeypatch, knob):
    cfg, params, _ = model
    monkeypatch.setenv(knob, "1")
    with pytest.raises(NotImplementedError, match=knob):
        make_engine(cfg, params)


@pytest.mark.parametrize("change,error,match", [
    ({"linear_attn_config": {**TINY["linear_attn_config"],
                             "kda_layers": [1, 2, 3]}}, ValueError,
     "1..5 once each"),
    ({"q_lora_rank": 32}, NotImplementedError, "q_lora_rank 32"),
    ({"mla_use_nope": False}, NotImplementedError, "mla_use_nope"),
    ({"rope_scaling": {"type": "yarn"}}, NotImplementedError, "rope_scaling"),
    ({"num_expert_group": 4}, NotImplementedError, "group-limited"),
    ({"moe_router_activation_func": "softmax"}, NotImplementedError, "softmax"),
    ({"tie_word_embeddings": True}, NotImplementedError, "tied"),
    ({"model_type": "kimi_k2"}, ValueError, "kimi_k2"),
])
def test_unsupported_variants_are_refused_by_name(change, error, match):
    with pytest.raises(error, match=match):
        K.KimiLinearConfig.from_hf({**TINY, **change})


def test_llm_server_knows_the_family(ckpt):
    from dora_tpu.models.hf.loader import read_config
    from dora_tpu.nodehub import llm_server

    module = llm_server.model_module(read_config(ckpt)["model_type"])
    assert module is K
    with pytest.raises(RuntimeError, match="kimi_linear") as err:
        llm_server.model_module("kimi_linear_v2")
    assert "kimi_linear_v2" in str(err.value)


def test_llm_server_builds_the_engine_with_the_prefix_cache_on(model, monkeypatch):
    from dora_tpu.nodehub import llm_server

    cfg, params, _ = model
    for key, value in {"DORA_BATCH_SLOTS": "5", "DORA_PAGE_SIZE": "8",
                       "DORA_PREFILL_CHUNK": "32", "DORA_MULTISTEP_K": "4"}.items():
        monkeypatch.setenv(key, value)
    monkeypatch.delenv("DORA_PREFIX_CACHE", raising=False)
    engine = llm_server.make_engine(params, cfg, module=K)
    assert engine.max_slots == 5
    assert engine.prefix_cache is not None and engine.snapshot_pool is not None
    assert engine.prefix_cache.snapshots == 2 * 5
    monkeypatch.setenv("DORA_PREFIX_CACHE", "0")
    engine = llm_server.make_engine(params, cfg, module=K)
    assert engine.prefix_cache is None and engine.snapshot_pool is None


# -- (f) the weights: the held experts alone, int8 within a step of the file --------


def test_the_loader_reads_the_held_experts_alone_and_int8_is_a_step_from_the_file(
        ckpt, monkeypatch):
    read = []
    get = K.TensorFiles.get
    monkeypatch.setattr(
        K.TensorFiles, "get",
        lambda self, name: (read.append(name), get(self, name))[1])
    cfg, params = K.load(ckpt, max_seq=MAX_SEQ, ep_rank=3)
    assert (cfg.expert_first, cfg.experts_held, cfg.n_experts) == (6, 2, 8)
    experts = {int(n.split(".experts.")[1].split(".")[0])
               for n in read if ".experts." in n}
    assert experts == {6, 7} and len(read) == len(set(read))
    assert cfg.linear == (True, True, True, False, True)
    assert cfg.sparse == (False, True, True, True, True)
    blk = params["blocks"]["3"]
    # 4 x (16 + 8) query columns, then the 24-wide cached row stored as 128
    assert blk["w_in"]["int8"].shape == (64, 96 + 128)
    assert blk["w_kv_b"]["k8"].shape == (4, 16, 16)
    assert blk["router"].shape == (64, 8)
    assert blk["experts"]["w_gateup"]["int8"].shape == (2, 64, 2 * 32)
    from safetensors.numpy import load_file

    raw = load_file(str(ckpt / "model.safetensors"))
    rp = R.reference_params(params, cfg)["blocks"]
    for got, name in (
            (rp["3"]["wq"], "model.layers.3.self_attn.q_proj.weight"),
            (rp["0"]["wfb"], "model.layers.0.self_attn.f_b_proj.weight"),
            (rp["2"]["experts"][7]["up"],
             "model.layers.2.block_sparse_moe.experts.7.w3.weight")):
        want = raw[name].T
        # one int8 step of the column's own scale
        step = np.abs(want).max(0, keepdims=True) / 127
        assert (np.abs(np.asarray(got) - want) <= step * 0.5 + 1e-7).all(), name


def test_int8_logits_lie_within_the_stated_band_of_the_unquantized_weights(
        ckpt, model):
    """The served program (int8 per output channel) against the reference
    whose mixers and head hold the checkpoint's OWN float32 matrices, as
    rms error over rms (a maximum reads the one row whose second expert
    changed: 1.4): under 0.12, measured 0.066 at these widths, where a
    column has 64 inputs. Quantization, not arithmetic: against the dequantized weights
    the difference is ``TOL``."""
    from safetensors.numpy import load_file

    cfg, params, rp = model
    raw = load_file(str(ckpt / "model.safetensors"))
    exact = jax.tree.map(lambda a: a, rp)  # a copy of the tree, same leaves
    exact["lm_head"] = jnp.asarray(raw["lm_head.weight"].T)
    for i in range(cfg.layers):
        a = f"model.layers.{i}.self_attn."
        p = exact["blocks"][str(i)]
        p["wo"] = jnp.asarray(raw[a + "o_proj.weight"].T)
        if cfg.linear[i]:
            for name in ("q", "k", "v"):
                p["w" + name] = jnp.asarray(raw[a + f"{name}_proj.weight"].T)
    prompt, emitted = prompt_ids(50, seed=71), prompt_ids(6, seed=72)
    got = Served(cfg, params).serve(0, prompt, emitted)
    want = np.asarray(R.forward(exact, cfg, jnp.asarray(prompt + emitted),
                                held=held_of(cfg)))
    band = float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))
    assert 1e-3 < band < 0.12


def test_the_published_cut_in_bytes():
    """The numbers the configuration's file and ``PERF.md`` state, from the
    config class at the published widths and the cell's cut."""
    kda = [1, 2, 3, 5, 6, 7, 9]
    cfg = K.KimiLinearConfig.from_hf({
        **TINY, "hidden_size": 2304, "num_attention_heads": 32,
        "intermediate_size": 9216, "moe_intermediate_size": 1024,
        "num_hidden_layers": 9, "vocab_size": 40960, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "num_experts": 256, "num_experts_per_token": 8,
        "linear_attn_config": {"kda_layers": kda, "full_attn_layers": [4, 8],
                               "num_heads": 32, "head_dim": 128,
                               "short_conv_kernel_size": 4}}, max_seq=16384)
    item = jnp.dtype(K.L.compute_dtype()).itemsize  # 4 on the CPU, 2 on the chip
    assert (cfg.experts_held, cfg.latent, cfg.row) == (64, 576, 640)
    assert cfg.softmax_scale == 192 ** -0.5
    assert cfg.kv_bytes_per_token == 2 * 640 * item
    assert 32 * 128 * 128 * 4 == 2_097_152
    assert cfg.state_bytes_per_slot == 7 * (2_097_152 + 3 * 12288 * item)
    snapshot = 7 * (2_097_152 + 3 * 12288 * 2)
    assert snapshot == 15_196_160
    # 64 slots: the floor of two rows a slot binds
    left = 16_909_336_064 - 4_500_000_000 - 64 * snapshot - PM.POOL_HEADROOM_BYTES
    assert PM.snapshots_that_fit(snapshot, left, 64) == 128
    assert cfg.moe_layers == 8 and len(cfg.mla_layers) == 2
