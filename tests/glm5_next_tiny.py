"""GLM-5.3-Flash at tiny widths: the config, the checkpoint writer, the
fixtures and the helpers that ``tests/test_glm5_next.py`` (the programs
against the reference) and ``tests/test_glm5_next_engine.py`` (the same
through ``PagedBatchEngine``) share; ``tests/program_text.py`` takes the
checkpoint from here too. Not a test module.

Tiny: hidden 64, 4 heads, ``index_topk`` 16 over ``index_kpool`` 4 (a row
picks 4 blocks), page 8, chunk 32, 8 experts of which 2 are held, 5
layers ``L | L L L D`` with layer 0 dense, 4 residual streams; ``hc_eps``
is 1e-2 here (1e-6 published), see ``tests/test_glm5_next.py``.
"""

from __future__ import annotations

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dora_tpu.models.hf import glm5_next as G
from dora_tpu.models.hf import glm5_next_reference as R

TOL = 3e-4
TOPK, KPOOL, PAGE, CHUNK, BLOCK, K_TICKS, SLOTS, MAX_SEQ = 16, 4, 8, 32, 16, 4, 3, 128
KINDS = ["linear_attention"] * 4 + ["deepseek_sparse_attention"]

TINY = dict(
    model_type="glm5_next_text", hidden_size=64, num_attention_heads=4,
    num_key_value_heads=4, head_dim=0, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=5, vocab_size=128,
    rms_norm_eps=1e-5, max_position_embeddings=MAX_SEQ,
    layer_types=KINDS, mlp_layer_types=["dense"] + ["sparse"] * 4,
    first_k_dense_replace=1, indexer_types=["full"] * 5,
    linear_attn_config={
        "num_heads": 4, "head_dim": 16, "short_conv_kernel_size": 4,
        "gate_lower_bound": -5, "kda_layers": [0, 1, 2, 3],
        "full_attn_layers": [4]},
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_head_dim=16,
    qk_rope_head_dim=0, v_head_dim=16, mla_use_nope=True,
    index_n_heads=2, index_head_dim=8, index_topk=TOPK, index_kpool=KPOOL,
    index_kpool_compress=True, index_kpool_always_select_tail=True,
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-2, mhc=True,
    n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
    routed_scaling_factor=2.5, norm_topk_prob=True, scoring_func="sigmoid",
    topk_method="noaux_tc", n_group=1, topk_group=1, swiglu_limit=1.0,
    ep_size=4, tie_word_embeddings=False, num_nextn_predict_layers=0,
    attention_bias=False,
)


def write_checkpoint(path: Path, cfg: dict, seed: int = 0) -> None:
    """A whole (all experts) float32 checkpoint under the HF names."""
    from safetensors.numpy import save_file

    rng = np.random.default_rng(seed)
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    lin = cfg["linear_attn_config"]
    kh, kd, taps = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    n = cfg["hc_mult"]
    t: dict[str, np.ndarray] = {}

    def w(out, inp, scale=None):
        return (rng.standard_normal((out, inp)) * (scale or inp ** -0.5)
                ).astype(np.float32)

    def vec(size, scale=1.0, mean=0.0):
        return (mean + scale * rng.standard_normal(size)).astype(np.float32)

    def ffn(prefix, width):
        t[prefix + "gate_proj.weight"] = w(width, d, 2.0 * d ** -0.5)
        t[prefix + "up_proj.weight"] = w(width, d, 2.0 * d ** -0.5)
        t[prefix + "down_proj.weight"] = w(d, width)

    t["model.embed_tokens.weight"] = w(cfg["vocab_size"], d, 1.0)
    t["model.norm.weight"] = vec(d, 0.1, 1.0)
    t["lm_head.weight"] = w(cfg["vocab_size"], d)
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        t[p + "input_layernorm.weight"] = vec(d, 0.1, 1.0)
        t[p + "post_attention_layernorm.weight"] = vec(d, 0.1, 1.0)
        for sub in ("attn", "ffn"):
            t[p + f"hc_{sub}_fn"] = w(2 * n + n * n, n * d)
            t[p + f"hc_{sub}_base"] = vec(2 * n + n * n, 0.5)
            t[p + f"hc_{sub}_scale"] = vec(3, 0.2, 1.0)
        a, m = p + "self_attn.", p + "mlp."
        if cfg["layer_types"][i] == "linear_attention":
            for name in "qkv":
                t[a + f"{name}_proj.weight"] = w(kh * kd, d)
                t[a + f"{name}_conv1d.weight"] = w(kh * kd, taps, 0.5).reshape(
                    kh * kd, 1, taps)
            t[a + "f_a_proj.weight"] = w(kd, d)
            t[a + "f_b_proj.weight"] = w(kh * kd, kd, 2.0 * kd ** -0.5)
            t[a + "g_a_proj.weight"] = w(kd, d)
            t[a + "g_b_proj.weight"] = w(kh * kd, kd)
            t[a + "b_proj.weight"] = w(kh, d)
            t[a + "A_log"] = vec(kh, 0.3)
            t[a + "dt_bias"] = vec(kh * kd, 1.0)
            t[a + "o_norm.weight"] = vec(kd, 0.1, 1.0)
            t[a + "o_proj.weight"] = w(d, kh * kd)
        else:
            qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
            nope, v = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
            ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
            t[a + "q_a_proj.weight"] = w(qr, d)
            t[a + "q_a_layernorm.weight"] = vec(qr, 0.1, 1.0)
            t[a + "q_b_proj.weight"] = w(h * nope, qr)
            t[a + "kv_a_proj_with_mqa.weight"] = w(kvr, d)
            t[a + "kv_a_layernorm.weight"] = vec(kvr, 0.1, 1.0)
            t[a + "kv_b_proj.weight"] = w(h * (nope + v), kvr)
            t[a + "o_proj.weight"] = w(d, h * v)
            t[a + "indexer.wq_b.weight"] = w(ih * idim, qr)
            t[a + "indexer.wk.weight"] = w(idim, d)
            t[a + "indexer.k_norm.weight"] = vec(idim, 0.1, 1.0)
            t[a + "indexer.k_norm.bias"] = vec(idim, 0.1)
            t[a + "indexer.weights_proj.weight"] = w(ih, d)
        if cfg["mlp_layer_types"][i] == "dense":
            ffn(m, cfg["intermediate_size"])
            continue
        t[m + "gate.weight"] = w(cfg["n_routed_experts"], d)
        t[m + "gate.e_score_correction_bias"] = vec(cfg["n_routed_experts"], 0.1)
        ffn(m + "shared_experts.", cfg["moe_intermediate_size"])
        for e in range(cfg["n_routed_experts"]):
            ffn(f"{m}experts.{e}.", cfg["moe_intermediate_size"])
    path.mkdir(parents=True, exist_ok=True)
    save_file(t, str(path / "model.safetensors"))
    (path / "config.json").write_text(json.dumps(cfg))


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory) -> Path:
    path = tmp_path_factory.mktemp("glm5") / "ckpt"
    write_checkpoint(path, TINY)
    return path


@pytest.fixture(scope="module")
def model(ckpt):
    """Rank 0's share (experts 0-1 of 8): (cfg, params, reference params)."""
    cfg, params = G.load(ckpt, max_seq=MAX_SEQ, ep_rank=0)
    return cfg, params, R.reference_params(params, cfg)


def prompt_ids(n: int, seed: int = 1) -> list[int]:
    return np.random.default_rng(seed).integers(1, 128, size=n).tolist()


def make_engine(cfg, params, **kw):
    kw = {"max_slots": SLOTS, "page_size": PAGE, "chunk": CHUNK,
          "window": K_TICKS, "attn_block": BLOCK, **kw}
    return G.make_paged_engine(params, cfg, **kw)


@functools.lru_cache(maxsize=None)
def programs(cfg, index_block: int = G.INDEX_BLOCK):
    """The two programs as the engine jits them, but with logits where
    the greedy tokens would be (cfg is static; one trace a config).
    ``index_block``: the positions of one block of a tick's scoring loop
    (the engine's covers ``MAX_SEQ`` in one)."""
    return (
        jax.jit(lambda p, *a: G.paged_chunk_logits(p, cfg, *a, block=BLOCK,
                                                   picks=True)),
        jax.jit(lambda p, *a: G.paged_batch_logits(
            p, cfg, *a, picks=True, index_block=index_block)),
    )


class Served:
    """What the engine does, by hand, keeping the logits: pools, slot
    state and counters of ``SLOTS`` slots, each stream with pages of its
    own. ``dirty``: every slot-state leaf starts as an earlier stream
    left it (a chunk at position 0 must zero-start)."""

    def __init__(self, cfg, params, chunk: int = CHUNK, dirty: bool = True,
                 slots: int = SLOTS, index_block: int = G.INDEX_BLOCK):
        self.cfg, self.params, self.chunk, self.slots = cfg, params, chunk, slots
        self.chunk_fn, self.tick_fn = programs(cfg, index_block)
        pages = slots * MAX_SEQ // PAGE + 1
        self.pools = G.init_page_pool(cfg, pages, PAGE)
        self.state = G.init_slot_state(cfg, slots)
        if dirty:
            self.state = jax.tree.map(lambda a: a + 3.0, self.state)
            self.pools = jax.tree.map(lambda a: a + 2.0, self.pools)
        self.stats = G.init_counters(cfg)
        per = MAX_SEQ // PAGE
        self.bts = np.zeros((slots, per), np.int32)
        for b in range(slots):
            self.bts[b] = 1 + b * per + np.arange(per)
        self.positions = np.zeros((slots,), np.int32)
        self.picked = {}  # slot -> [T, picked_blocks] of the chunks
        self.ticked = {}  # slot -> [[picked_blocks] a decode tick]
        self.look = None  # the last tick's look, every slot's row

    def prefill(self, slot: int, prompt: list[int], pad_id: int = 0):
        """Chunked prefill into ``slot``; the prompt's logits [T, vocab]."""
        out, picked = [], []
        for base in range(0, len(prompt), self.chunk):
            piece = prompt[base : base + self.chunk]
            ids = piece + [pad_id] * (self.chunk - len(piece))
            logits, self.pools, self.state, self.stats, picks = self.chunk_fn(
                self.params, jnp.asarray(ids, jnp.int32), self.pools,
                self.state, self.stats, jnp.asarray(base, jnp.int32),
                jnp.asarray(self.bts[slot]), jnp.asarray(len(piece), jnp.int32),
                jnp.asarray(slot, jnp.int32))
            out.append(np.asarray(logits)[: len(piece)])
            picked.append(np.asarray(picks[0]["picked"])[: len(piece)])
        self.positions[slot] = len(prompt)
        self.picked[slot] = np.concatenate(picked)
        return np.concatenate(out)

    def tick(self, tokens: dict[int, int]):
        """One decode tick: ``tokens`` = slot -> its next input token;
        the other rows are frozen (position 0, zeroed table row). ->
        slot -> logits [vocab]."""
        active = np.zeros((self.slots,), bool)
        toks = np.zeros((self.slots,), np.int32)
        for b, tok in tokens.items():
            active[b], toks[b] = True, tok
        pos = np.where(active, self.positions, 0).astype(np.int32)
        bts = np.where(active[:, None], self.bts, 0).astype(np.int32)
        logits, self.pools, self.state, self.stats, picks = self.tick_fn(
            self.params, jnp.asarray(toks), self.pools, self.state, self.stats,
            jnp.asarray(pos), jnp.asarray(bts), jnp.asarray(active))
        self.look = jax.tree.map(np.asarray, picks[0])
        for b in tokens:
            self.ticked.setdefault(b, []).append(self.look["picked"][b])
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
    return np.asarray(R.forward(rp, cfg, jnp.asarray(tokens), held=held_of(cfg),
                                **switches))


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
