"""Kimi Linear at tiny widths: the config, the checkpoint writer, the
fixtures and the by-hand drive that ``tests/test_kimi_linear.py`` uses;
``tests/program_text.py`` takes the checkpoint from here too. Not a test
module.

Tiny: hidden 64, 4 latent heads of 16 + 8 shared key columns over a
16-wide latent (a stored row of 24 values padded to 128), 4 delta-rule
heads of 16, 5 layers ``K K K M K`` (published numbering from 1: a KDA
layer reads a latent one's output too) with layer 1 dense, 8 experts of
which 2 are held (rank 0 of 4), 2 a token, page 8, chunk 32.
"""

from __future__ import annotations

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dora_tpu.models.hf import kimi_linear as K
from dora_tpu.models.hf import kimi_linear_reference as R

PAGE, CHUNK, BLOCK, K_TICKS, SLOTS, MAX_SEQ = 8, 32, 32, 4, 3, 256
#: float32 summation order on logits of magnitude 4 (the blocked delta rule
#: against the recurrence, a running softmax against a whole one, int8
#: scales after the product or before): measured 1e-5 to 6e-5
TOL = 3e-4

TINY = dict(
    model_type="kimi_linear", hidden_size=64, num_attention_heads=4,
    num_key_value_heads=4, head_dim=16, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=5, vocab_size=256,
    rms_norm_eps=1e-5, model_max_length=MAX_SEQ, hidden_act="silu",
    kv_lora_rank=16, q_lora_rank=None, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, mla_use_nope=True, rope_scaling=None,
    linear_attn_config=dict(kda_layers=[1, 2, 3, 5], full_attn_layers=[4],
                            num_heads=4, head_dim=16,
                            short_conv_kernel_size=4),
    first_k_dense_replace=1, moe_layer_freq=1, num_experts=8, ep_size=4,
    num_experts_per_token=2, num_shared_experts=1, moe_renormalize=True,
    moe_router_activation_func="sigmoid", num_expert_group=1, topk_group=1,
    use_grouped_topk=True, routed_scaling_factor=2.446,
    tie_word_embeddings=False,
)


def write_checkpoint(path: Path, cfg: dict, seed: int = 0) -> None:
    """A float32 checkpoint under the HF names the loader reads, EVERY
    expert in it (a rank reads its own)."""
    from safetensors.numpy import save_file

    rng = np.random.default_rng(seed)
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, shared, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                       cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    lin = cfg["linear_attn_config"]
    hk, r, taps = (lin["num_heads"] * lin["head_dim"], lin["head_dim"],
                   lin["short_conv_kernel_size"])
    t: dict[str, np.ndarray] = {}

    def w(out, inp, scale=None):
        return (rng.standard_normal((out, inp)) * (scale or inp ** -0.5)
                ).astype(np.float32)

    def vec(size, scale=1.0, mean=0.0):
        return (mean + scale * rng.standard_normal(size)).astype(np.float32)

    def ffn(prefix, width, names=("gate_proj", "up_proj", "down_proj")):
        t[prefix + names[0] + ".weight"] = w(width, d, 2.0 * d ** -0.5)
        t[prefix + names[1] + ".weight"] = w(width, d, 2.0 * d ** -0.5)
        t[prefix + names[2] + ".weight"] = w(d, width)

    t["model.embed_tokens.weight"] = w(cfg["vocab_size"], d, 1.0)
    t["model.norm.weight"] = vec(d, 0.1, 1.0)
    t["lm_head.weight"] = w(cfg["vocab_size"], d)
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        t[p + "input_layernorm.weight"] = vec(d, 0.1, 1.0)
        t[p + "post_attention_layernorm.weight"] = vec(d, 0.1, 1.0)
        if i + 1 in lin["kda_layers"]:
            for name in "qkv":
                t[a + f"{name}_proj.weight"] = w(hk, d)
                t[a + f"{name}_conv1d.weight"] = w(hk, taps, 0.5).reshape(
                    hk, 1, taps)
            t[a + "f_a_proj.weight"] = w(r, d)
            t[a + "f_b_proj.weight"] = w(hk, r)
            t[a + "g_a_proj.weight"] = w(r, d)
            t[a + "g_b_proj.weight"] = w(hk, r)
            t[a + "b_proj.weight"] = w(lin["num_heads"], d, 2.0 * d ** -0.5)
            t[a + "A_log"] = vec(lin["num_heads"], 0.3, -0.5)
            t[a + "dt_bias"] = vec(hk, 1.0, -1.0)
            t[a + "o_norm.weight"] = vec(r, 0.1, 1.0)
            t[a + "o_proj.weight"] = w(d, hk)
        else:
            t[a + "q_proj.weight"] = w(h * (nope + shared), d, 3.0 * d ** -0.5)
            t[a + "kv_a_proj_with_mqa.weight"] = w(rank + shared, d)
            t[a + "kv_a_layernorm.weight"] = vec(rank, 0.1, 1.0)
            t[a + "kv_b_proj.weight"] = w(h * (nope + v), rank)
            t[a + "o_proj.weight"] = w(d, h * v)
        if i < cfg["first_k_dense_replace"]:
            ffn(p + "mlp.", cfg["intermediate_size"])
            continue
        m = p + "block_sparse_moe."
        t[m + "gate.weight"] = w(cfg["num_experts"], d, 2.0 * d ** -0.5)
        t[m + "gate.e_score_correction_bias"] = vec(cfg["num_experts"], 0.05)
        ffn(m + "shared_experts.", cfg["moe_intermediate_size"])
        for e in range(cfg["num_experts"]):
            ffn(f"{m}experts.{e}.", cfg["moe_intermediate_size"],
                ("w1", "w3", "w2"))
    path.mkdir(parents=True, exist_ok=True)
    save_file(t, str(path / "model.safetensors"))
    (path / "config.json").write_text(json.dumps(cfg))


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory) -> Path:
    path = tmp_path_factory.mktemp("kimi_linear") / "ckpt"
    write_checkpoint(path, TINY)
    return path


@pytest.fixture(scope="module")
def model(ckpt):
    """(cfg, params, reference params) of rank 0."""
    cfg, params = K.load(ckpt, max_seq=MAX_SEQ, ep_rank=0)
    return cfg, params, R.reference_params(params, cfg)


def held_of(cfg) -> range:
    return range(cfg.expert_first, cfg.expert_first + cfg.experts_held)


def prompt_ids(n: int, seed: int = 1) -> list[int]:
    return np.random.default_rng(seed).integers(1, 256, size=n).tolist()


def make_engine(cfg, params, **kw):
    kw = {"max_slots": SLOTS, "page_size": PAGE, "chunk": CHUNK,
          "window": K_TICKS, "attn_block": BLOCK, **kw}
    return K.make_paged_engine(params, cfg, **kw)


@functools.lru_cache(maxsize=None)
def programs(cfg):
    """The two programs as the engine jits them, but with logits where the
    greedy tokens would be (cfg is static; one trace a config and shape)."""
    return (
        jax.jit(lambda p, *a: K.paged_chunk_logits(p, cfg, *a, block=BLOCK)),
        jax.jit(lambda p, *a: K.paged_batch_logits(p, cfg, *a, block=BLOCK)),
    )


class Served:
    """What the engine does, by hand, keeping the logits: pools, slot
    state and counters of ``slots`` slots, each stream with pages of its
    own. ``dirty``: every cache leaf starts as an earlier stream left it
    (a chunk at position 0 must zero-start)."""

    def __init__(self, cfg, params, chunk: int = CHUNK, dirty: bool = True,
                 slots: int = SLOTS):
        self.cfg, self.params, self.chunk, self.slots = cfg, params, chunk, slots
        self.chunk_fn, self.tick_fn = programs(cfg)
        pages = slots * MAX_SEQ // PAGE + 1
        self.pools = K.init_page_pool(cfg, pages, PAGE)
        self.state = K.init_slot_state(cfg, slots)
        if dirty:
            self.state = jax.tree.map(lambda a: a + 3.0, self.state)
            self.pools = jax.tree.map(lambda a: a + 2.0, self.pools)
        self.stats = K.init_counters(cfg)
        per = MAX_SEQ // PAGE
        self.bts = np.zeros((slots, per), np.int32)
        for b in range(slots):
            self.bts[b] = 1 + b * per + np.arange(per)
        self.positions = np.zeros((slots,), np.int32)

    def prefill(self, slot: int, prompt: list[int], pad_id: int = 0,
                base0: int = 0):
        """Chunked prefill into ``slot`` from row ``base0`` on; the logits
        of the rows that ran ``[T - base0, vocab]``."""
        out = []
        for base in range(base0, len(prompt), self.chunk):
            piece = prompt[base : base + self.chunk]
            ids = piece + [pad_id] * (self.chunk - len(piece))
            logits, self.pools, self.state, self.stats = self.chunk_fn(
                self.params, jnp.asarray(ids, jnp.int32), self.pools,
                self.state, self.stats, jnp.asarray(base, jnp.int32),
                jnp.asarray(self.bts[slot]), jnp.asarray(len(piece), jnp.int32),
                jnp.asarray(slot, jnp.int32))
            out.append(np.asarray(logits)[: len(piece)])
        self.positions[slot] = len(prompt)
        return np.concatenate(out)

    def tick(self, tokens: dict[int, int]):
        """One decode tick: ``tokens`` = slot -> its next input token; the
        other rows are frozen (position 0, zeroed table row). -> slot ->
        logits [vocab]."""
        active = np.zeros((self.slots,), bool)
        toks = np.zeros((self.slots,), np.int32)
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
    return np.asarray(R.forward(rp, cfg, jnp.asarray(tokens),
                                held=held_of(cfg), **switches))


def run(engine, rid) -> list[int]:
    """Step until ``rid`` is done; its tokens."""
    out = []
    for _ in range(400):
        for r, tok, done in engine.step():
            if r == rid:
                out.append(tok)
                if done:
                    return out
    raise AssertionError(f"{rid} never finished")
