"""Olmo-Hybrid at tiny widths: the config, the checkpoint writer, the
fixtures and the by-hand drive that ``tests/test_olmo_hybrid.py`` (the
programs against the reference) and ``tests/test_state_snapshot.py`` (the
engine's state snapshot) share; ``tests/program_text.py`` takes the
checkpoint from here too. Not a test module.

Tiny: hidden 64, 4 heads of 16 (4 K/V heads: one query row a K/V head, as
published), 4 delta-rule heads of 8 x 16 (d_v = 2 d_k, as published),
``beta`` doubled, 5 layers ``L L L F L`` (a linear layer reads a full
one's output too), page 8, chunk 32.
"""

from __future__ import annotations

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dora_tpu.models.hf import olmo_hybrid as O
from dora_tpu.models.hf import olmo_hybrid_reference as R

PAGE, CHUNK, BLOCK, K_TICKS, SLOTS, MAX_SEQ = 8, 32, 32, 4, 3, 256
KINDS = ["linear_attention"] * 3 + ["full_attention", "linear_attention"]

TINY = dict(
    model_type="olmo_hybrid", hidden_size=64, num_attention_heads=4,
    num_key_value_heads=4, intermediate_size=128, num_hidden_layers=5,
    vocab_size=256, rms_norm_eps=1e-6, max_position_embeddings=MAX_SEQ,
    hidden_act="silu", attention_bias=False, tie_word_embeddings=False,
    layer_types=KINDS, linear_num_key_heads=4, linear_num_value_heads=4,
    linear_key_head_dim=8, linear_value_head_dim=16,
    linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    rope_parameters={"rope_theta": None},
)


def write_checkpoint(path: Path, cfg: dict, seed: int = 0) -> None:
    """A float32 checkpoint under the HF names the loader reads."""
    from safetensors.numpy import save_file

    rng = np.random.default_rng(seed)
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = d // heads
    h, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    taps = cfg["linear_conv_kernel_dim"]
    t: dict[str, np.ndarray] = {}

    def w(out, inp, scale=None):
        return (rng.standard_normal((out, inp)) * (scale or inp ** -0.5)
                ).astype(np.float32)

    def vec(size, scale=1.0, mean=0.0):
        return (mean + scale * rng.standard_normal(size)).astype(np.float32)

    t["model.embed_tokens.weight"] = w(cfg["vocab_size"], d, 1.0)
    t["model.norm.weight"] = vec(d, 0.1, 1.0)
    t["lm_head.weight"] = w(cfg["vocab_size"], d)
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        t[p + "post_attention_layernorm.weight"] = vec(d, 0.1, 1.0)
        t[p + "post_feedforward_layernorm.weight"] = vec(d, 0.1, 1.0)
        if cfg["layer_types"][i] == "linear_attention":
            a = p + "linear_attn."
            for name, width in (("q", h * dk), ("k", h * dk), ("v", h * dv)):
                t[a + f"{name}_proj.weight"] = w(width, d)
                t[a + f"{name}_conv1d.weight"] = w(width, taps, 0.5).reshape(
                    width, 1, taps)
            t[a + "g_proj.weight"] = w(h * dv, d)
            t[a + "a_proj.weight"] = w(h, d)
            t[a + "b_proj.weight"] = w(h, d, 2.0 * d ** -0.5)
            t[a + "A_log"] = vec(h, 0.3, -1.0)
            t[a + "dt_bias"] = vec(h, 1.0)
            t[a + "o_norm.weight"] = vec(dv, 0.1, 1.0)
            t[a + "o_proj.weight"] = w(d, h * dv)
        else:
            a = p + "self_attn."
            kv = cfg["num_key_value_heads"] * hd
            t[a + "q_proj.weight"] = w(d, d, 2.0 * d ** -0.5)
            t[a + "k_proj.weight"] = w(kv, d, 2.0 * d ** -0.5)
            t[a + "v_proj.weight"] = w(kv, d)
            t[a + "o_proj.weight"] = w(d, d)
            t[a + "q_norm.weight"] = vec(d, 0.2, 1.5)
            t[a + "k_norm.weight"] = vec(kv, 0.2, 1.5)
        m = p + "mlp."
        t[m + "gate_proj.weight"] = w(cfg["intermediate_size"], d, 2.0 * d ** -0.5)
        t[m + "up_proj.weight"] = w(cfg["intermediate_size"], d, 2.0 * d ** -0.5)
        t[m + "down_proj.weight"] = w(d, cfg["intermediate_size"])
    path.mkdir(parents=True, exist_ok=True)
    save_file(t, str(path / "model.safetensors"))
    (path / "config.json").write_text(json.dumps(cfg))


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory) -> Path:
    path = tmp_path_factory.mktemp("olmo") / "ckpt"
    write_checkpoint(path, TINY)
    return path


@pytest.fixture(scope="module")
def model(ckpt):
    """(cfg, params, reference params)."""
    cfg, params = O.load(ckpt, max_seq=MAX_SEQ)
    return cfg, params, R.reference_params(params, cfg)


def prompt_ids(n: int, seed: int = 1) -> list[int]:
    return np.random.default_rng(seed).integers(1, 256, size=n).tolist()


def make_engine(cfg, params, **kw):
    kw = {"max_slots": SLOTS, "page_size": PAGE, "chunk": CHUNK,
          "window": K_TICKS, "attn_block": BLOCK, **kw}
    return O.make_paged_engine(params, cfg, **kw)


@functools.lru_cache(maxsize=None)
def programs(cfg):
    """The two programs as the engine jits them, but with logits where the
    greedy tokens would be (cfg is static; one trace a config)."""
    return (
        jax.jit(lambda p, *a: O.paged_chunk_logits(p, cfg, *a, block=BLOCK)),
        jax.jit(lambda p, *a: O.paged_batch_logits(p, cfg, *a)),
    )


class Served:
    """What the engine does, by hand, keeping the logits: pools, slot
    state and counters of ``SLOTS`` slots, each stream with pages of its
    own. ``dirty``: every cache leaf starts as an earlier stream left it
    (a chunk at position 0 must zero-start)."""

    def __init__(self, cfg, params, chunk: int = CHUNK, dirty: bool = True,
                 slots: int = SLOTS):
        self.cfg, self.params, self.chunk, self.slots = cfg, params, chunk, slots
        self.chunk_fn, self.tick_fn = programs(cfg)
        pages = slots * MAX_SEQ // PAGE + 1
        self.pools = O.init_page_pool(cfg, pages, PAGE)
        self.state = O.init_slot_state(cfg, slots)
        if dirty:
            self.state = jax.tree.map(lambda a: a + 3.0, self.state)
            self.pools = jax.tree.map(lambda a: a + 2.0, self.pools)
        self.stats = O.init_counters(cfg)
        per = MAX_SEQ // PAGE
        self.bts = np.zeros((slots, per), np.int32)
        for b in range(slots):
            self.bts[b] = 1 + b * per + np.arange(per)
        self.positions = np.zeros((slots,), np.int32)

    def prefill(self, slot: int, prompt: list[int], pad_id: int = 0,
                base0: int = 0):
        """Chunked prefill into ``slot`` from row ``base0`` on (the slot
        then holds the state at ``base0`` already); the logits of the
        rows that ran ``[T - base0, vocab]``."""
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
    return np.asarray(R.forward(rp, cfg, jnp.asarray(tokens), **switches))


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
