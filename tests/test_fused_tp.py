"""Tensor-parallel fused decode tier (parallel/fused_tp.py).

Round-5 composition seam: the fused Pallas kernels must produce
token-identical output when sharded over a tp mesh — per-rank partial
sublayers psummed in f32, vocab-sharded argmax combined with the
first-index tie-break. Runs on the virtual 8-device CPU mesh.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dora_tpu.models import vlm
from dora_tpu.ops import decode_block as DB
from dora_tpu.parallel import make_mesh
from dora_tpu.parallel import fused_tp as FTP
from dora_tpu.models import layers as L


def _quantized_tiny(int4: bool = False):
    cfg = vlm.VLMConfig.tiny()
    if int4:
        # int4 row-sharding slices whole nibble-pack groups: wo's K
        # (heads*head_dim) and w_down's K (ffn) must tile into
        # group-multiples per rank — use a config shaped like real
        # checkpoints (group 128) instead of .tiny()'s K=64.
        cfg = vlm.VLMConfig(
            image_size=32, patch_size=8, vision_dim=32, vision_layers=1,
            vision_heads=2, vision_ffn=64, vocab=256, dim=256, layers=2,
            heads=4, kv_heads=2, ffn=512, max_seq=64,
        )
    params = vlm.init_params(jax.random.PRNGKey(0), cfg)
    env = "DORA_INT4_DECODE" if int4 else "DORA_INT8_DECODE"
    os.environ[env] = "1"
    try:
        q = vlm.quantize_decode(params)
    finally:
        os.environ.pop(env, None)
    return cfg, q


def _run_fused(cfg, params, caches, first, position, steps):
    """Reference: unsharded fused decode loop."""
    tokens = []
    token = first
    caches = jax.tree.map(jnp.copy, caches)
    pos = position
    for _ in range(steps):
        tokens.append(int(token[0]))
        token, caches = vlm.decode_step_fused(params, cfg, token, caches, pos)
        pos += 1
    return tokens


def _run_tp(cfg, params, caches, first, position, steps, mesh):
    tp_params = FTP.prepare_decode_params(
        params, mesh, heads=cfg.heads, kv_heads=cfg.kv_heads,
        head_dim=cfg.head_dim, layers=cfg.layers,
    )
    caches = FTP.shard_caches(jax.tree.map(jnp.copy, caches), mesh)
    cos_t, sin_t = L.rope_table(cfg.max_seq, cfg.head_dim)
    tokens = []
    token = first
    pos = position
    for _ in range(steps):
        tokens.append(int(token[0]))
        cos, sin = DB.rope_rows(cos_t, sin_t, pos, 1)
        nxt, caches = FTP.decode_pass_tp(
            tp_params, params["embed"][token].astype(L.compute_dtype()),
            caches, jnp.asarray(pos, jnp.int32), cos, sin,
            heads=cfg.heads, kv_heads=cfg.kv_heads, head_dim=cfg.head_dim,
            layers=cfg.layers, mesh=mesh,
        )
        token = nxt
        pos += 1
    return tokens


@pytest.mark.parametrize("int4", [False, True], ids=["int8", "int4"])
def test_tp2_token_identical(int4):
    cfg, params = _quantized_tiny(int4)
    assert FTP.tp_compatible(
        2, heads=cfg.heads, kv_heads=cfg.kv_heads, ffn=cfg.ffn,
        vocab=cfg.vocab,
    )
    image = jax.random.uniform(
        jax.random.PRNGKey(1), (1, cfg.image_size, cfg.image_size, 3)
    )
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 6), 0, cfg.vocab)
    logits, caches, position = jax.jit(
        lambda p, im, pr: vlm.prefill(p, cfg, im, pr)
    )(params, image, prompt)
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    ref = _run_fused(cfg, params, caches, first, position, steps=8)
    mesh = make_mesh(tp=2, devices=jax.devices()[:2])
    out = _run_tp(cfg, params, caches, first, position, 8, mesh)
    assert ref == out, (ref, out)


def test_tp8_token_identical_wide_config():
    """tp=8 over all virtual devices (kv_heads=8 so every axis tiles)."""
    cfg = vlm.VLMConfig(
        image_size=32, patch_size=8, vision_dim=32, vision_layers=1,
        vision_heads=2, vision_ffn=64, vocab=256, dim=128, layers=2,
        heads=8, kv_heads=8, ffn=256, max_seq=64,
    )
    params = vlm.init_params(jax.random.PRNGKey(0), cfg)
    os.environ["DORA_INT8_DECODE"] = "1"
    try:
        params = vlm.quantize_decode(params)
    finally:
        os.environ.pop("DORA_INT8_DECODE", None)
    assert FTP.tp_compatible(
        8, heads=cfg.heads, kv_heads=cfg.kv_heads, ffn=cfg.ffn,
        vocab=cfg.vocab,
    )
    image = jax.random.uniform(
        jax.random.PRNGKey(1), (1, cfg.image_size, cfg.image_size, 3)
    )
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 4), 0, cfg.vocab)
    logits, caches, position = jax.jit(
        lambda p, im, pr: vlm.prefill(p, cfg, im, pr)
    )(params, image, prompt)
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    ref = _run_fused(cfg, params, caches, first, position, steps=6)
    mesh = make_mesh(tp=8)
    out = _run_tp(cfg, params, caches, first, position, 6, mesh)
    assert ref == out, (ref, out)


def test_tp_chunk_pass_matches_unsharded():
    """The M-row (speculative verify) shape through the tp pass."""
    cfg, params = _quantized_tiny()
    image = jax.random.uniform(
        jax.random.PRNGKey(1), (1, cfg.image_size, cfg.image_size, 3)
    )
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 6), 0, cfg.vocab)
    _, caches, position = jax.jit(
        lambda p, im, pr: vlm.prefill(p, cfg, im, pr)
    )(params, image, prompt)
    chunk = jax.random.randint(jax.random.PRNGKey(3), (1, 5), 0, cfg.vocab)

    ref, _ = vlm.decode_chunk_fused(
        params, cfg, chunk, jax.tree.map(jnp.copy, caches), position
    )

    mesh = make_mesh(tp=2, devices=jax.devices()[:2])
    tp_params = FTP.prepare_decode_params(
        params, mesh, heads=cfg.heads, kv_heads=cfg.kv_heads,
        head_dim=cfg.head_dim, layers=cfg.layers,
    )
    sharded = FTP.shard_caches(jax.tree.map(jnp.copy, caches), mesh)
    cos_t, sin_t = L.rope_table(cfg.max_seq, cfg.head_dim)
    cos, sin = DB.rope_rows(cos_t, sin_t, position, 5)
    out, _ = FTP.decode_pass_tp(
        tp_params, params["embed"][chunk[0]].astype(L.compute_dtype()),
        sharded, jnp.asarray(position, jnp.int32), cos, sin,
        heads=cfg.heads, kv_heads=cfg.kv_heads, head_dim=cfg.head_dim,
        layers=cfg.layers, mesh=mesh,
    )
    assert np.asarray(ref).tolist() == np.asarray(out).tolist()


def test_make_vlm_serves_fused_tier_on_mesh(monkeypatch):
    """DORA_MESH serving rides the tp kernel tier and emits the same
    tokens as the single-device operator (the round-4 seam closed)."""
    monkeypatch.setenv("DORA_INT8_DECODE", "1")
    monkeypatch.setenv("DORA_MAX_NEW_TOKENS", "6")
    monkeypatch.delenv("DORA_MESH", raising=False)
    from dora_tpu.nodehub import ops as hub

    image = jax.random.uniform(jax.random.PRNGKey(7), (32, 32, 3))
    op_ref = hub.make_vlm()
    _, out_ref = op_ref.step(op_ref.init_state, {"image": image})

    monkeypatch.setenv("DORA_MESH", "tp=2")
    op_tp = hub.make_vlm()
    _, out_tp = op_tp.step(op_tp.init_state, {"image": image})
    assert (
        np.asarray(out_ref["tokens"]).tolist()
        == np.asarray(out_tp["tokens"]).tolist()
    )


def test_make_vlm_state_on_mesh_shares_no_buffer(monkeypatch):
    """The executor donates the whole operator state on the chip, and the
    chip refuses one buffer donated twice (first four-chip run, PR 21:
    the tp tree's replicated norms and scales were the params' own
    buffers). No buffer may appear twice in the placed state."""
    monkeypatch.setenv("DORA_INT8_DECODE", "1")
    monkeypatch.setenv("DORA_MESH", "tp=2")  # dp takes the other devices
    from dora_tpu.nodehub import ops as hub
    from dora_tpu.parallel.mesh import shard_params
    from dora_tpu.tpu.fuse import mesh_from_env

    op = hub.make_vlm()
    assert set(op.init_state) == {"lm", "tp"}
    state = shard_params(op.init_state, mesh_from_env(), op.sharding)
    seen = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(state):
        for shard in leaf.addressable_shards:
            key = (shard.device.id, shard.data.unsafe_buffer_pointer())
            name = jax.tree_util.keystr(path)
            assert key not in seen, f"{name} shares a buffer with {seen[key]}"
            seen[key] = name


def test_make_vlm_on_a_mesh_tp_cannot_tile_says_so(monkeypatch, caplog):
    """tp=4 over 2 KV heads has no kernel tier: the node must not put
    Pallas kernels into a program XLA partitions (the chip's compiler
    refuses that), so it serves float weights — and says so."""
    monkeypatch.setenv("DORA_INT8_DECODE", "1")
    monkeypatch.setenv("DORA_MAX_NEW_TOKENS", "2")
    monkeypatch.setenv("DORA_MESH", "tp=4")
    from dora_tpu.models import vlm
    from dora_tpu.nodehub import ops as hub

    with caplog.at_level("WARNING"):
        op = hub.make_vlm()
    assert "no tensor-parallel kernel tier" in caplog.text
    assert set(op.init_state) == {"lm"}
    assert not vlm.fused_decode_ready(op.init_state["lm"])


def test_kernel_call_sites_take_the_xla_twin_in_a_partitioned_program():
    from dora_tpu import backend
    from dora_tpu.models import layers as L
    from dora_tpu.ops.int8_matmul import quantize_int8
    from dora_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(dp=2, tp=2, devices=jax.devices()[:4])
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64))
    w = quantize_int8(jax.random.normal(jax.random.PRNGKey(1), (64, 128)), False)
    seen = {}

    @jax.jit
    def f(x, w):
        seen["partitioned"] = backend.partitioned_by_xla()
        seen["flash"] = L.use_flash()
        return L.matmul(x, w)

    want = f(x, w)
    assert seen == {"partitioned": False, "flash": False}
    with jax.set_mesh(mesh):
        f.clear_cache()
        got = f(x, w)
        text = f.lower(x, w).as_text()
    assert seen["partitioned"] is True
    assert "pallas" not in text and "custom_call" not in text
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_tp_incompatible_shapes_gate():
    assert not FTP.tp_compatible(8, heads=12, kv_heads=2, ffn=8960,
                                 vocab=151936)
    assert FTP.tp_compatible(2, heads=12, kv_heads=2, ffn=8960,
                             vocab=151936)
    assert not FTP.tp_compatible(1, heads=12, kv_heads=2, ffn=8960,
                                 vocab=151936)
