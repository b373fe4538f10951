"""Pallas kernel parity tests (interpreter on CPU; compiled on TPU).

flash_attention must match the dense reference attention bit-for-tolerance
across aligned and unaligned shapes, causal and full.
"""

from __future__ import annotations

import math

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from dora_tpu.models import layers as L
from dora_tpu.ops import flash_attention


def dense_reference(q, k, v, causal: bool):
    mask = L.causal_mask(q.shape[2], k.shape[2]) if causal else None
    return L.attention(q, k, v, mask)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize(
    "b,h,t,d",
    [
        (1, 2, 128, 128),   # exactly one block, aligned
        (2, 4, 256, 64),    # multiple blocks, lane-padded D
        (1, 2, 272, 80),    # bench ViT shape: both axes unaligned
        (1, 1, 100, 128),   # T below one block
    ],
)
def test_flash_matches_dense(b, h, t, d, causal):
    key = jax.random.PRNGKey(hash((b, h, t, d, causal)) % (2**31))
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, h, t, d), jnp.float32)
    k = jax.random.normal(kk, (b, h, t, d), jnp.float32)
    v = jax.random.normal(kv, (b, h, t, d), jnp.float32)

    ours = flash_attention(q, k, v, causal=causal)
    ref = dense_reference(q, k, v, causal)
    np.testing.assert_allclose(
        np.asarray(ours), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_flash_bfloat16_io():
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 2, 128, 64), jnp.bfloat16)
    out = flash_attention(q, q, q, causal=True)
    assert out.dtype == jnp.bfloat16
    ref = dense_reference(q, q, q, True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=2e-2, rtol=2e-2,
    )


def test_vlm_loss_matches_with_flash(monkeypatch):
    """DORA_FLASH_ATTENTION=1 routes the VLM's no-cache attention through
    the Pallas kernel without changing the loss."""
    from dora_tpu.models import vlm

    cfg = vlm.VLMConfig.tiny()
    params = vlm.init_params(jax.random.PRNGKey(0), cfg)
    batch = {
        "images": jax.random.normal(
            jax.random.PRNGKey(1), (2, cfg.image_size, cfg.image_size, 3)
        ),
        "tokens": jax.random.randint(
            jax.random.PRNGKey(2), (2, 12), 0, cfg.vocab
        ),
    }
    monkeypatch.delenv("DORA_FLASH_ATTENTION", raising=False)
    dense = float(vlm.loss_fn(params, cfg, batch))
    monkeypatch.setenv("DORA_FLASH_ATTENTION", "1")
    flashed = float(vlm.loss_fn(params, cfg, batch))
    np.testing.assert_allclose(flashed, dense, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_long_context_flat_vmem(causal):
    """The online-softmax sweep handles T spanning many K blocks (the
    round-2 kernel held full [T, D] K/V tiles in VMEM and overflowed past
    T~8k; this kernel's footprint is flat in T). Interpreter-sized here;
    T=8192/16384 run compiled on TPU via bench_flash.py."""
    t = 1024
    key = jax.random.PRNGKey(7)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, 1, t, 64), jnp.float32)
    k = jax.random.normal(kk, (1, 1, t, 64), jnp.float32)
    v = jax.random.normal(kv, (1, 1, t, 64), jnp.float32)
    ours = flash_attention(q, k, v, causal=causal)
    ref = dense_reference(q, k, v, causal)
    np.testing.assert_allclose(
        np.asarray(ours), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


@pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="compiled long-T needs a TPU"
)
@pytest.mark.parametrize("t", [8192, 16384])
def test_flash_long_context_tpu(t):
    key = jax.random.PRNGKey(11)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, 2, t, 128), jnp.bfloat16)
    k = jax.random.normal(kk, (1, 2, t, 128), jnp.bfloat16)
    v = jax.random.normal(kv, (1, 2, t, 128), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    ref = dense_reference(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        True,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=3e-2, rtol=3e-2
    )


def test_flash_causal_first_row_attends_self_only():
    """Row 0 under causal masking sees exactly key 0 -> output == v[0]."""
    q = jnp.ones((1, 1, 128, 128), jnp.float32)
    k = jnp.ones_like(q)
    v = jnp.arange(128 * 128, dtype=jnp.float32).reshape(1, 1, 128, 128)
    out = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out[0, 0, 0]), np.asarray(v[0, 0, 0]), rtol=1e-6
    )


# ---------------------------------------------------------------------------
# int8 dequant-matmul
# ---------------------------------------------------------------------------

from dora_tpu.ops.int8_matmul import (  # noqa: E402
    dequantize,
    int8_matmul,
    quantize_int8,
    quantize_tree,
)


def test_quantize_roundtrip_error_bound():
    """Symmetric per-channel int8: worst-case error <= scale/2 per entry."""
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 48), jnp.float32)
    wq = quantize_int8(w)
    err = np.abs(np.asarray(dequantize(wq) - w))
    bound = np.asarray(wq["scale"])[0] / 2 + 1e-7
    assert (err <= bound[None, :]).all()


@pytest.mark.parametrize(
    "m,k,n",
    [
        (1, 256, 256),    # decode matvec, aligned
        (1, 1536, 512),   # bench LM width
        (4, 300, 100),    # both axes unaligned (padding path)
        (16, 256, 260),   # N ends in a ragged block
        (256, 7168, 256),  # prefill chunk, K = 4 x 1792 unpadded
        (64, 1792, 260),   # larger M, ragged N
        (64, 1000, 260),   # K no lane multiple, whole in one block
        (40, 2100, 130),   # K no lane multiple, too long: zero pad
    ],
)
def test_int8_matmul_matches_dequantized(m, k, n):
    key = jax.random.PRNGKey(hash((m, k, n)) % (2**31))
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32)
    wq = quantize_int8(w)
    ours = int8_matmul(x, wq["int8"], wq["scale"])
    ref = x @ dequantize(wq)
    np.testing.assert_allclose(
        np.asarray(ours), np.asarray(ref), atol=1e-3, rtol=1e-3
    )


def test_int8_matmul_3d_input():
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 128), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(4), (128, 64), jnp.float32)
    wq = quantize_int8(w)
    out = int8_matmul(x, wq["int8"], wq["scale"])
    assert out.shape == (2, 5, 64)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(x @ dequantize(wq)), atol=1e-3, rtol=1e-3
    )


def _int8_stack(e: int, k: int, n: int, seed: int = 5):
    each = [quantize_int8(jax.random.normal(jax.random.PRNGKey(seed + i),
                                            (k, n), jnp.float32))
            for i in range(e)]
    return (jnp.stack([w["int8"] for w in each]),
            jnp.stack([w["scale"] for w in each]))


@pytest.mark.parametrize(
    "ids,n_groups,m,k,n,shared",
    [
        ([0, 1, 2, 3], 4, 16, 256, 256, True),    # every expert touched
        ([2, 0, 0, 0], 1, 16, 256, 256, False),   # one
        ([0, 0, 0, 0], 0, 16, 256, 256, True),    # none: nothing to compare
        ([1, 3, 99, -7], 2, 5, 256, 256, False),  # garbage ids past n_groups
        ([3, 1, 2], 3, 16, 256, 260, False),      # a ragged N
        ([3, 0], 2, 32, 2100, 130, True),         # K zero-padded, a chunk's block
        ([1], 1, 64, 1792, 260, False),           # larger M: two M blocks a group
    ],
)
def test_int8_matmul_grouped_equals_separate_calls(ids, n_groups, m, k, n,
                                                   shared):
    """Group ``g`` of the grouped entry is ``int8_matmul`` against matrix
    ``ids[g]`` of the stack, to the bit, for every group that runs."""
    from dora_tpu.ops.int8_matmul import int8_matmul_grouped

    q, scale = _int8_stack(4, k, n)
    x = jax.random.normal(jax.random.PRNGKey(11),
                          (m, k) if shared else (len(ids), m, k), jnp.float32)
    out = int8_matmul_grouped(x, q, scale, jnp.asarray(ids, jnp.int32),
                              jnp.int32(n_groups))
    assert out.shape == (len(ids), m, n)
    for g in range(n_groups):
        want = int8_matmul(x if shared else x[g], q[ids[g]], scale[ids[g]])
        np.testing.assert_array_equal(np.asarray(out[g]), np.asarray(want))


def _plain_routed_sum(stack, x, local, weights, live):
    """``held_experts``' result by a plain loop over the experts: the
    dequantized SwiGLU of every expert on every row, weighted."""
    y = np.zeros((x.shape[0], stack["w_down"]["int8"].shape[-1]), np.float32)
    for e in range(stack["w_down"]["int8"].shape[0]):
        w = {k: {"int8": v["int8"][e], "scale": v["scale"][e]}
             for k, v in stack.items() if k != "limit"}
        gate, up = jnp.split(x @ dequantize(w["w_gateup"]), 2, axis=-1)
        if "limit" in stack:
            gate = jnp.minimum(gate, stack["limit"][e])
            up = jnp.clip(up, -stack["limit"][e], stack["limit"][e])
        out = (jax.nn.silu(gate) * up) @ dequantize(w["w_down"])
        w_e = (weights * ((local == e) & live[:, None])).sum(-1)
        y = y + np.asarray(out * w_e[:, None])
    return y


@pytest.mark.parametrize("case", ["all_live", "frozen_rows", "absent_picks",
                                  "limit", "none_touched", "chunk"])
def test_held_experts_against_a_plain_loop(case):
    """The decode branch (N <= ``EXPERT_BLOCK``: one grouped product a
    projection over the touched experts) and the chunk branch (an expert's
    own rows, a block at a time) against a plain per-expert loop."""
    from types import SimpleNamespace

    from dora_tpu.models import moe

    held, dim, inner, top_k = 6, 128, 64, 3
    n = 80 if case == "chunk" else 12
    gu_q, gu_s = _int8_stack(held, dim, 2 * inner, seed=20)
    dn_q, dn_s = _int8_stack(held, inner, dim, seed=40)
    stack = {"w_gateup": {"int8": gu_q, "scale": gu_s},
             "w_down": {"int8": dn_q, "scale": dn_s}}
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((n, dim)), jnp.float32)
    # picks over 10 experts of which this rank holds 6, from its 2nd on:
    # local runs -2 .. 7, so some picks are absent on either side
    local = jnp.asarray(np.stack(
        [rng.choice(10, top_k, replace=False) for _ in range(n)]) - 2, jnp.int32)
    weights = jnp.asarray(rng.random((n, top_k)), jnp.float32)
    live = jnp.ones((n,), bool)
    if case == "frozen_rows":
        live = jnp.asarray(rng.random(n) < 0.5)
    if case == "absent_picks":
        local = local.at[:, 0].set(-1).at[::2, 1].set(held)
    if case == "limit":
        stack["limit"] = jnp.full((held,), 0.05, jnp.float32)
    if case == "none_touched":
        live = jnp.zeros((n,), bool)
    cfg = SimpleNamespace(dim=dim)
    got = moe.held_experts({"experts": stack}, cfg, x, local, weights, live)
    want = _plain_routed_sum(stack, x, local, weights, live)
    assert got.shape == (n, dim) and got.dtype == jnp.float32
    if case == "none_touched":
        assert not np.asarray(got).any()
    else:
        assert np.abs(want).max() > 1e-3
    # float32 sums in another order, values in the hundreds
    assert np.abs(np.asarray(got) - want).max() <= 1e-5 * max(
        np.abs(want).max(), 1.0)


def test_quantize_tree_targets_decode_weights_only():
    blocks = {
        "0": {
            "wq": jnp.ones((8, 8)),
            "attn_norm": jnp.ones((8,)),
            "bq": jnp.ones((8,)),
        }
    }
    out = quantize_tree(blocks)
    # lone wq (no wk/wv partners): quantized individually, bf16 sidecar on
    assert set(out["0"]["wq"]) == {"int8", "scale", "bf16"}
    assert out["0"]["attn_norm"].shape == (8,)  # untouched
    assert out["0"]["bq"].shape == (8,)
    # idempotent: re-quantizing passes quantized dicts through
    again = quantize_tree(out)
    assert again["0"]["wq"] is out["0"]["wq"]
    # keep_bf16=False drops the sidecar
    lean = quantize_tree(blocks, keep_bf16=False)
    assert set(lean["0"]["wq"]) == {"int8", "scale"}


def test_quantize_tree_fuses_qkv_and_gateup():
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 6)
    block = {
        "wq": jax.random.normal(ks[0], (16, 32)),
        "wk": jax.random.normal(ks[1], (16, 8)),
        "wv": jax.random.normal(ks[2], (16, 8)),
        "bq": jnp.ones((32,)),  # bk/bv absent -> zero-filled segments
        "w_gate": jax.random.normal(ks[3], (16, 24)),
        "w_up": jax.random.normal(ks[4], (16, 24)),
        "w_down": jax.random.normal(ks[5], (24, 16)),
    }
    out = quantize_tree({"0": block})["0"]
    assert "wqkv" in out and "wq" not in out
    assert out["wqkv"]["int8"].shape == (16, 48)
    np.testing.assert_array_equal(
        np.asarray(out["bqkv"]), np.concatenate([np.ones(32), np.zeros(16)])
    )
    assert "w_gateup" in out and "w_gate" not in out
    assert out["w_gateup"]["int8"].shape == (16, 48)
    assert "b_gateup" not in out  # no source biases at all
    # fused dequantized weight matches the concatenated originals to
    # quantization precision
    wqkv = np.concatenate(
        [np.asarray(block["wq"]), np.asarray(block["wk"]), np.asarray(block["wv"])],
        axis=1,
    )
    np.testing.assert_allclose(
        np.asarray(dequantize(out["wqkv"])), wqkv, atol=2e-2
    )


def test_vlm_generate_fused_matches_unfused():
    """Fused-qkv/gateup decode produces the same tokens as per-weight
    quantization (same int8 values, different call grouping)."""
    from dora_tpu.models import vlm

    cfg = vlm.VLMConfig.tiny()
    params = vlm.init_params(jax.random.PRNGKey(0), cfg)
    from dora_tpu.ops.int8_matmul import quantize_tree

    fused = dict(params)
    fused["blocks"] = quantize_tree(params["blocks"])
    fused["lm_head"] = quantize_tree({"lm_head": params["lm_head"]})["lm_head"]
    unfused = dict(params)
    unfused["blocks"] = quantize_tree(params["blocks"], fuse=False)
    unfused["lm_head"] = quantize_tree(
        {"lm_head": params["lm_head"]}, fuse=False
    )["lm_head"]
    image = jax.random.uniform(
        jax.random.PRNGKey(1), (1, cfg.image_size, cfg.image_size, 3)
    )
    prompt = jnp.asarray([[5, 9, 2]], jnp.int32)
    t_fused = np.asarray(vlm.generate(fused, cfg, image, prompt, 6))
    t_unfused = np.asarray(vlm.generate(unfused, cfg, image, prompt, 6))
    np.testing.assert_array_equal(t_fused, t_unfused)


def test_vlm_int8_decode_logits_close():
    """Generation with int8-quantized LM weights matches generation with
    the explicitly dequantized float weights — the kernel path and the
    dense path agree; quantization error itself is the only delta."""
    from dora_tpu.models import vlm

    cfg = vlm.VLMConfig.tiny()
    params = vlm.init_params(jax.random.PRNGKey(0), cfg)
    qparams = vlm.quantize_decode(params)
    deq = jax.tree.map(
        lambda x: x,
        {
            **qparams,
            "blocks": {
                name: {
                    key: dequantize(val) if isinstance(val, dict) else val
                    for key, val in block.items()
                }
                for name, block in qparams["blocks"].items()
            },
            "lm_head": dequantize(qparams["lm_head"]),
        },
    )
    image = jax.random.uniform(
        jax.random.PRNGKey(1), (1, cfg.image_size, cfg.image_size, 3)
    )
    prompt = jnp.asarray([[5, 9, 2]], jnp.int32)
    logits_q, _, _ = vlm.prefill(qparams, cfg, image, prompt)
    logits_d, _, _ = vlm.prefill(deq, cfg, image, prompt)
    np.testing.assert_allclose(
        np.asarray(logits_q), np.asarray(logits_d), atol=2e-3, rtol=2e-3
    )
    # and the full generate path runs end to end on quantized weights
    tokens = vlm.generate(qparams, cfg, image, prompt, 4)
    assert tokens.shape == (1, 4)


# ---------------------------------------------------------------------------
# fused decode kernels (ops.decode_block)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pos", [0, 5, 37])
def test_decode_attention_step_matches_dense(pos):
    """attention_step (norm + int8 qkv + rope + in-place cache write +
    flash-decode + int8 wo + residual) matches the plain-JAX sublayer."""
    from dora_tpu.ops.decode_block import attention_step, rope_rows
    from dora_tpu.ops.int8_matmul import dequantize, quantize_int8

    rng = np.random.default_rng(pos)
    D, H, KV, HD, S = 64, 4, 2, 16, 64
    x = jnp.asarray(rng.standard_normal((1, D)), jnp.float32)
    nw = jnp.asarray(rng.standard_normal(D), jnp.float32)
    wqkv = quantize_int8(
        jnp.asarray(rng.standard_normal((D, (H + 2 * KV) * HD)), jnp.float32)
    )
    wo = quantize_int8(jnp.asarray(rng.standard_normal((H * HD, D)), jnp.float32))
    bqkv = jnp.asarray(rng.standard_normal((H + 2 * KV) * HD), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((KV, S, HD)), jnp.float32) * 0.1
    vc = jnp.asarray(rng.standard_normal((KV, S, HD)), jnp.float32) * 0.1
    cos_t, sin_t = L.rope_table(S, HD)
    cos_full, sin_signed = rope_rows(cos_t, sin_t, pos)

    xo, kc2, vc2 = attention_step(
        x, nw, wqkv["int8"], wqkv["scale"], bqkv, cos_full, sin_signed,
        kc, vc, wo["int8"], wo["scale"], pos,
        heads=H, kv_heads=KV, head_dim=HD,
    )

    h = L.rms_norm(x, nw)
    qkv = h @ dequantize(wqkv) + bqkv
    q, k, v = jnp.split(qkv, [H * HD, (H + KV) * HD], axis=-1)
    q = q.reshape(1, 1, H, HD).transpose(0, 2, 1, 3)
    k = k.reshape(1, 1, KV, HD).transpose(0, 2, 1, 3)
    v = v.reshape(1, 1, KV, HD).transpose(0, 2, 1, 3)
    posarr = jnp.broadcast_to(jnp.asarray(pos), (1, 1))
    q = L.apply_rope(q, cos_t, sin_t, posarr)
    k = L.apply_rope(k, cos_t, sin_t, posarr)
    kfull = jax.lax.dynamic_update_slice(kc[None], k, (0, 0, pos, 0))
    vfull = jax.lax.dynamic_update_slice(vc[None], v, (0, 0, pos, 0))
    kr = jnp.repeat(kfull, H // KV, axis=1)
    vr = jnp.repeat(vfull, H // KV, axis=1)
    mask = (jnp.arange(S) <= pos)[None, None, None, :]
    out = L.attention(q, kr, vr, mask)
    out = out.transpose(0, 2, 1, 3).reshape(1, H * HD)
    ref = x + out @ dequantize(wo)

    np.testing.assert_allclose(np.asarray(xo), np.asarray(ref), atol=1e-3)
    np.testing.assert_allclose(np.asarray(kc2), np.asarray(kfull[0]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(vc2), np.asarray(vfull[0]), atol=1e-5)


def test_decode_mlp_step_matches_dense():
    from dora_tpu.ops.decode_block import mlp_step
    from dora_tpu.ops.int8_matmul import dequantize, quantize_int8

    rng = np.random.default_rng(1)
    D, F = 64, 256
    x = jnp.asarray(rng.standard_normal((1, D)), jnp.float32)
    nw = jnp.asarray(rng.standard_normal(D), jnp.float32)
    wgu = quantize_int8(jnp.asarray(rng.standard_normal((D, 2 * F)), jnp.float32))
    wd = quantize_int8(jnp.asarray(rng.standard_normal((F, D)), jnp.float32))
    bgu = jnp.asarray(rng.standard_normal(2 * F), jnp.float32)

    out = mlp_step(
        x, nw, wgu["int8"], wgu["scale"], bgu, wd["int8"], wd["scale"]
    )

    h = L.rms_norm(x, nw)
    gu = h @ dequantize(wgu) + bgu
    g, u = jnp.split(gu, 2, axis=-1)
    ref = x + (jax.nn.silu(g) * u) @ dequantize(wd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3)


@pytest.mark.parametrize(
    "m,vocab,winner",
    [
        (1, 256, None),
        (5, 300, None),
        (5, 300, 299),            # one tile, wider than the vocab
        (1, 2048 + 384, 2431),    # winner in the ragged last tile
        (16, 4096 + 128, 4100),
        (16, 4096 + 128, 17),     # last tile loses to an earlier one
    ],
)
def test_decode_lm_head_argmax(m, vocab, winner):
    """Streamed argmax (incl. a vocab that ends in a ragged tile and M>1
    rows for speculative verify) matches argmax over the dense logits,
    index and value. The head goes in as stored: the interpreter fills
    the ragged tile's lanes past the vocab with NaN scales and -128
    weights, and a NaN logit wins any max that does not mask it."""
    from dora_tpu.ops.decode_block import lm_head_argmax
    from dora_tpu.ops.int8_matmul import dequantize, quantize_int8

    rng = np.random.default_rng(m * 1000 + vocab)
    D = 64
    # rows that lean one way, so one column can win every row
    x = jnp.asarray(
        rng.standard_normal(D) + 0.3 * rng.standard_normal((m, D)),
        jnp.float32,
    )
    nw = jnp.asarray(rng.standard_normal(D), jnp.float32)
    w = rng.standard_normal((D, vocab)).astype(np.float32)
    if winner is not None:
        lean = np.asarray(L.rms_norm(x, nw)).mean(0)
        w[:, winner] = 4.0 * lean / np.abs(lean).max()
    wh = quantize_int8(jnp.asarray(w))
    logits = L.rms_norm(x, nw) @ dequantize(wh)

    tok, val = lm_head_argmax(x, nw, wh["int8"], wh["scale"], return_val=True)
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(logits.argmax(-1)))
    np.testing.assert_allclose(
        np.asarray(val), np.asarray(logits.max(-1)), atol=1e-3, rtol=1e-4
    )
    if winner is not None:
        assert (np.asarray(tok) == winner).all()
    only = lm_head_argmax(x, nw, wh["int8"], wh["scale"])
    np.testing.assert_array_equal(np.asarray(only), np.asarray(tok))


def test_fused_decode_generate_matches_vanilla(monkeypatch):
    """vlm.generate through the fused Pallas decode tier emits the same
    tokens as the unfused int8 path on the same quantized weights."""
    from dora_tpu.models import vlm

    cfg = vlm.VLMConfig.tiny()
    params = vlm.init_params(jax.random.PRNGKey(0), cfg)
    qparams = vlm.quantize_decode(params)
    assert vlm.fused_decode_ready(qparams)
    image = jax.random.uniform(
        jax.random.PRNGKey(1), (1, cfg.image_size, cfg.image_size, 3)
    )
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 5), 0, cfg.vocab)

    monkeypatch.setenv("DORA_FUSED_DECODE", "0")
    vanilla = np.asarray(vlm.generate(qparams, cfg, image, prompt, 8))
    monkeypatch.setenv("DORA_FUSED_DECODE", "1")
    fused = np.asarray(vlm.generate(qparams, cfg, image, prompt, 8))
    np.testing.assert_array_equal(vanilla, fused)


def test_fused_chunk_attention_matches_dense():
    """attention_chunk_step (M-row speculative verify) matches the dense
    chunk-over-cache reference: causal within the chunk, prior cache
    visible to all rows, all M cache rows written in place."""
    from dora_tpu.ops.decode_block import attention_chunk_step, rope_rows
    from dora_tpu.ops.int8_matmul import dequantize, quantize_int8

    rng = np.random.default_rng(3)
    D, H, KV, HD, S, M = 64, 4, 2, 16, 64, 5
    pos = 9
    x = jnp.asarray(rng.standard_normal((M, D)), jnp.float32)
    nw = jnp.asarray(rng.standard_normal(D), jnp.float32)
    wqkv = quantize_int8(
        jnp.asarray(rng.standard_normal((D, (H + 2 * KV) * HD)), jnp.float32)
    )
    wo = quantize_int8(jnp.asarray(rng.standard_normal((H * HD, D)), jnp.float32))
    bqkv = jnp.asarray(rng.standard_normal((H + 2 * KV) * HD), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((KV, S, HD)), jnp.float32) * 0.1
    vc = jnp.asarray(rng.standard_normal((KV, S, HD)), jnp.float32) * 0.1
    cos_t, sin_t = L.rope_table(S, HD)
    cosr, sinr = rope_rows(cos_t, sin_t, pos, M)

    xo, kc2, vc2 = attention_chunk_step(
        x, nw, wqkv["int8"], wqkv["scale"], bqkv, cosr, sinr, kc, vc,
        wo["int8"], wo["scale"], pos, heads=H, kv_heads=KV, head_dim=HD,
    )

    h = L.rms_norm(x, nw)
    qkv = h @ dequantize(wqkv) + bqkv
    q, k, v = jnp.split(qkv, [H * HD, (H + KV) * HD], axis=-1)
    q = q.reshape(1, M, H, HD).transpose(0, 2, 1, 3)
    k = k.reshape(1, M, KV, HD).transpose(0, 2, 1, 3)
    v = v.reshape(1, M, KV, HD).transpose(0, 2, 1, 3)
    posarr = (pos + jnp.arange(M))[None]
    q = L.apply_rope(q, cos_t, sin_t, posarr)
    k = L.apply_rope(k, cos_t, sin_t, posarr)
    kfull = jax.lax.dynamic_update_slice(kc[None], k, (0, 0, pos, 0))
    vfull = jax.lax.dynamic_update_slice(vc[None], v, (0, 0, pos, 0))
    kr = jnp.repeat(kfull, H // KV, axis=1)
    vr = jnp.repeat(vfull, H // KV, axis=1)
    mask = jnp.arange(S)[None, None, None, :] <= posarr[0][None, None, :, None]
    out = L.attention(q, kr, vr, mask)
    ref = x + out.transpose(0, 2, 1, 3).reshape(M, H * HD) @ dequantize(wo)
    np.testing.assert_allclose(np.asarray(xo), np.asarray(ref), atol=2e-3)
    np.testing.assert_allclose(np.asarray(kc2), np.asarray(kfull[0]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(vc2), np.asarray(vfull[0]), atol=1e-5)


def test_paged_spec_attention_matches_dense_chunk():
    """attention_paged_spec_step (batched M-row verify through block
    tables) matches attention_chunk_step run per stream on the same
    cache laid out densely: output rows, the M written pool rows, AND
    bit-preservation of every untouched row. Streams sit at positions
    that exercise the page-straddle window (pos=6, M=5 crosses a page
    boundary) and a frozen stream (pos=0, null block table)."""
    from dora_tpu.ops.decode_block import (
        attention_chunk_step, attention_paged_spec_step, rope_rows,
        rope_rows_at,
    )
    from dora_tpu.ops.int8_matmul import quantize_int8

    rng = np.random.default_rng(3)
    D, H, KV, HD, S, M = 64, 4, 2, 16, 64, 5
    PAGE = 8
    npages = S // PAGE
    B = 4
    positions = [9, 30, 6, 0]  # stream 3: frozen (pos 0, zeroed bt row)
    frozen = [False, False, False, True]

    x = jnp.asarray(rng.standard_normal((B * M, D)), jnp.float32)
    nw = jnp.asarray(rng.standard_normal(D), jnp.float32)
    wqkv = quantize_int8(
        jnp.asarray(rng.standard_normal((D, (H + 2 * KV) * HD)), jnp.float32)
    )
    wo = quantize_int8(jnp.asarray(rng.standard_normal((H * HD, D)), jnp.float32))
    bqkv = jnp.asarray(rng.standard_normal((H + 2 * KV) * HD), jnp.float32)
    dense_k = [
        jnp.asarray(rng.standard_normal((KV, S, HD)), jnp.float32) * 0.1
        for _ in range(B)
    ]
    dense_v = [
        jnp.asarray(rng.standard_normal((KV, S, HD)), jnp.float32) * 0.1
        for _ in range(B)
    ]
    cos_t, sin_t = L.rope_table(S, HD)

    # Pool: page 0 is the null page; stream b owns pages 1+b*npages ...
    P = 1 + B * npages
    k_pool = np.zeros((P, KV, PAGE, HD), np.float32)
    v_pool = np.zeros((P, KV, PAGE, HD), np.float32)
    bt = np.zeros((B, npages), np.int32)
    for b in range(B):
        if frozen[b]:
            continue
        for j in range(npages):
            pg = 1 + b * npages + j
            bt[b, j] = pg
            k_pool[pg] = np.asarray(dense_k[b][:, j * PAGE:(j + 1) * PAGE])
            v_pool[pg] = np.asarray(dense_v[b][:, j * PAGE:(j + 1) * PAGE])

    pos_arr = jnp.asarray(positions, jnp.int32)
    flat_pos = (pos_arr[:, None] + jnp.arange(M)[None, :]).reshape(B * M)
    cosr, sinr = rope_rows_at(cos_t, sin_t, flat_pos)

    xo, kp2, vp2 = attention_paged_spec_step(
        x, nw, wqkv["int8"], wqkv["scale"], bqkv, cosr, sinr,
        jnp.asarray(k_pool), jnp.asarray(v_pool), wo["int8"], wo["scale"],
        pos_arr, jnp.asarray(bt), heads=H, kv_heads=KV, head_dim=HD, m=M,
    )
    xo, kp2, vp2 = np.asarray(xo), np.asarray(kp2), np.asarray(vp2)

    for b in range(B):
        pos = positions[b]
        cr, sr = rope_rows(cos_t, sin_t, pos, M)
        ref_xo, kc2, vc2 = attention_chunk_step(
            x[b * M:(b + 1) * M], nw, wqkv["int8"], wqkv["scale"], bqkv,
            cr, sr, dense_k[b], dense_v[b], wo["int8"], wo["scale"], pos,
            heads=H, kv_heads=KV, head_dim=HD,
        )
        # Same math in a different op order: equal to a few ulp of the
        # output's range (the interpreter's float rounding moves with
        # the installed JAX; a logic fault is orders of magnitude off).
        ref_xo = np.asarray(ref_xo)
        ulps = 4 * np.finfo(np.float32).eps * max(1.0, np.abs(ref_xo).max())
        np.testing.assert_allclose(
            xo[b * M:(b + 1) * M], ref_xo, atol=ulps, rtol=0,
            err_msg=f"stream {b}",
        )
        if frozen[b]:
            continue
        kc2, vc2 = np.asarray(kc2), np.asarray(vc2)
        for r in range(pos, pos + M):  # the M written rows
            pg, off = bt[b, r // PAGE], r % PAGE
            np.testing.assert_allclose(
                kp2[pg, :, off], kc2[:, r], atol=3e-7, err_msg=f"{b},{r}"
            )
            np.testing.assert_allclose(
                vp2[pg, :, off], vc2[:, r], atol=3e-7, err_msg=f"{b},{r}"
            )
        for r in range(pos):  # rows below pos: bit-preserved
            pg, off = bt[b, r // PAGE], r % PAGE
            assert np.array_equal(
                kp2[pg, :, off], np.asarray(dense_k[b][:, r])
            ), (b, r)
            assert np.array_equal(
                vp2[pg, :, off], np.asarray(dense_v[b][:, r])
            ), (b, r)


def test_speculative_fused_matches_fused_vanilla():
    """On int8-quantized params both speculation (fused M-row chunk
    verify) and vanilla generate ride the kernel tier — tokens must
    agree exactly, in fewer passes."""
    from dora_tpu.models import vlm

    cfg = vlm.VLMConfig.tiny()
    params = vlm.quantize_decode(vlm.init_params(jax.random.PRNGKey(0), cfg))
    assert vlm.fused_decode_ready(params)
    image = jax.random.uniform(
        jax.random.PRNGKey(1), (1, cfg.image_size, cfg.image_size, 3)
    )
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 5), 0, cfg.vocab)
    vanilla = np.asarray(vlm.generate(params, cfg, image, prompt, 16))
    spec, passes = vlm.generate_speculative(params, cfg, image, prompt, 16)
    np.testing.assert_array_equal(vanilla, np.asarray(spec))
    assert int(passes) < 16


# ---------------------------------------------------------------------------
# int4 decode weights (ops.int4)
# ---------------------------------------------------------------------------


def test_int4_quantize_roundtrip():
    """Group-wise int4: dequantize(quantize(w)) within the 4-bit grid
    (relative error bounded by half a quantization step per group)."""
    from dora_tpu.ops.int4 import dequantize_int4, quantize_int4

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((256, 384)), jnp.float32)
    q = quantize_int4(w)
    assert q["int4"].shape == (128, 384) and q["int4"].dtype == jnp.uint8
    deq = dequantize_int4(q)
    # max error <= scale/2 per group; scale = max|group|/7
    step = np.asarray(q["gscale"]).max()
    assert float(jnp.abs(deq - w).max()) <= step / 2 + 1e-6


def test_int4_fused_generate_matches_dequantized(monkeypatch):
    """The fused kernel tier on int4 weights emits the same tokens as
    the unfused path running on the explicitly dequantized weights —
    quantization error itself is the only delta, the kernels add none."""
    from dora_tpu.models import vlm

    monkeypatch.setenv("DORA_INT4_DECODE", "1")
    cfg = vlm.VLMConfig.tiny()
    params = vlm.init_params(jax.random.PRNGKey(0), cfg)
    qparams = vlm.quantize_decode(params)
    assert vlm.fused_decode_ready(qparams)
    image = jax.random.uniform(
        jax.random.PRNGKey(1), (1, cfg.image_size, cfg.image_size, 3)
    )
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 5), 0, cfg.vocab)
    fused = np.asarray(vlm.generate(qparams, cfg, image, prompt, 8))
    monkeypatch.setenv("DORA_FUSED_DECODE", "0")
    ref = np.asarray(vlm.generate(qparams, cfg, image, prompt, 8))
    np.testing.assert_array_equal(fused, ref)
    monkeypatch.delenv("DORA_FUSED_DECODE")
    spec, passes = vlm.generate_speculative(qparams, cfg, image, prompt, 8)
    np.testing.assert_array_equal(np.asarray(spec), fused)
