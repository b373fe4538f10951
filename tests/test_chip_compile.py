"""The serving path's kernels, compiled by the chip's own compiler.

No chip is attached here: the TPU compiler compiles for a *described*
v5e (``jax.experimental.topologies``), which is enough to hear what
Mosaic and XLA:TPU refuse — a slice not aligned to the tiling, a kernel
over its VMEM budget, an illegal block shape — none of which the Pallas
interpreter the other tests run can see. These are compiles, not chip
runs: nothing executes and nothing is timed.

Shapes are the published Qwen2.5-1.5B-Instruct widths (hidden 1536,
12 query / 2 KV heads of 128, intermediate 8960, vocab 151936) at the
serving defaults (16 slots, page 16, chunk 256, max_seq 2048, pool of
512 pages), depth cut to 2 layers — every layer is the same kernel.

The backend switch (``dora_tpu.backend.on_tpu``: bf16 compute, Mosaic
instead of the interpreter) is steered by monkeypatch in the fixture
below; the topology is described inside a module-scoped fixture so only
the worker that runs this file loads the TPU library.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from dora_tpu import backend
from dora_tpu.models.hf import qwen2

CFG = qwen2.Qwen2Config(
    vocab=151936, dim=1536, layers=2, heads=12, kv_heads=2, ffn=8960,
    rope_theta=1e6, norm_eps=1e-6, tie_embeddings=True, max_seq=2048,
)
SLOTS, PAGE, CHUNK = 16, 16, 256
PAGES = 4 * CFG.max_seq // PAGE
MAX_PAGES = CFG.max_seq // PAGE


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip(one_chip, monkeypatch):
    """Steer the program onto its chip branch and keep the persistent
    compile cache out of the way (a described-device executable cannot
    be read back, and the retry would warn)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()  # no interpret-mode trace may be reused
    assert backend.compute_dtype() == jnp.bfloat16 and not backend.interpret()

    def shaped(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
            tree,
        )

    yield shaped
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
    jax.clear_caches()  # nor a Mosaic trace leak into interpret-mode tests


def _s(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _qparams(cfg):
    """Shapes of the quantized serving params (int8 + bf16 twins) as
    ``llm_server.main`` builds them from a bf16 checkpoint."""
    bf = jnp.bfloat16
    qkv = cfg.kv_heads * cfg.head_dim
    block = {
        "attn_norm": _s((cfg.dim,), bf), "ffn_norm": _s((cfg.dim,), bf),
        "wq": _s((cfg.dim, cfg.dim), bf), "bq": _s((cfg.dim,), bf),
        "wk": _s((cfg.dim, qkv), bf), "bk": _s((qkv,), bf),
        "wv": _s((cfg.dim, qkv), bf), "bv": _s((qkv,), bf),
        "wo": _s((cfg.dim, cfg.dim), bf),
        "w_gate": _s((cfg.dim, cfg.ffn), bf),
        "w_up": _s((cfg.dim, cfg.ffn), bf),
        "w_down": _s((cfg.ffn, cfg.dim), bf),
    }
    raw = {
        "embed": _s((cfg.vocab, cfg.dim), bf),
        "out_norm": _s((cfg.dim,), bf),
        "blocks": {str(i): dict(block) for i in range(cfg.layers)},
    }
    return jax.eval_shape(partial(qwen2.quantize_decode, cfg=cfg), raw)


def _pools(kv_int8):
    return jax.eval_shape(
        lambda: qwen2.init_page_pool(CFG, PAGES, PAGE, kv_int8=kv_int8)
    )


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    assert "bf16[" in text, "program carries no bf16 operand"
    return compiled


def _step(fn):
    return lambda params, *args: fn(params, CFG, *args)


I32 = jnp.int32

#: fp KV pages must compile; int8 KV pages are refused by Mosaic today and
#: make_paged_engine refuses them on a tpu backend with the same message
#: (qwen2.KV_INT8_REFUSED). Strict: the day the scale planes get a
#: lane-dense layout these turn into XPASS failures and the refusal goes.
KV_KINDS = pytest.mark.parametrize("kv_int8", [
    pytest.param(False, id="fp_kv"),
    pytest.param(True, id="int8_kv", marks=pytest.mark.xfail(
        strict=True,
        reason="Mosaic failed to compile TPU kernel: Slice shape along "
               "dimension 2 must be aligned to tiling (128), but is 8 "
               "(16 in the chunk kernel) — [P, KV, page] f32 scale planes",
    )),
])


@KV_KINDS
def test_paged_batch_step_compiles(chip, kv_int8):
    _compile(
        _step(qwen2.fused_paged_batch_step),
        chip(_qparams(CFG)),
        *chip((_s((SLOTS,), I32), _pools(kv_int8), _s((SLOTS,), I32),
               _s((SLOTS, MAX_PAGES), I32))),
    )


@KV_KINDS
def test_paged_spec_step_compiles(chip, kv_int8):
    m = 5  # DORA_SPEC_K=4 drafts + the last emitted token
    _compile(
        _step(qwen2.fused_paged_spec_step),
        chip(_qparams(CFG)),
        *chip((_s((SLOTS, m), I32), _pools(kv_int8), _s((SLOTS,), I32),
               _s((SLOTS, MAX_PAGES), I32))),
    )


@KV_KINDS
def test_paged_chunk_step_compiles_at_default_chunk(chip, kv_int8):
    assert CHUNK == min(256, CFG.max_seq)  # make_paged_engine's default
    _compile(
        _step(qwen2.fused_paged_chunk_step),
        chip(_qparams(CFG)),
        *chip((_s((CHUNK,), I32), _pools(kv_int8), _s((), I32),
               _s((MAX_PAGES,), I32))),
    )


def test_dense_batch_step_compiles(chip):
    caches = jax.eval_shape(lambda: qwen2.init_cache(CFG, 8))
    _compile(
        _step(qwen2.fused_batch_step),
        chip(_qparams(CFG)),
        *chip((_s((8,), I32), caches, _s((8,), I32))),
    )


def test_lora_gather_matmul_compiles(chip):
    from dora_tpu.ops.lora import lora_gather_matmul

    bf = jnp.bfloat16
    rank, resident = 16, 9  # 8 tenants + the zero base slot
    _compile(
        lora_gather_matmul,
        *chip((_s((SLOTS, CFG.dim), bf), _s((SLOTS,), I32),
               _s((resident, CFG.dim, rank), bf),
               _s((resident, rank, CFG.dim), bf))),
    )


def test_mlp_step_compiles(chip):
    from dora_tpu.ops import decode_block as DB

    blk = _qparams(CFG)["blocks"]["0"]
    gu, dn = blk["w_gateup"], blk["w_down"]

    def step(x, norm_w, gu, b_gateup, dn):
        return DB.mlp_step(
            x, norm_w, gu["int8"], gu["scale"], b_gateup,
            dn["int8"], dn["scale"], eps=CFG.norm_eps,
        )

    _compile(
        step,
        *chip((_s((SLOTS, CFG.dim), jnp.bfloat16), blk["ffn_norm"], gu,
               _s((2 * CFG.ffn,), jnp.bfloat16), dn)),
    )


def test_lm_head_argmax_compiles(chip):
    from dora_tpu.ops import decode_block as DB

    params = _qparams(CFG)
    head = params["lm_head"]

    def step(x, norm_w, head):
        return DB.lm_head_argmax(
            x, norm_w, head["int8"], head["scale"], eps=CFG.norm_eps
        )

    _compile(
        step,
        *chip((_s((SLOTS, CFG.dim), jnp.bfloat16), params["out_norm"], head)),
    )


def test_flash_attention_compiles_at_2b_vision_widths(chip):
    from dora_tpu.models.vlm import VLMConfig
    from dora_tpu.ops.flash_attention import flash_attention

    v = VLMConfig.bench_2b()
    qkv = _s((1, v.vision_heads, v.n_patches, v.vision_dim // v.vision_heads),
             jnp.bfloat16)
    _compile(flash_attention, *chip((qkv, qkv, qkv)))
