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

import re
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


def _compile_batch_attention(chip, slots, blk, pool, bqkv, **shape):
    """``attention_paged_batch_step`` alone, ``slots`` rows over tables
    of ``MAX_PAGES`` pages, for the described chip."""
    from dora_tpu.ops import decode_block as DB

    hd = shape["head_dim"]

    def step(x, blk, bqkv, cos, sin, kp, vp, positions, tables):
        w, o = blk["wqkv"], blk["wo"]
        return DB.attention_paged_batch_step(
            x, blk["attn_norm"], w["int8"], w["scale"], bqkv, cos, sin,
            kp, vp, o["int8"], o["scale"], positions, tables, **shape,
        )

    _compile(
        step,
        *chip((_s((slots, blk["wqkv"]["int8"].shape[0]), jnp.bfloat16), blk,
               bqkv, _s((slots, hd), jnp.float32), _s((slots, hd), jnp.float32),
               pool["k"], pool["v"], _s((slots,), I32),
               _s((slots, MAX_PAGES), I32))),
    )


def _attention_widths(dim, heads, kv_heads, head_dim=128):
    """(layer weights, a layer's K and V pools, bias) shapes of one fused
    attention sublayer as the loaders lay it out: int8 ``wqkv`` and
    ``wo`` with a scale a column."""
    n = (heads + 2 * kv_heads) * head_dim
    blk = {
        "attn_norm": _s((dim,), jnp.bfloat16),
        "wqkv": {"int8": _s((dim, n), jnp.int8),
                 "scale": _s((1, n), jnp.float32)},
        "wo": {"int8": _s((heads * head_dim, dim), jnp.int8),
               "scale": _s((1, dim), jnp.float32)},
    }
    page_pool = _s((PAGES, kv_heads, PAGE, head_dim), jnp.bfloat16)
    return blk, {"k": page_pool, "v": page_pool}, _s((n,), jnp.float32)


#: (hidden, query heads, K/V heads) of 128: Qwen2.5-1.5B (5.5 MB of int8
#: ``wqkv`` + ``wo``), Ouro-2.6B (16.8 MB) and Falcon-H1-34B's attention
#: branch (31.5 MB: what kept it out of this kernel while the kernel held
#: both weights whole; ROADMAP speed 3 (d))
ATTENTION_WIDTHS = {
    "qwen": (1536, 12, 2), "ouro": (2048, 16, 16), "falcon_h1": (5120, 20, 4),
}


@pytest.mark.parametrize("widths,slots", [
    ("qwen", SLOTS), ("qwen", 8), ("ouro", SLOTS), ("falcon_h1", SLOTS),
])
def test_paged_batch_attention_compiles_with_its_group_buffers(
        chip, widths, slots):
    """The decode attention kernel alone at the serve cells' shape (16
    rows, tables of 128 pages of 16, 12/2 heads of 128; 8 rows as
    ``DORA_BATCH_SLOTS=8`` would give it): the pipelined sweep's scratch
    — two slots of [KV, 128, hd] for K and for V, the SMEM schedule of
    rows x 16 groups, q and the softmax state per row — beside a ring
    of three column tiles of about 1 MiB of the int8 qkv and output
    weights, which stay in HBM whatever their size. So Ouro's widths
    (16.8 MB of them, 16 K/V heads a group) and Falcon-H1's attention
    branch (31.5 MB, tiles of 128 columns of 5120 rows) compile inside
    the compiler's default scope too: the call passes no VMEM limit of
    its own. Falcon-H1's is a compile and nothing else: the model still
    runs that branch in plain XLA (``falcon_h1.attn_decode``)."""
    from dora_tpu.ops import decode_block as DB

    dim, heads, kv_heads = ATTENTION_WIDTHS[widths]
    blk, pool, bqkv = _attention_widths(dim, heads, kv_heads)
    if widths == "qwen":  # the loader's own tree, which has those shapes
        mine = _qparams(CFG)["blocks"]["0"]
        assert mine["wqkv"]["int8"].shape == blk["wqkv"]["int8"].shape
        assert mine["wo"]["int8"].shape == blk["wo"]["int8"].shape
        blk, pool, bqkv = mine, _pools(False)["0"], mine["bqkv"]
    hd = 128
    assert DB._sweep_pages(PAGE, MAX_PAGES) * PAGE == 128
    scratch = DB._sweep_scratch(
        slots, MAX_PAGES, kv_heads, heads // kv_heads, hd,
        PAGE, jnp.bfloat16, jnp.bfloat16, False)
    assert scratch[0].shape == (DB._SWEEP_SLOTS, kv_heads, 128, hd)
    assert scratch[3].shape == (slots * MAX_PAGES // 8,)  # step -> row
    rows = max(dim, heads * hd)  # of a ring buffer; int8: a byte a value
    assert DB._WEIGHT_SLOTS * rows * DB._weight_tile_cols(rows) <= 3 << 20
    _compile_batch_attention(
        chip, slots, blk, pool, bqkv, heads=heads, kv_heads=kv_heads,
        head_dim=hd, eps=CFG.norm_eps)


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


# -- Kimi-K2 / DeepSeek-V3: the latent pool's programs at published widths ----

KIMI_HF = dict(
    model_type="kimi_k2", hidden_size=7168, num_attention_heads=64,
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, intermediate_size=18432,
    moe_intermediate_size=2048, n_routed_experts=384, ep_size=32,
    num_experts_per_tok=8, n_shared_experts=1, first_k_dense_replace=1,
    num_hidden_layers=2, vocab_size=20480, rms_norm_eps=1e-5,
    rope_theta=50000, routed_scaling_factor=2.827, norm_topk_prob=True,
    scoring_func="sigmoid", n_group=1, topk_group=1,
    rope_scaling=dict(type="yarn", factor=64, beta_fast=32, beta_slow=1,
                      original_max_position_embeddings=4096, mscale=1,
                      mscale_all_dim=1),
)
KIMI_SEQ = 16384


def _kimi():
    """Config and parameter shapes of rank 0 of 32 (12 experts a layer) at
    Kimi-K2.5's widths, depth cut to the dense layer and one expert
    layer, as ``kimi_k2.load`` builds them (int8 from the start)."""
    from dora_tpu.models import moe
    from dora_tpu.models.hf import kimi_k2

    cfg = kimi_k2.KimiK2Config.from_hf(KIMI_HF, max_seq=KIMI_SEQ)
    d, h, bf = cfg.dim, cfg.heads, jnp.bfloat16
    shapes = {
        "self_attn.q_a_proj.weight": (cfg.q_rank, d),
        "self_attn.q_a_layernorm.weight": (cfg.q_rank,),
        "self_attn.q_b_proj.weight": (h * (cfg.nope + cfg.rope), cfg.q_rank),
        "self_attn.kv_a_proj_with_mqa.weight": (cfg.latent, d),
        "self_attn.kv_a_layernorm.weight": (cfg.kv_rank,),
        "self_attn.kv_b_proj.weight": (h * (cfg.nope + cfg.v_dim), cfg.kv_rank),
        "self_attn.o_proj.weight": (d, h * cfg.v_dim),
        "input_layernorm.weight": (d,),
        "post_attention_layernorm.weight": (d,),
        "mlp.gate.weight": (cfg.n_experts, d),
        "mlp.gate.e_score_correction_bias": (cfg.n_experts,),
    }

    def get(name):
        tail = name.split(".", 3)[3]  # after "model.layers.<i>."
        if tail in shapes:
            return jnp.zeros(shapes[tail], bf)
        # a SwiGLU matrix: the dense layer's, or an expert's / the shared one's
        dense = tail.startswith(("mlp.gate_proj", "mlp.up_proj", "mlp.down_proj"))
        width = cfg.ffn if dense else cfg.moe_ffn
        return jnp.zeros((d, width) if "down_proj" in tail else (width, d), bf)

    def build():
        return {
            "embed": jnp.zeros((cfg.vocab, d), bf),
            "out_norm": jnp.zeros((d,), bf),
            "lm_head": kimi_k2._quantize_t(jnp.zeros((cfg.vocab, d), bf)),
            "blocks": {str(i): kimi_k2.load_layer(get, cfg, i)
                       for i in range(cfg.layers)},
        }

    pools = jax.eval_shape(
        lambda: kimi_k2.init_page_pool(cfg, SLOTS * KIMI_SEQ // PAGE, PAGE))
    stats = jax.eval_shape(lambda: moe.init_counters(cfg))
    return kimi_k2, cfg, jax.eval_shape(build), pools, stats


def _pool_bytes(pools):
    return max(x.size * x.dtype.itemsize for x in jax.tree.leaves(pools))


def test_kimi_chunk_program_compiles_and_updates_the_pool_in_place(chip):
    """The prefill chunk over the latent pool at real widths. The pool's
    rows are stored 640 wide because a 576-wide minor dimension made
    XLA:TPU keep the pool transposed and copy it into and out of the
    program: the program's temporaries must stay under one layer's pool."""
    kimi_k2, cfg, params, pools, stats = _kimi()
    assert cfg.row == 640 and cfg.latent == 576 and cfg.experts_held == 12
    compiled = jax.jit(
        lambda p, *a: kimi_k2.fused_paged_chunk_step(p, cfg, *a),
        donate_argnums=(2, 3),
    ).lower(
        chip(params),
        *chip((_s((CHUNK,), I32), pools, stats, _s((), I32),
               _s((KIMI_SEQ // PAGE,), I32), _s((), I32))),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the int8 matmul kernel
    assert compiled.memory_analysis().temp_size_in_bytes < _pool_bytes(pools)
    assert _expert_stack_readers(
        compiled, params["blocks"]["1"]["experts"]) == []


def test_kimi_window_program_compiles_and_updates_the_pool_in_place(chip):
    """The K=8 decode window (vlm.make_paged_window over the absorbed
    batch step) over the latent pool at real widths, 16 slots."""
    kimi_k2, cfg, params, pools, stats = _kimi()

    def program(p, *args):
        return kimi_k2.window_program(p, cfg, 8, None, kimi_k2.ATTN_BLOCK,
                                      *args)

    compiled = jax.jit(program, donate_argnums=(2, 3)).lower(
        chip(params),
        *chip((_s((SLOTS,), I32), pools, stats, _s((SLOTS,), I32),
               _s((SLOTS, KIMI_SEQ // PAGE), I32), _s((SLOTS,), jnp.bool_),
               _s((SLOTS,), I32), _s((SLOTS,), I32))),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < _pool_bytes(pools)
    stack = params["blocks"]["1"]["experts"]
    assert stack["w_gateup"]["int8"].shape == (12, 7168, 4096)
    assert _expert_conds(compiled) == []
    assert _expert_stack_readers(compiled, stack) == []


# -- Falcon-H1-34B: a recurrent state beside the pages ------------------------

#: the catalog row's widths (benchmark/configs/falcon-h1-34b-pp8.json),
#: depth 2, an eighth of the vocabulary: hidden 5120, 20/4 heads of 128,
#: ffn 21504, a Mamba-2 mixer of 32 heads x 128 x state 256.
FALCON = dict(
    model_type="falcon_h1", hidden_size=5120, num_attention_heads=20,
    num_key_value_heads=4, head_dim=128, intermediate_size=21504,
    num_hidden_layers=2, vocab_size=32640, rms_norm_eps=1e-5,
    rope_theta=1e11, mamba_d_ssm=4096, mamba_n_heads=32, mamba_d_head=128,
    mamba_n_groups=2, mamba_d_state=256, mamba_d_conv=4,
    mamba_chunk_size=128,
)
FALCON_SEQ = 2048


def _falcon():
    """(module, cfg, the serving parameters' shapes as ``load`` builds
    them, pool, slot state and counter shapes) for 16 slots."""
    from dora_tpu.models.hf import falcon_h1

    cfg = falcon_h1.FalconH1Config.from_hf(FALCON, FALCON_SEQ)
    bf = jnp.bfloat16
    d = cfg.dim
    shapes = {
        "input_layernorm.weight": (d,), "pre_ff_layernorm.weight": (d,),
        "self_attn.q_proj.weight": (cfg.q_width, d),
        "self_attn.k_proj.weight": (cfg.kv_width, d),
        "self_attn.v_proj.weight": (cfg.kv_width, d),
        "self_attn.o_proj.weight": (d, cfg.q_width),
        "mamba.in_proj.weight": (cfg.in_width, d),
        "mamba.conv1d.weight": (cfg.conv_dim, 1, cfg.d_conv),
        "mamba.conv1d.bias": (cfg.conv_dim,), "mamba.dt_bias": (cfg.ssm_heads,),
        "mamba.A_log": (cfg.ssm_heads,), "mamba.D": (cfg.ssm_heads,),
        "mamba.norm.weight": (cfg.d_ssm,),
        "mamba.out_proj.weight": (d, cfg.d_ssm),
        "feed_forward.gate_proj.weight": (cfg.ffn, d),
        "feed_forward.up_proj.weight": (cfg.ffn, d),
        "feed_forward.down_proj.weight": (d, cfg.ffn),
    }

    def get(name):
        return jnp.zeros(shapes[name.split(".", 3)[3]], bf)

    def build():
        return {
            "embed": jnp.zeros((cfg.vocab, d), bf),
            "out_norm": jnp.zeros((d,), bf),
            "lm_head": falcon_h1._quantize_t(1.0, jnp.zeros((cfg.vocab, d), bf)),
            "blocks": {str(i): falcon_h1.load_layer(get, cfg, i)
                       for i in range(cfg.layers)},
        }

    pools = jax.eval_shape(lambda: falcon_h1.init_page_pool(
        cfg, SLOTS * FALCON_SEQ // PAGE + 1, PAGE))
    state = jax.eval_shape(lambda: falcon_h1.init_slot_state(cfg, SLOTS))
    stats = jax.eval_shape(falcon_h1.init_counters)
    return falcon_h1, cfg, jax.eval_shape(build), pools, state, stats


def _whole_array_copies(compiled, *trees) -> list[str]:
    """``copy`` instructions of the compiled program as large as the
    largest leaf of ``trees``: a cache that XLA moved instead of
    updating in place."""
    import math

    least = min(_pool_bytes(t) for t in trees)
    found = []
    for line in compiled.as_text().splitlines():
        m = re.search(r"= [a-z]+(\d+)\[([\d,]+)\]\S* copy\(", line)
        if m and int(m.group(1)) // 8 * math.prod(
                int(n) for n in m.group(2).split(",")) >= least:
            found.append(line.strip()[:120])
    return found


def _expert_conds(compiled) -> list[str]:
    """``conditional`` instructions under the expert layer's scope: the
    routed sum branching on what an expert was given."""
    return [line.strip()[:120] for line in compiled.as_text().splitlines()
            if " conditional(" in line and "moe_experts" in line]


def _expert_stack_readers(compiled, stack) -> list[str]:
    """Instructions of the compiled program that read an int8 matrix stack
    of the experts and are neither the grouped kernel nor a loop's
    plumbing: a copy of the stack, or of one expert's ``[K, N]`` cut from
    it, where the kernel's index map should have named the block. XLA's
    own staging of a kernel's operand in fast memory (``copy-start`` /
    ``slice-start`` into ``S(1)``: Keye's 50 MB and 25 MB stacks fit it,
    and the parent's program staged the separate matrices the same way)
    is a read ahead of the call, not a second copy in HBM, and passes."""
    text = compiled.as_text()
    shapes = tuple("s8[%s]" % ",".join(map(str, w["int8"].shape))
                   for w in (stack["w_gateup"], stack["w_down"]))
    lines = [m.groups() for m in re.finditer(
        r"^\s*(?:ROOT )?(%[\w.-]+) = (.*?)\s([a-z][a-z0-9-]*)\((.*)$", text, re.M)]
    stacks = {name for name, kind, _, _ in lines if kind.startswith(shapes)}
    assert stacks  # the program holds them under these shapes
    plumbing = ("parameter", "tuple", "get-tuple-element", "while")
    staging = ("copy-start", "slice-start")
    return [f"{name} = {kind} {op}({rest}"[:160]
            for name, kind, op, rest in lines
            if stacks & set(re.findall(r"%[\w.-]+", rest.split("), ")[0]))
            and op not in plumbing
            and not (op in staging and "S(1)" in kind)
            and not (op == "custom-call" and "int8_matmul_grouped" in name)]


def test_falcon_h1_window_program_compiles_and_moves_no_cache(chip):
    """The K=8 decode window at Falcon-H1-34B's widths, 16 slots: the
    state-step kernel, ``mlp_step`` at D = 5120 / F = 21504 (tile 256)
    and ``lm_head_argmax`` (tile 512) under Mosaic, and neither the K/V
    pool (67 MB a layer) nor the slots' state (67 MB a layer) copied."""
    falcon_h1, cfg, params, pools, state, stats = _falcon()
    from dora_tpu.ops import decode_block as DB

    assert DB._pick_bf(cfg.ffn, cfg.dim) == 256
    assert DB._pick_bf(8960, 1536) == 896  # Qwen2.5-1.5B's, as before

    def program(p, *args):
        return falcon_h1.window_program(p, cfg, 8, None, falcon_h1.ATTN_BLOCK,
                                        *args)

    compiled = jax.jit(program, donate_argnums=(2, 3, 9)).lower(
        chip(params),
        *chip((_s((SLOTS,), I32), pools, stats, _s((SLOTS,), I32),
               _s((SLOTS, FALCON_SEQ // PAGE), I32), _s((SLOTS,), jnp.bool_),
               _s((SLOTS,), I32), _s((SLOTS,), I32), state)),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _whole_array_copies(compiled, pools, state) == []


def test_falcon_h1_chunk_program_compiles_and_moves_no_cache(chip):
    """The 256-row prefill chunk: the chunked scan in plain XLA (two
    blocks of 128), every matrix through ``int8_matmul``."""
    falcon_h1, cfg, params, pools, state, stats = _falcon()

    def step(p, ids, pools, stats, position, bt, state, valid, slot):
        return falcon_h1.fused_paged_chunk_step(
            p, cfg, ids, pools, state, stats, position, bt, valid, slot)

    compiled = jax.jit(step, donate_argnums=(2, 3, 6)).lower(
        chip(params),
        *chip((_s((CHUNK,), I32), pools, stats, _s((), I32),
               _s((FALCON_SEQ // PAGE,), I32), state, _s((), I32),
               _s((), I32))),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _whole_array_copies(compiled, pools, state) == []


# -- Ouro-2.6B: the layers run four times, the pool four pools deep -----------

#: the catalog row's widths (benchmark/configs/ouro-2p6b.json), depth 2:
#: hidden 2048, 16 query = 16 K/V heads of 128, ffn 5632, vocabulary 49152,
#: 4 passes; the pool of the cell, 384 pages.
OURO = dict(
    model_type="ouro", hidden_size=2048, num_attention_heads=16,
    num_key_value_heads=16, head_dim=128, intermediate_size=5632,
    num_hidden_layers=2, vocab_size=49152, rms_norm_eps=1e-6, rope_theta=1e6,
    total_ut_steps=4, early_exit_threshold=1,
)
OURO_PAGES = 384


def _ouro():
    """(module, cfg, the serving parameters' shapes as ``load`` builds
    them, pool and counter shapes)."""
    from dora_tpu.models.hf import ouro

    cfg = ouro.OuroConfig.from_hf(OURO, 2048)
    bf = jnp.bfloat16
    d, q = cfg.dim, cfg.heads * cfg.head_dim
    shapes = {
        "self_attn.q_proj.weight": (q, d), "self_attn.k_proj.weight": (q, d),
        "self_attn.v_proj.weight": (q, d), "self_attn.o_proj.weight": (d, q),
        "mlp.gate_proj.weight": (cfg.ffn, d), "mlp.up_proj.weight": (cfg.ffn, d),
        "mlp.down_proj.weight": (d, cfg.ffn),
        "embed_tokens.weight": (cfg.vocab, d), "lm_head.weight": (cfg.vocab, d),
        "early_exit_gate.weight": (1, d), "early_exit_gate.bias": (1,),
    }

    def get(name):
        tail = name.removeprefix("model.")
        if tail.startswith("layers."):
            tail = tail.split(".", 2)[2]
        return jnp.zeros(shapes.get(tail, (d,)), bf)  # what is left: the norms

    params = jax.eval_shape(lambda: ouro.map_params(
        get, lambda name: not name.endswith(".bias") or "gate" in name, cfg))
    pools = jax.eval_shape(lambda: ouro.init_page_pool(cfg, OURO_PAGES, PAGE))
    stats = jax.eval_shape(ouro.init_counters)
    return ouro, cfg, params, pools, stats


def test_ouro_window_program_compiles_and_updates_the_pool_in_place(chip):
    """The K=8 decode window with the pass loop inside it, at the
    published widths: the batched attention kernel streams 16.8 MB of
    int8 ``wqkv`` and ``wo`` from HBM by column tiles (inside the
    compiler's 16 MiB default scope: the kernel asks no limit of its
    own), its page groups are 16 K/V heads wide, and the pools, four
    pools deep, ride two loop carries without a copy."""
    ouro, cfg, params, pools, stats = _ouro()
    assert pools["0"]["k"].shape == (4 * OURO_PAGES, 16, PAGE, 128)
    blk = params["blocks"]["0"]
    assert blk["wqkv"]["int8"].size + blk["wo"]["int8"].size == 16_777_216  # int8

    def program(p, *args):
        return ouro.window_program(p, cfg, 8, None, *args)

    compiled = jax.jit(program, donate_argnums=(2, 3)).lower(
        chip(params),
        *chip((_s((SLOTS,), I32), pools, stats, _s((SLOTS,), I32),
               _s((SLOTS, MAX_PAGES), I32), _s((SLOTS,), jnp.bool_),
               _s((SLOTS,), I32), _s((SLOTS,), I32))),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < _pool_bytes(pools) // 8


def test_paged_batch_attention_compiles_with_one_query_row_a_kv_head(chip):
    """The decode attention kernel alone at Ouro's shape: 16 rows, 16
    query = 16 K/V heads of 128, so a K/V head serves ONE query row and
    the sweep's step is the vector pass over all 16 heads of a group
    (``[16, 128, 128]`` products reduced along lanes for the scores and
    along sublanes for the mix: layouts only Mosaic can refuse), with
    group buffers of 2 x [16, 128, 128] for K and for V beside three
    1 MiB tiles of the 16.8 MB of weights; ``residual=False`` and a
    float32 result, as the looped model calls it."""
    from dora_tpu.ops import decode_block as DB

    ouro, cfg, params, pools, _ = _ouro()
    blk, hd = params["blocks"]["0"], cfg.head_dim
    assert cfg.heads // cfg.kv_heads == 1
    scratch = DB._sweep_scratch(
        SLOTS, MAX_PAGES, cfg.kv_heads, 1, hd, PAGE, jnp.bfloat16,
        jnp.bfloat16, False)
    assert scratch[0].shape == (DB._SWEEP_SLOTS, 16, 128, hd)
    assert scratch[-1].shape == (SLOTS, 16, 1, hd)  # accumulator: one row
    _compile_batch_attention(
        chip, SLOTS, blk, pools["0"],
        _s(((cfg.heads + 2 * cfg.kv_heads) * hd,), jnp.float32),
        **ouro._shape(cfg))


def test_ouro_chunk_program_compiles_and_updates_the_pool_in_place(chip):
    """The 256-row prefill chunk, four passes of the fused chunk kernels
    with ``residual=False`` and float32 sandwich norms between them."""
    ouro, cfg, params, pools, stats = _ouro()
    compiled = jax.jit(
        lambda p, *a: ouro.fused_paged_chunk_step(p, cfg, *a),
        donate_argnums=(2, 3),
    ).lower(
        chip(params),
        *chip((_s((CHUNK,), I32), pools, stats, _s((), I32),
               _s((MAX_PAGES,), I32), _s((), I32))),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < _pool_bytes(pools)


# -- K-EXAONE: window layers in a ring a slot, global layers in pages ---------

#: the catalog row's widths (benchmark/configs/k-exaone-236b-ep8.json), one
#: period ``LLLG`` deep (layer 0 dense, three expert layers), 16 of 128
#: experts held, the cell's vocabulary slice and context
EXAONE = dict(
    model_type="exaone_moe", hidden_size=6144, num_attention_heads=64,
    num_key_value_heads=8, head_dim=128, intermediate_size=18432,
    moe_intermediate_size=2048, num_hidden_layers=4, vocab_size=19200,
    rms_norm_eps=1e-5, rope_parameters={"rope_theta": 1e6, "rope_type": "default"},
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    sliding_window=128, mlp_layer_types=["dense"] + ["sparse"] * 3,
    num_experts=128, num_experts_per_tok=8, num_shared_experts=1,
    routed_scaling_factor=2.5, norm_topk_prob=True, scoring_func="sigmoid",
    n_group=1, topk_group=1, ep_size=8,
)
EXAONE_SEQ = 16384


def _exaone():
    """(module, cfg, the serving parameters' shapes as ``load`` builds
    them, pool, ring and counter shapes) for 16 slots of 16,384 rows."""
    from dora_tpu.models.hf import exaone_moe

    cfg = exaone_moe.ExaoneMoeConfig.from_hf(EXAONE, EXAONE_SEQ, 0)
    bf, d = jnp.bfloat16, cfg.dim
    fixed = {
        "input_layernorm.weight": (d,), "post_attention_layernorm.weight": (d,),
        "self_attn.q_proj.weight": (cfg.q_width, d),
        "self_attn.k_proj.weight": (cfg.kv_width, d),
        "self_attn.v_proj.weight": (cfg.kv_width, d),
        "self_attn.o_proj.weight": (d, cfg.q_width),
        "self_attn.q_norm.weight": (cfg.head_dim,),
        "self_attn.k_norm.weight": (cfg.head_dim,),
        "mlp.gate.weight": (cfg.n_experts, d),
        "mlp.gate.e_score_correction_bias": (cfg.n_experts,),
    }

    def get(name):
        tail = name.split(".", 3)[3]
        if tail in fixed:
            return jnp.zeros(fixed[tail], bf)
        width = cfg.ffn if tail.count(".") == 2 else cfg.moe_ffn  # mlp.x_proj.weight
        return jnp.zeros((d, width) if "down_proj" in tail else (width, d), bf)

    def build():
        return {
            "embed": jnp.zeros((cfg.vocab, d), bf),
            "out_norm": jnp.zeros((d,), bf),
            "lm_head": exaone_moe._quantize_t(jnp.zeros((cfg.vocab, d), bf)),
            "blocks": {str(i): exaone_moe.load_layer(get, cfg, i)
                       for i in range(cfg.layers)},
        }

    pools = jax.eval_shape(lambda: exaone_moe.init_page_pool(
        cfg, SLOTS * EXAONE_SEQ // PAGE + 1, PAGE))
    state = jax.eval_shape(lambda: exaone_moe.init_slot_state(cfg, SLOTS))
    stats = jax.eval_shape(lambda: exaone_moe.init_counters(cfg))
    return exaone_moe, cfg, jax.eval_shape(build), pools, state, stats


def _ring_copies(compiled) -> list[str]:
    """``copy`` instructions of a whole layer's rings, by shape (a
    gathered block of the global layer's keys has as many bytes and is
    relaid for its product: not a cache that moved)."""
    return [line.strip()[:120] for line in compiled.as_text().splitlines()
            if "bf16[16,128,2048]" in line.split(" copy(")[0] and " copy(" in line]


def test_exaone_window_program_compiles_and_moves_no_cache(chip):
    """The K=8 decode window at K-EXAONE's widths, 16 slots of 16,384
    rows: every matrix through ``int8_matmul`` (K = 6144, 8192, 18432;
    the 36,864-wide dense gate-up), ``lm_head_argmax`` over 19,200
    columns, the ring's one einsum in plain XLA, and the global layer's
    pages through ``attention_paged_rows_step`` under Mosaic (64 / 8
    heads of 128: 8 query rows a K/V head, a 64 KB block table in SMEM,
    two 512 KB group buffers); pages only for the global layer (1.07
    GB, an operand of the kernel left in HBM), rings only for the window
    layers (8 MB each), neither copied."""
    exaone_moe, cfg, params, pools, state, stats = _exaone()
    assert set(pools) == {"3"} and set(state) == {"0", "1", "2"}
    assert pools["3"]["kv"].shape == (SLOTS * EXAONE_SEQ // PAGE + 1, PAGE, 2048)
    assert state["0"]["kv"].shape == (SLOTS, 128, 2048)
    stack = params["blocks"]["1"]["experts"]
    assert stack["w_gateup"]["int8"].shape == (16, 6144, 4096)
    assert stack["w_down"]["scale"].shape == (16, 1, 6144)

    def program(p, *args):
        return exaone_moe.window_program(p, cfg, 8, None, *args)

    compiled = jax.jit(program, donate_argnums=(2, 3, 9)).lower(
        chip(params),
        *chip((_s((SLOTS,), I32), pools, stats, _s((SLOTS,), I32),
               _s((SLOTS, EXAONE_SEQ // PAGE), I32), _s((SLOTS,), jnp.bool_),
               _s((SLOTS,), I32), _s((SLOTS,), I32), state)),
    ).compile()
    text = compiled.as_text()
    # one call a global layer, inside the window's loop over its ticks
    assert len(re.findall(r"= \S+ custom-call\(.*attention_paged_rows_step",
                          text)) == len(pools)
    assert _whole_array_copies(compiled, pools) == []
    # each 8 MB ring is staged through fast memory (``S(1)``) around its
    # scatter, once a tick: as many such copies as window layers, no more
    assert len(_ring_copies(compiled)) <= len(state)
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20
    assert _expert_conds(compiled) == []
    assert _expert_stack_readers(compiled, stack) == []


def test_exaone_chunk_program_compiles_and_moves_no_cache(chip):
    """The 256-row prefill chunk: the band over ``[ring ++ chunk]`` (384
    keys) for the window layers, the block loop for the global one, the
    experts' rows gathered 32 at a time."""
    exaone_moe, cfg, params, pools, state, stats = _exaone()

    def step(p, ids, pools, stats, position, bt, state, valid, slot):
        return exaone_moe.fused_paged_chunk_step(
            p, cfg, ids, pools, state, stats, position, bt, valid, slot)

    compiled = jax.jit(step, donate_argnums=(2, 3, 6)).lower(
        chip(params),
        *chip((_s((CHUNK,), I32), pools, stats, _s((), I32),
               _s((EXAONE_SEQ // PAGE,), I32), state, _s((), I32),
               _s((), I32))),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _whole_array_copies(compiled, pools) == []
    assert _ring_copies(compiled) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 512 << 20
    assert _expert_stack_readers(
        compiled, params["blocks"]["1"]["experts"]) == []


# -- GLM-5.3-Flash: delta-rule state a slot, latent and pooled index rows in pages --

#: the catalog row's widths (benchmark/configs/glm-5p3-flash-ep8.json), a dense
#: delta-rule layer, a sparse one and the sparse-latent layer, 36 of 288
#: experts held, the cell's vocabulary slice and context
GLM5 = dict(
    model_type="glm5_next_text", hidden_size=4096, num_attention_heads=64,
    num_key_value_heads=64, head_dim=0, intermediate_size=12288,
    moe_intermediate_size=2048, num_hidden_layers=3, vocab_size=19360,
    rms_norm_eps=1e-5,
    layer_types=["linear_attention"] * 2 + ["deepseek_sparse_attention"],
    mlp_layer_types=["dense"] + ["sparse"] * 2, indexer_types=["full"] * 3,
    linear_attn_config={"num_heads": 64, "head_dim": 128,
                        "short_conv_kernel_size": 4, "gate_lower_bound": -5},
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=256, qk_head_dim=256,
    qk_rope_head_dim=0, v_head_dim=256, mla_use_nope=True,
    index_n_heads=32, index_head_dim=128, index_topk=2048, index_kpool=4,
    index_kpool_compress=True, index_kpool_always_select_tail=True,
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, mhc=True,
    n_routed_experts=288, num_experts_per_tok=8, n_shared_experts=1,
    routed_scaling_factor=2.5, norm_topk_prob=True, scoring_func="sigmoid",
    topk_method="noaux_tc", n_group=1, topk_group=1, swiglu_limit=10, ep_size=8,
)
GLM5_SEQ = 16384


def _glm5():
    """(module, cfg, the serving parameters' shapes as ``load`` builds
    them, pool, slot-state and counter shapes) for 16 slots of 16,384
    rows (1,024 pages a row)."""
    from dora_tpu.models.hf import glm5_next

    cfg = glm5_next.Glm5NextConfig.from_hf(GLM5, GLM5_SEQ, 0)
    bf, d, hk, r = jnp.bfloat16, cfg.dim, cfg.kda_width, cfg.kda_dim
    n = cfg.hc
    fixed = {
        "input_layernorm.weight": (d,), "post_attention_layernorm.weight": (d,),
        **{f"hc_{s}_fn": (2 * n + n * n, n * d) for s in ("attn", "ffn")},
        **{f"hc_{s}_base": (2 * n + n * n,) for s in ("attn", "ffn")},
        **{f"hc_{s}_scale": (3,) for s in ("attn", "ffn")},
        **{f"self_attn.{x}_proj.weight": (hk, d) for x in "qkv"},
        **{f"self_attn.{x}_conv1d.weight": (hk, 1, cfg.conv) for x in "qkv"},
        "self_attn.f_a_proj.weight": (r, d), "self_attn.f_b_proj.weight": (hk, r),
        "self_attn.g_a_proj.weight": (r, d), "self_attn.g_b_proj.weight": (hk, r),
        "self_attn.b_proj.weight": (cfg.kda_heads, d),
        "self_attn.A_log": (cfg.kda_heads,), "self_attn.dt_bias": (hk,),
        "self_attn.o_norm.weight": (r,),
        "self_attn.q_a_proj.weight": (cfg.q_rank, d),
        "self_attn.q_a_layernorm.weight": (cfg.q_rank,),
        "self_attn.q_b_proj.weight": (cfg.heads * cfg.nope, cfg.q_rank),
        "self_attn.kv_a_proj_with_mqa.weight": (cfg.kv_rank, d),
        "self_attn.kv_a_layernorm.weight": (cfg.kv_rank,),
        "self_attn.kv_b_proj.weight": (cfg.heads * (cfg.nope + cfg.v_dim),
                                       cfg.kv_rank),
        "self_attn.indexer.wq_b.weight": (cfg.idx_heads * cfg.idx_dim, cfg.q_rank),
        "self_attn.indexer.wk.weight": (cfg.idx_dim, d),
        "self_attn.indexer.k_norm.weight": (cfg.idx_dim,),
        "self_attn.indexer.k_norm.bias": (cfg.idx_dim,),
        "self_attn.indexer.weights_proj.weight": (cfg.idx_heads, d),
        "mlp.gate.weight": (cfg.n_experts, d),
        "mlp.gate.e_score_correction_bias": (cfg.n_experts,),
    }

    def get(name):
        layer, tail = name.split(".", 3)[2:]
        if tail == "self_attn.o_proj.weight":
            wide = hk if cfg.linear[int(layer)] else cfg.heads * cfg.v_dim
            return jnp.zeros((d, wide), bf)
        if tail in fixed:
            return jnp.zeros(fixed[tail], bf)
        width = cfg.ffn if tail.count(".") == 2 else cfg.moe_ffn  # mlp.x_proj.weight
        return jnp.zeros((d, width) if "down_proj" in tail else (width, d), bf)

    def build():
        return {
            "embed": jnp.zeros((cfg.vocab, d), bf),
            "out_norm": jnp.zeros((d,), bf),
            "lm_head": glm5_next._quantize_t(jnp.zeros((cfg.vocab, d), bf)),
            "blocks": {str(i): glm5_next.load_layer(get, cfg, i)
                       for i in range(cfg.layers)},
        }

    pools = jax.eval_shape(lambda: glm5_next.init_page_pool(
        cfg, SLOTS * GLM5_SEQ // PAGE + 1, PAGE))
    state = jax.eval_shape(lambda: glm5_next.init_slot_state(cfg, SLOTS))
    stats = jax.eval_shape(lambda: glm5_next.init_counters(cfg))
    return glm5_next, cfg, jax.eval_shape(build), pools, state, stats


def _cache_copies(compiled) -> list[str]:
    """``copy`` instructions of a whole pool leaf or a whole delta-rule
    state, by shape: a cache that XLA moved instead of updating in
    place."""
    shapes = ("bf16[16385,16,512]", "bf16[16385,4,128]", "f32[16,64,128,128]")
    return [line.strip()[:120] for line in compiled.as_text().splitlines()
            if " copy(" in line
            and any(s in line.split(" copy(")[0] for s in shapes)]


@pytest.mark.parametrize("picks", [False, True], ids=["served", "audited"])
def test_glm5_window_program_compiles_and_moves_no_cache(chip, picks):
    """The K=8 decode window at GLM-5.3-Flash's widths, 16 slots of
    16,384 rows (1,024 pages a row): every matrix through ``int8_matmul``
    (the 24,960-wide delta-rule input, the 20,480-wide query and indexer
    heads), ``lm_head_argmax`` over 19,360 columns; the delta-rule step
    through ``kda_state_step`` (1 MB blocks of 16 heads, a head's columns
    static lanes of their block); the residual maps, the index scores,
    ``top_k`` of 512 among 4,096 and the gather of 2,052 latent rows a
    row in plain XLA. Pages only for the sparse-latent layer (two
    leaves), 67 MB of float32 state a delta-rule layer; no copy of
    either, and the state is not staged on chip around the kernel (no
    ``copy-start`` of it, no ``S(1)`` on it). As the server jits it, and as a
    cache audit's engine does (``make_paged_engine(picks=True)``: every
    tick's picked blocks and sublayer output come out beside)."""
    glm5_next, cfg, params, pools, state, stats = _glm5()
    assert set(pools) == {"2"} and set(state) == {"0", "1", "2"}
    assert pools["2"]["kv"].shape == (SLOTS * GLM5_SEQ // PAGE + 1, PAGE, 512)
    assert pools["2"]["ik"].shape == (SLOTS * GLM5_SEQ // PAGE + 1, 4, 128)
    assert state["0"]["s"].shape == (SLOTS, 64, 128, 128)
    assert state["0"]["conv"].shape == (SLOTS, 3, 3 * 8192)
    assert state["2"]["acc"].shape == (SLOTS, 128)
    stack = params["blocks"]["1"]["experts"]
    assert stack["w_gateup"]["int8"].shape == (36, 4096, 4096)
    assert stack["limit"].shape == (36,)

    def program(p, *args):
        return glm5_next.window_program(p, cfg, 8, None, *args, picks=picks)

    lowered = jax.jit(program, donate_argnums=(2, 3, 9)).lower(
        chip(params),
        *chip((_s((SLOTS,), I32), pools, stats, _s((SLOTS,), I32),
               _s((SLOTS, GLM5_SEQ // PAGE), I32), _s((SLOTS,), jnp.bool_),
               _s((SLOTS,), I32), _s((SLOTS,), I32), state)),
    )
    looks = jax.tree.leaves(lowered.out_info)[-2:]
    assert ([x.shape for x in looks] == [(8, SLOTS, 4096), (8, SLOTS, 512)]) == picks
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert len(re.findall(r"%kda_state_step\S* = ", text)) == 2  # a KDA layer
    assert _cache_copies(compiled) == []
    state = "f32[16,64,128,128]"
    assert [line.strip()[:120] for line in text.splitlines()
            if state + "{3,2,1,0:T(8,128)S(1)}" in line
            or (state in line and "copy-start(" in line)] == []
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    assert _expert_conds(compiled) == []
    assert _expert_stack_readers(compiled, stack) == []


def _block_a_row(kv, ids):
    return kv.reshape(-1, 4 * kv.shape[-1])[ids].reshape(4, -1, kv.shape[-1])


def _block_a_slice(kv, ids):
    def block(i):
        return jax.lax.dynamic_slice(
            kv, (i // 4, i % 4 * 4, 0), (1, 4, kv.shape[-1]))[0]

    return jax.vmap(jax.vmap(block))(ids).reshape(4, -1, kv.shape[-1])


def _row_at_a_time(kv, ids):
    at = (ids[:, :, None] * 4 + jnp.arange(4)).reshape(4, -1)
    return kv.reshape(-1, kv.shape[-1])[at]


@pytest.mark.parametrize("fetch,relaid", [
    (_block_a_row, " reshape("), (_block_a_slice, " copy("),
    (_row_at_a_time, None)], ids=["block-a-row", "block-a-slice", "row-at-a-time"])
def test_xla_relays_the_latent_pool_to_fetch_a_pooled_block_whole(
        chip, fetch, relaid):
    """Why ``glm5_next.dsa_decode`` names a picked block's four latent rows
    one by one: the four follow one another in their page, 4 KB in
    row-major order, but not on the chip, where a ``[P, 16, 512]`` bf16
    leaf lies in tiles of 16 rows x 128 lanes. The pool seen a block a
    row (``[P * 4, 2048]``) is another layout, and so is a gather of
    ``[4, 512]`` slices at an offset inside the page: for either XLA
    relays the WHOLE leaf, 268 MB read and written a tick, before it
    gathers 8 MB from it. Seen a position a row (``[P * 16, 512]``) the
    leaf is a bitcast and nothing moves (``PERF.md`` section 6, PR 53).
    The day the first two compile without the copy, a block is one fetch
    of four."""
    pool = SLOTS * GLM5_SEQ // PAGE + 1

    def tick(kv, ids, row, pages):
        kv = kv.at[pages, pages % PAGE].set(row)  # the pool is a result too
        return kv, fetch(kv, ids)

    compiled = jax.jit(tick, donate_argnums=(0,)).lower(*chip((
        _s((pool, PAGE, 512), jnp.bfloat16), _s((4, 513), I32),
        _s((SLOTS, 512), jnp.bfloat16), _s((SLOTS,), I32)))).compile()
    whole = [line.strip()[:100] for line in compiled.as_text().splitlines()
             if re.search(r"= bf16\[(16385,16,512|65540,2048)\]\S* (reshape|copy)\(",
                          line)]
    temp = compiled.memory_analysis().temp_size_in_bytes
    if relaid is None:
        assert whole == [] and temp < 1 << 20
    else:
        assert [line for line in whole if relaid in line], whole
        assert temp >= pool * PAGE * 512 * 2


@pytest.mark.parametrize("picks", [False, True], ids=["served", "audited"])
def test_glm5_chunk_program_compiles_and_moves_no_cache(chip, picks):
    """The 256-row prefill chunk: the blocked delta rule (16 blocks of 16
    rows, the pairwise decays of a block in one fused sum), the index
    scores of 256 rows against 4,096 pooled rows, ``top_k``, the dense
    absorbed product under the picked mask a block of 256 cached rows at
    a time, the experts' rows gathered 32 at a time. As the server jits
    it, and with a cache audit's look at the selection."""
    glm5_next, cfg, params, pools, state, stats = _glm5()

    def step(p, ids, pools, stats, position, bt, state, valid, slot):
        return glm5_next.fused_paged_chunk_step(
            p, cfg, ids, pools, state, stats, position, bt, valid, slot,
            picks=picks)

    compiled = jax.jit(step, donate_argnums=(2, 3, 6)).lower(
        chip(params),
        *chip((_s((CHUNK,), I32), pools, stats, _s((), I32),
               _s((GLM5_SEQ // PAGE,), I32), state, _s((), I32),
               _s((), I32))),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _cache_copies(compiled) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30
    assert _expert_stack_readers(
        compiled, params["blocks"]["1"]["experts"]) == []


# -- Keye-VL-2.0: GQA pages read through an indexer's picks, two leaves a layer --

#: the catalog row's widths (benchmark/configs/keye-vl2-30b-ep8.json), two of
#: its identical layers, 16 of 128 experts held, the cell's vocabulary slice
#: and context
KEYE = dict(
    model_type="KeyeVL2", hidden_size=2048, num_attention_heads=32,
    num_key_value_heads=4, head_dim=128, intermediate_size=6144,
    moe_intermediate_size=768, num_hidden_layers=2, vocab_size=18992,
    rms_norm_eps=1e-6, rope_theta=10000000,
    rope_scaling={"mrope_section": [16, 24, 24], "rope_type": "default",
                  "type": "default"},
    sa_config={"indexer_head_dim": 64, "indexer_num_heads": 16,
               "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
               "q_chunk_size": 512, "topk": 2048},
    num_experts=128, num_local_experts=128, num_experts_per_tok=8,
    norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[],
    sliding_window=None, use_sliding_window=False, attention_bias=False,
    tie_word_embeddings=False, ep_size=8,
)
KEYE_SEQ = 16384


def _keye():
    """(module, cfg, the serving parameters' shapes as ``load`` builds
    them, pool and counter shapes) for 16 slots of 16,384 rows."""
    from dora_tpu.models.hf import keye_vl2

    cfg = keye_vl2.KeyeVL2Config.from_hf(KEYE, KEYE_SEQ, 0)
    bf, d = jnp.bfloat16, cfg.dim
    fixed = {
        "input_layernorm.weight": (d,), "post_attention_layernorm.weight": (d,),
        "self_attn.q_proj.weight": (cfg.q_width, d),
        "self_attn.k_proj.weight": (cfg.kv_width, d),
        "self_attn.v_proj.weight": (cfg.kv_width, d),
        "self_attn.o_proj.weight": (d, cfg.q_width),
        "self_attn.q_norm.weight": (cfg.head_dim,),
        "self_attn.k_norm.weight": (cfg.head_dim,),
        "self_attn.indexer.wq.weight": (cfg.idx_heads * cfg.idx_dim, d),
        "self_attn.indexer.wk.weight": (cfg.idx_dim, d),
        "self_attn.indexer.k_norm.weight": (cfg.idx_dim,),
        "self_attn.indexer.k_norm.bias": (cfg.idx_dim,),
        "self_attn.indexer.weights_proj.weight": (cfg.idx_heads, d),
        "mlp.gate.weight": (cfg.n_experts, d),
    }

    def get(name):
        tail = name.split(".", 3)[3]
        if tail in fixed:
            return jnp.zeros(fixed[tail], bf)
        return jnp.zeros(
            (d, cfg.moe_ffn) if "down_proj" in tail else (cfg.moe_ffn, d), bf)

    def build():
        return {
            "embed": jnp.zeros((cfg.vocab, d), bf),
            "out_norm": jnp.zeros((d,), bf),
            "lm_head": keye_vl2._quantize_t(jnp.zeros((cfg.vocab, d), bf)),
            "blocks": {str(i): keye_vl2.load_layer(get, cfg, i)
                       for i in range(cfg.layers)},
        }

    pools = jax.eval_shape(lambda: keye_vl2.init_page_pool(
        cfg, SLOTS * KEYE_SEQ // PAGE + 1, PAGE))
    stats = jax.eval_shape(lambda: keye_vl2.init_counters(cfg))
    return keye_vl2, cfg, jax.eval_shape(build), pools, stats


def _instructions(text: str, op: str) -> list[tuple[str, str]]:
    """(result shape, ``op_name``) of every ``op`` instruction of an
    optimised HLO module's text."""
    found = []
    for line in text.splitlines():
        if f" {op}(" not in line:
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        found.append((line.split(f" {op}(")[0].split(" = ", 1)[1],
                      name.group(1) if name else ""))
    return found


def test_picked_ids_compiles_with_no_sort_and_no_scatter(chip):
    """The tick's ids of one group, ``[4, 16384]`` float32 scores -> 2,048
    positions a row: ONE Mosaic kernel (the scores whole in VMEM, the
    counts' and the compaction's small products on the MXU) and nothing
    beside it that sorts, scatters or gathers."""
    from dora_tpu.ops.picked_ids import picked_ids

    compiled = picked_ids.lower(
        chip(_s((4, KEYE_SEQ), jnp.float32)), k=2048).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    for op in ("sort", "scatter", "gather"):
        assert _instructions(text, op) == [], op
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def _keye_pool_copies(compiled) -> list[str]:
    """``copy`` instructions of a whole pool leaf, by shape."""
    shapes = ("bf16[16385,16,1024]", "bf16[16385,8,128]")
    return [line.strip()[:120] for line in compiled.as_text().splitlines()
            if " copy(" in line
            and any(s in line.split(" copy(")[0] for s in shapes)]


@pytest.mark.parametrize("picks", [False, True], ids=["served", "audited"])
def test_keye_window_program_compiles_and_moves_no_pool(chip, picks):
    """The K=8 decode window at Keye-VL-2.0's widths, 16 slots of 16,384
    rows: every matrix through ``int8_matmul`` (the 6,336-wide fused
    attention and indexer projection), ``lm_head_argmax`` over 18,992
    columns; the index scores a block of 2,048 positions at a time and
    the gather of 2,048 K|V rows a row in plain XLA, the 2,048 of 16,384
    between them named by ``ops/picked_ids`` with no sort and no scatter:
    ONE row gather a layer, the rows' addresses with no
    gather of scalars (``pool_rows``), keys and values of a head as lane
    slices of the gathered rows, no relayout of them (``attend_rows``).
    Two leaves of pages a layer; no copy of either. As the server jits
    it, and as a cache audit's engine does (the slot-state window over
    ``audit_state``, every tick's picks and sublayer output beside)."""
    keye_vl2, cfg, params, pools, stats = _keye()
    assert pools["0"]["kv"].shape == (SLOTS * KEYE_SEQ // PAGE + 1, PAGE, 1024)
    assert pools["0"]["ik"].shape == (SLOTS * KEYE_SEQ // PAGE + 1, PAGE // 2, 128)
    stack = params["blocks"]["1"]["experts"]
    assert stack["w_gateup"]["int8"].shape == (16, 2048, 1536)
    assert stack["w_down"]["int8"].shape == (16, 768, 2048)
    assert params["blocks"]["0"]["wqkv"]["int8"].shape == (2048, 6336)

    def program(p, *args):
        return keye_vl2.window_program(
            p, cfg, 8, None, keye_vl2.INDEX_BLOCK, *args, picks=picks)

    rest = (_s((SLOTS,), I32), _s((SLOTS, KEYE_SEQ // PAGE), I32),
            _s((SLOTS,), jnp.bool_), _s((SLOTS,), I32), _s((SLOTS,), I32))
    if picks:
        rest += (jax.eval_shape(lambda: keye_vl2.audit_state(SLOTS)),)
    lowered = jax.jit(program, donate_argnums=(2, 3)).lower(
        chip(params), *chip((_s((SLOTS,), I32), pools, stats, *rest)))
    looks = jax.tree.leaves(lowered.out_info)[-2:]
    assert ([x.shape for x in looks] == [(8, SLOTS, 2048), (8, SLOTS, 2048)]
            ) == picks
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _keye_pool_copies(compiled) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    gathered = [shape for shape, _ in _instructions(text, "gather")]
    assert sum(g.startswith("bf16[4,2048,1024]") for g in gathered) == cfg.layers
    assert not [g for g in gathered if g.startswith(("s32[4,2048]", "s32[8192]"))]
    assert "bf16[4,2048,2,4,128]" not in text
    assert _expert_conds(compiled) == []
    assert _expert_stack_readers(compiled, stack) == []
    # the tick's ids come without a sort and without a scatter: the sorts
    # left are the router's 8 of 128 a layer and the slots' live order, and
    # nothing under the selection's scope sorts or scatters
    # (``ops/picked_ids``; ``nonzero`` or ``argsort`` would bring them back)
    sorts = _instructions(text, "sort")
    assert len(sorts) == cfg.layers + 1
    assert all("moe_router/top_k" in name or "argsort" in name
               for _, name in sorts)
    assert not [name for op in ("sort", "scatter")
                for _, name in _instructions(text, op) if "dsa_select" in name]
    assert "picked_ids" in text


def test_mosaic_copies_no_single_row_of_a_joined_page(chip):
    """Why the picked rows are XLA's gather and no kernel's copies: a
    ``[P, page, 2 * KV * hd]`` leaf is tiled (8, 128) over its last two
    dimensions, and Mosaic slices a tiled dimension by whole tiles, so a
    kernel cannot name ONE position's row as the source of a copy. (Over
    a leaf whose row is one tile, ``[P, page, 2 * KV, hd]``, it can, at
    21 ns a copy: ``PERF.md`` section 6, PR 50.) The day this compiles,
    ``ROADMAP.md`` reach A6 is open again."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(ids, pool, out, buf, sem):
        def one(j, carry):
            pltpu.make_async_copy(
                pool.at[ids[j] // PAGE, ids[j] % PAGE], buf.at[j], sem.at[0]
            ).start()
            return carry

        jax.lax.fori_loop(0, 256, one, 0)
        pltpu.make_async_copy(buf, buf, sem.at[0]).wait()
        out[...] = buf[...]

    def copy_rows(ids, pool):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(1,),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
                scratch_shapes=[pltpu.VMEM((256, 1024), jnp.bfloat16),
                                pltpu.SemaphoreType.DMA((1,))]),
            out_shape=jax.ShapeDtypeStruct((256, 1024), jnp.bfloat16),
        )(ids, pool)

    lowered = jax.jit(copy_rows).lower(
        *chip((_s((256,), I32), _s((SLOTS * KEYE_SEQ // PAGE + 1, PAGE, 1024),
                                    jnp.bfloat16))))
    with pytest.raises(Exception, match="aligned to tiling"):
        lowered.compile()


@pytest.mark.parametrize("picks", [False, True], ids=["served", "audited"])
def test_keye_chunk_program_compiles_and_moves_no_pool(chip, picks):
    """The 256-row prefill chunk: the index scores of 256 rows against
    the cached keys a block of 2,048 at a time, the mask by threshold at
    each row's 2,048th largest of 16,384 (no sort, no scatter; an audit's
    ids are the mask's positions through ``ops/picked_ids``), attention
    over cached blocks of 256 rows under it, the experts' rows gathered
    32 at a time."""
    keye_vl2, cfg, params, pools, stats = _keye()

    def step(p, ids, pools, stats, position, bt, valid):
        return keye_vl2.fused_paged_chunk_step(
            p, cfg, ids, pools, stats, position, bt, valid, picks=picks)

    compiled = jax.jit(step, donate_argnums=(2, 3)).lower(
        chip(params),
        *chip((_s((CHUNK,), I32), pools, stats, _s((), I32),
               _s((KEYE_SEQ // PAGE,), I32), _s((), I32))),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _keye_pool_copies(compiled) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30
    assert _expert_stack_readers(
        compiled, params["blocks"]["1"]["experts"]) == []


# -- ZAYA1-8B: convolution and value-shift tails a slot beside 1 KB pages, top-1 of 16 held --

#: the catalog row's widths (benchmark/configs/zaya1-8b-pp2.json), two of
#: its identical layers, every expert held, the cell's vocabulary slice
#: and context
ZAYA = dict(
    model_type="zaya", hidden_size=2048, num_attention_heads=8,
    num_key_value_heads=2, head_dim=128, moe_intermediate_size=2048,
    num_hidden_layers=2, layer_types=["hybrid"] * 2, vocab_size=131136,
    rms_norm_eps=1e-5, partial_rotary_factor=0.5, cca_time0=2, cca_time1=2,
    rope_parameters={"hybrid": {"partial_rotary_factor": 0.5,
                                "rope_theta": 5000000, "rope_type": "default"},
                     "rope_type": "default"},
    num_experts=16, num_experts_per_tok=1, router_hidden_size=256,
    hidden_act="silu", attention_bias=False, lm_head_bias=False,
    sliding_window=None, tie_word_embeddings=True,
)
ZAYA_SEQ = 8192


def _zaya():
    """(module, cfg, the serving parameters' shapes as ``load`` builds
    them, pool, tail and counter shapes) for 16 slots of 8,192 rows."""
    from dora_tpu.models.hf import zaya

    cfg = zaya.ZayaConfig.from_hf(ZAYA, ZAYA_SEQ, 0)
    bf, d, hd, r = jnp.bfloat16, cfg.dim, cfg.head_dim, cfg.router_hidden
    width = cfg.conv_width
    fixed = {
        "input_layernorm.weight": (d,), "post_attention_layernorm.weight": (d,),
        "self_attn.q_proj.weight": (cfg.q_width, d),
        "self_attn.k_proj.weight": (cfg.kv_width, d),
        "self_attn.v_proj1.weight": (hd, d), "self_attn.v_proj2.weight": (hd, d),
        "self_attn.o_proj.weight": (d, cfg.q_width),
        "self_attn.conv_qk.0.weight": (width, 1, 2),
        "self_attn.conv_qk.0.bias": (width,),
        "self_attn.conv_qk.1.weight": (width, hd, 2),
        "self_attn.conv_qk.1.bias": (width,), "self_attn.temp": (cfg.kv_heads,),
        "mlp.router.down_proj.weight": (r, d), "mlp.router.down_proj.bias": (r,),
        "mlp.router.state_scale": (r,), "mlp.router.norm.weight": (r,),
        "mlp.router.mlp.0.weight": (r, r), "mlp.router.mlp.0.bias": (r,),
        "mlp.router.mlp.1.weight": (r, r), "mlp.router.mlp.1.bias": (r,),
        "mlp.router.mlp.2.weight": (cfg.n_experts, r),
        "mlp.router.balancing_bias": (cfg.n_experts,),
    }

    def get(name):
        tail = name.split(".", 3)[3]
        if tail in fixed:
            return jnp.zeros(fixed[tail], bf)
        if "_residual." in tail:
            return jnp.zeros((d,), bf)
        return jnp.zeros(
            (d, cfg.moe_ffn) if "down_proj" in tail else (cfg.moe_ffn, d), bf)

    def build():
        embed = jnp.zeros((cfg.vocab, d), bf)
        return {
            "embed": embed, "out_norm": jnp.zeros((d,), bf),
            "lm_head": zaya._quantize_t(embed),
            "blocks": {str(i): zaya.load_layer(get, cfg, i)
                       for i in range(cfg.layers)},
        }

    pools = jax.eval_shape(lambda: zaya.init_page_pool(
        cfg, SLOTS * ZAYA_SEQ // PAGE + 1, PAGE))
    state = jax.eval_shape(lambda: zaya.init_slot_state(cfg, SLOTS))
    stats = jax.eval_shape(lambda: zaya.init_counters(cfg))
    return zaya, cfg, jax.eval_shape(build), pools, state, stats


def _zaya_cache_copies(compiled, pools) -> list[str]:
    """Whole-array copies as large as a pool leaf, but for the one this
    cut's head brings: 131,136 columns (64 x 2,049) are no lane multiple,
    so XLA relays the int8 head for ``lm_head_argmax`` once a program, a
    copy of the PARAMETER and so outside the window's loop over its ticks
    (``KNOWN_ISSUES.md`` "PR 52"; the published 262,272 columns, 128 x
    2,049, need none)."""
    copies = _whole_array_copies(compiled, pools)
    head = [c for c in copies if "s8[2048,131136]" in c]
    assert len(head) <= 1 and all("copy(%p__lm_head" in c for c in head), head
    return [c for c in copies if c not in head]


def test_zaya_window_program_compiles_and_moves_no_cache(chip):
    """The K=8 decode window at ZAYA1-8B's widths, 16 slots of 8,192 rows:
    the 1,536-wide fused projection and ``Wo`` through ``int8_matmul``,
    the two convolutions over ``[tail ++ row]`` and the router's MLP in
    float32 XLA, the pages (one ``[P, 16, 512]`` leaf a layer, 134 MB)
    through ``attention_paged_rows_step`` under Mosaic (8 / 2 heads of
    128: 4 query rows a K/V head), the 16 held experts as one stack of
    which a tick's grouped product reads the touched ones,
    ``lm_head_argmax`` over 131,136 columns. Neither a pool nor the
    experts' stack is copied. Every tick's top-1 picks come
    back beside (the server's program and a cache audit's are this one)."""
    zaya, cfg, params, pools, state, stats = _zaya()
    assert set(pools) == set(state) == {"0", "1"}
    assert pools["0"]["kv"].shape == (SLOTS * ZAYA_SEQ // PAGE + 1, PAGE, 512)
    assert state["0"]["c"].shape == (SLOTS, 2, 1280)
    assert state["0"]["v"].shape == (SLOTS, 128)
    stack = params["blocks"]["1"]["experts"]
    assert stack["w_gateup"]["int8"].shape == (16, 2048, 4096)
    assert stack["w_down"]["int8"].shape == (16, 2048, 2048)
    assert params["blocks"]["0"]["wqkv"]["int8"].shape == (2048, 1536)
    assert params["blocks"]["0"]["conv1_w"].shape == (2, 10, 128, 128)

    def program(p, *args):
        return zaya.window_program(p, cfg, 8, None, *args)

    lowered = jax.jit(program, donate_argnums=(2, 3, 9)).lower(
        chip(params),
        *chip((_s((SLOTS,), I32), pools, stats, _s((SLOTS,), I32),
               _s((SLOTS, ZAYA_SEQ // PAGE), I32), _s((SLOTS,), jnp.bool_),
               _s((SLOTS,), I32), _s((SLOTS,), I32), state)),
    )
    assert jax.tree.leaves(lowered.out_info)[-1].shape == (8, 2, SLOTS)
    compiled = lowered.compile()
    text = compiled.as_text()
    # one call a layer, inside the window's loop over its ticks
    assert len(re.findall(r"= \S+ custom-call\(.*attention_paged_rows_step",
                          text)) == cfg.layers
    assert _zaya_cache_copies(compiled, pools) == []
    # 268.6 MB of it the relaid head, 4 MB the rest
    assert compiled.memory_analysis().temp_size_in_bytes < 288 << 20
    assert _expert_conds(compiled) == []
    assert _expert_stack_readers(compiled, stack) == []


def test_zaya_chunk_program_compiles_and_moves_no_cache(chip):
    """The 256-row prefill chunk: the convolutions over ``[tail ++ chunk]``
    (258 rows), the block loop over the cached rows, the experts' rows
    gathered 32 at a time (some 16 rows an expert at top-1 of 16: a block
    runs half empty)."""
    zaya, cfg, params, pools, state, stats = _zaya()

    def step(p, ids, pools, stats, position, bt, state, valid, slot):
        return zaya.fused_paged_chunk_step(
            p, cfg, ids, pools, state, stats, position, bt, valid, slot)

    compiled = jax.jit(step, donate_argnums=(2, 3, 6)).lower(
        chip(params),
        *chip((_s((CHUNK,), I32), pools, stats, _s((), I32),
               _s((ZAYA_SEQ // PAGE,), I32), state, _s((), I32),
               _s((), I32))),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _zaya_cache_copies(compiled, pools) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    assert _expert_stack_readers(
        compiled, params["blocks"]["1"]["experts"]) == []


# -- Olmo-Hybrid-7B: delta-rule heads of 96 x 192, 30 ungrouped K/V heads -----

OLMO = dict(
    model_type="olmo_hybrid", vocab_size=100352, hidden_size=3840,
    intermediate_size=11008, num_hidden_layers=4, num_attention_heads=30,
    num_key_value_heads=30, rms_norm_eps=1e-6, tie_word_embeddings=False,
    attention_bias=False,
    layer_types=["linear_attention"] * 3 + ["full_attention"],
    linear_num_key_heads=30, linear_num_value_heads=30,
    linear_key_head_dim=96, linear_value_head_dim=192,
    linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    rope_parameters={"rope_theta": None},
)
OLMO_SEQ, OLMO_PAGES = 12288, 6844


def _olmo():
    """(module, cfg, the serving parameters' shapes as ``load`` builds
    them, pool, slot-state and counter shapes) for ONE period of the 16
    published layers (every period is the same four programs), 16 slots
    of 12,288 rows and the pool the bytes' rule gives the cell."""
    from dora_tpu.models.hf import olmo_hybrid

    cfg = olmo_hybrid.OlmoHybridConfig.from_hf(OLMO, OLMO_SEQ)
    bf, d = jnp.bfloat16, cfg.dim
    kw, vw = cfg.gdn_key_width, cfg.gdn_value_width
    shapes = {
        "post_attention_layernorm.weight": (d,),
        "post_feedforward_layernorm.weight": (d,),
        "linear_attn.q_proj.weight": (kw, d), "linear_attn.k_proj.weight": (kw, d),
        "linear_attn.v_proj.weight": (vw, d), "linear_attn.g_proj.weight": (vw, d),
        "linear_attn.a_proj.weight": (cfg.gdn_heads, d),
        "linear_attn.b_proj.weight": (cfg.gdn_heads, d),
        "linear_attn.q_conv1d.weight": (kw, 1, cfg.conv),
        "linear_attn.k_conv1d.weight": (kw, 1, cfg.conv),
        "linear_attn.v_conv1d.weight": (vw, 1, cfg.conv),
        "linear_attn.A_log": (cfg.gdn_heads,), "linear_attn.dt_bias": (cfg.gdn_heads,),
        "linear_attn.o_norm.weight": (cfg.gdn_dv,),
        "linear_attn.o_proj.weight": (d, vw),
        **{f"self_attn.{x}_proj.weight": (d, d) for x in "qkvo"},
        "self_attn.q_norm.weight": (d,), "self_attn.k_norm.weight": (d,),
        "mlp.gate_proj.weight": (cfg.ffn, d), "mlp.up_proj.weight": (cfg.ffn, d),
        "mlp.down_proj.weight": (d, cfg.ffn),
    }

    def get(name):
        return jnp.zeros(shapes[name.split(".", 3)[3]], bf)

    def build():
        return {
            "embed": jnp.zeros((cfg.vocab, d), bf),
            "out_norm": jnp.zeros((d,), bf),
            "lm_head": olmo_hybrid._quantize_t(jnp.zeros((cfg.vocab, d), bf)),
            "blocks": {str(i): olmo_hybrid.load_layer(get, cfg, i)
                       for i in range(cfg.layers)},
        }

    pools = jax.eval_shape(
        lambda: olmo_hybrid.init_page_pool(cfg, OLMO_PAGES, PAGE))
    state = jax.eval_shape(lambda: olmo_hybrid.init_slot_state(cfg, SLOTS))
    stats = jax.eval_shape(lambda: olmo_hybrid.init_counters(cfg))
    return olmo_hybrid, cfg, jax.eval_shape(build), pools, state, stats


def _olmo_cache_copies(compiled) -> list[str]:
    """``copy`` instructions of a whole pool leaf or a whole delta-rule
    state, by shape (the 1.1 MB tail a layer is relaid into fast memory
    by XLA for the convolution's sum, as GLM's is: 0.6 % of a tick's
    bytes)."""
    shapes = (f"bf16[{OLMO_PAGES},16,7680]", "f32[16,30,96,192]")
    return [line.strip()[:120] for line in compiled.as_text().splitlines()
            if " copy(" in line
            and any(s in line.split(" copy(")[0] for s in shapes)]


def test_delta_rule_step_kernel_compiles_at_30_heads_of_96_by_192(chip):
    """``ops/kda_state_step`` at Olmo-Hybrid-7B's heads: 30 has no divisor
    that is a multiple of 8 under the 1 MB cap (14 heads of 73,728 B), so
    a grid step holds all 30 (2.2 MB, four of those in VMEM); ``d_v`` =
    192 is one and a half lane tiles. Mosaic takes both. The gate comes a
    head and is spread over the key channels outside the kernel."""
    from dora_tpu.models.delta_rule import delta_rule_step
    from dora_tpu.ops.kda_state_step import head_block

    assert head_block(30, 96, 192) == 30
    assert head_block(64, 128, 128) == 16  # GLM's: as it was
    f32 = jnp.float32
    h, dk, dv = 30, 96, 192
    compiled = jax.jit(delta_rule_step, donate_argnums=(0,)).lower(*chip((
        _s((SLOTS, h, dk, dv), f32), _s((SLOTS, h), f32), _s((SLOTS, h, dk), f32),
        _s((SLOTS, h, dk), f32), _s((SLOTS, h, dv), f32), _s((SLOTS, h), f32),
        _s((SLOTS,), jnp.bool_)))).compile()
    text = compiled.as_text()
    assert "kda_state_step" in text and "tpu_custom_call" in text
    assert not [line for line in text.splitlines()
                if " copy(" in line and "f32[16,30,96,192]" in line.split(" copy(")[0]]


def test_paged_rows_attention_compiles_with_30_kv_heads_of_one_query_row(chip):
    """``attention_paged_rows_step`` as Olmo-Hybrid-7B's full layers call
    it: 30 K/V heads, each with ONE query row and a zero row beside it
    (the joined layout has no one-row form: the kernel refuses ``G`` = 1
    by name), a position's row 15,360 B, a 128-row group 1.97 MB a
    buffer."""
    from dora_tpu.ops import decode_block as DB

    bf = jnp.bfloat16
    pool = _s((OLMO_PAGES, PAGE, 2 * 30 * 128), bf)
    rest = (_s((SLOTS,), I32), _s((SLOTS, OLMO_SEQ // PAGE), I32))
    with pytest.raises(AssertionError, match="no one-row form"):
        jax.jit(DB.attention_paged_rows_step).lower(
            *chip((_s((SLOTS, 30, 1, 128), bf), pool, *rest)))
    compiled = jax.jit(DB.attention_paged_rows_step).lower(
        *chip((_s((SLOTS, 30, 2, 128), bf), pool, *rest))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_olmo_hybrid_window_program_compiles_and_moves_no_cache(chip):
    """The K=8 decode window at Olmo-Hybrid-7B's widths, one period, 16
    slots of 12,288 rows over 6,844 pages: the 17,536-wide fused input
    matrix of a linear layer and the 11,520-wide ``wqkv`` of the full one
    through ``int8_matmul``, the convolution over ``[tail ++ row]`` in
    float32 XLA, the state through ``kda_state_step`` (one call a linear
    layer a tick), the pages through ``attention_paged_rows_step``,
    ``lm_head_argmax`` over 100,352 columns (784 lane tiles). Neither the
    pool nor a state is copied."""
    olmo, cfg, params, pools, state, stats = _olmo()
    assert set(pools) == {"3"} and set(state) == {"0", "1", "2"}
    assert pools["3"]["kv"].shape == (OLMO_PAGES, PAGE, 7680)
    assert state["0"]["s"].shape == (SLOTS, 30, 96, 192)
    assert state["0"]["conv"].shape == (SLOTS, 3, 11520)
    assert params["blocks"]["0"]["w_in"]["int8"].shape == (3840, 17536)
    assert params["blocks"]["3"]["wqkv"]["int8"].shape == (3840, 11520)

    def program(p, *args):
        return olmo.window_program(p, cfg, 8, None, *args)

    compiled = jax.jit(program, donate_argnums=(2, 3, 9)).lower(
        chip(params),
        *chip((_s((SLOTS,), I32), pools, stats, _s((SLOTS,), I32),
               _s((SLOTS, OLMO_SEQ // PAGE), I32), _s((SLOTS,), jnp.bool_),
               _s((SLOTS,), I32), _s((SLOTS,), I32), state)),
    ).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%kda_state_step\S* = ", text)) == 3  # a linear layer
    assert len(re.findall(r"= \S+ custom-call\(.*attention_paged_rows_step",
                          text)) == 1
    assert _olmo_cache_copies(compiled) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_olmo_hybrid_chunk_program_compiles_and_moves_no_cache(chip):
    """The 256-row prefill chunk: the blocked delta rule in four blocks of
    64 rows with its unit-triangular systems SOLVED (``beta`` reaches 2:
    XLA's triangular solve, which the chip's compiler takes), the full
    layer's block loop over the cached rows."""
    olmo, cfg, params, pools, state, stats = _olmo()

    def step(p, ids, pools, stats, position, bt, state, valid, slot):
        return olmo.fused_paged_chunk_step(
            p, cfg, ids, pools, state, stats, position, bt, valid, slot)

    compiled = jax.jit(step, donate_argnums=(2, 3, 6)).lower(
        chip(params),
        *chip((_s((CHUNK,), I32), pools, stats, _s((), I32),
               _s((OLMO_SEQ // PAGE,), I32), state, _s((), I32),
               _s((), I32))),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _olmo_cache_copies(compiled) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_state_snapshot_copy_compiles_in_place(chip):
    """The engine's snapshot copy (a row of the slots' state into a row of
    the 35-row pool, and back) at the published state: a dynamic slice and
    a dynamic update a leaf on the donated tree, no whole-array copy."""
    olmo, cfg, _params, _pools, state, _stats = _olmo()
    rows = jax.eval_shape(lambda: olmo.init_slot_state(cfg, 35))

    def copy_row(into, of, to_row, from_row):
        return jax.tree.map(
            lambda a, b: jax.lax.dynamic_update_index_in_dim(
                a, jax.lax.dynamic_index_in_dim(b, from_row, keepdims=False),
                to_row, 0), into, of)

    for into, of, shape in ((rows, state, "f32[35,30,96,192]"),
                            (state, rows, "f32[16,30,96,192]")):
        compiled = jax.jit(copy_row, donate_argnums=(0,)).lower(
            *chip((into, of, _s((), I32), _s((), I32)))).compile()
        assert not [line for line in compiled.as_text().splitlines()
                    if " copy(" in line and shape in line.split(" copy(")[0]]


# -- Kimi-Linear-48B-A3B: 64 slots, KDA heads of 128 x 128, a 640-wide latent row --

KIMI_LINEAR = dict(
    model_type="kimi_linear", vocab_size=40960, hidden_size=2304,
    intermediate_size=9216, moe_intermediate_size=1024, num_hidden_layers=5,
    num_attention_heads=32, kv_lora_rank=512, q_lora_rank=None,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    mla_use_nope=True, rope_scaling=None, rms_norm_eps=1e-5,
    linear_attn_config=dict(kda_layers=[1, 2, 3, 5], full_attn_layers=[4],
                            num_heads=32, head_dim=128,
                            short_conv_kernel_size=4),
    first_k_dense_replace=1, moe_layer_freq=1, num_experts=256, ep_size=4,
    num_experts_per_token=8, num_shared_experts=1, moe_renormalize=True,
    moe_router_activation_func="sigmoid", num_expert_group=1, topk_group=1,
    routed_scaling_factor=2.446, tie_word_embeddings=False,
)
KL_SLOTS, KL_SEQ = 64, 16384
KL_PAGES = KL_SLOTS * KL_SEQ // PAGE + 1


def _kimi_linear():
    """(module, cfg, the serving parameters' shapes as ``load`` builds
    them, pool, slot-state and counter shapes) for published layers 1-5
    (the dense layer and one whole period: the cell's second period is the
    same programs), 64 slots of 16,384 rows, every slot's pages."""
    from dora_tpu.models.hf import kimi_linear

    cfg = kimi_linear.KimiLinearConfig.from_hf(KIMI_LINEAR, KL_SEQ, ep_rank=0)
    bf, d, hk, r = jnp.bfloat16, cfg.dim, cfg.kda_width, cfg.kda_dim
    ffn = {"gate_proj.weight": (cfg.moe_ffn, d), "up_proj.weight": (cfg.moe_ffn, d),
           "down_proj.weight": (d, cfg.moe_ffn), "w1.weight": (cfg.moe_ffn, d),
           "w3.weight": (cfg.moe_ffn, d), "w2.weight": (d, cfg.moe_ffn)}
    kda = {
        **{f"self_attn.{x}_proj.weight": (hk, d) for x in "qkv"},
        **{f"self_attn.{x}_conv1d.weight": (hk, 1, cfg.conv) for x in "qkv"},
        "self_attn.f_a_proj.weight": (r, d), "self_attn.f_b_proj.weight": (hk, r),
        "self_attn.g_a_proj.weight": (r, d), "self_attn.g_b_proj.weight": (hk, r),
        "self_attn.b_proj.weight": (cfg.kda_heads, d),
        "self_attn.A_log": (cfg.kda_heads,), "self_attn.dt_bias": (hk,),
        "self_attn.o_norm.weight": (r,), "self_attn.o_proj.weight": (d, hk),
    }
    mla = {
        "self_attn.q_proj.weight": (cfg.heads * (cfg.nope + cfg.shared), d),
        "self_attn.kv_a_proj_with_mqa.weight": (cfg.latent, d),
        "self_attn.kv_a_layernorm.weight": (cfg.kv_rank,),
        "self_attn.kv_b_proj.weight": (cfg.heads * (cfg.nope + cfg.v_dim),
                                       cfg.kv_rank),
        "self_attn.o_proj.weight": (d, cfg.heads * cfg.v_dim),
    }

    def get(name):
        layer, rest = name.split(".", 3)[2:]
        if rest in ("input_layernorm.weight", "post_attention_layernorm.weight"):
            return jnp.zeros((d,), bf)
        if rest.startswith("self_attn."):
            return jnp.zeros((kda if cfg.linear[int(layer)] else mla)[rest], bf)
        if rest.startswith("mlp."):
            shape = ffn[rest.split(".", 1)[1]]
            return jnp.zeros(tuple(cfg.ffn if n == cfg.moe_ffn else n
                                   for n in shape), bf)
        if rest == "block_sparse_moe.gate.weight":
            return jnp.zeros((cfg.n_experts, d), bf)
        if rest == "block_sparse_moe.gate.e_score_correction_bias":
            return jnp.zeros((cfg.n_experts,), bf)
        return jnp.zeros(ffn[rest.rsplit(".", 2)[1] + ".weight"], bf)

    def build():
        return {
            "embed": jnp.zeros((cfg.vocab, d), bf),
            "out_norm": jnp.zeros((d,), bf),
            "lm_head": kimi_linear._quantize_t(jnp.zeros((cfg.vocab, d), bf)),
            "blocks": {str(i): kimi_linear.load_layer(get, cfg, i)
                       for i in range(cfg.layers)},
        }

    pools = jax.eval_shape(
        lambda: kimi_linear.init_page_pool(cfg, KL_PAGES, PAGE))
    state = jax.eval_shape(lambda: kimi_linear.init_slot_state(cfg, KL_SLOTS))
    stats = jax.eval_shape(lambda: kimi_linear.init_counters(cfg))
    return kimi_linear, cfg, jax.eval_shape(build), pools, state, stats


def _kimi_linear_cache_copies(compiled) -> list[str]:
    """``copy`` instructions of a whole pool leaf or a whole delta-rule
    state, by shape."""
    shapes = (f"bf16[{KL_PAGES},16,640]", "f32[64,32,128,128]")
    return [line.strip()[:120] for line in compiled.as_text().splitlines()
            if " copy(" in line
            and any(s in line.split(" copy(")[0] for s in shapes)]


def test_kimi_linear_window_program_compiles_at_64_slots(chip):
    """The K=8 decode window at Kimi-Linear-48B-A3B's widths with 64 SLOTS
    of 16,384 rows over every slot's pages (2.68 GB a latent layer's pool
    at 640 stored values a row): the 12,672-wide fused input matrix of a
    KDA layer and the 6,784-wide one of the latent layer through
    ``int8_matmul`` at M = 64, the state through ``kda_state_step`` (one
    call a KDA layer a tick, 64 rows of 2 MB), the latent rows through
    ``attend_latent_blocks`` 512 at a time, the 64 held experts in two
    grouped products a layer (64 rows go whole), ``lm_head_argmax`` over
    40,960 columns. Neither the pool nor a state is copied."""
    kl, cfg, params, pools, state, stats = _kimi_linear()
    assert set(pools) == {"3"} and set(state) == {"0", "1", "2", "4"}
    assert pools["3"]["kv"].shape == (KL_PAGES, PAGE, 640)
    assert state["0"]["s"].shape == (64, 32, 128, 128)
    assert state["0"]["conv"].shape == (64, 3, 12288)
    assert params["blocks"]["0"]["w_in"]["int8"].shape == (2304, 12672)
    assert params["blocks"]["3"]["w_in"]["int8"].shape == (2304, 6784)
    assert params["blocks"]["1"]["experts"]["w_gateup"]["int8"].shape == (
        64, 2304, 2048)

    def program(p, *args):
        return kl.window_program(p, cfg, 8, None, 512, *args)

    compiled = jax.jit(program, donate_argnums=(2, 3, 9)).lower(
        chip(params),
        *chip((_s((KL_SLOTS,), I32), pools, stats, _s((KL_SLOTS,), I32),
               _s((KL_SLOTS, KL_SEQ // PAGE), I32), _s((KL_SLOTS,), jnp.bool_),
               _s((KL_SLOTS,), I32), _s((KL_SLOTS,), I32), state)),
    ).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%kda_state_step\S* = ", text)) == 4  # a KDA layer
    assert _kimi_linear_cache_copies(compiled) == []
    assert _expert_stack_readers(
        compiled, params["blocks"]["1"]["experts"]) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_kimi_linear_chunk_program_compiles_at_64_slots(chip):
    """The 256-row prefill chunk beside 64 slots' state: the blocked delta
    rule in 16 blocks of 16 rows from the slot's row of ``[64, 32, 128,
    128]``, the latent layer's block loop over the cached rows, every held
    expert its own rows 32 at a time."""
    kl, cfg, params, pools, state, stats = _kimi_linear()

    def step(p, ids, pools, stats, position, bt, state, valid, slot):
        return kl.fused_paged_chunk_step(
            p, cfg, ids, pools, state, stats, position, bt, valid, slot,
            block=512)

    compiled = jax.jit(step, donate_argnums=(2, 3, 6)).lower(
        chip(params),
        *chip((_s((CHUNK,), I32), pools, stats, _s((), I32),
               _s((KL_SEQ // PAGE,), I32), state, _s((), I32),
               _s((), I32))),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _kimi_linear_cache_copies(compiled) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30
