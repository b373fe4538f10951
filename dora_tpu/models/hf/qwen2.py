"""Qwen2-family causal LM serving pretrained HF checkpoints.

Faithful to transformers' `Qwen2ForCausalLM` compute graph (RMSNorm,
NeoX-style RoPE with configurable theta, GQA, SwiGLU, q/k/v biases) so
real checkpoint weights produce the same logits — asserted numerically in
tests/test_hf_parity.py. Reference serves this family through torch
(node-hub/dora-qwenvl/dora_qwenvl/main.py:24-56); here the whole
prefill+decode path jits into XLA programs with a static-shape KV cache.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp

from dora_tpu import backend, profiling
from dora_tpu.models import layers as L
from dora_tpu.models.hf.loader import (
    linear,
    maybe_bias,
    read_config,
    read_safetensors,
)
from dora_tpu.models.paged_window import (
    make_paged_spec_window,
    make_paged_window,
)


#: What the chip's compiler says to the three int8-KV paged kernels
#: (tests/test_chip_compile.py holds the compiles as strict xfails). The
#: engine refuses at construction rather than discover it mid-serve.
KV_INT8_REFUSED = (
    "DORA_KV_INT8 does not compile for the TPU yet: Mosaic refuses the "
    "[P, KV, page] f32 scale planes of the paged attention kernels — "
    "'Mosaic failed to compile TPU kernel: Slice shape along dimension 2 "
    "must be aligned to tiling (128), but is 8' (16 for the chunk kernel). "
    "The planes' minor dimension (the page, 16 rows) is narrower than a "
    "128-lane tile, so even a whole-page slice is refused; they need a "
    "lane-dense layout (KNOWN_ISSUES.md, ROADMAP speed item 9). Serve "
    "with fp KV pages on the chip."
)


@dataclass(frozen=True)
class Qwen2Config:
    vocab: int
    dim: int
    layers: int
    heads: int
    kv_heads: int
    ffn: int
    rope_theta: float
    norm_eps: float
    tie_embeddings: bool
    max_seq: int = 2048

    @property
    def head_dim(self) -> int:
        return self.dim // self.heads

    @classmethod
    def from_hf(cls, config: dict, max_seq: int | None = None) -> "Qwen2Config":
        return cls(
            vocab=config["vocab_size"],
            dim=config["hidden_size"],
            layers=config["num_hidden_layers"],
            heads=config["num_attention_heads"],
            kv_heads=config.get("num_key_value_heads", config["num_attention_heads"]),
            ffn=config["intermediate_size"],
            rope_theta=config.get("rope_theta", 10000.0),
            norm_eps=config.get("rms_norm_eps", 1e-6),
            tie_embeddings=config.get("tie_word_embeddings", False),
            max_seq=max_seq
            or min(config.get("max_position_embeddings", 2048), 2048),
        )


def load(model_dir: str | Path, max_seq: int | None = None):
    """(config, params) from a HF checkpoint directory."""
    hf_config = read_config(model_dir)
    cfg = Qwen2Config.from_hf(hf_config, max_seq)
    tensors = read_safetensors(model_dir)
    prefix = "model." if any(k.startswith("model.") for k in tensors) else ""
    params = map_params(tensors, cfg, prefix)
    return cfg, params


def map_params(tensors: dict, cfg: Qwen2Config, prefix: str = "model.") -> dict:
    """Checkpoint names → the shared-block parameter layout."""
    params: dict[str, Any] = {
        "embed": tensors[f"{prefix}embed_tokens.weight"],
        "out_norm": tensors[f"{prefix}norm.weight"],
        "blocks": {},
    }
    for i in range(cfg.layers):
        lp = f"{prefix}layers.{i}."
        block: dict[str, Any] = {
            "attn_norm": tensors[lp + "input_layernorm.weight"],
            "wq": linear(tensors, lp + "self_attn.q_proj.weight"),
            "wk": linear(tensors, lp + "self_attn.k_proj.weight"),
            "wv": linear(tensors, lp + "self_attn.v_proj.weight"),
            "wo": linear(tensors, lp + "self_attn.o_proj.weight"),
            "ffn_norm": tensors[lp + "post_attention_layernorm.weight"],
            "w_gate": linear(tensors, lp + "mlp.gate_proj.weight"),
            "w_up": linear(tensors, lp + "mlp.up_proj.weight"),
            "w_down": linear(tensors, lp + "mlp.down_proj.weight"),
        }
        maybe_bias(block, "bq", tensors, lp + "self_attn.q_proj.bias")
        maybe_bias(block, "bk", tensors, lp + "self_attn.k_proj.bias")
        maybe_bias(block, "bv", tensors, lp + "self_attn.v_proj.bias")
        maybe_bias(block, "bo", tensors, lp + "self_attn.o_proj.bias")
        params["blocks"][str(i)] = block
    if not cfg.tie_embeddings and "lm_head.weight" in tensors:
        params["lm_head"] = linear(tensors, "lm_head.weight")
    return jax.tree.map(jnp.asarray, params)


def _head(params, cfg: Qwen2Config, dtype):
    head = params.get("lm_head")
    if isinstance(head, dict):  # quantized (quantize_decode)
        return head
    if cfg.tie_embeddings or head is None:
        return params["embed"].astype(dtype).T
    return head.astype(dtype)


def _head_logits(h, head):
    """h @ head for a float head array or a quantized head dict."""
    if isinstance(head, dict):
        return L.matmul(h, head).astype(jnp.float32)
    return (h @ head).astype(jnp.float32)


def quantize_decode(params, cfg) -> dict:
    """Quantize a Qwen2-class LM's decode path (blocks + head) into the
    fused kernel layout — shared by the text model, Qwen2-VL, and
    InternVL (whose text model IS this module). Serving gates:
    DORA_INT8_DECODE / DORA_INT4_DECODE / DORA_INT8_PURE; a tied head
    materializes from the embedding transpose (the embedding itself
    stays float for the gather). ``DORA_WEIGHT_BITS`` (8 or 4) is the
    serving-plane spelling of the same choice: 4 selects the int4
    grouped layout exactly like DORA_INT4_DECODE=1."""
    import os

    from dora_tpu.ops.int8_matmul import quantize_int8, quantize_tree

    bits = os.environ.get("DORA_WEIGHT_BITS", "")
    if bits and bits not in ("4", "8"):
        raise ValueError(f"DORA_WEIGHT_BITS must be 4 or 8, got {bits!r}")
    quantizer = quantize_int8
    if os.environ.get("DORA_INT4_DECODE") or bits == "4":
        from dora_tpu.ops.int4 import quantize_int4 as quantizer  # noqa: F811

    keep_bf16 = not os.environ.get("DORA_INT8_PURE")
    out = dict(params)
    out["blocks"] = quantize_tree(
        params["blocks"], keep_bf16=keep_bf16, quantizer=quantizer
    )
    head = params.get("lm_head")
    if cfg.tie_embeddings or head is None:
        head = jnp.asarray(params["embed"]).T
    out["lm_head"] = quantize_tree(
        {"lm_head": jnp.asarray(head)}, keep_bf16=keep_bf16,
        quantizer=quantizer,
    )["lm_head"]
    return out


def fused_step(params, cfg, tokens, caches, position):
    """Standard-RoPE fused decode pass (ops.decode_block via
    models/vlm.fused_decode_pass): tokens [1, W] at cache AND rope
    positions ``position..position+W-1``. Gate with
    models/vlm.fused_decode_ready."""
    from dora_tpu.models import vlm as _vlm
    from dora_tpu.ops import decode_block as DB

    dtype = L.compute_dtype()
    w = tokens.shape[1]
    cos_t, sin_t = L.rope_table(cfg.max_seq, cfg.head_dim,
                                base=cfg.rope_theta)
    cos_rows, sin_rows = DB.rope_rows(cos_t, sin_t, position, w)
    x = params["embed"].astype(dtype)[tokens[0]]  # [W, dim]
    return _vlm.fused_decode_pass(
        params, x, caches, position, cos_rows, sin_rows,
        heads=cfg.heads, kv_heads=cfg.kv_heads, head_dim=cfg.head_dim,
        layers=cfg.layers, eps=cfg.norm_eps,
    )


def fused_paged_batch_step(params, cfg, tokens, pools, positions,
                           block_tables, lora=None):
    """One fused decode step for B independent sequences over PAGED KV
    pools. tokens/positions: [B] int32; block_tables: [B, max_pages]
    int32 (0 = the reserved null page); pools: {layer: {k/v:
    [P, KV, page, hd]}}. Returns (greedy [B], pools). The paged
    engine's inner step (models/batch_engine.PagedBatchEngine).
    ``lora`` is ``(groups [B], a_stack [S, L, dim, r],
    b_stack [S, L, r, dim])`` — per-row adapter deltas gathered by the
    grouped Pallas matmul inside the fused pass (ops/lora.py); None is
    the adapter-free program, byte-identical to before."""
    from dora_tpu.models import vlm as _vlm
    from dora_tpu.ops import decode_block as DB

    dtype = L.compute_dtype()
    cos_t, sin_t = L.rope_table(cfg.max_seq, cfg.head_dim,
                                base=cfg.rope_theta)
    cos_rows, sin_rows = DB.rope_rows_at(cos_t, sin_t, positions)
    x = params["embed"].astype(dtype)[tokens]  # [B, dim]
    return _vlm.fused_paged_pass_batch(
        params, x, pools, positions, block_tables, cos_rows, sin_rows,
        heads=cfg.heads, kv_heads=cfg.kv_heads, head_dim=cfg.head_dim,
        layers=cfg.layers, eps=cfg.norm_eps, lora=lora,
    )


def fused_paged_spec_step(params, cfg, chunks, pools, positions,
                          block_tables, lora=None):
    """Speculative VERIFICATION pass for B independent streams over
    PAGED KV pools: chunks [B, m] holds each stream's (last token +
    m-1 drafts) at positions ``positions[b]..positions[b]+m-1``;
    greedy[b, i] continues stream b's prefix through candidate i, so
    the caller's acceptance test over (greedy, drafts) replays the
    serial spec_decode contract exactly. Returns (greedy [B, m],
    pools). The spec window's inner step
    (models/paged_window.make_paged_spec_window)."""
    from dora_tpu.models import vlm as _vlm
    from dora_tpu.ops import decode_block as DB

    dtype = L.compute_dtype()
    b, m = chunks.shape
    cos_t, sin_t = L.rope_table(cfg.max_seq, cfg.head_dim,
                                base=cfg.rope_theta)
    flat_pos = (positions[:, None] + jnp.arange(m)[None, :]).reshape(b * m)
    cos_rows, sin_rows = DB.rope_rows_at(cos_t, sin_t, flat_pos)
    x = params["embed"].astype(dtype)[chunks.reshape(b * m)]  # [B*m, dim]
    if lora is not None:
        # The pass sees B*m flattened rows; every candidate row of a
        # stream gathers that stream's adapter.
        groups, a_stack, b_stack = lora
        lora = (jnp.repeat(groups, m), a_stack, b_stack)
    greedy, pools = _vlm.fused_paged_pass_spec(
        params, x, pools, positions, block_tables, cos_rows, sin_rows,
        heads=cfg.heads, kv_heads=cfg.kv_heads, head_dim=cfg.head_dim,
        layers=cfg.layers, m=m, eps=cfg.norm_eps, lora=lora,
    )
    return greedy.reshape(b, m), pools


def fused_paged_chunk_step(params, cfg, chunk_ids, pools, position,
                           block_table, lora=None):
    """One prefill chunk into paged pools: chunk_ids [C] int32 at
    positions ``position..position+C-1`` (both page-multiples; the tail
    chunk is right-padded — pad rows sit AFTER the real tokens, so no
    real token attends one; they land beyond ``true_len`` and are
    overwritten by decode before they become attendable, since decode
    at position p attends idx < p only). ``position`` is a TRACED scalar,
    so every chunk of every prompt shares ONE compiled program.
    Returns (greedy [C], pools)."""
    from dora_tpu.models import vlm as _vlm
    from dora_tpu.ops import decode_block as DB

    dtype = L.compute_dtype()
    c = chunk_ids.shape[0]
    cos_t, sin_t = L.rope_table(cfg.max_seq, cfg.head_dim,
                                base=cfg.rope_theta)
    cos_rows, sin_rows = DB.rope_rows(cos_t, sin_t, position, c)
    x = params["embed"].astype(dtype)[chunk_ids]  # [C, dim]
    if lora is not None:
        # One prompt per chunk call: every row is the same tenant.
        adapter, a_stack, b_stack = lora
        lora = (jnp.full((c,), 0, jnp.int32) + adapter, a_stack, b_stack)
    return _vlm.fused_paged_pass_chunk(
        params, x, pools, position, block_table, cos_rows, sin_rows,
        heads=cfg.heads, kv_heads=cfg.kv_heads, head_dim=cfg.head_dim,
        layers=cfg.layers, eps=cfg.norm_eps, lora=lora,
    )


def init_page_pool(cfg: Qwen2Config, num_pages: int, page_size: int,
                   dtype=None, kv_int8: bool = False):
    """Per-layer paged KV pools: {layer: {k/v: [P, KV, page, hd]}}.
    Page 0 is reserved as the null page (idle slots' masked rows write
    there harmlessly); HBM scales with pages actually held, not
    slots x max_seq.

    ``kv_int8`` makes the value pools int8 and adds parallel
    ``ks``/``vs`` [P, KV, page] f32 scale planes (one scale per page
    row per kv head — ops.decode_block.kv_quant_rows). The scale planes
    live INSIDE the same per-layer pools dict, so every custody path
    that moves pools as a pytree — donation through the window scan,
    checkpoint save/restore, drain-and-migrate, prefix-cache page
    sharing by table entry — carries values and scales atomically for
    free."""
    dtype = jnp.int8 if kv_int8 else (dtype or L.compute_dtype())
    shape = (num_pages, cfg.kv_heads, page_size, cfg.head_dim)
    pools = {
        str(i): {
            "k": jnp.zeros(shape, dtype),
            "v": jnp.zeros(shape, dtype),
        }
        for i in range(cfg.layers)
    }
    if kv_int8:
        sshape = (num_pages, cfg.kv_heads, page_size)
        for lp in pools.values():
            lp["ks"] = jnp.zeros(sshape, jnp.float32)
            lp["vs"] = jnp.zeros(sshape, jnp.float32)
    return pools


def page_pool_bytes(cfg: Qwen2Config, page_size: int,
                    kv_int8: bool = False) -> int:
    """Per-page HBM bytes of one layer's K+V (+ scales when int8) —
    the unit the engine's capacity math and the int8 default pool
    sizing are denominated in."""
    values = 2 * cfg.kv_heads * page_size * cfg.head_dim
    if kv_int8:
        return values * 1 + 2 * cfg.kv_heads * page_size * 4  # int8 + f32
    return values * jnp.dtype(L.compute_dtype()).itemsize


def make_lora_pool(cfg: Qwen2Config, lora_dir, *, max_resident: int = 8,
                   rank: int | None = None):
    """Adapter catalog + resident pool for multi-tenant LoRA serving
    (models/lora_pool.AdapterPool). ``lora_dir`` holds one
    ``<name>.npz`` per servable adapter with per-layer keys ``a_{i}``
    [dim, r] / ``b_{i}`` [r, dim]; the file stem is the tenant name
    requests route on (the OpenAI ``model`` field).

    The resident stack is homogeneous in rank: ``rank`` defaults to
    the LARGEST rank in the catalog and smaller adapters are
    zero-padded into it (zero rows/cols contribute exactly zero to the
    delta), so admission never changes stack shapes — the
    zero-steady-state-compile contract. See KNOWN_ISSUES round 19 for
    the rank ceiling (128-lane tile) and undersized-pool thrash."""
    import os

    import numpy as np

    from dora_tpu.models.lora_pool import AdapterPool

    files = {
        f[: -len(".npz")]: os.path.join(lora_dir, f)
        for f in sorted(os.listdir(lora_dir))
        if f.endswith(".npz")
    }
    if not files:
        raise ValueError(f"DORA_LORA_DIR {lora_dir!r} has no .npz adapters")
    if rank is None:
        rank = 1
        for path in files.values():
            with np.load(path) as z:
                rank = max(rank, z["a_0"].shape[-1])
    dtype = L.compute_dtype()
    template = {
        "a": jnp.zeros((cfg.layers, cfg.dim, rank), dtype),
        "b": jnp.zeros((cfg.layers, rank, cfg.dim), dtype),
    }

    def loader(name):
        with np.load(files[name]) as z:
            a = np.stack([z[f"a_{i}"] for i in range(cfg.layers)])
            b = np.stack([z[f"b_{i}"] for i in range(cfg.layers)])
        r = a.shape[-1]
        assert r <= rank, (name, r, rank)
        a = np.pad(a, ((0, 0), (0, 0), (0, rank - r)))
        b = np.pad(b, ((0, 0), (0, rank - r), (0, 0)))
        return {"a": jnp.asarray(a, dtype), "b": jnp.asarray(b, dtype)}

    return AdapterPool(
        loader, template, max_resident=max_resident, known=set(files)
    )


def make_paged_engine(params, cfg: Qwen2Config, *, max_slots: int = 16,
                      eos: int | None = None, page_size: int = 16,
                      chunk: int | None = None,
                      num_pages: int | None = None,
                      window: int | None = None,
                      spec_k: int | None = None,
                      spec_ngram: int | None = None,
                      prefix_cache: bool | None = None,
                      prefix_cache_pages: int | None = None,
                      kv_int8: bool | None = None,
                      lora_dir: str | None = None,
                      lora_max_resident: int | None = None):
    """Paged-KV continuous-batching engine (requires the quantized fused
    layout — models/vlm.fused_decode_ready). Defaults size the pool to
    4 * max_seq KV rows per layer, null page included — the engine runs
    ``max_slots`` streams inside it because pages are granted for
    actual context, not worst-case.

    ``window`` is the multi-step decode window K (default: env
    ``DORA_MULTISTEP_K``, else 8): each engine step runs K fused decode
    ticks in ONE jitted device program (models/paged_window.make_paged_window)
    and fetches one [B, K+1] token matrix, amortizing host dispatch and
    device->host fetch cost across K tokens. ``window=1`` is the
    per-token dispatch behavior of the pre-window engine, same greedy
    tokens either way (asserted in tests/test_paged_engine.py).

    ``spec_k`` (default: env ``DORA_SPEC_K``, else 0 = off) folds
    prompt-lookup speculation INTO each window tick
    (models/paged_window.make_paged_spec_window): per tick every stream drafts
    ``spec_k`` tokens by trailing-ngram lookup (``spec_ngram``, env
    ``DORA_SPEC_NGRAM``, default 2) and one batched verification pass
    checks them all — up to ``window * (spec_k + 1)`` tokens per
    dispatch, token-identical to ``spec_k = 0`` (verification replays
    the serial spec_decode acceptance test). ``spec_k = 0`` builds
    today's window program, byte-identical.

    ``lora_dir`` (default: env ``DORA_LORA_DIR``) enables multi-tenant
    LoRA serving: the engine carries a refcounted resident-adapter
    pool (:func:`make_lora_pool`, sized by ``lora_max_resident`` /
    env ``DORA_LORA_MAX_RESIDENT``, default 8) and the fused window
    applies each stream's residual-stream adapter delta through the
    grouped Pallas gather-matmul (ops/lora.py). Adapter ids are TRACED
    data — mixed-tenant batches share one window executable and
    adapter churn rewrites pool slot contents without recompiling."""
    import os

    from dora_tpu.models import vlm as _vlm
    from dora_tpu.models.batch_engine import PagedBatchEngine

    assert _vlm.fused_decode_ready(params, 1), (
        "paged engine needs quantize_decode params (DORA_INT8_DECODE / "
        "DORA_INT4_DECODE)"
    )
    chunk = chunk or min(256, cfg.max_seq)
    if kv_int8 is None:
        kv_int8 = os.environ.get("DORA_KV_INT8", "0") != "0"
    if kv_int8 and backend.on_tpu():
        raise NotImplementedError(KV_INT8_REFUSED)
    if num_pages is None:
        num_pages = 4 * cfg.max_seq // page_size
        if kv_int8:
            # Same HBM byte budget as the fp default, denominated in
            # int8 pages (values + scale planes) — this ratio IS the
            # capacity multiplier the quant-ab bench measures.
            budget = num_pages * page_pool_bytes(cfg, page_size)
            num_pages = int(
                budget // page_pool_bytes(cfg, page_size, kv_int8=True)
            )
    if window is None:
        window = int(os.environ.get("DORA_MULTISTEP_K", "8"))
    if spec_k is None:
        spec_k = int(os.environ.get("DORA_SPEC_K", "0"))
    if spec_ngram is None:
        spec_ngram = int(os.environ.get("DORA_SPEC_NGRAM", "2"))
    # Shared-prefix radix cache (models/prefix_cache.py). Raw-engine
    # default is OFF (tests/benches get the exact pre-cache program);
    # the serving front door (nodehub/llm_server.make_engine) defaults
    # it ON — DORA_PREFIX_CACHE=0 disables it everywhere.
    if prefix_cache is None:
        prefix_cache = os.environ.get("DORA_PREFIX_CACHE", "0") != "0"
    if prefix_cache_pages is None:
        prefix_cache_pages = int(
            os.environ.get("DORA_PREFIX_CACHE_PAGES", "0")
        )
    if lora_dir is None:
        lora_dir = os.environ.get("DORA_LORA_DIR") or None
    lora_pool = None
    if lora_dir:
        if lora_max_resident is None:
            lora_max_resident = int(
                os.environ.get("DORA_LORA_MAX_RESIDENT", "8")
            )
        rank_env = os.environ.get("DORA_LORA_RANK")
        lora_pool = make_lora_pool(
            cfg, lora_dir, max_resident=lora_max_resident,
            rank=int(rank_env) if rank_env else None,
        )

    # Every program takes ``params`` as its FIRST ARGUMENT and the engine
    # gets ``partial(program, params)``, never a closed-over constant
    # (why: paged_model.build_engine, which wires the other five
    # families the same way). Pools are argument 2 of the jitted
    # callable, hence the donation.
    has_lora = lora_pool is not None

    def with_lora(step_fn):
        # (params, ids, pools, positions, bts[, adapters, stacks]) ->
        # step_fn(..., lora=...) — one spelling for all three programs.
        def step(p, ids, pools, positions, bts, *lora_args):
            lora = None
            if has_lora:
                adapters, ls = lora_args
                lora = (adapters, ls["a"], ls["b"])
            return step_fn(p, cfg, ids, pools, positions, bts, lora=lora)
        return step

    def window_factory(k, sk):
        # (k, spec) -> jitted window program; PagedBatchEngine caches
        # built programs so the autotuner's ladder compiles each rung
        # once per process.
        if sk:
            step = with_lora(fused_paged_spec_step)
            make = partial(make_paged_spec_window, k=k, spec_k=sk,
                           ngram=spec_ngram, eos=eos, lora=has_lora)
        else:
            step = with_lora(fused_paged_batch_step)
            make = partial(make_paged_window, k=k, eos=eos,
                           lora=has_lora)

        def program(p, *args):
            return make(partial(step, p))(*args)

        return partial(jax.jit(program, donate_argnums=(2,)), params)

    window_fn = window_factory(window, spec_k)
    chunk_fn = partial(
        jax.jit(with_lora(fused_paged_chunk_step), donate_argnums=(2,)),
        params,
    )
    engine = PagedBatchEngine(
        init_pool=lambda n: init_page_pool(cfg, n, page_size,
                                           kv_int8=kv_int8),
        chunk_prefill=chunk_fn,
        window_step=window_fn,
        window_factory=window_factory,
        window=window,
        max_slots=max_slots,
        max_seq=cfg.max_seq,
        page_size=page_size,
        chunk=chunk,
        num_pages=num_pages,
        eos=eos,
        spec_k=spec_k,
        spec_ngram=spec_ngram,
        prefix_cache=prefix_cache,
        prefix_cache_pages=prefix_cache_pages,
        lora_pool=lora_pool,
    )
    # Device utilization plane constants: the analytic per-token FLOPs
    # of this config and the device's advertised peak, feeding the
    # serving node's mfu / device_busy_fraction gauges.
    engine.flops_per_token = profiling.flops_per_token_config(cfg)
    engine.device_peak_flops = profiling.detect_peak_flops()
    return engine


def _lm(params, cfg: Qwen2Config, h, positions, mask, caches=None, cache_index=None):
    rope = L.rope_table(cfg.max_seq, cfg.head_dim, base=cfg.rope_theta)
    new_caches = {}
    for i in range(cfg.layers):
        h, new_cache = L.block_forward(
            params["blocks"][str(i)], h, cfg.heads,
            n_kv_heads=cfg.kv_heads, rope=rope, positions=positions,
            mask=mask, cache=None if caches is None else caches[str(i)],
            cache_index=cache_index, norm_eps=cfg.norm_eps,
        )
        if new_cache is not None:
            new_caches[str(i)] = new_cache
    return L.rms_norm(h, params["out_norm"], cfg.norm_eps), new_caches


@partial(jax.jit, static_argnums=(1,))
def forward(params, cfg: Qwen2Config, tokens):
    """tokens [B, T] int32 → logits [B, T, vocab] float32."""
    dtype = L.compute_dtype()
    b, t = tokens.shape
    h = params["embed"].astype(dtype)[tokens]
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    mask = L.causal_mask(t, t)
    h, _ = _lm(params, cfg, h, positions, mask)
    return _head_logits(h, _head(params, cfg, dtype))


def init_cache(cfg: Qwen2Config, batch: int, dtype=None):
    dtype = dtype or L.compute_dtype()
    return {
        str(i): {
            "k": jnp.zeros((batch, cfg.kv_heads, cfg.max_seq, cfg.head_dim), dtype),
            "v": jnp.zeros((batch, cfg.kv_heads, cfg.max_seq, cfg.head_dim), dtype),
        }
        for i in range(cfg.layers)
    }


@partial(jax.jit, static_argnums=(1, 3))
def generate(params, cfg: Qwen2Config, prompt_ids, max_new_tokens: int):
    """Greedy generation as one traced computation. prompt_ids [B, T]."""
    dtype = L.compute_dtype()
    b, t = prompt_ids.shape
    if t + max_new_tokens > cfg.max_seq:
        # Out-of-bounds cache indices would be silently clamped by XLA,
        # corrupting the KV cache — fail loudly at trace time instead.
        raise ValueError(
            f"prompt ({t}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"max_seq ({cfg.max_seq}); reload with a larger max_seq"
        )
    head = _head(params, cfg, dtype)

    h = params["embed"].astype(dtype)[prompt_ids]
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    mask = L.causal_mask(t, cfg.max_seq) & (
        jnp.arange(cfg.max_seq)[None, None, None, :] < t
    )
    caches = init_cache(cfg, b)
    h, caches = _lm(params, cfg, h, positions, mask, caches=caches, cache_index=0)
    first = jnp.argmax(_head_logits(h[:, -1], head), axis=-1).astype(
        jnp.int32
    )

    from dora_tpu.models import vlm as _vlm

    use_fused = _vlm.fused_decode_ready(params, b)

    def step(carry, _):
        token, caches, position = carry
        if use_fused:
            nxt, caches = fused_step(
                params, cfg, token[:, None], caches, position
            )
            return (nxt, caches, position + 1), token
        h = params["embed"].astype(dtype)[token][:, None, :]
        positions = jnp.broadcast_to(position, (b, 1))
        mask = (jnp.arange(cfg.max_seq) <= position)[None, None, None, :]
        h, caches = _lm(
            params, cfg, h, positions, mask, caches=caches, cache_index=position
        )
        nxt = jnp.argmax(_head_logits(h[:, -1], head), axis=-1).astype(
            jnp.int32
        )
        return (nxt, caches, position + 1), token

    (_, _, _), tokens = jax.lax.scan(
        step, (first, caches, jnp.asarray(t, jnp.int32)), None,
        length=max_new_tokens,
    )
    return tokens.T
