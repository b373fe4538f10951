"""Ouro looped causal LM (``model_type`` ``ouro``) on the paged serving
path: one stack of decoder layers run ``total_ut_steps`` times a token,
every pass with K/V rows of its own.

The model, as the published ``config.json`` and the family's modelling
code give it (the float32 reference of the same mathematics over a whole
sequence is ``ouro_reference.py``; KNOWN_ISSUES.md "PR 35" lists what
the config's keys do not bear out):

    a layer (sandwich norm, four RMSNorm weights):
        a = x + N2(Attn(N1(x)))          N1 input_layernorm, N2 input_layernorm_2
        y = a + N4(MLP(N3(a)))           N3 post_attention_layernorm, N4 ..._2
    the loop:
        h_0 = Embed(ids)
        h_{t+1} = Norm_f(L_{n-1}(... L_0(h_t)))   t = 0 .. total_ut_steps - 1
        logits = W_head h_T
    the exit gate:
        lambda_t = sigmoid(w_g . h_{t+1} + b_g)

``Attn`` is rotary multi-head attention (rotate-half, no bias, no q/k
norm) over THIS PASS's rows of the cache: pass ``t`` of layer ``l``
writes and reads entry ``l + layers * t`` (HF's index), so a token
holds ``layers x passes`` K/V entries (192 at Ouro-2.6B: 1,572,864 B of
bf16). The same layers' weights, the same rotary positions and the final
norm serve every pass. ``early_exit_threshold`` 1, the published
setting, lets no token leave before the last pass: the gate is loaded,
evaluated and counted (``loop_exit_before_last`` stays 0), and changes
no logit; any other threshold is refused.

What this module adds to the serving path:

* **the pool is ``passes`` deep**: ``{layer: {k, v: [passes * P, KV,
  page, hd]}}``. Pass ``t`` owns pages ``t * P .. (t + 1) * P - 1`` of
  every layer's array; a stream's block table names pages ``1 .. P - 1``
  once, and pass ``t`` reads it shifted by ``t * P`` (page ``t * P`` is
  that pass's null page). So one allocator page is ``layers x passes``
  entries deep and ``PageAllocator``, ``PrefixCache``, ``preempt``,
  ``save_pools`` and drain-and-migrate carry it as they stand.
* **the pass loop is a ``lax.fori_loop``** inside both programs, the
  layers unrolled inside it: a program of ``layers`` layer bodies, not
  ``layers x passes``. The pools ride the loop's carry and every kernel
  call aliases them in and out, so they are updated in place.
* **sublayers without their residual**: the fused attention kernels and
  ``mlp_step`` (``ops/decode_block``) run with ``residual=False`` and
  hand back the float32 sublayer output; the post-norm and the add are
  plain XLA on a float32 residual stream (the kernels read it rounded
  to the compute dtype).
* **the pool's default size is a rule in bytes**
  (:func:`default_num_pages`): here the cache, not the weights, is the
  largest thing on the chip.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp

from dora_tpu.models import layers as L
from dora_tpu.models import paged_model as PM
from dora_tpu.models.hf.loader import TensorFiles, read_config
from dora_tpu.models.paged_window import make_paged_window
from dora_tpu.ops import decode_block as DB
from dora_tpu.ops.int8_matmul import quantize_int8_t

MODEL_TYPES = ("ouro",)

#: the default pool is a multiple of this many pages
POOL_PAGE_MULTIPLE = 8

#: serving knobs of the Qwen path that this model refuses (KNOWN_ISSUES.md)
NOT_OFFERED = {
    "DORA_KV_INT8": "the int8 page planes of the paged attention kernels "
                    "do not compile for the chip (qwen2.KV_INT8_REFUSED), "
                    "and a page of this model is 192 entries of them",
    "DORA_SPEC_K": "the speculative window's verify pass is the Qwen "
                   "skeleton's; no looped one is written",
    "DORA_LORA_DIR": "the grouped LoRA matmul is applied inside the Qwen "
                     "skeleton, once a layer; a looped model would need a "
                     "rule for which pass it rides",
}

COUNTERS = ("passes", "kv_rows_read", "decode_ticks", "chunk_rows", "chunks",
            "chunk_positions", "exit_before_last", "sweep_groups")


@dataclass(frozen=True)
class OuroConfig:
    vocab: int
    dim: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    passes: int
    exit_threshold: float
    norm_eps: float
    rope_theta: float
    max_seq: int

    @property
    def kv_entries(self) -> int:
        """K/V entries a token holds: one a layer a pass."""
        return self.layers * self.passes

    @property
    def kv_bytes_per_token(self) -> int:
        values = 2 * self.kv_heads * self.head_dim
        return self.kv_entries * values * jnp.dtype(L.compute_dtype()).itemsize

    @classmethod
    def from_hf(cls, config: dict, max_seq: int | None = None) -> "OuroConfig":
        if config.get("model_type") not in MODEL_TYPES:
            raise ValueError(
                f"model_type {config.get('model_type')!r} is not one of "
                f"{MODEL_TYPES}"
            )
        threshold = config.get("early_exit_threshold", 1)
        if float(threshold) != 1.0:
            raise NotImplementedError(
                f"ouro: early_exit_threshold {threshold!r} is not offered: "
                "only the published 1 (every token runs every pass); a token "
                "that leaves early would skip passes whose K/V rows later "
                "tokens read")
        if config.get("use_sliding_window") or config.get("sliding_window"):
            raise NotImplementedError(
                "ouro: use_sliding_window is not written (full attention only)")
        if any(kind != "full_attention"
               for kind in config.get("layer_types") or ()):
            raise NotImplementedError(
                "ouro: layer_types other than full_attention are not written")
        if config.get("rope_scaling"):
            raise NotImplementedError(
                f"ouro: rope_scaling {config['rope_scaling']!r} is not written")
        for key in ("attention_bias", "mlp_bias"):
            if config.get(key):
                raise NotImplementedError(f"ouro: {key} is not written")
        if config.get("hidden_act", "silu") != "silu":
            raise NotImplementedError(
                f"ouro: hidden_act {config['hidden_act']!r} is not written")
        heads = config["num_attention_heads"]
        return cls(
            vocab=config["vocab_size"],
            dim=config["hidden_size"],
            layers=config["num_hidden_layers"],
            heads=heads,
            kv_heads=config.get("num_key_value_heads", heads),
            head_dim=config.get("head_dim") or config["hidden_size"] // heads,
            ffn=config["intermediate_size"],
            passes=int(config.get("total_ut_steps", 1)),
            exit_threshold=float(threshold),
            norm_eps=config.get("rms_norm_eps", 1e-6),
            rope_theta=float(config.get("rope_theta", 10000.0)),
            max_seq=max_seq
            or min(config.get("max_position_embeddings", 2048), 2048),
        )


# ---------------------------------------------------------------------------
# loading: one layer at a time, int8 on the device
# ---------------------------------------------------------------------------

#: a layer's HF tensor names (after ``model.layers.<i>.``) -> where they go
LAYER_NORMS = {
    "input_layernorm.weight": "attn_norm",
    "input_layernorm_2.weight": "attn_post_norm",
    "post_attention_layernorm.weight": "ffn_norm",
    "post_attention_layernorm_2.weight": "ffn_post_norm",
}
LAYER_MATRICES = {
    "wqkv": ("self_attn.q_proj.weight", "self_attn.k_proj.weight",
             "self_attn.v_proj.weight"),
    "wo": ("self_attn.o_proj.weight",),
    "w_gateup": ("mlp.gate_proj.weight", "mlp.up_proj.weight"),
    "w_down": ("mlp.down_proj.weight",),
}
BIASES = ("self_attn.q_proj.bias", "self_attn.k_proj.bias",
          "self_attn.v_proj.bias", "self_attn.o_proj.bias")


def load_layer(get, i: int, prefix: str = "model.") -> dict:
    """Layer ``i``'s serving parameters from ``get(name) -> device array``
    under the HF tensor names: the four norms as they are, the seven
    matrices as four int8 ``[in, out]`` matrices with per-output-channel
    scales (the fused kernels' layout)."""
    lp = f"{prefix}layers.{i}."
    blk = {ours: get(lp + name) for name, ours in LAYER_NORMS.items()}
    for ours, names in LAYER_MATRICES.items():
        blk[ours] = quantize_int8_t(*(get(lp + name) for name in names))
    return blk


def map_params(get, has, cfg: OuroConfig) -> dict:
    """Every HF tensor of the checkpoint -> the serving tree. ``get(name)``
    gives a device array (and raises ``KeyError`` by name for a tensor
    the checkpoint lacks), ``has(name)`` says whether it is there."""
    prefix = "model." if has("model.embed_tokens.weight") else ""
    for name in BIASES:
        if has(f"{prefix}layers.0.{name}"):
            raise NotImplementedError(
                f"ouro: the checkpoint has {name}: projection biases are "
                "not written")
    embed = get(f"{prefix}embed_tokens.weight")
    head = get("lm_head.weight") if has("lm_head.weight") else embed
    return {
        "embed": embed,
        "out_norm": get(f"{prefix}norm.weight"),
        "lm_head": quantize_int8_t(head),
        "gate_w": get(f"{prefix}early_exit_gate.weight").astype(
            jnp.float32).reshape(cfg.dim),
        "gate_b": get(f"{prefix}early_exit_gate.bias").astype(
            jnp.float32).reshape(()),
        "blocks": {
            str(i): load_layer(get, i, prefix) for i in range(cfg.layers)
        },
    }


def load(model_dir: str | Path, max_seq: int | None = None):
    """(config, serving params) from a HF checkpoint directory. Tensors
    go from the file to the device one at a time and are quantized there
    (``ops/int8_matmul.quantize_int8``, per output channel), so no
    matrix is kept in a float format; the embedding and the norms stay
    in the compute dtype, the gate in float32."""
    cfg = OuroConfig.from_hf(read_config(model_dir), max_seq)
    files = TensorFiles(model_dir)
    dtype = L.compute_dtype()

    def get(name: str):
        if name not in files:
            raise KeyError(f"ouro: the checkpoint has no tensor {name!r}")
        return jnp.asarray(files.get(name)).astype(dtype)

    return cfg, map_params(get, files.__contains__, cfg)


def quantize_decode(params, cfg=None):
    """The serving layout IS what :func:`load` returns (int8 from the
    start); kept so that ``llm_server`` treats every model module alike."""
    return params


# ---------------------------------------------------------------------------
# one pass over the layers, the loop, the two programs
# ---------------------------------------------------------------------------


def _norm32(x, w, eps: float):
    """float32 RMSNorm of float32 rows."""
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def _one_pass(params, cfg: OuroConfig, x, pools, attend):
    """The layers once: ``x [N, dim]`` float32 residual stream,
    ``attend(x in the compute dtype, blk, layer pool) -> (float32
    sublayer output, k pool, v pool)``. Returns (the rows before the
    final norm, pools)."""
    dtype = L.compute_dtype()
    eps = cfg.norm_eps
    pools = dict(pools)

    def add(x, sub, w):
        with jax.named_scope("sandwich_norm"):
            return x + _norm32(sub, w, eps)

    no_bias = jnp.zeros((2 * cfg.ffn,), jnp.float32)
    for i in range(cfg.layers):
        blk = params["blocks"][str(i)]
        with jax.named_scope("attn_paged"):
            a, k, v = attend(x.astype(dtype), blk, pools[str(i)])
        pools[str(i)] = {"k": k, "v": v}
        x = add(x, a, blk["attn_post_norm"])
        gu, dn = blk["w_gateup"], blk["w_down"]
        with jax.named_scope("mlp"):
            m = DB.mlp_step(x.astype(dtype), blk["ffn_norm"], gu["int8"],
                            gu["scale"], no_bias, dn["int8"], dn["scale"],
                            eps=eps, residual=False)
        x = add(x, m, blk["ffn_post_norm"])
    return x, pools


def exit_gate(params, h):
    """``lambda = sigmoid(w_g . h + b_g)`` of normed float32 rows."""
    with jax.named_scope("exit_gate"):
        return jax.nn.sigmoid(h @ params["gate_w"] + params["gate_b"])


def looped(params, cfg: OuroConfig, x, pools, tables, attend):
    """The config's passes over the layers, as a ``lax.fori_loop``.
    ``x [N, dim]`` float32; ``tables`` the rows' block table(s), which
    pass ``t`` reads shifted by ``t`` pools' worth of pages;
    ``attend(x, blk, layer pool, tables)``.

    Returns (the last pass's rows BEFORE its final norm, the same rows
    normed — the model's ``h_T`` —, pools, lambdas ``[passes, N]``,
    ``before_last [N]``: whether the running exit sum reached the
    threshold before the last pass)."""
    passes = cfg.passes
    pages = next(iter(pools.values()))["k"].shape[0] // passes
    n = x.shape[0]

    def one(t, carry):
        h, _, pools, lambdas, survive, cdf, before_last = carry
        shifted = tables + t * pages
        with jax.named_scope("loop_pass"):
            raw, pools = _one_pass(
                params, cfg, h, pools,
                lambda x, blk, lp: attend(x, blk, lp, shifted))
            h = _norm32(raw, params["out_norm"], cfg.norm_eps)
        lam = exit_gate(params, h)
        cdf = cdf + lam * survive
        before_last = before_last | (
            (t < passes - 1) & (cdf >= cfg.exit_threshold))
        lambdas = jax.lax.dynamic_update_index_in_dim(lambdas, lam, t, 0)
        return h, raw, pools, lambdas, survive * (1.0 - lam), cdf, before_last

    zero = jnp.zeros((n,), jnp.float32)
    h, raw, pools, lambdas, _, _, before_last = jax.lax.fori_loop(
        0, passes, one,
        (x, x, pools, jnp.zeros((passes, n), jnp.float32), zero + 1.0, zero,
         jnp.zeros((n,), bool)))
    return raw, h, pools, lambdas, before_last


def _attn_weights(blk, cfg: OuroConfig):
    qkv, o = blk["wqkv"], blk["wo"]
    n_qkv = (cfg.heads + 2 * cfg.kv_heads) * cfg.head_dim
    return (qkv["int8"], qkv["scale"], jnp.zeros((n_qkv,), jnp.float32)), (
        o["int8"], o["scale"])


def _shape(cfg: OuroConfig) -> dict:
    return {"heads": cfg.heads, "kv_heads": cfg.kv_heads,
            "head_dim": cfg.head_dim, "eps": cfg.norm_eps, "residual": False}


def paged_batch_rows(params, cfg: OuroConfig, tokens, pools, stats, positions,
                     block_tables):
    """One decode step for B = slots independent sequences: tokens and
    positions ``[B]``, block_tables ``[B, max_pages]`` (a frozen row
    comes with position 0 and a zeroed table row, which is also how this
    step knows it: its K/V writes land in each pass's null page and it
    is not counted). Returns (the last pass's rows before the final
    norm, the normed rows, pools, stats, lambdas)."""
    cos_t, sin_t = L.rope_table(cfg.max_seq, cfg.head_dim, base=cfg.rope_theta)
    cos_rows, sin_rows = DB.rope_rows_at(cos_t, sin_t, positions)
    x = params["embed"][tokens].astype(jnp.float32)

    def attend(x, blk, lp, tables):
        qkv, o = _attn_weights(blk, cfg)
        return DB.attention_paged_batch_step(
            x, blk["attn_norm"], *qkv, cos_rows, sin_rows, lp["k"], lp["v"],
            *o, positions, tables, **_shape(cfg))

    raw, h, pools, lambdas, before_last = looped(
        params, cfg, x, pools, block_tables, attend)
    live = block_tables[:, 0] != 0
    n_live = live.sum(dtype=jnp.int32)
    # the (row, group) steps the decode kernel's sweep schedules: a
    # group is DB's page group of cache rows, the current token is none
    group = DB.sweep_group_rows(
        next(iter(pools.values()))["k"].shape[2], block_tables.shape[1])
    stats = PM.add_counts(
        stats, passes=cfg.passes * n_live,
        kv_rows_read=cfg.passes * jnp.where(live, positions + 1, 0).sum(
            dtype=jnp.int32),
        sweep_groups=cfg.passes * jnp.where(
            live, (positions + group - 1) // group, 0).sum(dtype=jnp.int32),
        decode_ticks=(n_live > 0).astype(jnp.int32),
        exit_before_last=(live & before_last).sum(dtype=jnp.int32))
    return raw, h, pools, stats, lambdas


def paged_chunk_rows(params, cfg: OuroConfig, chunk_ids, pools, stats,
                     position, block_table, valid):
    """One prefill chunk of one stream: ``chunk_ids [C]`` at positions
    ``position..position+C-1`` (page-aligned), of which the first
    ``valid`` are the prompt's; every pass writes the chunk's K/V as
    whole pages of its own and attends causally over them and the
    context before. ``position`` and ``valid`` are traced: one program
    for every chunk."""
    c = chunk_ids.shape[0]
    cos_t, sin_t = L.rope_table(cfg.max_seq, cfg.head_dim, base=cfg.rope_theta)
    cos_rows, sin_rows = DB.rope_rows(cos_t, sin_t, position, c)
    x = params["embed"][chunk_ids].astype(jnp.float32)

    def attend(x, blk, lp, table):
        qkv, o = _attn_weights(blk, cfg)
        return DB.attention_paged_chunk_step(
            x, blk["attn_norm"], *qkv, cos_rows, sin_rows, lp["k"], lp["v"],
            *o, position, table, **_shape(cfg))

    raw, h, pools, lambdas, before_last = looped(
        params, cfg, x, pools, block_table, attend)
    prompt = jnp.arange(c) < valid
    stats = PM.add_counts(
        stats, chunk_rows=valid.astype(jnp.int32), chunks=1,
        chunk_positions=jnp.asarray(position, jnp.int32),
        exit_before_last=(prompt & before_last).sum(dtype=jnp.int32))
    return raw, h, pools, stats, lambdas


def head_logits(params, h):
    """``W_head h`` of normed float32 rows (no second norm)."""
    return L.matmul(h.astype(L.compute_dtype()), params["lm_head"]).astype(
        jnp.float32)


def head_argmax(params, cfg: OuroConfig, raw):
    """The streamed head over the last pass's rows: the kernel applies
    the final norm itself, so it is given the rows before it."""
    w = params["lm_head"]
    return DB.lm_head_argmax(raw.astype(L.compute_dtype()), params["out_norm"],
                             w["int8"], w["scale"], eps=cfg.norm_eps)


def paged_batch_logits(params, cfg, *args):
    """-> (logits [B, vocab] float32, pools, stats, lambdas [passes, B])."""
    _, h, *rest = paged_batch_rows(params, cfg, *args)
    return head_logits(params, h), *rest


def paged_chunk_logits(params, cfg, *args):
    _, h, *rest = paged_chunk_rows(params, cfg, *args)
    return head_logits(params, h), *rest


def fused_paged_batch_step(params, cfg, *args):
    """-> (greedy [B], pools, stats)."""
    raw, _, pools, stats, _ = paged_batch_rows(params, cfg, *args)
    return head_argmax(params, cfg, raw), pools, stats


def fused_paged_chunk_step(params, cfg, *args):
    """-> (greedy [C], pools, stats)."""
    raw, _, pools, stats, _ = paged_chunk_rows(params, cfg, *args)
    return head_argmax(params, cfg, raw), pools, stats


def window_program(params, cfg, k: int, eos, tokens, pools, stats, *rest):
    """The K-tick decode window (models/paged_window.make_paged_window)
    over :func:`fused_paged_batch_step`: the pools and the counters ride
    the window's carry together and come back apart. Returns (the
    window's own results, pools last; stats)."""
    def batch(tokens, carried, positions, bts):
        nxt, pools, stats = fused_paged_batch_step(
            params, cfg, tokens, *carried, positions, bts)
        return nxt, (pools, stats)

    *out, (pools, stats) = make_paged_window(batch, k=k, eos=eos)(
        tokens, (pools, stats), *rest)
    return (*out, pools), stats


# ---------------------------------------------------------------------------
# the pool and the engine
# ---------------------------------------------------------------------------


def init_page_pool(cfg: OuroConfig, num_pages: int, page_size: int,
                   dtype=None) -> dict:
    """K/V page pools ``{layer: {k, v: [passes * P, KV, page, hd]}}``
    (the paged kernels' layout, ``passes`` pools' worth of pages in one
    array): pass ``t``'s copy of allocator page ``p`` is page ``t * P +
    p``. Page 0 of every pass is its null page."""
    dtype = dtype or L.compute_dtype()
    shape = (cfg.passes * num_pages, cfg.kv_heads, page_size, cfg.head_dim)
    return {
        str(i): {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        for i in range(cfg.layers)
    }


def entry_pages(pools, cfg: OuroConfig, t: int, layer: int, pages):
    """Pass ``t``'s K and V pages ``[len(pages), KV, page, hd]`` of
    ``layer`` for the allocator's page ids ``pages``: the one place that
    knows where a (pass, layer) entry lives."""
    lp = pools[str(layer)]
    ids = jnp.asarray(pages, jnp.int32) + t * (lp["k"].shape[0] // cfg.passes)
    return lp["k"][ids], lp["v"][ids]


def page_pool_bytes(cfg: OuroConfig, page_size: int) -> int:
    """Bytes one allocator page takes over all layers and passes."""
    return page_size * cfg.kv_bytes_per_token


def default_num_pages(cfg: OuroConfig, max_slots: int, page_size: int) -> int:
    """The pool's default size, ``paged_model.default_num_pages``' rule in
    bytes in multiples of :data:`POOL_PAGE_MULTIPLE`: here the cache, not
    the weights, is the largest thing on the chip. At Ouro-2.6B on a 16
    GB v5e: (16.91 - 2.77 - 4.29) GB / 25,165,824 B = 391 -> 384 pages,
    9.66 GB."""
    return PM.default_num_pages(
        page_pool_bytes(cfg, page_size), max_slots, cfg.max_seq, page_size,
        multiple=POOL_PAGE_MULTIPLE)


def init_counters() -> dict:
    """The loop's counters on the device: an operand and a result of
    their own of both programs, int32 that wraps;
    ``paged_model.DeviceCounters`` adds up the differences."""
    return {name: jnp.zeros((), jnp.int32) for name in COUNTERS}


def report(cfg: OuroConfig, page_size: int, totals: dict, engine) -> dict:
    """The gauges of one engine (``paged_model.build_engine``'s
    ``report``): the loop's counters' sums and the pool."""
    alloc = engine.allocator
    return {
        **{f"loop_{k}": int(totals[k]) for k in COUNTERS},
        "kv_bytes_per_token": cfg.kv_bytes_per_token,
        "kv_pool_bytes": alloc.num_pages * page_pool_bytes(cfg, page_size),
        "kv_pages_free": alloc.free_pages,
    }


def flops_per_token(cfg: OuroConfig) -> float:
    """Weight-matmul FLOPs of one token: every pass pays the layers, the
    head is paid once (no score term)."""
    q, kv = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    layer = cfg.dim * (q + 2 * kv) + q * cfg.dim + 3 * cfg.dim * cfg.ffn
    return 2.0 * (cfg.passes * cfg.layers * layer + cfg.dim * cfg.vocab)


def make_paged_engine(params, cfg: OuroConfig, *, max_slots: int = 16,
                      eos: int | None = None, page_size: int = 16,
                      chunk: int | None = None,
                      num_pages: int | None = None,
                      window: int | None = None,
                      prefix_cache: bool | None = None,
                      prefix_cache_pages: int | None = None):
    """The paged continuous-batching engine
    (models/batch_engine.PagedBatchEngine) over the looped pool: the
    same scheduler, allocator, prefix cache and K-tick window
    (models/paged_window.make_paged_window) as the Qwen engine, with this
    module's two programs (``paged_model.build_engine``; the pools and
    the counters are arguments 2 and 3 of both, hence the donation).
    ``num_pages`` defaults to :func:`default_num_pages`. Speculation,
    LoRA and int8 pages are not offered for this model
    (KNOWN_ISSUES.md)."""
    if num_pages is None:
        num_pages = default_num_pages(cfg, max_slots, page_size)

    def step(p, ids, pools, stats, position, bt, valid):
        return fused_paged_chunk_step(p, cfg, ids, pools, stats, position,
                                      bt, valid)

    return PM.build_engine(
        "ouro", cfg, params,
        window_program=lambda p, k, *args: window_program(
            p, cfg, k, eos, *args),
        chunk_step=step, donate_window=(2, 3), donate_chunk=(2, 3),
        init_page_pool=lambda n: init_page_pool(cfg, n, page_size),
        counters=init_counters(), report=partial(report, cfg, page_size),
        not_offered=NOT_OFFERED, flops_per_token=flops_per_token(cfg),
        max_slots=max_slots, eos=eos, page_size=page_size,
        chunk=PM.default_chunk(chunk, cfg.max_seq), num_pages=num_pages,
        window=window, prefix_cache=prefix_cache,
        prefix_cache_pages=prefix_cache_pages)
