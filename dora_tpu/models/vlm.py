"""Flagship vision-language model (Qwen2-VL / InternVL class).

Reference parity: node-hub/dora-qwenvl and dora-internvl serve pretrained
VLMs through torch/CUDA (dora_qwenvl/main.py:114-121). This is the
TPU-native counterpart: a ViT patch encoder feeding a causal LM, all pure
JAX — bfloat16 matmuls on the MXU, static-shape KV-cache decode under
`lax.scan`, greedy generation as one jit, and a dp/tp/sp-sharded training
step (the reference has no training path at all).

Architecture: ViT (non-causal pre-norm blocks over patch embeddings,
learned positions) → linear project to LM width → image tokens prefixed to
the prompt → causal LM (RoPE, GQA, SwiGLU) → greedy decode.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from dora_tpu.models import layers as L


@dataclass(frozen=True)
class VLMConfig:
    # vision tower
    image_size: int = 224
    patch_size: int = 16
    vision_dim: int = 256
    vision_layers: int = 4
    vision_heads: int = 4
    vision_ffn: int = 1024
    # language model
    vocab: int = 32000
    dim: int = 512
    layers: int = 6
    heads: int = 8
    kv_heads: int = 4
    ffn: int = 1408
    max_seq: int = 1024

    @property
    def n_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def head_dim(self) -> int:
        return self.dim // self.heads

    @classmethod
    def tiny(cls) -> "VLMConfig":
        """Test-size config: compiles in seconds on CPU."""
        return cls(
            image_size=32, patch_size=8, vision_dim=32, vision_layers=2,
            vision_heads=2, vision_ffn=64, vocab=256, dim=64, layers=2,
            heads=4, kv_heads=2, ffn=128, max_seq=64,
        )

    @classmethod
    def bench_2b(cls) -> "VLMConfig":
        """Qwen2-VL-2B-shaped config for benchmarking."""
        return cls(
            image_size=224, patch_size=14, vision_dim=1280, vision_layers=32,
            vision_heads=16, vision_ffn=5120, vocab=151936, dim=1536,
            layers=28, heads=12, kv_heads=2, ffn=8960, max_seq=2048,
        )


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_params(key, cfg: VLMConfig) -> dict:
    keys = jax.random.split(key, 8 + cfg.vision_layers + cfg.layers)
    patch_dim = cfg.patch_size * cfg.patch_size * 3
    params: dict[str, Any] = {
        "vision": {
            "patch_proj": L.dense_init(keys[0], patch_dim, cfg.vision_dim),
            "pos_embed": jax.random.normal(
                keys[1], (cfg.n_patches, cfg.vision_dim), jnp.float32
            ) * 0.02,
            "blocks": {
                str(i): L.init_block(
                    keys[2 + i], cfg.vision_dim, cfg.vision_heads, cfg.vision_ffn
                )
                for i in range(cfg.vision_layers)
            },
            "out_norm": jnp.ones((cfg.vision_dim,), jnp.float32),
            "project": L.dense_init(
                keys[2 + cfg.vision_layers], cfg.vision_dim, cfg.dim
            ),
        },
        "embed": L.embed_init(keys[3 + cfg.vision_layers], cfg.vocab, cfg.dim),
        "blocks": {
            str(i): L.init_block(
                keys[4 + cfg.vision_layers + i], cfg.dim, cfg.heads, cfg.ffn,
                cfg.kv_heads,
            )
            for i in range(cfg.layers)
        },
        "out_norm": jnp.ones((cfg.dim,), jnp.float32),
        "lm_head": L.dense_init(
            keys[5 + cfg.vision_layers + cfg.layers], cfg.dim, cfg.vocab
        ),
    }
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def quantize_decode(params) -> dict:
    """Quantize the decode-path weights (LM blocks + lm_head).

    The vision tower and embedding are untouched: they run once per
    frame in prefill (compute-bound), while the LM weights stream from
    HBM on every generated token (bandwidth-bound — the quantization
    payoff, see ops.int8_matmul / ops.int4). Serving gates:
    DORA_INT8_DECODE=1 (per-channel int8); DORA_INT4_DECODE=1
    (group-128 int4 — half the decode bytes again, fused-kernel tier
    only); DORA_INT8_PURE=1 drops the bf16 prefill sidecar (halves LM
    weight memory, slower prefill).
    """
    import os

    keep_bf16 = not os.environ.get("DORA_INT8_PURE")
    out = dict(params)
    if os.environ.get("DORA_INT4_DECODE"):
        from dora_tpu.ops.int4 import quantize_tree_int4

        out["blocks"] = quantize_tree_int4(
            params["blocks"], keep_bf16=keep_bf16
        )
        out["lm_head"] = quantize_tree_int4(
            {"lm_head": params["lm_head"]}, keep_bf16=keep_bf16
        )["lm_head"]
        return out
    from dora_tpu.ops.int8_matmul import quantize_tree

    out["blocks"] = quantize_tree(params["blocks"], keep_bf16=keep_bf16)
    out["lm_head"] = quantize_tree(
        {"lm_head": params["lm_head"]}, keep_bf16=keep_bf16
    )["lm_head"]
    return out


# ---------------------------------------------------------------------------
# vision tower
# ---------------------------------------------------------------------------


def patchify(images, patch: int):
    """[B, H, W, 3] -> [B, n_patches, patch*patch*3]."""
    b, h, w, c = images.shape
    gh, gw = h // patch, w // patch
    x = images.reshape(b, gh, patch, gw, patch, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, gh * gw, patch * patch * c)


def encode_image(params, cfg: VLMConfig, images):
    """[B, H, W, 3] float -> [B, n_patches, dim] image tokens (LM width)."""
    dtype = L.compute_dtype()
    vp = params["vision"]
    x = patchify(images.astype(dtype), cfg.patch_size)
    x = x @ vp["patch_proj"].astype(dtype)
    x = x + vp["pos_embed"].astype(dtype)[None]
    flash = "full" if L.use_flash() else None
    for i in range(cfg.vision_layers):
        x, _ = L.block_forward(
            vp["blocks"][str(i)], x, cfg.vision_heads, mask=None, flash=flash
        )
    x = L.rms_norm(x, vp["out_norm"])
    return x @ vp["project"].astype(dtype)


# ---------------------------------------------------------------------------
# language model
# ---------------------------------------------------------------------------


def _lm_forward(
    params, cfg: VLMConfig, h, positions, mask, caches=None, cache_index=None,
    mesh=None, ring_axis=None, flash=None, sp_impl=None,
):
    rope = L.rope_table(cfg.max_seq, cfg.head_dim)
    new_caches = {}
    for i in range(cfg.layers):
        h, new_cache = L.block_forward(
            params["blocks"][str(i)],
            h,
            cfg.heads,
            n_kv_heads=cfg.kv_heads,
            rope=rope,
            positions=positions,
            mask=mask,
            cache=None if caches is None else caches[str(i)],
            cache_index=cache_index,
            mesh=mesh,
            ring_axis=ring_axis,
            flash=flash,
            sp_impl=sp_impl,
        )
        if new_cache is not None:
            new_caches[str(i)] = new_cache
    h = L.rms_norm(h, params["out_norm"])
    return h, new_caches


def init_cache(cfg: VLMConfig, batch: int, dtype=None):
    dtype = dtype or L.compute_dtype()
    kv_head_dim = cfg.head_dim
    return {
        str(i): {
            "k": jnp.zeros((batch, cfg.kv_heads, cfg.max_seq, kv_head_dim), dtype),
            "v": jnp.zeros((batch, cfg.kv_heads, cfg.max_seq, kv_head_dim), dtype),
        }
        for i in range(cfg.layers)
    }


def prefill(params, cfg: VLMConfig, images, prompt_ids):
    """Encode image + prompt, fill the KV cache.

    Returns (last_logits [B, vocab], caches, next_position).
    """
    dtype = L.compute_dtype()
    b = prompt_ids.shape[0]
    img_tokens = encode_image(params, cfg, images)  # [B, P, dim]
    txt = params["embed"].astype(dtype)[prompt_ids]  # [B, T, dim]
    h = jnp.concatenate([img_tokens, txt], axis=1)
    t = h.shape[1]
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    mask = L.causal_mask(t, cfg.max_seq) & (
        jnp.arange(cfg.max_seq)[None, None, None, :] < t
    )
    caches = init_cache(cfg, b)
    h, caches = _lm_forward(
        params, cfg, h, positions, mask, caches=caches, cache_index=0
    )
    logits = L.matmul(h[:, -1], params["lm_head"]).astype(jnp.float32)
    return logits, caches, t


def decode_step(params, cfg: VLMConfig, token, caches, position):
    """One greedy decode step. token: [B] int32; position: scalar int32."""
    dtype = L.compute_dtype()
    b = token.shape[0]
    h = params["embed"].astype(dtype)[token][:, None, :]  # [B,1,dim]
    positions = jnp.broadcast_to(position, (b, 1))
    mask = (jnp.arange(cfg.max_seq) <= position)[None, None, None, :]
    h, caches = _lm_forward(
        params, cfg, h, positions, mask, caches=caches, cache_index=position
    )
    logits = L.matmul(h[:, -1], params["lm_head"]).astype(jnp.float32)
    return logits, caches


# ---------------------------------------------------------------------------
# fused decode (Pallas kernel tier)
# ---------------------------------------------------------------------------


def fused_decode_ready(params, batch: int = 1) -> bool:
    """True when the decode step can run the fused Pallas tier
    (ops.decode_block): batch 1, a quantized fused layout from
    quantize_decode (wqkv / w_gateup / wo / w_down / lm_head all int8
    OR int4 dicts), and no output-projection biases (Qwen2/bench
    layout). Opt-out: DORA_FUSED_DECODE=0."""
    import os

    if os.environ.get("DORA_FUSED_DECODE", "1") in ("", "0"):
        return False
    if batch != 1:
        return False
    blocks = params.get("blocks", {})
    blk = blocks.get("0")
    if blk is None:
        return False

    def _q(x):
        return isinstance(x, dict) and ("int8" in x or "int4" in x)

    return (
        _q(blk.get("wqkv"))
        and _q(blk.get("w_gateup"))
        and _q(blk.get("wo"))
        and _q(blk.get("w_down"))
        and _q(params.get("lm_head"))
        and "bo" not in blk
        and "b_down" not in blk
    )


def _qw(d: dict):
    """Quantized dict -> (weights, scales) in the kernel layout."""
    if "int4" in d:
        return d["int4"], d["gscale"]
    return d["int8"], d["scale"]


def decode_step_fused(params, cfg: VLMConfig, token, caches, position):
    """One greedy decode step through the fused kernels: two Pallas
    calls per layer + one for the lm_head, KV caches updated in place
    (no logits materialize — returns the argmax token directly).

    Requires :func:`fused_decode_ready`. token: [1] int32. Returns
    (next_token [1] int32, caches).
    """
    return decode_chunk_fused(params, cfg, token[:, None], caches, position)


def decode_chunk_fused(params, cfg: VLMConfig, tokens, caches, position):
    """M-row fused greedy pass: rows are consecutive tokens at positions
    ``position..position+M-1`` (the speculative-verify shape — one
    weight stream serves all rows). tokens: [1, M] int32. Returns
    (greedy [M] int32 — greedy[i] continues the prefix through row i —
    and the in-place-updated caches). Caller guarantees
    ``position + M <= max_seq`` (speculation headroom)."""
    from dora_tpu.ops import decode_block as DB

    dtype = L.compute_dtype()
    m = tokens.shape[1]
    x = params["embed"].astype(dtype)[tokens[0]]  # [M, dim]
    cos_t, sin_t = L.rope_table(cfg.max_seq, cfg.head_dim)
    cos_rows, sin_rows = DB.rope_rows(cos_t, sin_t, position, m)
    return fused_decode_pass(
        params, x, caches, position, cos_rows, sin_rows,
        heads=cfg.heads, kv_heads=cfg.kv_heads, head_dim=cfg.head_dim,
        layers=cfg.layers,
    )


def _fused_pass(params, x, attn_apply, *, heads: int, kv_heads: int,
                head_dim: int, layers: int, eps: float, lora=None):
    """Shared skeleton of every fused decode pass: per-layer quantized
    weight unpacking, bias zero-fill, the MLP sweep and the streamed
    lm_head argmax. ``attn_apply(layer_index, x, blk, wqkv, sqkv, bqkv,
    wo, swo) -> (x, cache_entry)`` supplies the attention variant
    (single-row / M-row chunk / B-row batch — they differ only in cache
    indexing and position plumbing).

    ``lora`` (multi-tenant serving) is ``(groups [R], a_stack
    [S, L, D, r], b_stack [S, L, r, D])``: per-layer rank-r
    residual-stream adapters gathered per ROW by adapter id (slot 0 is
    the all-zeros base, so adapter-less rows pay an exact zero delta)
    — ops/lora.py's grouped gather-matmul, executed inside the fused
    pass so a mixed-tenant batch stays ONE program."""
    from dora_tpu.ops import decode_block as DB

    n_qkv = (heads + 2 * kv_heads) * head_dim
    new_caches = {}
    for i in range(layers):
        blk = params["blocks"][str(i)]
        bqkv = blk.get("bqkv")
        if bqkv is None:
            bqkv = jnp.zeros((n_qkv,), jnp.float32)
        wqkv, sqkv = _qw(blk["wqkv"])
        wo, swo = _qw(blk["wo"])
        x, new_caches[str(i)] = attn_apply(
            i, x, blk, wqkv, sqkv, bqkv, wo, swo
        )
        wgu, sgu = _qw(blk["w_gateup"])
        wd, sd = _qw(blk["w_down"])
        ffn = wd.shape[0] * (2 if "int4" in blk["w_down"] else 1)
        bgu = blk.get("b_gateup")
        if bgu is None:
            bgu = jnp.zeros((2 * ffn,), jnp.float32)
        x = DB.mlp_step(x, blk["ffn_norm"], wgu, sgu, bgu, wd, sd, eps=eps)
        if lora is not None:
            from dora_tpu.ops.lora import lora_gather_matmul

            groups, a_stack, b_stack = lora
            x = x + lora_gather_matmul(
                x, groups, a_stack[:, i], b_stack[:, i]
            ).astype(x.dtype)
    wh, sh = _qw(params["lm_head"])
    greedy = DB.lm_head_argmax(x, params["out_norm"], wh, sh, eps=eps)
    return greedy, new_caches


def fused_decode_pass(params, x, caches, position, cos_rows, sin_rows, *,
                      heads: int, kv_heads: int, head_dim: int, layers: int,
                      eps: float = 1e-6):
    """The family-agnostic fused decode pass: the caller embeds the
    tokens and supplies per-row rope tables (standard RoPE here, M-RoPE
    text continuation in models/hf/qwen2_vl — at decode all three axes
    share the position, so its rows reduce to standard rows at the rope
    position, which may differ from the cache ``position``). params
    needs blocks/out_norm/lm_head in the quantized fused layout."""
    from dora_tpu.ops import decode_block as DB

    m = x.shape[0]
    attn = DB.attention_step if m == 1 else DB.attention_chunk_step

    def attn_apply(i, x, blk, wqkv, sqkv, bqkv, wo, swo):
        kc = caches[str(i)]["k"][0]  # [KV, S, hd]
        vc = caches[str(i)]["v"][0]
        x, kc, vc = attn(
            x, blk["attn_norm"], wqkv, sqkv, bqkv, cos_rows, sin_rows,
            kc, vc, wo, swo, position,
            heads=heads, kv_heads=kv_heads, head_dim=head_dim, eps=eps,
        )
        return x, {"k": kc[None], "v": vc[None]}

    return _fused_pass(
        params, x, attn_apply, heads=heads, kv_heads=kv_heads,
        head_dim=head_dim, layers=layers, eps=eps,
    )


def generate(params, cfg: VLMConfig, images, prompt_ids, max_new_tokens: int):
    """Greedy generation as one traced computation (scan over decode steps).

    Returns [B, max_new_tokens] int32. jit this (static: cfg,
    max_new_tokens).
    """
    logits, caches, position = prefill(params, cfg, images, prompt_ids)
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    if fused_decode_ready(params, prompt_ids.shape[0]):
        def step(carry, _):
            token, caches, position = carry
            nxt, caches = decode_step_fused(
                params, cfg, token, caches, position
            )
            return (nxt, caches, position + 1), token
    else:
        def step(carry, _):
            token, caches, position = carry
            logits, caches = decode_step(params, cfg, token, caches, position)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, caches, position + 1), token

    # Unrolling the decode scan amortizes the per-step while-loop
    # bookkeeping (batch-1 steps are sub-3ms; the loop overhead is a
    # measurable slice). DORA_DECODE_UNROLL=1 opts out.
    import os

    # Read at trace time: changing it after the jit cache is warm needs
    # a process restart. Clamped to >= 1 (0 would crash lax.scan).
    unroll = max(1, int(os.environ.get("DORA_DECODE_UNROLL", "4")))
    (_, _, _), tokens = jax.lax.scan(
        step, (first, caches, jnp.asarray(position, jnp.int32)), None,
        length=max_new_tokens, unroll=min(unroll, max_new_tokens),
    )
    return tokens.T  # [B, max_new]


def fused_paged_pass_batch(params, x, pools, positions, block_tables,
                           cos_rows, sin_rows, *, heads: int, kv_heads: int,
                           head_dim: int, layers: int, eps: float = 1e-6,
                           lora=None):
    """Batched fused pass over PAGED KV pools: per-layer K/V live as a
    pool of [P, KV, page, hd] blocks and each row's context streams
    through its ``block_tables`` row instead of a contiguous
    [slot, max_seq] plane (ops.decode_block.attention_paged_batch_step).
    Same per-row math as :func:`fused_decode_pass` (caller embeds tokens
    and gathers per-row rope rows; hf families pass their own rope
    base) — the paged engine's greedy tokens stay identical to the
    serial reference's."""
    from dora_tpu.ops import decode_block as DB

    def attn_apply(i, x, blk, wqkv, sqkv, bqkv, wo, swo):
        lp = pools[str(i)]
        if "ks" in lp:  # int8-KV pools carry parallel scale planes
            x, kp, vp, ksp, vsp = DB.attention_paged_batch_step(
                x, blk["attn_norm"], wqkv, sqkv, bqkv, cos_rows, sin_rows,
                lp["k"], lp["v"], wo, swo, positions, block_tables,
                lp["ks"], lp["vs"],
                heads=heads, kv_heads=kv_heads, head_dim=head_dim, eps=eps,
            )
            return x, {"k": kp, "v": vp, "ks": ksp, "vs": vsp}
        x, kp, vp = DB.attention_paged_batch_step(
            x, blk["attn_norm"], wqkv, sqkv, bqkv, cos_rows, sin_rows,
            lp["k"], lp["v"], wo, swo, positions,
            block_tables,
            heads=heads, kv_heads=kv_heads, head_dim=head_dim, eps=eps,
        )
        return x, {"k": kp, "v": vp}

    return _fused_pass(
        params, x, attn_apply, heads=heads, kv_heads=kv_heads,
        head_dim=head_dim, layers=layers, eps=eps, lora=lora,
    )


def fused_paged_pass_chunk(params, x, pools, position, block_table,
                           cos_rows, sin_rows, *, heads: int, kv_heads: int,
                           head_dim: int, layers: int, eps: float = 1e-6,
                           lora=None):
    """One prefill CHUNK through the fused kernels into paged pools:
    x [M, dim] holds the chunk's embedded tokens at positions
    ``position..position+M-1`` (``position`` and M page-multiples — the
    chunk's K/V land as whole pool pages through this slot's
    ``block_table`` row). M is fixed by the engine, so prefill compiles
    exactly one chunk shape — ever — instead of one program per
    power-of-two bucket. Returns (greedy [M], pools); greedy[i]
    continues the prefix through row i, so the final chunk's row at
    ``true_len - 1 - position`` is the stream's first generated token."""
    from dora_tpu.ops import decode_block as DB

    def attn_apply(i, x, blk, wqkv, sqkv, bqkv, wo, swo):
        lp = pools[str(i)]
        if "ks" in lp:  # int8-KV pools carry parallel scale planes
            x, kp, vp, ksp, vsp = DB.attention_paged_chunk_step(
                x, blk["attn_norm"], wqkv, sqkv, bqkv, cos_rows, sin_rows,
                lp["k"], lp["v"], wo, swo, position, block_table,
                lp["ks"], lp["vs"],
                heads=heads, kv_heads=kv_heads, head_dim=head_dim, eps=eps,
            )
            return x, {"k": kp, "v": vp, "ks": ksp, "vs": vsp}
        x, kp, vp = DB.attention_paged_chunk_step(
            x, blk["attn_norm"], wqkv, sqkv, bqkv, cos_rows, sin_rows,
            lp["k"], lp["v"], wo, swo, position,
            block_table,
            heads=heads, kv_heads=kv_heads, head_dim=head_dim, eps=eps,
        )
        return x, {"k": kp, "v": vp}

    return _fused_pass(
        params, x, attn_apply, heads=heads, kv_heads=kv_heads,
        head_dim=head_dim, layers=layers, eps=eps, lora=lora,
    )


def fused_paged_pass_spec(params, x, pools, positions, block_tables,
                          cos_rows, sin_rows, *, heads: int, kv_heads: int,
                          head_dim: int, layers: int, m: int,
                          eps: float = 1e-6, lora=None):
    """Speculative VERIFICATION pass over paged KV pools: x [B*m, dim]
    holds, stream-major, each stream's m = k+1 candidate rows (last
    emitted token + its k drafts) at positions
    ``positions[b]..positions[b]+m-1`` of that stream's paged context
    (ops.decode_block.attention_paged_spec_step). One weight stream
    verifies all B·m rows; greedy[b*m + i] continues stream b's prefix
    through candidate i, so comparing it against the drafts replays
    exactly the serial spec_decode acceptance test. Returns
    (greedy [B*m], pools)."""
    from dora_tpu.ops import decode_block as DB

    def attn_apply(i, x, blk, wqkv, sqkv, bqkv, wo, swo):
        lp = pools[str(i)]
        if "ks" in lp:  # int8-KV pools carry parallel scale planes
            x, kp, vp, ksp, vsp = DB.attention_paged_spec_step(
                x, blk["attn_norm"], wqkv, sqkv, bqkv, cos_rows, sin_rows,
                lp["k"], lp["v"], wo, swo, positions, block_tables,
                lp["ks"], lp["vs"],
                heads=heads, kv_heads=kv_heads, head_dim=head_dim, m=m,
                eps=eps,
            )
            return x, {"k": kp, "v": vp, "ks": ksp, "vs": vsp}
        x, kp, vp = DB.attention_paged_spec_step(
            x, blk["attn_norm"], wqkv, sqkv, bqkv, cos_rows, sin_rows,
            lp["k"], lp["v"], wo, swo, positions,
            block_tables,
            heads=heads, kv_heads=kv_heads, head_dim=head_dim, m=m, eps=eps,
        )
        return x, {"k": kp, "v": vp}

    return _fused_pass(
        params, x, attn_apply, heads=heads, kv_heads=kv_heads,
        head_dim=head_dim, layers=layers, eps=eps, lora=lora,
    )


def generate_tp(params, tp_params, cfg: VLMConfig, images, prompt_ids,
                max_new_tokens: int, mesh):
    """Greedy generation with the decode scan on the FUSED kernel tier
    sharded over the tp mesh axis (parallel/fused_tp.py): per-rank
    Pallas kernels + one f32 psum per sublayer + vocab-sharded argmax.
    ``tp_params`` comes from fused_tp.prepare_decode_params. Prefill
    rides the unfused path (runs once; decode dominates). Emits the
    same tokens as :func:`generate` (asserted in tests/test_fused_tp.py
    and the driver serving dryrun)."""
    from dora_tpu.ops import decode_block as DB
    from dora_tpu.parallel import fused_tp as FTP

    dtype = L.compute_dtype()
    logits, caches, position = prefill(params, cfg, images, prompt_ids)
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    caches = FTP.shard_caches(caches, mesh)
    cos_t, sin_t = L.rope_table(cfg.max_seq, cfg.head_dim)

    def step(carry, _):
        token, caches, pos = carry
        cos, sin = DB.rope_rows(cos_t, sin_t, pos, 1)
        nxt, caches = FTP.decode_pass_tp(
            tp_params, params["embed"].astype(dtype)[token], caches, pos,
            cos, sin, heads=cfg.heads, kv_heads=cfg.kv_heads,
            head_dim=cfg.head_dim, layers=cfg.layers, mesh=mesh,
        )
        return (nxt, caches, pos + 1), token

    (_, _, _), tokens = jax.lax.scan(
        step, (first, caches, jnp.asarray(position, jnp.int32)), None,
        length=max_new_tokens,
    )
    return tokens.T


# ---------------------------------------------------------------------------
# speculative decoding (prompt lookup)
# ---------------------------------------------------------------------------


def generate_speculative(params, cfg: VLMConfig, images, prompt_ids,
                         max_new_tokens: int, k: int = 4, ngram: int = 2):
    """Greedy generation with prompt-lookup speculation — bit-identical
    output to :func:`generate`, up to ``k+1`` tokens per model pass.

    Batch-1 decode pays the full LM weight stream per token; verifying a
    ``k+1``-token chunk costs the same weight traffic as one token, so
    every accepted draft token is nearly free. Drafts come from the
    sequence itself (the continuation of the most recent occurrence of
    the trailing ``ngram``) — no draft model, exact greedy equivalence
    by construction (every emitted token is an argmax of the full
    model): camera captions and transcripts are repetitive, which is
    exactly when batch-1 decode throughput matters.

    The KV cache stays static-shape: each verification writes positions
    ``p..p+k``; rejected tail entries are provably overwritten before
    they become attendable (the next chunk starts at the first rejected
    position). jit-compiled once; B must be 1.
    """
    from dora_tpu.models.spec_decode import check_headroom

    assert prompt_ids.shape[0] == 1, "speculative decode is batch-1"
    # Exactness guard: the loop must never hit the context limit with
    # tokens still owed (it would stop early and leave unverified
    # spillover in the buffer). Context = image patches + prompt text.
    check_headroom(
        cfg.n_patches + prompt_ids.shape[1], max_new_tokens, cfg.max_seq,
        "prompt", k,
    )
    return _generate_spec_jit(
        params, cfg, images, prompt_ids, max_new_tokens, k, ngram
    )


@partial(jax.jit, static_argnums=(1, 4, 5, 6))
def _generate_spec_jit(params, cfg: VLMConfig, images, prompt_ids,
                       max_new_tokens: int, k: int, ngram: int):
    from dora_tpu.models import spec_decode

    dtype = L.compute_dtype()
    logits, caches, position = prefill(params, cfg, images, prompt_ids)
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [1]

    seq = cfg.max_seq
    # Rolling token history for the lookup (prompt text + generated).
    t_prompt = prompt_ids.shape[1]
    history = jnp.zeros((seq,), jnp.int32)
    history = jax.lax.dynamic_update_slice(
        history, prompt_ids[0].astype(jnp.int32), (0,)
    )
    history = history.at[t_prompt].set(first[0])

    use_fused = fused_decode_ready(params)

    def verify(chunk, n_emitted, caches):
        # generated token j lives at cache position `position + j`
        # (image patches + prompt precede it); `chunk[0, 0]` is
        # generated index n_emitted-1.
        cache_index = position + n_emitted - 1
        if use_fused:
            # Both pass widths ride the fused kernel tier (the M-row
            # chunk kernel streams the weights once for all rows), so a
            # verification pass costs ~one fused decode step and
            # speculation cannot meaningfully lose even at zero
            # acceptance.
            return decode_chunk_fused(
                params, cfg, chunk, caches, cache_index
            )
        chunk_pos = cache_index + jnp.arange(chunk.shape[1])
        mask = (
            jnp.arange(cfg.max_seq)[None, None, None, :]
            <= chunk_pos[None, None, :, None]
        )
        h = params["embed"].astype(dtype)[chunk]
        h, new_caches = _lm_forward(
            params, cfg, h, chunk_pos[None], mask, caches=caches,
            cache_index=cache_index,
        )
        greedy = jnp.argmax(
            L.matmul(h[0], params["lm_head"]).astype(jnp.float32), axis=-1
        ).astype(jnp.int32)
        return greedy, new_caches

    return spec_decode.run_loop(
        caches=caches, history=history, hist_len=t_prompt + 1,
        first=first[0], max_new_tokens=max_new_tokens, seq=seq,
        verify=verify, k=k, ngram=ngram,
        body=spec_decode.fitting_body_passes(
            cfg.n_patches + t_prompt, max_new_tokens, seq, k
        ),
    )


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


def loss_fn(params, cfg: VLMConfig, batch, mesh=None, ring_axis=None,
            sp_impl=None):
    """Next-token cross-entropy on the text portion, image tokens prefixed.

    batch: {"images": [B,H,W,3], "tokens": [B,T] int32}; predicts tokens
    shifted by one, with the image prefix never scored.
    """
    dtype = L.compute_dtype()
    images, tokens = batch["images"], batch["tokens"]
    b, t = tokens.shape
    img = encode_image(params, cfg, images)
    txt = params["embed"].astype(dtype)[tokens]
    h = jnp.concatenate([img, txt], axis=1)
    seq = h.shape[1]
    positions = jnp.broadcast_to(jnp.arange(seq), (b, seq))
    flash = "causal" if L.use_flash() and not ring_axis else None
    h, _ = _lm_forward(
        params, cfg, h, positions, L.causal_mask(seq, seq),
        mesh=mesh, ring_axis=ring_axis, flash=flash, sp_impl=sp_impl,
    )
    # Score only text positions: logits at [P-1 .. P+T-2] predict tokens.
    p = cfg.n_patches
    h_txt = h[:, p - 1 : p + t - 1]
    logits = L.matmul(h_txt, params["lm_head"]).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


def make_train_step(cfg: VLMConfig, optimizer, mesh=None, ring_axis=None,
                    sp_impl=None):
    """Returns jitted (params, opt_state, batch) -> (params, opt_state, loss).

    With a mesh: batch sharded over dp (and sequence over sp when
    ring_axis is set); parameters follow the Megatron tp rules; XLA
    inserts the gradient psum from the shardings. ``sp_impl`` picks the
    sequence-parallel strategy ("ring" | "ulysses"); unset, it resolves
    from DORA_SP_IMPL here, once, at step construction.
    """
    if sp_impl is None:
        import os

        sp_impl = os.environ.get("DORA_SP_IMPL", "ring")

    def train_step(params, opt_state, batch):
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            seq_spec = ("sp",) if ring_axis else (None,)
            batch = {
                "images": jax.lax.with_sharding_constraint(
                    batch["images"], NamedSharding(mesh, P("dp"))
                ),
                "tokens": jax.lax.with_sharding_constraint(
                    batch["tokens"], NamedSharding(mesh, P("dp"))
                ),
            }
        loss, grads = jax.value_and_grad(loss_fn)(
            params, cfg, batch, mesh=mesh, ring_axis=ring_axis,
            sp_impl=sp_impl,
        )
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: p + u, params, updates)
        return params, opt_state, loss

    return jax.jit(train_step, donate_argnums=(0, 1))
