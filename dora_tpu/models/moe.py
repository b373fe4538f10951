"""The expert layer that four served configurations run (Kimi-K2,
K-EXAONE, GLM-5.3-Flash; Keye-VL-2.0 under a router of its own), as ONE
RANK of an expert group computes it.

The router keeps its published width (a sigmoid score for every expert
of the model, top-k of the biased scores, unbiased normalised weights
times ``routed_scaling_factor``); this rank computes the part of the
result that its own ``experts_held`` experts (``expert_first`` onward)
give, for the pairs that land on them, and leaves out what the absent
experts would add. One chip runs the layer without its exchange. Plain
``jax.numpy`` over ``ops/int8_matmul``.

A model file (``models/hf/``) brings the config and the weights:
``cfg`` is any object with the fields :class:`ExpertLayerConfig` names,
a layer's ``blk`` holds ``"dense"`` (a SwiGLU) or ``"router"``,
``"router_bias"``, ``"experts"`` (the held experts' SwiGLUs as ONE
stack: every leaf of a SwiGLU with a leading axis over them) and, where
the model has one, ``"shared"``; :func:`expert_layer_weights`,
:func:`swiglu_weights` and :func:`stack_experts` load them.
"""

from __future__ import annotations

import os
from typing import Protocol

import jax
import jax.numpy as jnp

from dora_tpu.models import layers as L
from dora_tpu.ops.int8_matmul import int8_matmul_grouped, quantize_int8_t

#: rows one expert computes at a time in a prefill chunk.
EXPERT_BLOCK = 32
#: a batch of at most this many rows (a decode tick's, up to 64 slots)
#: goes to every expert it touched whole: two grouped products a layer,
#: where the chunk's form would run a loop an expert a tick.
WHOLE_ROWS = 64


class ExpertLayerConfig(Protocol):
    """What the expert layer reads of a model's config."""

    dim: int
    top_k: int
    norm_topk: bool
    routed_scale: float
    n_shared: int
    #: this rank's share: experts ``expert_first .. +experts_held``
    expert_first: int
    experts_held: int
    #: how many of the model's layers are expert layers
    moe_layers: int


def expert_share(config: dict, ep_rank: int | None = None) -> tuple[int, int]:
    """``(first, held)``: the experts of every layer that this rank
    computes. HF's meaning of the keys: ``n_routed_experts`` counts the
    model's experts and ``ep_size`` the ranks that divide them, each
    holding ``n_routed_experts // ep_size`` consecutive ones. The ranks
    of a group share one checkpoint directory, so ``ep_size`` is its
    ``config.json``'s and nothing else's; which share is this process's
    is the launcher's to say: ``ep_rank``, else ``DORA_EP_RANK``, else 0."""
    total = config["n_routed_experts"]
    ep_size = int(config.get("ep_size") or 1)
    if ep_rank is None:
        ep_rank = int(os.environ.get("DORA_EP_RANK") or 0)
    if total % ep_size or not 0 <= ep_rank < ep_size:
        raise ValueError(
            f"moe: {total} experts do not divide over ep_size "
            f"{ep_size} (rank {ep_rank})"
        )
    held = total // ep_size
    return ep_rank * held, held


def pad_outputs(w, to: int):
    """Zero output channels up to ``to`` (HF layout: rows are outputs)."""
    return jnp.pad(w, ((0, to - w.shape[0]), (0, 0)))


def swiglu_weights(get, prefix: str) -> dict:
    """A SwiGLU's three HF matrices under ``prefix`` (``get(name) -> device
    array``) as two int8 matrices, gate and up side by side."""
    return {
        "w_gateup": quantize_int8_t(
            get(prefix + "gate_proj.weight"), get(prefix + "up_proj.weight")
        ),
        "w_down": quantize_int8_t(get(prefix + "down_proj.weight")),
    }


@jax.jit
def _stack(each: list):
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *each)


def stack_experts(get, cfg: ExpertLayerConfig, prefix: str,
                  swiglu_weights=swiglu_weights) -> dict:
    """The HELD experts under ``prefix`` (``experts.<e>.``; an absent
    expert is never read) as one SwiGLU whose leaves have a leading axis
    over them: ``w_gateup`` ``{"int8": [E, K, 2I], "scale": [E, 1,
    2I]}``, ``w_down`` alike, a ``"limit"`` an expert where
    ``swiglu_weights`` put one. One program a layer shape joins them."""
    return _stack([
        swiglu_weights(get, f"{prefix}experts.{e}.")
        for e in range(cfg.expert_first, cfg.expert_first + cfg.experts_held)
    ])


def unstack_experts(stack: dict) -> list:
    """The stack's SwiGLUs one by one (what a plain reference reads)."""
    held = stack["w_down"]["int8"].shape[0]
    return [jax.tree.map(lambda leaf: leaf[e], stack) for e in range(held)]


def expert_layer_weights(get, cfg: ExpertLayerConfig, prefix: str,
                         swiglu_weights=swiglu_weights) -> dict:
    """An expert layer's entries of ``blk`` from the tensors under
    ``prefix`` (HF's DeepseekV3 names): the router and its bias, the
    shared expert where ``cfg.n_shared``, and the stack of the held
    experts. ``swiglu_weights`` loads one SwiGLU."""
    block = {
        "router": get(prefix + "gate.weight").T.astype(L.compute_dtype()),
        "router_bias": get(prefix + "gate.e_score_correction_bias").astype(
            jnp.float32),
    }
    if cfg.n_shared:
        block["shared"] = swiglu_weights(get, prefix + "shared_experts.")
    block["experts"] = stack_experts(get, cfg, prefix, swiglu_weights)
    return block


def _gated(h, limit=None):
    """``silu(gate) * up`` of a first projection's result ``h [..., 2I]``;
    with a ``limit``, the gate held to ``(-inf, limit]`` and the up part
    to ``[-limit, limit]`` first."""
    gate, up = jnp.split(h, 2, axis=-1)
    if limit is not None:
        limit = limit.astype(h.dtype)
        gate = jnp.minimum(gate, limit)
        up = jnp.clip(up, -limit, limit)
    return jax.nn.silu(gate) * up


def swiglu(w: dict, x):
    """One SwiGLU on rows ``x [N, dim]``. ``w["limit"]``, where a loader
    put one beside the matrices (a checkpoint's ``swiglu_limit``; Kimi-K2
    has none), is :func:`_gated`'s."""
    h = L.matmul(x, w["w_gateup"])
    return L.matmul(_gated(h, w.get("limit")), w["w_down"])


def stacked_swiglu(stack: dict, x, ids, n_groups):
    """SwiGLU ``ids[g]`` of ``stack`` (:func:`stack_experts`) on rows ``x
    [N, dim]`` for each of the first ``n_groups`` entries of ``ids [G]``,
    a grouped int8 product a projection: ``[G, N, dim]``, anything past
    ``n_groups``. Only the experts named there are read."""
    up, down = stack["w_gateup"], stack["w_down"]
    h = int8_matmul_grouped(x, up["int8"], up["scale"], ids, n_groups)
    limit = stack["limit"][ids][:, None, None] if "limit" in stack else None
    return int8_matmul_grouped(
        _gated(h, limit), down["int8"], down["scale"], ids, n_groups)


def route(blk, cfg: ExpertLayerConfig, x):
    """``noaux_tc`` routing with one group: sigmoid scores in float32
    over all experts; the top-k of ``score + bias`` are chosen; the
    weights are the UNBIASED scores of the chosen, normalised over all
    of them, times ``routed_scaling_factor``. Returns (ids [N, k] —
    global expert numbers — and weights [N, k], float32)."""
    with jax.named_scope("moe_router"):
        logits = jnp.dot(
            x.astype(jnp.float32), blk["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        scores = jax.nn.sigmoid(logits)
        _, ids = jax.lax.top_k(scores + blk["router_bias"], cfg.top_k)
        w = jnp.take_along_axis(scores, ids, axis=-1)
        if cfg.norm_topk:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        return ids, w * cfg.routed_scale


def held_experts(blk, cfg: ExpertLayerConfig, x, local, weights, live):
    """This rank's part of the routed sum: ``sum over chosen ∩ held of
    w_i E_i(x)`` for rows ``x [N, dim]``, in ascending order of expert;
    ``local [N, k]`` numbers the chosen experts from this rank's first
    (outside ``0..held`` = absent). Work follows the pairs that land
    here: an expert no live row chose is not read. A decode tick's rows
    go whole to every expert they touched, the touched experts' numbers
    compacted to the front of one grouped product a projection; in a
    chunk an expert computes only its own rows, ``EXPERT_BLOCK`` at a
    time, gathered and scattered by one-hot products. ``live [N]`` masks
    rows whose result nobody reads (frozen decode rows). Returns y [N,
    dim] in float32."""
    n = x.shape[0]
    stack = blk["experts"]
    held = stack["w_down"]["int8"].shape[0]
    with jax.named_scope("moe_experts"):
        if n <= WHOLE_ROWS:
            experts = jnp.arange(held)
            landed = jnp.where(live[:, None], local, -1)  # [N, k]
            touched = (landed == experts[:, None, None]).any((1, 2))  # [E]
            count = touched.sum().astype(jnp.int32)
            # group g <- the g-th touched expert, in ascending order
            place = (touched & (experts <= experts[:, None])).sum(-1) - 1
            ids = jnp.where(touched & (place == experts[:, None]), experts,
                            0).sum(-1).astype(jnp.int32)  # [G]
            ran = experts < count
            # [G, N] float32, 0 where the row did not choose the group's
            w = jnp.where(ran[:, None, None] & (landed == ids[:, None, None]),
                          weights, 0.0).sum(-1)
            out = stacked_swiglu(stack, x, ids, count)
            return jnp.where(ran[:, None, None],
                             out.astype(jnp.float32) * w[:, :, None], 0.0).sum(0)
        y = jnp.zeros((n, cfg.dim), jnp.float32)
        for e in range(held):
            hit = (local == e) & live[:, None]  # [N, k]
            mine = hit.any(-1)
            w_e = (weights * hit).sum(-1)  # [N] float32, 0 where not chosen
            # rank of each of the expert's rows among them, in order
            rank = jnp.cumsum(mine) - 1
            only = jnp.full((1,), e, jnp.int32)

            def body(j, y, only=only, w_e=w_e, mine=mine, rank=rank):
                slot = j * EXPERT_BLOCK + jnp.arange(EXPERT_BLOCK)
                pick = (mine[None, :] & (rank[None, :] == slot[:, None]))
                pick = pick.astype(x.dtype)  # [block, N] one-hot rows
                # this block's rows, in order
                out = stacked_swiglu(stack, pick @ x, only, 1)[0]
                back = jnp.dot(pick.T, out, preferred_element_type=jnp.float32)
                return y + back * w_e[:, None]

            n_e = mine.sum().astype(jnp.int32)
            blocks = (n_e + EXPERT_BLOCK - 1) // EXPERT_BLOCK
            y = jax.lax.fori_loop(0, blocks, body, y)
    return y


def mlp(blk, cfg: ExpertLayerConfig, x, live, counted):
    """The feed-forward sublayer on normed rows ``x``. Returns (output
    [N, dim], counters or None): for an expert layer ``(rows routed,
    pairs that landed on held experts, rows per held expert [held])``
    over the rows ``counted`` marks."""
    if "dense" in blk:
        with jax.named_scope("dense_mlp"):
            return swiglu(blk["dense"], x), None
    ids, weights = route(blk, cfg, x)
    local = ids - cfg.expert_first
    y = held_experts(blk, cfg, x, local, weights, live)
    if "shared" in blk:
        with jax.named_scope("moe_shared"):
            y = y + swiglu(blk["shared"], x).astype(jnp.float32)
    landed = (local >= 0) & (local < cfg.experts_held) & counted[:, None]
    per_expert = (
        (local[..., None] == jnp.arange(cfg.experts_held)) & landed[..., None]
    ).sum((0, 1)).astype(jnp.int32)
    return y.astype(x.dtype), (
        counted.sum().astype(jnp.int32), landed.sum().astype(jnp.int32),
        per_expert,
    )


def init_counters(cfg: ExpertLayerConfig) -> dict:
    """Routing counters on the device: an operand and a result of their
    own of the window and the chunk program, donated like the pools but
    no part of them (the cache's snapshot, restore and byte count never
    see them). int32 that wraps; :class:`paged_model.DeviceCounters`
    adds up the differences on the host."""
    names = ("tokens", "local_pairs", "decode_ticks", "touched")
    return {
        # a buffer each: the programs donate them one by one
        **{name: jnp.zeros((), jnp.int32) for name in names},
        "expert_tokens": jnp.zeros((cfg.moe_layers, cfg.experts_held),
                                   jnp.int32),
    }


def add_layer(stats: dict, per_layer: list, counters, decode: bool) -> None:
    """One layer's counters (:func:`mlp`'s second result; None for a dense
    layer) into a stack's running ``stats`` (:func:`init_counters`' keys,
    updated in place) and ``per_layer``."""
    if counters is None:
        return
    tokens, pairs, per_expert = counters
    stats["tokens"] = stats["tokens"] + tokens
    stats["local_pairs"] = stats["local_pairs"] + pairs
    per_layer.append(per_expert)
    if decode:
        stats["touched"] = stats["touched"] + (per_expert > 0).sum(
            dtype=jnp.int32)


def add_stack(stats: dict, per_layer: list, counted, decode: bool) -> None:
    """After the last layer: the rows per expert of every expert layer,
    and the tick itself where it was a decode tick with a counted row."""
    if not per_layer:
        return
    stats["expert_tokens"] = stats["expert_tokens"] + jnp.stack(per_layer)
    if decode:
        stats["decode_ticks"] = stats["decode_ticks"] + counted.any().astype(
            jnp.int32)


def report(totals: dict, moe_layers: int) -> dict:
    """The routing gauges ``ServingMetrics.model`` shows, from the host's
    running ``totals`` of :func:`init_counters`' tree (one reader serves
    the three configurations)."""
    ticks = int(totals["decode_ticks"]) * max(moe_layers, 1)
    return {
        "moe_tokens": int(totals["tokens"]),
        "moe_local_pairs": int(totals["local_pairs"]),
        "moe_expert_tokens": [int(n) for n in totals["expert_tokens"].sum(0)],
        "moe_experts_touched": (
            round(int(totals["touched"]) / ticks, 4) if ticks else None),
    }
