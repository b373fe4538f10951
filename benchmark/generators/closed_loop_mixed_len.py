"""Closed loop over short and long prompts in one queue: N callers, each
sends its next streaming chat completion when its last one finished.
Parameters come from the traffic file: ``callers``, ``long_every``,
``long_prompt_tokens``, ``prompt_tokens`` (the short ones),
``output_tokens``, ``block``, ``max_requests_per_s`` (only sizes the
plan: the loop stops at the window's end, not at the plan's). Each
caller's first request is one of the warm wave (``chat_plan.warm_wave``);
the window opens when every caller has finished it.

The schedule. Slot ``p`` is long iff ``p % long_every == place``, with
``place`` drawn once from ``shape_seed``: exactly one long request in
every ``long_every`` consecutive slots. Lengths are the quantiles of the
traffic file's distributions over a block of ``block`` slots (``block /
long_every`` long ones, the rest short, ``block`` outputs), dealt evenly
over the block's groups of 16 (``chat_plan._spread``: a group holds one
value of every stratum) and laid out in one order drawn from
``shape_seed``: the same schedule for every ``--seed``, which gives the
token ids. No prompt repeats (the model has no prefix cache).
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "lib"))

import chat_plan  # noqa: E402
from checkpoint import token_code  # noqa: E402

KIND = "process"  # a load process of its own beside the dataflow
GROUP = 16


def block_layout(traffic: dict) -> list[dict]:
    """One block's slots in the order every run sends them: ``long``,
    ``prompt_tokens``, ``max_tokens``. Pure, from the traffic file."""
    import numpy as np

    block, every = traffic["block"], traffic["long_every"]
    group = min(GROUP, block)
    if block % group or group % every:
        raise ValueError(f"block {block}: no multiple of {group}, or {group} of {every}")
    groups = block // group
    rng = np.random.default_rng(traffic["shape_seed"])
    place = int(rng.integers(0, every))
    longs = chat_plan._spread(
        chat_plan.lengths(traffic["long_prompt_tokens"], block // every), groups, rng)
    shorts = chat_plan._spread(
        chat_plan.lengths(traffic["prompt_tokens"], block - block // every), groups, rng)
    outputs = chat_plan._spread(chat_plan.lengths(traffic["output_tokens"], block), groups, rng)
    layout = []
    for g in range(groups):
        long_ = rng.permutation(longs[g]).tolist()
        short = rng.permutation(shorts[g]).tolist()
        new = rng.permutation(outputs[g]).tolist()
        for k in range(group):
            is_long = k % every == place
            layout.append({
                "long": is_long, "prompt_tokens": (long_ if is_long else short).pop(),
                "max_tokens": new[k],
            })
    return layout


def requests(traffic: dict, seed: int, count: int, vocab: int) -> list[dict]:
    """The block layout over and over with token ids from ``seed`` (a
    stream a slot, so a slot's ids do not depend on the plan's length)."""
    import numpy as np

    layout = block_layout(traffic)
    out = []
    for p in range(count):
        slot = layout[p % len(layout)]
        ids = np.random.default_rng([seed, 3, p]).integers(
            0, vocab, size=slot["prompt_tokens"]).tolist()
        out.append({
            "ids": ids, "prompt_tokens": len(ids), "twin_of": None, "long": slot["long"],
            "max_tokens": slot["max_tokens"], "text": "".join(map(token_code, ids)),
        })
    return out


def plan(traffic: dict, seed: int, seconds: float, config: dict) -> dict:
    callers, vocab = traffic["callers"], config["model"]["vocab_size"]
    count = int(traffic["max_requests_per_s"] * seconds)
    warm = chat_plan.warm_wave(
        callers, seed, vocab, traffic["warm_prompt_tokens"], traffic["warm_step_tokens"]
    )
    return {"mode": "closed", "callers": callers,
            "requests": warm + requests(traffic, seed, count, vocab)}


def measure(ctx, run: dict) -> dict:
    import chat_measure_k_exaone

    return chat_measure_k_exaone.measure(
        ctx, run, plan(ctx.traffic, ctx.traffic_seed, ctx.seconds, ctx.config)
    )


if __name__ == "__main__":
    import chat_client

    sys.exit(chat_client.main(plan))
