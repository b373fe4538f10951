"""Closed loop over long prompts and long answers: N callers, each sends
its next streaming chat completion when its last one finished.
Parameters come from the traffic file: ``callers``, ``prompt_tokens``,
``output_tokens``, ``block``, ``max_requests_per_s`` (only sizes the
plan: the loop stops at the window's end, not at the plan's). Each
caller's first request is one of the warm wave (``chat_plan.warm_wave``);
the window opens when every caller has finished it.

The schedule. Lengths are the quantiles of the traffic file's
distributions over a block of ``block`` slots, dealt evenly over the
block's groups of 16 (``chat_plan._spread``: a group holds one value of
every stratum) and laid out in one order drawn from ``shape_seed``: the
same schedule for every ``--seed``, which gives the token ids. No prompt
repeats (the model has no prefix cache). ``closed_loop_mixed_len`` with
``long_every`` 1 lays out such a plan too, but its ``measure`` is
K-EXAONE's; ``closed_loop_chat`` ends every group on a repeat.
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
    """One block's slots in the order every run sends them:
    ``prompt_tokens``, ``max_tokens``. Pure, from the traffic file."""
    import numpy as np

    block = traffic["block"]
    group = min(GROUP, block)
    if block % group:
        raise ValueError(f"block {block}: no multiple of {group}")
    groups = block // group
    rng = np.random.default_rng(traffic["shape_seed"])
    prompts = chat_plan._spread(chat_plan.lengths(traffic["prompt_tokens"], block), groups, rng)
    outputs = chat_plan._spread(chat_plan.lengths(traffic["output_tokens"], block), groups, rng)
    layout = []
    for g in range(groups):
        layout += [{"prompt_tokens": p, "max_tokens": m} for p, m in zip(
            rng.permutation(prompts[g]).tolist(), rng.permutation(outputs[g]).tolist())]
    return layout


def requests(traffic: dict, seed: int, count: int, vocab: int) -> list[dict]:
    """The block layout over and over with token ids from ``seed`` (a
    stream a slot, so a slot's ids do not depend on the plan's length)."""
    import numpy as np

    layout = block_layout(traffic)
    out = []
    for p in range(count):
        slot = layout[p % len(layout)]
        ids = np.random.default_rng([seed, 5, p]).integers(
            0, vocab, size=slot["prompt_tokens"]).tolist()
        out.append({
            "ids": ids, "prompt_tokens": len(ids), "twin_of": None,
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
    import chat_measure_glm5_next

    return chat_measure_glm5_next.measure(
        ctx, run, plan(ctx.traffic, ctx.traffic_seed, ctx.seconds, ctx.config)
    )


if __name__ == "__main__":
    import chat_client

    sys.exit(chat_client.main(plan))
