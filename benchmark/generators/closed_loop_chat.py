"""Closed loop: N callers, each sends its next streaming chat completion
when its last one finished. Parameters come from the traffic file:
``callers``, ``prompt_tokens``, ``output_tokens``, ``repeat_every``,
``max_requests_per_s`` (only sizes the plan: the loop stops at the
window's end, not at the plan's). Each caller's first request is one of
the warm wave (``chat_plan.warm_wave``); the window opens when every
caller has finished it."""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "lib"))

import chat_plan  # noqa: E402

KIND = "process"  # a load process of its own beside the dataflow


def plan(traffic: dict, seed: int, seconds: float, config: dict) -> dict:
    callers, vocab = traffic["callers"], config["model"]["vocab_size"]
    count = int(traffic["max_requests_per_s"] * seconds)
    warm = chat_plan.warm_wave(
        callers, seed, vocab, traffic["warm_prompt_tokens"], traffic["warm_step_tokens"]
    )
    rest = chat_plan.requests(traffic, seed, count, vocab)
    for r in rest:  # twin_of indexes the whole plan
        if r["twin_of"] is not None:
            r["twin_of"] += len(warm)
    return {"mode": "closed", "callers": callers, "requests": warm + rest}


def measure(ctx, run: dict) -> dict:
    import chat_measure

    return chat_measure.measure(
        ctx, run, plan(ctx.traffic, ctx.traffic_seed, ctx.seconds, ctx.config)
    )


if __name__ == "__main__":
    import chat_client

    sys.exit(chat_client.main(plan))
