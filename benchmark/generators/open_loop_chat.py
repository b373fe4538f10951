"""Open loop: arrivals on a schedule at the traffic file's fixed
``rate_per_s``, whatever the server does; a backlog may form. The plan
holds exactly round(rate x seconds) arrivals inside the window, preceded
by ``lead_s`` seconds of arrivals at the same rate (the lead-in that
brings the server to its steady state; not measured for latency) and by
a warm wave of ``warm_wave`` requests sent at once."""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "lib"))

import chat_plan  # noqa: E402

KIND = "process"  # a load process of its own beside the dataflow


def plan(traffic: dict, seed: int, seconds: float, config: dict) -> dict:
    rate, lead_s = traffic["rate_per_s"], traffic["lead_s"]
    shape = traffic["shape_seed"]
    lead = [t - lead_s for t in chat_plan.arrivals(rate, lead_s, shape + 7)]
    inside = chat_plan.arrivals(rate, seconds, shape)
    vocab = config["model"]["vocab_size"]
    warm = chat_plan.warm_wave(
        traffic["warm_wave"], seed, vocab, traffic["warm_prompt_tokens"],
        traffic["warm_step_tokens"],
    )
    n = len(lead) + len(inside)
    rest = chat_plan.requests(traffic, seed, n, vocab)[:n]
    for r, due in zip(rest, lead + inside):
        r["due_s"] = due
    return {
        "mode": "open", "warm": warm, "requests": rest, "lead_s": lead_s,
        "drain_s": traffic["drain_s"],
    }


def measure(ctx, run: dict) -> dict:
    import chat_measure

    return chat_measure.measure(
        ctx, run, plan(ctx.traffic, ctx.traffic_seed, ctx.seconds, ctx.config)
    )


if __name__ == "__main__":
    import chat_client

    sys.exit(chat_client.main(plan))
