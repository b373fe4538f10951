"""``closed_loop_long_ctx``'s plan and load process (N callers, each sends
its next streaming chat completion when its last one finished; one
schedule for every seed, no repeats; the same parameters of the traffic
file) for a ``zaya`` configuration: what differs is ``measure``, which
holds the run to the plain reference of that model and to what its pages
and its convolution and value-shift tails keep
(``lib/chat_measure_zaya.py``).
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "lib"))

from closed_loop_long_ctx import KIND, block_layout, plan  # noqa: E402,F401


def measure(ctx, run: dict) -> dict:
    import chat_measure_zaya

    return chat_measure_zaya.measure(
        ctx, run, plan(ctx.traffic, ctx.traffic_seed, ctx.seconds, ctx.config)
    )


if __name__ == "__main__":
    import chat_client

    sys.exit(chat_client.main(plan))
