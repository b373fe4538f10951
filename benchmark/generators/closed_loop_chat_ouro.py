"""``closed_loop_chat``'s plan and load process (N callers, each sends
its next streaming chat completion when its last one finished; the same
parameters of the traffic file) for an ``ouro`` configuration: what
differs is ``measure``, which holds the run to the plain reference of
that model and to the rows its passes keep (``lib/chat_measure_ouro.py``).
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "lib"))

from closed_loop_chat import KIND, plan  # noqa: E402,F401


def measure(ctx, run: dict) -> dict:
    import chat_measure_ouro

    return chat_measure_ouro.measure(
        ctx, run, plan(ctx.traffic, ctx.traffic_seed, ctx.seconds, ctx.config)
    )


if __name__ == "__main__":
    import chat_client

    sys.exit(chat_client.main(plan))
