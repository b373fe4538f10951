"""Sink of the frames cells: stamps every output's arrival on
``time.monotonic()`` and keeps its tokens; writes both when the dataflow
stops (the sink of ``bench_vlm.py:bench_e2e``, PR 21, without its own
arithmetic: the harness computes rates and gaps over its window)."""

from __future__ import annotations

import json
import os
import time

from dora_tpu.node import Node


def main() -> None:
    stamps, tokens = [], []
    out = os.environ["BENCH_SINK_OUT"]

    def write() -> None:
        tmp = out + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"stamps": stamps, "tokens": tokens}, f)
        os.replace(tmp, out)

    with Node() as node:
        for event in node:
            if event["type"] == "STOP":
                break
            if event["type"] != "INPUT":
                continue
            stamps.append(time.monotonic())
            tokens.append(event["value"].to_pylist())
            if len(stamps) == 1:
                write()  # tells the harness that outputs flow
    write()


if __name__ == "__main__":
    main()
