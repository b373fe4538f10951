"""Camera of the frames cells: on every timer tick, the next of
``BENCH_FRAMES`` images drawn from ``BENCH_SEED`` (generators/
camera_cycle.plan draws the same ones)."""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "generators"))

from camera_cycle import plan  # noqa: E402

import dora_tpu.node  # noqa: E402
from dora_tpu.node import Node  # noqa: E402

# The model node stops with this camera's last frame still in its queue and
# never hands it back; do not wait the library's 10 s for it on the way out.
dora_tpu.node.DROP_TOKEN_WAIT_S = 0.5


def main() -> None:
    size = int(os.environ["BENCH_IMAGE_SIZE"])
    frames = plan(
        {"frames": int(os.environ["BENCH_FRAMES"])}, int(os.environ["BENCH_SEED"]),
        0.0, {"as_run": {"image_size": size}},
    )["frames"]
    flat = [f.ravel() for f in frames]
    meta = {"width": size, "height": size, "encoding": "bgr8",
            "shape": [size, size, 3], "dtype": "uint8"}
    sent = 0
    with Node() as node:
        for event in node:
            if event["type"] == "STOP":
                break
            if event["type"] != "INPUT":
                continue
            node.send_output("image", flat[sent % len(flat)], meta)
            sent += 1
    print(json.dumps({"camera_frames_sent": sent}), flush=True)


if __name__ == "__main__":
    main()
