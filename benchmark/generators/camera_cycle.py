"""A camera faster than the model: a benchmark-owned camera node on a
daemon timer cycles ``frames`` seeded images into the model node
(``queue_size 1``: the latest wins), and a sink stamps every output.
No load process: the harness opens the window on its own clock once the
model node has served its first tick."""

from __future__ import annotations

import sys
from pathlib import Path

KIND = "nodes"  # nodes inside the dataflow; the harness times the window
NODES = Path(__file__).resolve().parent.parent / "nodes"


def plan(traffic: dict, seed: int, seconds: float, config: dict) -> dict:
    """The seeded frames, as the camera node draws them."""
    import numpy as np

    size = config["as_run"]["image_size"]
    rng = np.random.default_rng(seed)
    return {"frames": [
        rng.integers(0, 256, size=(size, size, 3), dtype=np.uint8)
        for _ in range(traffic["frames"])
    ]}


def nodes(ctx) -> list[dict]:
    t = ctx.traffic
    return [
        {
            "id": "camera", "path": str(NODES / "camera_node.py"),
            "inputs": {"tick": f"dora/timer/millis/{t['tick_ms']}"},
            "outputs": ["image"],
            "env": {
                "BENCH_SEED": str(ctx.seed), "BENCH_FRAMES": str(t["frames"]),
                "BENCH_IMAGE_SIZE": str(ctx.config["as_run"]["image_size"]),
            },
        },
        {
            "id": "sink", "path": str(NODES / "sink_node.py"),
            "inputs": {"tokens": "vlm/op/tokens"},
            "env": {"BENCH_SINK_OUT": str(ctx.workdir / "sink.json")},
        },
    ]


def measure(ctx, run: dict) -> dict:
    """frames_per_s over the window, and ``correct``: every output has the
    configured number of tokens, all in the vocabulary, and the run saw no
    more distinct outputs than the camera has frames (the same frame
    always gives the same tokens; outputs carry no frame id yet)."""
    import json

    sys.path.insert(0, str(NODES.parent / "lib"))
    import stats

    got = json.loads((ctx.workdir / "sink.json").read_text())
    t0, t1 = run["t0"], run["t1"]
    inside = [(s, t) for s, t in zip(got["stamps"], got["tokens"]) if t0 <= s < t1]
    want = int(ctx.config["node_env"]["vlm"]["DORA_MAX_NEW_TOKENS"])
    vocab = ctx.config["as_run"]["vocab"]
    bad = [t for _, t in inside if len(t) != want or not all(0 <= x < vocab for x in t)]
    distinct = {tuple(t) for _, t in inside}
    gaps = stats.gaps_ms(got["stamps"], t0, t1)
    run["gaps_ms"] = gaps
    lines = [{"window": {
        "seconds": t1 - t0, "outputs": len(inside), "outputs_before_window":
        sum(s < t0 for s in got["stamps"]), "distinct_outputs": len(distinct),
        "gap_p50_ms": stats.median(gaps) if gaps else None,
        "gap_p95_ms": stats.percentile(gaps, 95) if gaps else None,
    }}]
    return {
        "metrics": {"frames_per_s": {"value": len(inside) / (t1 - t0), "unit": "frames/s"}},
        "attempted": len(inside), "failed": len(bad),
        "correct": bool(inside) and not bad and len(distinct) <= ctx.traffic["frames"],
        "lines": lines,
        "compared": {
            "outputs": stats.compared(len(inside), 1, at_most=False),
            "bad_outputs": stats.compared(len(bad), 0),
            "distinct_outputs": stats.compared(len(distinct), ctx.traffic["frames"]),
        },
    }


if __name__ == "__main__":
    sys.exit("camera_cycle has no load process: its nodes run in the dataflow")
