"""camera -> make_vlm -> sink: the frames pipeline.

The one JAX process is ``vlm``, a runtime node whose operator is
``benchmark.nodes.vlm_operator:make_vlm``: the program's own ``make_vlm``
untouched, with the weights' seed set from ``--seed``, a once-a-second
report of compiles and device memory, and (traced runs) a profiler
capture on a timer, because ``make_vlm`` has no capture hook of its own.
Camera and sink are the generator's nodes.
"""

from __future__ import annotations

MODEL_NODE = "vlm"
PROFILE_BY = "operator"


def build(ctx) -> dict:
    env = {k: str(v) for k, v in ctx.config["node_env"]["vlm"].items()}
    env["BENCH_WEIGHT_SEED"] = str(ctx.seed)
    env["BENCH_TRACE_DIR"] = str(ctx.workdir / "profile") if ctx.trace else ""
    env["BENCH_TRACE_FLAG"] = str(ctx.workdir / "trace.go")
    env["BENCH_TRACE_SECONDS"] = str(ctx.trace_seconds)
    return {"nodes": [{
        "id": "vlm",
        "operator": {
            "jax": "benchmark.nodes.vlm_operator:make_vlm",
            "inputs": {"image": {"source": "camera/image", "queue_size": 1}},
            "outputs": ["tokens"],
        },
        "env": env,
    }]}


def ready(ctx, reports: dict) -> bool:
    """The first tick has run (and compiled, or found its program)."""
    return "device" in reports and "first_tick" in reports


def memory_peak_bytes(run: dict) -> int | None:
    rows = run["reports"].get("bench_tick") or run["reports"].get("first_tick") or [{}]
    peaks = [m.get("peak_bytes_in_use") or 0 for m in rows[-1].get("memory", [])]
    return max(peaks, default=0) or None


def compiles(run: dict) -> dict:
    """XLA compiles the operator's watcher had counted by the last report
    before the window and by the first after it (once a second: a compile
    between a report and the window's edge counts as inside)."""
    ticks = run["reports"].get("bench_tick", [])
    before = [t["compiles"] for t in ticks if t["t"] <= run["t0"]]
    after = [t["compiles"] for t in ticks if t["t"] >= run["t1"]]
    return {"before": before[-1] if before else None,
            "after": after[0] if after else None}
