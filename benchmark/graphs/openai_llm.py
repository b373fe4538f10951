"""driver (outside) -> openai_server -> llm_server: the serving endpoint.

The one JAX process is ``llm``. The checkpoint is written from the seed
into the work directory (never the checkout), in the format the server
loads. The traced run's capture is taken through the daemon's own
control (``Daemon.profile_node`` -> ``llm_server.handle_profile``), with
``DORA_PROFILE_DIR`` set on the node.
"""

from __future__ import annotations

from checkpoint import write_checkpoint  # benchmark/lib, on run.py's path

MODEL_NODE = "llm"
#: A dataflow input holds 10 events by default and drops the oldest beyond
#: that: a burst of 16 requests then loses some for good (chip call 2, run
#: 3: a warm-wave request never answered). A serving deployment sizes its
#: request and token queues; this is the graph's, not the program's, to set.
QUEUE = 4096
PROFILE_BY = "daemon"


def build(ctx) -> dict:
    ckpt = ctx.workdir / "checkpoint"
    ctx.notes["checkpoint"] = write_checkpoint(ckpt, ctx.config["model"], ctx.seed)
    env = {k: str(v) for k, v in ctx.config["node_env"]["llm"].items()}
    env["DORA_HF_CHECKPOINT"] = str(ckpt)
    env["DORA_PROFILE_DIR"] = str(ctx.workdir / "profile")
    api_env = {k: str(v) for k, v in ctx.config["node_env"]["api"].items()}
    api_env["PORT"] = str(ctx.port)
    return {"nodes": [
        {
            "id": "api", "path": "module:dora_tpu.nodehub.openai_server",
            "outputs": ["text"],
            "inputs": {"response": {"source": "llm/response", "queue_size": QUEUE}},
            "env": api_env,
        },
        {
            "id": "llm", "path": "module:dora_tpu.nodehub.llm_server",
            "inputs": {"text": {"source": "api/text", "queue_size": QUEUE}},
            "outputs": ["response"],
            "env": env,
        },
    ]}


def ready(ctx, reports: dict) -> bool:
    """The model node has said where it runs and has built its engine."""
    return "device" in reports and "engine_built" in reports


def memory_peak_bytes(run: dict) -> int | None:
    """Peak bytes in use on the device: the server's own gauge after the
    window, else what it said when the engine was built."""
    peak = (run.get("serving_after") or {}).get("hbm_peak_bytes")
    built = run["reports"].get("engine_built") or [{}]
    peaks = [m.get("peak_bytes_in_use") or 0 for m in built[-1].get("memory", [])]
    return max([peak or 0] + peaks) or None
