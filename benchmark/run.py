#!/usr/bin/env python3
"""One run of one benchmark cell.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds everything by name: the cell in ``BENCHMARK.json``, its
configuration in ``benchmark/configs/``, its traffic mix in
``benchmark/traffic/``, the graph kind and the generator those two name
in ``benchmark/graphs/`` and ``benchmark/generators/``, and each
per-layer metric's reader through ``benchmark/layer_metrics/``. This
process never imports JAX: the dataflow's model node is the one process
that holds the chip; the reference and the trace reduction are children
that run after it has exited. The last stdout line is the result.
``--tiny`` (with ``JAX_PLATFORMS=cpu``) rehearses the control flow at toy
sizes and always exits non-zero: no node said ``tpu``.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib.util
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path
from types import SimpleNamespace

T_START = time.monotonic()
HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE / "lib"))

import node_reports as nr  # noqa: E402

#: seconds a run may take before its process group is killed: a node that
#: hangs at exit is a failed run, not a hung machine. The first run in a
#: checkout compiles and gets the longer one.
DEADLINE_WARM_S, DEADLINE_COLD_S = 340.0, 1150.0
TRACE_SECONDS = 3.0


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def load_module(kind: str, name: str):
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def deep_update(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = deep_update(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def load_config(path: Path, tiny: bool) -> dict:
    """Published keys sit at the file's top level; what the benchmark adds
    (graph kind, node env, cuts, assumptions) under ``bench``."""
    raw = json.loads(path.read_text())
    bench = raw.pop("bench")
    cfg = {"model": raw, **bench}
    if tiny:
        cfg = deep_update(cfg, bench["tiny"])
    return cfg


def load_cell(name: str, tiny: bool):
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = load_config(ROOT / entry["file"], tiny)
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    if tiny:
        traffic = deep_update(traffic, traffic.get("tiny", {}))
    return manifest, cell, config, traffic


def free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def watchdog(seconds: float) -> None:
    def fire() -> None:
        log(f"benchmark: passed its hard deadline of {seconds:.0f}s; killing the process group")
        os.killpg(0, signal.SIGKILL)

    t = threading.Timer(seconds, fire)
    t.daemon = True
    t.start()


# ---------------------------------------------------------------------------
# the dataflow, under an in-process daemon
# ---------------------------------------------------------------------------


async def run_dataflow(ctx, graph, generator) -> dict:
    import yaml

    from dora_tpu.core.descriptor import Descriptor
    from dora_tpu.daemon import Daemon

    nodes = graph.build(ctx)["nodes"]
    if hasattr(generator, "nodes"):
        nodes = nodes + generator.nodes(ctx)
    path = ctx.workdir / "dataflow.yml"
    path.write_text(yaml.safe_dump({"nodes": nodes}))
    descriptor = Descriptor.read(path)
    descriptor.check(ctx.workdir)
    daemon = Daemon(local_comm=descriptor.communication.local.kind)
    profile: dict = {}
    daemon.profile_sink = lambda _df, _node, artifact, error: profile.update(
        artifact=artifact, error=error
    )
    await daemon.start()
    run: dict = {"profile": profile, "compiles": {}}
    load = None
    try:
        df = await daemon.spawn_dataflow(
            descriptor, working_dir=ctx.workdir,
            local_nodes={str(n.id) for n in descriptor.nodes},
        )

        def reports() -> dict:
            return nr.node_reports(ctx.workdir, graph.MODEL_NODE)

        def serving() -> dict:
            return dict(df.node_serving.get(graph.MODEL_NODE) or {})

        while not graph.ready(ctx, reports()):
            if df.done.done():
                raise RuntimeError(
                    f"dataflow ended before the model node was ready: {df.done.result().errors()}"
                )
            await asyncio.sleep(0.2)
        run["ready_s"] = time.monotonic() - T_START
        log(f"benchmark: model node ready at {run['ready_s']:.1f}s")

        tasks = []  # the loop holds tasks weakly

        async def start_trace(at: float) -> None:
            await asyncio.sleep(max(0.0, at - time.monotonic()))
            if graph.PROFILE_BY == "daemon":
                daemon.profile_node(df, graph.MODEL_NODE, "start", ctx.trace_seconds)
                # The capture is written at the node's next report, and
                # writing it holds the server's loop for seconds (9-12 s at
                # Qwen2.5-1.5B) inside one dispatch gap: the profiler's time,
                # not the program's. A third snapshot, two reports after the
                # capture was written, lets a reader of the serving
                # histograms start behind it.
                while not profile and time.monotonic() < at + 75.0:
                    await asyncio.sleep(0.1)
                await asyncio.sleep(2.5)
                run["serving_traced"] = serving()
            else:
                (ctx.workdir / "trace.go").touch()

        if generator.KIND == "process":
            ctx_file = ctx.workdir / "load_ctx.json"
            ctx_file.write_text(json.dumps({
                "traffic": ctx.traffic, "seed": ctx.traffic_seed,
                "seconds": ctx.seconds, "config": {"model": ctx.config["model"]},
                "port": ctx.port, "timeout_s": ctx.config["request_timeout_s"],
                "result": str(ctx.workdir / "load_result.json"),
            }))
            load = await asyncio.create_subprocess_exec(
                sys.executable, str(HERE / "generators" / f"{ctx.traffic['generator']}.py"),
                str(ctx_file), stdout=asyncio.subprocess.PIPE,
            )
            while True:
                line = await load.stdout.readline()
                if not line:
                    break
                event = json.loads(line)
                run.setdefault("timeline_s", {})[event["event"]] = event["t"] - T_START
                if event["event"] == "window_start":
                    run["t0"] = event["t0"]
                    run["serving_before"] = serving()
                    run["compiles"]["before"] = run["serving_before"].get("compiles")
                    if ctx.trace:
                        tasks.append(asyncio.create_task(start_trace(event["t0"] + 1.0)))
                elif event["event"] == "window_end":
                    run["t1"] = event["t1"]
                    await asyncio.sleep(1.3)  # the node reports once a second
                    run["serving_after"] = serving()
                    run["compiles"]["after"] = run["serving_after"].get("compiles")
            if await load.wait() != 0:
                raise RuntimeError(f"load process exited {load.returncode}")
        else:
            first = ctx.workdir / "sink.json"
            while not first.exists():
                if df.done.done():
                    raise RuntimeError("dataflow ended before the sink saw an output")
                await asyncio.sleep(0.1)
            await asyncio.sleep(ctx.traffic["warm_s"])
            run["t0"] = time.monotonic()
            if ctx.trace:
                tasks.append(asyncio.create_task(start_trace(run["t0"] + 1.0)))
            await asyncio.sleep(ctx.seconds)
            run["t1"] = time.monotonic()
            await asyncio.sleep(1.3)  # the operator's watcher reports once a second
            for _ in range(300 if ctx.trace else 0):  # until the capture is written
                marks = reports().get("bench_trace", [])
                if marks and "stop" in marks[-1]:
                    await asyncio.sleep(1.1)
                    break
                await asyncio.sleep(0.1)
        for task in tasks:
            await task
        daemon.stop_dataflow(df, grace_s=10.0)
        try:
            result = await asyncio.wait_for(asyncio.shield(df.done), 25.0)
            run["dataflow_errors"] = None if result.is_ok() else str(result.errors())
            # A node the daemon had to kill 10 s after the stop (llm_server is
            # sometimes that slow to exit; PR 21 saw it hang) did its work
            # before: noted, not a wrong result. Any other node error is one.
            run["node_failed"] = any(
                getattr(getattr(err, "cause", None), "kind", None) != "grace_duration"
                for _node, err in ([] if result.is_ok() else result.errors())
            )
        except asyncio.TimeoutError:
            run["dataflow_errors"] = "a node outlived the stop by 25 s and was killed"
            run["node_failed"] = False
        run["reports"] = reports()
        if hasattr(graph, "compiles"):
            run["compiles"] = graph.compiles(run)
    finally:
        if load is not None and load.returncode is None:
            load.kill()
            await load.wait()
        await daemon.close()
    return run


# ---------------------------------------------------------------------------
# after the dataflow: trace reduction, per-layer readers
# ---------------------------------------------------------------------------


def reduce_trace(ctx, run: dict) -> dict | None:
    capture = run["profile"].get("artifact") or str(ctx.workdir / "profile")
    if run["profile"].get("error"):
        log(f"benchmark: the node's capture failed: {run['profile']['error']}")
    out = ctx.workdir / "trace_events.json"
    argv = [sys.executable, str(HERE / "lib" / "trace_reduce.py"), capture, str(out),
            "--keep-events"]
    keep = os.environ.get("BENCH_KEEP_TRACE")  # a builder's own look at a trace
    if keep:
        argv += ["--dump", str(Path(keep) / f"{ctx.cell['name']}.events.json"), "1.0", "1.25"]
    proc = subprocess.run(argv, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    if proc.returncode != 0 or not out.exists():
        log("benchmark: no trace could be reduced;", run["reports"].get("bench_trace"))
        for path in sorted(ctx.workdir.glob("out/*/log_*.txt")):
            log(f"--- {path.name}", *path.read_text(errors="replace").splitlines()[-40:])
        return None
    traced = json.loads(out.read_text())
    if keep:
        (Path(keep) / f"{ctx.cell['name']}.reduced.json").write_text(
            json.dumps(traced["reduced"], indent=1)
        )
    return traced


def layer_metrics(ctx, manifest: dict, run: dict) -> dict:
    out = {}
    for entry in manifest["per_layer"]:
        if "workloads" in entry and ctx.cell["name"] not in entry["workloads"]:
            continue
        spec = json.loads((HERE / "layer_metrics" / f"{entry['name']}.json").read_text())
        value = load_module("readers", spec["reader"]).read(run, spec.get("args", {}))
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes for the CPU rehearsal (never a result)")
    args = ap.parse_args()

    if not (ROOT / "dora_tpu").is_dir():
        log("benchmark: the system under test (dora_tpu/) is not in this directory")
        return 2
    manifest, cell, config, traffic = load_cell(args.workload, args.tiny)
    seconds = args.seconds if args.seconds is not None else float(manifest["run_seconds"])

    try:
        os.setpgrp()
    except OSError:
        pass  # already a group leader
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache and not args.tiny:
        # a fixed directory inside the checkout: the path is part of the key
        cache = os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    cold = not (cache and os.path.isdir(cache) and os.listdir(cache))
    watchdog(DEADLINE_COLD_S if cold else DEADLINE_WARM_S)

    os.environ["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT)] + [p for p in [os.environ.get("PYTHONPATH")] if p]
    )
    sys.path.insert(0, str(ROOT))
    from dora_tpu.native import build_native

    build_native()  # g++ once, here, so that no node does it mid-run

    workdir = Path(tempfile.mkdtemp(prefix="dora-bench-"))  # under TMPDIR
    ctx = SimpleNamespace(
        root=ROOT, workdir=workdir, cell=cell, config=config, traffic=traffic,
        seed=args.seed, traffic_seed=args.seed + 1, seconds=seconds,
        trace=bool(args.trace),
        trace_seconds=min(traffic.get("trace_seconds", TRACE_SECONDS), seconds / 2),
        tiny=args.tiny, port=free_port(), notes={},
    )
    graph = load_module("graphs", config["graph"])
    generator = load_module("generators", traffic["generator"])
    status = 1
    try:
        run = asyncio.run(run_dataflow(ctx, graph, generator))
        run.update(config=config, traffic=traffic, workdir=workdir, ctx=ctx)
        reports = run["reports"]
        device = (reports.get("device") or [{}])[-1]
        on_chip = device.get("platform") == "tpu"
        measured = generator.measure(ctx, run)
        for line in measured.get("lines", []):
            print(json.dumps(line), flush=True)
        setup_s = run["t0"] - T_START
        print(json.dumps({"notes": {
            **ctx.notes, "ready_s": run["ready_s"], "setup_s": setup_s,
            "timeline_s": run.get("timeline_s"), "total_s": time.monotonic() - T_START,
            "dataflow_errors": run["dataflow_errors"], "cold_cache": cold,
            "device_said": device,
        }}), flush=True)
        result = {
            "correct": bool(measured["correct"]) and not run["node_failed"],
            "attempted": measured["attempted"], "failed": measured["failed"],
            "device": {
                "platform": device.get("platform"), "kind": device.get("kind"),
                "count": device.get("count"),
                "memory_peak_bytes": graph.memory_peak_bytes(run),
            },
        }
        if ctx.trace:
            traced = reduce_trace(ctx, run)
            run["events"] = traced["events"] if traced else None
            run["reduced"] = traced["reduced"] if traced else None
            peaks = json.loads((HERE / "lib" / "peaks.json").read_text())
            run["peaks"] = peaks.get(device.get("kind"))
            if on_chip and run["peaks"] is None:
                raise RuntimeError(f"no peaks for device kind {device.get('kind')!r}")
            result["metrics"] = layer_metrics(ctx, manifest, run)
            if traced:
                result["device"]["busy_s"] = traced["reduced"]["busy_s"]
                result["device"]["window_s"] = traced["reduced"]["window_s"]
                result["breakdown"] = traced["reduced"]["breakdown"]
                print(json.dumps({"programs_on_device": traced["reduced"]["modules"]}),
                      flush=True)
        else:
            names = {m["name"] for m in manifest["end_to_end"]}
            result["metrics"] = {
                k: v for k, v in measured["metrics"].items() if k in names
            }
            result["metrics"]["setup_s"] = {"value": setup_s, "unit": "s"}
        # every number ``correct`` rests on beside its limit: the last lines
        # of stderr, and the last key of the result
        compared = measured.get("compared") or {}
        for name, c in compared.items():
            log(f"compared {name}: {c['value']} {c['rule']} {c['limit']}"
                f"{'' if c['holds'] else '  FAILS'}")
        if not on_chip:
            # a rehearsal: counts and names only, never a device number
            print(json.dumps({
                "correct": False, "rehearsal": True,
                "reason": f"the model node said {device.get('platform')!r}, not 'tpu'",
                "checks_passed": result["correct"],
                "attempted": result["attempted"], "failed": result["failed"],
                "metric_names": sorted(result["metrics"]), "compared": compared,
            }), flush=True)
            return 1
        result["compared"] = compared
        print(json.dumps(result), flush=True)
        status = 0
    except Exception as e:
        log(f"benchmark: run failed: {e!r}")
        for path in sorted(workdir.glob("out/*/log_*.txt")):
            tail = path.read_text(errors="replace").splitlines()[-25:]
            log(f"--- {path.name}", *tail)
    finally:
        if os.environ.get("BENCH_KEEP_WORKDIR"):  # a builder's own look
            log(f"benchmark: kept {workdir}")
        else:
            shutil.rmtree(workdir, ignore_errors=True)
    return status


if __name__ == "__main__":
    sys.exit(main())
