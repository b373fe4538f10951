"""``make_vlm`` as the program ships it, observed from the same process.

The frames tier has no capture hook, no compile report and no seed knob
of its own (all three are listed for the tracing issue in PERF.md), and
only the process that holds the chip can trace it. So the benchmark's
graph names this module as the runtime node's operator: it returns the
program's ``dora_tpu.nodehub.ops.make_vlm()`` unchanged, after

* pointing ``vlm.init_params`` at a key made from ``BENCH_WEIGHT_SEED``;
* starting a thread that logs, once a second, a ``dora_tpu.backend
  bench_tick: {...}`` line (monotonic time, XLA compiles so far, device
  memory), which the harness reads out of the node's log;
* in a traced run, starting ``jax.profiler`` when the harness touches
  ``BENCH_TRACE_FLAG`` and stopping it ``BENCH_TRACE_SECONDS`` later, on
  the node's main thread (a SIGUSR2 handler the watcher raises).
"""

from __future__ import annotations

import os
import threading
import time


_trace = {"started": None, "stopped": None}


def _toggle_trace(*_signal_args) -> None:
    """Start the capture, or stop it if it runs. Runs on the node's main
    thread (a signal handler, so between two of its steps), as
    ``llm_server`` runs its own captures: ``stop_trace`` called from the
    watcher's thread while the main thread kept dispatching never came
    back (PR 23, chip calls 2 and 3)."""
    import faulthandler
    import sys

    import jax

    from dora_tpu import backend

    trace_dir = os.environ["BENCH_TRACE_DIR"]
    now = time.monotonic()
    try:
        if _trace["started"] is None:
            _trace["started"] = now
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # device and runtime lines only
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            backend.report("bench_trace", {"start": now})
        elif _trace["stopped"] is None:
            _trace["stopped"] = now
            faulthandler.dump_traceback_later(20, file=sys.stderr)
            jax.profiler.stop_trace()
            faulthandler.cancel_dump_traceback_later()
            backend.report("bench_trace", {
                "start": _trace["started"], "stop": time.monotonic(), "dir": trace_dir,
            })
    except Exception as e:
        _trace["stopped"] = now
        backend.report("bench_trace", {"start": _trace["started"], "stop": now,
                                       "error": repr(e)})


def _watch() -> None:
    import signal

    from dora_tpu import backend, telemetry

    trace_dir = os.environ.get("BENCH_TRACE_DIR", "")
    flag = os.environ.get("BENCH_TRACE_FLAG", "")
    seconds = float(os.environ.get("BENCH_TRACE_SECONDS", "3"))
    asked = 0  # toggles asked for so far
    t_start = None
    while True:
        now = time.monotonic()
        if trace_dir and flag:
            if asked == 0 and os.path.exists(flag):
                asked, t_start = 1, now
                os.kill(os.getpid(), signal.SIGUSR2)
            elif asked == 1 and now - t_start >= seconds:
                asked = 2
                os.kill(os.getpid(), signal.SIGUSR2)
        backend.report("bench_tick", {
            "t": now, "compiles": telemetry.compile_count(),
            "memory": backend.memory_report(),
        })
        time.sleep(0.25 if trace_dir and _trace["stopped"] is None else 1.0)


def make_vlm():
    import jax

    from dora_tpu import telemetry
    from dora_tpu.models import vlm
    from dora_tpu.nodehub import ops

    telemetry.install_compile_listener()
    if os.environ.get("BENCH_TRACE_DIR"):
        import signal

        signal.signal(signal.SIGUSR2, _toggle_trace)  # make_vlm runs on the main thread
    seed = int(os.environ.get("BENCH_WEIGHT_SEED", "0")) % (2 ** 31 - 1)
    init = vlm.init_params
    vlm.init_params = lambda _key, cfg: init(jax.random.PRNGKey(seed), cfg)
    try:
        operator = ops.make_vlm()
    finally:
        vlm.init_params = init
    threading.Thread(target=_watch, daemon=True).start()
    return operator
