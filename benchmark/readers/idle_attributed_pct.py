"""Share of the device's idle time that lies under one of the host's
``loop.*`` spans (``lib/host_spans.py``): over the first device plane and
the span that ``device_idle_pct.serve`` reads, 100 x idle time under some
span / idle time. Before the result it prints a line of its own:
``idle_by_phase`` (seconds of idle under each phase's self time and under
``no_span``; idle under ``window_wait`` or ``first_token_wait`` is the
device's own, between operations of a program the host already waits for,
idle under any other phase is the host's), ``idle_gaps`` (the ten longest
idle stretches: the phase that owns most of each, seconds, offset in
seconds) and ``launch_wait_margins`` (``args["program"]``'s executions on
the device against the ``window_launch`` and ``window_wait`` spans around
them: both margins positive where the planes share a clock). None where
there is no capture, no device operation, or no ``loop.*`` span in it (a
server older than the phases)."""
import json
from pathlib import Path

import host_spans


def read(run: dict, args: dict):
    events = run.get("events")
    idle = host_spans.idle_intervals(events) if events else []
    if not idle:
        return None
    capture = (run.get("profile") or {}).get("artifact") or str(
        Path(run["workdir"]) / "profile")
    spans = host_spans.load_in_child(capture, Path(run["workdir"]) / "host_spans.json")
    if not spans:
        return None
    found = host_spans.attribute(idle, spans)
    print(json.dumps({
        "idle_by_phase": {p: ns / 1e9 for p, ns in found["by_phase"].items()},
        "idle_gaps": [[p, ns / 1e9, at / 1e9] for p, ns, at in found["gaps"]],
        "launch_wait_margins": host_spans.launch_and_wait_margins(
            events, spans, args["program"]),
    }), flush=True)
    return found["attributed_pct"]
