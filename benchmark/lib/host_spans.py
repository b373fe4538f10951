"""What the host was doing while the device sat idle.

``llm_server``'s loop and ``PagedBatchEngine`` enter each phase of a turn
as a ``jax.profiler.TraceAnnotation`` named ``loop.<phase>``
(``dora_tpu/telemetry.py``: ``LOOP_PHASES``), so a capture holds them on
its ``/host:CPU`` plane, on the clock of its ``/device:`` planes. Two
halves, as in ``trace_reduce``. :func:`load_spans` reads those events of
a ``*.xplane.pb`` into plain lists (``jax.profiler.ProfileData``: run it
in a child with ``JAX_PLATFORMS=cpu``, :func:`load_in_child`, so that the
harness never imports JAX). The rest is pure Python over those lists and
the device-operation lists of ``trace_reduce.load_events``, and is what
``benchmark/tests`` checks against a recorded fixture.

Spans: ``[[phase, start_ns, dur_ns], ...]`` with ``loop.`` cut off, all
of one thread and therefore nested: ``admit.can_admit`` lies inside
``admit``, ``first_token_wait`` inside ``chunk_launch``. The deepest span
that covers an instant owns it (a phase's self time); an instant under
no span is ``no_span``.

    python benchmark/lib/host_spans.py <capture dir or .pb> <out.json> [--dump <fixture.json> <from_s> <to_s>]
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
from pathlib import Path

import trace_reduce

PREFIX = "loop."
NO_SPAN = "no_span"
#: idle under these is the device's own: between operations of a program
#: the host is already waiting for; idle under any other phase is the host's
DEVICE_PHASES = ("window_wait", "first_token_wait")


def load_spans(path: str) -> list[list]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(trace_reduce.find_xplane(path))
    spans = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans += [
                [ev.name[len(PREFIX):], int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events if ev.name.startswith(PREFIX)
            ]
    return sorted(spans, key=lambda e: (e[1], -e[2]))


def load_in_child(capture: str, out: Path) -> list[list] | None:
    """The capture's spans, read by a child on the CPU; None where the
    capture cannot be read or holds no ``loop.*`` span."""
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).resolve()), capture, str(out)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    if proc.returncode != 0 or not out.exists():
        return None
    return json.loads(out.read_text())["spans"] or None


# ---------------------------------------------------------------------------
# pure reductions
# ---------------------------------------------------------------------------


def self_segments(spans: list) -> list[tuple[int, int, str]]:
    """Disjoint ``(start, end, phase)`` pieces in time order: each span
    less the spans nested in it."""
    out: list[tuple[int, int, str]] = []
    stack: list[tuple[str, int]] = []  # (phase, end), outermost first
    cursor = 0

    def close(upto: int) -> None:
        nonlocal cursor
        if stack and upto > cursor:
            out.append((cursor, upto, stack[-1][0]))
        cursor = max(cursor, upto)

    for phase, start, dur in sorted(spans, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            close(stack[-1][1])
            stack.pop()
        close(start)
        stack.append((phase, start + dur))
    while stack:
        close(stack[-1][1])
        stack.pop()
    return out


def idle_intervals(events: dict) -> list[tuple[int, int]]:
    """The stretches of the traced span in which no operation ran on
    the first device plane: what ``device_idle_pct`` counts."""
    planes = trace_reduce.device_planes(events)
    if not planes:
        return []
    ops = planes[sorted(planes)[0]][trace_reduce.OPS_LINE]
    lo, hi = events["span_ns"]
    out, end = [], lo
    for start, dur in sorted((s, d) for _, s, d in ops):
        if start > end:
            out.append((end, start))
        end = max(end, start + dur)
    if hi > end:
        out.append((end, hi))
    return out


def attribute(idle: list, spans: list, n: int = 10) -> dict:
    """Idle nanoseconds under each phase's self time and under no span,
    and the ``n`` longest idle stretches, each with the phase that owns
    most of it and its offset from the first stretch's start."""
    segs = self_segments(spans)
    by_phase: dict[str, int] = {}
    gaps = []
    k = 0
    for lo, hi in idle:
        while k < len(segs) and segs[k][1] <= lo:
            k += 1
        inside: dict[str, int] = {}
        covered, j = 0, k
        while j < len(segs) and segs[j][0] < hi:
            ns = min(hi, segs[j][1]) - max(lo, segs[j][0])
            if ns > 0:
                inside[segs[j][2]] = inside.get(segs[j][2], 0) + ns
                covered += ns
            j += 1
        if hi - lo > covered:
            inside[NO_SPAN] = hi - lo - covered
        for phase, ns in inside.items():
            by_phase[phase] = by_phase.get(phase, 0) + ns
        gaps.append((hi - lo, lo, max(inside, key=inside.get)))
    t0 = idle[0][0] if idle else 0
    total = sum(by_phase.values())
    return {
        "idle_ns": total,
        "by_phase": dict(sorted(by_phase.items(), key=lambda kv: -kv[1])),
        "gaps": [[phase, ns, at - t0] for ns, at, phase in sorted(gaps, reverse=True)[:n]],
        "attributed_pct": (
            100.0 * (total - by_phase.get(NO_SPAN, 0)) / total if total else None
        ),
    }


def launch_and_wait_margins(events: dict, spans: list, pattern: str) -> dict | None:
    """For every execution of a program matching ``pattern`` on the
    device: its start less the start of the ``window_launch`` span
    nearest to it, and the end of the ``window_wait`` span nearest to
    its end less that end. Both are positive where host and device
    planes share a clock (the host launches before the device starts
    and learns of the end after it); smallest and median of each."""
    launches = [s for p, s, _ in spans if p == "window_launch"]
    wait_ends = [s + d for p, s, d in spans if p == "window_wait"]
    programs = trace_reduce.module_events(events, pattern)
    if not (launches and wait_ends and programs):
        return None
    before = [start - min(launches, key=lambda t: abs(t - start))
              for start, _ in programs]
    after = [min(wait_ends, key=lambda t: abs(t - (start + dur))) - (start + dur)
             for start, dur in programs]
    return {
        "programs": len(programs),
        "launch_before_start_ns": [min(before), statistics.median(before)],
        "wait_end_after_end_ns": [min(after), statistics.median(after)],
    }


def cut(spans: list, lo: int, hi: int) -> list:
    return [s for s in spans if lo <= s[1] < hi]


def main(argv: list[str]) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    spans = load_spans(argv[0])
    json.dump({"spans": spans}, open(argv[1], "w"))
    if "--dump" in argv:
        k = argv.index("--dump")
        events = trace_reduce.load_events(argv[0])
        t0 = events["span_ns"][0]
        lo, hi = t0 + int(float(argv[k + 2]) * 1e9), t0 + int(float(argv[k + 3]) * 1e9)
        piece = trace_reduce.cut(events, float(argv[k + 2]), float(argv[k + 3]))
        piece["host_spans"] = cut(spans, lo, hi)
        json.dump(piece, open(argv[k + 1], "w"))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
