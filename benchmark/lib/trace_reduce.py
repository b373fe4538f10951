"""From a profiler trace to the numbers the per-layer readers take.

Two halves. :func:`load_events` turns a ``*.xplane.pb`` into plain lists
(``jax.profiler.ProfileData``; run it in a child with ``JAX_PLATFORMS=cpu``
so that the harness itself never imports JAX). Everything else is pure
Python over those lists and is what ``benchmark/tests`` checks against a
recorded fixture with hand-computed values.

Event lists: ``{"planes": {plane: {line: [[name, start_ns, dur_ns], ...]}}}``.
A device plane is one whose name starts with ``/device:``; its ``XLA
Ops`` line holds one event per executed operation (nested: a ``while``
holds the operations of its body), its ``XLA Modules`` line one per
executed program. The traced window is the span of the device planes'
own events, first start to last end: the stretch in which the device
was being recorded (the host planes also cover the seconds the profiler
takes to start and to write itself out, in which the serving loop
stands still). Operation names arrive as whole HLO instructions and are
cut to ``<name> <result shape>`` on loading.

    python benchmark/lib/trace_reduce.py <capture dir or .pb> <out.json> [--dump <events.json> <from_s> <to_s>]
"""

from __future__ import annotations

import json
import os
import re
import statistics
import sys
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(path: str) -> str:
    p = Path(path)
    if p.is_file():
        return str(p)
    found = sorted(p.rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    return str(found[-1])


def short_name(name: str) -> str:
    """``%pad.45 = s8[1536,153600]{1,0:T(8,128)} pad(...)`` -> ``pad.45
    s8[1536,153600]``; a program's name (no `` = ``) stays as it is."""
    lhs, eq, rhs = name.partition(" = ")
    if not eq:
        return name
    shape = "" if rhs.startswith("(") else rhs.split("{")[0].split(" ")[0]
    return f"{lhs.lstrip('%')} {shape}".strip()


def load_events(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    planes: dict = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines: dict = {}
        for line in plane.lines:
            events = [
                [short_name(ev.name), int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events
            ]
            if events:
                lines.setdefault(line.name, []).extend(events)
        if lines:
            planes[plane.name] = lines
    return {"planes": planes, "span_ns": device_span(planes)}


def device_span(planes: dict) -> list:
    starts = [e[1] for lines in planes.values() for evs in lines.values() for e in evs]
    ends = [e[1] + e[2] for lines in planes.values() for evs in lines.values() for e in evs]
    return [min(starts), max(ends)] if starts else [0, 0]


# ---------------------------------------------------------------------------
# pure reductions
# ---------------------------------------------------------------------------


def union_ns(intervals) -> int:
    """Total length of the union of [start, start+dur) intervals."""
    total, end = 0, None
    for start, dur in sorted(intervals):
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def device_planes(events: dict) -> dict:
    return {
        name: lines for name, lines in events["planes"].items()
        if name.startswith("/device:") and OPS_LINE in lines
    }


def busy_and_window_s(events: dict) -> tuple[float, float]:
    """Seconds in which an operation ran on the device (union over the
    ops line, averaged over device planes) and the traced span."""
    planes = device_planes(events)
    if not planes:
        return 0.0, 0.0
    busy = [
        union_ns((s, d) for _, s, d in lines[OPS_LINE]) for lines in planes.values()
    ]
    span = events["span_ns"]
    return sum(busy) / len(busy) / 1e9, (span[1] - span[0]) / 1e9


def idle_pct(events: dict) -> float | None:
    busy, window = busy_and_window_s(events)
    return None if window <= 0 else 100.0 * (1.0 - busy / window)


def module_events(events: dict, pattern: str) -> list[tuple[int, int]]:
    """(start, dur) of every program execution whose name matches."""
    rx = re.compile(pattern)
    out = []
    for lines in device_planes(events).values():
        out += [(s, d) for n, s, d in lines.get(MODULES_LINE, []) if rx.search(n)]
    return sorted(out)


def module_median_ms(events: dict, pattern: str) -> float | None:
    found = module_events(events, pattern)
    if not found:
        return None
    return statistics.median(d for _, d in found) / 1e6


def module_names(events: dict) -> dict[str, list]:
    """name -> [count, total seconds] of every program on the device."""
    out: dict[str, list] = {}
    for lines in device_planes(events).values():
        for n, _, d in lines.get(MODULES_LINE, []):
            row = out.setdefault(n, [0, 0.0])
            row[0] += 1
            row[1] += d / 1e9
    return out


def ops_busy_inside_ms(events: dict, pattern: str) -> float | None:
    """Median over matching program executions of the device-op time
    (union of ops-line events) that falls inside the execution."""
    per = []
    for lines in device_planes(events).values():
        ops = sorted((s, d) for _, s, d in lines[OPS_LINE])
        rx = re.compile(pattern)
        mods = sorted((s, d) for n, s, d in lines.get(MODULES_LINE, []) if rx.search(n))
        k = 0
        for ms, md in mods:
            while k < len(ops) and ops[k][0] + ops[k][1] <= ms:
                k += 1
            inside, j = [], k
            while j < len(ops) and ops[j][0] < ms + md:
                s, d = ops[j]
                lo, hi = max(s, ms), min(s + d, ms + md)
                if hi > lo:
                    inside.append((lo, hi - lo))
                j += 1
            per.append(union_ns(inside))
    return statistics.median(per) / 1e6 if per else None


def self_times(ops: list) -> list[tuple[str, int]]:
    """(name, self ns) of nested events: an event's duration less that of
    the events directly inside it (a ``while`` less its body)."""
    evs = sorted(ops, key=lambda e: (e[1], -e[2]))
    own = [e[2] for e in evs]
    stack: list[tuple[int, int]] = []  # (end, index)
    for i, (_, start, dur) in enumerate(evs):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= min(dur, stack[-1][0] - start)
        stack.append((start + dur, i))
    return [(e[0], ns) for e, ns in zip(evs, own)]


def op_family(name: str) -> str:
    """``attention_paged_batch_step.24 ...`` -> ``attention_paged_batch_step``:
    one row for the 28 per-layer copies of an operation."""
    return re.sub(r"\.\d+$", "", name.split(" ")[0])


def top_ops(events: dict, n: int = 10) -> list[list]:
    """The n operation families with most self time on the device,
    seconds averaged over device planes."""
    total: dict[str, int] = {}
    planes = device_planes(events)
    for lines in planes.values():
        for name, ns in self_times(lines[OPS_LINE]):
            key = op_family(name)
            total[key] = total.get(key, 0) + ns
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9 / max(1, len(planes))] for name, ns in ranked]


def idle_gaps(events: dict, n: int = 10) -> list[list]:
    """The n longest gaps between device operations on the first device
    plane, named by their offset into the trace (host attribution needs
    spans the program does not write yet)."""
    planes = device_planes(events)
    if not planes:
        return []
    lines = planes[sorted(planes)[0]]
    t0 = events["span_ns"][0]
    gaps, end = [], None
    for start, dur in sorted((s, d) for _, s, d in lines[OPS_LINE]):
        if end is not None and start > end:
            gaps.append((start - end, end))
        end = max(end or 0, start + dur)
    gaps.sort(reverse=True)
    return [
        [f"not attributed, at +{(at - t0) / 1e9:.4f}s", ns / 1e9]
        for ns, at in gaps[:n]
    ]


def reduce(events: dict) -> dict:
    busy, window = busy_and_window_s(events)
    return {
        "busy_s": busy, "window_s": window,
        "modules": module_names(events),
        "breakdown": {"device_ops": top_ops(events), "idle_gaps": idle_gaps(events)},
        "planes": {
            name: {line: len(evs) for line, evs in lines.items()}
            for name, lines in events["planes"].items()
        },
    }


def cut(events: dict, lo_s: float, hi_s: float) -> dict:
    """The events that start inside [lo_s, hi_s) of the trace, for a
    fixture small enough to commit."""
    t0 = events["span_ns"][0]
    lo, hi = t0 + int(lo_s * 1e9), t0 + int(hi_s * 1e9)
    planes = {}
    for name, lines in events["planes"].items():
        kept = {
            line: [e for e in evs if lo <= e[1] < hi] for line, evs in lines.items()
        }
        kept = {k: v for k, v in kept.items() if v}
        if kept:
            planes[name] = kept
    return {"planes": planes, "span_ns": device_span(planes)}


def main(argv: list[str]) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    events = load_events(argv[0])
    out = {"events": events} if "--keep-events" in argv else {}
    out["reduced"] = reduce(events)
    json.dump(out, open(argv[1], "w"))
    if "--dump" in argv:
        k = argv.index("--dump")
        json.dump(cut(events, float(argv[k + 2]), float(argv[k + 3])),
                  open(argv[k + 1], "w"))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
