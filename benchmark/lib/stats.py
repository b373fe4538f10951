"""Percentile, window and lateness arithmetic: the yardstick's own.

Everything here is pure Python over timestamps taken by the load
generator (``time.monotonic()``, one clock for every process of a host).
"""

from __future__ import annotations

import math
import statistics


#: the synthetic tokenizer's fixed code width (lib/checkpoint.py)
CHARS_PER_TOKEN = 3


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the sample at or below it. No interpolation, so a tail is
    always a latency some request really had."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def median(values) -> float:
    return statistics.median(values)


def in_window(t: float | None, t0: float, t1: float) -> bool:
    return t is not None and t0 <= t < t1


def chat_metrics(requests: list[dict], t0: float, t1: float) -> dict:
    """End-to-end numbers of one chat window.

    ``requests`` carry ``due`` (when the request was due to be sent),
    ``first``/``last`` (first and last content delta at the client),
    ``deltas`` ([arrival, characters] of every content delta, three
    characters a token), ``done``, ``tokens`` (emitted ids),
    ``max_tokens`` and ``error``.

    * ``tokens_per_s``: output tokens that reached a client inside the
      window, each counted when its delta arrived, over the window; of
      every request that ended well, whenever it was sent or finished.
      (Counting whole requests at their completion measures the same
      rate with the 16 requests in flight at either edge as noise.)
    * latencies: over the requests *due* inside the window. One that
      failed, was refused, or did not finish with all its tokens counts
      in ``failed`` and misses every latency.
    * ``ttft``: first delta minus the due time. ``tpot``: (last delta -
      first delta) / (tokens - 1), a per-request mean because deltas
      arrive a decode window at a time.
    """
    seconds = t1 - t0
    delivered_tokens = sum(
        chars for r in requests if ok(r)
        for at, chars in r["deltas"] if t0 <= at < t1
    ) / CHARS_PER_TOKEN
    completed = sum(1 for r in requests if ok(r) and in_window(r["done"], t0, t1))
    due = [r for r in requests if in_window(r["due"], t0, t1)]
    good = [r for r in due if ok(r)]
    ttft = [(r["first"] - r["due"]) * 1e3 for r in good]
    tpot = [
        (r["last"] - r["first"]) * 1e3 / (len(r["tokens"]) - 1)
        for r in good if len(r["tokens"]) > 1
    ]
    out = {
        "attempted": len(due), "failed": len(due) - len(good),
        "completed_in_window": completed,
        "tokens_per_s": delivered_tokens / seconds,
        "requests_per_s": completed / seconds,
    }
    if ttft:
        out.update(
            ttft_p95_ms=percentile(ttft, 95), ttft_p50_ms=median(ttft),
        )
    if tpot:
        out.update(
            tpot_p95_ms=percentile(tpot, 95), tpot_p50_ms=median(tpot),
        )
    return out


def ok(r: dict) -> bool:
    """The request ended well: no error, a finish reason, all its tokens."""
    return (
        not r.get("error") and r.get("finish") is not None
        and r.get("first") is not None
        and len(r["tokens"]) == r["max_tokens"]
    )


def lateness_ms(requests: list[dict], t0: float, t1: float) -> dict:
    """How late the generator sent what was due inside the window."""
    late = [
        (r["sent"] - r["due"]) * 1e3 for r in requests
        if in_window(r["due"], t0, t1) and r.get("sent") is not None
    ]
    if not late:
        return {"n": 0}
    return {"n": len(late), "p50": median(late), "p95": percentile(late, 95),
            "max": max(late)}


def stalls(requests: list[dict], t0: float, t1: float, over_ms: float = 250.0) -> dict:
    """Gaps between consecutive content deltas of one stream that are
    longer than ``over_ms`` and end inside the window: how many there
    were, on how many episodes (gaps that overlap in time are one: a
    pause of the host or of its machine falls on every live stream at
    once), the seconds those episodes cover and the longest gap. Deltas
    come a decode window apart (tens of ms), so 250 ms is a pause."""
    spans = sorted(
        (a, b) for r in requests if ok(r)
        for (a, _), (b, _) in zip(r["deltas"], r["deltas"][1:])
        if (b - a) * 1e3 > over_ms and t0 <= b < t1
    )
    episodes: list[list[float]] = []
    for a, b in spans:
        if episodes and a < episodes[-1][1]:
            episodes[-1][1] = max(episodes[-1][1], b)
        else:
            episodes.append([a, b])
    return {
        "over_ms": over_ms, "gaps": len(spans), "episodes": len(episodes),
        "episode_s": sum(b - a for a, b in episodes),
        "longest_ms": max(((b - a) * 1e3 for a, b in spans), default=0.0),
        "episodes_at_s": [[a - t0, b - a] for a, b in episodes[:8]],
    }


def pauses_in_window(pauses: list[list[float]], t0: float, t1: float) -> dict:
    """The load process's own late wakes (``chat_client.Heartbeat``:
    [when due, seconds late]) that fell inside the window."""
    inside = [(at, late) for at, late in pauses if t0 <= at < t1]
    return {
        "n": len(inside), "total_s": sum(late for _, late in inside),
        "longest_ms": max((late * 1e3 for _, late in inside), default=0.0),
        "at_s": [[at - t0, late] for at, late in inside[:8]],
    }


def hist_delta(before: dict | None, after: dict | None, key: str) -> dict | None:
    """What one of ``ServingMetrics``' histograms (``count``, ``sum_us``,
    octave ``counts``: bucket i holds [2^(i-1), 2^i) us) gained between
    two snapshots of the node; None where either snapshot lacks it."""
    a, b = (before or {}).get(key), (after or {}).get(key)
    if not a or not b or not all(k in h for h in (a, b) for k in ("count", "sum_us")):
        return None
    return {
        "count": b["count"] - a["count"], "sum_us": b["sum_us"] - a["sum_us"],
        "counts": [y - x for x, y in zip(a.get("counts", []), b.get("counts", []))],
    }


def compared(value, limit, at_most: bool = True) -> dict:
    """One number that ``correct`` rests on, beside its limit: it holds
    where the value is at most (or, ``at_most=False``, at least) the
    limit. A value that could not be read (None) does not hold."""
    holds = value is not None and (value <= limit if at_most else value >= limit)
    return {"value": value, "limit": limit, "rule": "<=" if at_most else ">=", "holds": holds}


def gaps_ms(stamps: list[float], t0: float, t1: float) -> list[float]:
    inside = [s for s in stamps if t0 <= s < t1]
    return [(b - a) * 1e3 for a, b in zip(inside, inside[1:])]


def agreed(a: list[int], b: list[int]) -> int:
    n = 0
    for x, y in zip(a, b):
        if x != y:
            break
        n += 1
    return n
