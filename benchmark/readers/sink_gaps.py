"""Percentile ``args["q"]`` of the gaps between outputs at the sink
inside the window, in ms (host clock of the sink node)."""
import stats


def read(run: dict, args: dict):
    gaps = run.get("gaps_ms")
    return stats.percentile(gaps, args["q"]) if gaps else None
