"""Mean, in ms, of what one of the model node's ``ServingMetrics``
histograms (``args["hist"]``, e.g. ``dispatch_gap_us``: host time from
``collect()`` returning to the next launch) gained between two of the
snapshots the harness takes: sum_us gained / count gained / 1000. From
the snapshot before the window to the one after it; in a traced run from
the one taken once the capture was written (``serving_traced``: writing a
capture holds the server's loop for seconds, inside one dispatch gap) to
the one after the window. None where either snapshot lacks the histogram
or nothing was observed between them."""
import stats


def read(run: dict, args: dict):
    start = run.get("serving_traced") or run.get("serving_before")
    d = stats.hist_delta(start, run.get("serving_after"), args["hist"])
    if d is None or d["count"] <= 0:
        return None
    return d["sum_us"] / d["count"] / 1e3
