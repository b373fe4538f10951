"""What one of the model node's ``ServingMetrics`` histograms
(``args["hist"]``, e.g. ``phase_rebuild_us``: one of the serving loop's
phases, ``dora_tpu/telemetry.py`` ``LOOP_PHASES``) gained, in ms, per
occurrence of another (``args["per"]``): sum_us gained by the first /
count gained by the second / 1000, between the two snapshots
``serving_hist_mean_ms`` uses (from ``serving_traced``, taken once the
capture was written, where there is one). With ``"per":
"dispatch_gap_us"`` that is a phase's milliseconds a period, whatever
share of turns the phase runs in, so the phases that lie in the gap add
up to ``dispatch_gap_ms.serve``. With ``args["minus"]`` (a list of
histograms) their sums are taken off the first's: what of it they do not
account for, signed. None where a snapshot is missing or lacks one of the
histograms (a server older than the phases), or ``per`` counted nothing."""
import stats


def read(run: dict, args: dict):
    start = run.get("serving_traced") or run.get("serving_before")
    end = run.get("serving_after")
    gained = [
        stats.hist_delta(start, end, key)
        for key in (args["per"], args["hist"], *args.get("minus", ()))
    ]
    if any(d is None for d in gained) or gained[0]["count"] <= 0:
        return None
    per, first, *others = gained
    return (first["sum_us"] - sum(d["sum_us"] for d in others)) / per["count"] / 1e3
