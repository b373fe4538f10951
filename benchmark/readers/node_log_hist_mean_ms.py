"""Mean, in ms, of what one histogram of a node's own report lines
(``dora_tpu.backend <args["kind"]>: {json}`` in ``log_<args["node"]>.txt``,
printed cumulatively once a second and at exit, each with ``t_mono``, the
host's CLOCK_MONOTONIC, which the generator's window stamps share) gained
over the window: from the last line at or before ``run["t0"]`` to the
first at or after ``run["t1"]``, sum_us gained / count gained / 1000. For
histograms of a process that sends the daemon no snapshot, e.g. the HTTP
front's ``stage_route_out_us`` and ``stage_sse_us``
(``dora_tpu/telemetry.py`` ``REQUEST_STAGES``). None where the node
printed no such line on either side of the window (a program older than
the lines), a line lacks the histogram, or nothing was observed between
the two."""
import node_reports
import stats


def read(run: dict, args: dict):
    t0, t1 = run.get("t0"), run.get("t1")
    if t0 is None or t1 is None or run.get("workdir") is None:
        return None
    lines = [
        ln for ln in node_reports.node_reports(run["workdir"], args["node"]).get(args["kind"], [])
        if isinstance(ln.get("t_mono"), (int, float))
    ]
    before = [ln for ln in lines if ln["t_mono"] <= t0]
    after = [ln for ln in lines if ln["t_mono"] >= t1]
    if not before or not after:
        return None
    d = stats.hist_delta(before[-1], after[0], args["hist"])
    if d is None or d["count"] <= 0:
        return None
    return d["sum_us"] / d["count"] / 1e3
