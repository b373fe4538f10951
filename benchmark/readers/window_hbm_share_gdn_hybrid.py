"""Share of the chip's peak HBM bandwidth that the decode window of an
``olmo_hybrid`` configuration reaches on its weights, its delta-rule
states AND the K/V rows it attends: ``ticks`` x [the int8 weights every
tick reads + the delta-rule states a tick read and wrote (``gdn_row_ticks``
x 4,423,680 B) + the rows its live rows attended in the full layers'
pages (``global_kv_rows_read`` x 15,360 B)], the last two over the
captured ticks (the program's counters / ``gdn_decode_ticks`` between the
capture's edges) (``lib/model_bytes_gdn_hybrid``) / the device kind's
peak bytes per second (``lib/peaks.json``) / the median device time of the
window program. Rows ATTENDED, whatever fetches them. None where the
program has no such counters."""
import model_bytes_gdn_hybrid as mb
import trace_reduce


def read(run: dict, args: dict):
    edges = mb.capture_edges(run)
    if not run.get("events") or not edges:
        return None
    states, rows = (mb.per(*edges, key, "gdn_decode_ticks")
                    for key in ("gdn_row_ticks", "global_kv_rows_read"))
    if states is None or rows is None:
        return None
    ms = trace_reduce.module_median_ms(run["events"], args["match"])
    if not ms:
        return None
    env = run["config"]["node_env"][args["node"]]
    ticks = int(env.get(args["ticks_env"], args["ticks_default"]))
    bytes_ = ticks * mb.decode_tick_bytes(run["config"]["model"], states, rows)
    return 100.0 * bytes_ / run["peaks"]["hbm_bytes_per_s"] / (ms / 1e3)
