"""Share of the chip's peak HBM bandwidth that the decode window of an
``exaone_moe`` configuration reaches on its weights AND the cache rows
it attends: ``ticks`` x [the int8 weights every tick reads + the bf16
routers + the routed experts a tick touched x one expert's bytes + the
K/V rows a tick attended in the pages and in the rings x 4,096 B], the
last three over the captured ticks (the program's ``moe_touched``,
``global_kv_rows_read``, ``swa_ring_rows_read`` / ``swa_decode_ticks``
between the capture's edges) (``lib/model_bytes_swa_moe``) / the device
kind's peak bytes per second (``lib/peaks.json``) / the median device
time of the window program. None where the program has no such counters."""
import model_bytes_swa_moe as mb
import trace_reduce


def read(run: dict, args: dict):
    edges = mb.capture_edges(run)
    if not run.get("events") or not edges:
        return None
    touched, pages, rings = (mb.per(*edges, key, "swa_decode_ticks") for key in (
        "moe_touched", "global_kv_rows_read", "swa_ring_rows_read"))
    if None in (touched, pages, rings):
        return None
    ms = trace_reduce.module_median_ms(run["events"], args["match"])
    if not ms:
        return None
    env = run["config"]["node_env"][args["node"]]
    ticks = int(env.get(args["ticks_env"], args["ticks_default"]))
    bytes_ = ticks * mb.decode_tick_bytes(run["config"]["model"], touched, pages, rings)
    return 100.0 * bytes_ / run["peaks"]["hbm_bytes_per_s"] / (ms / 1e3)
