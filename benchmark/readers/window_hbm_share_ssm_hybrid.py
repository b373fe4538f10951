"""Share of the chip's peak HBM bandwidth that the decode window of a
``falcon_h1`` configuration reaches on its weights and its recurrent
state: ``ticks`` x [the int8 weights every tick reads + mean live rows a
tick (the program's counters over the captured ticks) x layers x the bytes one row's state takes
read and written] (``lib/model_bytes_falcon_h1``) / the device kind's
peak bytes per second (``lib/peaks.json``) / the median device time of
the window program. A lower bound on the bytes: no K/V term. None where
the program has no such counters."""
import model_bytes_falcon_h1 as mb
import trace_reduce


def read(run: dict, args: dict):
    rows = mb.live_rows_in_capture(run)
    if not run.get("events") or rows is None:
        return None
    ms = trace_reduce.module_median_ms(run["events"], args["match"])
    if not ms:
        return None
    env = run["config"]["node_env"][args["node"]]
    ticks = int(env.get(args["ticks_env"], args["ticks_default"]))
    bytes_ = ticks * mb.decode_tick_bytes(run["config"]["model"], rows)
    return 100.0 * bytes_ / run["peaks"]["hbm_bytes_per_s"] / (ms / 1e3)
