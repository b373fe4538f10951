"""Share of the chip's peak HBM bandwidth that the decode window of an
``ouro`` configuration reaches on its weights AND its K/V rows:
``ticks`` x [the int8 weights a tick reads (the stack once a pass, the
head once) + the K/V rows a tick read over the captured ticks (the
program's ``loop_kv_rows_read`` / ``loop_decode_ticks`` between the
capture's edges) x layers x the bytes of one entry's K and V]
(``lib/model_bytes_ouro``) / the device kind's peak bytes per second
(``lib/peaks.json``) / the median device time of the window program.
None where the program has no such counters."""
import model_bytes_ouro as mb
import trace_reduce


def read(run: dict, args: dict):
    edges = mb.capture_edges(run)
    rows = mb.rows_read_a_tick(*edges) if edges else None
    if not run.get("events") or rows is None:
        return None
    ms = trace_reduce.module_median_ms(run["events"], args["match"])
    if not ms:
        return None
    env = run["config"]["node_env"][args["node"]]
    ticks = int(env.get(args["ticks_env"], args["ticks_default"]))
    bytes_ = ticks * mb.decode_tick_bytes(run["config"]["model"], rows)
    return 100.0 * bytes_ / run["peaks"]["hbm_bytes_per_s"] / (ms / 1e3)
