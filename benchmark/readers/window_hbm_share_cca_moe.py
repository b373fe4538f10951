"""Share of the chip's peak HBM bandwidth that the decode window of a
``zaya`` configuration reaches on its weights, the cache rows it attends
and the tails it steps: ``ticks`` x [the int8 matrices every tick reads +
the bf16 routers and convolutions + the experts a tick touched x one
expert's bytes + the K|V rows a tick attended x 1,024 B + the live rows'
tails, read and written], the last three over the captured ticks (the
program's ``moe_touched``, ``cca_kv_rows_read``, ``cca_row_ticks`` /
``cca_decode_ticks`` between the capture's edges)
(``lib/model_bytes_cca_moe``) / the device kind's peak bytes per second
(``lib/peaks.json``) / the median device time of the window program. None
where the program has no such counters."""
import model_bytes_cca_moe as mb
import trace_reduce


def read(run: dict, args: dict):
    edges = mb.capture_edges(run)
    if not run.get("events") or not edges:
        return None
    touched, rows, row_ticks = (mb.per(*edges, key, "cca_decode_ticks") for key in (
        "moe_touched", "cca_kv_rows_read", "cca_row_ticks"))
    if None in (touched, rows, row_ticks):
        return None
    ms = trace_reduce.module_median_ms(run["events"], args["match"])
    if not ms:
        return None
    env = run["config"]["node_env"][args["node"]]
    ticks = int(env.get(args["ticks_env"], args["ticks_default"]))
    bytes_ = ticks * mb.decode_tick_bytes(run["config"]["model"], touched, rows, row_ticks)
    return 100.0 * bytes_ / run["peaks"]["hbm_bytes_per_s"] / (ms / 1e3)
