"""Share of the chip's peak HBM bandwidth that the decode window of a
``kimi_linear`` configuration reaches on its weights, its KDA states AND
the latent rows it attends: ``ticks`` x [the int8 weights every tick reads
+ the bf16 routers + the routed experts a tick touched x one expert's
bytes + the KDA states a tick read and wrote (``kda_row_ticks`` x
4,194,304 B) + the latent rows its live rows attended
(``mla_rows_in_context`` x 1,280 B)], the last three over the captured
ticks (the program's ``moe_touched``, ``kda_row_ticks``,
``mla_rows_in_context`` / ``kda_decode_ticks`` between the capture's edges)
(``lib/model_bytes_kda_mla``) / the device kind's peak bytes per second
(``lib/peaks.json``) / the median device time of the window program. Rows
ATTENDED, whatever a sweep fetches beside them. None where the program has
no such counters."""
import model_bytes_kda_mla as mb
import trace_reduce


def read(run: dict, args: dict):
    edges = mb.capture_edges(run)
    if not run.get("events") or not edges:
        return None
    touched, states, rows = (
        mb.per(*edges, key, "kda_decode_ticks") for key in (
            "moe_touched", "kda_row_ticks", "mla_rows_in_context"))
    if None in (touched, states, rows):
        return None
    ms = trace_reduce.module_median_ms(run["events"], args["match"])
    if not ms:
        return None
    env = run["config"]["node_env"][args["node"]]
    ticks = int(env.get(args["ticks_env"], args["ticks_default"]))
    bytes_ = ticks * mb.decode_tick_bytes(run["config"]["model"], touched, states, rows)
    return 100.0 * bytes_ / run["peaks"]["hbm_bytes_per_s"] / (ms / 1e3)
