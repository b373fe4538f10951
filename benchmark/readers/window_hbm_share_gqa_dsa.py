"""Share of the chip's peak HBM bandwidth that the decode window of a
``KeyeVL2`` configuration reaches on its weights AND the cache rows it
scores and picks: ``ticks`` x [the int8 weights every tick reads + the
bf16 routers + the routed experts a tick touched x one expert's bytes +
the indexer keys it scored x 128 B + the K|V rows it picked x 2,048 B],
the last three over the captured ticks (the program's ``moe_touched``,
``dsa_index_rows_scored``, ``dsa_rows_picked`` / ``dsa_decode_ticks``
between the capture's edges) (``lib/model_bytes_gqa_dsa``) / the device
kind's peak bytes per second (``lib/peaks.json``) / the median device time
of the window program. Rows PICKED, whatever fetches them. None where the
program has no such counters."""
import model_bytes_gqa_dsa as mb
import trace_reduce


def read(run: dict, args: dict):
    edges = mb.capture_edges(run)
    if not run.get("events") or not edges:
        return None
    touched, scored, picked = (
        mb.per(*edges, key, "dsa_decode_ticks") for key in (
            "moe_touched", "dsa_index_rows_scored", "dsa_rows_picked"))
    if None in (touched, scored, picked):
        return None
    ms = trace_reduce.module_median_ms(run["events"], args["match"])
    if not ms:
        return None
    env = run["config"]["node_env"][args["node"]]
    ticks = int(env.get(args["ticks_env"], args["ticks_default"]))
    bytes_ = ticks * mb.decode_tick_bytes(run["config"]["model"], touched, scored, picked)
    return 100.0 * bytes_ / run["peaks"]["hbm_bytes_per_s"] / (ms / 1e3)
