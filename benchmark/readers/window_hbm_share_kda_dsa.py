"""Share of the chip's peak HBM bandwidth that the decode window of a
``glm5_next_text`` configuration reaches on its weights, its delta-rule
states AND the cache rows it scores and picks: ``ticks`` x [the int8
weights every tick reads + the bf16 routers and residual maps + the
routed experts a tick touched x one expert's bytes + the delta-rule
states a tick read and wrote (``kda_row_ticks`` x 8,388,608 B) + the
pooled indexer rows it scored x 256 B + the latent rows it picked x
1,024 B], the last four over the captured ticks (the program's
``moe_touched``, ``kda_row_ticks``, ``dsa_index_rows_scored``,
``dsa_rows_picked`` / ``kda_decode_ticks`` between the capture's edges)
(``lib/model_bytes_kda_dsa``) / the device kind's peak bytes per second
(``lib/peaks.json``) / the median device time of the window program.
Rows PICKED, whatever fetches them. None where the program has no such
counters."""
import model_bytes_kda_dsa as mb
import trace_reduce


def read(run: dict, args: dict):
    edges = mb.capture_edges(run)
    if not run.get("events") or not edges:
        return None
    touched, states, scored, picked = (
        mb.per(*edges, key, "kda_decode_ticks") for key in (
            "moe_touched", "kda_row_ticks", "dsa_index_rows_scored", "dsa_rows_picked"))
    if None in (touched, states, scored, picked):
        return None
    ms = trace_reduce.module_median_ms(run["events"], args["match"])
    if not ms:
        return None
    env = run["config"]["node_env"][args["node"]]
    ticks = int(env.get(args["ticks_env"], args["ticks_default"]))
    bytes_ = ticks * mb.decode_tick_bytes(
        run["config"]["model"], touched, states, scored, picked)
    return 100.0 * bytes_ / run["peaks"]["hbm_bytes_per_s"] / (ms / 1e3)
