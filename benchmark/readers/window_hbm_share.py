"""Share of the chip's peak HBM bandwidth that the decode window reaches
on its weights alone: ``ticks`` x the bytes of quantized decode weights
one tick must read (``lib/model_bytes.decode_tick_weight_bytes``, from
the configuration) / the peak bytes per second of the device kind
(``lib/peaks.json``) / the median device time of the window program.
A lower bound on the bytes: KV pages need the live contexts, which only
the tracing issue's counters will give."""
import model_bytes
import trace_reduce


def read(run: dict, args: dict):
    if not run.get("events"):
        return None
    ms = trace_reduce.module_median_ms(run["events"], args["match"])
    if not ms:
        return None
    env = run["config"]["node_env"][args["node"]]
    ticks = int(env.get(args["ticks_env"], args["ticks_default"]))
    bytes_ = ticks * model_bytes.decode_tick_weight_bytes(
        run["config"]["model"], args.get("bytes_per_weight", 1.0)
    )
    return 100.0 * bytes_ / run["peaks"]["hbm_bytes_per_s"] / (ms / 1e3)
