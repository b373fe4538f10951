"""Share of the chip's peak HBM bandwidth that the decode window of a
``kimi_k2`` configuration reaches on its weights alone: ``ticks`` x
[the bytes every tick reads + ``moe_experts_touched`` (the program's
counter: mean distinct held experts a layer a tick) x one expert's
bytes x the expert layers] (``lib/model_bytes_mla_moe``) / the device
kind's peak bytes per second (``lib/peaks.json``) / the median device
time of the window program. A lower bound on the bytes: no cache term.
None where the program has no such counter."""
import model_bytes_mla_moe as mb
import trace_reduce


def read(run: dict, args: dict):
    touched = (run.get("serving_after") or {}).get("moe_experts_touched")
    if not run.get("events") or touched is None:
        return None
    ms = trace_reduce.module_median_ms(run["events"], args["match"])
    if not ms:
        return None
    env = run["config"]["node_env"][args["node"]]
    ticks = int(env.get(args["ticks_env"], args["ticks_default"]))
    bytes_ = ticks * mb.decode_tick_bytes(run["config"]["model"], touched)
    return 100.0 * bytes_ / run["peaks"]["hbm_bytes_per_s"] / (ms / 1e3)
