"""Share of the chip's peak bf16 FLOP/s that the prefill-chunk program
of a ``falcon_h1`` configuration reaches on its weight matmuls alone:
``chunk`` rows x the matmul FLOPs of one token
(``lib/model_bytes_falcon_h1``; no score and no scan term, so a lower
bound) / the device kind's peak (``lib/peaks.json``) / the median device
time of the chunk program. None where the run served no ``falcon_h1``
model."""
import model_bytes_falcon_h1 as mb
import trace_reduce


def read(run: dict, args: dict):
    model = run["config"]["model"]
    if not run.get("events") or model.get("model_type") != "falcon_h1":
        return None
    ms = trace_reduce.module_median_ms(run["events"], args["match"])
    if not ms:
        return None
    env = run["config"]["node_env"][args["node"]]
    chunk = int(env.get(args["chunk_env"], args["chunk_default"]))
    flops = chunk * mb.matmul_flops_per_token(model)
    return 100.0 * flops / run["peaks"]["bf16_flops"] / (ms / 1e3)
