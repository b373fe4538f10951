"""Share of the chip's peak bf16 FLOP/s that the prefill-chunk program of
an ``olmo_hybrid`` configuration reaches: ``chunk`` rows x 2 x the
parameters a token touches + the delta rule's block products + the full
layers' causal score and mix products at the captured chunks' mean
context (``gdn_chunk_positions`` / ``gdn_chunk_rows`` between the
capture's edges) (``lib/model_bytes_gdn_hybrid.chunk_flops``) / the device
kind's peak (``lib/peaks.json``) / the median device time of the chunk
program. None where the program has no such counters."""
import model_bytes_gdn_hybrid as mb
import trace_reduce


def read(run: dict, args: dict):
    edges = mb.capture_edges(run)
    if not run.get("events") or not edges:
        return None
    context = mb.per(*edges, "gdn_chunk_positions", "gdn_chunk_rows")
    if context is None:
        return None
    ms = trace_reduce.module_median_ms(run["events"], args["match"])
    if not ms:
        return None
    env = run["config"]["node_env"][args["node"]]
    chunk = int(env.get(args["chunk_env"], args["chunk_default"]))
    flops = mb.chunk_flops(run["config"]["model"], chunk, context)
    return 100.0 * flops / run["peaks"]["bf16_flops"] / (ms / 1e3)
