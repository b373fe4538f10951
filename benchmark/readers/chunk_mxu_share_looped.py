"""Share of the chip's peak bf16 FLOP/s that the prefill-chunk program
of an ``ouro`` configuration reaches: ``chunk`` rows through the stack
once a pass plus the causal score and mix products at the captured
chunks' mean start position (the program's ``loop_chunk_positions`` /
``loop_chunks`` between the capture's edges)
(``lib/model_bytes_ouro.chunk_flops``; no head, so a lower bound) / the
device kind's peak (``lib/peaks.json``) / the median device time of the
chunk program. None where the program has no such counters."""
import model_bytes_ouro as mb
import trace_reduce


def read(run: dict, args: dict):
    edges = mb.capture_edges(run)
    position = mb.chunk_position(*edges) if edges else None
    if not run.get("events") or position is None:
        return None
    ms = trace_reduce.module_median_ms(run["events"], args["match"])
    if not ms:
        return None
    env = run["config"]["node_env"][args["node"]]
    chunk = int(env.get(args["chunk_env"], args["chunk_default"]))
    flops = mb.chunk_flops(run["config"]["model"], chunk, position)
    return 100.0 * flops / run["peaks"]["bf16_flops"] / (ms / 1e3)
