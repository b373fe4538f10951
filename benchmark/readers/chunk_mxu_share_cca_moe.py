"""Share of the chip's peak bf16 FLOP/s that the prefill-chunk program of
a ``zaya`` configuration reaches: ``chunk`` rows x 2 x the parameters a
token touches (attention, head, routers, convolutions and the landed
pairs a token a layer, the program's ``moe_local_pairs`` / ``moe_tokens``:
1 where every expert is held) + the causal score and mix products at the
captured chunks' mean start position (``cca_chunk_positions`` /
``cca_chunks`` between the capture's edges)
(``lib/model_bytes_cca_moe.chunk_flops``) / the device kind's peak
(``lib/peaks.json``) / the median device time of the chunk program. None
where the program has no such counters."""
import model_bytes_cca_moe as mb
import trace_reduce


def read(run: dict, args: dict):
    edges = mb.capture_edges(run)
    if not run.get("events") or not edges:
        return None
    position = mb.per(*edges, "cca_chunk_positions", "cca_chunks")
    pairs = mb.per(*edges, "moe_local_pairs", "moe_tokens")
    if position is None or pairs is None:
        return None
    ms = trace_reduce.module_median_ms(run["events"], args["match"])
    if not ms:
        return None
    env = run["config"]["node_env"][args["node"]]
    chunk = int(env.get(args["chunk_env"], args["chunk_default"]))
    flops = mb.chunk_flops(run["config"]["model"], chunk, position, pairs)
    return 100.0 * flops / run["peaks"]["bf16_flops"] / (ms / 1e3)
