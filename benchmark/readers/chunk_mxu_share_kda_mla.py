"""Share of the chip's peak bf16 FLOP/s that the prefill-chunk program of a
``kimi_linear`` configuration reaches: ``chunk`` rows x 2 x the parameters
a token touches (non-expert + the landed pairs a token an expert layer, the
program's ``moe_local_pairs`` / ``moe_tokens``) + the delta rule's block
products + the latent layers' score and mix products at the captured
chunks' mean context (``mla_chunk_rows_in_context`` / ``kda_chunk_rows``
between the capture's edges, a latent layer)
(``lib/model_bytes_kda_mla.chunk_flops``) / the device kind's peak
(``lib/peaks.json``) / the median device time of the chunk program. None
where the program has no such counters."""
import model_bytes_kda_mla as mb
import trace_reduce


def read(run: dict, args: dict):
    edges = mb.capture_edges(run)
    if not run.get("events") or not edges:
        return None
    model = run["config"]["model"]
    context = mb.per(*edges, "mla_chunk_rows_in_context", "kda_chunk_rows")
    pairs = mb.per(*edges, "moe_local_pairs", "moe_tokens")
    if context is None or pairs is None:
        return None
    ms = trace_reduce.module_median_ms(run["events"], args["match"])
    if not ms:
        return None
    env = run["config"]["node_env"][args["node"]]
    chunk = int(env.get(args["chunk_env"], args["chunk_default"]))
    flops = mb.chunk_flops(model, chunk, context / mb.mla_layers(model), pairs)
    return 100.0 * flops / run["peaks"]["bf16_flops"] / (ms / 1e3)
