"""Latent rows that the decode window's block loop FETCHED for each row a
live stream ATTENDED, over the window: ``mla_rows_swept`` /
``mla_rows_in_context`` gained between the two serving snapshots (the
program's counters: blocks run x block x slots a tick, against position + 1
a live row). 1.0 = nothing fetched in vain; the plain-XLA loop runs to the
longest context for every slot, live or not: the engagement reading of the
full sweep. None where the program has no such counters."""
import model_bytes_kda_mla as mb


def read(run: dict, args: dict):
    return mb.per(run.get("serving_before"), run.get("serving_after"),
                  "mla_rows_swept", "mla_rows_in_context")
