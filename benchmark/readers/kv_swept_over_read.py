"""Rows of the global layers' pages that the decode window's block loop
FETCHED for each row a live stream ATTENDED, over the window:
``global_kv_rows_swept`` / ``global_kv_rows_read`` gained between the two
serving snapshots (the program's counters: blocks run x block x rows a
tick, against position + 1 a live row). 1.0 = nothing fetched in vain;
with one long row of sixteen the plain-XLA loop runs to the longest
context for every row. None where the program has no such counters."""
import model_bytes_swa_moe as mb


def read(run: dict, args: dict):
    return mb.per(run.get("serving_before"), run.get("serving_after"),
                  "global_kv_rows_swept", "global_kv_rows_read")
