"""Rows of the pages that the decode sweep FETCHED for each row a live
stream ATTENDED, over the window: ``cca_kv_rows_swept`` /
``cca_kv_rows_read`` gained between the two serving snapshots (the
program's counters: 128 rows a (row, group) step of the sweep x layers,
against (position + 1) x layers a live row a tick). 1.0 = nothing fetched
in vain; 1.0-1.1 says the sweep reads the live rows' own pages and stops
at each row's own count. (``kv_swept_over_read.py`` is this quotient of
K-EXAONE's counters, whose names it holds.) None where the program has no
such counters."""
import model_bytes_cca_moe as mb


def read(run: dict, args: dict):
    return mb.per(run.get("serving_before"), run.get("serving_after"),
                  "cca_kv_rows_swept", "cca_kv_rows_read")
