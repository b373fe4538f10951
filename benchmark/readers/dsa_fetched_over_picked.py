"""Latent rows the decode window's sparse-latent layers FETCHED for each
row a live stream PICKED, over the window: ``dsa_rows_fetched`` /
``dsa_rows_picked`` gained between the two serving snapshots (the
program's counters: rows gathered through the block table against rows
attended). 1.0 where only picked rows are read; ``context / 2048`` where
a dense product runs under a mask. None where the program has no such
counters."""
import model_bytes_kda_dsa as mb


def read(run: dict, args: dict):
    return mb.per(run.get("serving_before"), run.get("serving_after"),
                  "dsa_rows_fetched", "dsa_rows_picked")
