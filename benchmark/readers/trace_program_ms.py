"""Median device duration, in ms, of the executions of the program whose
name matches ``args["match"]`` (a regular expression over the names on
the device's ``XLA Modules`` line)."""
import trace_reduce


def read(run: dict, args: dict):
    if not run.get("events"):
        return None
    return trace_reduce.module_median_ms(run["events"], args["match"])
