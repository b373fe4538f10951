"""Median, over the executions of the program matching ``args["match"]``,
of the device-operation time inside one execution, in ms."""
import trace_reduce


def read(run: dict, args: dict):
    if not run.get("events"):
        return None
    return trace_reduce.ops_busy_inside_ms(run["events"], args["match"])
