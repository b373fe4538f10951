"""Device idle share of the traced span: 1 - union of device-operation
intervals / traced span, in percent, averaged over device planes."""
import trace_reduce


def read(run: dict, args: dict):
    if not run.get("events"):
        return None
    return trace_reduce.idle_pct(run["events"])
