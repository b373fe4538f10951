"""Share of the prompt tokens admitted inside the window that the prefix
cache granted: 100 x ``prefix_hit_tokens`` gained / (``prefix_hit_tokens``
gained + the rows the chunk program prefilled, the program's counter
``args["prefilled"]``), between the two ``ServingMetrics`` snapshots around
the window. The engagement reading of a prefix cache (for a slot-state
model, of its state snapshots: a grant ends where one stands): near 0
means the cell measures cold prefill. None where the program has no such
counter or admitted nothing."""


def read(run: dict, args: dict):
    before, after = run.get("serving_before") or {}, run.get("serving_after") or {}
    keys = ("prefix_hit_tokens", args["prefilled"])
    if any(k not in snap for snap in (before, after) for k in keys):
        return None
    hit, cold = (after[k] - before[k] for k in keys)
    if hit + cold <= 0:
        return None
    return 100.0 * hit / (hit + cold)
