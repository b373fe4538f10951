"""Largest over mean of ``moe_expert_tokens``, the program's count of
routed pairs per held expert summed over layers (1.0 = every held expert
saw the same number of rows). None where the program has no such
counter or nothing was routed."""


def read(run: dict, args: dict):
    counts = (run.get("serving_after") or {}).get("moe_expert_tokens")
    if not counts or not sum(counts):
        return None
    return max(counts) / (sum(counts) / len(counts))
