"""Request plans of the chat generators, drawn from a seed.

Every seed gets the same prompt lengths, output lengths and arrival gaps
in the same order (drawn once, from the traffic file's ``shape_seed``)
and other token ids. The seed must change neither how much work a run
holds nor how the long prompts fall together and which arrivals they
meet, or runs with different seeds spread far wider than two runs of one
seed: with an order of its own for every seed, ``ttft_p95_ms`` repeated
within 1 % for a seed and spread 11 % across six in the closed loop (PR
23, chip call 4); entering one cyclic order at a point of the seed's
choosing still left 20 % in the open loop (call 5). Lengths are the quantiles of
the traffic file's distributions (a stratified sample), laid out in
blocks of ``block`` requests, dealt evenly over the block's groups of
``repeat_every``; in each group the last request repeats the prompt of
an earlier one of its group (a prefix-cache hit, and a twin for the
correctness check).
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

from checkpoint import token_code


def lognormal_quantiles(median: float, sigma: float, lo: int, hi: int, n: int) -> list[int]:
    nd = NormalDist()
    return [
        int(min(hi, max(lo, round(median * math.exp(sigma * nd.inv_cdf((i + 0.5) / n))))))
        for i in range(n)
    ]


def uniform_quantiles(lo: int, hi: int, n: int) -> list[int]:
    return [int(round(lo + (hi - lo) * (i + 0.5) / n)) for i in range(n)]


def lengths(spec: dict, n: int) -> list[int]:
    if spec["dist"] == "lognormal":
        return lognormal_quantiles(spec["median"], spec["sigma"], spec["min"], spec["max"], n)
    if spec["dist"] == "uniform":
        return uniform_quantiles(spec["min"], spec["max"], n)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def exponential_gaps(n: int) -> list[float]:
    """The n quantiles of a unit exponential: one fixed set of gaps."""
    return [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]


def _spread(pool: list[int], groups: int, rng) -> list[list[int]]:
    """Deal a pool over ``groups`` hands so that each hand holds one value
    of every stratum of the sorted pool (a seeded choice of which)."""
    ordered = sorted(pool)
    hands: list[list[int]] = [[] for _ in range(groups)]
    for k in range(0, len(ordered), groups):
        stratum = ordered[k:k + groups]
        for hand, j in zip(hands, rng.permutation(len(stratum)).tolist()):
            hand.append(stratum[j])
    return hands


def block_layout(traffic: dict) -> list[dict]:
    """One block's lengths in the order every run sends them, drawn from
    the traffic file's ``shape_seed`` and not from the run's seed. A block
    is ``block / repeat_every`` groups; every group holds one prompt and
    one output length of each stratum of the block's quantiles and ends
    on a repeat of one of its own prompts (``twin_back`` positions back)."""
    block = traffic.get("block", 64)
    every = traffic["repeat_every"]
    if block % every:
        raise ValueError(f"block {block} is no multiple of repeat_every {every}")
    groups = block // every
    rng = np.random.default_rng(traffic["shape_seed"])
    prompts = _spread(lengths(traffic["prompt_tokens"], block - groups), groups, rng)
    outputs = _spread(lengths(traffic["output_tokens"], block), groups, rng)
    layout = []
    for g in range(groups):
        fresh = rng.permutation(prompts[g]).tolist()
        new = rng.permutation(outputs[g]).tolist()
        for k in range(every):
            last = k == every - 1
            layout.append({
                "prompt_tokens": None if last else fresh[k], "max_tokens": new[k],
                "twin_back": int(rng.integers(1, every)) if last else None,
            })
    return layout


def requests(traffic: dict, seed: int, count: int, vocab: int) -> list[dict]:
    """``count`` requests: text, ids, prompt_tokens, max_tokens, and
    ``twin_of`` on a repeat. The lengths are the block layout over and
    over, the same for every seed; the token ids are the seed's. A run's
    tails depend on where the long prompts fall and which arrivals they
    meet, and must not depend on the seed."""
    layout = block_layout(traffic)
    rng = np.random.default_rng(seed)
    out: list[dict] = []
    for n in range(count):
        slot = layout[n % len(layout)]
        if slot["twin_back"] is not None:
            twin_of = n - slot["twin_back"]
            ids = out[twin_of]["ids"]
        else:
            twin_of = None
            ids = rng.integers(0, vocab, size=slot["prompt_tokens"]).tolist()
        out.append({
            "ids": ids, "prompt_tokens": len(ids), "twin_of": twin_of,
            "max_tokens": slot["max_tokens"], "text": "".join(map(token_code, ids)),
        })
    return out


def arrivals(rate: float, seconds: float, shape_seed: int) -> list[float]:
    """Exactly round(rate x seconds) Poisson-like arrival offsets inside
    [0, seconds): the quantiles of the exponential as gaps, in the one
    order the traffic file's ``shape_seed`` gives (the same for every
    run), scaled so that they and one closing mean gap span the window."""
    n = round(rate * seconds)
    gaps = np.random.default_rng(shape_seed).permutation(exponential_gaps(n))
    closing = 1.0  # one mean gap after the last arrival, the same for every seed
    at = np.cumsum(gaps) * (seconds / (float(np.sum(gaps)) + closing))
    return at.tolist()


def warm_wave(n: int, seed: int, vocab: int, prompt_tokens: int = 32,
              step: int = 8) -> list[dict]:
    """The wave that fills every slot before a window: n short prompts
    sent at once whose outputs end one decode window apart (8, 16, ...
    tokens), so that the slots free up, and the callers' next requests
    start, spread out as in the steady state and not in step."""
    rng = np.random.default_rng(seed + 0x5EED)
    out = []
    for j in range(n):
        ids = rng.integers(0, vocab, size=prompt_tokens).tolist()
        out.append({
            "ids": ids, "prompt_tokens": len(ids), "twin_of": None,
            "max_tokens": step * (j + 1), "text": "".join(map(token_code, ids)),
        })
    return out
