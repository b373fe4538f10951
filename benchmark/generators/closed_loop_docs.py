"""Closed loop over documents: N callers, each sends its next streaming
chat completion when its last one finished; every request is a document
followed by a fresh question. Parameters come from the traffic file:
``callers``, ``document_tokens``, ``question_tokens``, ``output_tokens``,
``asks_per_document``, ``ask_spacing``, ``block_documents``,
``max_requests_per_s`` (only sizes the plan). Each caller's first request
is one of the warm wave (``chat_plan.warm_wave``), as in
``closed_loop_chat``; the window opens when every caller has finished it.

The schedule. With A = ``asks_per_document`` and S = ``ask_spacing``
(S = 1 modulo A), ask ``k`` of document ``d`` sits at slot ``A*d + S*k``,
so slot ``p`` holds ask ``k = p % A`` of document ``(p - S*k) / A``: in
every A consecutive slots one first ask (a cache miss) and A-1 repeats
of documents first asked S, 2S, ... slots earlier, whose pages the
later ask finds in the prefix cache because S exceeds the callers in
flight. Slots whose document would lie before the plan (``d < 0``, among
the first ``S*(A-1)`` slots) hold asks of run-in documents that the plan
meets in mid-life: those are asked fewer than A times, and so are the
documents the plan's end cuts. Lengths are the quantiles of the traffic
file's distributions over a block of ``block_documents``, in one order
drawn from ``shape_seed``: the same schedule for every ``--seed``, which
gives the token ids.
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "lib"))

import chat_plan  # noqa: E402
from checkpoint import token_code  # noqa: E402

KIND = "process"  # a load process of its own beside the dataflow


def schedule(traffic: dict, count: int) -> list[dict]:
    """``count`` slots: document number (negative for a run-in
    document), ask number, and the three lengths. Pure, from the
    traffic file alone."""
    import numpy as np

    asks, step = traffic["asks_per_document"], traffic["ask_spacing"]
    block = traffic["block_documents"]
    if step % asks != 1:
        raise ValueError(f"ask_spacing {step} must be 1 modulo asks_per_document {asks}")
    rng = np.random.default_rng(traffic["shape_seed"])
    docs = rng.permutation(chat_plan.lengths(traffic["document_tokens"], block)).tolist()
    per = block * asks
    questions = rng.permutation(chat_plan.lengths(traffic["question_tokens"], per)).tolist()
    outputs = rng.permutation(chat_plan.lengths(traffic["output_tokens"], per)).tolist()
    out = []
    for p in range(count):
        k = p % asks
        d = (p - step * k) // asks
        out.append({
            "doc": d, "ask": k, "document_tokens": docs[d % block],
            "question_tokens": questions[p % per], "max_tokens": outputs[p % per],
        })
    return out


def requests(traffic: dict, seed: int, count: int, vocab: int) -> list[dict]:
    """The schedule with token ids from ``seed``: a document's ids are
    drawn once (a stream a document), a question's for each ask."""
    import numpy as np

    docs: dict[int, list[int]] = {}
    out = []
    for p, slot in enumerate(schedule(traffic, count)):
        d = slot["doc"]
        if d not in docs:
            rng = np.random.default_rng([seed, 1, d + 2 ** 20])
            docs[d] = rng.integers(0, vocab, size=slot["document_tokens"]).tolist()
        rng = np.random.default_rng([seed, 2, p])
        ids = docs[d] + rng.integers(0, vocab, size=slot["question_tokens"]).tolist()
        out.append({
            "ids": ids, "prompt_tokens": len(ids), "twin_of": None,
            "doc": d, "ask": slot["ask"], "document_tokens": slot["document_tokens"],
            "max_tokens": slot["max_tokens"], "text": "".join(map(token_code, ids)),
        })
    return out


def plan(traffic: dict, seed: int, seconds: float, config: dict) -> dict:
    callers, vocab = traffic["callers"], config["model"]["vocab_size"]
    count = int(traffic["max_requests_per_s"] * seconds)
    warm = chat_plan.warm_wave(
        callers, seed, vocab, traffic["warm_prompt_tokens"], traffic["warm_step_tokens"]
    )
    return {"mode": "closed", "callers": callers,
            "requests": warm + requests(traffic, seed, count, vocab)}


def measure(ctx, run: dict) -> dict:
    import docs_measure

    return docs_measure.measure(
        ctx, run, plan(ctx.traffic, ctx.traffic_seed, ctx.seconds, ctx.config)
    )


if __name__ == "__main__":
    import chat_client

    sys.exit(chat_client.main(plan))
