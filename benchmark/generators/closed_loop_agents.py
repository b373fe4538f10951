"""Closed loop over agent turns: N callers, each sends its next streaming
chat completion when its last one finished; every request is its caller's
SHARED PREFIX (an agent's system prompt and tool schemas: long, the same
for every caller of its group) followed by a fresh tail (a tool result or
a short turn) and a long answer (reasoning and a tool call). Parameters
come from the traffic file: ``callers``, ``prefixes``, ``prefix_tokens``,
``tail_tokens``, ``output_tokens``, ``warm_output_tokens``,
``requests_per_caller`` (only sizes the plan: the loop stops at the
window's end, not at the plan's). No history is carried from request to
request: a real agent's turn would carry its earlier steps too; stated as
the simplification it is.

The schedule. The prefixes' lengths are a stratified draw over
``prefix_tokens`` (one in each of ``prefixes`` equal parts of the range,
none a multiple of 16: aligned to no page and no chunk); caller ``c`` uses
prefix ``c % prefixes``. Every request's tail and answer lengths are
quantiles of the traffic file's distributions over all of the plan's
requests, dealt in one order. All of it is drawn from ``shape_seed``: the
same schedule for every ``--seed``, which gives the token ids (a stream a
prefix, a stream a request).

Before the window, the warm wave: each prefix is sent ALONE, twice in
turn, by one caller, with a fresh tail and ``warm_output_tokens`` new
tokens: first every prefix cold (the radix tree learns its pages), then
every prefix again (the second prompt of a prefix leaves the tree after
the shared part: the engine saves its branch snapshot). Then every caller
sends its first request, and the window opens when all of them have
answered it: from its first second every prompt is granted its prefix.
64 callers arriving cold together would each prefill their prefix (some
2,300 chunks), and an admission that waits for a prefix another stream is
still prefilling is not built.
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "lib"))

import chat_plan  # noqa: E402
from checkpoint import token_code  # noqa: E402

KIND = "process"  # a load process of its own beside the dataflow
ALIGN = 16  # no prefix length is a multiple of the page (so none of the chunk)


def schedule(traffic: dict) -> dict:
    """``{"prefix_tokens": [a prefix each], "callers": [[{"tail_tokens",
    "max_tokens"} a request] a caller], "warm": [{"prefix", "tail_tokens"}
    a warm request, in order]}``. Pure, from the traffic file."""
    import numpy as np

    callers, per, groups = (traffic["callers"], traffic["requests_per_caller"],
                            traffic["prefixes"])
    if callers % groups:
        raise ValueError(f"{callers} callers do not divide over {groups} prefixes")
    rng = np.random.default_rng(traffic["shape_seed"])
    lo, hi = traffic["prefix_tokens"]["min"], traffic["prefix_tokens"]["max"]
    prefixes = []
    for g, u in enumerate(rng.random(groups)):
        n = lo + int((hi - lo) * (g + u) / groups)
        prefixes.append(n + 1 if n % ALIGN == 0 else n)
    n = callers * per
    tails = rng.permutation(chat_plan.lengths(traffic["tail_tokens"], n + 2 * groups))
    outputs = rng.permutation(chat_plan.lengths(traffic["output_tokens"], n))
    return {
        "prefix_tokens": prefixes,
        "callers": [[{"tail_tokens": int(tails[c * per + k]),
                      "max_tokens": int(outputs[c * per + k])} for k in range(per)]
                    for c in range(callers)],
        "warm": [{"prefix": g, "tail_tokens": int(tails[n + j * groups + g])}
                 for j in range(2) for g in range(groups)],
    }


def plan(traffic: dict, seed: int, seconds: float, config: dict) -> dict:
    """The schedule with token ids from ``seed``."""
    import numpy as np

    vocab = config["model"]["vocab_size"]
    made = schedule(traffic)

    def ids(*stream, n):
        return np.random.default_rng([seed, *stream]).integers(0, vocab, size=n).tolist()

    prefixes = [ids(7, g, n=n) for g, n in enumerate(made["prefix_tokens"])]
    groups = len(prefixes)
    return {
        "mode": "agents", "callers": traffic["callers"], "prefixes": prefixes,
        "warm_output_tokens": traffic["warm_output_tokens"],
        "warm": [{**w, "tail_ids": ids(9, j, n=w["tail_tokens"])}
                 for j, w in enumerate(made["warm"])],
        "requests": [[{**r, "prefix": c % groups, "tail_ids": ids(8, c, k, n=r["tail_tokens"])}
                      for k, r in enumerate(mine)]
                     for c, mine in enumerate(made["callers"])],
    }


def prompt_ids(made: dict, request: dict) -> list[int]:
    return made["prefixes"][request["prefix"]] + request["tail_ids"]


def run_agents(port: int, made: dict, seconds: float, timeout_s: float) -> dict:
    """The warm wave by one caller, then a thread a caller. The window opens
    when every caller has finished its first request, lasts ``seconds``; no
    request starts after it, those in flight drain. A record is
    ``chat_client``'s, with the request's place (``caller`` -1 for the warm
    wave, ``k``, ``prefix``) and ``i``, the order in which it was sent."""
    import threading
    import time

    import chat_client

    lock = threading.Lock()
    state = {"next": 0, "warm": 0, "t0": None, "t1": None, "exhausted": False}
    records: list[dict] = []
    callers = made["callers"]
    texts = ["".join(map(token_code, p)) for p in made["prefixes"]]

    def send(request: dict, max_tokens: int, caller: int, k: int) -> dict:
        with lock:
            i = state["next"]
            state["next"] += 1
        req = {"text": texts[request["prefix"]] + "".join(map(token_code, request["tail_ids"])),
               "max_tokens": max_tokens,
               "prompt_tokens": len(made["prefixes"][request["prefix"]]) + len(request["tail_ids"])}
        due = time.monotonic()
        got = chat_client.ask(port, req, timeout_s)
        with lock:
            records.append({**chat_client._record(i, req, due, got),
                            "caller": caller, "k": k, "prefix": request["prefix"]})
        return got

    for j, request in enumerate(made["warm"]):
        send(request, made["warm_output_tokens"], -1, j)
    chat_client.say("warm_wave_done")

    def caller(c: int) -> None:
        for k, request in enumerate(made["requests"][c]):
            with lock:
                if state["t1"] is not None and time.monotonic() >= state["t1"]:
                    return
            send(request, request["max_tokens"], c, k)
            if k == 0:
                with lock:
                    state["warm"] += 1
                    if state["warm"] == callers:
                        state["t0"] = time.monotonic()
                        state["t1"] = state["t0"] + seconds
                        chat_client.say("window_start", t0=state["t0"])
        with lock:
            state["exhausted"] = True

    threads = [threading.Thread(target=caller, args=(c,), daemon=True)
               for c in range(callers)]
    for t in threads:
        t.start()
    while state["t1"] is None or time.monotonic() < state["t1"]:
        time.sleep(0.01)
        if not any(t.is_alive() for t in threads):
            break
    chat_client.say("window_end", t1=state["t1"])
    for t in threads:
        t.join(timeout_s)
    return {"t0": state["t0"], "t1": state["t1"], "requests": records,
            "plan_exhausted": state["exhausted"]}


def measure(ctx, run: dict) -> dict:
    import chat_measure_kimi_linear

    return chat_measure_kimi_linear.measure(
        ctx, run, plan(ctx.traffic, ctx.traffic_seed, ctx.seconds, ctx.config)
    )


def main() -> int:
    import json

    import chat_client

    ctx = json.load(open(sys.argv[1]))
    made = plan(ctx["traffic"], ctx["seed"], ctx["seconds"], ctx["config"])
    chat_client.wait_for_server(ctx["port"], ctx["timeout_s"])
    chat_client.say("server_up")
    beat = chat_client.Heartbeat()
    beat.start()
    raw = run_agents(ctx["port"], made, ctx["seconds"], ctx["timeout_s"])
    raw["generator_pauses"] = beat.stop()
    json.dump(raw, open(ctx["result"], "w"))
    chat_client.say("done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
