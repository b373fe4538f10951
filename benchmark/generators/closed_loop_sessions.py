"""Closed loop over multi-turn sessions: N callers, each runs
conversations back to back and sends its next streaming chat completion
when its last one finished. Parameters come from the traffic file:
``callers``, ``first_prompt_tokens``, ``turns``, ``message_tokens``,
``output_tokens``, ``row_reserve``, ``conversations_per_caller`` (only
sizes the plan: the loop stops at the window's end, not at the plan's).

A conversation is a first prompt, then ``turns`` follow-ups; turn ``n``
sends the whole history — turn ``n - 1``'s prompt, the answer the run
RECEIVED for it, and a new user message — as the one user message the
front door's chat path takes (``openai_server`` hands ``llm_server`` the
last user message's content, and the synthetic tokenizer makes a text's
codes its ids: turn ``n``'s prompt begins with turn ``n - 1``'s, token for
token). No EOS is ever emitted, so an answer has exactly ``max_tokens``
tokens and every prompt's LENGTH is the schedule's, whatever the model
said. A conversation stops before a prompt + answer that would pass
``max_position_embeddings - row_reserve`` rows; the caller then starts its
next one cold. No two conversations share text.

The schedule. Every conversation's lengths are quantiles of the traffic
file's distributions (a stratified sample over all of the plan's
conversations and turns), dealt to the callers in one order drawn from
``shape_seed``: the same schedule for every ``--seed``, which gives the
token ids (a stream a conversation and turn). Each caller's first request
is its first conversation's first prompt: that is the warm wave, and the
window opens when every caller has finished it.
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "lib"))

import chat_plan  # noqa: E402
from checkpoint import token_code  # noqa: E402

KIND = "process"  # a load process of its own beside the dataflow


def schedule(traffic: dict, max_seq: int) -> list[list[dict]]:
    """A caller each, a conversation each: ``{"first_tokens", "turns":
    [{"message_tokens", "max_tokens", "prompt_tokens"}]}`` where turn 0 is
    the first prompt (``message_tokens`` 0) and ``prompt_tokens`` is the
    whole prompt's length at that turn. Pure, from the traffic file."""
    import numpy as np

    callers, per = traffic["callers"], traffic["conversations_per_caller"]
    n = callers * per
    rng = np.random.default_rng(traffic["shape_seed"])
    firsts = rng.permutation(chat_plan.lengths(traffic["first_prompt_tokens"], n))
    turns = rng.permutation(chat_plan.lengths(traffic["turns"], n))
    most = int(max(turns)) + 1
    messages = rng.permutation(chat_plan.lengths(traffic["message_tokens"], n * most))
    outputs = rng.permutation(chat_plan.lengths(traffic["output_tokens"], n * most))
    limit = max_seq - traffic["row_reserve"]
    out = [[] for _ in range(callers)]
    for c in range(n):
        prompt, listed = int(firsts[c]), []
        for t in range(int(turns[c]) + 1):
            message = 0 if t == 0 else int(messages[c * most + t])
            new = int(outputs[c * most + t])
            if prompt + message + new > limit:
                break
            prompt += message
            listed.append({"message_tokens": message, "max_tokens": new,
                           "prompt_tokens": prompt})
            prompt += new  # the answer joins the history
        out[c % callers].append({"first_tokens": int(firsts[c]), "turns": listed})
    return out


def plan(traffic: dict, seed: int, seconds: float, config: dict) -> dict:
    """The schedule with token ids from ``seed``: a conversation's first
    prompt and every turn's new message (what the model answers is the
    run's to add)."""
    import numpy as np

    vocab = config["model"]["vocab_size"]
    sessions = []
    for caller, conversations in enumerate(
            schedule(traffic, config["model"]["max_position_embeddings"])):
        mine = []
        for k, conv in enumerate(conversations):
            def ids(turn, n, caller=caller, k=k):
                return np.random.default_rng([seed, 5, caller, k, turn]).integers(
                    0, vocab, size=n).tolist()

            mine.append({
                "first_ids": ids(0, conv["first_tokens"]),
                "turns": [{**t, "message_ids": ids(j, t["message_tokens"])}
                          for j, t in enumerate(conv["turns"])],
            })
        sessions.append(mine)
    return {"mode": "sessions", "callers": traffic["callers"], "sessions": sessions}


def prompt_ids(conversation: dict, turn: int, answers: list[list[int]]) -> list[int]:
    """The prompt of ``turn``: the first prompt, then every earlier turn's
    answer and the message that followed it."""
    ids = list(conversation["first_ids"])
    for j in range(1, turn + 1):
        ids += answers[j - 1] + conversation["turns"][j]["message_ids"]
    return ids


def run_sessions(port: int, sessions: list[list[dict]], seconds: float,
                 timeout_s: float) -> dict:
    """A thread a caller. The window opens when every caller has finished
    its first request, lasts ``seconds``; no request starts after it,
    those in flight drain. A record is ``chat_client``'s, with the
    request's place (``caller``, ``conversation``, ``turn``) and ``i``, the
    order in which it was sent."""
    import threading
    import time

    import chat_client

    lock = threading.Lock()
    state = {"next": 0, "warm": 0, "t0": None, "t1": None, "exhausted": False}
    records: list[dict] = []
    callers = len(sessions)

    def caller(c: int) -> None:
        first = True
        for k, conv in enumerate(sessions[c]):
            text = "".join(map(token_code, conv["first_ids"]))
            for turn, spec in enumerate(conv["turns"]):
                text += "".join(map(token_code, spec["message_ids"]))
                with lock:
                    if state["t1"] is not None and time.monotonic() >= state["t1"]:
                        return
                    i = state["next"]
                    state["next"] += 1
                req = {"text": text, "max_tokens": spec["max_tokens"],
                       "prompt_tokens": spec["prompt_tokens"]}
                due = time.monotonic()
                got = chat_client.ask(port, req, timeout_s)
                with lock:
                    records.append({**chat_client._record(i, req, due, got),
                                    "caller": c, "conversation": k, "turn": turn})
                    if first:
                        first = False
                        state["warm"] += 1
                        if state["warm"] == callers:
                            state["t0"] = time.monotonic()
                            state["t1"] = state["t0"] + seconds
                            chat_client.say("window_start", t0=state["t0"])
                if got["error"] or len(got["text"]) != 3 * spec["max_tokens"]:
                    break  # a broken history: the caller starts its next one
                text += got["text"]
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
    import chat_measure_olmo_hybrid

    return chat_measure_olmo_hybrid.measure(
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
    raw = run_sessions(ctx["port"], made["sessions"], ctx["seconds"], ctx["timeout_s"])
    raw["generator_pauses"] = beat.stop()
    json.dump(raw, open(ctx["result"], "w"))
    chat_client.say("done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
