"""The load process of the chat cells: one process, a thread a request.

Copied from ``chip_smoke.py``'s DRIVER (PR 21) and grown into a
generator's engine: streaming ``/v1/chat/completions`` over plain
``urllib``, every stamp on ``time.monotonic()``. A generator module under
``benchmark/generators/`` builds a *plan* (pure, from the seed) and calls
:func:`run_closed` or :func:`run_open` on it; both print
``{"event": "window_start"|"window_end", "t": ...}`` lines that the
harness follows, and return the raw per-request records. Metrics are
computed by the harness (``lib/stats.py``), not here.
"""

from __future__ import annotations

import json
import sys
import threading
import time
import urllib.request


def say(event: str, **kw) -> None:
    print(json.dumps({"event": event, "t": time.monotonic(), **kw}), flush=True)


def ask(port: int, req: dict, timeout_s: float) -> dict:
    """One streaming chat completion. ``req`` has ``text`` and
    ``max_tokens``; the record gets sent/first/last/done stamps."""
    body = json.dumps({
        "stream": True, "max_tokens": req["max_tokens"],
        "messages": [{"role": "user", "content": req["text"]}],
    }).encode()
    http = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/chat/completions",
        data=body, headers={"Content-Type": "application/json"},
    )
    out = {"text": "", "finish": None, "first": None, "last": None,
           "error": None, "deltas": [], "sent": time.monotonic()}
    try:
        with urllib.request.urlopen(http, timeout=timeout_s) as r:
            for raw in r:
                if not raw.startswith(b"data: ") or raw.startswith(b"data: [DONE]"):
                    continue
                payload = json.loads(raw[6:])
                choice = payload["choices"][0]
                delta = choice["delta"].get("content", "")
                if delta:
                    now = time.monotonic()
                    if out["first"] is None:
                        out["first"] = now
                    out["last"] = now
                    out["deltas"].append([now, len(delta)])
                    out["text"] += delta
                if choice.get("finish_reason"):
                    out["finish"] = choice["finish_reason"]
                    if payload.get("dora"):
                        out["error"] = json.dumps(payload["dora"])
    except Exception as e:  # refused, reset, timed out: a failed request
        out["error"] = repr(e)
    out["done"] = time.monotonic()
    return out


def wait_for_server(port: int, deadline_s: float) -> None:
    end = time.monotonic() + deadline_s
    while True:
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/models", timeout=5
            ) as r:
                r.read()
            return
        except OSError:
            if time.monotonic() > end:
                raise
            time.sleep(0.2)


def sleep_until(t: float) -> None:
    """Sleep to within 2 ms of ``t``, then in steps of at most 2 ms."""
    while True:
        wait = t - time.monotonic()
        if wait <= 0:
            return
        time.sleep(wait - 0.002 if wait > 0.004 else min(wait, 0.002))


class Heartbeat(threading.Thread):
    """Sleeps ``step_s`` at a time and keeps every wake that came more
    than ``over_s`` late, as ``[when it should have woken, seconds late]``:
    a pause of this process or of its whole machine, seen from the load
    generator's side. A pause the server makes alone does not show here."""

    def __init__(self, step_s: float = 0.02, over_s: float = 0.1):
        super().__init__(daemon=True)
        self.step_s, self.over_s = step_s, over_s
        self.pauses: list[list[float]] = []
        self._halt = threading.Event()

    def run(self) -> None:
        while not self._halt.is_set():
            due = time.monotonic() + self.step_s
            time.sleep(self.step_s)
            late = time.monotonic() - due
            if late > self.over_s:
                self.pauses.append([due, late])

    def stop(self) -> list[list[float]]:
        self._halt.set()
        self.join(1.0)
        return list(self.pauses)


def _record(i: int, req: dict, due: float, got: dict) -> dict:
    return {"i": i, "due": due, "max_tokens": req["max_tokens"],
            "prompt_tokens": req["prompt_tokens"], **got}


def run_closed(port: int, requests: list[dict], callers: int, seconds: float,
               timeout_s: float) -> dict:
    """``callers`` threads, each sends the plan's next request when its
    last one finished. The window opens when every caller has finished
    its first request (the warm wave that filled every slot), lasts
    ``seconds``; no request starts after it, those in flight drain."""
    lock = threading.Lock()
    state = {"next": 0, "warm": 0, "t0": None, "t1": None}
    records: list[dict] = []

    def caller() -> None:
        first = True
        while True:
            with lock:
                if state["t1"] is not None and time.monotonic() >= state["t1"]:
                    return
                i = state["next"]
                if i >= len(requests):
                    return
                state["next"] += 1
            due = time.monotonic()
            got = ask(port, requests[i], timeout_s)
            with lock:
                records.append(_record(i, requests[i], due, got))
                if first:
                    first = False
                    state["warm"] += 1
                    if state["warm"] == callers:
                        state["t0"] = time.monotonic()
                        state["t1"] = state["t0"] + seconds
                        say("window_start", t0=state["t0"])

    threads = [threading.Thread(target=caller, daemon=True) for _ in range(callers)]
    for t in threads:
        t.start()
    while state["t1"] is None or time.monotonic() < state["t1"]:
        time.sleep(0.01)
        if not any(t.is_alive() for t in threads):
            break
    say("window_end", t1=state["t1"])
    for t in threads:
        t.join(timeout_s)
    return {"t0": state["t0"], "t1": state["t1"], "requests": records,
            "plan_exhausted": state["next"] >= len(requests)}


def run_open(port: int, warm: list[dict], requests: list[dict], lead_s: float,
             seconds: float, drain_s: float, timeout_s: float) -> dict:
    """Open loop. A warm wave (all at once, awaited) fills every slot;
    then the plan's arrivals are sent at their ``due_s`` offsets, the
    negative ones being the lead-in that brings the server to its steady
    state before the window opens at offset 0. Nothing waits for a reply
    before the next is sent. After the window, requests in flight drain
    for at most ``drain_s``."""
    records: list[dict] = []
    lock = threading.Lock()

    def one(i: int, req: dict, due: float) -> None:
        got = ask(port, req, timeout_s)
        with lock:
            records.append(_record(i, req, due, got))

    wave = [
        threading.Thread(target=one, args=(-1 - j, r, time.monotonic()), daemon=True)
        for j, r in enumerate(warm)
    ]
    for t in wave:
        t.start()
    for t in wave:
        t.join(timeout_s)
    t0 = time.monotonic() + lead_s + 0.05
    t1 = t0 + seconds
    threads = []
    opened = False
    for i, req in enumerate(requests):
        due = t0 + req["due_s"]
        if not opened and req["due_s"] >= 0:
            sleep_until(t0)  # the window opens on the clock, not on an arrival
            say("window_start", t0=t0)
            opened = True
        sleep_until(due)
        t = threading.Thread(target=one, args=(i, req, due), daemon=True)
        t.start()
        threads.append(t)
    sleep_until(t1)
    say("window_end", t1=t1)
    end = t1 + drain_s
    for t in threads:
        t.join(max(0.0, end - time.monotonic()))
    with lock:
        got = list(records)
    seen = {r["i"] for r in got}
    for i, req in enumerate(requests):  # still in flight: unfinished
        if i not in seen:
            got.append(_record(i, req, t0 + req["due_s"], {
                "text": "", "finish": None, "first": None, "last": None,
                "error": "unfinished at the end of the drain", "deltas": [],
                "sent": None, "done": None,
            }))
    return {"t0": t0, "t1": t1, "requests": got, "plan_exhausted": False}


def main(plan_fn) -> int:
    """Entry of a chat generator run as the load process:
    ``python benchmark/generators/<name>.py <ctx.json>``."""
    ctx = json.load(open(sys.argv[1]))
    plan = plan_fn(ctx["traffic"], ctx["seed"], ctx["seconds"], ctx["config"])
    wait_for_server(ctx["port"], ctx["timeout_s"])
    say("server_up")
    beat = Heartbeat()
    beat.start()
    if plan["mode"] == "closed":
        raw = run_closed(ctx["port"], plan["requests"], plan["callers"],
                         ctx["seconds"], ctx["timeout_s"])
    else:
        raw = run_open(ctx["port"], plan["warm"], plan["requests"],
                       plan["lead_s"], ctx["seconds"], plan["drain_s"],
                       ctx["timeout_s"])
    raw["generator_pauses"] = beat.stop()
    json.dump(raw, open(ctx["result"], "w"))
    say("done")
    return 0
