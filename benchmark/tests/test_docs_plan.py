"""The document-QA generator: every document asked 4 times at least 24
plan positions apart, one schedule for every seed, ids inside the
vocabulary slice, lengths from the traffic file."""
import json
from collections import defaultdict

import closed_loop_docs
from conftest import BENCH

CONFIG = {"model": {"vocab_size": 20480}}
TRAFFIC = json.loads((BENCH / "traffic" / "doc-qa-16.json").read_text())


def test_asks_and_spacing():
    slots = closed_loop_docs.schedule(TRAFFIC, 400)
    at = defaultdict(list)
    for p, s in enumerate(slots):
        at[s["doc"]].append((p, s["ask"]))
    whole = [v for d, v in at.items() if d >= 0 and v[0][0] + 3 * 25 < 400]
    assert len(whole) > 70
    for asks in whole:  # a document inside the plan: asks 0, 1, 2, 3 in order
        assert [k for _, k in asks] == [0, 1, 2, 3]
    for asks in at.values():  # run-in and cut documents too: never closer
        assert len(asks) <= 4
        assert all(b[0] - a[0] >= 24 for a, b in zip(asks, asks[1:]))
    # steady state: one first ask in every four slots
    assert sum(s["ask"] == 0 for s in slots[100:300]) == 50
    assert all(len({s["document_tokens"] for _, s in
                    [(p, slots[p]) for p, _ in asks]}) == 1 for asks in at.values())


def test_lengths_follow_the_traffic_file():
    slots = closed_loop_docs.schedule(TRAFFIC, 512)
    docs = sorted({s["doc"]: s["document_tokens"] for s in slots if 0 <= s["doc"] < 32}.values())
    assert docs[0] >= 512 and docs[-1] <= 8192 and len(docs) == 32
    assert 1900 <= docs[16] <= 2200  # median 2048
    assert {s["question_tokens"] for s in slots} <= set(range(32, 65))
    outs = [s["max_tokens"] for s in slots]
    assert min(outs) >= 32 and max(outs) <= 96 and 62 <= sum(outs) / len(outs) <= 66


def test_same_schedule_for_every_seed_and_ids_inside_the_slice():
    a = closed_loop_docs.plan(TRAFFIC, 5, 10.0, CONFIG)
    b = closed_loop_docs.plan(TRAFFIC, 5, 10.0, CONFIG)
    c = closed_loop_docs.plan(TRAFFIC, 2 ** 31 + 11, 10.0, CONFIG)
    assert a == b and a["mode"] == "closed" and a["callers"] == 16
    assert len(a["requests"]) == 16 + 60
    shape = lambda p: [(r.get("doc"), r.get("ask"), r["prompt_tokens"], r["max_tokens"])
                       for r in p["requests"]]
    assert shape(a) == shape(c)
    assert [r["ids"] for r in a["requests"]] != [r["ids"] for r in c["requests"]]
    warm, reqs = a["requests"][:16], a["requests"][16:]
    assert [r["max_tokens"] for r in warm] == [8 * (j + 1) for j in range(16)]
    for r in reqs:
        assert 0 <= min(r["ids"]) and max(r["ids"]) < 20480
        assert len(r["text"]) == 3 * r["prompt_tokens"]
        assert r["prompt_tokens"] == r["document_tokens"] + len(r["ids"]) - r["document_tokens"]
    # asks of one document share the document and end in different questions
    by_doc = defaultdict(list)
    for r in closed_loop_docs.requests(TRAFFIC, 7, 120, 20480):
        by_doc[r["doc"]].append(r)
    pairs = [v for v in by_doc.values() if len(v) >= 2]
    assert pairs
    for v in pairs:
        n = v[0]["document_tokens"]
        assert v[0]["ids"][:n] == v[1]["ids"][:n] and v[0]["ids"][n:] != v[1]["ids"][n:]


def test_reference_sample_holds_a_first_and_a_repeat_ask():
    import docs_measure

    plan = {"requests": [{"ask": k % 4} for k in range(40)]}
    done = [{"i": i} for i in range(16, 40)]
    for seed in (1, 2, 2 ** 31 + 3):
        got = docs_measure.sample_requests(done, plan, seed, 4)
        asks = [plan["requests"][r["i"]]["ask"] for r in got]
        assert len(got) == 4 and 0 in asks and any(k > 0 for k in asks)
        assert got == docs_measure.sample_requests(done, plan, seed, 4)
    only_repeats = [r for r in done if plan["requests"][r["i"]]["ask"]]
    assert len(docs_measure.sample_requests(only_repeats, plan, 1, 4)) == 4
