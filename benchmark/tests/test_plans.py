"""The generators reproduce their schedule from a seed, differ across
seeds, and give every seed the same set of sizes and gaps."""
import json
from collections import Counter

import chat_plan
import closed_loop_chat
import open_loop_chat
import camera_cycle
from conftest import BENCH

CONFIG = {"model": {"vocab_size": 151936}}


def traffic(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def test_closed_loop_plan_is_seeded():
    t = traffic("callers-16")
    a = closed_loop_chat.plan(t, 5, 10.0, CONFIG)
    b = closed_loop_chat.plan(t, 5, 10.0, CONFIG)
    c = closed_loop_chat.plan(t, 6, 10.0, CONFIG)
    assert a == b
    assert [r["ids"] for r in a["requests"]] != [r["ids"] for r in c["requests"]]
    assert a["callers"] == 16 and a["mode"] == "closed"
    # the same set of sizes in another order
    sizes = lambda p: Counter((r["max_tokens"]) for r in p["requests"][16:16 + 320])
    assert sizes(a) == sizes(c)
    fresh = lambda p: Counter(r["prompt_tokens"] for r in p["requests"][16:16 + 320] if r["twin_of"] is None)
    assert fresh(a) == fresh(c)


def test_lengths_follow_the_traffic_file():
    t = traffic("callers-16")
    plan = closed_loop_chat.plan(t, 1, 10.0, CONFIG)["requests"]
    warm, reqs = plan[:16], plan[16:]
    # the warm wave: short prompts whose outputs end a decode window apart
    assert [r["max_tokens"] for r in warm] == [8 * (j + 1) for j in range(16)]
    assert all(r["prompt_tokens"] == 32 and r["twin_of"] is None for r in warm)
    prompts = sorted(r["prompt_tokens"] for r in reqs if r["twin_of"] is None)
    assert prompts[0] >= 16 and prompts[-1] <= 768
    assert 140 <= prompts[len(prompts) // 2] <= 180  # median 160
    outs = [r["max_tokens"] for r in reqs]
    assert min(outs) >= 64 and max(outs) <= 256
    assert 155 <= sum(outs) / len(outs) <= 165
    # one request in eight repeats an earlier prompt of its group
    for k, r in enumerate(reqs):
        if k % 8 == 7:
            assert r["twin_of"] is not None and k - 7 <= r["twin_of"] - 16 < k
            assert r["ids"] == plan[r["twin_of"]]["ids"]
        else:
            assert r["twin_of"] is None
    # text is the ids, three characters a token
    assert all(len(r["text"]) == 3 * r["prompt_tokens"] for r in reqs)


def test_open_loop_holds_exactly_rate_times_seconds():
    t = traffic("chat-open")
    for seed in (1, 2, 2 ** 31 + 11):
        p = open_loop_chat.plan(t, seed, 20.0, CONFIG)
        inside = [r["due_s"] for r in p["requests"] if r["due_s"] >= 0]
        lead = [r["due_s"] for r in p["requests"] if r["due_s"] < 0]
        assert len(inside) == round(t["rate_per_s"] * 20.0)
        assert len(lead) == round(t["rate_per_s"] * t["lead_s"])
        assert all(0 <= d < 20.0 for d in inside) and inside == sorted(inside)
        assert all(-t["lead_s"] <= d < 0 for d in lead)
        assert len(p["warm"]) == t["warm_wave"]
    a = open_loop_chat.plan(t, 1, 20.0, CONFIG)
    assert a == open_loop_chat.plan(t, 1, 20.0, CONFIG)
    b = open_loop_chat.plan(t, 2, 20.0, CONFIG)
    assert [r["ids"] for r in a["requests"]] != [r["ids"] for r in b["requests"]]


def test_every_seed_gets_the_same_schedule_and_other_words():
    t = traffic("chat-open")
    a = open_loop_chat.plan(t, 1, 45.0, CONFIG)
    b = open_loop_chat.plan(t, 2, 45.0, CONFIG)
    shape = lambda p: [(r["due_s"], r["prompt_tokens"], r["max_tokens"], r["twin_of"])
                       for r in p["requests"]]
    assert shape(a) == shape(b)
    assert [r["ids"] for r in a["requests"]] != [r["ids"] for r in b["requests"]]
    gaps = [y - x for x, y in zip([0.0] + chat_plan.arrivals(10.0, 20.0, 99),
                                  chat_plan.arrivals(10.0, 20.0, 99))]
    want = chat_plan.exponential_gaps(200)
    scale = 20.0 / (sum(want) + 1.0)
    assert sorted(round(g, 9) for g in gaps) == sorted(round(g * scale, 9) for g in want)
    # the block layout, over and over
    layout = [x["max_tokens"] for x in chat_plan.block_layout(t)]
    outs = [r["max_tokens"] for r in chat_plan.requests(t, 3, 128, 151936)]
    assert outs == layout + layout


def test_camera_frames_are_seeded():
    cfg = {"as_run": {"image_size": 32}}
    a = camera_cycle.plan({"frames": 8}, 9, 0.0, cfg)["frames"]
    b = camera_cycle.plan({"frames": 8}, 9, 0.0, cfg)["frames"]
    c = camera_cycle.plan({"frames": 8}, 10, 0.0, cfg)["frames"]
    assert len(a) == 8 and a[0].shape == (32, 32, 3) and a[0].dtype.name == "uint8"
    assert all((x == y).all() for x, y in zip(a, b))
    assert not all((x == y).all() for x, y in zip(a, c))
