"""``loop-chat-16``'s schedule comes from its ``shape_seed`` and is the
same in every run; the run's seed gives the token ids alone."""
import json
from collections import Counter

import chat_plan
import closed_loop_chat_ouro
from conftest import BENCH

CONFIG = {"model": {"vocab_size": 49152}}
TRAFFIC = json.loads((BENCH / "traffic" / "loop-chat-16.json").read_text())


def shape(plan):
    return [(r["prompt_tokens"], r["max_tokens"], r["twin_of"]) for r in plan["requests"]]


def test_every_seed_gets_the_same_schedule_and_other_words():
    a = closed_loop_chat_ouro.plan(TRAFFIC, 5, 45.0, CONFIG)
    b = closed_loop_chat_ouro.plan(TRAFFIC, 2 ** 31 + 35, 45.0, CONFIG)
    assert a == closed_loop_chat_ouro.plan(TRAFFIC, 5, 45.0, CONFIG)
    assert shape(a) == shape(b)
    assert [r["ids"] for r in a["requests"]] != [r["ids"] for r in b["requests"]]
    assert a["mode"] == "closed" and a["callers"] == 16
    assert len(a["requests"]) == 16 + int(40 * 45.0)
    assert max(t for r in b["requests"] for t in r["ids"]) < 49152


def test_the_schedule_is_this_files_own():
    """Another ``shape_seed`` deals the same lengths in another order."""
    base = json.loads((BENCH / "traffic" / "callers-16.json").read_text())
    mine = chat_plan.block_layout(TRAFFIC)
    theirs = chat_plan.block_layout(base)
    assert mine != theirs
    sizes = lambda layout: Counter(x["max_tokens"] for x in layout)
    assert sizes(mine) == sizes(theirs)
    assert mine == chat_plan.block_layout(dict(TRAFFIC))


def test_what_a_request_reserves_of_the_pool():
    """Prompt + max_tokens in whole pages of 16: 16-64 of the pool's 383
    pages a request, some 25 on average, so 16 callers want about 400."""
    reqs = closed_loop_chat_ouro.plan(TRAFFIC, 1, 45.0, CONFIG)["requests"][16:16 + 640]
    pages = [-(-(r["prompt_tokens"] + r["max_tokens"]) // 16) for r in reqs]
    assert 5 <= min(pages) and max(pages) <= 64
    assert 380 <= 16 * sum(pages) / len(pages) <= 420
    twins = [r for r in reqs if r["twin_of"] is not None]
    assert len(twins) == len(reqs) // 8
