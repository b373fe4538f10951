"""The plan of ``closed_loop_mixed_len`` (``traffic/mixed-len-16.json``):
exactly one long prompt in every four consecutive slots, one schedule for
every seed, ids inside the configuration's vocabulary slice, no repeats."""
import json

import pytest
from conftest import BENCH

import closed_loop_mixed_len as gen

TRAFFIC = json.loads((BENCH / "traffic" / "mixed-len-16.json").read_text())
CONFIG = {"model": {"vocab_size": json.loads(
    (BENCH / "configs" / "k-exaone-236b-ep8.json").read_text())["vocab_size"]}}


def plan(seed, seconds=45.0, traffic=TRAFFIC):
    return gen.plan(traffic, seed, seconds, CONFIG)


def test_the_traffic_file_holds_the_issues_parameters_letter_for_letter():
    assert TRAFFIC["callers"] == 16 and TRAFFIC["long_every"] == 4
    assert TRAFFIC["long_prompt_tokens"] == {
        "dist": "lognormal", "median": 4096, "sigma": 0.6, "min": 2048, "max": 15360}
    assert TRAFFIC["prompt_tokens"] == {
        "dist": "lognormal", "median": 160, "sigma": 0.8, "min": 16, "max": 768}
    assert TRAFFIC["output_tokens"] == {"dist": "uniform", "min": 128, "max": 512}
    assert TRAFFIC["shape_seed"] == 20261001
    assert CONFIG["model"]["vocab_size"] == 19200


def test_exactly_one_long_in_every_four_consecutive_slots():
    reqs = plan(7)["requests"][TRAFFIC["callers"]:]
    assert len(reqs) == int(TRAFFIC["max_requests_per_s"] * 45)
    flags = [r["long"] for r in reqs]
    assert all(sum(flags[p:p + 4]) == 1 for p in range(len(flags) - 3))
    for r in reqs:
        lo, hi = (2048, 15360) if r["long"] else (16, 768)
        assert lo <= r["prompt_tokens"] <= hi and 128 <= r["max_tokens"] <= 512
        # prompt + output inside the server's max_seq
        assert r["prompt_tokens"] + r["max_tokens"] <= 16384
    longs = sorted(r["prompt_tokens"] for r in reqs[:64] if r["long"])
    assert len(longs) == 16 and longs[0] == 2048 and longs[-1] > 12000
    assert 3800 < longs[7] < 4400  # the block's median long prompt, about 4096


@pytest.mark.parametrize("other", [8, 2 ** 31 + 5])
def test_the_schedule_is_the_shape_seeds_and_the_ids_the_seeds(other):
    a, b = plan(7)["requests"], plan(other)["requests"]
    assert [(r["prompt_tokens"], r["max_tokens"], r.get("long")) for r in a] == [
        (r["prompt_tokens"], r["max_tokens"], r.get("long")) for r in b]
    assert [r["ids"] for r in a[16:24]] != [r["ids"] for r in b[16:24]]
    assert plan(7)["requests"][20]["ids"] == a[20]["ids"]  # pure


def test_ids_lie_inside_the_slice_and_no_prompt_repeats():
    reqs = plan(2 ** 31 + 11)["requests"]
    vocab = CONFIG["model"]["vocab_size"]
    assert all(0 <= t < vocab for r in reqs for t in r["ids"])
    assert max(t for r in reqs for t in r["ids"]) > vocab - 64  # the whole slice
    assert all(r["twin_of"] is None for r in reqs)
    texts = [r["text"] for r in reqs[TRAFFIC["callers"]:]]
    assert len(set(texts)) == len(texts)
    assert all(len(r["text"]) == 3 * r["prompt_tokens"] for r in reqs)


def test_a_slots_ids_do_not_depend_on_the_plans_length():
    short, long_ = plan(7, 4.0)["requests"], plan(7, 45.0)["requests"]
    assert short == long_[: len(short)]


def test_quantiles_are_dealt_evenly_over_the_blocks_groups():
    layout = gen.block_layout(TRAFFIC)
    assert len(layout) == 64
    groups = [layout[g:g + 16] for g in range(0, 64, 16)]
    for group in groups:
        longs = sorted(s["prompt_tokens"] for s in group if s["long"])
        assert len(longs) == 4
    # each group holds one long prompt of every quarter of the 16 quantiles
    ordered = sorted(s["prompt_tokens"] for s in layout if s["long"])
    for group in groups:
        ranks = sorted(ordered.index(s["prompt_tokens"]) // 4
                       for s in group if s["long"] and s["prompt_tokens"] > 2048)
        assert len(set(ranks)) == len(ranks)
    assert gen.block_layout(TRAFFIC) == layout  # one schedule, every run


def test_the_tiny_plan_wraps_a_ring_of_eight_rows_several_times():
    tiny = {**TRAFFIC, **TRAFFIC["tiny"]}
    reqs = gen.plan(tiny, 3, 4.0, {"model": {"vocab_size": 512}})["requests"][4:]
    assert all(r["prompt_tokens"] >= 40 for r in reqs if r["long"])
    assert sum(r["long"] for r in reqs[:16]) == 4
