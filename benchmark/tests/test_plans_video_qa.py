"""The ``video-qa-16`` plan: every prompt ends past ``sa_config.topk``, the
schedule is the same for every seed, the ids are the seed's and lie
inside the vocabulary slice, nothing repeats and everything fits
``max_seq``."""
import json

from conftest import BENCH

import closed_loop_video_qa as gen

TRAFFIC = json.loads((BENCH / "traffic" / "video-qa-16.json").read_text())
RAW = json.loads((BENCH / "configs" / "keye-vl2-30b-ep8.json").read_text())
CONFIG = {"model": {k: v for k, v in RAW.items() if k != "bench"}}


def test_the_parameters_are_the_issues():
    assert TRAFFIC["callers"] == 16 and TRAFFIC["shape_seed"] == 20261003
    assert TRAFFIC["prompt_tokens"] == {
        "dist": "lognormal", "median": 6144, "sigma": 0.4, "min": 4096, "max": 12288}
    assert TRAFFIC["output_tokens"] == {"dist": "uniform", "min": 512, "max": 2048}
    assert TRAFFIC["generator"] == "closed_loop_video_qa" and TRAFFIC["block"] == 64


def test_every_prompt_ends_past_topk_and_fits_max_seq():
    layout = gen.block_layout(TRAFFIC)
    prompts = sorted(s["prompt_tokens"] for s in layout)
    outputs = sorted(s["max_tokens"] for s in layout)
    assert len(layout) == 64
    assert prompts[0] == 4096 > RAW["sa_config"]["topk"] and prompts[-1] <= 12288
    assert prompts[32] in range(5900, 6400)  # the median
    assert outputs[0] >= 512 and outputs[-1] <= 2048
    llm = RAW["bench"]["node_env"]["llm"]
    assert outputs[-1] <= int(llm["DORA_MAX_NEW_TOKENS"])
    assert 12288 + 2048 + 64 <= int(llm["DORA_MAX_SEQ"]) == RAW["max_position_embeddings"]
    # every group of 16 holds one value of every stratum: the long ones are dealt evenly
    for g in range(4):
        group = [s["prompt_tokens"] for s in layout[16 * g : 16 * g + 16]]
        assert max(group) >= prompts[-4] and min(group) <= prompts[3]


def test_the_schedule_is_the_same_for_every_seed_and_the_ids_are_the_seeds():
    a = gen.plan(TRAFFIC, 5, 4, CONFIG)["requests"]
    b = gen.plan(TRAFFIC, 2 ** 31 + 77, 4, CONFIG)["requests"]
    assert len(a) == len(b) == 16 + int(TRAFFIC["max_requests_per_s"] * 4)
    assert [(r["prompt_tokens"], r["max_tokens"]) for r in a] == [
        (r["prompt_tokens"], r["max_tokens"]) for r in b]
    assert all(x["ids"] != y["ids"] for x, y in zip(a[16:], b[16:]))
    again = gen.plan(TRAFFIC, 5, 4, CONFIG)["requests"]
    assert [r["ids"] for r in a] == [r["ids"] for r in again]
    vocab = RAW["vocab_size"]
    assert all(0 <= t < vocab for r in a for t in r["ids"])
    assert max(t for r in a for t in r["ids"]) > vocab * 0.99  # the whole slice is drawn from


def test_nothing_repeats():
    reqs = gen.plan(TRAFFIC, 9, 6, CONFIG)["requests"]
    assert all(r["twin_of"] is None for r in reqs)
    heads = [tuple(r["ids"][:64]) for r in reqs[16:]]
    assert len(set(heads)) == len(heads)


def test_the_plan_is_the_long_context_generators_and_the_measure_is_keyes():
    import closed_loop_long_ctx as base

    assert gen.plan is base.plan and gen.block_layout is base.block_layout
    assert gen.measure is not base.measure
    assert "chat_measure_keye_vl2" in gen.measure.__code__.co_names


def test_the_tiny_plan_is_several_times_the_tiny_topk():
    tiny = {**TRAFFIC, **TRAFFIC["tiny"]}
    layout = gen.block_layout(tiny)
    topk = RAW["bench"]["tiny"]["model"]["sa_config"]["topk"]
    assert min(s["prompt_tokens"] for s in layout) >= 2 * topk
    assert max(s["prompt_tokens"] for s in layout) >= 6 * topk
    seq = int(RAW["bench"]["tiny"]["node_env"]["llm"]["DORA_MAX_SEQ"])
    assert max(s["prompt_tokens"] + s["max_tokens"] for s in layout) + 64 <= seq
