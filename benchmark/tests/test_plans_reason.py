"""The ``reason-16`` plan: ISSUE 52's parameters letter for letter, the
schedule the same for every seed, the ids the seed's and inside the
vocabulary slice, nothing repeats and everything fits ``max_seq``."""
import json

from conftest import BENCH

import closed_loop_reason as gen

TRAFFIC = json.loads((BENCH / "traffic" / "reason-16.json").read_text())
RAW = json.loads((BENCH / "configs" / "zaya1-8b-pp2.json").read_text())
CONFIG = {"model": {k: v for k, v in RAW.items() if k != "bench"}}


def test_the_parameters_are_the_issues():
    assert TRAFFIC["callers"] == 16 and TRAFFIC["shape_seed"] == 20261004
    assert TRAFFIC["prompt_tokens"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.6, "min": 256, "max": 4096}
    assert TRAFFIC["output_tokens"] == {"dist": "uniform", "min": 1024, "max": 3072}
    assert TRAFFIC["generator"] == "closed_loop_reason" and TRAFFIC["block"] == 64
    assert TRAFFIC["reference_sample"] == 4


def test_lengths_lie_inside_their_clips_and_fit_max_seq():
    layout = gen.block_layout(TRAFFIC)
    prompts = sorted(s["prompt_tokens"] for s in layout)
    outputs = sorted(s["max_tokens"] for s in layout)
    assert len(layout) == 64
    assert prompts[0] >= 256 and prompts[-1] <= 4096
    assert prompts[32] in range(950, 1100)  # the median
    assert outputs[0] >= 1024 and outputs[-1] <= 3072
    llm = RAW["bench"]["node_env"]["llm"]
    assert outputs[-1] <= int(llm["DORA_MAX_NEW_TOKENS"]) == 3072
    # prompt + output + the chat template, and the audit's 32 + 18 tokens beyond
    assert 4096 + 3072 + 64 + 50 <= int(llm["DORA_MAX_SEQ"]) == RAW[
        "max_position_embeddings"] == 8192
    assert max(RAW["bench"]["reference"]["pads"]) == 8192
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
    assert vocab == 131136 < 62 ** 3  # every id has a three-character code
    assert all(0 <= t < vocab for r in a for t in r["ids"])
    assert max(t for r in a for t in r["ids"]) > vocab * 0.99  # the whole slice is drawn from


def test_nothing_repeats_and_the_plan_outlasts_the_window():
    reqs = gen.plan(TRAFFIC, 9, 45, CONFIG)["requests"]
    assert all(r["twin_of"] is None for r in reqs)
    heads = [tuple(r["ids"][:64]) for r in reqs[16:]]
    assert len(set(heads)) == len(heads)
    # 16 callers x 45 s / (1,024 tokens x some 8 ms) is under 90 requests due
    assert len(reqs) - 16 >= 2 * 90


def test_the_plan_is_the_long_context_generators_and_the_measure_is_zayas():
    import closed_loop_long_ctx as base

    assert gen.plan is base.plan and gen.block_layout is base.block_layout
    assert gen.measure is not base.measure
    assert "chat_measure_zaya" in gen.measure.__code__.co_names


def test_the_tiny_plan_fits_the_tiny_context():
    tiny = {**TRAFFIC, **TRAFFIC["tiny"]}
    layout = gen.block_layout(tiny)
    env = RAW["bench"]["tiny"]["node_env"]["llm"]
    seq, window = int(env["DORA_MAX_SEQ"]), int(env["DORA_MULTISTEP_K"])
    spare = RAW["bench"]["tiny"]["reference"]["audit_decode"] + 2 * window + 2
    assert max(s["max_tokens"] for s in layout) <= int(env["DORA_MAX_NEW_TOKENS"])
    assert max(s["prompt_tokens"] + s["max_tokens"] for s in layout) + spare + 64 <= seq
    # prompts shorter and longer than a chunk, ragged against the page
    chunk, page = int(env["DORA_PREFILL_CHUNK"]), int(env["DORA_PAGE_SIZE"])
    lengths = [s["prompt_tokens"] for s in layout]
    assert min(lengths) < chunk < max(lengths) and any(n % page for n in lengths)
