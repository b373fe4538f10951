"""The ``sessions-16`` plan: ISSUE 56's parameters letter for letter, turn
n's prompt extends turn n - 1's token for token, the schedule the same for
every seed, the ids the seed's, every prompt + answer inside ``max_seq``
less its reserve, no two conversations share text."""
import json

from conftest import BENCH, ROOT

import closed_loop_sessions as gen

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = next(w for w in MANIFEST["workloads"] if w["name"] == "olmo-hybrid-7b-pp2.sessions-16")
TRAFFIC = json.loads((BENCH / "traffic" / f"{CELL['traffic']}.json").read_text())
RAW = json.loads((BENCH / "configs" / f"{CELL['config']}.json").read_text())
CONFIG = {"model": {k: v for k, v in RAW.items() if k != "bench"}}
MAX_SEQ = RAW["max_position_embeddings"]


def test_the_parameters_are_the_issues():
    assert TRAFFIC["callers"] == 16 and TRAFFIC["shape_seed"] == 20261004
    assert TRAFFIC["first_prompt_tokens"] == {
        "dist": "lognormal", "median": 2048, "sigma": 0.5, "min": 1024, "max": 6144}
    assert TRAFFIC["turns"] == {"dist": "uniform", "min": 4, "max": 8}
    assert TRAFFIC["message_tokens"] == {"dist": "uniform", "min": 32, "max": 128}
    assert TRAFFIC["output_tokens"] == {"dist": "uniform", "min": 128, "max": 384}
    assert TRAFFIC["row_reserve"] == 512 and MAX_SEQ == 12288
    assert TRAFFIC["generator"] == "closed_loop_sessions"
    assert CELL["chips"] == 1 and len(CELL["why"]) <= 200


def test_lengths_lie_inside_their_clips_and_fit_max_seq():
    schedule = gen.schedule(TRAFFIC, MAX_SEQ)
    assert len(schedule) == 16
    conversations = [c for caller in schedule for c in caller]
    assert len(conversations) == 16 * TRAFFIC["conversations_per_caller"]
    firsts = sorted(c["first_tokens"] for c in conversations)
    assert firsts[0] >= 1024 and firsts[-1] <= 6144
    assert firsts[len(firsts) // 2] in range(1900, 2200)  # the median
    llm = RAW["bench"]["node_env"]["llm"]
    for c in conversations:
        turns = c["turns"]
        # a first prompt and 4-8 follow-ups, fewer only where the rows ran out
        assert 1 <= len(turns) <= 9 and turns[0]["message_tokens"] == 0
        assert turns[0]["prompt_tokens"] == c["first_tokens"]
        assert len(turns) >= 5 or (
            turns[-1]["prompt_tokens"] + turns[-1]["max_tokens"] + 32 + 128
            > MAX_SEQ - 512 - 128 - 384)
        for before, t in zip(turns, turns[1:]):
            assert 32 <= t["message_tokens"] <= 128
            assert t["prompt_tokens"] == (
                before["prompt_tokens"] + before["max_tokens"] + t["message_tokens"])
        for t in turns:
            assert 128 <= t["max_tokens"] <= 384 <= int(llm["DORA_MAX_NEW_TOKENS"])
            assert t["prompt_tokens"] + t["max_tokens"] <= MAX_SEQ - 512
    longest = max(t["prompt_tokens"] + t["max_tokens"] for c in conversations
                  for t in c["turns"])
    # the audit decodes a few tokens beyond, and the reference pads to ONE size
    assert longest + RAW["bench"]["reference"]["audit_decode"] <= min(
        RAW["bench"]["reference"]["pads"])
    assert int(llm["DORA_MAX_SEQ"]) == MAX_SEQ


def test_turn_n_extends_turn_n_minus_1_token_for_token():
    plan = gen.plan(TRAFFIC, 5, 45, CONFIG)
    conv = plan["sessions"][3][1]
    # whatever the model answers (here: its max_tokens of a fixed id)
    answers = [[7] * t["max_tokens"] for t in conv["turns"]]
    before = gen.prompt_ids(conv, 0, answers)
    assert before == conv["first_ids"]
    for turn in range(1, len(conv["turns"])):
        prompt = gen.prompt_ids(conv, turn, answers)
        assert prompt[: len(before)] == before
        assert prompt[len(before) :] == answers[turn - 1] + conv["turns"][turn]["message_ids"]
        assert len(prompt) == conv["turns"][turn]["prompt_tokens"]
        before = prompt


def test_the_schedule_is_the_same_for_every_seed_and_the_ids_are_the_seeds():
    a = gen.plan(TRAFFIC, 5, 45, CONFIG)["sessions"]
    b = gen.plan(TRAFFIC, 2 ** 31 + 77, 45, CONFIG)["sessions"]

    def shape(sessions):
        return [[(len(c["first_ids"]), [(len(t["message_ids"]), t["max_tokens"])
                                        for t in c["turns"]]) for c in caller]
                for caller in sessions]

    assert shape(a) == shape(b) == [
        [(c["first_tokens"], [(t["message_tokens"], t["max_tokens"]) for t in c["turns"]])
         for c in caller] for caller in gen.schedule(TRAFFIC, MAX_SEQ)]
    assert all(x["first_ids"] != y["first_ids"]
               for ca, cb in zip(a, b) for x, y in zip(ca, cb))
    again = gen.plan(TRAFFIC, 5, 45, CONFIG)["sessions"]
    assert again == a
    vocab = RAW["vocab_size"]
    assert vocab == 100352 < 62 ** 3  # every id has a three-character code
    ids = [t for caller in a for c in caller for t in c["first_ids"]]
    assert min(ids) >= 0 and max(ids) < vocab and max(ids) > vocab * 0.99


def test_no_two_conversations_share_text_and_the_plan_outlasts_the_window():
    sessions = gen.plan(TRAFFIC, 9, 45, CONFIG)["sessions"]
    heads = {tuple(c["first_ids"][:16]) for caller in sessions for c in caller}
    assert len(heads) == 16 * TRAFFIC["conversations_per_caller"]
    # 45 s and the warm turn at some 15 ms a token and a second a cold prompt:
    # a caller's plan holds several times the tokens it can be sent
    for caller in sessions:
        out = sum(t["max_tokens"] for c in caller for t in c["turns"])
        assert out * 0.010 > 2 * 45
