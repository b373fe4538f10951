"""The ``agents-64`` plan: ISSUE 58's parameters letter for letter, 16
callers a prefix, every request its caller's prefix + a fresh tail, the
warm wave each prefix alone twice in turn, the schedule the same for every
seed, the ids the seed's, every prompt + answer inside ``max_seq``."""
import json

from conftest import BENCH, ROOT

import closed_loop_agents as gen

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = next(w for w in MANIFEST["workloads"] if w["name"] == "kimi-linear-48b-ep4.agents-64")
TRAFFIC = json.loads((BENCH / "traffic" / f"{CELL['traffic']}.json").read_text())
RAW = json.loads((BENCH / "configs" / f"{CELL['config']}.json").read_text())
CONFIG = {"model": {k: v for k, v in RAW.items() if k != "bench"}}
LLM = RAW["bench"]["node_env"]["llm"]


def test_the_parameters_are_the_issues():
    assert TRAFFIC["callers"] == 64 == int(LLM["DORA_BATCH_SLOTS"])
    assert TRAFFIC["prefixes"] == 4 and TRAFFIC["shape_seed"] == 20261004
    assert TRAFFIC["prefix_tokens"] == {"dist": "uniform", "min": 6144, "max": 12288}
    assert TRAFFIC["tail_tokens"] == {"dist": "uniform", "min": 64, "max": 256}
    assert TRAFFIC["output_tokens"] == {"dist": "uniform", "min": 512, "max": 1536}
    assert TRAFFIC["warm_output_tokens"] == 8 and TRAFFIC["reference_sample"] == 3
    # a capture long enough to hold a chunk: a request needs one or two, 5 a second
    assert TRAFFIC["trace_seconds"] == 2.0
    assert TRAFFIC["generator"] == "closed_loop_agents"
    assert CELL["chips"] == 1 and len(CELL["why"]) <= 200


def test_the_schedule_is_pure_and_its_lengths_lie_inside_their_ranges():
    made = gen.schedule(TRAFFIC)
    assert made == gen.schedule(json.loads(json.dumps(TRAFFIC)))
    prefixes = made["prefix_tokens"]
    # one in each quarter of the range, aligned to no page (so to no chunk)
    assert len(prefixes) == 4 and prefixes == sorted(prefixes)
    for g, n in enumerate(prefixes):
        assert 6144 + 1536 * g <= n <= 6144 + 1536 * (g + 1) and n % 16
    assert len(made["callers"]) == 64
    requests = [r for mine in made["callers"] for r in mine]
    assert len(requests) == 64 * TRAFFIC["requests_per_caller"]
    tails = sorted(r["tail_tokens"] for r in requests)
    outs = sorted(r["max_tokens"] for r in requests)
    assert 64 <= tails[0] and tails[-1] <= 256 and 512 <= outs[0] and outs[-1] <= 1536
    assert abs(sum(outs) / len(outs) - 1024) < 2 and abs(sum(tails) / len(tails) - 160) < 2
    assert outs[-1] <= int(LLM["DORA_MAX_NEW_TOKENS"])
    # the longest request, and the audit's decode beyond it, fit ONE reference pad
    longest = max(prefixes) + tails[-1] + outs[-1]
    assert longest + RAW["bench"]["reference"]["audit_decode"] <= min(
        RAW["bench"]["reference"]["pads"]) == int(LLM["DORA_MAX_SEQ"]) == RAW["model_max_length"]
    # the warm wave: every prefix cold, then every prefix again
    assert [w["prefix"] for w in made["warm"]] == [0, 1, 2, 3, 0, 1, 2, 3]
    assert all(64 <= w["tail_tokens"] <= 256 for w in made["warm"])


def test_sixteen_callers_a_prefix_and_every_prompt_begins_with_it():
    plan = gen.plan(TRAFFIC, 5, 45.0, CONFIG)
    assert plan["callers"] == 64 and len(plan["prefixes"]) == 4
    by_prefix = {}
    for c, mine in enumerate(plan["requests"]):
        assert {r["prefix"] for r in mine} == {c % 4}
        by_prefix.setdefault(c % 4, []).append(c)
    assert all(len(v) == 16 for v in by_prefix.values())
    seen = set()
    for mine in plan["requests"]:
        for r in mine[:3]:
            ids = gen.prompt_ids(plan, r)
            prefix = plan["prefixes"][r["prefix"]]
            assert ids[: len(prefix)] == prefix and len(ids) == len(prefix) + r["tail_tokens"]
            assert all(0 <= t < RAW["vocab_size"] for t in r["tail_ids"])
            seen.add(tuple(r["tail_ids"][:8]))
    assert len(seen) == 64 * 3  # no two tails alike
    # two prefixes share no page, two warm prompts of one prefix its pages alone
    assert plan["prefixes"][0][:16] != plan["prefixes"][1][:16]
    first, second = plan["warm"][0], plan["warm"][4]
    assert first["prefix"] == second["prefix"] == 0
    assert first["tail_ids"][:4] != second["tail_ids"][:4]


def test_the_seed_gives_the_ids_and_nothing_else():
    a, b = gen.plan(TRAFFIC, 5, 45.0, CONFIG), gen.plan(TRAFFIC, 4200003507, 45.0, CONFIG)
    assert a == gen.plan(TRAFFIC, 5, 45.0, CONFIG)

    def shape(plan):
        return ([len(p) for p in plan["prefixes"]],
                [[(r["tail_tokens"], r["max_tokens"]) for r in mine]
                 for mine in plan["requests"]],
                [(w["prefix"], w["tail_tokens"]) for w in plan["warm"]])

    assert shape(a) == shape(b)
    assert a["prefixes"][0] != b["prefixes"][0]
    assert a["requests"][0][0]["tail_ids"] != b["requests"][0][0]["tail_ids"]


def test_half_of_the_prefixes_branch_in_this_schedule():
    """A prefix BRANCHES where its cold warm prompt's last full chunk edge
    lies past the prefix's whole pages; where it lies inside them, the cold
    prompt's own snapshot already serves (PR 56's rule). The audit takes
    its first sample from a prefix that branches."""
    import cache_audit_kimi_linear as audit

    plan = gen.plan(TRAFFIC, 7, 45.0, CONFIG)
    branching = []
    for g in range(4):
        befores = [gen.prompt_ids(plan, plan["warm"][j * 4 + g]) for j in range(2)]
        edge = audit.branch_edge(befores, 16, 256)
        shared = len(plan["prefixes"][g]) // 16 * 16
        assert shared - 256 < edge <= shared
        if len(befores[0]) // 256 * 256 > edge:
            branching.append(g)
    assert branching == [0, 2]


def test_the_tiny_plan_branches_too():
    tiny = {**TRAFFIC, **TRAFFIC["tiny"]}
    made = gen.schedule(tiny)
    assert len(made["callers"]) == 6 and len(made["prefix_tokens"]) == 2
    assert all(n % 16 for n in made["prefix_tokens"])
    # tails longer than a tiny chunk (32): a cold prompt's last full chunk
    # edge lies past its prefix
    assert min(r["tail_tokens"] for mine in made["callers"] for r in mine) >= 34
