"""The cache audit of the ``glm-5p3-flash-ep8`` cell through the harness's
own comparison (``cache_audit_glm5_next.compare`` / ``picked_against``,
``chat_measure_glm5_next.verdict``): a faultless program's readings hold,
each control in the program's place comes out as not correct by the limit
named, and a comparison that cannot tell a control apart is refused."""
import copy

import numpy as np
import pytest

import cache_audit_glm5_next as audit
import chat_measure_glm5_next as measure

KINDS = ["linear_attention"] * 4 + ["deepseek_sparse_attention"]


def test_the_audited_layers():
    assert audit.audited_layers(KINDS) == {"state_first": 0, "state_deep": 3, "pages": 4}
    period = (["linear_attention"] * 3 + ["deepseek_sparse_attention"]) * 2
    assert audit.audited_layers(period) == {"state_first": 0, "state_deep": 6, "pages": 7}


def test_through_bf16_is_one_rounding():
    x = np.asarray([1.0, 1.0 + 2.0 ** -9, 3.14159], np.float32)
    got = audit.through_bf16(x)
    assert got[0] == 1.0 and got[1] in (1.0, 1.0 + 2.0 ** -7) and abs(got[2] - 3.140625) < 1e-6


def scores_and_picks(rows=40, blocks=16, k=4, seed=0):
    rng = np.random.default_rng(seed)
    scores = rng.standard_normal((rows, blocks)).astype(np.float32)
    picked = np.argsort(-scores, axis=1)[:, :k]
    return scores, picked


def test_equal_picked_sets_differ_nowhere():
    scores, picked = scores_and_picks()
    shuffled = picked[:, ::-1]  # the order inside a set does not matter
    got = audit.picked_against(scores, picked, shuffled, 16)
    assert got["picked_rows"] == 24 and got["picked_differ"] == 0.0
    assert got["picked_score_gap"] == 0.0 and got["picked_rank_gap"] == 0.0
    # the first 4 blocks of 16 against the top 4 of random scores: most differ
    assert 0.5 < got["picked_differ_unscored"] < 0.95


def test_a_near_tie_differs_by_a_small_gap_and_a_wrong_block_by_a_large_one():
    scores, picked = scores_and_picks()
    kept = np.sort(scores[20][picked[20]])
    spread = kept[-1] - kept[0]
    # the program took block 15 for the reference's last kept one
    theirs = picked.copy()
    last = picked[20][-1]
    outsider = next(b for b in range(16) if b not in picked[20])
    theirs[20][-1] = outsider
    near = scores.copy()
    near[20][outsider] = near[20][last] - 1e-4
    got = audit.picked_against(near, picked, theirs, 16)
    assert got["picked_differ"] == 1 / (24 * 4)
    assert abs(got["picked_score_gap"] - 1e-4 / spread) < 1e-5
    far = scores.copy()
    far[20][outsider] = far[20][last] - 3 * spread
    assert audit.picked_against(far, picked, theirs, 16)["picked_score_gap"] > 2.9
    assert audit.picked_against(near, picked, theirs, 16)["picked_rank_gap"] == 0.25  # rank 5 of 4
    assert audit.picked_against(far, picked, theirs, 16)["picked_rank_gap"] >= 2.5  # among the last of 16
    # rows below index_topk are not compared
    theirs[3] = (picked[3] + 1) % 16
    assert audit.picked_against(near, picked, theirs, 16)["picked_differ"] == 1 / (24 * 4)
    assert audit.picked_against(near, picked, theirs, 40)["picked_differ"] is None


def stream(seed=1, rows=48, decode=3):
    """An audited stream of ``rows`` positions written, the last ``decode -
    1`` of them by decode ticks, and the reference over the same tokens."""
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    written = rows - 1
    chunk_rows = written - (decode - 1)
    scores, picked = scores_and_picks(written, rows // 4, 4, seed)
    ref = {"state_first": f(4, 8, 8), "state_deep": f(4, 8, 8), "latent": f(rows, 16),
           "index": f(rows // 4, 8), "scores": scores, "picked": picked,
           "attended": f(written, 12)}
    got = {"emitted": list(range(decode)), "prompt_rows": chunk_rows,
           "picked": picked[:chunk_rows].copy(), "picked_decode": picked[chunk_rows:].copy(),
           "attended": ref["attended"][:chunk_rows:8] * (1 + 2e-3),
           "attended_decode": ref["attended"][chunk_rows:] * (1 + 4e-3),
           "state_first": ref["state_first"] * (1 + 1e-3), "state_deep": ref["state_deep"],
           "latent": ref["latent"][:written], "index": ref["index"][: written // 4]}
    return got, ref


def test_compare_reads_every_cache_kind_and_the_controls():
    got, ref = stream()
    controls = {
        "softplus_gate": {"state_first": ref["state_first"] * 2, "state_deep": ref["state_deep"] * 3},
        "one_stream": {"state_first": ref["state_first"], "state_deep": -ref["state_deep"]},
        "no_selection": {"attended": -ref["attended"]},
    }
    read = audit.compare(got, ref, controls, 16)
    assert abs(read["state_first"] - 1e-3) < 1e-5 and read["state_deep"] == 0.0
    assert read["latent_rows"] == 0.0 and read["index_rows"] == 0.0
    assert read["rows"] == 47 and read["emitted"] == 3
    assert 0.0005 < read["state_first_bf16"] < 0.004  # one rounding: about 0.17 %
    assert abs(read["state_first_softplus_gate"] - 0.4995) < 1e-3
    assert abs(read["state_deep_softplus_gate"] - 2 / 3) < 1e-6
    assert read["state_deep_one_stream"] == 2.0
    # the control in the program's place, against the reference
    assert abs(read["state_first_softplus_gate_alone"] - 1.0) < 1e-6
    assert read["state_first_one_stream_alone"] == 0.0
    # a float32 state's values are not bf16 values; through bf16 all of them are
    assert read["state_2byte_share"] < 0.01 and read["state_2byte_share_bf16"] == 1.0
    # chunk rows 16..44 and the two decode ticks' rows 45, 46, apart
    assert read["picked_differ"] == 0.0 and read["picked_rows"] == 45 - 16
    assert read["picked_differ_decode"] == 0.0 and read["picked_rows_decode"] == 2
    # every eighth chunk row at or past index_topk 16: positions 16, 24, 32, 40
    assert abs(read["attended_rows"] - 2e-3) < 1e-5
    assert abs(read["attended_rows_no_selection"] - 2.002) < 1e-3
    assert abs(read["attended_rows_decode"] - 4e-3) < 1e-5
    assert abs(read["attended_rows_decode_no_selection"] - 2.004) < 1e-3
    # a pooled row written one block off, a latent row of another position: seen
    got["index"] = np.roll(got["index"], 1, axis=0)
    got["latent"] = np.roll(got["latent"], 1, axis=0)
    off = audit.compare(got, ref, {}, 16)
    assert off["index_rows"] > 1.0 and off["latent_rows"] > 1.0


def test_a_decode_tick_that_picks_other_blocks_is_seen_apart_from_the_chunks():
    got, ref = stream(rows=64, decode=9)
    # every tick took the first blocks, as a picker that never scored
    got["picked_decode"] = np.broadcast_to(np.arange(4), got["picked_decode"].shape)
    read = audit.compare(got, ref, {}, 16)
    assert read["picked_differ"] == 0.0
    assert read["picked_differ_decode"] == read["picked_differ_unscored_decode"] > 0.5
    assert read["picked_rows_decode"] == 8
    # a stream whose prompt ended below index_topk: only the ticks past it count
    short, ref = stream(rows=24, decode=12)
    assert short["prompt_rows"] == 12
    read = audit.compare(short, ref, {}, 16)
    assert read["picked_rows"] == 0 and read["picked_differ"] is None
    assert read["picked_rows_decode"] == 23 - 16 and read["picked_differ_decode"] == 0.0
    assert "attended_rows" not in read and abs(read["attended_rows_decode"] - 4e-3) < 1e-5


def test_the_ticks_of_a_slots_windows_come_out_by_position():
    k, n, dim = 4, 3, 5
    windows = [(first, np.full((k, n), first) + np.arange(k)[:, None],
                np.full((k, dim), float(first)) + np.arange(k)[:, None])
               for first in (20, 24, 28)]
    got = audit.ticks(windows, 21, 30)
    assert got["picked_decode"].shape == (9, n) and got["attended_decode"].shape == (9, dim)
    assert got["picked_decode"][:, 0].tolist() == list(range(21, 30))
    assert got["attended_decode"][:, 0].tolist() == [float(t) for t in range(21, 30)]
    with pytest.raises(KeyError):
        audit.ticks(windows, 21, 40)  # a tick the audit never saw


def reference_line(**over):
    """What a faultless program's run prints, in the verdict's terms."""
    row = {"state_first": 0.002, "state_deep": 0.02, "latent_rows": 0.01, "index_rows": 0.01,
           "attended_rows": 0.2, "attended_rows_decode": 0.2,
           "picked_rows": 900, "picked_differ": 0.05, "picked_score_gap": 0.4,
           "picked_differ_unscored": 0.3, "picked_rank_gap": 0.1,
           "picked_rows_decode": 66, "picked_differ_decode": 0.05,
           "picked_differ_unscored_decode": 0.3,
           "state_first_bf16": 0.0027, "state_2byte_share": 0.0001,
           "state_2byte_share_bf16": 1.0}
    rows = [dict(row) for _ in range(4)]
    rows[3]["attended_rows_no_selection"] = 0.8
    rows[3]["attended_rows_decode_no_selection"] = 0.8
    rows[3]["picked_differ_unscored"] = 0.6  # the longest sample
    rows[3]["picked_differ_unscored_decode"] = 0.7
    rows[0].update(state_first_softplus_gate=0.6, state_deep_softplus_gate=0.9,
                   state_first_one_stream=0.002, state_deep_one_stream=0.8)
    ref = {
        "samples": [{"max_deficit_bf16_ulps": 12.0}] * 4,
        "what_if": {name: {"least_deficit_bf16_ulps": 400.0} for name in
                    ("no_selection", "softplus_gate", "one_stream")},
        "cache": {"rows": rows},
    }
    ref.update(over)
    return ref


def verdict(ref, token_bytes=1088, picked_share=0.35):
    return measure.verdict(ref, 0, 50, token_bytes, picked_share)


def test_a_faultless_run_holds():
    compared, holds = verdict(reference_line())
    assert holds, compared
    assert compared["controls_refused"]["value"] == len(measure.CONTROLS) == 7


@pytest.mark.parametrize("key,value,by", [
    ("state_first", 0.05, "state_first_rel_err"),
    ("state_2byte_share", 1.0, "state_2byte_share"),   # a 2-byte state
    ("state_deep", 0.9, "state_deep_rel_err"),         # one residual stream, the other gate
    ("latent_rows", 1.2, "latent_rows_rel_err"),
    ("index_rows", 1.2, "index_rows_rel_err"),         # max pooling, a block off
    ("attended_rows", 0.9, "attended_rows_rel_err"),   # every row attended
    ("picked_differ", 0.9, "picked_differ_share"),     # an indexer that picks at random
    ("attended_rows_decode", 0.9, "attended_rows_decode_rel_err"),  # a tick that attends every row
    ("picked_differ_decode", 0.9, "picked_differ_decode_share"),    # a tick that never scored
    ("picked_rows_decode", 0, "picked_rows_decode_compared"),       # no tick's picks were read
])
def test_a_faulty_reading_in_the_programs_place_is_not_correct(key, value, by):
    ref = reference_line()
    ref["cache"]["rows"][2][key] = value
    compared, holds = verdict(ref)
    assert not holds
    assert [k for k, c in compared.items() if not c["holds"]] == [by]


def test_the_ticks_share_has_a_limit_of_its_own():
    """A stream's last positions are where the picked sets differ most: a
    share that the ticks may read is not one the chunk rows may."""
    assert measure.PICKED_DIFFER < 0.2 < measure.PICKED_DIFFER_DECODE
    ref = reference_line()
    ref["cache"]["rows"][1]["picked_differ_decode"] = 0.2
    assert verdict(ref)[1]
    ref["cache"]["rows"][1]["picked_differ"] = 0.2
    compared, holds = verdict(ref)
    assert not holds and not compared["picked_differ_share"]["holds"]


def test_tokens_under_a_control_are_not_correct():
    ref = reference_line()
    ref["samples"] = [{"max_deficit_bf16_ulps": 400.0}] + ref["samples"][1:]
    compared, holds = verdict(ref)
    assert not holds and not compared["max_deficit_bf16_ulps"]["holds"]


@pytest.mark.parametrize("control", measure.CONTROLS)
def test_a_comparison_that_cannot_tell_a_control_apart_is_refused(control):
    ref = copy.deepcopy(reference_line())
    if control == "state_bf16":
        for row in ref["cache"]["rows"]:
            row["state_2byte_share_bf16"] = 0.0001
    elif control.startswith("unscored_picks"):
        for row in ref["cache"]["rows"]:
            row[control.replace("unscored_picks", "picked_differ_unscored")] = 0.05
    elif control == "no_selection_decode":
        ref["cache"]["rows"][3]["attended_rows_decode_no_selection"] = 0.21
    else:
        ref["what_if"][control]["least_deficit_bf16_ulps"] = 5.0
        for key in ("state_first", "state_deep", "attended_rows"):
            ref["cache"]["rows"][0][f"{key}_{control}"] = 0.001
    compared, holds = verdict(ref)
    assert not holds and compared["controls_refused"]["value"] == len(measure.CONTROLS) - 1


def test_what_the_window_must_show():
    compared, holds = verdict(reference_line(), token_bytes=5 * 1088)  # pages for every layer
    assert not holds and not compared["kv_bytes_per_token"]["holds"]
    compared, holds = verdict(reference_line(), picked_share=1.0)  # the cell never selected
    assert not holds and not compared["dsa_rows_picked_over_in_context"]["holds"]
    compared, holds = verdict(reference_line(), picked_share=None)
    assert not holds


def test_no_reference_is_not_correct():
    compared, holds = measure.verdict(None, 0, 50, 1088, 0.3)
    assert not holds and not compared["controls_refused"]["holds"]
    assert not compared["reference_samples"]["holds"]


def test_the_longest_completed_prompt_is_in_the_sample():
    done = [{"i": 16 + k, "prompt_tokens": 2304 + 100 * (k % 7)} for k in range(20)]
    picked = measure.sample_requests(done, 5, 4)
    assert len(picked) == 4 and max(r["prompt_tokens"] for r in picked) == 2904
    assert picked == sorted(picked, key=lambda r: r["i"])
    assert measure.sample_requests(done, 5, 4) == picked  # seeded
    assert measure.sample_requests(done, 6, 4) != picked
    assert measure.sample_requests([], 5, 4) == []
