"""``cache_audit_keye_vl2``'s arithmetic and ``chat_measure_keye_vl2``'s
verdict on made-up readings: what a faultless program reads, and what each
control must read to be refused."""
import numpy as np

import cache_audit_keye_vl2 as audit
import chat_measure_keye_vl2 as measure

TOPK = 8


def test_ticks_puts_a_slots_windows_in_row_order():
    rng = np.random.default_rng(0)
    windows = []
    for first in (40, 44):  # two windows of four ticks, three layers
        windows.append((first, rng.integers(0, 40, (3, 4, TOPK)).astype(np.int16),
                        rng.standard_normal((4, 6)).astype(np.float32)))
    got = audit.ticks(windows, 41, 47)
    assert got["picked_decode"].shape == (3, 6, TOPK)
    assert got["attended_decode"].shape == (6, 6)
    assert (got["picked_decode"][:, 0] == windows[0][1][:, 1]).all()
    assert (got["picked_decode"][:, 5] == windows[1][1][:, 2]).all()
    assert (got["attended_decode"][3] == windows[1][2][0]).all()


def test_picked_summary_over_chunk_rows_and_decode_ticks():
    per_row = np.zeros((30, 4), np.float32)
    per_row[10] = [2, 0.5, 0.25, 6]   # a chunk row with two picks of its own
    per_row[25] = [1, 0.125, 0.75, 8]  # a decode tick with one
    chunk = audit.picked_summary(per_row, TOPK, 20, TOPK)
    assert chunk == {"picked_rows": 12, "picked_differ": 2 / 96, "picked_rank_gap": 0.5,
                     "picked_score_gap": 0.25, "picked_differ_unscored": 6 / 96}
    decode = audit.picked_summary(per_row, 20, 30, TOPK)
    assert decode["picked_rows"] == 10 and decode["picked_differ"] == 1 / 80
    assert decode["picked_rank_gap"] == 0.125
    assert audit.picked_summary(per_row, 20, 20, TOPK)["picked_differ"] is None


def stream(rows=40, prompt=32, width=16, noise=0.0, seed=1):
    rng = np.random.default_rng(seed)
    ref = {
        "kv_first": rng.standard_normal((rows + 4, width)).astype(np.float32),
        "ik_first": rng.standard_normal((rows + 4, 4)).astype(np.float32),
        "kv_last": rng.standard_normal((rows + 4, width)).astype(np.float32),
        "ik_last": rng.standard_normal((rows + 4, 4)).astype(np.float32),
        "attended": rng.standard_normal((rows + 4, 6)).astype(np.float32),
        "per_row": np.zeros((rows + 4, 4), np.float32),
    }

    def near(x):
        return x + noise * rng.standard_normal(x.shape).astype(np.float32)

    got = {
        "emitted": list(range(rows - prompt + 1)), "prompt_rows": prompt,
        **{k: near(ref[k][:rows]) for k in ("kv_first", "ik_first", "kv_last", "ik_last")},
        "attended": near(ref["attended"][:prompt:audit.ATTENDED_EVERY]),
        "attended_decode": near(ref["attended"][prompt:rows]),
    }
    return got, ref


def test_compare_reads_rows_attended_and_the_controls():
    got, ref = stream(noise=0.001)
    other = {k: v + 1.0 for k, v in ref.items()}
    out = audit.compare(got, ref, {"no_selection": other, "no_qk_norm": other}, TOPK)
    assert out["rows"] == 40 and out["emitted"] == 9
    for key in ("kv_rows_first", "ik_rows_first", "kv_rows_last", "ik_rows_last",
                "attended_rows", "attended_rows_decode"):
        assert 0.0005 < out[key] < 0.002, key
    assert out["kv_rows_first_8bit"] > 0.004  # 8 bits a row show
    assert out["attended_rows_no_selection"] > 0.5
    assert out["attended_rows_decode_no_selection"] > 0.5
    assert out["kv_rows_first_no_qk_norm"] > 0.5 and out["ik_rows_last_no_selection"] > 0.5
    assert out["picked_rows"] == 24 and out["picked_rows_decode"] == 8
    assert out["picked_differ"] == 0 and out["picked_differ_decode"] == 0
    # a control that did not run on this sample leaves no reading
    assert "kv_rows_first_no_qk_norm" not in audit.compare(got, ref, {}, TOPK)


def reading(**over):
    row = {"kv_rows_first": 0.003, "ik_rows_first": 0.003, "kv_rows_last": 0.01,
           "ik_rows_last": 0.01, "attended_rows": 0.006, "attended_rows_decode": 0.006,
           "kv_rows_first_8bit": 0.009, "ik_rows_first_8bit": 0.009,
           "kv_rows_last_no_selection": 0.3, "ik_rows_last_no_selection": 0.3,
           "picked_differ_unscored": 0.5, "picked_differ_unscored_decode": 0.6,
           "attended_rows_no_selection": 0.9,
           "attended_rows_decode_no_selection": 0.9, "kv_rows_first_no_qk_norm": 0.8,
           "kv_rows_last_no_qk_norm": 0.9,
           "picked_rows": 500, "picked_rows_decode": 30, "picked_differ": 0.01,
           "picked_differ_decode": 0.013, **over}
    return {"samples": [{"max_deficit_bf16_ulps": 3.0}],
            "what_if": {"no_selection": {"least_deficit_bf16_ulps": 5.0},
                        "no_qk_norm": {"least_deficit_bf16_ulps": 400.0}},
            "cache": {"rows": [row]}}


def test_the_verdict_holds_for_a_faultless_program():
    compared, holds = measure.verdict(reading(), 0, 40, 26112, 0.25)
    assert holds, {k: c for k, c in compared.items() if not c["holds"]}
    assert compared["controls_refused"]["value"] == len(measure.CONTROLS) == 4


def test_each_limit_and_each_control_can_fail_it():
    for over in ({"kv_rows_first": 0.02}, {"ik_rows_last": 0.5}, {"attended_rows_decode": 0.6},
                 {"picked_differ": 0.4}, {"picked_differ_decode": 0.5},
                 {"attended_rows_no_selection": 0.001, "attended_rows_decode_no_selection": 0.001,
                  "kv_rows_last_no_selection": 0.001, "ik_rows_last_no_selection": 0.001},
                 {"kv_rows_first_8bit": 0.003, "ik_rows_first_8bit": 0.003},  # 8 bits unseen
                 {"picked_differ_unscored": 0.01, "picked_differ_unscored_decode": 0.01},
                 ):
        assert not measure.verdict(reading(**over), 0, 40, 26112, 0.25)[1], over
    assert not measure.verdict(reading(), 1, 40, 26112, 0.25)[1]     # a short stream
    assert not measure.verdict(reading(), 0, 40, 27648, 0.25)[1]     # a wider page
    assert not measure.verdict(reading(), 0, 40, 26112, 0.95)[1]     # nothing selected
    assert not measure.verdict(None, 0, 40, 26112, 0.25)[1]          # no reference


def test_the_sample_holds_the_longest_completed_prompt():
    done = [{"i": 16 + k, "prompt_tokens": n} for k, n in enumerate((5000, 12000, 4100, 7000,
                                                                     6000, 12000))]
    picked = measure.sample_requests(done, 3, 4)
    assert len(picked) == 4 and 17 in [r["i"] for r in picked]
    assert measure.sample_requests(done[:2], 3, 4) == done[:2]
    assert measure.sample_requests([], 3, 4) == []
