"""``cache_audit_zaya``'s arithmetic and ``chat_measure_zaya``'s verdict on
made-up readings: what a faultless program reads, and what each control
must read to be refused."""
import numpy as np

import cache_audit_zaya as audit
import chat_measure_zaya as measure


def test_ticks_puts_a_slots_windows_in_row_order():
    rng = np.random.default_rng(0)
    windows = [(first, rng.integers(0, 16, (4, 3)).astype(np.int8))  # [K, L]
               for first in (40, 44)]
    got = audit.ticks(windows, 41, 47)
    assert got.shape == (3, 6)
    assert (got[:, 0] == windows[0][1][1]).all() and (got[:, 5] == windows[1][1][2]).all()


def test_pick_summary_reads_shares_and_the_widest_gap():
    per_row = np.zeros((30, 4), np.float32)
    per_row[:, 1] = 0.6  # margins: clear
    per_row[4] = [1, 0.02, 0.02, 1]   # a near-tie decided the other way
    per_row[9] = [0, 0.04, 0.0, 1]    # inside the margin, agreed
    per_row[12] = [1, 0.6, 0.6, 1]    # clear of a tie, and not the reference's
    got = audit.pick_summary(per_row, 20)
    assert got["picks_differ"] == 2 / 20 and abs(got["rows_inside_margin"] - 2 / 20) < 1e-9
    assert got["picks_differ_clear"] == 1 / 20
    assert abs(got["pick_gap"] - 0.6) < 1e-7
    assert got["picks_differ_clear_no_router_carry"] == 1 / 20


def stream(rows=40, prompt=32, noise=0.0, seed=1, tail_from=None):
    """(what the audit read, the reference's rows): K|V rows of 512, c rows
    of 10 and Wv2 h of 4; ``tail_from`` = the last position the tail holds
    (a tail nobody stepped holds an earlier one)."""
    rng = np.random.default_rng(seed)
    ref = {}
    for name in ("first", "last"):
        ref[f"kv_{name}"] = rng.standard_normal((rows + 4, 512)).astype(np.float32)
        ref[f"c_{name}"] = rng.standard_normal((rows + 4, 10)).astype(np.float32)
        ref[f"v2_{name}"] = rng.standard_normal((rows + 4, 4)).astype(np.float32)
    # three layers: every pick the reference's own, clear of a tie; the last
    # layer's router needs its carry
    ref["per_row"] = [np.tile(np.float32([0, 0.6, 0, layer == 2]), (rows + 4, 1))
                      for layer in range(3)]

    def near(x):
        return x + noise * rng.standard_normal(x.shape).astype(np.float32)

    t = rows - 1 if tail_from is None else tail_from
    got = {"emitted": list(range(rows - prompt + 1)), "prompt_rows": prompt}
    for name in ("first", "last"):
        got[f"kv_{name}"] = near(ref[f"kv_{name}"][:rows])
        got[f"tail_{name}"] = near(np.concatenate(
            [ref[f"c_{name}"][t - 1], ref[f"c_{name}"][t], ref[f"v2_{name}"][t]]))
    return got, ref


def test_compare_reads_a_faultless_stream_as_zeros_and_a_stale_tail_as_not():
    got, ref = stream()
    out = audit.compare(got, ref, {})
    assert out["rows"] == 40 and out["prompt_rows"] == 32 and out["emitted"] == 9
    for key in ("kv_rows_first", "kv_rows_last", "tail_first", "tail_last"):
        assert out[key] == 0.0
    assert 0.001 < out["kv_rows_first_8bit"] < 0.02
    assert out["picks_differ_clear"] == 0.0 and out["picks_differ_clear_by_layer"] == [0.0] * 3
    assert out["picks_differ_clear_no_router_carry"] == 1.0
    stale, _ = stream(tail_from=38)  # the last tick did not step the tail
    assert audit.compare(stale, ref, {})["tail_first"] > 0.5
    assert 0.001 < out["tail_first_8bit"] < 0.05
    other = {"no_conv": {"kv_first": ref["kv_first"][::-1].copy(),
                         "kv_last": ref["kv_last"][::-1].copy(),
                         "c_last": ref["c_last"][::-1].copy(),
                         "v2_last": ref["v2_last"][::-1].copy()}, "no_value_shift": {}}
    out = audit.compare(got, ref, other)
    assert out["kv_rows_first_no_conv"] > 1.0 and "kv_rows_first_no_value_shift" not in out
    assert out["kv_rows_last_no_conv"] > 1.0 and out["tail_last_no_conv"] > 1.0
    # a wrong pick planted in the MIDDLE layer shows as that layer's share
    planted = [one.copy() for one in ref["per_row"]]
    planted[1][::4, 0] = 1
    out = audit.compare(got, ref, {"wrong_pick": {"per_row": planted}})
    assert out["picks_differ_clear_wrong_pick"] == 10 / 40
    assert out["picks_differ_clear_at_wrong_pick"] == 1 and out["picks_differ_clear"] == 0.0


def test_the_largest_share_over_the_layers_is_the_one_judged():
    got, ref = stream()
    ref["per_row"][1][:6, 0] = 1  # six clear rows of the middle layer went elsewhere
    ref["per_row"][2][:2, 0] = 1
    ref["per_row"][2][2, 2] = 0.3
    out = audit.compare(got, ref, {})
    assert out["picks_differ_clear"] == 6 / 40 and out["picks_differ_clear_at"] == 1
    assert out["picks_differ_clear_by_layer"] == [0.0, 6 / 40, 2 / 40]
    assert abs(out["pick_gap"] - 0.3) < 1e-7


def reference_line(rows, what_if=None):
    return {"device": {"platform": "tpu"},
            "samples": [{"max_deficit_bf16_ulps": 12.0}, {"max_deficit_bf16_ulps": 30.5}],
            "what_if": what_if if what_if is not None else {
                "no_conv": {"least_deficit_bf16_ulps": 260.0},
                "no_value_shift": {"least_deficit_bf16_ulps": 180.0}},
            "cache": {"rows": rows}}


SERVING = {"kv_bytes_per_token": 20480, "moe_tokens": 900, "moe_local_pairs": 900}


def good_rows():
    got, ref = stream(noise=0.001)
    planted = [one.copy() for one in ref["per_row"]]
    planted[1][::4, 0] = 1
    return [audit.compare(got, ref, {"wrong_pick": {"per_row": planted}})]


def test_the_verdict_holds_for_a_faultless_program():
    compared, holds = measure.verdict(reference_line(good_rows()), 0, 40, SERVING)
    assert holds, {k: c for k, c in compared.items() if not c["holds"]}
    assert compared["controls_refused"]["value"] == 5
    assert compared["decode_rows_audited"]["value"] == 8
    assert compared["max_deficit_bf16_ulps"]["value"] == 30.5


def test_each_fault_fails_its_own_limit():
    def verdict(**change):
        rows = good_rows()
        rows[0].update(change.pop("row", {}))
        args = {"ref": reference_line(rows), "short": 0, "attempted": 40,
                "serving": SERVING, **change}
        return measure.verdict(args["ref"], args["short"], args["attempted"], args["serving"])

    for key, row in (("kv_rows_first_rel_err", {"kv_rows_first": 0.02}),
                     ("kv_rows_last_rel_err", {"kv_rows_last": 0.9}),
                     ("tail_first_rel_err", {"tail_first": 0.7}),
                     ("tail_last_rel_err", {"tail_last": 0.7}),
                     ("picks_differ_clear", {"picks_differ_clear": 0.6})):
        compared, holds = verdict(row=row)
        assert not holds and not compared[key]["holds"]
        assert sum(not c["holds"] for c in compared.values()) == 1
    compared, holds = verdict(short=1)
    assert not holds and not compared["short_streams"]["holds"]
    compared, holds = verdict(serving={**SERVING, "moe_local_pairs": 880})
    assert not holds and compared["moe_pairs_not_landed"]["value"] == 20
    compared, holds = verdict(serving={**SERVING, "kv_bytes_per_token": 40960})
    assert not holds and not compared["kv_bytes_per_token"]["holds"]
    compared, holds = measure.verdict(None, 0, 40, SERVING)
    assert not holds and compared["reference_samples"]["value"] == 0


def test_a_control_that_reads_like_the_program_is_not_refused():
    # the tokens cannot tell the control and its rows were not read
    compared, holds = measure.verdict(
        reference_line(good_rows(), {"no_conv": {"least_deficit_bf16_ulps": 20.0}}),
        0, 40, SERVING)
    assert not holds and compared["controls_refused"]["value"] == 3
    # its rows can: the program's layer-0 pages against the control's
    rows = good_rows()
    rows[0].update(kv_rows_first_no_conv=0.8, kv_rows_first_no_value_shift=0.4)
    compared, holds = measure.verdict(reference_line(rows, {}), 0, 40, SERVING)
    assert holds and compared["controls_refused"]["value"] == 5
    # a router without carry that picks like the reference's is not refused,
    # nor a planted pick that no layer's share shows
    for key in ("picks_differ_clear_no_router_carry", "picks_differ_clear_wrong_pick"):
        rows = good_rows()
        rows[0][key] = 0.0
        compared, holds = measure.verdict(reference_line(rows), 0, 40, SERVING)
        assert not holds and compared["controls_refused"]["value"] == 4


def test_the_sample_holds_the_longest_completed_request():
    done = [{"i": 16 + k, "prompt_tokens": 300 + 10 * k, "tokens": [0] * (50 if k != 3 else 4000)}
            for k in range(9)]
    picked = measure.sample_requests(done, seed=5, n=4)
    assert len(picked) == 4 and 19 in {r["i"] for r in picked}
    assert picked == sorted(picked, key=lambda r: r["i"])
    assert measure.sample_requests(done, seed=5, n=4) == picked
    assert measure.sample_requests([], seed=5, n=4) == []
