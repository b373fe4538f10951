"""Percentile, window and lateness arithmetic against hand-computed values."""
import pytest
import stats


def test_percentile_is_nearest_rank():
    values = list(range(1, 21))  # 1..20
    assert stats.percentile(values, 95) == 19  # ceil(0.95 * 20) = 19th
    assert stats.percentile(values, 50) == 10
    assert stats.percentile(values, 100) == 20
    assert stats.percentile([7.5], 95) == 7.5
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def _req(i, due, first, last, done, n, max_tokens=None, error=None, sent=None):
    deltas = [] if first is None or n == 0 else (
        [[first, 3 * n]] if n == 1 else
        [[first + (last - first) * k / (n - 1), 3] for k in range(n)]
    )
    return {"i": i, "due": due, "sent": due if sent is None else sent,
            "first": first, "last": last, "done": done, "tokens": list(range(n)),
            "deltas": deltas,
            "max_tokens": n if max_tokens is None else max_tokens,
            "finish": "length", "error": error}


def test_chat_metrics_window_arithmetic():
    t0, t1 = 100.0, 110.0
    reqs = [
        # sent before the window, 50 tokens from 99.2 to 100.18: those that
        # arrived at or after 100.0 count, 50 - 40 = 10 of them
        _req(0, 99.0, 99.2, 100.18, 100.5, 50),
        # due and done inside: ttft 100 ms, tpot (1.0 s / 10) = 100 ms
        _req(1, 101.0, 101.1, 102.1, 102.1, 11),
        # due inside, done after the window: latencies count, and the 8
        # tokens that arrived before 110.0 (109.3, 109.4, ... 110.0 is out)
        _req(2, 109.0, 109.3, 111.3, 111.3, 21),
        # due inside, failed: counts in failed, misses every latency
        _req(3, 105.0, None, None, 105.5, 0, max_tokens=8, error="refused"),
        # due inside, finished short of max_tokens: failed
        _req(4, 106.0, 106.1, 106.2, 106.2, 5, max_tokens=9),
    ]
    m = stats.chat_metrics(reqs, t0, t1)
    assert m["attempted"] == 4 and m["failed"] == 2
    assert m["tokens_per_s"] == pytest.approx((10 + 11 + 7) / 10.0)
    assert m["completed_in_window"] == 2
    assert m["ttft_p50_ms"] == pytest.approx(200.0)  # 100 and 300
    assert m["ttft_p95_ms"] == pytest.approx(300.0)
    assert m["tpot_p95_ms"] == pytest.approx(100.0)  # 100 and 2000/20 = 100
    assert m["tpot_p50_ms"] == pytest.approx(100.0)
    # a third reading off the other two: the median is the middle one
    reqs.append(_req(5, 107.0, 107.1, 107.4, 107.4, 7))  # tpot 300 / 6 = 50 ms
    m = stats.chat_metrics(reqs, t0, t1)
    assert m["tpot_p50_ms"] == pytest.approx(100.0) and m["tpot_p95_ms"] == pytest.approx(100.0)
    reqs.append(_req(6, 107.5, 107.6, 108.2, 108.2, 4))  # tpot 600 / 3 = 200 ms
    m = stats.chat_metrics(reqs, t0, t1)
    assert m["tpot_p50_ms"] == pytest.approx(100.0) and m["tpot_p95_ms"] == pytest.approx(200.0)
    assert m["tpot_p50_ms"] <= m["tpot_p95_ms"] and m["ttft_p50_ms"] <= m["ttft_p95_ms"]


def test_chat_measure_reports_the_median_beside_the_tail(tmp_path, monkeypatch):
    """``measure``'s metrics carry ``tpot_p50_ms`` with unit ms, the median
    of the same per-request readings whose p95 is ``tpot_p95_ms``."""
    import json
    from types import SimpleNamespace

    import chat_measure
    import checkpoint

    def raw(i, due, first, last, n):
        r = _req(i, due, first, last, last, n)
        r["text"] = "".join(checkpoint.token_code(t) for t in r.pop("tokens"))
        return r

    reqs = [raw(0, 101.0, 101.1, 102.1, 11), raw(1, 102.0, 102.1, 102.4, 7),
            raw(2, 103.0, 103.1, 103.7, 4)]
    (tmp_path / "load_result.json").write_text(json.dumps(
        {"t0": 100.0, "t1": 110.0, "requests": reqs, "plan_exhausted": False}))
    monkeypatch.setattr(chat_measure, "reference", lambda ctx, samples: {
        "device": None, "samples": [{"max_deficit_bf16_ulps": 0} for _ in samples]})
    plan = {"requests": [{"ids": [1, 2], "twin_of": None} for _ in reqs]}
    out = chat_measure.measure(SimpleNamespace(workdir=tmp_path, seed=3), {}, plan)
    assert out["correct"] and out["attempted"] == 3 and out["failed"] == 0
    assert out["metrics"]["tpot_p50_ms"] == {"value": pytest.approx(100.0), "unit": "ms"}
    assert out["metrics"]["tpot_p95_ms"] == {"value": pytest.approx(200.0), "unit": "ms"}
    assert set(out["metrics"]) == {"tokens_per_s", "ttft_p95_ms", "tpot_p50_ms", "tpot_p95_ms"}
    window = out["lines"][0]["window"]
    assert window["tpot_p50_ms"] == pytest.approx(100.0)
    assert window["delta_stalls"]["gaps"] == 0
    # every number `correct` rests on, beside its limit
    assert out["compared"]["max_deficit_bf16_ulps"] == {
        "value": 0, "limit": chat_measure.NEAR_TIE_ULPS, "rule": "<=", "holds": True}
    assert out["compared"]["short_streams"]["value"] == 0
    assert all(c["holds"] for c in out["compared"].values())
    monkeypatch.setattr(chat_measure, "reference", lambda ctx, samples: None)  # the child died
    out = chat_measure.measure(SimpleNamespace(workdir=tmp_path, seed=3), {}, plan)
    assert not out["correct"] and not out["compared"]["max_deficit_bf16_ulps"]["holds"]


def test_stalls_merge_gaps_that_overlap_into_one_episode():
    def stream(i, stamps):
        r = _req(i, stamps[0] - 0.1, stamps[0], stamps[-1], stamps[-1], len(stamps))
        r["deltas"] = [[s, 3] for s in stamps]
        return r

    reqs = [
        stream(0, [101.0, 101.05, 101.60, 101.65]),        # 550 ms: 101.05 -> 101.60
        stream(1, [101.02, 101.07, 101.62, 101.67]),       # the same pause, another stream
        stream(2, [104.0, 104.3, 104.35]),                 # 300 ms, a second episode
        stream(3, [99.0, 99.5, 100.2, 100.25]),            # 99.0 -> 99.5 ends before t0; 700 ms ends inside
        stream(4, [109.8, 110.4]),                         # ends after t1: out
    ]
    s = stats.stalls(reqs, 100.0, 110.0)
    assert s["gaps"] == 4 and s["episodes"] == 3
    assert s["longest_ms"] == pytest.approx(700.0)
    assert s["episode_s"] == pytest.approx(0.7 + 0.57 + 0.3)


def test_lateness_counts_only_what_was_due_in_the_window():
    reqs = [_req(0, 99.0, 0, 0, 0, 1, sent=99.5),
            _req(1, 101.0, 0, 0, 0, 1, sent=101.002),
            _req(2, 102.0, 0, 0, 0, 1, sent=102.004)]
    late = stats.lateness_ms(reqs, 100.0, 110.0)
    assert late["n"] == 2
    assert late["max"] == pytest.approx(4.0)
    assert late["p50"] == pytest.approx(3.0)


def test_compared_holds_by_its_rule():
    assert stats.compared(24, 24)["holds"] and not stats.compared(24.5, 24)["holds"]
    assert stats.compared(2.0, 2, at_most=False)["holds"]
    assert not stats.compared(1.9, 2, at_most=False)["holds"]
    assert not stats.compared(None, 24)["holds"] and stats.compared(None, 24)["rule"] == "<="


def test_gaps_and_agreed():
    assert stats.gaps_ms([0.9, 1.0, 1.02, 1.05, 2.5], 1.0, 2.0) == pytest.approx([20.0, 30.0])
    assert stats.agreed([1, 2, 3, 4], [1, 2, 9, 4]) == 2


def test_heartbeat_keeps_late_wakes_and_the_window_counts_its_own(monkeypatch):
    import chat_client

    clock = iter([0.0, 0.021, 0.021, 0.541, 0.541, 0.562])  # due at 0.02, 0.041, 0.561
    beat = chat_client.Heartbeat(step_s=0.02, over_s=0.1)
    naps = []

    def sleep(s):
        naps.append(s)
        if len(naps) == 3:
            beat._halt.set()

    monkeypatch.setattr(chat_client.time, "monotonic", lambda: next(clock))
    monkeypatch.setattr(chat_client.time, "sleep", sleep)
    beat.run()
    assert beat.pauses == [[pytest.approx(0.041), pytest.approx(0.5)]]
    got = stats.pauses_in_window([[99.0, 0.3], [101.0, 0.5], [104.0, 0.2], [110.0, 1.0]], 100.0, 110.0)
    assert got["n"] == 2 and got["total_s"] == pytest.approx(0.7)
    assert got["longest_ms"] == pytest.approx(500.0) and got["at_s"][0] == [pytest.approx(1.0), 0.5]
