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


def test_lateness_counts_only_what_was_due_in_the_window():
    reqs = [_req(0, 99.0, 0, 0, 0, 1, sent=99.5),
            _req(1, 101.0, 0, 0, 0, 1, sent=101.002),
            _req(2, 102.0, 0, 0, 0, 1, sent=102.004)]
    late = stats.lateness_ms(reqs, 100.0, 110.0)
    assert late["n"] == 2
    assert late["max"] == pytest.approx(4.0)
    assert late["p50"] == pytest.approx(3.0)


def test_gaps_and_agreed():
    assert stats.gaps_ms([0.9, 1.0, 1.02, 1.05, 2.5], 1.0, 2.0) == pytest.approx([20.0, 30.0])
    assert stats.agreed([1, 2, 3, 4], [1, 2, 9, 4]) == 2
