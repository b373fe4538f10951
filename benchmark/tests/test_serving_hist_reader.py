"""The reader over the two ``ServingMetrics`` snapshots around the window."""
import json

import pytest
import serving_hist_mean_ms as reader
from conftest import BENCH

ARGS = json.loads((BENCH / "layer_metrics" / "dispatch_gap_ms.serve.json").read_text())["args"]


def _snap(count, sum_us, counts=None):
    return {"compiles": 3, "dispatch_gap_us": {
        "count": count, "sum_us": sum_us, "counts": counts or [0, 0, count], "p50_us": 4.0}}


def test_mean_of_what_the_window_added():
    run = {"serving_before": _snap(100, 1_000_000.0), "serving_after": _snap(900, 11_400_000.0)}
    assert reader.read(run, ARGS) == pytest.approx(13.0)  # 10.4 s over 800 dispatches


def test_a_traced_run_reads_from_behind_the_capture():
    """The capture's stop held the loop 9.4 s in one gap: the snapshot
    taken after it is where the reading starts."""
    run = {"serving_before": _snap(100, 1_000_000.0),
           "serving_traced": _snap(300, 13_000_000.0),
           "serving_after": _snap(900, 20_800_000.0)}
    assert reader.read(run, ARGS) == pytest.approx(13.0)  # 7.8 s over 600, not 19.8 s over 800
    late = dict(run, serving_traced=_snap(950, 21e6))  # written after the window closed
    assert reader.read(late, ARGS) is None


@pytest.mark.parametrize("run", [
    {"serving_after": _snap(900, 11_400_000.0)},                              # one snapshot
    {"serving_before": {}, "serving_after": _snap(900, 11_400_000.0)},        # the node had not reported
    {"serving_before": _snap(100, 1e6), "serving_after": {"compiles": 3}},    # lacks the histogram
    {"serving_before": _snap(100, 1e6), "serving_after": {"dispatch_gap_us": {"p50_us": 4.0}}},
    {"serving_before": _snap(100, 1e6), "serving_after": _snap(100, 1e6)},    # no dispatch inside
    {},
], ids=["one", "empty-before", "no-hist", "no-sum", "no-dispatch", "neither"])
def test_nothing_to_read_is_none_never_zero(run):
    assert reader.read(run, ARGS) is None


def test_hist_delta_keeps_the_octaves():
    import stats

    d = stats.hist_delta(_snap(10, 50.0, [1, 4, 5]), _snap(16, 90.0, [1, 6, 9]), "dispatch_gap_us")
    assert d == {"count": 6, "sum_us": 40.0, "counts": [0, 2, 4]}
