"""The seven per-layer metrics that read a request's stages
(``dora_tpu/telemetry.py`` ``REQUEST_STAGES``): five off the model node's
snapshots, two off the api node's own ``front`` report lines."""
import json
import os
import subprocess
import sys

import node_log_hist_mean_ms as log_reader
import pytest
import serving_hist_mean_ms as snap_reader
from conftest import BENCH, ROOT

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
#: metric -> (layer, reader, the histogram it reads)
WANTED = {
    "ttft_front_ms.serve": ("HTTP front", "serving_hist_mean_ms", "stage_front_us"),
    "ttft_route_in_ms.serve": ("daemon route", "serving_hist_mean_ms", "stage_route_in_us"),
    "ttft_prefill_queue_ms.serve": ("engine step (host)", "serving_hist_mean_ms", "stage_prefill_queue_us"),
    "ttft_prefill_ms.serve": ("window and chunk programs", "serving_hist_mean_ms", "stage_prefill_us"),
    "ttft_first_emit_ms.serve": ("engine step (host)", "serving_hist_mean_ms", "stage_first_emit_us"),
    "ttft_route_out_ms.serve": ("daemon route", "node_log_hist_mean_ms", "stage_route_out_us"),
    "ttft_sse_ms.serve": ("HTTP front", "node_log_hist_mean_ms", "stage_sse_us"),
}


def _entry(name: str) -> dict:
    return next(m for m in MANIFEST["per_layer"] if m["name"] == name)


def _spec(name: str) -> dict:
    return json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", WANTED)
def test_the_entry_and_its_file_agree(name):
    layer, reader, hist = WANTED[name]
    entry, spec = _entry(name), _spec(name)
    assert entry == {
        "name": name, "unit": "ms", "better": "lower", "source": "program_counter",
        "layer": layer, "moves": "ttft_p95_ms", "workloads": entry["workloads"]}
    assert (spec["reader"], spec["args"]["hist"]) == (reader, hist)
    assert (spec["layer"], spec["unit"], spec["moves"]) == (layer, "ms", "ttft_p95_ms")
    assert (BENCH / "readers" / f"{reader}.py").exists() and len(spec["what"]) > 80
    # only cells that report the metric it moves, and never the frames tier
    ttft = next(m for m in MANIFEST["end_to_end"] if m["name"] == "ttft_p95_ms")
    assert entry["workloads"] and set(entry["workloads"]) <= set(ttft["workloads"])


def test_the_stages_histograms_are_the_programs():
    sys.path.insert(0, str(ROOT))
    try:
        from dora_tpu import telemetry
        from dora_tpu.metrics import ServingMetrics
    finally:
        sys.path.pop(0)
    snap = ServingMetrics().snapshot()
    for name, (_layer, reader, hist) in WANTED.items():
        assert hist in {telemetry.stage_histogram_key(s) for s in telemetry.REQUEST_STAGES}
        assert (hist in snap) == (reader == "serving_hist_mean_ms"), name


# --- serving_hist_mean_ms over two hand-made snapshots ----------------------


def _snap(**hists) -> dict:
    return {"compiles": 3, **{
        key: {"count": count, "sum_us": sum_us, "counts": [0, 0, count]}
        for key, (count, sum_us) in hists.items()}}


def test_a_stages_mean_between_two_snapshots():
    args = _spec("ttft_prefill_queue_ms.serve")["args"]
    before = _snap(stage_prefill_queue_us=(10, 2_000_000.0), stage_prefill_us=(10, 500_000.0))
    after = _snap(stage_prefill_queue_us=(50, 10_000_000.0), stage_prefill_us=(50, 2_500_000.0))
    run = {"serving_before": before, "serving_after": after}
    assert snap_reader.read(run, args) == pytest.approx(200.0)  # 8 s over 40 first messages
    assert snap_reader.read(run, _spec("ttft_prefill_ms.serve")["args"]) == pytest.approx(50.0)
    # a server older than the stages, and a window with no first message
    assert snap_reader.read(run, _spec("ttft_front_ms.serve")["args"]) is None
    assert snap_reader.read({"serving_before": after, "serving_after": after}, args) is None
    # a traced run reads from behind the capture
    traced = _snap(stage_prefill_queue_us=(30, 4_000_000.0))
    assert snap_reader.read({**run, "serving_traced": traced}, args) == pytest.approx(300.0)


# --- node_log_hist_mean_ms over a hand-made log_api.txt ---------------------


def _front(t_mono, route_out, sse=None) -> str:
    payload = {"t_mono": t_mono, "requests": route_out[0],
               "stage_route_out_us": {"count": route_out[0], "sum_us": route_out[1], "counts": []}}
    if sse is not None:
        payload["stage_sse_us"] = {"count": sse[0], "sum_us": sse[1], "counts": []}
    return f"dora_tpu.backend front: {json.dumps(payload)}"


def _workdir(tmp_path, lines: list[str]):
    out = tmp_path / "out" / "0190-run"
    out.mkdir(parents=True)
    (out / "log_api.txt").write_text("\n".join(
        ["openai server listening on 127.0.0.1:8123", *lines, "a line of something else"]) + "\n")
    (out / "log_llm.txt").write_text(_front(0.0, (1, 1.0)) + "\n")  # another node's log
    return tmp_path


ROUTE_OUT = {"node": "api", "kind": "front", "hist": "stage_route_out_us"}
SSE = {"node": "api", "kind": "front", "hist": "stage_sse_us"}


def test_the_fronts_lines_around_the_window(tmp_path):
    assert _spec("ttft_route_out_ms.serve")["args"] == ROUTE_OUT
    assert _spec("ttft_sse_ms.serve")["args"] == SSE
    workdir = _workdir(tmp_path, [
        _front(98.0, (5, 5_000.0), (5, 1_000.0)),
        _front(99.5, (10, 10_000.0), (10, 2_000.0)),      # the last at or before t0
        _front(100.5, (20, 500_000.0), (19, 9_000.0)),    # inside: passed over
        _front(139.9, (400, 900_000.0), (400, 90_000.0)),
        _front(140.0, (410, 830_000.0), (410, 82_000.0)),  # the first at or after t1
        _front(141.0, (500, 999_000.0), (500, 99_000.0)),
    ])
    run = {"workdir": workdir, "t0": 100.0, "t1": 140.0}
    assert log_reader.read(run, ROUTE_OUT) == pytest.approx(2.05)  # 820 ms over 400
    assert log_reader.read(run, SSE) == pytest.approx(0.2)  # 80 ms over 400
    # the window's edges count as outside it on both sides
    assert log_reader.read({**run, "t0": 99.5, "t1": 141.0}, SSE) == pytest.approx(
        97_000.0 / 490 / 1e3)


@pytest.mark.parametrize("lines,t0,t1", [
    ([], 100.0, 140.0),                                                      # a program older than the lines
    ([_front(120.0, (9, 9.0), (9, 9.0))], 100.0, 140.0),                     # a single line, inside
    ([_front(99.0, (9, 9.0), (9, 9.0))], 100.0, 140.0),                      # none behind the window
    ([_front(141.0, (9, 9.0), (9, 9.0))], 100.0, 140.0),                     # none before it
    ([_front(99.0, (9, 9.0)), _front(141.0, (99, 99.0), (90, 90.0))], 100.0, 140.0),  # lacks the histogram
    ([_front(99.0, (9, 9.0), (9, 9.0)), _front(141.0, (99, 99.0), (9, 9.0))], 100.0, 140.0),  # nothing flushed
    ([_front(99.0, (9, 9.0), (9, 9.0)), _front(141.0, (99, 99.0), (99, 99.0))], None, 140.0),
], ids=["no-lines", "one-inside", "one-before", "one-after", "no-hist", "no-count", "no-window"])
def test_nothing_to_read_is_none_and_never_raises(tmp_path, lines, t0, t1):
    run = {"workdir": _workdir(tmp_path, lines), "t0": t0, "t1": t1}
    assert log_reader.read(run, SSE) is None


def test_a_run_without_a_workdir_or_a_log_reads_none(tmp_path):
    assert log_reader.read({"t0": 1.0, "t1": 2.0}, SSE) is None
    assert log_reader.read({"workdir": tmp_path, "t0": 1.0, "t1": 2.0}, SSE) is None


# --- the rehearsal prints them ----------------------------------------------


def test_the_tiny_traced_rehearsal_lists_the_seven():
    """Thirty seconds of window: on the CPU the profiler takes ten to
    twenty-odd seconds to write its capture, and the five snapshot-read
    metrics start behind it (``serving_traced``). The open-loop cell: its
    window lasts to its ``t1`` whatever the server does. The closed loop's
    tiny plan (``callers-16``: 120 requests a second of window) runs out a
    second or two before ``t1`` where the CPU is quick: the load process
    then ends the window early, the api node exits before ``t1`` and
    prints no line behind it — which a run on the chip (19 requests a
    second against a plan of 40) never sees."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "qwen25-1p5b.chat-open",
         "--seed", str(2 ** 31 + 54), "--seconds", "30", "--trace", "1", "--tiny"],
        cwd=ROOT, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=900,
    )
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["failed"] == 0
    missing = set(WANTED) - set(last["metric_names"])
    assert not missing, (missing, proc.stderr[-2000:])
