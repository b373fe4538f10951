"""The host's ``loop.*`` spans against the device's idle time: pure
arithmetic on hand-made spans, the recorded fixture (a 0.1 s cut of a real
v5e capture of the llm node with its ``/host:CPU`` spans,
``fixtures/serve_host_spans_cut.json``) against hand-computed values, a
real ``jax.profiler`` capture on the CPU, and the per-gap reader over the
serving snapshots."""
import json
import subprocess
import sys

import host_spans as hs
import pytest
import serving_hist_per_ms as per
from conftest import BENCH, ROOT

US = 1000
FIXTURE = BENCH / "tests" / "fixtures" / "serve_host_spans_cut.json"


def events(ops, span):
    return {"planes": {"/device:TPU:0": {"XLA Ops": [["op", s, d] for s, d in ops]}},
            "span_ns": list(span)}


# one turn: housekeeping 0-10, admit 10-30 (can_admit 12-20 inside), chunk_launch
# 30-80 (first_token_wait 50-70 inside), window_launch 80-90, window_wait 95-200
SPANS = [["housekeeping", 0, 10 * US], ["admit", 10 * US, 20 * US],
         ["admit.can_admit", 12 * US, 8 * US], ["chunk_launch", 30 * US, 50 * US],
         ["first_token_wait", 50 * US, 20 * US], ["window_launch", 80 * US, 10 * US],
         ["window_wait", 95 * US, 105 * US]]


def test_self_segments_give_each_instant_to_the_deepest_span():
    assert hs.self_segments(SPANS) == [
        (0, 10 * US, "housekeeping"), (10 * US, 12 * US, "admit"),
        (12 * US, 20 * US, "admit.can_admit"), (20 * US, 30 * US, "admit"),
        (30 * US, 50 * US, "chunk_launch"), (50 * US, 70 * US, "first_token_wait"),
        (70 * US, 80 * US, "chunk_launch"), (80 * US, 90 * US, "window_launch"),
        (95 * US, 200 * US, "window_wait")]


@pytest.mark.parametrize("idle, by_phase, owner", [
    ([(32 * US, 48 * US)], {"chunk_launch": 16 * US}, "chunk_launch"),          # wholly under one
    ([(5 * US, 11 * US)], {"housekeeping": 5 * US, "admit": 1 * US}, "housekeeping"),  # split
    ([(13 * US, 19 * US)], {"admit.can_admit": 6 * US}, "admit.can_admit"),     # the child owns it
    ([(45 * US, 75 * US)], {"first_token_wait": 20 * US, "chunk_launch": 10 * US},
     "first_token_wait"),                                                        # carved out of its parent
    ([(90 * US, 95 * US)], {"no_span": 5 * US}, "no_span"),                     # under no span
    ([(88 * US, 97 * US)], {"window_launch": 2 * US, "no_span": 5 * US, "window_wait": 2 * US},
     "no_span"),
], ids=["one", "two", "child", "carved", "none", "across"])
def test_idle_under_each_phase(idle, by_phase, owner):
    found = hs.attribute(idle, SPANS)
    assert found["by_phase"] == by_phase and found["idle_ns"] == idle[0][1] - idle[0][0]
    assert found["gaps"] == [[owner, idle[0][1] - idle[0][0], 0]]
    share = 100.0 * (1 - by_phase.get("no_span", 0) / found["idle_ns"])
    assert found["attributed_pct"] == pytest.approx(share)


def test_idle_is_what_device_idle_pct_counts_and_gaps_come_longest_first():
    ev = events([(10 * US, 20 * US), (15 * US, 5 * US), (60 * US, 30 * US), (96 * US, 4 * US)],
                (0, 110 * US))
    idle = hs.idle_intervals(ev)
    # before the first operation, between them, behind the last: the span is the device's
    assert idle == [(0, 10 * US), (30 * US, 60 * US), (90 * US, 96 * US), (100 * US, 110 * US)]
    found = hs.attribute(idle, SPANS)
    assert found["idle_ns"] == 56 * US == 110 * US - 54 * US
    # 30-60: chunk_launch to 50, then the wait carved out of it; 90-96: 5 under
    # no span, 1 under window_wait, which also holds 100-110
    assert found["by_phase"] == {"chunk_launch": 20 * US, "first_token_wait": 10 * US,
                                 "housekeeping": 10 * US, "window_wait": 11 * US,
                                 "no_span": 5 * US}
    assert found["gaps"][:2] == [["chunk_launch", 30 * US, 30 * US],
                                 ["window_wait", 10 * US, 100 * US]]
    assert found["attributed_pct"] == pytest.approx(100 * 51 / 56)


def test_no_device_operation_and_no_span_read_nothing():
    assert hs.idle_intervals({"planes": {"/host:CPU": {"python": [["x", 0, 10]]}},
                              "span_ns": [0, 10]}) == []
    assert hs.attribute([], SPANS)["attributed_pct"] is None
    assert hs.attribute([(0, 10)], [])["attributed_pct"] == 0.0


def test_the_reader_is_none_without_a_capture_or_a_host_plane(tmp_path):
    import idle_attributed_pct as reader

    args = {"program": r"^jit_program\("}
    assert reader.read({"events": None, "workdir": tmp_path}, args) is None
    assert reader.read({"workdir": tmp_path}, args) is None
    ev = events([(0, 10 * US), (20 * US, 10 * US)], (0, 30 * US))
    # a capture directory with no *.xplane.pb: the child fails, the reader says None
    assert reader.read({"events": ev, "workdir": tmp_path, "profile": {}}, args) is None


def test_launch_and_wait_margins_are_signed():
    ev = events([(100 * US, 50 * US)], (100 * US, 150 * US))
    ev["planes"]["/device:TPU:0"]["XLA Modules"] = [
        ["jit_program(1)", 100 * US, 50 * US], ["jit_step(2)", 10 * US, 5 * US]]
    spans = [["window_launch", 90 * US, 8 * US], ["window_wait", 120 * US, 33 * US]]
    m = hs.launch_and_wait_margins(ev, spans, r"^jit_program\(")
    assert m == {"programs": 1, "launch_before_start_ns": [10 * US, 10 * US],
                 "wait_end_after_end_ns": [3 * US, 3 * US]}
    skewed = [[p, s + 40 * US, d] for p, s, d in spans]  # a host clock 40 us ahead
    m = hs.launch_and_wait_margins(ev, skewed, r"^jit_program\(")
    assert m["launch_before_start_ns"][0] == -30 * US
    assert hs.launch_and_wait_margins(ev, [], r"^jit_program\(") is None


# ---------------------------------------------------------------------------
# the recorded fixture
# ---------------------------------------------------------------------------


def brute_force(idle, spans):
    """Nanosecond by nanosecond would be the definition; by segment
    boundary is the same thing: cut every idle stretch at every span edge
    and give each piece to the shortest span that covers it."""
    edges = sorted({t for _, s, d in spans for t in (s, s + d)})
    out = {}
    for lo, hi in idle:
        cuts = [lo] + [t for t in edges if lo < t < hi] + [hi]
        for a, b in zip(cuts, cuts[1:]):
            over = [(d, p) for p, s, d in spans if s <= a and b <= s + d]
            owner = min(over)[1] if over else "no_span"
            out[owner] = out.get(owner, 0) + b - a
    return out


def test_the_recorded_captures_longest_gap_by_hand():
    """From the window's last operation (``copy-done.3`` ends at
    1,088,460,801) to the next chunk's first (``copy.1`` at 1,095,858,219):
    7,397,418 ns in which the host left ``window_wait`` (at 1,090,642,286),
    unpacked, kept house, drained the backlog and took two requests in."""
    ev = json.loads(FIXTURE.read_text())
    idle = hs.idle_intervals(ev)
    gap = max(idle, key=lambda g: g[1] - g[0])
    assert gap == (1_088_460_801, 1_095_858_219)
    found = hs.attribute([gap], ev["host_spans"])
    assert found["by_phase"] == {
        # the second request's handling outlasts the gap: 1,590,820 of the first
        # (1,980,800 less its can_admit) + 2,819,083 of the second up to the gap's end
        "intake.handle_input": 4_409_903,
        "window_wait": 1_090_642_286 - 1_088_460_801,
        "admit.can_admit": 389_980 + 223_120,
        "unpack": 124_010,
        "intake": 14_750 + 14_870,  # before and between the two handlers
        "no_span": 8_300 + 3_810 + 3_100 + 2_290 + 1_740,  # between one span's end and the next's start
        "housekeeping": 7_040 + 5_550,
        "admit": 7_470,
    }
    assert found["gaps"] == [["intake.handle_input", 7_397_418, 0]]
    assert found["attributed_pct"] == pytest.approx(100 * (1 - 19_240 / 7_397_418))
    margins = hs.launch_and_wait_margins(ev, ev["host_spans"], r"^jit_program\(")
    # two windows in the cut: launched 344,659 and 164,778 ns before they start on the
    # device, their tokens in the host's hands 2,181,229 and 2,247,355 ns after they end
    assert margins == {"programs": 2, "launch_before_start_ns": [164_778, 254_718.5],
                       "wait_end_after_end_ns": [2_181_229, 2_214_292.0]}


def test_the_recorded_capture():
    ev = json.loads(FIXTURE.read_text())
    spans = ev["host_spans"]
    assert {p for p, _, _ in spans} >= {
        "window_launch", "window_wait", "unpack", "emit", "admit", "intake", "housekeeping"}
    idle = hs.idle_intervals(ev)
    found = hs.attribute(idle, spans)
    assert found["by_phase"] == dict(
        sorted(brute_force(idle, spans).items(), key=lambda kv: -kv[1]))
    import trace_reduce

    busy, window = trace_reduce.busy_and_window_s(ev)
    assert found["idle_ns"] / 1e9 == pytest.approx(window - busy, abs=1e-9)


# ---------------------------------------------------------------------------
# a real capture, on the CPU
# ---------------------------------------------------------------------------

CAPTURE = """
import jax, jax.numpy as jnp, sys
f = jax.jit(lambda x: (x @ x).sum())
x = jnp.ones((64, 64))
f(x).block_until_ready()
options = jax.profiler.ProfileOptions()
options.python_tracer_level = 0
jax.profiler.start_trace(sys.argv[1], profiler_options=options)
for turn in range(3):
    for phase in ("window_launch", "window_wait", "unpack"):
        with jax.profiler.TraceAnnotation("loop." + phase):
            if phase == "window_wait":
                with jax.profiler.TraceAnnotation("loop.admit.can_admit"):
                    f(x).block_until_ready()
with jax.profiler.TraceAnnotation("not.a.phase"):
    pass
jax.profiler.stop_trace()
"""


def test_a_real_capture_gives_the_phases_back_in_order(tmp_path):
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu", "HOME": str(tmp_path)}
    made = subprocess.run([sys.executable, "-c", CAPTURE, str(tmp_path / "cap")],
                          env=env, capture_output=True, text=True, timeout=300)
    assert made.returncode == 0, made.stderr[-2000:]
    spans = hs.load_in_child(str(tmp_path / "cap"), tmp_path / "spans.json")
    turn = ["window_launch", "window_wait", "admit.can_admit", "unpack"]
    assert [p for p, _, _ in spans] == turn * 3
    for (_, s0, d0), (_, s1, _) in zip(spans, spans[1:]):
        assert s1 >= s0 and d0 >= 0
    wait, child = spans[1], spans[2]
    assert wait[1] <= child[1] and child[1] + child[2] <= wait[1] + wait[2]
    assert hs.load_in_child(str(tmp_path / "nothing-here"), tmp_path / "none.json") is None


# ---------------------------------------------------------------------------
# a phase's milliseconds a period
# ---------------------------------------------------------------------------


def _snap(**hists):
    return {key: {"count": c, "sum_us": s, "counts": [0, 0, c]} for key, (c, s) in hists.items()}


GAP = json.loads((BENCH / "layer_metrics" / "gap_rebuild_ms.serve.json").read_text())["args"]
REST = json.loads((BENCH / "layer_metrics" / "gap_unattributed_ms.serve.json").read_text())["args"]


def test_a_phases_share_of_a_period():
    # rebuild ran in 300 of 800 periods, 2 ms each time: 0.75 ms a period
    run = {"serving_before": _snap(dispatch_gap_us=(100, 1e6), phase_rebuild_us=(40, 80_000.0)),
           "serving_after": _snap(dispatch_gap_us=(900, 10.6e6), phase_rebuild_us=(340, 680_000.0))}
    assert GAP == {"hist": "phase_rebuild_us", "per": "dispatch_gap_us"}
    assert per.read(run, GAP) == pytest.approx(0.75)


def test_behind_a_capture_and_never_zero_for_nothing():
    before = _snap(dispatch_gap_us=(100, 1e6), phase_rebuild_us=(40, 80_000.0))
    traced = _snap(dispatch_gap_us=(300, 13e6), phase_rebuild_us=(100, 200_000.0))
    after = _snap(dispatch_gap_us=(900, 20.8e6), phase_rebuild_us=(400, 800_000.0))
    run = {"serving_before": before, "serving_traced": traced, "serving_after": after}
    assert per.read(run, GAP) == pytest.approx(1.0)  # 600 ms over 600 periods, from the capture on
    # one histogram only (a server older than the phases), one snapshot, no period
    assert per.read({"serving_before": _snap(dispatch_gap_us=(1, 1.0)),
                     "serving_after": _snap(dispatch_gap_us=(9, 9.0))}, GAP) is None
    assert per.read({"serving_before": _snap(phase_rebuild_us=(1, 1.0)),
                     "serving_after": _snap(phase_rebuild_us=(9, 9.0))}, GAP) is None
    assert per.read({"serving_after": after}, GAP) is None
    assert per.read({"serving_before": after, "serving_after": after}, GAP) is None
    assert per.read({}, GAP) is None


def test_the_remainder_is_signed():
    parts = [k for k in REST["minus"]]
    assert len(parts) == 8 and REST["hist"] == REST["per"] == "dispatch_gap_us"
    zero = {k: (0, 0.0) for k in parts}
    # 800 periods of 12 ms; the eight parts gained 1 ms a period each but the last, 2.2 ms
    grown = {k: (800, 800_000.0) for k in parts[:-1]} | {parts[-1]: (5, 1_760_000.0)}
    run = {"serving_before": _snap(dispatch_gap_us=(0, 0.0), **zero),
           "serving_after": _snap(dispatch_gap_us=(800, 9.6e6), **grown)}
    assert per.read(run, REST) == pytest.approx(12.0 - 7.0 - 2.2)
    over = dict(grown, **{parts[0]: (800, 8e6)})  # phases of turns that observed no gap
    run["serving_after"] = _snap(dispatch_gap_us=(800, 9.6e6), **over)
    assert per.read(run, REST) == pytest.approx(12.0 - 16.0 - 2.2)
    del run["serving_after"][parts[3]]
    assert per.read(run, REST) is None
