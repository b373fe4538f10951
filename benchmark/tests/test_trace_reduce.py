"""The trace reduction: pure arithmetic on hand-made events, and the
recorded fixture (a 0.25 s cut of a real v5e trace of the llm node,
``fixtures/serve_trace_cut.json``) against hand-computed values."""
import json

import pytest
import trace_reduce as tr
from conftest import BENCH

US = 1000


def synthetic():
    ops = [["while.1", 0, 36 * US],                       # holds the three below
           ["fusion.1 f32[8]", 0, 10 * US], ["fusion.2 f32[8]", 12 * US, 10 * US],
           ["copy.3 f32[8]", 30 * US, 5 * US],
           ["fusion.7", 100 * US, 20 * US]]                # after a gap of 64
    mods = [["jit_window(1)", 0, 36 * US], ["jit_chunk(2)", 100 * US, 20 * US]]
    return {"planes": {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods}},
            "span_ns": [0, 120 * US]}


def test_union_merges_overlaps():
    assert tr.union_ns([(0, 10), (5, 10), (30, 5)]) == 20
    assert tr.union_ns([(0, 10), (2, 3)]) == 10
    assert tr.union_ns([]) == 0


def test_idle_share_busy_and_window():
    ev = synthetic()
    busy, window = tr.busy_and_window_s(ev)
    assert busy == pytest.approx(56e-6) and window == pytest.approx(120e-6)
    assert tr.idle_pct(ev) == pytest.approx(100 * (1 - 56 / 120))


def test_program_times_and_top_ops_and_gaps():
    ev = synthetic()
    assert tr.module_median_ms(ev, "window") == pytest.approx(0.036)
    assert tr.module_median_ms(ev, "chunk") == pytest.approx(0.020)
    assert tr.module_median_ms(ev, "nothing") is None
    assert tr.ops_busy_inside_ms(ev, "window") == pytest.approx(0.036)
    # self time: the while owns 36 - 10 - 10 - 5 = 11; families sum their copies
    assert tr.top_ops(ev) == [["fusion", pytest.approx(40e-6)],
                              ["while", pytest.approx(11e-6)],
                              ["copy", pytest.approx(5e-6)]]
    gaps = tr.idle_gaps(ev)
    assert len(gaps) == 1 and gaps[0][1] == pytest.approx(64e-6)
    assert gaps[0][0] == "not attributed, at +0.0000s"


def test_no_device_plane_reads_nothing():
    ev = {"planes": {"/host:CPU": {"python": [["x", 0, 10]]}}, "span_ns": [0, 10]}
    assert tr.idle_pct(ev) is None and tr.top_ops(ev) == [] and tr.idle_gaps(ev) == []


def test_cut_keeps_events_that_start_inside():
    ev = tr.cut(synthetic(), 0.00002, 0.00009)  # starts inside [20 us, 90 us)
    ops = ev["planes"]["/device:TPU:0"]["XLA Ops"]
    assert [o[0] for o in ops] == ["copy.3 f32[8]"]
    assert ev["span_ns"] == [30 * US, 35 * US]


# --- the recorded fixture: one period of the llm node's serving loop on a
# v5e (PR 23, chip call 1): a prefill chunk, the host's hand-over, one
# 8-tick decode window, the host's gap, the next chunk.

def fixture():
    return json.loads((BENCH / "tests" / "fixtures" / "serve_trace_cut.json").read_text())


def test_fixture_idle_share_by_hand():
    ev = fixture()
    mods = sorted(ev["planes"]["/device:TPU:0"]["XLA Modules"], key=lambda e: e[1])
    big = [m for m in mods if m[2] > 1_000_000]
    assert [m[0].split("(")[0] for m in big] == ["jit_step", "jit_program", "jit_step"]
    # by hand: two chunks of 6.040932 and 6.041066 ms, one window of
    # 60.334491 ms, nine sub-microsecond casts and slot writes (7.264 us):
    # 72.424 ms of programs, of which all but some 10 us (the seams
    # between operations) is operations, in a span of 122.930 ms.
    busy, window = tr.busy_and_window_s(ev)
    assert busy == pytest.approx(0.072414177, abs=1e-9)
    assert window == pytest.approx(0.122930268, abs=1e-9)
    assert busy == pytest.approx((6040932 + 6041066 + 60334491 + 7264) / 1e9, abs=2e-5)
    assert tr.idle_pct(ev) == pytest.approx(41.0933, abs=1e-3)


def test_fixture_program_times_and_top_operations():
    ev = fixture()
    assert tr.module_median_ms(ev, r"^jit_program\(") == pytest.approx(60.334491)
    assert tr.module_median_ms(ev, r"^jit_step\(") == pytest.approx((6.040932 + 6.041066) / 2)
    assert tr.ops_busy_inside_ms(ev, r"^jit_program\(") == pytest.approx(60.333313)
    top = tr.top_ops(ev)
    assert [t[0] for t in top[:5]] == ["attention_paged_batch_step", "mlp_step", "pad",
                                       "lm_head_argmax", "attention_paged_chunk_step"]
    # 28 layers x 8 ticks of the fused attention kernel: 224 calls
    calls = [o for o in ev["planes"]["/device:TPU:0"]["XLA Ops"]
             if o[0].startswith("attention_paged_batch_step")]
    assert len(calls) == 224
    assert top[0][1] == pytest.approx(sum(c[2] for c in calls) / 1e9)
    # the while loop itself owns next to nothing: its body is accounted to the body
    assert dict(map(tuple, top))["while"] < 2e-5
    gaps = tr.idle_gaps(ev)
    assert gaps[0][1] == pytest.approx(0.041539092)  # window end -> next chunk
    assert gaps[0][0] == "not attributed, at +0.0733s"


def test_short_names():
    assert tr.short_name("%pad.45 = s8[1536,153600]{1,0:T(8,128)(4,1)} pad(s8[1536,151936]{1,0} %x)") == "pad.45 s8[1536,153600]"
    assert tr.short_name("%while.2 = (s32[]{:T(128)}, s32[16]{0}) while(%t)") == "while.2"
    assert tr.short_name("jit_program(8306912539396743923)") == "jit_program(8306912539396743923)"
    assert tr.op_family("attention_paged_batch_step.24") == "attention_paged_batch_step"
