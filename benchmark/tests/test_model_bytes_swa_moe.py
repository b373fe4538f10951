"""``lib/model_bytes_swa_moe`` against hand arithmetic at K-EXAONE's
published widths as the cell cuts them (ISSUE 41's numbers), and the
three readers over made-up snapshots."""
import json

import pytest
from conftest import BENCH

import model_bytes_swa_moe as mb

CFG = {k: v for k, v in json.loads(
    (BENCH / "configs" / "k-exaone-236b-ep8.json").read_text()).items() if k != "bench"}


def test_parameters_a_layer():
    assert mb.attention_params(CFG) == 6144 * (8192 + 2 * 1024) + 8192 * 6144 == 113_246_208
    assert mb.expert_params(CFG) == 3 * 6144 * 2048 == 37_748_736
    assert mb.router_params(CFG) == 6144 * 128 == 786_432
    assert (mb.expert_layers(CFG), mb.global_layers(CFG), mb.window_layers(CFG)) == (7, 2, 6)


def test_what_every_tick_reads():
    dense = 3 * 6144 * 18432
    assert dense == 339_738_624
    assert mb.always_read_params(CFG) == (
        8 * 113_246_208 + dense + 7 * 37_748_736 + 6144 * 19200) == 1_627_914_240
    # the whole chip's int8: the 16 held experts of 7 layers beside it
    assert 8 * 113_246_208 + dense + 7 * 17 * 37_748_736 == 5_737_807_872


def test_cache_bytes_a_token():
    assert mb.kv_row_bytes(CFG) == 4096
    assert mb.kv_bytes_per_token(CFG) == 8192  # 2 global layers, not 8 x 4,096
    assert 16 * 16384 * mb.kv_bytes_per_token(CFG) == 2_147_483_648  # the pool
    assert 16 * 6 * 128 * mb.kv_row_bytes(CFG) == 50_331_648  # the rings


def test_decode_tick_bytes():
    base = 1_627_914_240 + 2 * 7 * 786_432
    assert 2 * 7 * 786_432 == 11_010_048
    assert mb.decode_tick_bytes(CFG, 0, 0, 0) == base
    # 6 live rows, each touching one held expert in every expert layer; one
    # row at position 9999, five at 199: rows attended x global layers, and
    # min(position + 1, 128) x window layers
    pages = 2 * (10000 + 5 * 200)
    rings = 6 * (6 * 128)
    assert mb.decode_tick_bytes(CFG, 6 * 7, pages, rings) == (
        base + 42 * 37_748_736 + (pages + rings) * 4096)


def test_chunk_flops():
    matrices = 256 * 2.0 * (1_627_914_240 + 7 * (786_432 + 1.0 * 37_748_736))
    per_pair = 4 * 64 * 128
    first = mb.chunk_flops(CFG, 256, 0, 1.0)
    assert first == matrices + 2 * (256 * 257 / 2) * per_pair + 6 * 256 * 128 * per_pair
    # 16.8 MFLOP a row of position in the two global layers
    at = mb.chunk_flops(CFG, 256, 8192, 1.0)
    assert (at - first) / 8192 == 2 * 256 * per_pair == 16_777_216
    assert 0.9e12 < first < 1.0e12


def test_per_and_capture_edges():
    before = {"a": 10, "n": 2}
    after = {"a": 40, "n": 8}
    assert mb.per(before, after, "a", "n") == 5.0
    assert mb.per(None, after, "a", "n") == 5.0
    assert mb.per(before, after, "missing", "n") is None
    assert mb.per(before, {"a": 40, "n": 2}, "a", "n") is None  # no unit gained
    assert mb.capture_edges({}) is None
    run = {"serving_traced": {"capture_counters": {"start": before, "stop": after}}}
    assert mb.capture_edges(run) == (before, after)


def _run(**over):
    start = {"swa_decode_ticks": 100, "moe_touched": 1000, "global_kv_rows_read": 10_000,
             "swa_ring_rows_read": 5_000, "swa_chunks": 10, "swa_chunk_positions": 1000,
             "moe_tokens": 7000, "moe_local_pairs": 7000}
    stop = {"swa_decode_ticks": 300, "moe_touched": 9400, "global_kv_rows_read": 4_410_000,
            "swa_ring_rows_read": 925_000, "swa_chunks": 30, "swa_chunk_positions": 41_960,
            "moe_tokens": 70_000, "moe_local_pairs": 70_000}
    run = {
        "events": [["pid", "XLA Modules", "jit_program(1)", 0.0, 40_000.0],
                   ["pid", "XLA Modules", "jit_step(2)", 50_000.0, 20_000.0]],
        "config": {"model": CFG, "node_env": {"llm": {}}},
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        "serving_traced": {"capture_counters": {"start": start, "stop": stop}},
        "serving_before": {"global_kv_rows_swept": 1000, "global_kv_rows_read": 500},
        "serving_after": {"global_kv_rows_swept": 9000, "global_kv_rows_read": 2500},
    }
    run.update(over)
    return run


def test_the_readers_read_nothing_from_a_program_without_the_counters():
    """As on the parent commit: every reader returns None, and raises
    nothing."""
    import importlib

    for name in ("window_hbm_share_swa_moe", "chunk_mxu_share_swa_moe", "kv_swept_over_read"):
        reader = importlib.import_module(name)
        args = {"match": "^jit_program\\(", "node": "llm", "ticks_env": "K", "ticks_default": 8,
                "chunk_env": "C", "chunk_default": 256}
        empty = _run(serving_traced={}, serving_before={"x": 1}, serving_after={"x": 2})
        assert reader.read(empty, args) is None
        assert reader.read({**empty, "events": None}, args) is None


def test_swept_over_read():
    import kv_swept_over_read

    assert kv_swept_over_read.read(_run(), {}) == 4.0


def test_window_share_over_the_captured_ticks(monkeypatch):
    import trace_reduce
    import window_hbm_share_swa_moe as reader

    monkeypatch.setattr(trace_reduce, "module_median_ms", lambda events, match: 40.0)
    args = {"match": "^jit_program\\(", "node": "llm", "ticks_env": "DORA_MULTISTEP_K",
            "ticks_default": 8}
    # a tick: 42 experts touched, 22,000 rows attended in pages, 4,600 in rings
    want = 8 * mb.decode_tick_bytes(CFG, 42.0, 22_000.0, 4_600.0) / 819e9 / 0.040 * 100
    assert reader.read(_run(), args) == pytest.approx(want)
    assert 0 < want < 100


def test_chunk_share_at_the_captured_chunks_mean_position(monkeypatch):
    import chunk_mxu_share_swa_moe as reader
    import trace_reduce

    monkeypatch.setattr(trace_reduce, "module_median_ms", lambda events, match: 20.0)
    args = {"match": "^jit_step\\(", "node": "llm", "chunk_env": "DORA_PREFILL_CHUNK",
            "chunk_default": 256}
    want = mb.chunk_flops(CFG, 256, 2048.0, 1.0) / 197e12 / 0.020 * 100
    assert reader.read(_run(), args) == pytest.approx(want)
    assert 0 < want < 100
