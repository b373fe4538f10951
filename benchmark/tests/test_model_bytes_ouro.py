"""The looped model's bytes and FLOPs against values computed by hand
from Ouro-2.6B's published widths (ISSUE 35's arithmetic), the K/V term
against a counted example, and the two readers over them."""
import json
from pathlib import Path

import model_bytes_ouro as mb
from conftest import BENCH

CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
RAW = json.loads((BENCH / "configs" / "ouro-2p6b.json").read_text())
CFG = {k: v for k, v in RAW.items() if k != "bench"}
CELL = "ouro-2p6b.loop-chat-16"


def test_parameters_of_the_parts():
    # 4 x 2048^2 of attention + 3 x 2048 x 5632 of SwiGLU
    assert mb.layer_params(CFG) == 4 * 2048 ** 2 + 3 * 2048 * 5632 == 51_380_224
    assert mb.stack_params(CFG) == 48 * 51_380_224 == 2_466_250_752
    assert mb.head_params(CFG) == 2048 * 49152 == 100_663_296
    # a tick streams the stack four times and the head once: 9.97 GB of int8
    assert mb.tick_weight_params(CFG) == 4 * 2_466_250_752 + 100_663_296 == 9_965_666_304


def test_the_cut_is_the_context_and_nothing_else():
    bench = RAW["bench"]
    assert list(bench["reduced"]) == ["max_position_embeddings"]
    assert bench["published"] == {"max_position_embeddings": 65536}
    assert CFG["max_position_embeddings"] == int(bench["node_env"]["llm"]["DORA_MAX_SEQ"])
    assert (CFG["num_hidden_layers"], CFG["total_ut_steps"], CFG["early_exit_threshold"],
            CFG["vocab_size"]) == (48, 4, 1, 49152)
    # the catalog row's keys, all of them, as published (where the catalog is at hand)
    if CATALOG.exists():
        row = next(json.loads(line) for line in CATALOG.open() if '"Ouro-2.6B"' in line)
        assert bench["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if CFG.get(k) != v} == {
            "max_position_embeddings"}
    # bytes on the device, as the file states them: 2.47 + 0.10 + 0.20 + 9.66
    weights = mb.stack_params(CFG) + mb.head_params(CFG) + 2 * mb.head_params(CFG)
    pool = 384 * 16 * mb.kv_bytes_per_token(CFG)
    assert abs(weights - 2.77e9) < 1e7 and pool == 9_663_676_416
    assert 0.7 < (weights + pool) / 16e9 < 0.8


def test_the_kv_term_against_a_counted_example():
    assert mb.kv_entry_bytes(CFG) == 2 * 16 * 128 * 2 == 8192
    assert mb.kv_bytes_per_token(CFG) == 192 * 8192 == 1_572_864  # 55 x Qwen2.5-1.5B's 28,672
    assert mb.decode_tick_bytes(CFG, 0.0) == 9_965_666_304
    # three live rows at positions 9, 99 and 299 attend to 10, 100 and 300 rows,
    # in each of 4 passes: the program counts 4 x 410 rows read that tick, and
    # every one is K and V of 48 layers
    rows_read = 4 * (10 + 100 + 300)
    counted = sum(4 * 48 * n * (2 * 16 * 128 * 2) for n in (10, 100, 300))
    assert mb.decode_tick_bytes(CFG, rows_read) - 9_965_666_304 == counted == 644_874_240
    # the issue's 14 rows of 300 tokens: 6.6 GB beside 9.97 GB of weights
    assert abs(mb.decode_tick_bytes(CFG, 4 * 14 * 300) - (9.9657e9 + 6.606e9)) < 1e7


def test_chunk_flops():
    # 256 rows x 4 passes x 2 x 2,466,250,752 = 5.05 TFLOP: 25.6 ms at the peak
    weights = 256 * 4 * 2 * 2_466_250_752
    assert abs(weights - 5.0509e12) < 1e9
    # a first chunk: row i attends to i + 1 rows; q.k and p.v are 2 x 2 x 16 x 128 FLOP a pair
    pairs = 256 * 257 // 2
    assert mb.chunk_flops(CFG, 256, 0) == weights + 4 * 48 * pairs * 4 * 16 * 128
    # a chunk at position 512 sees 512 more rows from every one of its own
    more = mb.chunk_flops(CFG, 256, 512) - mb.chunk_flops(CFG, 256, 0)
    assert more == 4 * 48 * 256 * 512 * 4 * 16 * 128
    assert mb.chunk_flops(CFG, 256, 512) / weights < 1.06  # the score term is a few percent


def _run(events=None, after=None, before=None):
    """A traced run whose capture started at ``before`` and stopped at ``after``."""
    return {
        "events": events, "serving_before": {}, "serving_after": after or {},
        "serving_traced": {**(after or {}), "capture_counters": {
            "start": before or {}, "stop": after or {}}},
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        "config": {"model": CFG, "node_env": {"llm": {}}},
    }


def _events(modules):
    return {"planes": {"/device:TPU:0": {"XLA Ops": [], "XLA Modules": modules}},
            "span_ns": [0, 10 ** 9]}


WINDOW_ARGS = {"match": "^jit_program\\(", "node": "llm", "ticks_env": "DORA_MULTISTEP_K",
               "ticks_default": 8}
CHUNK_ARGS = {"match": "^jit_step\\(", "node": "llm", "chunk_env": "DORA_PREFILL_CHUNK",
              "chunk_default": 256}


def test_readers_compute_shares_from_the_counters_and_the_trace():
    import chunk_mxu_share_looped
    import window_hbm_share_looped

    before = {"loop_kv_rows_read": 5000, "loop_decode_ticks": 100, "loop_passes": 4000,
              "loop_chunks": 10, "loop_chunk_positions": 1280}
    # 16 ticks of 14 live rows at 300 rows each; 4 chunks, three first ones and one at 256
    after = {"loop_kv_rows_read": 5000 + 16 * 4 * 14 * 300, "loop_decode_ticks": 116,
             "loop_passes": 4000 + 16 * 4 * 14, "loop_chunks": 14,
             "loop_chunk_positions": 1280 + 256}
    events = _events([["jit_program(123)", 0, 250_000_000], ["jit_step(456)", 0, 45_000_000]])
    run = _run(events, after, before)
    assert mb.rows_read_a_tick(before, after) == 4 * 14 * 300
    assert mb.live_rows_a_tick(before, after, 4) == 14.0
    assert mb.chunk_position(before, after) == 64.0
    window = window_hbm_share_looped.read(run, WINDOW_ARGS)
    assert abs(window - 100 * 8 * mb.decode_tick_bytes(CFG, 16800) / 819e9 / 0.25) < 1e-9
    assert 64 < window < 66  # 8 x 16.57 GB in 250 ms
    chunk = chunk_mxu_share_looped.read(run, CHUNK_ARGS)
    assert abs(chunk - 100 * mb.chunk_flops(CFG, 256, 64.0) / 197e12 / 0.045) < 1e-9
    assert 57 < chunk < 59
    metric = json.loads((BENCH / "layer_metrics" / "decode_window_hbm_pct.looped.json").read_text())
    assert metric["args"] == WINDOW_ARGS and metric["reader"] == "window_hbm_share_looped"
    metric = json.loads((BENCH / "layer_metrics" / "prefill_chunk_mxu_pct.looped.json").read_text())
    assert metric["args"] == CHUNK_ARGS and metric["reader"] == "chunk_mxu_share_looped"


def test_readers_return_nothing_without_the_programs_counters():
    """On a program that has no such counters (the parent of the PR that
    added them, or another model) every reader returns None."""
    import chunk_mxu_share_looped
    import window_hbm_share_looped

    events = _events([["jit_program(1)", 0, 10 ** 7], ["jit_step(2)", 0, 10 ** 6]])
    other = {"decode_tokens": 5, "ssm_row_ticks": 80, "ssm_decode_ticks": 8}
    for run in (_run(events, other), _run(events, other, other), _run(events)):
        assert window_hbm_share_looped.read(run, WINDOW_ARGS) is None
        assert chunk_mxu_share_looped.read(run, CHUNK_ARGS) is None
    counted = {"loop_kv_rows_read": 800, "loop_decode_ticks": 8, "loop_chunks": 2,
               "loop_chunk_positions": 256}
    # counters but no capture, one edge alone, or no tick and no chunk between the edges
    assert window_hbm_share_looped.read(_run(None, counted), WINDOW_ARGS) is None
    blind = _run(events, counted)
    blind["serving_traced"]["capture_counters"] = {"start": {}}
    assert window_hbm_share_looped.read(blind, WINDOW_ARGS) is None
    still = _run(events, counted, counted)
    assert window_hbm_share_looped.read(still, WINDOW_ARGS) is None
    assert chunk_mxu_share_looped.read(still, CHUNK_ARGS) is None


def test_backlog_wait_is_the_existing_reader_over_another_histogram():
    import serving_hist_mean_ms

    metric = json.loads((BENCH / "layer_metrics" / "backlog_wait_ms.serve.json").read_text())
    assert metric["reader"] == "serving_hist_mean_ms" and metric["layer"] == "admission"
    hist = lambda count, total: {"backlog_wait_us": {
        "count": count, "sum_us": total, "counts": [count]}}
    run = {"serving_before": hist(10, 1_000.0), "serving_after": hist(30, 4_001_000.0)}
    assert serving_hist_mean_ms.read(run, metric["args"]) == 200.0
    assert serving_hist_mean_ms.read({"serving_before": {}, "serving_after": {}},
                                     metric["args"]) is None


def test_the_manifest_lists_the_cell_where_it_reports():
    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert manifest["workloads"][-1] == cell and manifest["configs"][-1]["name"] == "ouro-2p6b"
    assert manifest["configs"][-1]["reduced"] == ["max_position_embeddings"]
    assert manifest["configs"][-1]["source"] == RAW["bench"]["source"]
    listed = {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
              if CELL in m.get("workloads", [])}
    assert {"decode_window_hbm_pct.looped", "prefill_chunk_mxu_pct.looped",
            "backlog_wait_ms.serve", "device_idle_pct.serve", "dispatch_gap_ms.serve",
            "decode_window_dev_ms", "prefill_chunk_dev_ms", "compiles_in_window.serve",
            "emit_ms.serve"} <= listed
    assert len(listed & {"tokens_per_s", "ttft_p95_ms", "tpot_p50_ms", "tpot_p95_ms"}) >= 1
    new = [m for m in manifest["per_layer"] if m["name"].endswith(".looped")
           or m["name"] == "backlog_wait_ms.serve"]
    assert [m["workloads"] for m in new] == [[CELL]] * 3
    traffic = json.loads((BENCH / "traffic" / "loop-chat-16.json").read_text())
    base = json.loads((BENCH / "traffic" / "callers-16.json").read_text())
    same = ("callers", "prompt_tokens", "output_tokens", "repeat_every", "block",
            "warm_prompt_tokens", "warm_step_tokens", "max_requests_per_s")
    assert all(traffic[k] == base[k] for k in same)
    assert traffic["shape_seed"] == 20260930 and traffic["reference_sample"] == 4
