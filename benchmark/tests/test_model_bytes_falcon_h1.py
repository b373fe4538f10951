"""The hybrid (state-space + attention) bytes and FLOPs against values
computed by hand from Falcon-H1-34B's published widths (ISSUE 33's
arithmetic), and the three readers over them."""
import json

import model_bytes_falcon_h1 as mb
from conftest import BENCH

RAW = json.loads((BENCH / "configs" / "falcon-h1-34b-pp8.json").read_text())
CFG = {k: v for k, v in RAW.items() if k != "bench"}
CELL = "falcon-h1-34b-pp8.chat-16"


def test_parameters_of_the_parts():
    # q 5120x2560, k and v 5120x512, o 2560x5120
    assert mb.attention_params(CFG) == 13_107_200 + 2 * 2_621_440 + 13_107_200 == 31_457_280
    # W_in 5120 x [z 4096 | x 4096, B 512, C 512 | dt 32], W_out 4096 x 5120
    assert mb.mixer_in_width(CFG) == 4096 + 5120 + 32 == 9248
    assert mb.mixer_params(CFG) == 47_349_760 + 20_971_520 == 68_321_280
    assert mb.mlp_params(CFG) == 3 * 5120 * 21504 == 330_301_440
    assert mb.layer_params(CFG) == 430_080_000  # the issue's 430.1 M
    assert mb.tick_weight_params(CFG) == 9 * 430_080_000 + 5120 * 32640 == 4_037_836_800


def test_the_cut_is_the_stage_and_nothing_inside_a_layer():
    assert RAW["bench"]["published"] == {
        "num_hidden_layers": 72, "vocab_size": 261120, "max_position_embeddings": 262144}
    assert sorted(RAW["bench"]["reduced"]) == sorted(RAW["bench"]["published"])
    assert 72 == 8 * CFG["num_hidden_layers"] and 261120 == 8 * CFG["vocab_size"]
    # bytes on the device, as the file states them: 3.87 + 0.334 + 0.167 + 0.604 + 0.604
    state = 16 * 9 * mb.state_values_a_row_a_layer(CFG) * 4
    pool = 16 * 2048 * 9 * 2 * 4 * 128 * 2
    total = 9 * 430_080_000 + 2 * 5120 * 32640 + 5120 * 32640 + state + pool
    assert state == pool == 603_979_776
    assert abs(total - 5.58e9) < 1e7 and total / 16e9 > 0.25


def test_state_and_tick_bytes():
    assert mb.state_values_a_row_a_layer(CFG) == 32 * 128 * 256 == 1_048_576
    assert mb.state_step_bytes(CFG) == 8_388_608  # 4.19 MB read and written
    assert mb.decode_tick_bytes(CFG, 0.0) == 4_037_836_800
    one_row = mb.decode_tick_bytes(CFG, 1.0) - mb.decode_tick_bytes(CFG, 0.0)
    assert one_row == 9 * 8_388_608
    # 16 live rows: 1.21 GB of state beside 4.04 GB of weights, 6.4 ms at 819 GB/s
    assert abs(mb.decode_tick_bytes(CFG, 16.0) - 5.2458e9) < 1e6


def test_chunk_flops():
    assert mb.matmul_flops_per_token(CFG) == 2 * 4_037_836_800
    assert abs(256 * mb.matmul_flops_per_token(CFG) - 2.0674e12) < 1e9  # 10.5 ms at the peak


def _run(events=None, after=None, before=None):
    """A traced run whose capture started at ``before`` and stopped at ``after``."""
    return {
        "events": events, "serving_before": {}, "serving_after": after or {},
        "serving_traced": {**(after or {}), "capture_counters": {
            "start": before or {}, "stop": after or {}}},
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        "config": {"model": CFG, "node_env": {"llm": {}}},
    }


def _events(ops, modules):
    return {"planes": {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules}},
            "span_ns": [0, 10 ** 9]}


def test_readers_compute_shares_from_the_counters_and_the_trace():
    import chunk_mxu_share_ssm_hybrid
    import ssm_kernel_hbm_share
    import window_hbm_share_ssm_hybrid

    before = {"ssm_row_ticks": 1000, "ssm_decode_ticks": 100}
    after = {"ssm_row_ticks": 1000 + 8 * 12, "ssm_decode_ticks": 108}
    # two kernel calls of 200 us, one window of 100 ms, one chunk of 40 ms
    events = _events(
        [["ssm_state_step.3 f32[16,32,128]", 0, 200_000],
         ["ssm_state_step f32[16,32,128]", 300_000, 200_000],
         ["mlp_step.1 bf16[16,5120]", 600_000, 500_000]],
        [["jit_program(123)", 0, 100_000_000], ["jit_step(456)", 0, 40_000_000]])
    run = _run(events, after, before)
    assert mb.live_rows_a_tick(before, after) == 12.0
    assert mb.live_rows_a_tick(None, {"decode_tokens": 5}) is None
    share = ssm_kernel_hbm_share.read(run, {"match": "^ssm_state_step"})
    assert abs(share - 100 * 12 * 2 * 8_388_608 / 819e9 / 400e-6) < 1e-9  # 61.45 %
    assert mb.live_rows_in_capture(run) == 12.0
    # the window's own counters say nothing of the captured ticks: one edge
    # alone, or none, and nothing is read
    for edges in ({"start": before}, {}):
        blind = {**run, "serving_traced": {**after, "capture_counters": edges}}
        assert mb.live_rows_in_capture(blind) is None
        assert ssm_kernel_hbm_share.read(blind, {"match": "^ssm_state_step"}) is None
    # the device's wait for a whole state array that the compiler keeps on chip
    # around the kernel is the state's movement too; other copies are not
    copied = _run(_events(
        [["ssm_state_step.3 f32[16,32,128]", 0, 200_000],
         ["copy-done.122 f32[16,32,128,256]", 200_000, 100_000],
         ["ssm_state_step f32[16,32,128]", 300_000, 200_000],
         ["copy-done.17 bf16[16,1,5120]", 500_000, 50_000],
         ["copy-done.9 f32[16,32,128]", 550_000, 50_000]],
        [["jit_program(123)", 0, 100_000_000]]), after, before)
    with_copies = {"match": "^ssm_state_step", "copies": "^copy-done"}
    assert abs(ssm_kernel_hbm_share.read(copied, with_copies) - share * 400 / 500) < 1e-9
    assert abs(ssm_kernel_hbm_share.read(copied, {"match": "^ssm_state_step"}) - share) < 1e-9
    metric = json.loads((BENCH / "layer_metrics" / "ssm_state_step_hbm_pct.json").read_text())
    assert metric["args"] == with_copies
    args = {"match": "^jit_program\\(", "node": "llm", "ticks_env": "DORA_MULTISTEP_K",
            "ticks_default": 8}
    window = window_hbm_share_ssm_hybrid.read(run, args)
    assert abs(window - 100 * 8 * mb.decode_tick_bytes(CFG, 12.0) / 819e9 / 0.1) < 1e-9
    assert 40 < window < 50
    chunk = chunk_mxu_share_ssm_hybrid.read(run, {
        "match": "^jit_step\\(", "node": "llm", "chunk_env": "DORA_PREFILL_CHUNK",
        "chunk_default": 256})
    assert abs(chunk - 100 * 2.0674e12 / 197e12 / 0.04) < 0.01  # 26.2 %


def test_readers_return_nothing_without_the_programs_counters():
    """On a program that has no such kernel, counter or model (the
    parent of the PR that added them) every reader returns None."""
    import chunk_mxu_share_ssm_hybrid
    import ssm_kernel_hbm_share
    import window_hbm_share_ssm_hybrid

    events = _events([["mlp_step.1 bf16[16,1536]", 0, 500_000]],
                     [["jit_program(1)", 0, 10 ** 7], ["jit_step(2)", 0, 10 ** 6]])
    run = _run(events, {"decode_tokens": 5})
    run["config"] = {"model": {"model_type": "qwen2"}, "node_env": {"llm": {}}}
    args = {"match": "^jit_program\\(", "node": "llm", "ticks_env": "K", "ticks_default": 8}
    assert ssm_kernel_hbm_share.read(run, {"match": "^ssm_state_step"}) is None
    assert window_hbm_share_ssm_hybrid.read(run, args) is None
    assert chunk_mxu_share_ssm_hybrid.read(run, {**args, "match": "^jit_step\\(",
                                                 "chunk_env": "C", "chunk_default": 256}) is None
    # counters but no kernel in the capture, and no capture at all
    counted = _run(events, {"ssm_row_ticks": 80, "ssm_decode_ticks": 8})
    assert ssm_kernel_hbm_share.read(counted, {"match": "^ssm_state_step"}) is None
    assert ssm_kernel_hbm_share.read(_run(None, counted["serving_after"]),
                                     {"match": "^ssm_state_step"}) is None


def test_the_manifest_lists_the_cell_where_it_reports():
    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    listed = {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
              if CELL in m.get("workloads", [])}
    assert {"ssm_state_step_hbm_pct", "decode_window_hbm_pct.ssm-hybrid",
            "prefill_chunk_mxu_pct.ssm-hybrid", "device_idle_pct.serve",
            "dispatch_gap_ms.serve", "decode_window_dev_ms", "prefill_chunk_dev_ms",
            "compiles_in_window.serve"} <= listed
    traffic = json.loads((BENCH / "traffic" / "chat-16.json").read_text())
    base = json.loads((BENCH / "traffic" / "callers-16.json").read_text())
    same = ("callers", "prompt_tokens", "output_tokens", "repeat_every", "block",
            "warm_prompt_tokens", "warm_step_tokens", "max_requests_per_s")
    assert all(traffic[k] == base[k] for k in same)
    assert traffic["shape_seed"] != base["shape_seed"] and traffic["reference_sample"] == 4
