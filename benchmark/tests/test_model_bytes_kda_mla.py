"""``model_bytes_kda_mla``'s arithmetic at the cell's configuration: the
numbers ISSUE 58, the configuration's file and the layer metrics' ``what``
state, and the two roofline functions on made-up counts."""
import json

from conftest import BENCH

import model_bytes_kda_mla as mb

RAW = json.loads((BENCH / "configs" / "kimi-linear-48b-ep4.json").read_text())
CFG = {k: v for k, v in RAW.items() if k != "bench"}


def test_the_parameters_a_kind_of_layer_holds():
    assert mb.kda_layers(CFG) == 7 and mb.mla_layers(CFG) == 2 and mb.expert_layers(CFG) == 8
    # q, k, v, o 4 x 9,437,184; the two low-rank gates; beta; the convolutions
    assert mb.kda_params(CFG) == 4 * 9_437_184 + 2 * (294_912 + 524_288) + 73_728 + 49_152
    assert mb.kda_params(CFG) == 39_510_016
    assert mb.mla_params(CFG) == 14_155_776 + 1_327_104 + 4_194_304 + 9_437_184 == 29_114_368
    assert mb.expert_params(CFG) == 7_077_888 and mb.dense_params(CFG) == 63_700_992
    assert mb.router_params(CFG) == 2304 * 256  # the router keeps its published width
    assert [mb.layer_params(CFG, i) for i in range(9)] == [
        103_211_008, 499_572_736, 499_572_736, 489_177_088, 499_572_736,
        499_572_736, 499_572_736, 489_177_088, 499_572_736]


def test_the_bytes_on_the_device_are_the_configurations():
    w = mb.weight_bytes(CFG)
    assert w == {"layers_int8": 4_079_001_600, "head_int8": 94_371_840,
                 "embedding_bf16": 188_743_680, "routers_bf16": 9_437_184,
                 "total": 4_371_554_304}
    text = RAW["bench"]["bytes_on_the_device"]
    for number in (39_510_016, 29_114_368, 7_077_888, 63_700_992, 103_211_008,
                   499_572_736, 489_177_088, 4_079_001_600, 94_371_840, 188_743_680,
                   939_524_096, 2_684_354_560, 15_196_160):
        assert f"{number:,}" in text, number
    assert 64 * 7 * mb.state_bytes_per_row(CFG) == 939_524_096
    assert 64 * 16384 * mb.kv_bytes_per_token(CFG) == 2_684_354_560
    # the bf16 checkpoint: every matrix and both ends at 2 bytes
    assert 2 * (w["layers_int8"] + 2 * w["head_int8"]) == 8_535_490_560


def test_a_decode_ticks_bytes():
    assert mb.always_read_params(CFG) == 549_494_784
    assert mb.bf16_params(CFG) == 8 * 589_824
    assert mb.state_bytes_per_row(CFG) == 2_097_152 and mb.state_step_bytes(CFG) == 4_194_304
    assert mb.latent_row_bytes(CFG) == 1280 and mb.kv_bytes_per_token(CFG) == 2560
    nothing = mb.decode_tick_bytes(CFG, 0, 0, 0)
    assert nothing == 549_494_784 + 2 * 4_718_592
    # ISSUE 58's tick: 64 live rows at 10k rows, 55 of 64 experts a layer
    tick = mb.decode_tick_bytes(CFG, 8 * 55, 7 * 64, 2 * 64 * 10_000)
    assert tick - nothing == 440 * 7_077_888 + 448 * 4_194_304 + 1_280_000 * 1280
    assert 7.1e9 < tick < 7.3e9  # 8.8 ms at 819 GB/s
    # the layer metrics' texts state the same numbers
    spec = json.loads((BENCH / "layer_metrics" / "decode_window_hbm_pct.kda-mla.json").read_text())
    for number in (549_494_784, 9_437_184, 7_077_888, 4_194_304):
        assert f"{number:,}" in spec["what"]
    assert "1,280 B" in spec["what"]


def test_a_chunks_flops():
    rule = mb.delta_rule_flops(CFG, 256)
    per_block = 2 * 3 * 16 ** 3 + 3 * 16 * 128 * 128 + 2 * 16 * 16 * 128
    assert rule == 2.0 * 32 * 16 * per_block
    cold = mb.chunk_flops(CFG, 256, 0.0, 0.0)
    assert cold == 256 * 2.0 * (549_494_784 + 4_718_592) + 7 * rule
    # pairs: 8 of 256 experts chosen, a quarter held: 2 a token a layer
    full = mb.chunk_flops(CFG, 256, 8000.0, 2.0)
    assert full - cold == (256 * 2.0 * 8 * 2.0 * 7_077_888
                           + 2 * 256 * 2.0 * 32 * (576 + 512) * 8000.0)
    assert 0.0 < full / 197e12 < 0.01  # some 4 ms of the MXU's peak


def test_the_readers_return_nothing_where_a_program_has_no_such_counter():
    """The parent's program serves no ``kimi_linear`` and prints none of
    these counters: each new reader returns None and raises nothing."""
    import importlib

    run = {"serving_before": {"decode_tokens": 1}, "serving_after": {"decode_tokens": 9},
           "serving_traced": {"decode_tokens": 5}, "events": None, "reduced": None,
           "config": {"model": CFG, "node_env": RAW["bench"]["node_env"]},
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}
    for name in ("decode_window_hbm_pct.kda-mla", "prefill_chunk_mxu_pct.kda-mla",
                 "kda_state_step_hbm_pct", "latent_kv_swept_over_read"):
        spec = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
        reader = importlib.import_module(spec["reader"])
        assert reader.read(run, spec.get("args", {})) is None, name
    swept = importlib.import_module("latent_swept_over_read")
    assert swept.read({"serving_before": {"mla_rows_swept": 10, "mla_rows_in_context": 4},
                       "serving_after": {"mla_rows_swept": 40, "mla_rows_in_context": 24}},
                      {}) == 1.5
