"""``model_bytes_cca_moe``: the bytes a decode tick must move and the
operations a chunk needs at the cell's cut (the published widths), by hand."""
import json

from conftest import BENCH

import model_bytes_cca_moe as mb

RAW = json.loads((BENCH / "configs" / "zaya1-8b-pp2.json").read_text())
CFG = {k: v for k, v in RAW.items() if k != "bench"}


def test_the_parameters_of_a_layer_by_hand():
    assert mb.attention_params(CFG) == 2048 * (1024 + 256 + 128 + 128) + 1024 * 2048 == 5_242_880
    assert mb.conv_params(CFG) == 1280 * 2 + 1280 + 10 * 128 * 128 * 2 + 1280 == 332_800
    assert mb.router_params(CFG) == 2048 * 256 + 2 * 256 * 256 + 256 * 16 + 3 * 256 == 660_224
    assert mb.expert_params(CFG) == 3 * 2048 * 2048 == 12_582_912
    assert mb.always_read_params(CFG) == 20 * 5_242_880 + 2048 * 131_136 == 373_424_128
    # what the configuration file says a layer holds in int8
    assert mb.attention_params(CFG) + 16 * mb.expert_params(CFG) == 206_569_472
    assert "206,569,472" in RAW["bench"]["bytes_on_the_device"]


def test_a_cached_token_is_20480_bytes_and_a_tail_5376():
    assert mb.kv_row_bytes(CFG) == 1024
    assert mb.kv_bytes_per_token(CFG) == 20_480
    assert mb.kv_bytes_per_token(CFG, 4.0) == 40_960  # float32 on the CPU
    assert mb.tail_bytes(CFG) == (2 * 1280 + 128) * 2 == 5_376
    assert "5,376" in RAW["bench"]["bytes_on_the_device"]


def test_a_decode_tick_by_hand():
    # 16 live rows at 3,000 rows of context, 10 experts touched a layer
    touched, rows, row_ticks = 20 * 10, 20 * 16 * 3000, 20 * 16
    got = mb.decode_tick_bytes(CFG, touched, rows, row_ticks)
    assert got == (373_424_128 + 2 * 20 * (660_224 + 332_800) + 200 * 12_582_912
                   + 960_000 * 1024 + 2 * 320 * 5_376)
    # ISSUE 52's "some 4 GB": the experts are the larger part, the cache a quarter
    assert 3.8e9 < got < 4.0e9
    assert 0.6 < 200 * 12_582_912 / got < 0.7 and 0.2 < 960_000 * 1024 / got < 0.3
    # no live row: the always-read matrices, the routers and the convolutions alone
    assert mb.decode_tick_bytes(CFG, 0, 0, 0) == 373_424_128 + 39_720_960


def test_a_chunk_by_hand():
    matrices = 373_424_128 + 20 * (660_224 + 332_800 + 1.0 * 12_582_912)
    got = mb.chunk_flops(CFG, 256, 1000.0, 1.0)
    causal = 256 * 1000.0 + 256 * 257 / 2.0
    assert got == 256 * 2 * matrices + 20 * causal * 4 * 8 * 128
    # ONE expert a layer: every expert is held, so a pair a token lands
    assert mb.chunk_flops(CFG, 256, 1000.0, 0.5) < got
    # the causal products are a small part of a chunk at these contexts
    assert 20 * causal * 4 * 8 * 128 / got < 0.1


def test_the_readers_return_none_where_the_program_has_no_such_counters():
    import cca_kv_swept_over_read
    import chunk_mxu_share_cca_moe
    import window_hbm_share_cca_moe

    parent = {"serving_before": {"moe_tokens": 1}, "serving_after": {"moe_tokens": 9},
              "events": [[0, 1, "x"]]}
    assert cca_kv_swept_over_read.read(parent, {}) is None
    assert window_hbm_share_cca_moe.read(parent, {}) is None
    assert chunk_mxu_share_cca_moe.read(parent, {}) is None
    mine = {"serving_before": {"cca_kv_rows_swept": 1280, "cca_kv_rows_read": 1000},
            "serving_after": {"cca_kv_rows_swept": 3840, "cca_kv_rows_read": 3500}}
    assert cca_kv_swept_over_read.read(mine, {}) == 2560 / 2500
