"""``lib/model_bytes_kda_dsa`` against hand arithmetic at GLM-5.3-Flash's
cut (the numbers ISSUE 43 and the configuration's file state)."""
import json

import pytest
from conftest import BENCH

import model_bytes_kda_dsa as mb

RAW = json.loads((BENCH / "configs" / "glm-5p3-flash-ep8.json").read_text())
CFG = {k: v for k, v in RAW.items() if k != "bench"}


def test_the_parameters_of_each_part():
    assert mb.kda_params(CFG) == 3 * 33_554_432 + 33_554_432 + 3_506_176 == 137_723_904
    assert mb.dsa_params(CFG) == 117_440_512 + 6_946_816 == 124_387_328
    assert mb.expert_params(CFG) == 25_165_824
    assert mb.router_params(CFG) == 4096 * 288 == 1_179_648
    assert mb.map_params(CFG) == 2 * 393_216
    assert (mb.kda_layers(CFG), mb.dsa_layers(CFG), mb.expert_layers(CFG)) == (4, 1, 4)


def test_what_every_tick_reads():
    mixers = 4 * 137_723_904 + 124_387_328
    assert mb.always_read_params(CFG) == (
        mixers + 150_994_944 + 4 * 25_165_824 + 4096 * 19360) == 1_006_239_744
    assert mb.bf16_params(CFG) == 4 * 1_179_648 + 5 * 786_432
    # the int8 on the device: every layer whole (ISSUE 43: 4,550,819,840 B)
    held = 36 * 4 * 25_165_824
    assert mb.always_read_params(CFG) - 4096 * 19360 + held == 4_550_819_840


def test_state_and_cache_rows():
    assert mb.state_bytes_per_row(CFG) == 64 * 128 * 128 * 4 == 4_194_304
    assert mb.latent_row_bytes(CFG) == 1024 and mb.index_row_bytes(CFG) == 256
    assert mb.kv_bytes_per_token(CFG) == 1088 == RAW["kv_lora_rank"] * 2 + 128 * 2 / 4


def test_a_decode_ticks_bytes():
    # 12 live rows at 6,000 rows of context, 40 (layer, expert) pairs touched
    got = mb.decode_tick_bytes(CFG, 40, 12 * 4, 12 * 1500, 12 * 2050)
    want = (1_006_239_744 + 2 * 8_650_752 + 40 * 25_165_824
            + 48 * 8_388_608 + 18000 * 256 + 24600 * 1024)
    assert got == want
    assert 2.4e9 < got < 2.5e9  # ISSUE 43 sized a tick at 2.5 GB
    # nothing live: the weights every tick reads and the bf16 tables
    assert mb.decode_tick_bytes(CFG, 0, 0, 0, 0) == 1_006_239_744 + 17_301_504


def test_the_delta_rules_block_products():
    # 16 blocks of 16 rows, 64 heads: the inverse by doubling (2 x 3 products of
    # 16^3), K S and Q S and K^T U (16 x 128 x 128 each), T rhs and B U (16 x 16 x 128)
    per_block = 6 * 16 ** 3 + 3 * 16 * 128 * 128 + 2 * 16 * 16 * 128
    assert mb.delta_rule_flops(CFG, 256) == 2.0 * 64 * 16 * per_block
    assert mb.delta_rule_flops(CFG, 256, block=64) == 2.0 * 64 * 4 * (
        10 * 64 ** 3 + 3 * 64 * 128 * 128 + 2 * 64 * 64 * 128)


@pytest.mark.parametrize("context", [1000.0, 5000.0])
def test_a_chunks_flops(context):
    pairs = 1.0  # 8 x 36 / 288 landed pairs a token an expert layer
    touched = 1_006_239_744 + 8_650_752 + 4 * pairs * 25_165_824
    picked = min(context, 2052)
    scored = context / 4 if context > 2048 else 0.0
    want = (256 * 2.0 * touched + 4 * mb.delta_rule_flops(CFG, 256)
            + 256 * (4.0 * 64 * 512 * picked + 2.0 * 32 * 128 * scored))
    assert mb.chunk_flops(CFG, 256, context, pairs) == want
    assert 0.55e12 < want < 0.75e12  # ISSUE 43: 0.63 TFLOP of matrix products a chunk


def test_the_readers_find_nothing_where_the_program_has_no_such_counters():
    import chunk_mxu_share_kda_dsa
    import dsa_fetched_over_picked
    import window_hbm_share_kda_dsa

    parent = {"events": [{"name": "jit_program(1)"}], "serving_traced": {
        "capture_counters": {"start": {"moe_touched": 1}, "stop": {"moe_touched": 9}}},
        "serving_before": {"moe_tokens": 1}, "serving_after": {"moe_tokens": 2},
        "config": {"model": CFG, "node_env": {"llm": {}}}, "peaks": {}}
    args = {"match": "^jit_program\\(", "node": "llm", "ticks_env": "K", "ticks_default": 8}
    assert window_hbm_share_kda_dsa.read(parent, args) is None
    assert chunk_mxu_share_kda_dsa.read(parent, {**args, "chunk_env": "C",
                                                 "chunk_default": 256}) is None
    assert dsa_fetched_over_picked.read(parent, {}) is None
    assert dsa_fetched_over_picked.read({}, {}) is None
    gained = {"serving_before": {"dsa_rows_fetched": 100, "dsa_rows_picked": 50},
              "serving_after": {"dsa_rows_fetched": 2152, "dsa_rows_picked": 2100}}
    assert dsa_fetched_over_picked.read(gained, {}) == 2052 / 2050
