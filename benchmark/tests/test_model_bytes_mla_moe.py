"""The MLA + expert-layer bytes and FLOPs against values computed by
hand from Kimi-K2.5's published widths (ISSUE 27's table)."""
import json

import model_bytes_mla_moe as mb
from conftest import BENCH

CFG = json.loads((BENCH / "configs" / "kimi-k2p5-ep32.json").read_text())


def test_parameters_of_the_parts():
    # q_a 7168x1536, kv_a 7168x576, q_b 1536x12288, kv_b 512x16384, o 8192x7168
    assert mb.attention_params(CFG) == (
        11_010_048 + 4_128_768 + 18_874_368 + 8_388_608 + 58_720_256)
    assert mb.attention_params(CFG) == 101_122_048
    assert mb.expert_params(CFG) == 3 * 7168 * 2048 == 44_040_192
    assert mb.router_params(CFG) == 7168 * 384  # the router keeps 384
    assert mb.expert_layers(CFG) == 7
    assert mb.local_pairs_per_token(CFG) == 0.25


def test_decode_tick_bytes():
    always = 8 * 101_122_048 + 3 * 7168 * 18432 + 7 * 44_040_192 + 7168 * 20480
    assert mb.always_read_params(CFG) == always == 1_660_420_096
    assert mb.decode_tick_bytes(CFG, 0.0) == always + 2 * 7 * 7168 * 384
    one_more = mb.decode_tick_bytes(CFG, 1.0) - mb.decode_tick_bytes(CFG, 0.0)
    assert one_more == 7 * 44_040_192  # one expert touched in every expert layer
    assert abs(mb.decode_tick_bytes(CFG, 3.5) - 2.778e9) < 1e6


def test_chunk_flops():
    per_token = 2 * (1_660_420_096 + 7 * (7168 * 384 + 0.25 * 44_040_192))
    assert mb.matmul_flops_per_token(CFG) == per_token
    assert abs(256 * per_token - 0.8995e12) < 1e9  # 4.57 ms at 197 TFLOP/s


def test_attention_forms_at_2k_and_8k():
    # absorbed: 2 x 256 x 64 x T x (512 + 64 + 512); expanded: kv_b over T
    # latents, then 2 x 256 x 64 x T x (192 + 128)
    for t, absorbed, expanded in ((2048, 73.01e9, 55.83e9), (8192, 292.06e9, 223.34e9)):
        assert mb.attention_flops_absorbed(CFG, 256, t) == 2 * 256 * 64 * t * 1088
        assert mb.attention_flops_expanded(CFG, 256, t) == (
            2 * t * 512 * 64 * 256 + 2 * 256 * 64 * t * 320)
        assert abs(mb.attention_flops_absorbed(CFG, 256, t) - absorbed) < 1e8
        assert abs(mb.attention_flops_expanded(CFG, 256, t) - expanded) < 1e8
    # one decode row: absorbed reads the latents once; expanded would
    # re-expand the whole context every tick
    assert mb.attention_flops_absorbed(CFG, 1, 8192) < mb.attention_flops_expanded(CFG, 1, 8192) / 100


def test_readers_return_nothing_without_the_programs_counters():
    import chunk_mxu_share_mla_moe
    import expert_load_skew
    import window_hbm_share_mla_moe

    run = {"serving_after": {"decode_tokens": 5}, "events": [["x"]],
           "config": {"model": {"model_type": "qwen2"}, "node_env": {"llm": {}}}}
    args = {"match": "^jit_program\\(", "node": "llm", "ticks_env": "K", "ticks_default": 8}
    assert window_hbm_share_mla_moe.read(run, args) is None
    assert expert_load_skew.read(run, {}) is None
    assert chunk_mxu_share_mla_moe.read(run, {"match": "x", "node": "llm"}) is None
    run["serving_after"]["moe_expert_tokens"] = [4, 2, 0, 2]
    assert expert_load_skew.read(run, {}) == 2.0
