"""``model_bytes_gdn_hybrid``: the bytes a decode tick must move and the
operations a chunk needs at the cell's cut (the published widths), by
hand; and that its readers say nothing where the program has no such
counters (the parent commit)."""
import json

from conftest import BENCH

import model_bytes_gdn_hybrid as mb

RAW = json.loads((BENCH / "configs" / "olmo-hybrid-7b-pp2.json").read_text())
CFG = {k: v for k, v in RAW.items() if k != "bench"}


def test_the_parameters_of_a_layer_by_hand():
    assert mb.linear_layers(CFG) == 12 and mb.full_layers(CFG) == 4 and mb.head_dim(CFG) == 128
    assert mb.linear_params(CFG) == (
        2 * 3840 * 2880 + 3 * 3840 * 5760 + 2 * 3840 * 30 + 4 * 11520) == 88_750_080
    assert mb.full_params(CFG) == 4 * 3840 * 3840 == 58_982_400
    assert mb.mlp_params(CFG) == 3 * 3840 * 11008 == 126_812_160
    period = 3 * (88_750_080 + 126_812_160) + 58_982_400 + 126_812_160
    assert period == 832_481_280
    assert mb.always_read_params(CFG) == 4 * period + 3840 * 100_352 == 3_715_276_800
    # what the configuration file says
    text = RAW["bench"]["bytes_on_the_device"]
    assert "88,704,000" in text and "126,812,160" in text and "58,982,400" in text
    assert "385,351,680" in text


def test_a_state_a_cached_token_and_a_snapshot_in_bytes():
    assert mb.state_bytes_per_row(CFG) == 30 * 96 * 192 * 4 == 2_211_840
    assert mb.state_step_bytes(CFG) == 4_423_680
    assert mb.kv_row_bytes(CFG) == 2 * 30 * 128 * 2 == 15_360
    assert mb.kv_bytes_per_token(CFG) == 61_440
    assert mb.kv_bytes_per_token(CFG, 4.0) == 122_880  # float32 on the CPU
    assert mb.snapshot_bytes(CFG) == 12 * (2_211_840 + 3 * 11_520 * 2) == 27_371_520
    text = RAW["bench"]["bytes_on_the_device"]
    assert "61,440 B a token" in text and "27,371,520" in text and "2,211,840" in text
    assert 27_371_520 // 61_440 == 445  # "the price of 445 cached tokens"


def test_a_decode_tick_by_hand():
    # 15 live rows at 4,000 rows of context
    row_ticks, rows = 12 * 15, 4 * 15 * 4000
    got = mb.decode_tick_bytes(CFG, row_ticks, rows)
    assert got == 3_715_276_800 + 180 * 4_423_680 + 240_000 * 15_360
    # ISSUE 56's rough size: the weights about half, the K/V next, the states a tenth
    assert 8.0e9 < got < 8.4e9
    assert 0.42 < 3_715_276_800 / got < 0.48 and 0.40 < 240_000 * 15_360 / got < 0.48
    assert 0.08 < 180 * 4_423_680 / got < 0.12


def test_a_chunks_operations_by_hand():
    block = 2 * 64 * 64 * 96 + 64 ** 3 / 3.0 + 3 * 64 * 96 * 192 + 2 * 64 * 64 * 192
    assert mb.delta_rule_flops(CFG, 256) == 2.0 * 30 * 4 * block
    got = mb.chunk_flops(CFG, 256, 3000.0)
    weights = 256 * 2.0 * 3_715_276_800
    attend = 4 * 256 * 4.0 * 30 * 128 * 3000.0
    assert got == weights + 12 * mb.delta_rule_flops(CFG, 256) + attend
    # the matrices carry a chunk: the delta rule's blocks are under 1 % of it
    assert 0.95 < weights / got < 0.98
    assert 12 * mb.delta_rule_flops(CFG, 256) / got < 0.01


def _run(edges=None, before=None, after=None):
    return {"events": [{"name": "x"}], "serving_traced": {"capture_counters": edges or {}},
            "serving_before": before, "serving_after": after, "peaks": {
                "hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
            "config": {"model": CFG, "node_env": {"llm": {}}}}


def test_the_readers_say_nothing_where_the_program_has_no_such_counters():
    import chunk_mxu_share_gdn_hybrid
    import gdn_kernel_hbm_share
    import prefix_hit_tokens_pct
    import window_hbm_share_gdn_hybrid

    specs = {
        name: json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
        for name in ("decode_window_hbm_pct.gdn-hybrid", "prefill_chunk_mxu_pct.gdn-hybrid",
                     "gdn_state_step_hbm_pct", "prefix_hit_tokens_pct.serve")}
    readers = {
        "decode_window_hbm_pct.gdn-hybrid": window_hbm_share_gdn_hybrid,
        "prefill_chunk_mxu_pct.gdn-hybrid": chunk_mxu_share_gdn_hybrid,
        "gdn_state_step_hbm_pct": gdn_kernel_hbm_share,
        "prefix_hit_tokens_pct.serve": prefix_hit_tokens_pct}
    for name, reader in readers.items():
        assert specs[name]["reader"] == reader.__name__
        # no capture edges at all, and edges of another model's counters
        assert reader.read(_run(), specs[name]["args"]) is None
        other = {"start": {"kda_decode_ticks": 1}, "stop": {"kda_decode_ticks": 9}}
        assert reader.read(_run(other, {"prefix_hits": 1}, {"prefix_hits": 2}),
                           specs[name]["args"]) is None


def test_the_share_of_prompt_tokens_granted_by_hand():
    import prefix_hit_tokens_pct

    args = {"prefilled": "gdn_chunk_rows"}
    before = {"prefix_hit_tokens": 1000, "gdn_chunk_rows": 50_000}
    after = {"prefix_hit_tokens": 301_000, "gdn_chunk_rows": 150_000}
    assert prefix_hit_tokens_pct.read(_run(None, before, after), args) == 75.0
    assert prefix_hit_tokens_pct.read(_run(None, before, before), args) is None
