"""``model_bytes_gqa_dsa``: the bytes a decode tick must move and the
operations a chunk needs at the cell's cut, by hand."""
import json

from conftest import BENCH

import model_bytes_gqa_dsa as mb

RAW = json.loads((BENCH / "configs" / "keye-vl2-30b-ep8.json").read_text())
CFG = {k: v for k, v in RAW.items() if k != "bench"}


def test_the_parameters_of_a_layer_by_hand():
    assert mb.attention_params(CFG) == 2048 * (4096 + 512 + 512) + 4096 * 2048 == 18_874_368
    assert mb.indexer_params(CFG) == 2048 * (16 * 64 + 64 + 16) == 2_260_992
    assert mb.expert_params(CFG) == 3 * 2048 * 768 == 4_718_592
    assert mb.router_params(CFG) == 2048 * 128  # the published width: 16 held x 8 ranks
    assert mb.always_read_params(CFG) == 12 * 21_135_360 + 2048 * 18992 == 292_519_936
    assert mb.bf16_params(CFG) == 12 * 262_144
    # what the configuration file says a layer holds
    assert (mb.attention_params(CFG) + mb.indexer_params(CFG)
            + 16 * mb.expert_params(CFG)) == 96_632_832


def test_a_cached_token_is_26112_bytes():
    assert mb.kv_row_bytes(CFG) == 2048 and mb.index_row_bytes(CFG) == 128
    assert mb.kv_bytes_per_token(CFG) == 26_112
    assert mb.kv_bytes_per_token(CFG, 4.0) == 52_224  # float32 on the CPU


def test_a_decode_tick_by_hand():
    # 6 live rows at 9,000 rows of context, all selecting, 5 experts touched a layer
    scored, picked, touched = 12 * 6 * 9000, 12 * 6 * 2048, 12 * 5
    got = mb.decode_tick_bytes(CFG, touched, scored, picked)
    assert got == (292_519_936 + 2 * 3_145_728 + 60 * 4_718_592
                   + 648_000 * 128 + 147_456 * 2048)
    # the selection is more than a third of such a tick's bytes
    selection = 648_000 * 128 + 147_456 * 2048
    assert 0.33 < selection / got < 0.5
    # no live row: the weights and routers alone
    assert mb.decode_tick_bytes(CFG, 0, 0, 0) == 292_519_936 + 6_291_456


def test_a_chunk_by_hand():
    matrices = 292_519_936 + 3_145_728 + 12 * 1.0 * 4_718_592
    below = mb.chunk_flops(CFG, 256, 1000.0, 1.0)
    assert below == 256 * (2 * matrices + 12 * 4 * 32 * 128 * 1000.0)
    above = mb.chunk_flops(CFG, 256, 9000.0, 1.0)
    assert above == 256 * (2 * matrices + 12 * (4 * 32 * 128 * 2048 + 2 * 16 * 64 * 9000.0))
    # past topk the picked rows' products stop growing; the index scores go on
    assert (mb.chunk_flops(CFG, 256, 12000.0, 1.0) - above
            == 256 * 12 * 2 * 16 * 64 * 3000.0)


def test_the_readers_return_nothing_where_the_program_has_no_counters():
    import importlib.util
    import sys

    sys.modules.setdefault("trace_reduce", type(sys)("trace_reduce"))
    for name in ("window_hbm_share_gqa_dsa", "chunk_mxu_share_gqa_dsa"):
        spec = importlib.util.spec_from_file_location(name, BENCH / "readers" / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.read({}, {}) is None
        assert mod.read({"events": [1], "serving_after": {"capture_counters": {
            "start": {"moe_tokens": 1}, "stop": {"moe_tokens": 2}}}}, {}) is None


def test_per_reads_the_captures_edges():
    start = {"dsa_decode_ticks": 10, "dsa_rows_picked": 1000, "moe_touched": 50}
    stop = {"dsa_decode_ticks": 18, "dsa_rows_picked": 99_304, "moe_touched": 450}
    assert mb.per(start, stop, "dsa_rows_picked", "dsa_decode_ticks") == 98_304 / 8
    assert mb.per(start, stop, "moe_touched", "dsa_decode_ticks") == 50
    assert mb.per(start, stop, "dsa_index_rows_scored", "dsa_decode_ticks") is None
