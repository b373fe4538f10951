"""The ``keye-vl2-30b-ep8`` configuration holds every published width
unchanged, names every cut, and its one cell reports what ISSUE 49 says.
The cell, the configuration and their metrics are found by name."""
import json

from conftest import BENCH, ROOT

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
RAW = json.loads((BENCH / "configs" / "keye-vl2-30b-ep8.json").read_text())
CONFIG, CELL = "keye-vl2-30b-ep8", "keye-vl2-30b-ep8.video-qa-16"
CUT = {"num_hidden_layers": 12, "num_experts": 16, "num_local_experts": 16, "ep_size": 8,
       "vocab_size": 18992, "max_position_embeddings": 16384}
SOURCE = "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json"


def test_every_published_width_is_unchanged():
    want = {
        "model_type": "KeyeVL2", "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 4, "head_dim": 128, "intermediate_size": 6144,
        "moe_intermediate_size": 768, "num_experts_per_tok": 8, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [], "max_window_layers": 48,
        "rms_norm_eps": 1e-06, "rope_theta": 10000000, "hidden_act": "silu",
        "attention_bias": False, "tie_word_embeddings": False, "sliding_window": None,
        "use_sliding_window": False,
        "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                         "type": "default"},
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
    }
    assert {k: RAW[k] for k in want} == want
    # every top-level key of the catalog row is there (26, ep_size beside
    # them), and no other beside bench
    assert set(want) | set(CUT) == set(RAW) - {"bench"} and len(RAW) == 27 + 1


def test_the_cuts_are_the_ones_named_and_no_other():
    bench = RAW["bench"]
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert entry["source"] == bench["source"] == SOURCE
    assert entry["file"] == "benchmark/configs/keye-vl2-30b-ep8.json"
    assert {k: RAW[k] for k in CUT} == CUT
    assert sorted(bench["reduced"]) == sorted(entry["reduced"]) == sorted(CUT)
    assert bench["published"] == {
        "num_hidden_layers": 48, "num_experts": 128, "num_local_experts": 128,
        "ep_size": 1, "vocab_size": 151936, "max_position_embeddings": 262144}
    # the floors: four layers of the period, 8 experts, an eighth of the vocabulary
    assert RAW["num_hidden_layers"] >= 4 and RAW["num_experts"] >= 8
    assert RAW["vocab_size"] * 8 == 151936
    # no width is among the cuts
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k not in ("vocab_size", "ep_size")
                   for k in bench["reduced"])
    # the six lines the config does not settle are written down as assumed
    assert {"index_projections", "index_key_norm_and_rotary", "index_head_weights_scale",
            "chunk_sizes_are_tiles", "no_forced_picks", "qk_norm_and_pre_norm",
            "mrope_on_ids", "tensor_names", "tokenizer", "weights", "no_tower"} <= set(
        bench["assumed"])
    assert "4 pipeline stages" in bench["deployment"] and "8 chips" in bench["deployment"]
    assert "26,112 B a token" in bench["bytes_on_the_device"]
    assert bench["node_env"]["llm"] == {
        "DORA_MAX_SEQ": "16384", "DORA_MAX_NEW_TOKENS": "2048", "DORA_EP_RANK": "0"}
    assert bench["graph"] == "openai_llm_keye_vl2"
    assert bench["tiny"]["model"]["sa_config"]["topk"] == 16
    assert bench["tiny"]["node_env"]["llm"]["DORA_PAGE_SIZE"] == "8"


def test_the_checkpoint_restores_hfs_meaning_of_the_expert_keys():
    import checkpoint_keye_vl2 as ck

    model = {k: v for k, v in RAW.items() if k != "bench"}
    hf = ck.hf_config(model)
    assert hf["num_experts"] == hf["num_local_experts"] == 128
    shapes = ck.layer_shapes(model, 3)
    p = "model.layers.3."
    assert shapes[p + "self_attn.q_proj.weight"] == (4096, 2048)
    assert shapes[p + "self_attn.k_proj.weight"] == (512, 2048)
    assert shapes[p + "self_attn.indexer.wq.weight"] == (1024, 2048)
    assert shapes[p + "self_attn.indexer.wk.weight"] == (64, 2048)
    assert shapes[p + "self_attn.indexer.weights_proj.weight"] == (16, 2048)
    assert shapes[p + "mlp.gate.weight"] == (128, 2048)
    assert not any("e_score_correction_bias" in n or "shared" in n for n in shapes)
    experts = {n.split(".")[5] for n in shapes if ".experts." in n}
    assert experts == {str(e) for e in range(16)}
    assert ck.layer_shapes(model, 1, rank=7)[
        "model.layers.1.mlp.experts.127.up_proj.weight"] == (768, 2048)

    def size(shape):
        n = 1
        for s in shape:
            n *= s
        return n

    params = sum(size(s) for s in shapes.values() if len(s) >= 2)
    assert params - 128 * 2048 == 96_632_832  # the int8 bytes of a layer


def test_the_draws_are_a_unit_row_out_and_the_two_query_projections_three():
    import numpy as np

    import checkpoint_keye_vl2 as ck

    rng = np.random.default_rng(7)

    def drawn(name, shape):
        return np.asarray(ck.draw(rng, shape, name)).astype(np.float32)

    wide = drawn("model.layers.0.self_attn.k_proj.weight", (64, 2048))
    assert abs(wide.std() * 2048 ** 0.5 - 1.0) < 0.05
    for name in ("self_attn.q_proj.weight", "self_attn.indexer.wq.weight"):
        peaky = drawn(f"model.layers.0.{name}", (64, 2048))
        assert abs(peaky.std() * 2048 ** 0.5 - 3.0) < 0.15
    down = drawn("model.layers.0.mlp.experts.3.down_proj.weight", (64, 768))
    assert abs(down.std() * 768 ** 0.5 - 1.0) < 0.05
    assert (drawn("model.norm.weight", (16,)) == 1).all()
    assert (drawn("model.layers.0.self_attn.indexer.k_norm.weight", (64,)) == 1).all()
    assert np.abs(drawn("model.layers.0.self_attn.indexer.k_norm.bias", (64,))).max() <= 0.0101


def test_the_cell_and_its_metrics():
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": CONFIG, "traffic": "video-qa-16", "chips": 1}
    assert len(cell["why"]) <= 200 and "no exchange" in cell["why"]
    e2e = {m["name"] for m in MANIFEST["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert e2e == {"tokens_per_s", "ttft_p95_ms", "tpot_p50_ms", "tpot_p95_ms", "setup_s"}
    mine = {m["name"]: m for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]}
    assert set(mine) == {"decode_window_hbm_pct.gqa-dsa", "prefill_chunk_mxu_pct.gqa-dsa"}
    assert {m["layer"] for m in mine.values()} == {"picked pages"}
    assert mine["decode_window_hbm_pct.gqa-dsa"]["moves"] == "tpot_p50_ms"
    assert mine["prefill_chunk_mxu_pct.gqa-dsa"]["moves"] == "ttft_p95_ms"
    reported = {m["name"] for m in MANIFEST["per_layer"] if CELL in m["workloads"]}
    other = next(w["name"] for w in MANIFEST["workloads"]
                 if w["name"] == "glm-5p3-flash-ep8.long-ctx-16")
    shared = {m["name"] for m in MANIFEST["per_layer"]
              if other in m["workloads"] and len(m["workloads"]) > 1}
    # every shared metric the other long-context cell lists, and its reader of the
    # selection, but the admission's wait: a traced run of this cell admits nothing
    # behind its capture, so that reader finds nothing to read there (PERF.md section 7)
    shared -= {"backlog_wait_ms.serve"}
    assert shared | {"dsa_rows_fetched_over_picked"} <= reported
    assert len(reported) == 2 + len(shared)
    # appended: wherever the cell is named it comes after the cells that were there
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"] == [CELL] or (
                m["workloads"].index(CELL) > m["workloads"].index(other))
    # every reader named by a layer metric of the cell is a file beside the others
    for m in mine.values():
        spec = json.loads((BENCH / "layer_metrics" / f"{m['name']}.json").read_text())
        assert (BENCH / "readers" / f"{spec['reader']}.py").is_file()
        assert spec["layer"] == m["layer"] and spec["unit"] == m["unit"]
        assert spec["moves"] == m["moves"]
    # the cell's files are found by the names the manifest and the two files give
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    assert (BENCH / "generators" / f"{traffic['generator']}.py").is_file()
    assert (BENCH / "graphs" / f"{RAW['bench']['graph']}.py").is_file()
    for lib in ("reference_keye_vl2", "chat_measure_keye_vl2", "cache_audit_keye_vl2",
                "checkpoint_keye_vl2", "model_bytes_gqa_dsa"):
        assert (BENCH / "lib" / f"{lib}.py").is_file()
