"""The ``k-exaone-236b-ep8`` configuration holds every published width
unchanged, names every cut, and its one cell reports what ISSUE 41 says."""
import json

from conftest import BENCH, ROOT

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
RAW = json.loads((BENCH / "configs" / "k-exaone-236b-ep8.json").read_text())
CELL = "k-exaone-236b-ep8.mixed-len-16"


def test_every_published_width_is_unchanged():
    want = {
        "model_type": "exaone_moe", "hidden_size": 6144, "num_attention_heads": 64,
        "num_key_value_heads": 8, "head_dim": 128, "intermediate_size": 18432,
        "moe_intermediate_size": 2048, "num_experts_per_tok": 8, "num_shared_experts": 1,
        "routed_scaling_factor": 2.5, "norm_topk_prob": True, "scoring_func": "sigmoid",
        "n_group": 1, "topk_group": 1, "sliding_window": 128, "sliding_window_pattern": "LLLG",
        "rms_norm_eps": 1e-05, "first_k_dense_replace": 1, "hidden_act": "silu",
        "tie_word_embeddings": False,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "mtp_layer_types": ["full_attention"], "mtp_sliding_windows": [0],
    }
    assert {k: RAW[k] for k in want} == want


def test_the_cuts_are_the_ones_named_and_no_other():
    bench = RAW["bench"]
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "k-exaone-236b-ep8")
    assert entry is MANIFEST["configs"][-1]  # appended, nothing before it moved
    cut = {"num_hidden_layers": 8, "num_experts": 16, "ep_size": 8, "vocab_size": 19200,
           "max_position_embeddings": 16384, "num_nextn_predict_layers": 0}
    assert {k: RAW[k] for k in cut} == cut
    period = ["sliding_attention"] * 3 + ["full_attention"]
    assert RAW["layer_types"] == period * 2
    assert RAW["mlp_layer_types"] == ["dense"] + ["sparse"] * 7
    assert RAW["sliding_windows"] == [128, 128, 128, 0] * 2
    assert sorted(bench["reduced"]) == sorted(entry["reduced"]) == sorted(
        [*cut, "layer_types", "mlp_layer_types", "sliding_windows"])
    assert bench["published"]["num_hidden_layers"] == 48
    assert bench["published"]["num_experts"] == 128 and bench["published"]["vocab_size"] == 153600
    # no width is among the cuts
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k not in ("vocab_size", "ep_size")
                   for k in bench["reduced"])
    # the four lines the config does not settle are written down as assumed
    assert {"norm_placement", "qkv_bias", "qk_norm", "rope_on_window_layers_only",
            "e_score_correction_bias", "tensor_names", "tokenizer", "weights"} <= set(
        bench["assumed"])
    assert bench["node_env"]["llm"] == {
        "DORA_MAX_SEQ": "16384", "DORA_MAX_NEW_TOKENS": "512", "DORA_EP_RANK": "0"}


def test_the_checkpoint_restores_hfs_meaning_of_the_expert_key():
    import checkpoint_k_exaone as ck

    model = {k: v for k, v in RAW.items() if k != "bench"}
    assert ck.hf_config(model)["num_experts"] == 128
    dense, sparse = ck.layer_shapes(model, 0), ck.layer_shapes(model, 1)
    assert dense["model.layers.0.mlp.gate_proj.weight"] == (18432, 6144)
    assert sparse["model.layers.1.mlp.gate.weight"] == (128, 6144)
    experts = {n.split(".")[5] for n in sparse if ".experts." in n}
    assert experts == {str(e) for e in range(16)}
    assert ck.layer_shapes(model, 1, rank=7)["model.layers.1.mlp.experts.127.up_proj.weight"]
    params = sum(a * b for shapes in (ck.layer_shapes(model, i) for i in range(8))
                 for a, b in (s for s in shapes.values() if len(s) == 2))
    routers = 7 * 128 * 6144
    assert params - routers == 5_737_807_872  # the int8 bytes of the layers


def test_the_cell_and_its_metrics():
    cell = MANIFEST["workloads"][-1]
    assert cell == {**cell, "name": CELL, "config": "k-exaone-236b-ep8",
                    "traffic": "mixed-len-16", "chips": 1}
    e2e = {m["name"] for m in MANIFEST["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert e2e == {"tokens_per_s", "ttft_p95_ms", "tpot_p50_ms", "tpot_p95_ms", "setup_s"}
    mine = [m for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "decode_window_hbm_pct.swa-moe", "prefill_chunk_mxu_pct.swa-moe",
        "global_kv_swept_over_read"]
    assert mine == MANIFEST["per_layer"][-3:]
    assert {m["layer"] for m in mine} == {"window ring"}
    reported = {m["name"] for m in MANIFEST["per_layer"] if CELL in m["workloads"]}
    assert {"moe_expert_load_max_over_mean", "backlog_wait_ms.serve", "decode_window_dev_ms",
            "prefill_chunk_dev_ms", "compiles_in_window.serve", "device_idle_pct.serve",
            "idle_attributed_pct.serve", "dispatch_gap_ms.serve", "emit_ms.serve",
            "gap_unattributed_ms.serve", "gap_first_token_wait_ms.serve"} <= reported
    assert len(reported) == 3 + 9 + 8
    # appended to each list: the cell comes last wherever it is named
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL
