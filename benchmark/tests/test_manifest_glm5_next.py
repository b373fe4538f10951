"""The ``glm-5p3-flash-ep8`` configuration holds every published width
unchanged, names every cut, and its one cell reports what ISSUE 43 says."""
import json

from conftest import BENCH, ROOT

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
RAW = json.loads((BENCH / "configs" / "glm-5p3-flash-ep8.json").read_text())
CELL = "glm-5p3-flash-ep8.long-ctx-16"
CUT = {"num_hidden_layers": 5, "first_k_dense_replace": 1, "n_routed_experts": 36,
       "ep_size": 8, "vocab_size": 19360, "max_position_embeddings": 16384,
       "num_nextn_predict_layers": 0}
GROUPS = ["layer_types", "mlp_layer_types", "indexer_types", "linear_attn_config"]


def test_every_published_width_is_unchanged():
    want = {
        "model_type": "glm5_next_text", "hidden_size": 4096, "num_attention_heads": 64,
        "num_key_value_heads": 64, "head_dim": 0, "intermediate_size": 12288,
        "moe_intermediate_size": 2048, "num_experts_per_tok": 8, "n_shared_experts": 1,
        "routed_scaling_factor": 2.5, "norm_topk_prob": True, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1, "swiglu_limit": 10,
        "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_head_dim": 256,
        "qk_nope_head_dim": 256, "qk_rope_head_dim": 0, "v_head_dim": 256,
        "mla_use_nope": True, "index_n_heads": 32, "index_head_dim": 128,
        "index_topk": 2048, "index_kpool": 4, "index_kpool_compress": True,
        "index_kpool_always_select_tail": True, "indexer_rope_interleave": True,
        "index_share_for_mtp_iteration": True, "hc_mult": 4, "hc_sinkhorn_iters": 20,
        "hc_eps": 1e-06, "mhc": True, "rms_norm_eps": 1e-05, "hidden_act": "silu",
        "attention_bias": False, "tie_word_embeddings": False,
    }
    assert {k: RAW[k] for k in want} == want
    lin = RAW["linear_attn_config"]
    assert {k: lin[k] for k in ("num_heads", "head_dim", "short_conv_kernel_size",
                                "gate_lower_bound")} == {
        "num_heads": 64, "head_dim": 128, "short_conv_kernel_size": 4,
        "gate_lower_bound": -5}
    # every top-level key of the catalog row is there, and no other beside bench
    assert len(RAW) == 50 + 1 and set(want) | set(CUT) | set(GROUPS) == set(RAW) - {"bench"}


def test_the_cuts_are_the_ones_named_and_no_other():
    bench = RAW["bench"]
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "glm-5p3-flash-ep8")
    names = [c["name"] for c in MANIFEST["configs"]]
    assert names.index("glm-5p3-flash-ep8") == names.index("k-exaone-236b-ep8") + 1
    assert entry["source"] == bench["source"] == (
        "https://huggingface.co/zai-org/GLM-5.3-Flash/blob/main/config.json")
    assert {k: RAW[k] for k in CUT} == CUT
    assert RAW["layer_types"] == ["linear_attention"] * 4 + ["deepseek_sparse_attention"]
    assert RAW["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert RAW["indexer_types"] == ["full"] * 5
    assert RAW["linear_attn_config"]["kda_layers"] == [0, 1, 2, 3]
    assert RAW["linear_attn_config"]["full_attn_layers"] == [4]
    assert sorted(bench["reduced"]) == sorted(entry["reduced"]) == sorted([*CUT, *GROUPS])
    assert bench["published"]["num_hidden_layers"] == 45
    assert bench["published"]["n_routed_experts"] == 288
    assert bench["published"]["vocab_size"] == 154880
    # no width is among the cuts
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k not in ("vocab_size", "ep_size")
                   for k in bench["reduced"])
    # the seven lines the config does not settle are written down as assumed
    assert {"hc_eps_in_sinkhorn", "streams_in_and_out", "kda_gate", "kda_low_rank",
            "index_pooling", "index_topk_counts_positions", "swiglu_limit_everywhere",
            "e_score_correction_bias", "tensor_names", "tokenizer", "weights"} <= set(
        bench["assumed"])
    assert bench["node_env"]["llm"] == {
        "DORA_MAX_SEQ": "16384", "DORA_MAX_NEW_TOKENS": "2048", "DORA_EP_RANK": "0"}
    tiny = bench["tiny"]["model"]
    assert (tiny["index_topk"], tiny["index_kpool"]) == (16, 4)
    assert bench["tiny"]["node_env"]["llm"]["DORA_PAGE_SIZE"] == "8"


def test_the_checkpoint_restores_hfs_meaning_of_the_expert_key():
    import checkpoint_glm5_next as ck

    model = {k: v for k, v in RAW.items() if k != "bench"}
    assert ck.hf_config(model)["n_routed_experts"] == 288
    dense, sparse, latent = (ck.layer_shapes(model, i) for i in (0, 1, 4))
    assert dense["model.layers.0.mlp.gate_proj.weight"] == (12288, 4096)
    assert dense["model.layers.0.self_attn.q_conv1d.weight"] == (8192, 1, 4)
    assert dense["model.layers.0.hc_attn_fn"] == (24, 16384)
    assert sparse["model.layers.1.mlp.gate.weight"] == (288, 4096)
    assert latent["model.layers.4.self_attn.kv_b_proj.weight"] == (64 * 512, 512)
    assert latent["model.layers.4.self_attn.indexer.wq_b.weight"] == (32 * 128, 1536)
    assert "model.layers.4.self_attn.q_proj.weight" not in latent
    experts = {n.split(".")[5] for n in sparse if ".experts." in n}
    assert experts == {str(e) for e in range(36)}
    assert ck.layer_shapes(model, 1, rank=7)["model.layers.1.mlp.experts.287.up_proj.weight"]

    def size(shape):
        n = 1
        for s in shape:
            n *= s
        return n

    params = sum(size(s) for i in range(5) for s in ck.layer_shapes(model, i).values()
                 if len(s) >= 2)
    routers, maps = 4 * 288 * 4096, 5 * 2 * 24 * 16384
    assert params - routers - maps == 4_550_819_840  # the int8 bytes of the layers


def test_the_draws_leave_gates_decays_and_sinkhorn_unsaturated():
    import numpy as np

    import checkpoint_glm5_next as ck

    rng = np.random.default_rng(7)

    def drawn(name, shape):
        return np.asarray(ck.draw(rng, shape, name, 4)).astype(np.float32)

    dt = drawn("model.layers.0.self_attn.dt_bias", (8192,))
    assert -6.0 <= dt.min() and dt.max() <= -1.0
    g = -5.0 / (1.0 + np.exp(-dt))  # at exp(A_log) = 1 and r = 0
    assert g.min() > -1.4 and g.max() < -0.01
    base = drawn("model.layers.0.hc_attn_base", (24,))
    res = base[8:].reshape(4, 4)
    assert (np.diag(res) >= 1.5).all() and np.abs(res - np.diag(np.diag(res))).max() <= 0.5
    assert np.abs(base[:8]).max() <= 0.5
    scale = drawn("model.layers.0.hc_ffn_scale", (3,))
    assert 0.5 <= scale.min() and scale.max() <= 1.5
    wide = drawn("model.layers.0.self_attn.q_proj.weight", (64, 4096))
    assert abs(wide.std() * 4096 ** 0.5 - 1.0) < 0.05
    peaky = drawn("model.layers.4.self_attn.q_b_proj.weight", (64, 1536))
    assert abs(peaky.std() * 1536 ** 0.5 - 3.0) < 0.15
    taps = {x: drawn(f"model.layers.0.self_attn.{x}_conv1d.weight", (512, 1, 4)) for x in "qkv"}
    assert np.abs(taps["q"]).max() <= 0.0501 and np.abs(taps["k"]).max() <= 0.0501
    assert 0.4 < np.abs(taps["v"]).max() <= 0.501
    assert (drawn("model.norm.weight", (16,)) == 1).all()
    assert np.abs(drawn("model.layers.1.mlp.gate.e_score_correction_bias", (288,))).max() <= 0.0101


def test_the_cell_and_its_metrics():
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": "glm-5p3-flash-ep8", "traffic": "long-ctx-16",
                    "chips": 1}
    assert len(MANIFEST["workloads"]) == 8 and not any(
        w["chips"] == 4 for w in MANIFEST["workloads"])
    e2e = {m["name"] for m in MANIFEST["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert e2e == {"tokens_per_s", "ttft_p95_ms", "tpot_p50_ms", "tpot_p95_ms", "setup_s"}
    mine = [m for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "decode_window_hbm_pct.kda-dsa", "prefill_chunk_mxu_pct.kda-dsa",
        "dsa_rows_fetched_over_picked"]
    assert mine == MANIFEST["per_layer"][-3:]
    assert {m["layer"] for m in mine} == {"state and selection"}
    assert [m["moves"] for m in mine] == ["tpot_p50_ms", "ttft_p95_ms", "tpot_p50_ms"]
    reported = {m["name"] for m in MANIFEST["per_layer"] if CELL in m["workloads"]}
    assert {"moe_expert_load_max_over_mean", "backlog_wait_ms.serve", "decode_window_dev_ms",
            "prefill_chunk_dev_ms", "compiles_in_window.serve", "device_idle_pct.serve",
            "idle_attributed_pct.serve", "dispatch_gap_ms.serve", "emit_ms.serve",
            "gap_unattributed_ms.serve", "gap_first_token_wait_ms.serve"} <= reported
    assert len(reported) == 3 + 9 + 8
    # appended: wherever the cell is named it comes after the cells that were there
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL
    # every reader named by a layer metric of the cell is a file beside the others
    for m in mine:
        spec = json.loads((BENCH / "layer_metrics" / f"{m['name']}.json").read_text())
        assert (BENCH / "readers" / f"{spec['reader']}.py").is_file()
        assert spec["layer"] == m["layer"] and spec["unit"] == m["unit"]
