"""The ``zaya1-8b-pp2`` configuration holds every key of the catalog row
at its published width, names every cut, and its one cell reports what
ISSUE 52 says. The cell, the configuration and their metrics are found by
name."""
import json
from pathlib import Path

import pytest
from conftest import BENCH, ROOT

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
RAW = json.loads((BENCH / "configs" / "zaya1-8b-pp2.json").read_text())
CONFIG, CELL = "zaya1-8b-pp2", "zaya1-8b-pp2.reason-16"
CUT = {"num_hidden_layers": 20, "layer_types": ["hybrid"] * 20, "vocab_size": 131136,
       "max_position_embeddings": 8192}
SOURCE = "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def test_every_published_width_is_unchanged():
    want = {
        "model_type": "zaya", "hidden_size": 2048, "num_attention_heads": 8,
        "num_key_value_heads": 2, "head_dim": 128, "moe_intermediate_size": 2048,
        "num_experts": 16, "num_experts_per_tok": 1, "router_hidden_size": 256,
        "cca_time0": 2, "cca_time1": 2, "partial_rotary_factor": 0.5,
        "rms_norm_eps": 1e-05, "hidden_act": "silu", "attention_bias": False,
        "lm_head_bias": False, "tie_word_embeddings": True, "sliding_window": None,
        "rope_parameters": {
            "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                       "rope_type": "default"},
            "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                               "rope_type": "default"},
            "rope_type": "default"},
    }
    assert {k: RAW[k] for k in want} == want
    # every top-level key of the catalog row is there, and no other beside bench
    assert set(want) | set(CUT) == set(RAW) - {"bench"} and len(RAW) == 23 + 1


def test_the_file_is_the_catalog_row_but_for_the_cuts():
    if not CATALOG.is_file():
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "ZAYA1-8B")
    assert row["source_url"] == SOURCE
    differ = {k for k, v in row["config"].items() if RAW.get(k) != v}
    assert differ == set(CUT) and set(row["config"]) == set(RAW) - {"bench"}
    assert RAW["bench"]["published"] == {
        "num_hidden_layers": row["config"]["num_hidden_layers"],
        "layer_types": "'hybrid' x 40", "vocab_size": row["config"]["vocab_size"],
        "max_position_embeddings": row["config"]["max_position_embeddings"]}
    assert row["config"]["layer_types"] == ["hybrid"] * 40


def test_the_cuts_are_the_ones_named_and_no_other():
    bench = RAW["bench"]
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert entry["source"] == bench["source"] == SOURCE
    assert entry["file"] == "benchmark/configs/zaya1-8b-pp2.json"
    assert {k: RAW[k] for k in CUT} == CUT
    assert sorted(bench["reduced"]) == sorted(entry["reduced"]) == sorted(CUT)
    assert sorted(bench["published"]) == sorted(CUT)
    # the floors: four layers of the period, 8 experts, an eighth of the vocabulary
    assert RAW["num_hidden_layers"] >= 4 and RAW["num_experts"] >= 8
    assert RAW["vocab_size"] * 2 == 262272
    # no width is among the cuts
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in bench["reduced"])
    # the eight lines the config does not settle are written down as assumed
    daggers = {k: v for k, v in bench["assumed"].items() if v.startswith("†")}
    assert sorted(v[1] for v in daggers.values()) == list("12345678")
    assert {"tensor_names", "tokenizer", "weights", "quantization", "l2_norms"} <= set(
        bench["assumed"])
    assert "2 pipeline stages of 20 whole layers" in bench["deployment"]
    assert "every expert" in bench["deployment"] and "2048 + 256" in bench["deployment"]
    assert "20,480 B a token" in bench["bytes_on_the_device"]
    assert bench["node_env"]["llm"] == {"DORA_MAX_SEQ": "8192", "DORA_MAX_NEW_TOKENS": "3072"}
    assert bench["graph"] == "openai_llm_zaya"
    assert bench["tiny"]["model"]["router_hidden_size"] == 32
    assert bench["tiny"]["node_env"]["llm"]["DORA_PAGE_SIZE"] == "8"


def test_the_checkpoint_holds_every_expert_and_no_head():
    import checkpoint_zaya as ck

    model = {k: v for k, v in RAW.items() if k != "bench"}
    assert ck.hf_config(model) == model
    shapes = ck.layer_shapes(model, 3)
    p = "model.layers.3."
    assert shapes[p + "self_attn.q_proj.weight"] == (1024, 2048)
    assert shapes[p + "self_attn.k_proj.weight"] == (256, 2048)
    assert shapes[p + "self_attn.v_proj1.weight"] == shapes[
        p + "self_attn.v_proj2.weight"] == (128, 2048)
    assert shapes[p + "self_attn.conv_qk.0.weight"] == (1280, 1, 2)
    assert shapes[p + "self_attn.conv_qk.1.weight"] == (1280, 128, 2)
    assert shapes[p + "self_attn.temp"] == (2,)
    assert shapes[p + "mlp.router.down_proj.weight"] == (256, 2048)
    assert shapes[p + "mlp.router.mlp.2.weight"] == (16, 256)
    assert shapes[p + "mlp_residual.hidden_scale"] == (2048,)
    experts = {n.split(".")[5] for n in shapes if ".experts." in n}
    assert experts == {str(e) for e in range(16)}

    def size(shape):
        n = 1
        for s in shape:
            n *= s
        return n

    matrices = sum(size(s) for n, s in shapes.items()
                   if "_proj" in n and "router" not in n and len(s) == 2)
    assert matrices == 206_569_472  # the int8 bytes of a layer
    import model_bytes_cca_moe as mb

    router = sum(size(s) for n, s in shapes.items() if ".router." in n)
    assert router - 256 - 256 - 16 == mb.router_params(model)  # but gamma, norm, bias
    assert sum(size(s) for n, s in shapes.items() if ".conv_qk." in n) == mb.conv_params(model)


def test_the_draws_are_a_unit_row_out_and_the_vectors_off_their_neutral_values():
    import numpy as np

    import checkpoint_zaya as ck

    rng = np.random.default_rng(7)

    def drawn(name, shape):
        return np.asarray(ck.draw(rng, shape, name)).astype(np.float32)

    wide = drawn("model.layers.0.self_attn.k_proj.weight", (64, 2048))
    assert abs(wide.std() * 2048 ** 0.5 - 1.0) < 0.05
    peaky = drawn("model.layers.0.self_attn.q_proj.weight", (64, 2048))
    assert abs(peaky.std() * 2048 ** 0.5 - 3.0) < 0.15
    last = drawn("model.layers.0.mlp.router.mlp.2.weight", (16, 256))
    assert abs(last.std() * 256 ** 0.5 - 4.0) < 0.4
    hidden = drawn("model.layers.0.mlp.router.mlp.1.weight", (256, 256))
    assert abs(hidden.std() * 256 ** 0.5 - 1.0) < 0.05
    # an output's weights sum to zero (to bf16's rounding; some 4 and 1 where
    # they are not drawn so): a GELU layer's mean favours no expert
    assert np.abs(last.sum(-1)).max() < 0.2 and np.abs(hidden.sum(-1)).max() < 0.05
    first = drawn("model.layers.0.mlp.router.mlp.0.weight", (256, 256))
    assert abs(first.std() * 256 ** 0.5 - 1.0) < 0.05 and np.abs(first.sum(-1)).max() > 0.25
    conv = drawn("model.layers.0.self_attn.conv_qk.1.weight", (1280, 128, 2))
    assert abs(conv.std() * 256 ** 0.5 - 1.0) < 0.05
    depthwise = drawn("model.layers.0.self_attn.conv_qk.0.weight", (1280, 1, 2))
    assert abs(depthwise.std() * 2 ** 0.5 - 1.0) < 0.05
    assert (drawn("model.norm.weight", (16,)) == 1).all()
    assert (drawn("model.layers.0.mlp.router.norm.weight", (64,)) == 1).all()
    temp = drawn("model.layers.0.self_attn.temp", (2,))
    assert np.abs(temp - np.log(3.0)).max() <= 0.11
    gamma = drawn("model.layers.0.mlp.router.state_scale", (256,))
    assert np.abs(gamma - 0.5).max() <= 0.11 and gamma.std() > 0.02
    scale = drawn("model.layers.0.attn_residual.hidden_scale", (2048,))
    assert np.abs(scale - 1).max() <= 0.11 and scale.std() > 0.03
    shift = drawn("model.layers.0.mlp_residual.residual_bias", (2048,))
    assert np.abs(shift).max() <= 0.051 and shift.std() > 0.02
    assert np.abs(drawn("model.layers.0.mlp.router.balancing_bias", (16,))).max() <= 0.0101
    assert np.abs(drawn("model.layers.0.self_attn.conv_qk.0.bias", (64,))).max() <= 0.051


def test_the_cell_and_its_metrics():
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": CONFIG, "traffic": "reason-16", "chips": 1}
    assert len(cell["why"]) <= 200 and "no prefix cache" in cell["why"]
    assert "every expert held" in cell["why"] and "20/40 layers" in cell["why"]
    # the head's relayout a program is the vocabulary cut's own (KNOWN_ISSUES.md, PR 52)
    assert "vocab cut" in cell["why"]
    assert MANIFEST["workloads"][-1] == cell and MANIFEST["configs"][-1]["name"] == CONFIG
    e2e = {m["name"] for m in MANIFEST["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert e2e == {"tokens_per_s", "ttft_p95_ms", "tpot_p50_ms", "tpot_p95_ms", "setup_s"}
    mine = {m["name"]: m for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]}
    assert set(mine) == {"decode_window_hbm_pct.cca-moe", "prefill_chunk_mxu_pct.cca-moe",
                         "cca_kv_swept_over_read"}
    assert [m["name"] for m in MANIFEST["per_layer"][-3:]] == list(mine)
    assert {m["layer"] for m in mine.values()} == {"compressed attention"}
    assert mine["decode_window_hbm_pct.cca-moe"]["moves"] == "tpot_p50_ms"
    assert mine["prefill_chunk_mxu_pct.cca-moe"]["moves"] == "ttft_p95_ms"
    assert mine["cca_kv_swept_over_read"]["moves"] == "tpot_p50_ms"
    reported = {m["name"] for m in MANIFEST["per_layer"] if CELL in m["workloads"]}
    # every serving metric ISSUE 52 names, and the cell's own three
    named = {"device_idle_pct.serve", "decode_window_dev_ms", "prefill_chunk_dev_ms",
             "compiles_in_window.serve", "moe_expert_load_max_over_mean",
             "idle_attributed_pct.serve", "backlog_wait_ms.serve", "emit_ms.serve",
             "chunk_ahead_ms.serve", "dispatch_gap_ms.serve"}
    gaps = {m["name"] for m in MANIFEST["per_layer"] if m["name"].startswith("gap_")}
    assert len(gaps) == 8 and reported == named | gaps | set(mine)
    # appended: wherever the cell is named it comes last
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL and m["workloads"].count(CELL) == 1
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
    for lib in ("reference_zaya", "chat_measure_zaya", "cache_audit_zaya",
                "checkpoint_zaya", "model_bytes_cca_moe"):
        assert (BENCH / "lib" / f"{lib}.py").is_file()
