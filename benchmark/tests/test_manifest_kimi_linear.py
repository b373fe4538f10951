"""The ``kimi-linear-48b-ep4`` configuration holds every key of the catalog
row at its published width, names every cut, states the bytes
``model_bytes_kda_mla`` computes, and its one cell reports what ISSUE 58
says. The cell, the configuration and their metrics are found BY NAME,
wherever later PRs append theirs."""
import json
import re
from pathlib import Path

import pytest
from conftest import BENCH, ROOT

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
RAW = json.loads((BENCH / "configs" / "kimi-linear-48b-ep4.json").read_text())
CONFIG, CELL = "kimi-linear-48b-ep4", "kimi-linear-48b-ep4.agents-64"
LINEAR = {"full_attn_layers": [4, 8], "head_dim": 128, "kda_layers": [1, 2, 3, 5, 6, 7, 9],
          "num_heads": 32, "short_conv_kernel_size": 4}
CUT = {"num_hidden_layers": 9, "linear_attn_config": LINEAR, "num_experts": 64,
       "ep_size": 4, "vocab_size": 40960, "model_max_length": 16384}
SOURCE = "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def test_every_published_width_is_unchanged():
    want = {
        "model_type": "kimi_linear", "hidden_size": 2304, "intermediate_size": 9216,
        "moe_intermediate_size": 1024, "num_attention_heads": 32, "num_key_value_heads": 32,
        "head_dim": 72, "kv_lora_rank": 512, "q_lora_rank": None, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "mla_use_nope": True,
        "rope_scaling": None, "num_experts_per_token": 8, "num_shared_experts": 1,
        "first_k_dense_replace": 1, "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_expert_group": 1, "topk_group": 1,
        "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-05, "hidden_act": "silu",
        "tie_word_embeddings": False,
    }
    assert {k: RAW[k] for k in want} == want
    lin = RAW["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]) == (32, 128, 4)
    # the vocabulary is a quarter, a lane multiple, and every id has a code
    assert RAW["vocab_size"] == 320 * 128 == 163840 // 4 < 62 ** 3


def test_the_file_is_the_catalog_row_but_for_the_cuts():
    if not CATALOG.is_file():
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert row["source_url"] == SOURCE
    differ = {k for k in set(row["config"]) | set(RAW) - {"bench"}
              if RAW.get(k, "absent") != row["config"].get(k, "absent")}
    assert differ == set(CUT)
    assert set(row["config"]) == set(RAW) - {"bench", "ep_size"}
    # the layers kept are the published ones' beginning, in their order
    full = row["config"]["linear_attn_config"]
    assert full["kda_layers"][:7] == LINEAR["kda_layers"]
    assert full["full_attn_layers"][:2] == LINEAR["full_attn_layers"]
    assert {k: full[k] for k in ("num_heads", "head_dim", "short_conv_kernel_size")} == {
        k: LINEAR[k] for k in ("num_heads", "head_dim", "short_conv_kernel_size")}
    assert RAW["bench"]["published"] == {
        "num_hidden_layers": 27, "linear_attn_config": full, "num_experts": 256,
        "vocab_size": 163840, "model_max_length": 1048576}


def test_the_cuts_are_the_ones_named_and_no_other():
    bench = RAW["bench"]
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert entry["source"] == bench["source"] == SOURCE
    assert entry["file"] == "benchmark/configs/kimi-linear-48b-ep4.json"
    assert len(entry["why"]) <= 200
    assert {k: RAW[k] for k in CUT} == CUT
    assert sorted(bench["reduced"]) == sorted(entry["reduced"]) == sorted(CUT)
    assert "340 s" in bench["reduced"]["num_hidden_layers"]
    # the dense layer, then two whole periods: over the guide's floors
    assert RAW["num_hidden_layers"] - RAW["first_k_dense_replace"] == 2 * 4 >= 4
    assert RAW["num_experts"] >= 8 and RAW["vocab_size"] * 8 >= 163840
    # no width is among the cuts
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k not in ("ep_size", "vocab_size")
                   for k in bench["reduced"])
    daggers = {k: v for k, v in bench["assumed"].items() if v.startswith("†")}
    assert sorted(v[1] for v in daggers.values()) == list("1234")
    assert set(daggers) == {"conv_bias", "kda_gate", "nope_shared_columns", "tensor_names"}
    assert {"weights", "quantization", "tokenizer", "trained_gates"} <= set(bench["assumed"])
    assert "3 pipeline stages of 9" in bench["deployment"]
    assert "no code stands in for the absent 3 chips" in bench["deployment"]
    assert bench["node_env"]["llm"] == {
        "DORA_MAX_SEQ": "16384", "DORA_MAX_NEW_TOKENS": "1536", "DORA_EP_RANK": "0",
        "DORA_BATCH_SLOTS": "64"}
    # no knob of the prefix cache or of the snapshot pool is set: the defaults serve
    assert not any("PREFIX" in k or "SNAPSHOT" in k for k in bench["node_env"]["llm"])
    assert bench["graph"] == "openai_llm_kimi_linear"
    assert bench["tiny"]["node_env"]["llm"]["DORA_PAGE_SIZE"] == "8"


def test_the_switches_the_configuration_names_are_the_references():
    source = (ROOT / "dora_tpu" / "models" / "hf" / "kimi_linear_reference.py").read_text()
    switches = re.search(r"SWITCHES = \(([^)]*)\)", source, re.S).group(1)
    named = [name for text in RAW["bench"]["assumed"].values()
             for name in re.findall(r"reference's switch (\w+)", text)]
    assert sorted(named) == ["bounded_gate", "drop_shared_columns"]
    for name in named:
        assert f'"{name}"' in switches, name


def test_the_bytes_the_file_states_are_model_bytes_kda_mlas():
    import model_bytes_kda_mla as mb

    model = {k: v for k, v in RAW.items() if k != "bench"}
    text = RAW["bench"]["bytes_on_the_device"]
    w = mb.weight_bytes(model)
    for number in (mb.kda_params(model), mb.mla_params(model), mb.expert_params(model),
                   mb.dense_params(model), mb.router_params(model), w["layers_int8"],
                   w["head_int8"], w["embedding_bf16"], w["routers_bf16"]):
        assert f"{number:,}" in text, number
    assert "4.37 GB of weights" in text and f"{w['total'] / 1e9:.2f}" == "4.37"
    assert "8.54 GB" in text


def test_the_checkpoint_holds_both_mixers_the_dense_layer_and_a_ranks_experts():
    import checkpoint_kimi_linear as ck
    import model_bytes_kda_mla as mb

    model = {k: v for k, v in RAW.items() if k != "bench"}
    assert ck.hf_config(model)["num_experts"] == 256  # HF's meaning restored
    assert [ck.is_kda(model, i) for i in range(9)] == [
        True, True, True, False, True, True, True, False, True]
    assert [ck.is_sparse(model, i) for i in range(9)] == [False] + [True] * 8
    dense, kda, mla = (ck.layer_shapes(model, i) for i in (0, 1, 3))
    a = "model.layers.1.self_attn."
    assert kda[a + "q_proj.weight"] == kda[a + "v_proj.weight"] == (4096, 2304)
    assert kda[a + "f_a_proj.weight"] == (128, 2304) and kda[a + "f_b_proj.weight"] == (4096, 128)
    assert kda[a + "b_proj.weight"] == (32, 2304) and kda[a + "A_log"] == (32,)
    assert kda[a + "dt_bias"] == (4096,) and kda[a + "q_conv1d.weight"] == (4096, 1, 4)
    a = "model.layers.3.self_attn."
    assert mla[a + "q_proj.weight"] == (32 * 192, 2304)  # no query rank
    assert mla[a + "kv_a_proj_with_mqa.weight"] == (576, 2304)
    assert mla[a + "kv_b_proj.weight"] == (32 * 256, 512)
    assert "model.layers.0.mlp.gate_proj.weight" in dense
    m = "model.layers.1.block_sparse_moe."
    assert kda[m + "gate.weight"] == (256, 2304)  # the router keeps its width
    assert kda[m + "experts.63.w1.weight"] == (1024, 2304)
    assert m + "experts.64.w1.weight" not in kda
    assert m + "experts.64.w1.weight" in ck.layer_shapes(model, 1, rank=1)

    def params(shapes):
        n = 0
        for name, shape in shapes.items():
            if name.endswith((".weight",)) and len(shape) >= 2 and "gate.weight" not in name:
                size = 1
                for s in shape:
                    size *= s
                n += size
        return n

    assert params(dense) == mb.layer_params(model, 0)
    assert params(kda) == mb.layer_params(model, 1)
    assert params(mla) == mb.layer_params(model, 3)


def test_the_draws_are_a_unit_row_out_and_the_vectors_in_their_stated_ranges():
    import numpy as np

    import checkpoint_kimi_linear as ck

    rng = np.random.default_rng(7)

    def drawn(name, shape, gain=1.0):
        return np.asarray(ck.draw(rng, shape, name, gain)).astype(np.float32)

    wide = drawn("model.layers.0.self_attn.k_proj.weight", (64, 2304))
    assert abs(wide.std() * 2304 ** 0.5 - 1.0) < 0.05
    queries = drawn("model.layers.3.self_attn.q_proj.weight", (64, 2304), ck.QUERY_GAIN)
    assert abs(queries.std() * 2304 ** 0.5 - 3.0) < 0.15
    taps = drawn("model.layers.0.self_attn.q_conv1d.weight", (4096, 1, 4))
    assert np.abs(taps).max() <= 0.0501  # bf16 rounds the last level up
    assert np.abs(drawn("model.layers.0.self_attn.v_conv1d.weight", (4096, 1, 4))).max() <= 0.501
    assert (drawn("model.norm.weight", (16,)) == 1).all()
    bias = drawn("model.layers.1.block_sparse_moe.gate.e_score_correction_bias", (256,))
    assert np.abs(bias).max() <= 0.0101 and abs(bias.mean()) < 0.003  # a centred router
    a = np.exp(drawn("model.layers.0.self_attn.A_log", (3000,)))
    dt = drawn("model.layers.0.self_attn.dt_bias", (3000,))
    assert 0.6 <= a.min() and a.max() <= 1.66 and -5.52 <= dt.min() and dt.max() <= -0.48
    # a channel forgets (1 / |g|, r = 0) in one to a few hundred positions
    slowest, fastest = a.min() * np.log1p(np.exp(dt.min())), a.max() * np.log1p(np.exp(dt.max()))
    assert 1 / 500 < slowest < 1 / 300 and 0.5 < fastest < 1.0


def test_the_cell_and_its_metrics():
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": CONFIG, "traffic": "agents-64", "chips": 1}
    assert len(cell["why"]) <= 200 and "64 callers=64 slots" in cell["why"]
    assert "1/4" in cell["why"] and "4x" in cell["why"] and "snapshot" in cell["why"]
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 0
    assert sum(w["config"] == CONFIG for w in MANIFEST["workloads"]) == 1  # the only new cell
    e2e = {m["name"] for m in MANIFEST["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert {"tokens_per_s", "tpot_p50_ms", "setup_s"} <= e2e
    assert e2e <= {"tokens_per_s", "tpot_p50_ms", "setup_s", "ttft_p95_ms", "tpot_p95_ms"}
    mine = {m["name"]: m for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]}
    assert set(mine) == {"decode_window_hbm_pct.kda-mla", "prefill_chunk_mxu_pct.kda-mla",
                         "kda_state_step_hbm_pct", "latent_kv_swept_over_read"}
    assert mine["prefill_chunk_mxu_pct.kda-mla"]["moves"] == "tokens_per_s"
    assert all(m["moves"] == "tpot_p50_ms" for name, m in mine.items()
               if name != "prefill_chunk_mxu_pct.kda-mla")
    assert mine["kda_state_step_hbm_pct"]["layer"] == "Pallas kernels"
    assert mine["latent_kv_swept_over_read"] == {
        **mine["latent_kv_swept_over_read"], "unit": "ratio", "better": "lower",
        "source": "program_counter"}
    reported = {m["name"] for m in MANIFEST["per_layer"] if CELL in m.get("workloads", [])}
    # every metric that names the cell moves an end-to-end metric the cell reports
    assert all(m["moves"] in e2e for m in MANIFEST["per_layer"] if m["name"] in reported)
    named = {"device_idle_pct.serve", "compiles_in_window.serve", "dispatch_gap_ms.serve",
             "emit_ms.serve", "idle_attributed_pct.serve", "chunk_ahead_ms.serve",
             "prefix_hit_tokens_pct.serve"}
    gaps = {m["name"] for m in MANIFEST["per_layer"] if m["name"].startswith("gap_")}
    assert len(gaps) == 8 and named | gaps | set(mine) <= reported
    # no metric of another model's layer names the cell
    assert not any(tag in name for name in reported - set(mine)
                   for tag in ("cca", "dsa", "ssm", "swa", "looped", "gdn"))
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert m.get("workloads", []).count(CELL) <= 1
    for m in mine.values():
        spec = json.loads((BENCH / "layer_metrics" / f"{m['name']}.json").read_text())
        assert (BENCH / "readers" / f"{spec['reader']}.py").is_file()
        assert spec["layer"] == m["layer"] and spec["unit"] == m["unit"]
        assert spec["moves"] == m["moves"]
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    assert (BENCH / "generators" / f"{traffic['generator']}.py").is_file()
    assert (BENCH / "graphs" / f"{RAW['bench']['graph']}.py").is_file()
    for lib in ("reference_kimi_linear", "chat_measure_kimi_linear", "cache_audit_kimi_linear",
                "checkpoint_kimi_linear", "model_bytes_kda_mla"):
        assert (BENCH / "lib" / f"{lib}.py").is_file()
    # the program reports the prefilled rows under the name the accepted
    # prefix_hit_tokens_pct.serve reads them by
    spec = json.loads((BENCH / "layer_metrics" / "prefix_hit_tokens_pct.serve.json").read_text())
    source = (ROOT / "dora_tpu" / "models" / "hf" / "kimi_linear.py").read_text()
    assert f'"{spec["args"]["prefilled"]}"' in source


def test_a_full_check_fits_its_budget_with_this_cell():
    cells = len(MANIFEST["workloads"])
    runs = 2 + 14 * cells
    assert cells >= 12
    assert runs * (MANIFEST["run_seconds"] + 60) + 2 * 90 * cells + 1200 <= 43200
    assert MANIFEST["run_seconds"] == 45
