"""The ``olmo-hybrid-7b-pp2`` configuration holds every key of the catalog
row at its published width, names every cut, and its one cell reports what
ISSUE 56 says. The cell, the configuration and their metrics are found BY
NAME, wherever later PRs append theirs."""
import json
from pathlib import Path

import pytest
from conftest import BENCH, ROOT

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
RAW = json.loads((BENCH / "configs" / "olmo-hybrid-7b-pp2.json").read_text())
CONFIG, CELL = "olmo-hybrid-7b-pp2", "olmo-hybrid-7b-pp2.sessions-16"
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
CUT = {"num_hidden_layers": 16, "layer_types": PERIOD * 4, "max_position_embeddings": 12288}
SOURCE = "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def test_every_published_width_is_unchanged():
    want = {
        "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
        "intermediate_size": 11008, "num_attention_heads": 30, "num_key_value_heads": 30,
        "hidden_act": "silu", "attention_bias": False, "rms_norm_eps": 1e-06,
        "tie_word_embeddings": False, "linear_num_key_heads": 30,
        "linear_num_value_heads": 30, "linear_key_head_dim": 96,
        "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
        "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None},
    }
    assert {k: RAW[k] for k in want} == want
    # every top-level key of the catalog row is there, and no other beside bench
    assert set(want) | set(CUT) == set(RAW) - {"bench"} and len(RAW) == 20 + 1
    # the vocabulary is whole: a lane multiple, and every id has a code
    assert RAW["vocab_size"] == 784 * 128 < 62 ** 3


def test_the_file_is_the_catalog_row_but_for_the_cuts():
    if not CATALOG.is_file():
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Olmo-Hybrid-7B")
    assert row["source_url"] == SOURCE
    differ = {k for k, v in row["config"].items() if RAW.get(k) != v}
    assert differ == set(CUT) and set(row["config"]) == set(RAW) - {"bench"}
    assert RAW["layer_types"] == row["config"]["layer_types"][:16]
    assert row["config"]["layer_types"] == PERIOD * 8
    assert RAW["bench"]["published"] == {
        "num_hidden_layers": row["config"]["num_hidden_layers"],
        "layer_types": "('linear_attention' x 3, 'full_attention') x 8",
        "max_position_embeddings": row["config"]["max_position_embeddings"]}


def test_the_cuts_are_the_ones_named_and_no_other():
    bench = RAW["bench"]
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert entry["source"] == bench["source"] == SOURCE
    assert entry["file"] == "benchmark/configs/olmo-hybrid-7b-pp2.json"
    assert len(entry["why"]) <= 200
    assert {k: RAW[k] for k in CUT} == CUT
    assert sorted(bench["reduced"]) == sorted(entry["reduced"]) == sorted(CUT)
    assert sorted(bench["published"]) == sorted(CUT)
    assert "340 s" in bench["reduced"]["num_hidden_layers"]
    # whole periods, over the guide's floor of one
    assert RAW["num_hidden_layers"] % 4 == 0 and RAW["num_hidden_layers"] >= 4
    # no width is among the cuts
    assert not any(k.endswith(("_dim", "_rank", "_size")) for k in bench["reduced"])
    # the five lines the config does not settle are written down as assumed
    daggers = {k: v for k, v in bench["assumed"].items() if v.startswith("†")}
    assert sorted(v[1] for v in daggers.values()) == list("12345")
    assert set(daggers) == {"norm_placement", "qk_norm_extent", "rope_theta_null",
                            "tensor_names", "conv_padding"}
    for key in ("norm_placement", "qk_norm_extent", "rope_theta_null", "conv_padding"):
        assert "Reference switch" in daggers[key]
    assert {"gates", "tokenizer", "weights", "quantization"} <= set(bench["assumed"])
    assert "2 pipeline stages of 16 whole layers" in bench["deployment"]
    assert "61,440 B a token" in bench["bytes_on_the_device"]
    assert "snapshots_that_fit" in bench["pool"] and "pages_that_fit" in bench["pool"]
    assert "an eighth" in bench["pool"]  # the snapshot pool's rule is stated
    assert bench["node_env"]["llm"] == {"DORA_MAX_SEQ": "12288", "DORA_MAX_NEW_TOKENS": "384"}
    # no knob of the prefix cache or of the snapshot pool is set: the defaults serve
    assert not any("PREFIX" in k or "SNAPSHOT" in k for k in bench["node_env"]["llm"])
    assert bench["graph"] == "openai_llm_olmo_hybrid"
    assert bench["tiny"]["node_env"]["llm"]["DORA_PAGE_SIZE"] == "8"


def test_the_switches_the_configuration_names_are_the_references():
    import re

    source = (ROOT / "dora_tpu" / "models" / "hf" / "olmo_hybrid_reference.py").read_text()
    switches = re.search(r"SWITCHES = \(([^)]*)\)", source).group(1)
    for text in RAW["bench"]["assumed"].values():
        for name in re.findall(r"Reference switch (\w+)", text):
            assert f'"{name}"' in switches, name


def test_the_checkpoint_holds_both_kinds_of_layer_and_an_untied_head():
    import checkpoint_olmo_hybrid as ck
    import model_bytes_gdn_hybrid as mb

    model = {k: v for k, v in RAW.items() if k != "bench"}
    assert ck.hf_config(model) == model
    linear, full = ck.layer_shapes(model, 4), ck.layer_shapes(model, 7)
    p = "model.layers.4.linear_attn."
    assert linear[p + "q_proj.weight"] == linear[p + "k_proj.weight"] == (2880, 3840)
    assert linear[p + "v_proj.weight"] == linear[p + "g_proj.weight"] == (5760, 3840)
    assert linear[p + "a_proj.weight"] == linear[p + "b_proj.weight"] == (30, 3840)
    assert linear[p + "o_proj.weight"] == (3840, 5760)
    assert linear[p + "v_conv1d.weight"] == (5760, 1, 4)
    assert linear[p + "A_log"] == linear[p + "dt_bias"] == (30,)
    assert linear[p + "o_norm.weight"] == (192,)
    p = "model.layers.7.self_attn."
    assert full[p + "k_proj.weight"] == full[p + "o_proj.weight"] == (3840, 3840)
    assert full[p + "q_norm.weight"] == full[p + "k_norm.weight"] == (3840,)
    assert not any("self_attn" in n for n in linear)
    assert not any("linear_attn" in n for n in full)

    def size(shape):
        n = 1
        for s in shape:
            n *= s
        return n

    def params(shapes):
        return sum(size(s) for n, s in shapes.items()
                   if "_proj" in n or "conv1d" in n)

    assert params(linear) == mb.linear_params(model) + mb.mlp_params(model)
    assert params(full) == mb.full_params(model) + mb.mlp_params(model)
    # 8.20 GB of bf16 in all
    total = 12 * sum(map(size, linear.values())) + 4 * sum(map(size, full.values())) + (
        2 * 100352 * 3840 + 3840)
    assert 8.19e9 < 2 * total < 8.21e9


def test_the_draws_are_a_unit_row_out_and_the_vectors_in_their_stated_ranges():
    import numpy as np

    import checkpoint_olmo_hybrid as ck

    rng = np.random.default_rng(7)

    def drawn(name, shape):
        return np.asarray(ck.draw(rng, shape, name)).astype(np.float32)

    wide = drawn("model.layers.0.linear_attn.k_proj.weight", (64, 3840))
    assert abs(wide.std() * 3840 ** 0.5 - 1.0) < 0.05
    beta = drawn("model.layers.0.linear_attn.b_proj.weight", (30, 3840))
    assert abs(beta.std() * 3840 ** 0.5 - 2.0) < 0.1
    conv = drawn("model.layers.0.linear_attn.v_conv1d.weight", (5760, 1, 4))
    assert abs(conv.std() * 4 ** 0.5 - 1.0) < 0.05
    assert (drawn("model.norm.weight", (16,)) == 1).all()
    assert (drawn("model.layers.0.post_feedforward_layernorm.weight", (16,)) == 1).all()
    for name in ("q_norm", "k_norm"):
        w = drawn(f"model.layers.3.self_attn.{name}.weight", (3840,))
        assert np.abs(w - 3 ** 0.5).max() <= 0.11 and w.std() > 0.03
    gain = drawn("model.layers.0.linear_attn.o_norm.weight", (192,))
    assert np.abs(gain - 1).max() <= 0.11 and gain.std() > 0.03
    a_log = drawn("model.layers.0.linear_attn.A_log", (3000,))
    assert np.log(0.25) - 0.01 <= a_log.min() and a_log.max() <= np.log(4.0) + 0.01
    dt = np.log1p(np.exp(drawn("model.layers.0.linear_attn.dt_bias", (3000,))))
    assert 0.00099 <= dt.min() < 0.002 and 0.05 < dt.max() <= 0.101
    # a head's decay a token: from some exp(-0.0003) to some exp(-0.4)
    slowest, fastest = np.exp(a_log.min()) * dt.min(), np.exp(a_log.max()) * dt.max()
    assert slowest < 0.0005 and 0.2 < fastest <= 0.41


def test_the_cell_and_its_metrics():
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": CONFIG, "traffic": "sessions-16", "chips": 1}
    assert len(cell["why"]) <= 200 and "sessions" in cell["why"]
    assert "snapshot" in cell["why"] and "whole history" in cell["why"]
    assert len(MANIFEST["workloads"]) >= 11
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 0
    # a closed loop at capacity: tokens/s, and not the tails, which swing with
    # the course a run takes through the prefill queue (PERF.md section 2, PR 56)
    e2e = {m["name"] for m in MANIFEST["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert e2e == {"tokens_per_s", "setup_s"}
    mine = {m["name"]: m for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]}
    assert set(mine) == {"decode_window_hbm_pct.gdn-hybrid", "prefill_chunk_mxu_pct.gdn-hybrid",
                         "gdn_state_step_hbm_pct", "prefix_hit_tokens_pct.serve"}
    assert all(m["moves"] == "tokens_per_s" for m in mine.values())
    assert mine["gdn_state_step_hbm_pct"]["layer"] == "Pallas kernels"
    assert mine["prefix_hit_tokens_pct.serve"]["source"] == "program_counter"
    assert all(m["unit"] == "%" and m["better"] == "higher" for m in mine.values())
    reported = {m["name"] for m in MANIFEST["per_layer"] if CELL in m.get("workloads", [])}
    # every accepted serving metric that moves an end-to-end metric the cell
    # reports, beside the cell's own four, and none that moves another
    named = {"device_idle_pct.serve", "compiles_in_window.serve", "dispatch_gap_ms.serve",
             "emit_ms.serve", "idle_attributed_pct.serve", "chunk_ahead_ms.serve"}
    gaps = {m["name"] for m in MANIFEST["per_layer"] if m["name"].startswith("gap_")}
    assert len(gaps) == 8
    assert named | gaps | set(mine) == reported
    assert all(m["moves"] in e2e for m in MANIFEST["per_layer"] if m["name"] in reported)
    # no metric of another model's layer names the cell
    assert not any(tag in name for name in reported
                   for tag in ("moe", "cca", "dsa", "ssm", "kda", "swa", "looped", "mla"))
    # the cell is named once wherever it is named, and no entry lost a cell for it
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert m.get("workloads", []).count(CELL) <= 1
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
    for lib in ("reference_olmo_hybrid", "chat_measure_olmo_hybrid", "cache_audit_olmo_hybrid",
                "checkpoint_olmo_hybrid", "model_bytes_gdn_hybrid"):
        assert (BENCH / "lib" / f"{lib}.py").is_file()


def test_a_full_check_fits_its_budget_with_eleven_cells():
    cells = len(MANIFEST["workloads"])
    runs = 2 + 14 * cells
    assert runs * (MANIFEST["run_seconds"] + 60) + 2 * 90 * cells + 1200 <= 43200
    assert MANIFEST["run_seconds"] == 45
