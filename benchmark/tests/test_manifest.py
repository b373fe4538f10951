"""Every name in BENCHMARK.json resolves to a file and uses only the
allowed characters; the per-metric files agree with the manifest."""
import json
import re

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_keys_and_names():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["benchmark"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(names) == len(set(names))
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert any(m["name"] == "setup_s" for m in MANIFEST["end_to_end"])
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200 and NAME.match(w["traffic"])
        assert w["name"] == f"{w['config']}.{w['traffic']}"


def test_every_name_resolves_to_a_file():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    for c in configs.values():
        raw = json.loads((ROOT / c["file"]).read_text())
        assert raw["bench"]["source"] == c["source"]
        assert sorted(raw["bench"]["reduced"]) == sorted(c["reduced"])
        assert (BENCH / "graphs" / f"{raw['bench']['graph']}.py").exists()
    used = set()
    for w in MANIFEST["workloads"]:
        used.add(w["config"])
        assert w["config"] in configs
        t = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
        assert (BENCH / "generators" / f"{t['generator']}.py").exists()
    assert used == set(configs)
    cells = {w["name"] for w in MANIFEST["workloads"]}
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        spec = json.loads((BENCH / "layer_metrics" / f"{m['name']}.json").read_text())
        assert (BENCH / "readers" / f"{spec['reader']}.py").exists()
        for key in ("layer", "unit", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        where = set(m.get("workloads", cells))
        assert where <= cells
        moved = e2e[m["moves"]]
        assert where <= set(moved.get("workloads", cells)), m["name"]
    for cell in cells:  # setup_s, one more end-to-end, one per-layer
        assert sum(cell in m.get("workloads", cells) for m in MANIFEST["end_to_end"]) >= 2
        assert any(cell in m.get("workloads", cells) for m in MANIFEST["per_layer"])


def test_benchmark_imports_nothing_it_may_not():
    banned = re.compile(r"^\s*(from|import)\s+(chip_smoke|bench|bench_vlm|dora_tpu\.tools)\b", re.M)
    for path in BENCH.rglob("*.py"):
        if "tests" in path.parts:
            continue
        assert not banned.search(path.read_text()), path
