"""A --tiny CPU rehearsal of each cell runs the whole control flow and
exits non-zero with ``correct: false``, because no node said ``tpu``."""
import json
import os
import subprocess
import sys

import pytest
from conftest import ROOT

CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_rehearsal_is_never_a_result(cell, trace):
    if trace and not cell.endswith(CELLS[0].split(".")[-1]) and "frames" not in cell:
        pytest.skip("one traced rehearsal for each graph kind is enough")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         str(2 ** 31 + 17), "--seconds", "4", "--trace", str(trace), "--tiny"],
        cwd=ROOT, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=600,
    )
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and last["rehearsal"] is True
    assert "metrics" not in last and "device" not in last  # no device number
    assert last["checks_passed"] is True, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["compared"] and all(c["holds"] for c in last["compared"].values())
    assert proc.stderr.strip().splitlines()[-1].startswith("compared ")
    if trace == 0:
        assert "setup_s" in last["metric_names"]
