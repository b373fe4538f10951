"""The state audit of a ``falcon_h1`` cell, through the harness's own
comparison: the program's state after prefill and decode beside other
live streams passes in every layer, and the reference's bfloat16-state
control, put in the program's place, comes out as not correct by the
state's limit and by no other (a ``--tiny`` rehearsal on the CPU: the
program computes in float32 there, the control rounds as it does on the
chip)."""
import copy
import json
import os
import subprocess
import sys

import pytest
from conftest import ROOT

import chat_measure_falcon_h1 as measure
import state_audit_falcon_h1 as audit

CELL = "falcon-h1-34b-pp8.chat-16"


@pytest.fixture(scope="module")
def reference_line():
    """The reference child's last line, as a rehearsal of the cell prints it."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2 ** 31 + 33), "--seconds", "4", "--trace", "0", "--tiny"],
        cwd=ROOT, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=600,
    )
    for line in proc.stdout.splitlines():
        if line.startswith('{"reference"'):
            return json.loads(line)["reference"]
    raise AssertionError(proc.stdout[-2000:] + proc.stderr[-2000:])


def test_every_layers_state_is_audited_after_decode_beside_live_rows(reference_line):
    state = reference_line["state"]
    tiny = json.loads((ROOT / "benchmark/configs/falcon-h1-34b-pp8.json").read_text())["bench"]["tiny"]
    layers = tiny["model"]["num_hidden_layers"]
    assert state["layers"] == layers and len(state["rel_err"]) == 4
    assert all(len(row) == layers for row in state["rel_err"] + state["rel_err_bf16_state"])
    # each audited stream decoded a whole reply through windows that held other rows
    decoded = min(64, int(tiny["node_env"]["llm"]["DORA_MAX_NEW_TOKENS"]))
    assert [s["emitted"] for s in state["samples"]] == [decoded] * 4
    assert state["streams"] == 16 + audit.EXTRA_STREAMS and state["streams_in_slots_a_window"] > 8
    assert len(set(state["slots"])) == 4 and state["state_dtype"] == "float32"
    assert state["prefix_cache"] is False
    compared, holds = measure.verdict(reference_line, 0, 100)
    assert holds, compared
    assert compared["state_rel_err"]["value"] < measure.STATE_REL_ERR / 100  # float32 on the CPU


def test_a_bf16_state_comes_out_as_not_correct_by_the_states_limit_alone(reference_line):
    control = copy.deepcopy(reference_line)
    control["state"]["rel_err"] = control["state"]["rel_err_bf16_state"]
    compared, holds = measure.verdict(control, 0, 100)
    assert not holds
    assert [k for k, c in compared.items() if not c["holds"]] == ["state_rel_err"]
    # every audited stream's control fails on its own, in its last layer at least
    assert all(row[-1] > measure.STATE_REL_ERR for row in control["state"]["rel_err"])
    # and no emitted token shows it: the tokens' comparison reads the
    # bfloat16-state reference as it reads the published one
    assert reference_line["what_if"]["bf16_state"]["max_deficit_bf16_ulps"] <= measure.NEAR_TIE_ULPS


@pytest.mark.parametrize("broken,fails", [
    (lambda r: r["state"].update(rel_err=[]), "state_rel_err"),
    (lambda r: r.update(state=None), "state_rel_err"),
    (lambda r: r["state"]["samples"][0].update(max_deficit_bf16_ulps=7.0),
     "audit_max_deficit_bf16_ulps"),
    (lambda r: r["samples"][0].update(max_deficit_bf16_ulps=7.0), "max_deficit_bf16_ulps"),
])
def test_a_reading_that_is_missing_or_over_its_limit_does_not_hold(reference_line, broken, fails):
    ref = copy.deepcopy(reference_line)
    broken(ref)
    compared, holds = measure.verdict(ref, 0, 100)
    assert not holds and not compared[fails]["holds"]


def test_no_reference_and_short_streams_are_not_correct(reference_line):
    assert not measure.verdict(None, 0, 100)[1]
    assert not measure.verdict(reference_line, 1, 100)[1]
    assert not measure.verdict(reference_line, 0, 0)[1]


def test_fillers_keep_to_the_sampled_prompts_ids_and_differ():
    prompts = [list(range(100, 140)), list(range(200, 226)), list(range(300, 316))]
    made = audit.fillers(prompts, 20, 64)
    assert len(made) == 20 and len({tuple(p) for p, _ in made}) == 20
    for k, (prompt, max_new) in enumerate(made):
        base = prompts[k % 3]
        assert prompt and set(prompt) <= set(base) and len(prompt) <= len(base)
        assert 16 <= max_new <= 128
    assert len({n for _, n in made}) == 8  # replies of eight lengths
    assert audit.fillers(prompts, 20, 64) == made
