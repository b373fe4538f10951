"""The cache audit of an ``ouro`` cell, through the harness's own
comparison: the rows the program's engine holds after prefill and decode
beside other live streams pass at every audited entry; the reference's
own rows with the residual stream held to bfloat16, rows held to 8 bits
and a pool that shares rows between passes each come out as not correct,
by the rows' limits and by no other (a ``--tiny`` rehearsal on the CPU:
the program computes in float32 there, and so does the reference's
``as_stated``)."""
import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest
from conftest import ROOT

import cache_audit_ouro as audit
import chat_measure_ouro as measure

CELL = "ouro-2p6b.loop-chat-16"


@pytest.fixture(scope="module")
def reference_line():
    """The reference child's last line, as a rehearsal of the cell prints it."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2 ** 31 + 35), "--seconds", "4", "--trace", "0", "--tiny"],
        cwd=ROOT, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=900,
    )
    for line in proc.stdout.splitlines():
        if line.startswith('{"reference"'):
            return json.loads(line)["reference"]
    raise AssertionError(proc.stdout[-2000:] + proc.stderr[-2000:])


def test_every_passes_last_entry_is_audited_after_decode_beside_live_rows(reference_line):
    cache = reference_line["cache"]
    tiny = json.loads((ROOT / "benchmark/configs/ouro-2p6b.json").read_text())["bench"]["tiny"]
    layers = tiny["model"]["num_hidden_layers"]
    assert cache["entries"] == [[0, 0], [0, 1]] + [[t, layers - 1] for t in range(4)]
    assert len(cache["rows"]) == 4 and all(len(r["by_pass"]) == 4 for r in cache["rows"])
    decoded = min(32, int(tiny["node_env"]["llm"]["DORA_MAX_NEW_TOKENS"]))
    assert [s["emitted"] for s in cache["samples"]] == [decoded] * 4
    assert cache["streams"] == 4 + audit.FILLERS and cache["streams_in_slots_a_window"] > 4
    assert cache["loop_exit_before_last"] == 0 and reference_line["would_leave_before_last"] == 0
    compared, holds = measure.verdict(reference_line, 0, 100, 0)
    assert holds, compared
    # float32 on the CPU: every row is the stated precision's, far inside
    # the chip's limits at every pass, and the passes far apart
    assert compared["k_rows_same_as_stated_share"]["value"] == 1.0
    assert compared["k_rows_deep_rel_err"]["value"] < 1e-4
    assert all(p[key] < 1e-4 for r in cache["rows"] for p in r["by_pass"]
               for key in ("float32", "as_stated"))
    assert compared["passes_apart"]["value"] > 1.0
    assert compared["controls_refused"]["value"] == len(measure.CONTROLS)
    # every what-if fails the comparison of tokens
    for name in ("three_passes", "shared_rows", "no_post_norms", "no_pass_norm"):
        assert reference_line["what_if"][name]["max_deficit_bf16_ulps"] > measure.NEAR_TIE_ULPS


@pytest.mark.parametrize("control,fails", [
    # the reference's own rows, computed with the stream rounded to
    # bfloat16 after every add: none is the stated precision's row two
    # adds down (2 * 48 - 1 sublayers down they also lie too far, on the
    # chip; the rehearsal's 3 layers do not take them past that limit)
    ("bf16_residual", ["k_rows_same_as_stated_share"]),
    # the program's rows held to 8 bits: an int8 K/V pool
    ("first_8bit", ["k_rows_first_rel_err"]),
])
def test_a_lower_precision_in_the_programs_place_is_not_correct(reference_line, control, fails):
    counts = (0, 100, 0)
    compared, _ = measure.verdict(reference_line, *counts)
    assert measure.refused(reference_line, control, compared, *counts) == fails
    for row in reference_line["cache"]["rows"]:
        assert row["lead_rows_same_bf16_residual"] == 0 < row["lead_rows_same"]
        assert measure.K_ROWS_DEEP > row["deep"] and row["deep_bf16_residual"] > 100 * row["deep"]
        assert row["first_8bit"] > measure.K_ROWS_FIRST > row["first"]


def test_a_comparison_that_cannot_tell_a_control_apart_is_not_correct(reference_line):
    blind = copy.deepcopy(reference_line)
    for row in blind["cache"]["rows"]:
        row["first_8bit"] = row["first"]
    compared, holds = measure.verdict(blind, 0, 100, 0)
    assert not holds
    assert [k for k, c in compared.items() if not c["holds"]] == ["controls_refused"]


def test_a_pool_that_shares_rows_between_passes_is_not_correct(reference_line):
    """A pool that kept one entry a layer would hand the last pass the
    first pass's rows: the passes are then not apart at all."""
    control = copy.deepcopy(reference_line)
    for row in control["cache"]["rows"]:
        row["passes_apart"] = 0.0
    compared, holds = measure.verdict(control, 0, 100, 0)
    assert not holds
    assert [k for k, c in compared.items() if not c["holds"]] == ["passes_apart"]


@pytest.mark.parametrize("broken,fails", [
    (lambda r: r["cache"].update(rows=[]), "k_rows_first_rel_err"),
    (lambda r: r.update(cache=None), "k_rows_deep_rel_err"),
    (lambda r: r.update(cache=None), "controls_refused"),
    (lambda r: [row.update(lead_rows_same=0) for row in r["cache"]["rows"]],
     "k_rows_same_as_stated_share"),
    (lambda r: r["cache"]["samples"][0].update(max_deficit_bf16_ulps=1e3),
     "audit_max_deficit_bf16_ulps"),
    (lambda r: r["samples"][0].update(max_deficit_bf16_ulps=1e3), "max_deficit_bf16_ulps"),
    (lambda r: r.update(would_leave_before_last=3), "exit_before_last"),
    (lambda r: r["cache"].update(loop_exit_before_last=1), "exit_before_last"),
])
def test_a_reading_that_is_missing_or_over_its_limit_does_not_hold(reference_line, broken, fails):
    ref = copy.deepcopy(reference_line)
    broken(ref)
    compared, holds = measure.verdict(ref, 0, 100, 0)
    assert not holds and not compared[fails]["holds"]


def test_no_reference_short_streams_and_an_early_exit_are_not_correct(reference_line):
    assert not measure.verdict(None, 0, 100, 0)[1]
    assert not measure.verdict(reference_line, 1, 100, 0)[1]
    assert not measure.verdict(reference_line, 0, 0, 0)[1]
    assert not measure.verdict(reference_line, 0, 100, 2)[1]   # the server counted an exit
    assert not measure.verdict(reference_line, 0, 100, None)[1]  # or has no such counter


def test_compare_on_rows_made_by_hand():
    rng = np.random.default_rng(0)
    at = audit.entries(4, 48)
    assert at == [(0, 0), (0, 1), (0, 47), (1, 47), (2, 47), (3, 47)]
    ref = [rng.standard_normal((80, 4, 16)).astype(np.float32) for _ in at]
    own = [r[:77] for r in ref]  # the engine holds every row but the last token's
    bent = [r + 0.01 * rng.standard_normal(r.shape).astype(np.float32) for r in ref]
    read = audit.compare(own, {"as_published": ref, "as_stated": ref, "bf16_residual": bent}, at, 45)
    assert read["first"] == read["deep"] == 0.0
    assert read["lead_rows"] == read["lead_rows_same"] == audit.LEAD_ROWS
    assert read["lead_rows_same_bf16_residual"] == 0
    assert 0.006 < read["lead_row_least_bf16_residual"] < 0.01
    assert [p["as_stated"] for p in read["by_pass"]] == [0.0] * 4
    assert all(0.008 < p["bf16_residual"] < 0.012 for p in read["by_pass"])
    assert read["deep_bf16_residual"] == read["by_pass"][0]["bf16_residual"]
    assert 0.003 < read["first_8bit"] < 0.01 and 1.2 < read["passes_apart"] < 1.6
    # the program is held to the stated precision's rows, not to float32's
    read = audit.compare(own, {"as_published": bent, "as_stated": ref, "bf16_residual": ref}, at, 45)
    assert read["first"] == read["deep"] == 0.0 and read["by_pass"][0]["float32"] > 0.008
    assert read["by_pass"][0]["stated_from_float32"] > 0.008
    # a short prompt: only its own rows are looked at, and one that went another way is seen
    mixed = [o.copy() for o in own]
    mixed[1][3] += 0.01
    read = audit.compare(mixed, {v: ref for v in ("as_published", "as_stated", "bf16_residual")}, at, 20)
    assert (read["lead_rows"], read["lead_rows_same"]) == (20, 19)
    # pass 0's rows where the last pass's belong
    shared = audit.compare(own[:5] + [own[2]], {v: ref for v in (
        "as_published", "as_stated", "bf16_residual")}, at, 45)
    assert shared["passes_apart"] == 0.0 and shared["by_pass"][3]["as_stated"] > 1.2
    assert audit.entries(4, 3) == [(0, 0), (0, 1), (0, 2), (1, 2), (2, 2), (3, 2)]


def test_fillers_keep_to_the_sampled_prompts_ids_and_differ():
    prompts = [list(range(100, 240)), list(range(300, 326)), list(range(400, 416))]
    made = audit.fillers(prompts, 8, 32)
    assert len(made) == 8 and len({tuple(p) for p, _ in made}) == 8
    for k, (prompt, max_new) in enumerate(made):
        assert prompt and set(prompt) <= set(prompts[k % 3]) and len(prompt) <= 88
        assert 16 <= max_new <= 64
    assert audit.fillers(prompts, 8, 32) == made
