"""The cache audit of the ``k-exaone-236b-ep8`` cell through the
harness's own comparison: the rows the program's engine holds in its
rings and pages after prefill and decode beside other live streams pass
at every audited layer; the reference with every layer full, the
reference with rotary on the global layers and the program's rows
through 8 bits each come out as not correct (a ``--tiny`` rehearsal on
the CPU, where the program computes in float32)."""
import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest
from conftest import ROOT

import cache_audit_k_exaone as audit
import chat_measure_k_exaone as measure

CELL = "k-exaone-236b-ep8.mixed-len-16"
KINDS = ["sliding_attention"] * 3 + ["full_attention", "sliding_attention"]


@pytest.fixture(scope="module")
def reference_line():
    """The reference child's last line, as a rehearsal of the cell prints it."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2 ** 31 + 41), "--seconds", "4", "--trace", "0", "--tiny"],
        cwd=ROOT, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=900,
    )
    for line in proc.stdout.splitlines():
        if line.startswith('{"reference"'):
            return json.loads(line)["reference"]
    raise AssertionError(proc.stdout[-2000:] + proc.stderr[-2000:])


def verdict(ref, long_samples=1, token_bytes=256):
    return measure.verdict(ref, 0, 100, long_samples, token_bytes)


def test_rings_and_pages_are_audited_after_decode_beside_live_rows(reference_line):
    cache = reference_line["cache"]
    assert cache["layers"] == audit.entries(KINDS) == [0, 3, 4]
    assert cache["pool_layers"] == [3] and cache["ring_layers"] == [0, 1, 2, 4]
    assert len(cache["rows"]) == 4
    assert cache["served"] == 4 + audit.FILLERS and cache["streams_in_slots_a_window"] > 2
    for row in cache["rows"]:
        assert set(row["by_layer"]) == {"0", "3", "4"}
        assert row["first_decode_rows"] >= 1  # a tick's ring write was read back
        assert row["first_rows"] <= 8
    compared, holds = verdict(reference_line)
    assert holds, compared
    # float32 on the CPU: far inside the chip's limits at every layer
    assert compared["ring_rows_first_rel_err"]["value"] < 1e-5
    assert compared["kv_rows_global_first_rel_err"]["value"] < 1e-5
    assert compared["kv_rows_deep_rel_err"]["value"] < 1e-5
    assert compared["max_deficit_bf16_ulps"]["value"] < 1.0
    assert compared["controls_refused"]["value"] == 3


def test_the_longest_long_prompt_is_in_the_sample_and_wraps_the_ring(reference_line):
    assert sum(reference_line["long"].values()) >= 1
    longest = max(s["prompt_tokens"] for s in reference_line["samples"])
    assert longest >= 40  # five revolutions of a ring of 8 rows
    assert reference_line["what_if"]["full_everywhere"]["prompt_tokens"] == [longest]


@pytest.mark.parametrize("control,by", [
    ("full_everywhere", ["max_deficit_bf16_ulps", "kv_rows_deep_rel_err"]),
    ("rope_on_global", ["kv_rows_global_first_rel_err"]),
    ("rows_8bit", ["ring_rows_first_rel_err"]),
])
def test_a_control_in_the_programs_place_is_not_correct(reference_line, control, by):
    """The control's readings where the program's are: the verdict fails,
    by the limits named and by no other."""
    ref = copy.deepcopy(reference_line)
    if control == "rows_8bit":
        for row in ref["cache"]["rows"]:
            row["first"] = row["first_8bit"]
    elif control == "rope_on_global":
        for row in ref["cache"]["rows"]:
            if row["global_first_rope_on_global"] is not None:
                row["global_first"] = row["global_first_rope_on_global"]
    else:
        worst = ref["what_if"]["full_everywhere"]["max_deficit_bf16_ulps"]
        ref["samples"][0]["max_deficit_bf16_ulps"] = worst
        for row in ref["cache"]["rows"]:
            if row["deep_full_everywhere"] is not None:
                row["deep"] = row["deep_full_everywhere"]
    compared, holds = verdict(ref)
    assert not holds
    assert sorted(k for k, c in compared.items() if not c["holds"]) == sorted(by)


def test_a_comparison_that_cannot_tell_a_control_apart_is_refused(reference_line):
    ref = copy.deepcopy(reference_line)
    for row in ref["cache"]["rows"]:
        row["first_8bit"] = row["first"]  # as if 8 bits could not be seen
    compared, holds = verdict(ref)
    assert not holds and compared["controls_refused"]["value"] == 2


@pytest.mark.parametrize("change,key", [
    ({"long_samples": 0}, "long_reference_samples"),
    ({"token_bytes": 4 * 256}, "kv_bytes_per_token"),  # pages for every layer
])
def test_what_the_window_must_show(reference_line, change, key):
    compared, holds = verdict(reference_line, **{
        "long_samples": change.get("long_samples", 1),
        "token_bytes": change.get("token_bytes", 256)})
    if key == "kv_bytes_per_token":
        # the limit is the real cell's 8,192 B; the tiny model's is far under
        assert compared[key]["holds"]
        compared, holds = measure.verdict(reference_line, 0, 100, 1, 4 * 8192)
    assert not holds and not compared[key]["holds"]


def test_no_reference_is_not_correct():
    compared, holds = measure.verdict(None, 0, 100, 0, 8192)
    assert not holds and not compared["controls_refused"]["holds"]


def test_ring_positions():
    # 8 rows after position 20 was written: rows 5, 6, 7 still hold 13-15
    assert audit.ring_positions(20, 8).tolist() == [16, 17, 18, 19, 20, 13, 14, 15]
    assert audit.ring_positions(3, 8).tolist() == [0, 1, 2, 3, -4, -3, -2, -1]


def test_compare_reads_prompt_rows_by_position_and_decode_rows_at_layer_0():
    rng = np.random.default_rng(0)
    prompt, window, width = 13, 8, 64
    ref = {layer: rng.standard_normal((20, width)).astype(np.float32) for layer in (0, 3, 4)}
    emitted = [1, 2, 3, 4, 5]  # ticks wrote positions 13..16
    decode0 = rng.standard_normal((4, width)).astype(np.float32)
    at = audit.ring_positions(prompt + len(emitted) - 2, window)
    ring0 = np.stack([ref[0][p] if p < prompt else decode0[p - prompt] for p in at])
    ring4 = np.stack([ref[4][p] if p < prompt else np.full(width, 9.0) for p in at])
    got = {"emitted": emitted, "rings": {0: ring0, 4: ring4}, "pages": {3: ref[3][:prompt]}}
    read = audit.compare(got, {"as_served": ref, "rope_on_global": {3: ref[3] + 1.0}},
                         decode0, prompt, KINDS, window)
    assert read["first"] == 0.0 and read["global_first"] == 0.0 and read["deep"] == 0.0
    assert read["first_rows"] == 8 and read["first_decode_rows"] == 4
    assert read["first_8bit"] > 0.004
    assert read["global_first_rope_on_global"] > 0.5
    assert read["deep_full_everywhere"] is None
    # a ring written one row off is seen
    got["rings"][0] = np.roll(ring0, 1, axis=0)
    assert audit.compare(got, {"as_served": ref}, decode0, prompt, KINDS, window)["first"] > 1.0
