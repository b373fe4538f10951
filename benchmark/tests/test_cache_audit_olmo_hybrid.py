"""``cache_audit_olmo_hybrid``'s arithmetic and ``chat_measure_olmo_hybrid``'s
verdict on made-up readings: what a faultless program reads, and what each
control (a grant without its snapshot, a state through bfloat16) must read
to be refused. The cell's files are found by name."""
import json

import ml_dtypes
import numpy as np
import pytest
from conftest import BENCH, ROOT

import cache_audit_olmo_hybrid as audit
import chat_measure_olmo_hybrid as measure

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = next(w for w in MANIFEST["workloads"] if w["name"] == "olmo-hybrid-7b-pp2.sessions-16")
RAW = json.loads((BENCH / "configs" / f"{CELL['config']}.json").read_text())


def test_the_audited_layers_are_the_first_and_last_of_each_kind():
    assert audit.linear_and_full(RAW["layer_types"]) == ((0, 14), (3, 15))
    assert audit.linear_and_full(RAW["bench"]["tiny"]["model"]["layer_types"]) == (
        (0, 2), (3, 3))


def test_the_two_byte_share_tells_a_float32_state_from_one_through_bfloat16():
    rng = np.random.default_rng(3)
    state = rng.standard_normal((4, 8, 16)).astype(np.float32)
    assert audit.two_byte_share(state) < 0.01
    through = state.astype(ml_dtypes.bfloat16).astype(np.float32)
    assert audit.two_byte_share(through) == 1.0
    # a relative error limit could not: bf16 keeps 8 bits of a value
    assert audit.rel_err(through, state) < 0.005
    assert measure.STATE_2BYTE_SHARE == 0.1


def stream(rows=70, granted=32, noise=0.0, seed=1):
    """(what the audit read, the reference's rows, the bf16 reference's):
    states of 2 x 4 x 8, 6 channels of pre-convolution rows, K|V rows of
    16."""
    rng = np.random.default_rng(seed)
    ref = {}
    for name in ("first", "last"):
        ref[f"state_{name}"] = rng.standard_normal((2, 4, 8)).astype(np.float32)
        ref[f"c_{name}"] = rng.standard_normal((rows, 6)).astype(np.float32)
        ref[f"kv_{name}"] = rng.standard_normal((rows, 16)).astype(np.float32)

    def near(x):
        return x + noise * rng.standard_normal(x.shape).astype(np.float32)

    got = {"rows": rows, "granted_tokens": granted, "snapshots_restored": 1,
           "snapshot_row_2byte_share": 0.00004, "restored_slot_2byte_share": 0.00005,
           "restore_bits_differ": 0}
    for name in ("first", "last"):
        got[f"state_{name}"] = near(ref[f"state_{name}"])
        got[f"tail_{name}"] = near(ref[f"c_{name}"][rows - 3 :])
        got[f"kv_{name}"] = near(ref[f"kv_{name}"])
    bf16 = {k: v.astype(ml_dtypes.bfloat16).astype(np.float32)
            for k, v in ref.items() if k.startswith("state_")}
    return got, ref, bf16


def test_rows_that_differ_in_their_bits_are_counted_a_value_each():
    rng = np.random.default_rng(5)
    row = [rng.standard_normal((3, 4)).astype(np.float32),
           rng.standard_normal((2, 6)).astype(ml_dtypes.bfloat16), np.zeros((0, 2), np.float32)]
    assert audit.bits_differ(row, [x.copy() for x in row]) == 0
    other = [x.copy() for x in row]
    other[0][1, 2] = np.nextafter(other[0][1, 2], np.float32(9))  # one bit of one value
    other[1][0, :2] = -other[1][0, :2]
    assert audit.bits_differ(row, other) == 3
    # +0.0 and -0.0 are equal values and different bits
    assert audit.bits_differ([np.float32([0.0])], [np.float32([-0.0])]) == 1
    # a leaf of another dtype counts whole, whatever its values
    assert audit.bits_differ(row[:1], [row[0].astype(ml_dtypes.bfloat16)]) == 12


def test_the_states_share_reads_both_audited_layers_and_a_narrower_leaf_as_one():
    rng = np.random.default_rng(6)
    kinds = ["linear_attention", "linear_attention", "full_attention", "linear_attention"]

    def tree(dtype=np.float32, through=()):
        out = {}
        for i in (0, 1, 3):
            s = rng.standard_normal((2, 4, 8, 16)).astype(np.float32)
            if i in through:
                s = s.astype(ml_dtypes.bfloat16).astype(np.float32)
            out[str(i)] = {"s": s.astype(dtype), "conv": np.zeros((2, 3, 8), ml_dtypes.bfloat16)}
        return out

    assert audit.states_share(tree(), 1, kinds) < 0.01
    assert audit.states_share(tree(through=(3,)), 1, kinds) == 1.0  # the last linear layer
    assert audit.states_share(tree(through=(1,)), 1, kinds) < 0.01  # not an audited one
    assert audit.states_share(tree(ml_dtypes.bfloat16), 0, kinds) == 1.0


def test_compare_reads_a_faultless_stream_as_zeros_and_a_noisy_one_as_its_noise():
    got, ref, bf16 = stream()
    out = audit.compare(got, ref, bf16)
    assert out["granted_from_snapshot"] is True and out["granted_tokens"] == 32
    for key in ("state_first", "state_last", "tail_first", "tail_last", "kv_rows_first",
                "kv_rows_last", "kv_rows_granted_first", "kv_rows_granted_last"):
        assert out[key] == 0.0, key
    assert out["state_2byte_share"] < 0.1 and out["state_2byte_share_bf16"] == 1.0
    # the copy's readings: the larger share of the pool's row and the restored slot
    assert out["snapshot_2byte_share"] == 0.00005 and out["restore_bits_differ"] == 0
    unread = {**got, "restored_slot_2byte_share": None, "restore_bits_differ": None}
    out = audit.compare(unread, ref, bf16)
    assert out["snapshot_2byte_share"] is None and out["restore_bits_differ"] is None
    out = audit.compare(got, ref, bf16)
    assert 0 < out["state_last_bf16"] < 0.005
    noisy, ref, _ = stream(noise=0.1)
    out = audit.compare(noisy, ref)
    assert 0.05 < out["state_last"] < 0.2 and 0.05 < out["kv_rows_first"] < 0.2
    assert "state_2byte_share_bf16" not in out


def test_a_stream_that_was_granted_nothing_or_never_copied_is_not_granted_from_a_snapshot():
    got, ref, _ = stream(granted=0)
    assert audit.compare(got, ref)["granted_from_snapshot"] is False
    got, ref, _ = stream()
    got["snapshots_restored"] = 0
    assert audit.compare(got, ref)["granted_from_snapshot"] is False


def reading(**over):
    """The reference child's last line for a faultless run."""
    cache = {
        "granted_from_snapshot": True, "granted_tokens": 2560, "state_first": 0.003,
        "state_last": 0.21, "kv_rows_first": 0.018, "kv_rows_last": 0.096,
        "state_2byte_share": 0.00004, "state_2byte_share_bf16": 1.0,
        "snapshot_2byte_share": 0.00005, "restore_bits_differ": 0,
        "state_last_zero_state": 0.64}
    ref = {
        "cut": 2560,
        "samples": [{"max_deficit_bf16_ulps": 12.0}, {"max_deficit_bf16_ulps": 17.5}],
        "what_if": {"zero_state": {"least_deficit_bf16_ulps": 60.0},
                    "state_bf16": {"least_deficit_bf16_ulps": 13.0}},
        "cache": {**cache, **{k: v for k, v in over.items() if k in cache}}}
    ref.update({k: v for k, v in over.items() if k not in cache})
    return ref


def verdict(ref, short=0, attempted=120, kv=61440, granted=0.98, share=0.78):
    return measure.verdict(ref, short, attempted, kv, granted, share)


def test_a_faultless_run_holds_and_every_fault_breaks_its_own_line():
    compared, holds = verdict(reading())
    assert holds and all(c["holds"] for c in compared.values())
    assert compared["controls_refused"]["value"] == 2
    faults = {
        "max_deficit_bf16_ulps": reading(samples=[{"max_deficit_bf16_ulps": 300.0}]),
        "state_first_rel_err": reading(state_first=0.5),
        "state_deep_rel_err": reading(state_last=0.9),
        "state_2byte_share": reading(state_2byte_share=1.0),
        # a snapshot pool of a narrower dtype, a save that casts: the end's
        # share reads float32 again (state_2byte_share holds here), the copy's not
        "snapshot_2byte_share": reading(snapshot_2byte_share=1.0),
        # a restore that is no copy
        "restore_bits_differ": reading(restore_bits_differ=1),
        "kv_rows_rel_err": reading(kv_rows_last=0.7),
        # the grant was not made, or not at the depth the earlier turn left
        "snapshot_granted_samples": reading(granted_from_snapshot=False),
    }
    for line, ref in faults.items():
        compared, holds = verdict(ref)
        assert not holds and not compared[line]["holds"], line
        assert [k for k, c in compared.items() if not c["holds"]] == [line]
    compared, holds = verdict(reading(granted_tokens=2304))
    assert not holds and not compared["snapshot_granted_samples"]["holds"]
    # a copy that was never read (nothing granted) holds nothing
    compared, holds = verdict(reading(snapshot_2byte_share=None, restore_bits_differ=None))
    assert not holds and not compared["snapshot_2byte_share"]["holds"]
    assert not compared["restore_bits_differ"]["holds"]


def test_the_servers_own_counters_are_judged_too():
    for kw, line in ((dict(kv=122880), "kv_bytes_per_token"),
                     (dict(granted=0.3), "granted_turns_over_follow_ups"),
                     (dict(share=0.02), "prefix_hit_tokens_share"),
                     (dict(granted=None), "granted_turns_over_follow_ups"),
                     (dict(short=1), "short_streams"), (dict(attempted=0), "requests_due")):
        compared, holds = verdict(reading(), **kw)
        assert not holds and [k for k, c in compared.items() if not c["holds"]] == [line]


def test_a_control_that_passes_is_a_run_that_fails():
    # a zero state that no limit catches: the snapshot would be worth nothing
    ref = reading(state_last_zero_state=0.1)
    ref["what_if"]["zero_state"]["least_deficit_bf16_ulps"] = 20.0
    compared, holds = verdict(ref)
    assert not holds and compared["controls_refused"]["value"] == 1
    # the state alone is enough to refuse it
    ref["cache"]["state_last_zero_state"] = 1.2
    assert verdict(ref)[1]
    # a bf16 state that the bit patterns do not show
    compared, holds = verdict(reading(state_2byte_share_bf16=0.0001))
    assert not holds and compared["controls_refused"]["value"] == 1
    # no reference at all
    compared, holds = verdict(None)
    assert not holds and compared["controls_refused"]["value"] is None


def test_the_sample_puts_the_longest_follow_up_turn_first():
    done = [{"i": i, "turn": t, "prompt_tokens": p} for i, (t, p) in enumerate(
        [(0, 6000), (1, 2500), (3, 4100), (0, 1500), (2, 4100), (1, 3000)])]
    sample = measure.sample_requests(done, seed=5, n=3)
    assert len(sample) == 3 and sample[0]["i"] == 2  # the earlier of the two longest
    assert sample[0]["turn"] > 0 and sample[1]["i"] < sample[2]["i"]
    assert measure.sample_requests([d for d in done if d["turn"] == 0], 5, 3) == []


def test_a_prompt_is_the_plan_and_the_answers_the_run_received():
    plan = {"sessions": [[{"first_ids": [1, 2, 3], "turns": [
        {"message_ids": []}, {"message_ids": [9]}, {"message_ids": [8, 7]}]}]]}

    def rec(turn, tokens, ok=True):
        return {"caller": 0, "conversation": 0, "turn": turn, "tokens": tokens,
                "max_tokens": len(tokens), "finish": "length" if ok else None,
                "first": 1.0, "error": None}

    reqs = [rec(0, [4, 5]), rec(1, [6]), rec(2, [0])]
    told = measure.histories(plan, reqs)
    assert measure.prompt_of(plan, told, reqs[0]) == [1, 2, 3]
    assert measure.prompt_of(plan, told, reqs[1]) == [1, 2, 3, 4, 5, 9]
    assert measure.prompt_of(plan, told, reqs[2]) == [1, 2, 3, 4, 5, 9, 6, 8, 7]
    # an earlier turn that did not end well: the prompt cannot be rebuilt
    told = measure.histories(plan, [rec(0, [4, 5]), rec(1, [6], ok=False), reqs[2]])
    assert measure.prompt_of(plan, told, reqs[2]) is None


# -- serve(), on the tiny configuration: the copy read where it stands -------

@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """(checkpoint, the tiny cell's llm env, layer_types)."""
    import checkpoint_olmo_hybrid as ck

    bench = RAW["bench"]
    model = {**{k: v for k, v in RAW.items() if k != "bench"}, **bench["tiny"]["model"]}
    env = {**bench["node_env"]["llm"], **bench["tiny"]["node_env"]["llm"]}
    path = tmp_path_factory.mktemp("olmo_tiny") / "checkpoint"
    ck.write_checkpoint(path, model, seed=2 ** 31 + 5)
    return path, env, model["layer_types"]


def served(tiny, monkeypatch, fault=None):
    """``audit.serve`` of a turn of 70 rows and the follow-up of 84 that
    begins with it (chunk 32: granted 64), on an engine whose snapshot copy
    is the engine's own, or one that rounds float32 leaves through bfloat16
    on its way ``fault`` = ``"save"`` (slot -> pool) or ``"restore"``."""
    import os

    import jax

    from dora_tpu.nodehub import llm_server

    path, env, _ = tiny
    monkeypatch.setattr(os, "environ", {**os.environ, "JAX_PLATFORMS": "cpu"})
    make = llm_server.make_engine

    def faulty(*args, **kw):
        engine = make(*args, **kw)
        copy = engine._copy_row

        def rounding(into, of, to_row, from_row):
            restoring = into is engine.slot_state
            if restoring == (fault == "restore"):
                of = jax.tree.map(
                    lambda x: jax.lax.reduce_precision(x, 8, 7)
                    if x.dtype == np.float32 else x, of)
            return copy(into, of, to_row, from_row)

        engine._copy_row = rounding
        return engine

    if fault:
        monkeypatch.setattr(llm_server, "make_engine", faulty)
    rng = np.random.default_rng(11)
    before = rng.integers(0, 512, 70).tolist()
    sample = before + rng.integers(0, 512, 14).tolist()
    return audit.serve(str(path), env, before, sample, decode=3)


def test_the_engines_own_copy_is_read_as_a_copy_of_float32_states(tiny, monkeypatch):
    got = served(tiny, monkeypatch)
    assert got["granted_tokens"] == 64 and got["snapshots_restored"] == 1
    assert got["restore_bits_differ"] == 0
    assert got["snapshot_row_2byte_share"] < 0.01
    assert got["restored_slot_2byte_share"] < 0.01
    assert got["rows"] == 84 + len(got["emitted"]) - 1 and len(got["emitted"]) >= 3
    assert audit.two_byte_share(got["state_last"]) < 0.01


@pytest.mark.parametrize("fault", ["save", "restore"])
def test_a_copy_through_bfloat16_is_seen_where_it_stands_and_not_at_the_end(
        tiny, monkeypatch, fault):
    got = served(tiny, monkeypatch, fault)
    assert got["granted_tokens"] == 64 and got["snapshots_restored"] == 1
    # the end's states went through float32 chunks and ticks: nothing to see
    assert audit.two_byte_share(got["state_first"]) < 0.01
    assert audit.two_byte_share(got["state_last"]) < 0.01
    if fault == "save":  # the pool holds rounded values; the restore copies them
        assert got["snapshot_row_2byte_share"] == 1.0
        assert got["restored_slot_2byte_share"] == 1.0
        assert got["restore_bits_differ"] == 0
    else:  # the pool is whole, the slot is not what it holds
        assert got["snapshot_row_2byte_share"] < 0.01
        assert got["restored_slot_2byte_share"] == 1.0
        assert got["restore_bits_differ"] > 100
    shares = (got["snapshot_row_2byte_share"], got["restored_slot_2byte_share"])
    assert max(shares) > measure.STATE_2BYTE_SHARE
