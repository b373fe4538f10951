"""``cache_audit_kimi_linear``'s arithmetic and ``chat_measure_kimi_linear``'s
verdict on made-up readings (what a faultless program reads, and what each
control must read to be refused), and ``serve()`` on the tiny configuration
with a PLANTED FAULT: a branch snapshot that holds the state of the wrong
depth, which ``correct`` must catch. The cell's files are found by name."""
import json

import ml_dtypes
import numpy as np
import pytest
from conftest import BENCH, ROOT

import cache_audit_kimi_linear as audit
import chat_measure_kimi_linear as measure

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = next(w for w in MANIFEST["workloads"] if w["name"] == "kimi-linear-48b-ep4.agents-64")
RAW = json.loads((BENCH / "configs" / f"{CELL['config']}.json").read_text())


def test_the_audited_layers_are_the_first_and_last_of_each_kind():
    # the published lists number the layers from 1
    assert audit.first_and_last(RAW) == ((0, 8), (3, 7))


def test_the_branch_edge_is_the_last_chunk_edge_inside_the_shared_pages():
    prefix = list(range(1000, 1000 + 8305))
    a, b = prefix + [1] * 124, prefix + [2] * 196
    assert audit.branch_edge([a, b], 16, 256) == 8304 // 256 * 256 == 8192
    # two tails that begin alike share a page more, never the last row's page
    assert audit.branch_edge([prefix + [7] * 30, prefix + [7] * 7], 16, 256) == 8192
    assert audit.branch_edge([prefix[:100], prefix[:100]], 16, 32) == 96


def stream(rows=70, granted=32, noise=0.0, seed=1):
    """(what the audit read, the reference's rows, the bf16 reference's)."""
    rng = np.random.default_rng(seed)
    ref = {}
    for name in ("first", "last"):
        ref[f"state_{name}"] = rng.standard_normal((2, 4, 8)).astype(np.float32)
        ref[f"c_{name}"] = rng.standard_normal((rows, 6)).astype(np.float32)
        ref[f"kv_{name}"] = rng.standard_normal((rows, 12)).astype(np.float32)
        ref[f"cut_{name}"] = rng.standard_normal((2, 4, 8)).astype(np.float32)
    ref["router_ids"] = np.sort(rng.integers(0, 32, (20, 4)), -1)

    def near(x):
        return x + noise * rng.standard_normal(x.shape).astype(np.float32)

    got = {"rows": rows, "granted_tokens": granted, "snapshots_restored": 1,
           "branch_saved": 1, "branch_expected": 1, "kv_pad_first": 0.0, "kv_pad_last": 0.0,
           "snapshot_row_2byte_share": 0.00004, "restored_slot_2byte_share": 0.00005,
           "restore_bits_differ": 0, "restored": {},
           "router": {"ids": ref["router_ids"].copy()}}
    for name in ("first", "last"):
        got["restored"][f"state_{name}"] = near(ref[f"cut_{name}"])
        got["restored"][f"tail_{name}"] = near(ref[f"c_{name}"][granted - 3 : granted])
        got[f"state_{name}"] = near(ref[f"state_{name}"])
        got[f"tail_{name}"] = near(ref[f"c_{name}"][rows - 3 :])
        got[f"kv_{name}"] = near(ref[f"kv_{name}"])
    bf16 = {k: v.astype(ml_dtypes.bfloat16).astype(np.float32)
            for k, v in ref.items() if k.startswith("state_")}
    return got, ref, bf16


def test_compare_reads_a_faultless_stream_as_zeros_and_a_noisy_one_as_its_noise():
    got, ref, bf16 = stream()
    out = audit.compare(got, ref, bf16)
    assert out["granted_from_snapshot"] is True and out["granted_tokens"] == 32
    for key in ("state_first", "state_last", "tail_first", "tail_last", "latent_rows_first",
                "latent_rows_last", "latent_rows_granted_first", "latent_row_padding",
                "latent_rows_past_grant_last", "restored_state_first", "restored_state_last",
                "restored_tail_last", "router_rows_differ"):
        assert out[key] == 0.0, key
    assert out["state_2byte_share"] < 0.1 and out["state_2byte_share_bf16"] == 1.0
    assert out["snapshot_2byte_share"] == 0.00005 and out["restore_bits_differ"] == 0
    noisy, ref, _ = stream(noise=0.1)
    out = audit.compare(noisy, ref)
    assert 0.05 < out["state_last"] < 0.2 and 0.05 < out["latent_rows_first"] < 0.2
    # a grant that no branch snapshot stood behind, where one was due
    got, ref, _ = stream()
    assert audit.compare({**got, "branch_saved": 0}, ref)["granted_from_snapshot"] is False
    assert audit.compare({**got, "branch_saved": 0, "branch_expected": 0},
                         ref)["granted_from_snapshot"] is True
    assert audit.compare({**got, "snapshots_restored": 0}, ref)["granted_from_snapshot"] is False
    # a restored state that is another depth's, a router that chose otherwise
    other = {**got, "restored": {**got["restored"], "state_last": -got["restored"]["state_last"]}}
    assert audit.compare(other, ref)["restored_state_last"] == 2.0
    moved = got["router"]["ids"].copy()
    moved[:5, 0] += 1
    assert audit.compare({**got, "router": {"ids": moved}}, ref)["router_rows_differ"] == 0.25
    # a stored row whose padding columns are not zeros
    assert audit.compare({**got, "kv_pad_last": 0.5}, ref)["latent_row_padding"] == 0.5


def reading(**over):
    """The reference child's last line for a faultless run."""
    cache = {
        "granted_from_snapshot": True, "granted_tokens": 9984, "state_first": 0.003,
        "state_last": 0.1, "latent_rows_first": 0.01, "latent_rows_last": 0.05,
        "latent_rows_past_grant_last": 0.06,
        "latent_row_padding": 0.0, "state_2byte_share": 0.00004,
        "state_2byte_share_bf16": 1.0, "snapshot_2byte_share": 0.00005,
        "restore_bits_differ": 0, "restored_state_first": 0.003,
        "restored_state_last": 0.3, "router_rows_differ": 0.002,
        "restored_state_zero_state": 1.0, "router_rows_differ_bf16": 0.55,
        "latent_rows_past_grant_last_zero_state": 0.5}
    ref = {
        "cut": 9984,
        "samples": [{"max_deficit_bf16_ulps": 12.0}, {"max_deficit_bf16_ulps": 17.5}],
        "what_if": {"zero_state": {"least_deficit_bf16_ulps": 90.0},
                    "state_bf16": {"least_deficit_bf16_ulps": 13.0},
                    "bounded_gate": {"least_deficit_bf16_ulps": 3 * measure.NEAR_TIE_ULPS}},
        "cache": {**cache, **{k: v for k, v in over.items() if k in cache}}}
    ref.update({k: v for k, v in over.items() if k not in cache})
    return ref


def verdict(ref, short=0, attempted=120, kv=2560, branch=2, share=0.97, live=61.0,
            expected=9984):
    return measure.verdict(ref, short, attempted, kv, branch, share, live, expected)


def test_a_faultless_run_holds_and_every_fault_breaks_its_own_line():
    compared, holds = verdict(reading())
    assert holds and all(c["holds"] for c in compared.values())
    assert compared["controls_refused"]["value"] == len(measure.CONTROLS) == 4
    faults = {
        "max_deficit_bf16_ulps": reading(samples=[{"max_deficit_bf16_ulps": 300.0}]),
        "state_first_rel_err": reading(state_first=0.5),
        "state_deep_rel_err": reading(state_last=0.9),
        "state_2byte_share": reading(state_2byte_share=1.0),
        "snapshot_2byte_share": reading(snapshot_2byte_share=1.0),
        "restore_bits_differ": reading(restore_bits_differ=1),
        # a faithful copy of another depth's row
        "restored_state_first_rel_err": reading(restored_state_first=0.4),
        "restored_state_deep_rel_err": reading(restored_state_last=1.3),
        "router_rows_differ": reading(router_rows_differ=0.5),
        "latent_rows_rel_err": reading(latent_rows_past_grant_last=0.7),
        "latent_row_padding": reading(latent_row_padding=0.01),
        "snapshot_granted_samples": reading(granted_from_snapshot=False),
    }
    for line, ref in faults.items():
        compared, holds = verdict(ref)
        assert not holds and not compared[line]["holds"], line
        assert [k for k, c in compared.items() if not c["holds"]] == [line]
    # granted, and not at the branch edge
    compared, holds = verdict(reading(granted_tokens=9728))
    assert not holds and not compared["snapshot_granted_samples"]["holds"]


def test_the_servers_own_counters_are_judged_too():
    for kw, line in ((dict(kv=5120), "kv_bytes_per_token"),
                     (dict(branch=0), "branch_snapshots_saved"),
                     (dict(share=0.4), "prefix_hit_tokens_share"),
                     (dict(live=12.0), "live_rows_a_tick"),
                     (dict(short=1), "short_streams"), (dict(attempted=0), "requests_due")):
        compared, holds = verdict(reading(), **kw)
        assert not holds and [k for k, c in compared.items() if not c["holds"]] == [line]


def test_a_control_that_passes_is_a_run_that_fails():
    # a grant without its snapshot that the restored state does not show, or
    # whose rows past the grant are the reference's
    compared, holds = verdict(reading(restored_state_zero_state=0.1))
    assert not holds and compared["controls_refused"]["value"] == 3
    assert not verdict(reading(latent_rows_past_grant_last_zero_state=0.2))[1]
    # a bf16 state that the bit patterns do not show
    assert not verdict(reading(state_2byte_share_bf16=0.0001))[1]
    # a bf16 router that chooses as the float32 one does
    assert not verdict(reading(router_rows_differ_bf16=0.01))[1]
    # another gate whose tokens lie as near the top as the program's
    ref = reading()
    ref["what_if"]["bounded_gate"]["least_deficit_bf16_ulps"] = 10.0
    compared, holds = verdict(ref)
    assert not holds and compared["controls_refused"]["value"] == 3
    compared, holds = verdict(None)
    assert not holds and compared["controls_refused"]["value"] is None


def test_the_sample_puts_the_longest_prompt_of_a_prefix_that_branched_first():
    done = [{"i": i, "prefix": g, "prompt_tokens": p} for i, (g, p) in enumerate(
        [(3, 12400), (0, 6400), (2, 10300), (1, 8400), (2, 10300), (0, 6300)])]
    sample = measure.sample_requests(done, seed=5, n=3, branching={0, 2})
    assert len(sample) == 3 and sample[0]["i"] == 2  # the earlier of the two longest
    assert sample[1]["i"] < sample[2]["i"]
    # where no prefix branched, the longest of all
    assert measure.sample_requests(done, 5, 3, set())[0]["i"] == 0
    assert measure.sample_requests([], 5, 3, {0}) == []


def test_a_prompt_is_its_prefix_and_its_tail_and_the_warm_wave_has_its_own():
    plan = {"prefixes": [[1, 2, 3], [4, 5]],
            "warm": [{"prefix": 0, "tail_ids": [9]}, {"prefix": 1, "tail_ids": [8]}],
            "requests": [[{"prefix": 0, "tail_ids": [7, 7]}], [{"prefix": 1, "tail_ids": [6]}]]}
    assert measure.prompt_of(plan, {"caller": -1, "k": 1}) == [4, 5, 8]
    assert measure.prompt_of(plan, {"caller": 0, "k": 0}) == [1, 2, 3, 7, 7]
    assert measure.prompt_of(plan, {"caller": 1, "k": 0}) == [4, 5, 6]


# -- serve(), on the tiny configuration: a branch snapshot of the wrong depth -------

@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """(checkpoint, the tiny cell's llm env, the tiny model's config)."""
    import checkpoint_kimi_linear as ck

    bench = RAW["bench"]
    model = {k: v for k, v in RAW.items() if k != "bench"}
    for key, value in bench["tiny"]["model"].items():
        model[key] = {**model[key], **value} if isinstance(value, dict) else value
    env = {**bench["node_env"]["llm"], **bench["tiny"]["node_env"]["llm"]}
    path = tmp_path_factory.mktemp("kimi_linear_tiny") / "checkpoint"
    ck.write_checkpoint(path, model, seed=2 ** 31 + 5)
    return path, env, ck.hf_config(model)


def prompts(seed=11):
    """Two warm prompts that share 100 rows (chunk 32: the second leaves
    the tree after 96 = 12 pages of 8, its branch edge; the first one's own
    snapshot stands at 128, past them) and the sample that shares them."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, 512, 100).tolist()
    return [prefix + rng.integers(0, 512, n).tolist() for n in (40, 37, 45)]


def served(tiny, monkeypatch, fault=False):
    """``audit.serve`` on the engine as it is, or on one whose BRANCH
    snapshot is copied a chunk late: the row stands at the branch edge and
    holds the state a chunk past it."""
    import os

    from dora_tpu.models.batch_engine import PagedBatchEngine

    path, env, _ = tiny
    monkeypatch.setattr(os, "environ", {**os.environ, "JAX_PLATFORMS": "cpu"})
    save = PagedBatchEngine._save_snapshot
    late = {}

    def a_chunk_late(self, s, b, depth):
        if late.get(b) is not None:  # the chunk after the branch edge has run
            save(self, s, b, late.pop(b))
        if depth == s.branch_edge:
            late[b] = depth
            return
        save(self, s, b, depth)

    if fault:
        monkeypatch.setattr(PagedBatchEngine, "_save_snapshot", a_chunk_late)
    first, second, sample = prompts()
    return audit.serve(str(path), env, [first, second], sample, decode=3), sample


def wanted(tiny, sequence):
    """The reference's rows over ``sequence`` (the program's own float32
    twin, at the tiny widths), as ``compare`` takes them."""
    import jax.numpy as jnp

    from dora_tpu.models.hf import kimi_linear as K
    from dora_tpu.models.hf import kimi_linear_reference as R

    path, _, config = tiny
    cfg, params = K.load(path, max_seq=512, ep_rank=0)
    rp = R.reference_params(params, cfg)
    _, kept = R.forward(rp, cfg, jnp.asarray(sequence), rows=True,
                        held=range(cfg.expert_first, cfg.expert_first + cfg.experts_held))
    (k0, k1), (m0, m1) = audit.first_and_last(config)
    cut = {}
    for name, layer in (("first", k0), ("last", k1)):
        _, at_cut = R.forward(rp, cfg, jnp.asarray(sequence[:96]), rows=True, held=range(
            cfg.expert_first, cfg.expert_first + cfg.experts_held))
        cut[f"cut_{name}"] = np.asarray(at_cut[layer]["s"])
    return {**cut,
            "state_first": np.asarray(kept[k0]["s"]), "state_last": np.asarray(kept[k1]["s"]),
            "c_first": np.asarray(kept[k0]["pre"]), "c_last": np.asarray(kept[k1]["pre"]),
            "kv_first": np.asarray(kept[m0]["kv"]), "kv_last": np.asarray(kept[m1]["kv"])}


def judged(got, want):
    """``chat_measure_kimi_linear.verdict`` of an audit's readings, every
    other reading a faultless run's."""
    cache = audit.compare(got, want)
    ref = reading()
    ref["cache"] = {**ref["cache"], **cache}
    return measure.verdict(ref, 0, 120, 2560, 2, 0.97, 61.0, 96)


def test_the_engines_own_branch_snapshot_is_granted_and_holds_the_references_state(
        tiny, monkeypatch):
    got, sample = served(tiny, monkeypatch)
    assert got["branch_saved"] == 1 and got["granted_tokens"] == 96
    assert got["snapshots_restored"] == 1 and got["restore_bits_differ"] == 0
    assert got["snapshot_row_2byte_share"] < 0.01 and got["slots"] == 6
    assert got["rows"] == len(sample) + len(got["emitted"]) - 1
    want = wanted(tiny, sample + got["emitted"][:-1])
    compared, holds = judged(got, want)
    assert holds, [k for k, c in compared.items() if not c["holds"]]
    assert compared["state_deep_rel_err"]["value"] < 1e-4  # float32 on the CPU
    assert compared["restored_state_deep_rel_err"]["value"] < 1e-4


def test_a_branch_snapshot_of_the_wrong_depth_is_caught(tiny, monkeypatch):
    got, sample = served(tiny, monkeypatch, fault=True)
    # the grant is made, at the right depth, and the copy is a faithful copy ...
    assert got["branch_saved"] == 1 and got["granted_tokens"] == 96
    assert got["snapshots_restored"] == 1 and got["restore_bits_differ"] == 0
    # ... of the state a chunk too deep: what the slot holds at the end is not
    # the reference's, and ``correct`` is false by the states' own limits
    want = wanted(tiny, sample + got["emitted"][:-1])
    compared, holds = judged(got, want)
    assert not holds
    broken = {k for k, c in compared.items() if not c["holds"]}
    # the restored slot is not the reference's state at the grant's boundary
    assert {"restored_state_first_rel_err", "restored_state_deep_rel_err"} <= broken
    assert compared["restored_state_deep_rel_err"]["value"] > 0.8
