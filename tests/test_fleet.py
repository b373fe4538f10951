"""Fleet state plane: digests, merge, placement scoring, surfaces.

Unit layers drive dora_tpu/fleet.py directly — hash-chain round trips
against a real PrefixCache, build_digest over the stub paged engine, the
publish cadence, HLC-skewed merge, and the deterministic placement
ranking. The e2e boots a coordinator plus two daemons, serves two stub
engines warmed with DISJOINT prompts, then asserts QueryFleet ->
score_placement routes each prompt to the replica that actually holds
its prefix.
"""

from __future__ import annotations

import asyncio
import textwrap

import pytest

from dora_tpu import fleet
from dora_tpu.models.prefix_cache import prompt_hash_chain

G = 1_000_000_000  # ns per second


def _cache(num_pages=32, page_size=4, **kw):
    from dora_tpu.models.batch_engine import PageAllocator
    from dora_tpu.models.prefix_cache import PrefixCache

    a = PageAllocator(num_pages)
    return a, PrefixCache(a, page_size, **kw)


# ---------------------------------------------------------------------------
# hash chains: insert-time chains match router-side prompt hashing
# ---------------------------------------------------------------------------


def test_prompt_hash_chain_matches_cache_digest():
    a, c = _cache(page_size=4)
    ids = list(range(1, 13))  # 3 full pages
    c.insert(ids, a.alloc(3))
    digest = c.digest()
    chains = {(chain, tlen) for chain, tlen, _pages in digest}
    assert chains == set(prompt_hash_chain(ids, 4))
    # pages column counts path depth in pages
    assert sorted(p for _, _, p in digest) == [1, 2, 3]
    # token_len is always a full-page multiple
    assert all(tlen == pages * 4 for _, tlen, pages in digest)


def test_prompt_hash_chain_is_deterministic_and_prefix_free():
    one = prompt_hash_chain([1, 2, 3, 4, 5, 6, 7, 8], 4)
    two = prompt_hash_chain([1, 2, 3, 4, 5, 6, 7, 8], 4)
    assert one == two and len(one) == 2
    # a different first page changes EVERY later chain (chained hash)
    other = prompt_hash_chain([9, 2, 3, 4, 5, 6, 7, 8], 4)
    assert one[0][0] != other[0][0] and one[1][0] != other[1][0]
    # the trailing partial page contributes nothing
    assert prompt_hash_chain([1, 2, 3, 4, 5], 4) == prompt_hash_chain(
        [1, 2, 3, 4], 4
    )


def test_adapter_scopes_the_chain_root():
    """Tenant isolation is part of the hash: the same tokens under a
    different adapter produce different chains, so a router can never
    match one tenant's prompt against another's cached pages."""
    base = prompt_hash_chain([1, 2, 3, 4], 4, None)
    tenant = prompt_hash_chain([1, 2, 3, 4], 4, "tenant-b")
    assert base[0][0] != tenant[0][0]
    a, c = _cache(page_size=4)
    c.insert([1, 2, 3, 4], a.alloc(1), adapter="tenant-b")
    (chain, tlen, _pages), = c.digest()
    assert (chain, tlen) == tenant[0]


def test_digest_is_bounded_and_mru_first():
    a, c = _cache(num_pages=64, page_size=4)
    for i in range(6):
        ids = [100 * i + j for j in range(1, 5)]
        c.insert(ids, a.alloc(1))
    assert len(c.digest(top_n=4)) == 4
    # the most recently inserted prefix survives the cut
    last = prompt_hash_chain([500 + j for j in range(1, 5)], 4)[0][0]
    assert any(chain == last for chain, _, _ in c.digest(top_n=4))


# ---------------------------------------------------------------------------
# build_digest over the stub paged engine
# ---------------------------------------------------------------------------


def _stub_engine(**kw):
    pytest.importorskip("jax")
    from dora_tpu.models.batch_engine import make_stub_paged_engine

    kw.setdefault("prefix_cache", True)
    return make_stub_paged_engine(**kw)


def test_build_digest_snapshots_the_stub_engine():
    eng = _stub_engine(max_slots=4)
    d = fleet.build_digest(eng, model_id="stub", seq=3)
    assert d.seq == 3 and d.model_id == "stub"
    assert d.page_size == eng.page_size and d.window == eng.window
    assert d.total_pages == eng.allocator.num_pages - 1  # null page
    assert d.used_pages == 0 and d.free_streams > 0
    assert d.prefixes == [] and d.adapters == []
    # fingerprint is a pure function of the config tuple
    again = fleet.build_digest(eng, model_id="stub", seq=4)
    assert again.fingerprint == d.fingerprint
    other = fleet.config_fingerprint(
        model_id="stub", window=d.window + 1, spec_k=d.spec_k,
        kv_dtype=d.kv_dtype, weight_bits=d.weight_bits,
        page_size=d.page_size,
    )
    assert other != d.fingerprint


def test_free_stream_capacity_shrinks_with_the_page_pool():
    eng = _stub_engine(max_slots=4, num_pages=8, max_seq=32, page_size=8)
    full = fleet.free_stream_capacity(eng)
    assert 0 < full <= 4
    # drain the free pool: capacity must fall, never go negative
    eng.allocator.alloc(eng.allocator.free_pages)
    assert fleet.free_stream_capacity(eng) == 0


def test_free_stream_capacity_is_zero_for_a_request_that_never_fits():
    eng = _stub_engine(max_slots=4, num_pages=8, max_seq=32, page_size=8)
    assert eng.free_slots == 4 and eng.free_pages == 7
    assert not eng.fits(30, 16)  # 46 rows > max_seq
    assert fleet.free_stream_capacity(eng, prompt_len=30, max_new=16) == 0
    assert fleet.free_stream_capacity(eng, prompt_len=8, max_new=8) > 0


# ---------------------------------------------------------------------------
# publish cadence
# ---------------------------------------------------------------------------


class _FleetNode:
    def __init__(self):
        self.digests = []

    def report_engine_state(self, digest):
        self.digests.append(digest)


def test_digest_publisher_honors_cadence():
    eng = _stub_engine()
    node = _FleetNode()
    now = [100.0]
    pub = fleet.DigestPublisher(
        node, eng, model_id="stub", interval_s=2.0, clock=lambda: now[0]
    )
    assert pub.tick()            # first tick publishes immediately
    assert not pub.tick()        # same instant: cadence not elapsed
    now[0] += 1.9
    assert not pub.tick()
    now[0] += 0.2
    assert pub.tick()
    assert [d.seq for d in node.digests] == [1, 2]
    assert node.digests[0].unix_ts <= node.digests[1].unix_ts


def test_digest_publisher_disabled_paths():
    eng = _stub_engine()
    # cadence 0 = the plane is off (the A/B bench's off arm)
    off = fleet.DigestPublisher(_FleetNode(), eng, interval_s=0)
    assert not off.enabled and not off.tick()

    class _NoFleetNode:
        pass

    legacy = fleet.DigestPublisher(_NoFleetNode(), eng, interval_s=1.0)
    assert not legacy.enabled and not legacy.tick()


def test_digest_publisher_survives_a_failing_node():
    class _Boom:
        def report_engine_state(self, digest):
            raise RuntimeError("daemon gone")

    pub = fleet.DigestPublisher(
        _Boom(), _stub_engine(), interval_s=1.0, clock=lambda: 0.0
    )
    assert pub.tick() is False  # swallowed: fleet state is best-effort


def test_interval_env_parsing(monkeypatch):
    monkeypatch.setenv(fleet.DIGEST_INTERVAL_ENV, "0.5")
    assert fleet.digest_interval_s() == 0.5
    assert fleet.stale_after_s() == 1.5
    monkeypatch.setenv(fleet.DIGEST_INTERVAL_ENV, "bogus")
    assert fleet.digest_interval_s() == fleet.DEFAULT_DIGEST_INTERVAL_S


# ---------------------------------------------------------------------------
# merge: HLC skew, staleness, collisions
# ---------------------------------------------------------------------------


def _snap(machine, wall_ns, hlc_ns, replicas):
    return {
        "machine_id": machine, "wall_ns": wall_ns, "hlc_ns": hlc_ns,
        "replicas": replicas,
    }


def _entry(recv_wall_ns, **digest):
    digest.setdefault("page_size", 4)
    digest.setdefault("prefixes", [])
    digest.setdefault("total_pages", 10)
    digest.setdefault("used_pages", 0)
    return {**digest, "recv_wall_ns": recv_wall_ns}


def test_merge_ages_are_skew_free():
    """Machine B's wall clock lags 500 s behind the HLC axis. Its
    replica's digest is 1 s old BY B'S OWN CLOCK — the merge must
    report ~1 s, not 501, because age is computed against the local
    wall pair while t_ns is aligned through the HLC offset."""
    base = 1_000 * G
    skew = 500 * G
    merged = fleet.merge_fleet_snapshots([
        _snap("A", base, base, {"llm-a": _entry(base - 2 * G)}),
        _snap("B", base - skew, base, {"llm-b": _entry(base - skew - G)}),
    ])
    reps = merged["replicas"]
    assert reps["llm-a"]["age_s"] == 2.0
    assert reps["llm-b"]["age_s"] == 1.0
    # both receive stamps land on the SAME cluster axis
    assert reps["llm-b"]["t_ns"] == base - G
    assert reps["llm-a"]["t_ns"] == base - 2 * G
    assert merged["machines"] == ["A", "B"]


def test_merge_collision_keeps_the_newer_digest():
    base = 1_000 * G
    older = _entry(base - 5 * G, free_streams=1)
    newer = _entry(base - G, free_streams=7)
    merged = fleet.merge_fleet_snapshots([
        _snap("A", base, base, {"llm": older}),
        _snap("B", base, base, {"llm": newer}),
    ])
    assert merged["replicas"]["llm"]["free_streams"] == 7


def test_merge_tolerates_empty_and_junk_snapshots():
    assert fleet.merge_fleet_snapshots([]) == {
        "replicas": {}, "machines": [], "t_ns": 0,
    }
    merged = fleet.merge_fleet_snapshots([{}, None, "bogus"])
    assert merged["replicas"] == {}


# ---------------------------------------------------------------------------
# placement scoring
# ---------------------------------------------------------------------------


def _replica(prompt=None, page_size=4, cached_pages=0, used=0, total=10,
             age=0.0, free_streams=4, adapter=None):
    prefixes = []
    if prompt is not None and cached_pages:
        prefixes = [
            [chain, tlen, i + 1]
            for i, (chain, tlen) in enumerate(
                prompt_hash_chain(prompt, page_size, adapter)[:cached_pages]
            )
        ]
    return {
        "page_size": page_size, "prefixes": prefixes,
        "used_pages": used, "total_pages": total, "age_s": age,
        "free_streams": free_streams, "fingerprint": "f" * 16,
    }


PROMPT = list(range(1, 17))  # 4 pages of 4


def test_longest_cached_prefix_wins():
    ranked = fleet.score_placement(PROMPT, None, {
        "cold": _replica(),
        "warm2": _replica(PROMPT, cached_pages=2),
        "warm4": _replica(PROMPT, cached_pages=4),
    }, stale_after=6.0)
    assert [e["replica"] for e in ranked] == ["warm4", "warm2", "cold"]
    assert ranked[0]["matched_tokens"] == 16
    assert ranked[1]["matched_tokens"] == 8
    assert ranked[2]["score"] == 0.0


def test_occupancy_breaks_score_ties_then_replica_id():
    ranked = fleet.score_placement(PROMPT, None, {
        "busy": _replica(PROMPT, cached_pages=2, used=9),
        "idle": _replica(PROMPT, cached_pages=2, used=1),
    }, stale_after=6.0)
    assert [e["replica"] for e in ranked] == ["idle", "busy"]
    # full tie: deterministic by replica id
    ranked = fleet.score_placement(PROMPT, None, {
        "b": _replica(), "a": _replica(), "c": _replica(),
    }, stale_after=6.0)
    assert [e["replica"] for e in ranked] == ["a", "b", "c"]


def test_staleness_discounts_a_cached_claim_to_zero():
    """A fresh empty replica must beat one whose big cache claim is
    older than the staleness bound — a stale digest is a guess."""
    ranked = fleet.score_placement(PROMPT, None, {
        "stale": _replica(PROMPT, cached_pages=4, age=6.0, used=0),
        "fresh": _replica(PROMPT, cached_pages=1, age=0.0, used=5),
    }, stale_after=6.0)
    assert ranked[0]["replica"] == "fresh"
    assert ranked[1]["score"] == 0.0
    # halfway to the bound: linear discount
    half = fleet.score_placement(PROMPT, None, {
        "r": _replica(PROMPT, cached_pages=4, age=3.0),
    }, stale_after=6.0)
    assert half[0]["score"] == pytest.approx(8.0)


def test_adapter_mismatch_never_matches():
    ranked = fleet.score_placement(PROMPT, "tenant-b", {
        "base": _replica(PROMPT, cached_pages=4, adapter=None),
    }, stale_after=6.0)
    assert ranked[0]["matched_tokens"] == 0


def test_mixed_page_sizes_hash_per_replica():
    ranked = fleet.score_placement(PROMPT, None, {
        "ps4": _replica(PROMPT, page_size=4, cached_pages=2),
        "ps8": _replica(PROMPT, page_size=8, cached_pages=1),
    }, stale_after=6.0)
    by_id = {e["replica"]: e for e in ranked}
    assert by_id["ps4"]["matched_tokens"] == 8
    assert by_id["ps8"]["matched_tokens"] == 8


# ---------------------------------------------------------------------------
# daemon gauges + flattened series + surfaces
# ---------------------------------------------------------------------------


def test_fleet_gauges_and_flatten():
    from dora_tpu.metrics_history import flatten_snapshot

    g = fleet.fleet_gauges(
        {"free_streams": 3, "used_pages": 6, "total_pages": 8,
         "prefix_pages": 2, "seq": 9},
        age_s=1.25,
    )
    assert g["occupancy"] == 0.75 and g["digest_age_s"] == 1.25
    _counters, gauges, _hists = flatten_snapshot({"fleet": {"llm": g}})
    assert gauges["fleet:llm:digest_age_s"] == 1.25
    assert gauges["fleet:llm:occupancy"] == 0.75
    assert gauges["fleet:llm:free_streams"] == 3


def test_default_pack_has_fleet_digest_stale_rule():
    from dora_tpu.alerts import default_rule_pack, selector_class

    rules = {r.name: r for r in default_rule_pack()}
    r = rules["fleet-digest-stale"]
    assert r.selector == "fleet:*:digest_age_s"
    assert r.threshold == fleet.stale_after_s()
    assert selector_class("fleet:llm:digest_age_s") == "gauge"
    assert selector_class("fleet:llm:occupancy") == "gauge"
    assert selector_class("fleet:llm:bogus") is None


def test_fleet_prom_families_render():
    from dora_tpu.prom import render_exposition, validate_exposition

    snap = {"fleet": {"llm": fleet.fleet_gauges(
        {"free_streams": 2, "used_pages": 4, "total_pages": 8,
         "prefix_pages": 3, "seq": 1}, age_s=0.5,
    )}}
    text = render_exposition({"demo": snap})
    assert validate_exposition(text) == []
    assert 'dora_fleet_digest_age_s{dataflow="demo",node="llm"} 0.5' in text
    assert 'dora_fleet_occupancy{dataflow="demo",node="llm"} 0.5' in text


def test_fleet_digest_is_a_registered_instant():
    from dora_tpu.tracing import INSTANT_NAMES

    assert "fleet_digest" in INSTANT_NAMES


def test_render_fleet_and_panel_tolerate_partial_data():
    from dora_tpu.cli.fleet_view import render_fleet, render_fleet_panel

    # pre-fleet snapshot: no replicas at all
    text = render_fleet("uuid-1", {})
    assert "no engine digests" in text
    # a replica dict missing every new field renders dashes, not a crash
    text = render_fleet("uuid-1", {"replicas": {"llm": {}}})
    assert "llm" in text and "-" in text
    assert render_fleet_panel({}) == []
    panel = render_fleet_panel({"llm": {}})
    assert any("llm" in line for line in panel)
    assert any("-" in line for line in panel)


def test_top_view_fleet_panel_and_backward_compat():
    from dora_tpu.cli.top_view import render_top

    history = {"samples": [], "rates": {}, "percentiles": {}}
    snap = {"fleet": {"llm": fleet.fleet_gauges(
        {"free_streams": 2, "used_pages": 4, "total_pages": 8,
         "prefix_pages": 3, "seq": 1}, age_s=0.4,
    )}}
    out = render_top("u", snap, history)
    assert "FLEET" in out and "4/8" in out and "50%" in out
    # Pre-fleet snapshot (older daemon): the panel drops out entirely
    # instead of fabricating zeros — the UTIL-panel convention.
    assert "FLEET" not in render_top("u", {}, history)


def test_render_fleet_groups_interchangeable_replicas():
    from dora_tpu.cli.fleet_view import render_fleet

    d = {"fingerprint": "aa" * 8, "model_id": "m", "window": 2,
         "spec_k": 0, "kv_dtype": "fp", "weight_bits": 16,
         "free_streams": 1, "used_pages": 0, "total_pages": 4,
         "prefix_pages": 0, "prefixes": [], "adapters": [], "age_s": 0.1,
         "machine": "A"}
    text = render_fleet("u", {"replicas": {"r1": dict(d), "r2": dict(d)},
                              "machines": ["A"]})
    assert "interchangeable: r1, r2" in text


# ---------------------------------------------------------------------------
# graphcheck: replica identity and routability
# ---------------------------------------------------------------------------


def _parse(spec):
    from dora_tpu.core.descriptor import Descriptor

    return Descriptor.parse(spec)


def _llm(nid, extra_env=None, **node):
    return {
        "id": nid,
        "path": "module:dora_tpu.nodehub.llm_server",
        "inputs": {"text": "router/text"},
        "outputs": ["response"],
        "env": {"DORA_STUB_ENGINE": "1", **(extra_env or {})},
        **node,
    }


def _router():
    return {"id": "router", "path": "router.py", "outputs": ["text"]}


def test_graphcheck_flags_unrouted_interchangeable_replicas():
    from dora_tpu.analysis.graphcheck import check_descriptor

    spec = {"nodes": [
        _router(),
        _llm("llm-a"),
        {**_llm("llm-b"), "inputs": {"text": "other/text"}},
        {"id": "other", "path": "other.py", "outputs": ["text"]},
    ]}
    codes = [f.code for f in check_descriptor(_parse(spec))]
    assert "graph-fleet-unrouted" in codes
    f = next(f for f in check_descriptor(_parse(spec))
             if f.code == "graph-fleet-unrouted")
    assert f.level == "warning"
    assert f.detail["replicas"] == ["llm-a", "llm-b"]


def test_graphcheck_routed_or_different_config_is_clean():
    from dora_tpu.analysis.graphcheck import check_descriptor

    # one upstream fans out to both replicas: routed, no finding
    spec = {"nodes": [_router(), _llm("llm-a"), _llm("llm-b")]}
    assert not [f for f in check_descriptor(_parse(spec))
                if f.code == "graph-fleet-unrouted"]
    # different configs: not interchangeable, no finding
    spec = {"nodes": [
        _router(),
        _llm("llm-a"),
        {**_llm("llm-b", extra_env={"DORA_MULTISTEP_K": "2"}),
         "inputs": {"text": "other/text"}},
        {"id": "other", "path": "other.py", "outputs": ["text"]},
    ]}
    assert not [f for f in check_descriptor(_parse(spec))
                if f.code == "graph-fleet-unrouted"]


def test_graphcheck_errors_on_duplicate_replica_id():
    """Descriptor.parse rejects duplicate ids up front, but graphcheck
    must also hold its own line (a descriptor assembled another way —
    merged fragments, programmatic construction — still reaches it)."""
    import dataclasses

    from dora_tpu.analysis.graphcheck import _fleet

    d = _parse({"nodes": [_router(), _llm("llm-a")]})
    dup = dataclasses.replace(d, nodes=d.nodes + (d.nodes[-1],))
    findings = [f for f in _fleet(dup)
                if f.code == "graph-fleet-duplicate-replica"]
    assert len(findings) == 1 and findings[0].level == "error"


# ---------------------------------------------------------------------------
# e2e: two daemons, disjoint warmed prefixes, QueryFleet -> placement
# ---------------------------------------------------------------------------


WARM_CLIENT = textwrap.dedent(
    """
    import os
    import pyarrow as pa
    from dora_tpu.node import Node

    node = Node()
    node.send_output(
        "text", pa.array([os.environ["WARM_PROMPT"]]),
        {"request_id": "warm", "max_new_tokens": 2},
    )
    node.close()
    """
)

# Long enough for 3 full stub pages (page_size 8) and fully disjoint
# from the first token on, so each replica's radix tree shares nothing.
PROMPT_A = "aaaaaaaabbbbbbbbcccccccc"
PROMPT_B = "zzzzzzzzyyyyyyyyxxxxxxxx"


def _stub_encode(text):
    return [ord(ch) % 97 for ch in text] or [1]  # llm_server stub encode


def _fleet_spec() -> dict:
    def leg(suffix, prompt, machine):
        env = {
            "DORA_STUB_ENGINE": "1",
            "DORA_MULTISTEP_K": "2",
            "DORA_BATCH_SLOTS": "2",
            "DORA_MAX_NEW_TOKENS": "4",
            "DORA_FLEET_DIGEST_S": "0.2",
            "JAX_PLATFORMS": "cpu",
        }
        return [
            {
                "id": f"client-{suffix}",
                "path": "warm_client.py",
                "outputs": ["text"],
                "env": {"WARM_PROMPT": prompt},
                "deploy": {"machine": machine},
            },
            {
                "id": f"llm-{suffix}",
                "path": "module:dora_tpu.nodehub.llm_server",
                "inputs": {"text": f"client-{suffix}/text"},
                "outputs": ["response"],
                "env": env,
                "deploy": {"machine": machine},
            },
        ]

    return {"nodes": leg("a", PROMPT_A, "A") + leg("b", PROMPT_B, "B")}


@pytest.mark.slow
def test_fleet_e2e_places_prompts_on_the_warm_replica(tmp_path):
    pytest.importorskip("jax")
    from dora_tpu.coordinator import Coordinator
    from dora_tpu.daemon.core import Daemon
    from dora_tpu.message import coordinator as cm
    from tests.test_coordinator_multidaemon import (
        _wait_finished,
        _wait_machines,
    )

    (tmp_path / "warm_client.py").write_text(WARM_CLIENT)

    async def main():
        coord = Coordinator()
        await coord.start()
        addr = f"127.0.0.1:{coord.daemon_port}"
        daemon_a, daemon_b = Daemon(), Daemon()
        tasks = [
            asyncio.create_task(daemon_a.run(addr, "A")),
            asyncio.create_task(daemon_b.run(addr, "B")),
        ]
        try:
            await _wait_machines(coord, {"A", "B"})
            start = await coord.handle_control_request(
                cm.Start(
                    dataflow=_fleet_spec(),
                    name="fleet",
                    local_working_dir=str(tmp_path),
                )
            )
            assert isinstance(start, cm.DataflowStarted), start
            result = await _wait_finished(coord, start.uuid)
            assert result.is_ok(), result.errors()

            reply = await coord.handle_control_request(
                cm.QueryFleet(dataflow_uuid=start.uuid)
            )
            assert isinstance(reply, cm.FleetReply), reply
            return reply.fleet
        finally:
            await coord.handle_control_request(cm.Destroy())
            for t in tasks:
                t.cancel()
            await coord.close()

    fleet_view = asyncio.run(main())
    replicas = fleet_view["replicas"]
    assert set(replicas) == {"llm-a", "llm-b"}
    assert set(fleet_view["machines"]) == {"A", "B"}
    for rid in replicas:
        d = replicas[rid]
        assert d["prefixes"], f"{rid} published no cached prefixes"
        assert d["fingerprint"] == replicas["llm-a"]["fingerprint"]
        assert d["seq"] >= 1 and d["age_s"] >= 0

    # Placement is deterministic and prefix-aware: each warm prompt
    # routes to the replica that served it; both orders agree.
    for prompt, want in ((PROMPT_A, "llm-a"), (PROMPT_B, "llm-b")):
        ranked = fleet.score_placement(
            _stub_encode(prompt), None, replicas, stale_after=3600.0
        )
        assert ranked[0]["replica"] == want, ranked
        assert ranked[0]["matched_tokens"] >= 16
        again = fleet.score_placement(
            _stub_encode(prompt), None, replicas, stale_after=3600.0
        )
        assert [e["replica"] for e in again] == [
            e["replica"] for e in ranked
        ]
