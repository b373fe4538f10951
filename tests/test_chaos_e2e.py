"""Fault-injection e2e: kill -9 a serving node mid-generation and assert
the respawned engine resumes from its checkpoint with byte-identical
client-visible output; drain-and-migrate a live KV stream between two
engines under one contiguous trace id. Chaos legs run with deterministic
seeds and hard timeouts (tier-1: the ``chaos`` marker is informational,
not excluded)."""

from __future__ import annotations

import asyncio
import json
import random
import textwrap

import pytest

import dora_tpu.telemetry as tel
from dora_tpu.telemetry import trace_id_of
from dora_tpu.tracing import to_chrome_trace, validate_chrome_trace
from tests.test_checkpoint_resume import _expected_text

pytestmark = pytest.mark.chaos

#: one seed for every chaos leg: respawn backoff jitter (in-process
#: daemon) and any strike-time jitter draw from the same deterministic
#: stream, so a failing run replays exactly.
CHAOS_SEED = 0x5EED


# Dedups response chunks by (request_id, seq) FIRST-wins — the consumer
# contract that turns at-least-once crash replay into byte-identical
# streams — and journals every fresh chunk to a progress file the test
# polls to time its kill.
SINK = textwrap.dedent(
    """
    import json, os
    from dora_tpu.node import Node

    out_path = os.environ["SINK_OUT"]
    progress = out_path + ".progress"
    seen = {}
    with Node() as node:
        for event in node:
            if event["type"] == "STOP":
                break
            if event["type"] != "INPUT":
                continue
            meta = event["metadata"] or {}
            rid = meta.get("request_id")
            if rid is None:
                continue
            key = (rid, int(meta.get("seq", 0)))
            if key in seen:
                continue
            seen[key] = event["value"].to_pylist()[0]
            with open(progress, "a") as f:
                print(json.dumps([rid, key[1], bool(meta.get("done"))]),
                      file=f, flush=True)
    texts = {}
    for (rid, seq) in sorted(seen):
        texts[rid] = texts.get(rid, "") + seen[(rid, seq)]
    open(out_path, "w").write(json.dumps(texts))
    """
)


async def _wait_lines(path, minimum: int, deadline_s: float) -> list[str]:
    loop = asyncio.get_running_loop()
    deadline = loop.time() + deadline_s
    while True:
        lines = []
        if path.exists():
            lines = [l for l in path.read_text().splitlines() if l.strip()]
        if len(lines) >= minimum:
            return lines
        assert loop.time() < deadline, f"stalled waiting for {path}"
        await asyncio.sleep(0.05)


def _llm_env(**extra) -> dict:
    env = {
        "DORA_TRACING": "1",
        "DORA_STUB_ENGINE": "1",
        "DORA_MULTISTEP_K": "2",
        "DORA_BATCH_SLOTS": "2",
        "DORA_MAX_NEW_TOKENS": "12",
        "JAX_PLATFORMS": "cpu",
    }
    env.update(extra)
    return env


# ---------------------------------------------------------------------------
# kill -9 mid-generation -> respawn -> checkpoint resume, byte-identical
# ---------------------------------------------------------------------------


def test_kill9_mid_generation_resumes_byte_identical(tmp_path, monkeypatch):
    from dora_tpu.coordinator import Coordinator
    from dora_tpu.daemon.core import Daemon
    from dora_tpu.message import coordinator as cm
    from dora_tpu.tools import chaos
    from tests.test_trace import _wait_finished, _wait_machines

    random.seed(CHAOS_SEED)
    monkeypatch.setenv("DORA_P2P", "0")
    monkeypatch.setenv("DORA_TRACING", "1")
    tel.TRACING.configure_from_env()
    tel.FLIGHT.configure_from_env()
    tel.FLIGHT.clear()

    client = textwrap.dedent(
        """
        import pyarrow as pa
        from dora_tpu.node import Node

        node = Node()
        for i, text in enumerate(["hi there", "ok go"]):
            node.send_output(
                "text", pa.array([text]),
                {"request_id": f"r{i}", "max_new_tokens": 12},
            )
        node.close()
        """
    )
    (tmp_path / "client.py").write_text(client)
    (tmp_path / "sink.py").write_text(SINK)
    sink_out = tmp_path / "sink_out.json"
    ckpt_dir = tmp_path / "ckpt"
    spec = {
        "nodes": [
            {"id": "client", "path": "client.py", "outputs": ["text"],
             "env": {"DORA_TRACING": "1"}},
            {
                "id": "llm",
                "path": "module:dora_tpu.nodehub.llm_server",
                "inputs": {"text": "client/text"},
                "outputs": ["response"],
                # 12 tokens at K=2 are 6 windows: at 0.5 s each the
                # generation lasts 3 s, so the strike below (>= 4 chunks
                # seen, pid found, checkpoint on disk — each a poll on a
                # machine that may be loaded) still lands mid-generation.
                # At 0.1 s the whole generation was 0.6 s and a slow poll
                # struck after it had finished.
                "env": _llm_env(
                    DORA_STEP_DELAY_S="0.5",
                    DORA_CHECKPOINT_DIR=str(ckpt_dir),
                    DORA_CHECKPOINT_EVERY="1",
                ),
                "restart": {"max_attempts": 2, "backoff_base_s": 0.05,
                            "backoff_max_s": 0.2},
            },
            {
                "id": "sink",
                "path": "sink.py",
                "inputs": {"resp": "llm/response"},
                "env": {"DORA_TRACING": "1", "SINK_OUT": str(sink_out)},
            },
        ]
    }
    progress = tmp_path / "sink_out.json.progress"

    async def main():
        coord = Coordinator()
        await coord.start()
        daemon = Daemon()
        task = asyncio.create_task(
            daemon.run(f"127.0.0.1:{coord.daemon_port}", "A")
        )
        try:
            await _wait_machines(coord, {"A"})
            start = await coord.handle_control_request(
                cm.Start(dataflow=spec, name=None,
                         local_working_dir=str(tmp_path))
            )
            assert isinstance(start, cm.DataflowStarted), start

            # Strike window: generation underway (>= 4 deduped chunks
            # landed) AND at least one cadence checkpoint on disk — the
            # kill provably hits MID-generation with resumable state.
            await _wait_lines(progress, 4, deadline_s=240)
            await asyncio.wait_for(
                asyncio.get_running_loop().run_in_executor(
                    None,
                    lambda: chaos.wait_for(start.uuid, "llm", timeout_s=30),
                ),
                timeout=40,
            )
            deadline = asyncio.get_running_loop().time() + 60
            while not (ckpt_dir / "state.json").exists():
                assert asyncio.get_running_loop().time() < deadline
                await asyncio.sleep(0.05)
            struck = chaos.kill(chaos.find_pids(start.uuid, "llm"))
            assert struck, "chaos found no llm pid to kill"

            result = await _wait_finished(coord, start.uuid, timeout=300)
            assert result.is_ok(), result.errors()

            mreply = await coord.handle_control_request(
                cm.QueryMetrics(dataflow_uuid=start.uuid)
            )
            assert isinstance(mreply, cm.MetricsReply), mreply
            treply = await coord.handle_control_request(
                cm.QueryTrace(dataflow_uuid=start.uuid)
            )
            assert isinstance(treply, cm.TraceReply), treply
            return mreply.metrics, treply.trace
        finally:
            await coord.handle_control_request(cm.Destroy())
            task.cancel()
            await coord.close()

    metrics, trace = asyncio.run(asyncio.wait_for(main(), timeout=420))

    # Byte-identical client-visible streams despite the kill.
    texts = json.loads(sink_out.read_text())
    assert texts == {
        "r0": _expected_text("hi there", 12),
        "r1": _expected_text("ok go", 12),
    }

    # Recovery reached the metrics plane (and the CLI table renders it).
    assert (metrics.get("recovery") or {}).get("respawns") == {"llm": 1}
    s = (metrics.get("serving") or {}).get("llm") or {}
    assert s.get("checkpoints", 0) >= 1
    assert s.get("restored_streams", 0) >= 1
    from dora_tpu.cli.metrics_view import render_metrics

    rendered = render_metrics("test-uuid", metrics)
    assert "RECOVERY" in rendered and "RESPAWNS" in rendered

    # Recovery reached the trace timeline, and the export still passes
    # the `dora-tpu trace --check` validator.
    procs = {p["process"]: p["events"] for p in trace["processes"]}
    llm_kinds = {e[2] for e in procs.get("llm", [])}
    assert "s_checkpoint" in llm_kinds, sorted(llm_kinds)
    assert "s_restore" in llm_kinds, sorted(llm_kinds)
    daemon_kinds = {e[2] for e in procs.get("(daemon)", [])}
    assert "node_respawn" in daemon_kinds, sorted(daemon_kinds)
    assert validate_chrome_trace(to_chrome_trace(trace)) == []


# ---------------------------------------------------------------------------
# drain and migrate: live stream moves engines under ONE trace id
# ---------------------------------------------------------------------------


def test_drain_and_migrate_live_stream(tmp_path, monkeypatch):
    from dora_tpu.coordinator import Coordinator
    from dora_tpu.daemon.core import Daemon
    from dora_tpu.message import coordinator as cm
    from tests.test_trace import _wait_machines

    random.seed(CHAOS_SEED)
    monkeypatch.setenv("DORA_P2P", "0")
    monkeypatch.setenv("DORA_TRACING", "1")
    tel.TRACING.configure_from_env()
    tel.FLIGHT.configure_from_env()
    tel.FLIGHT.clear()

    # The client stays alive until STOP (timer-held) so every input
    # stream stays open across the migration; "hold" exists only to give
    # llm-b an input edge and never fires.
    client = textwrap.dedent(
        """
        import pyarrow as pa
        from dora_tpu.node import Node

        with Node() as node:
            sent = False
            for event in node:
                if event["type"] == "STOP":
                    break
                if not sent:
                    node.send_output(
                        "text", pa.array(["hi there"]),
                        {"request_id": "r0", "max_new_tokens": 12},
                    )
                    sent = True
        """
    )
    (tmp_path / "client.py").write_text(client)
    (tmp_path / "sink.py").write_text(SINK)
    sink_out = tmp_path / "sink_out.json"
    handoff = tmp_path / "handoff"
    spec = {
        "nodes": [
            {
                "id": "client",
                "path": "client.py",
                "inputs": {"tick": "dora/timer/millis/100"},
                "outputs": ["text", "hold"],
                "env": {"DORA_TRACING": "1"},
            },
            {
                "id": "llm-a",
                "path": "module:dora_tpu.nodehub.llm_server",
                "inputs": {"text": "client/text"},
                "outputs": ["response"],
                "env": _llm_env(DORA_STEP_DELAY_S="0.1"),
            },
            {
                "id": "llm-b",
                "path": "module:dora_tpu.nodehub.llm_server",
                "inputs": {"hold": "client/hold"},
                "outputs": ["response"],
                "env": _llm_env(DORA_MIGRATE_DIR=str(handoff)),
            },
            {
                "id": "sink",
                "path": "sink.py",
                "inputs": {"a": "llm-a/response", "b": "llm-b/response"},
                "env": {"DORA_TRACING": "1", "SINK_OUT": str(sink_out)},
            },
        ]
    }
    progress = tmp_path / "sink_out.json.progress"

    async def main():
        coord = Coordinator()
        await coord.start()
        daemon = Daemon()
        task = asyncio.create_task(
            daemon.run(f"127.0.0.1:{coord.daemon_port}", "A")
        )
        try:
            await _wait_machines(coord, {"A"})
            start = await coord.handle_control_request(
                cm.Start(dataflow=spec, name=None,
                         local_working_dir=str(tmp_path))
            )
            assert isinstance(start, cm.DataflowStarted), start

            # Mid-generation: at least 2 chunks out of llm-a.
            await _wait_lines(progress, 2, deadline_s=240)
            migrated = await asyncio.wait_for(
                coord.handle_control_request(
                    cm.MigrateNode(
                        dataflow_uuid=start.uuid,
                        node_id="llm-a",
                        handoff_dir=str(handoff),
                    )
                ),
                timeout=30,
            )
            assert isinstance(migrated, cm.NodeMigrated), migrated

            # llm-b finishes the stream: wait for the done-flagged chunk.
            deadline = asyncio.get_running_loop().time() + 240
            while True:
                lines = await _wait_lines(progress, 1, deadline_s=240)
                if any(json.loads(l)[2] for l in lines):
                    break
                assert asyncio.get_running_loop().time() < deadline
                await asyncio.sleep(0.1)

            stopped = await asyncio.wait_for(
                coord.handle_control_request(
                    cm.StopRequest(dataflow_uuid=start.uuid,
                                   grace_duration_s=10)
                ),
                timeout=120,
            )
            assert isinstance(stopped, cm.DataflowStopped), stopped
            assert stopped.result.is_ok(), stopped.result.errors()

            # Metrics AFTER stop: serve()'s final report (sent at node
            # close) carries the migrated_out/in counters even when the
            # 1 s report cadence never fired post-migration.
            mreply = await coord.handle_control_request(
                cm.QueryMetrics(dataflow_uuid=start.uuid)
            )
            assert isinstance(mreply, cm.MetricsReply), mreply

            treply = await coord.handle_control_request(
                cm.QueryTrace(dataflow_uuid=start.uuid)
            )
            assert isinstance(treply, cm.TraceReply), treply
            return mreply.metrics, treply.trace
        finally:
            await coord.handle_control_request(cm.Destroy())
            task.cancel()
            await coord.close()

    metrics, trace = asyncio.run(asyncio.wait_for(main(), timeout=420))

    # The stream moved engines token-identically: one byte-exact text,
    # assembled from chunks emitted by BOTH engines.
    texts = json.loads(sink_out.read_text())
    assert texts == {"r0": _expected_text("hi there", 12)}

    serving = metrics.get("serving") or {}
    assert (serving.get("llm-a") or {}).get("migrated_out", 0) >= 1
    assert (serving.get("llm-b") or {}).get("migrated_in", 0) >= 1

    # ONE contiguous trace id spans both engines: the id that migrated
    # out of llm-a is the id llm-b admitted and finished under.
    procs = {p["process"]: p["events"] for p in trace["processes"]}

    def _tids(proc: str, kind: str) -> set[str]:
        return {
            trace_id_of(str(e[4] or ""))
            for e in procs.get(proc, [])
            if e[2] == kind and e[4]
        }

    out_tids = _tids("llm-a", "s_migrate_out")
    assert out_tids, {e[2] for e in procs.get("llm-a", [])}
    assert out_tids & _tids("llm-a", "s_admitted")
    assert out_tids & _tids("llm-b", "s_migrate_in")
    assert out_tids & _tids("llm-b", "s_finish")
    assert validate_chrome_trace(to_chrome_trace(trace)) == []
