"""End-to-end dataflow tests: standalone daemon + spawned node processes.

Mirrors the reference's integration strategy (SURVEY.md §4): example
dataflows driven by the standalone daemon (`dora daemon --run-dataflow`
mode), with assertion-fixture nodes
(examples/echo, node-hub/pyarrow-{sender,assert}).
"""

from __future__ import annotations

import os
import textwrap

import pytest
import yaml

from dora_tpu.daemon import run_dataflow


def write_dataflow(tmp_path, spec: dict) -> str:
    path = tmp_path / "dataflow.yml"
    path.write_text(yaml.safe_dump(spec))
    return str(path)


def sender_assert_spec(data="[1, 2, 3]", count=1, comm=None) -> dict:
    spec = {
        "nodes": [
            {
                "id": "sender",
                "path": "module:dora_tpu.nodehub.pyarrow_sender",
                "outputs": ["data"],
                "env": {"DATA": data, "COUNT": str(count)},
            },
            {
                "id": "receiver",
                "path": "module:dora_tpu.nodehub.pyarrow_assert",
                "inputs": {"in": "sender/data"},
                "env": {"DATA": data, "MIN_COUNT": str(count)},
            },
        ]
    }
    if comm:
        spec["communication"] = {"local": comm}
    return spec


@pytest.mark.parametrize("comm", ["tcp", "uds", "shmem"])
def test_sender_assert_roundtrip(tmp_path, comm):
    path = write_dataflow(tmp_path, sender_assert_spec(comm=comm))
    result = run_dataflow(path, local_comm=comm, timeout_s=60)
    assert result.is_ok(), result.errors()
    log = (tmp_path / "out" / result.uuid / "log_receiver.txt").read_text()
    assert "asserted 1 inputs OK" in log


def test_large_payload_shmem_roundtrip(tmp_path):
    """A >4 KiB payload travels via a shared-memory region and survives the
    zero-copy read intact."""
    data = str(list(range(5000)))  # ~5000-element int array, IPC > 4 KiB
    path = write_dataflow(tmp_path, sender_assert_spec(data=data, count=3))
    result = run_dataflow(path, timeout_s=60)
    assert result.is_ok(), result.errors()
    log = (tmp_path / "out" / result.uuid / "log_receiver.txt").read_text()
    assert "asserted 3 inputs OK" in log


def test_echo_chain(tmp_path):
    """sender -> echo -> assert: two hops preserve the value."""
    spec = {
        "nodes": [
            {
                "id": "sender",
                "path": "module:dora_tpu.nodehub.pyarrow_sender",
                "outputs": ["data"],
                "env": {"DATA": "[7, 8]", "COUNT": "2"},
            },
            {
                "id": "relay",
                "path": "module:dora_tpu.nodehub.echo",
                "inputs": {"in": "sender/data"},
                "outputs": ["echo"],
            },
            {
                "id": "receiver",
                "path": "module:dora_tpu.nodehub.pyarrow_assert",
                "inputs": {"in": "relay/echo"},
                "env": {"DATA": "[7, 8]", "MIN_COUNT": "2"},
            },
        ]
    }
    result = run_dataflow(write_dataflow(tmp_path, spec), timeout_s=60)
    assert result.is_ok(), result.errors()


def test_timer_input(tmp_path):
    """A node fed by a daemon timer receives periodic ticks."""
    script = tmp_path / "ticker.py"
    script.write_text(textwrap.dedent("""
        from dora_tpu.node import Node

        node = Node()
        ticks = 0
        for event in node:
            if event["type"] == "INPUT" and event["id"] == "tick":
                ticks += 1
                if ticks >= 3:
                    break
        node.close()
        print(f"got {ticks} ticks")
    """))
    spec = {
        "nodes": [
            {
                "id": "ticker",
                "path": "ticker.py",
                "inputs": {"tick": "dora/timer/millis/50"},
            }
        ]
    }
    result = run_dataflow(write_dataflow(tmp_path, spec), timeout_s=60)
    assert result.is_ok(), result.errors()
    log = (tmp_path / "out" / result.uuid / "log_ticker.txt").read_text()
    assert "got 3 ticks" in log


def test_queue_size_drop_oldest(tmp_path):
    """queue_size: 1 keeps only the newest event when the receiver is slow
    (reference: daemon-side drop-oldest, node_communication/mod.rs:320-359)."""
    sender = tmp_path / "burst_sender.py"
    sender.write_text(textwrap.dedent("""
        import pyarrow as pa
        from dora_tpu.node import Node

        with Node() as node:
            for i in range(20):
                node.send_output("data", pa.array([i]))
            open("burst_sent", "w").close()
    """))
    receiver = tmp_path / "slow_receiver.py"
    receiver.write_text(textwrap.dedent("""
        import os
        import sys
        import time

        from dora_tpu.node import Node

        node = Node()
        # Let the whole burst arrive and overflow the queue before the
        # first read. Wait for the sender's own word, not for a fixed
        # second: on a loaded machine the sender can take longer than
        # that to get its 20 sends out.
        deadline = time.time() + 30
        while not os.path.exists("burst_sent") and time.time() < deadline:
            time.sleep(0.05)
        time.sleep(0.5)  # routed and queued on the daemon side too
        values = []
        for event in node:
            if event["type"] == "INPUT":
                values.append(event["value"][0].as_py())
        node.close()
        print("received", values)
        # The bound-1 queue keeps only the newest of the backlog; the
        # node-side 2-slot local buffer (EventStream.DEFAULT_MAX_QUEUE,
        # present on both the daemon and the p2p path) may additionally
        # hold up to two early events that arrived before the consumer
        # lagged. Contract under test: bounded delivery, newest wins —
        # never the unbounded 20-event replay.
        assert values[-1] == 19, values
        assert len(values) <= 4, values
    """))
    spec = {
        "nodes": [
            {"id": "sender", "path": "burst_sender.py", "outputs": ["data"]},
            {
                "id": "receiver",
                "path": "slow_receiver.py",
                "inputs": {"data": {"source": "sender/data", "queue_size": 1}},
            },
        ]
    }
    result = run_dataflow(write_dataflow(tmp_path, spec), timeout_s=60)
    assert result.is_ok(), result.errors()


def test_timeout_returns_and_kills_a_wedged_node(tmp_path):
    """A dataflow that passes its time limit with a node that will not
    die politely (wedged in a device call, or ignoring SIGTERM as here)
    must still give the caller back control and leave no process behind:
    the daemon kills stragglers before it waits for its servers to close
    (``Server.wait_closed`` waits for every open connection)."""
    import time

    wedged = tmp_path / "wedged.py"
    wedged.write_text(textwrap.dedent("""
        import os
        import signal
        import time

        from dora_tpu.node import Node

        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        node = Node()
        open("wedged.pid", "w").write(str(os.getpid()))
        time.sleep(600)
    """))
    spec = {"nodes": [{"id": "wedged", "path": "wedged.py"}]}
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        run_dataflow(write_dataflow(tmp_path, spec), timeout_s=5)
    assert time.monotonic() - t0 < 30
    pid = int((tmp_path / "wedged.pid").read_text())
    deadline = time.monotonic() + 10
    while os.path.exists(f"/proc/{pid}") and time.monotonic() < deadline:
        state = open(f"/proc/{pid}/stat").read().split(")")[-1].split()[0]
        if state == "Z":  # killed, not yet reaped
            break
        time.sleep(0.1)
    else:
        assert not os.path.exists(f"/proc/{pid}"), "wedged node survived"


def test_allocate_sample_zero_copy_send(tmp_path):
    """The DataSample producer API: write directly into the shared region,
    publish with no producer-side copy."""
    sender = tmp_path / "sample_sender.py"
    sender.write_text(textwrap.dedent("""
        from dora_tpu.node import Node

        N = 100_000
        with Node() as node:
            sample = node.allocate_sample(N)
            view = sample.view
            view[:N] = bytes(range(256)) * 390 + bytes(160)
            view.release()
            node.send_sample("data", sample, N)
    """))
    receiver = tmp_path / "sample_receiver.py"
    receiver.write_text(textwrap.dedent("""
        from dora_tpu.node import Node

        node = Node()
        seen = 0
        for event in node:
            if event["type"] != "INPUT":
                continue
            data = bytes(event["value"])
            assert data == bytes(range(256)) * 390 + bytes(160)
            seen += 1
        node.close()
        assert seen == 1, seen
        print("sample ok")
    """))
    spec = {
        "nodes": [
            {"id": "sender", "path": "sample_sender.py", "outputs": ["data"]},
            {"id": "receiver", "path": "sample_receiver.py",
             "inputs": {"in": "sender/data"}},
        ],
        "communication": {"local": "shmem"},
    }
    result = run_dataflow(write_dataflow(tmp_path, spec), local_comm="shmem",
                          timeout_s=60)
    assert result.is_ok(), result.errors()


def test_failing_node_reported(tmp_path):
    """A node exiting nonzero is reported with its stderr tail; the dataflow
    result is not ok."""
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        import sys
        from dora_tpu.node import Node

        node = Node()
        print("about to fail", file=sys.stderr)
        sys.exit(3)
    """))
    spec = {"nodes": [{"id": "bad", "path": "bad.py"}]}
    result = run_dataflow(write_dataflow(tmp_path, spec), timeout_s=60)
    assert not result.is_ok()
    [(node_id, error)] = result.errors()
    assert node_id == "bad"
    assert error.exit_status.code == 3
    assert "about to fail" in (error.cause.stderr or "")


def test_send_stdout_as(tmp_path):
    """send_stdout_as republishes a node's stdout as a dataflow output."""
    printer = tmp_path / "printer.py"
    printer.write_text(textwrap.dedent("""
        from dora_tpu.node import Node

        with Node() as node:
            print("hello-dataflow")
    """))
    catcher = tmp_path / "catcher.py"
    catcher.write_text(textwrap.dedent("""
        from dora_tpu.node import Node

        node = Node()
        lines = []
        for event in node:
            if event["type"] == "INPUT":
                lines.append(event["value"][0].as_py())
        node.close()
        assert "hello-dataflow" in lines, lines
    """))
    spec = {
        "nodes": [
            {
                "id": "printer",
                "path": "printer.py",
                "outputs": ["stdout"],
                "send_stdout_as": "stdout",
            },
            {
                "id": "catcher",
                "path": "catcher.py",
                "inputs": {"in": "printer/stdout"},
            },
        ]
    }
    result = run_dataflow(write_dataflow(tmp_path, spec), timeout_s=60)
    assert result.is_ok(), result.errors()
