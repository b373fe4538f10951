"""OpenAI server concurrent mode + continuous-batching responder.

Round 5: N clients hold streaming requests open SIMULTANEOUSLY; chunks
route back per request_id. The reference's proxy serializes requests
through the dataflow (openai-proxy-server/src/main.rs:30-50) — these
tests assert the axis it concedes: concurrent streams with correct
per-request isolation, and (with the real engine) token streams exactly
matching the serial batch-1 reference.
"""

from __future__ import annotations

import textwrap

import pytest
import torch
import yaml

from dora_tpu.daemon import run_dataflow


def test_concurrent_streams_route_by_request_id(tmp_path):
    """3 concurrent streaming clients, one responder that interleaves
    chunks across requests — each client must receive exactly its own
    text."""
    responder = tmp_path / "fanout.py"
    responder.write_text(textwrap.dedent("""
        import pyarrow as pa

        from dora_tpu.node import Node

        # Collect all 3 requests first, then interleave their chunks —
        # chunks for different requests alternate on the wire, so
        # correct delivery PROVES per-request routing.
        pending = []
        with Node() as node:
            for event in node:
                if event["type"] == "STOP":
                    break
                if event["type"] != "INPUT":
                    continue
                meta = event["metadata"] or {}
                pending.append((meta["request_id"],
                                event["value"][0].as_py()))
                if len(pending) < 3:
                    continue
                for i in range(3):  # 3 chunks each, round-robin
                    for rid, text in pending:
                        node.send_output(
                            "reply",
                            pa.array([f"{text.upper()}-{i}"]),
                            {"request_id": rid, "done": i == 2},
                        )
                pending.clear()
    """))
    driver = tmp_path / "driver.py"
    driver.write_text(textwrap.dedent("""
        import json
        import threading
        import time
        import urllib.request

        from dora_tpu.node import Node

        node = Node()
        time.sleep(0.5)
        results = {}

        def ask(word):
            body = json.dumps({
                "stream": True,
                "messages": [{"role": "user", "content": word}],
            }).encode()
            req = urllib.request.Request(
                "http://127.0.0.1:8133/v1/chat/completions",
                data=body, headers={"Content-Type": "application/json"},
            )
            for attempt in range(40):
                try:
                    with urllib.request.urlopen(req, timeout=30) as r:
                        raw = r.read().decode()
                    break
                except Exception:
                    time.sleep(0.25)
            deltas = [
                json.loads(line[6:])["choices"][0]["delta"]
                for line in raw.splitlines()
                if line.startswith("data: ") and line != "data: [DONE]"
            ]
            results[word] = "".join(d.get("content", "") for d in deltas)

        threads = [
            threading.Thread(target=ask, args=(w,))
            for w in ("alpha", "beta", "gamma")
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for w in ("alpha", "beta", "gamma"):
            want = "".join(f"{w.upper()}-{i}" for i in range(3))
            assert results[w] == want, (w, results[w])
        print("concurrent routing ok")
        node.close()
    """))
    spec = {
        "nodes": [
            {
                "id": "api",
                "path": "module:dora_tpu.nodehub.openai_server",
                "outputs": ["text"],
                "inputs": {"response": "fanout/reply"},
                "env": {
                    "PORT": "8133",
                    "MAX_REQUESTS": "3",
                    "DORA_OPENAI_CONCURRENT": "1",
                    "RESPONSE_TIMEOUT": "60",
                },
            },
            {
                "id": "fanout",
                "path": "fanout.py",
                "inputs": {"text": "api/text"},
                "outputs": ["reply"],
            },
            {"id": "driver", "path": "driver.py"},
        ]
    }
    df = tmp_path / "dataflow.yml"
    df.write_text(yaml.safe_dump(spec))
    result = run_dataflow(df, timeout_s=180)
    assert result.is_ok(), result.errors()
    log_dir = next((tmp_path / "out").iterdir())
    assert "concurrent routing ok" in (log_dir / "log_driver.txt").read_text()


def test_the_front_stamps_a_request_and_times_its_first_delta(tmp_path):
    """Concurrent mode's part of ``telemetry.REQUEST_STAGES``: the
    request's metadata carries ``t_http_ns`` and ``t_publish_ns``; a
    stream's first message, which the responder stamps ``t_emit_ns``, is
    observed once on its way back and once where its delta is flushed;
    and the two histograms come out as a ``front`` line of the node's
    log that the benchmark's ``node_reports.parse`` reads."""
    responder = tmp_path / "stamped.py"
    responder.write_text(textwrap.dedent("""
        import time

        import pyarrow as pa

        from dora_tpu.node import Node

        with Node() as node:
            for event in node:
                if event["type"] == "STOP":
                    break
                if event["type"] != "INPUT":
                    continue
                meta = event["metadata"] or {}
                stamps = [meta["t_http_ns"], meta["t_publish_ns"],
                          time.time_ns()]
                assert all(isinstance(t, int) for t in stamps), stamps
                assert stamps == sorted(stamps), stamps
                assert stamps[2] - stamps[0] < 60e9, stamps
                print("stamps ok", flush=True)
                for seq in range(3):
                    out = {"request_id": meta["request_id"], "seq": seq,
                           "n_tokens": 1, "done": seq == 2}
                    if seq == 0:
                        out["t_emit_ns"] = time.time_ns()
                    node.send_output("reply", pa.array([f"w{seq}"]), out)
    """))
    driver = tmp_path / "driver.py"
    driver.write_text(textwrap.dedent("""
        import json
        import time
        import urllib.request

        from dora_tpu.node import Node

        node = Node()
        time.sleep(0.5)
        # three streams, then one answer in one piece: no delta to flush
        for stream in (True, True, True, False):
            body = json.dumps({
                "stream": stream,
                "messages": [{"role": "user", "content": "hi"}],
            }).encode()
            req = urllib.request.Request(
                "http://127.0.0.1:8139/v1/chat/completions",
                data=body, headers={"Content-Type": "application/json"},
            )
            for attempt in range(40):
                try:
                    with urllib.request.urlopen(req, timeout=30) as r:
                        raw = r.read().decode()
                    break
                except Exception:
                    time.sleep(0.25)
            assert "w0" in raw and "w2" in raw, raw
        print("four answers ok")
        node.close()
    """))
    spec = {
        "nodes": [
            {
                "id": "api",
                "path": "module:dora_tpu.nodehub.openai_server",
                "outputs": ["text"],
                "inputs": {"response": "stamped/reply"},
                "env": {
                    "PORT": "8139",
                    "MAX_REQUESTS": "4",
                    "DORA_OPENAI_CONCURRENT": "1",
                    "RESPONSE_TIMEOUT": "60",
                },
            },
            {
                "id": "stamped",
                "path": "stamped.py",
                "inputs": {"text": "api/text"},
                "outputs": ["reply"],
            },
            {"id": "driver", "path": "driver.py"},
        ]
    }
    df = tmp_path / "dataflow.yml"
    df.write_text(yaml.safe_dump(spec))
    result = run_dataflow(df, timeout_s=180)
    assert result.is_ok(), result.errors()
    log_dir = next((tmp_path / "out").iterdir())
    assert "four answers ok" in (log_dir / "log_driver.txt").read_text()
    assert (log_dir / "log_stamped.txt").read_text().count("stamps ok") == 4

    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "benchmark" / "lib"))
    try:
        import node_reports
    finally:
        sys.path.pop(0)
    lines = node_reports.parse((log_dir / "log_api.txt").read_text())["front"]
    last = lines[-1]
    assert [ln["t_mono"] for ln in lines] == sorted(ln["t_mono"] for ln in lines)
    # every stream's first message came back once; three had a delta to flush
    assert last["stage_route_out_us"]["count"] == 4
    assert last["stage_sse_us"]["count"] == last["requests"] == 3
    for key in ("stage_route_out_us", "stage_sse_us"):
        assert 0 < last[key]["sum_us"] < 60e6 and sum(last[key]["counts"]) == last[key]["count"]


def test_a_response_of_several_tokens_is_one_sse_delta(tmp_path):
    """``llm_server`` sends one ``response`` message per stream per
    flush, holding every token of a window. The HTTP front writes one
    SSE event a message, whatever it holds: a 24-character message is
    ONE delta of 24 characters, and ``seq`` / ``n_tokens`` ride along
    without changing that."""
    responder = tmp_path / "windows.py"
    responder.write_text(textwrap.dedent("""
        import pyarrow as pa

        from dora_tpu.node import Node

        with Node() as node:
            for event in node:
                if event["type"] == "STOP":
                    break
                if event["type"] != "INPUT":
                    continue
                rid = (event["metadata"] or {})["request_id"]
                # a first token alone, a window of eight, the last five
                for seq, n, done in ((0, 1, False), (1, 8, False),
                                     (9, 5, True)):
                    text = "".join(
                        f"t{i:02d}" for i in range(seq, seq + n)
                    )
                    meta = {"request_id": rid, "done": done, "seq": seq,
                            "n_tokens": n}
                    if done:
                        meta["finish"] = "length"
                    node.send_output("reply", pa.array([text]), meta)
    """))
    driver = tmp_path / "driver.py"
    driver.write_text(textwrap.dedent("""
        import json
        import time
        import urllib.request

        from dora_tpu.node import Node

        node = Node()
        time.sleep(0.5)
        body = json.dumps({
            "stream": True,
            "messages": [{"role": "user", "content": "go"}],
        }).encode()
        req = urllib.request.Request(
            "http://127.0.0.1:8137/v1/chat/completions",
            data=body, headers={"Content-Type": "application/json"},
        )
        for attempt in range(40):
            try:
                with urllib.request.urlopen(req, timeout=30) as r:
                    raw = r.read().decode()
                break
            except Exception:
                time.sleep(0.25)
        choices = [
            json.loads(line[6:])["choices"][0]
            for line in raw.splitlines()
            if line.startswith("data: ") and line != "data: [DONE]"
        ]
        contents = [
            c["delta"]["content"] for c in choices
            if c["delta"].get("content")
        ]
        want = ["".join(f"t{i:02d}" for i in range(a, b))
                for a, b in ((0, 1), (1, 9), (9, 14))]
        assert contents == want, contents
        assert [len(c) for c in contents] == [3, 24, 15]
        assert [c["finish_reason"] for c in choices
                if c.get("finish_reason")] == ["length"]
        print("one delta a message ok")
        node.close()
    """))
    spec = {
        "nodes": [
            {
                "id": "api",
                "path": "module:dora_tpu.nodehub.openai_server",
                "outputs": ["text"],
                "inputs": {"response": "windows/reply"},
                "env": {
                    "PORT": "8137",
                    "MAX_REQUESTS": "1",
                    "DORA_OPENAI_CONCURRENT": "1",
                    "RESPONSE_TIMEOUT": "60",
                },
            },
            {
                "id": "windows",
                "path": "windows.py",
                "inputs": {"text": "api/text"},
                "outputs": ["reply"],
            },
            {"id": "driver", "path": "driver.py"},
        ]
    }
    df = tmp_path / "dataflow.yml"
    df.write_text(yaml.safe_dump(spec))
    result = run_dataflow(df, timeout_s=180)
    assert result.is_ok(), result.errors()
    log_dir = next((tmp_path / "out").iterdir())
    assert "one delta a message ok" in (
        log_dir / "log_driver.txt"
    ).read_text()


@pytest.fixture(scope="module")
def tiny_checkpoint(tmp_path_factory):
    from transformers import Qwen2Config, Qwen2ForCausalLM

    config = Qwen2Config(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rope_theta=10000.0,
        rms_norm_eps=1e-6, tie_word_embeddings=False,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = Qwen2ForCausalLM(config).eval()
    path = tmp_path_factory.mktemp("qwen2-llm-server")
    model.save_pretrained(path, safe_serialization=True)
    return path


def test_llm_server_end_to_end_matches_serial(tmp_path, tiny_checkpoint):
    """openai_server(concurrent) + llm_server(batch engine) + 3 parallel
    clients: every stream must equal the serial qwen2.generate tokens
    for its prompt (continuous batching changes latency, not output)."""
    driver = tmp_path / "driver.py"
    driver.write_text(textwrap.dedent(f"""
        import json
        import threading
        import time
        import urllib.request

        import jax.numpy as jnp

        from dora_tpu.node import Node
        from dora_tpu.models import tokenizer as bytecodec
        from dora_tpu.models.hf import qwen2

        import os
        os.environ["DORA_INT8_DECODE"] = "1"
        cfg, params = qwen2.load({str(tiny_checkpoint)!r}, max_seq=64)
        qparams = qwen2.quantize_decode(params, cfg)

        MAX_NEW = 6
        prompts = ["hello", "robot", "dora!"]

        def reference(text):
            ids = [t % cfg.vocab for t in bytecodec.encode(text)]
            out = qwen2.generate(
                qparams, cfg, jnp.asarray([ids], jnp.int32), MAX_NEW
            )
            return "".join(
                bytecodec.decode([int(t)]) for t in out[0]
            )

        refs = {{p: reference(p) for p in prompts}}

        node = Node()
        time.sleep(0.5)
        results = {{}}

        def ask(word):
            body = json.dumps({{
                "stream": True,
                "max_tokens": MAX_NEW,
                "messages": [{{"role": "user", "content": word}}],
            }}).encode()
            req = urllib.request.Request(
                "http://127.0.0.1:8135/v1/chat/completions",
                data=body, headers={{"Content-Type": "application/json"}},
            )
            for attempt in range(120):
                try:
                    with urllib.request.urlopen(req, timeout=120) as r:
                        raw = r.read().decode()
                    break
                except Exception:
                    time.sleep(0.5)
            deltas = [
                json.loads(line[6:])["choices"][0]["delta"]
                for line in raw.splitlines()
                if line.startswith("data: ") and line != "data: [DONE]"
            ]
            results[word] = "".join(d.get("content", "") for d in deltas)

        threads = [threading.Thread(target=ask, args=(p,)) for p in prompts]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for p in prompts:
            assert results[p] == refs[p], (p, results[p], refs[p])
        print("llm e2e ok")
        node.close()
    """))
    spec = {
        "nodes": [
            {
                "id": "api",
                "path": "module:dora_tpu.nodehub.openai_server",
                "outputs": ["text"],
                "inputs": {"response": "llm/response"},
                "env": {
                    "PORT": "8135",
                    "MAX_REQUESTS": "3",
                    "DORA_OPENAI_CONCURRENT": "1",
                    "RESPONSE_TIMEOUT": "120",
                },
            },
            {
                "id": "llm",
                "path": "module:dora_tpu.nodehub.llm_server",
                "inputs": {"text": "api/text"},
                "outputs": ["response"],
                "env": {
                    "DORA_HF_CHECKPOINT": str(tiny_checkpoint),
                    "DORA_MAX_SEQ": "64",
                    "DORA_MAX_NEW_TOKENS": "6",
                    "DORA_BATCH_SLOTS": "3",
                },
            },
            {"id": "driver", "path": "driver.py"},
        ]
    }
    df = tmp_path / "dataflow.yml"
    df.write_text(yaml.safe_dump(spec))
    result = run_dataflow(df, timeout_s=300)
    assert result.is_ok(), result.errors()
    log_dir = next((tmp_path / "out").iterdir())
    assert "llm e2e ok" in (log_dir / "log_driver.txt").read_text()
