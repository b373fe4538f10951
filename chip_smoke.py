#!/usr/bin/env python3
"""Prove that the serving path still starts and answers on the chip.

    python chip_smoke.py              # one TPU chip: facts, serve, frames
    python chip_smoke.py --chips 4    # four chips: frames on dp=2,tp=2 vs one device

Drives the system through the entry points a user calls, at the published
widths and full depth of the models the repo serves, with seeded random
weights:

* **facts**  — a plain child on the chip: what device this is, whether
  ``block_until_ready`` synchronizes, dispatch round trip, memory limits,
  the peak-FLOPs table's answer, compile seconds cold and cached.
* **serve**  — dataflow ``driver -> openai_server -> llm_server``
  (``PagedBatchEngine``, Qwen2.5-1.5B-Instruct config, 28 layers): eight
  concurrent streaming chat completions with prompts of 40-700 tokens and
  64 new tokens each, then a resend that hits the prefix cache; after the
  dataflow has exited, a child of its own computes the serial reference
  (``qwen2.generate``) from the same checkpoint and the two are compared.
* **frames** — dataflow ``camera -> make_vlm -> sink`` at the Qwen2-VL-2B
  shape, int8 decode, 4 tokens per frame.

One process per chip: this parent never imports JAX. Every phase that
needs the device is one child at a time — a dataflow whose single JAX
node holds it, or a plain child — and each child says where it ran; the
last line says ``"ok": true`` only if every one of them said ``tpu``.

Every line printed is one JSON object — one per phase (``"passed"``,
seconds, compile seconds, token counts) — and the last is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failure exits non-zero and no line says ``"ok": true``. ``--tiny`` rehearses
the control flow at toy size where ``JAX_PLATFORMS=cpu`` is set — it runs
every phase and still exits non-zero, because no phase ran on a chip.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import socket
import subprocess
import sys
import textwrap
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: Qwen/Qwen2.5-1.5B-Instruct config.json, as published.
QWEN25_1P5B = {
    "architectures": ["Qwen2ForCausalLM"],
    "attention_dropout": 0.0,
    "bos_token_id": 151643,
    "eos_token_id": 151645,
    "hidden_act": "silu",
    "hidden_size": 1536,
    "initializer_range": 0.02,
    "intermediate_size": 8960,
    "max_position_embeddings": 32768,
    "max_window_layers": 21,
    "model_type": "qwen2",
    "num_attention_heads": 12,
    "num_hidden_layers": 28,
    "num_key_value_heads": 2,
    "rms_norm_eps": 1e-06,
    "rope_theta": 1000000.0,
    "sliding_window": 32768,
    "tie_word_embeddings": True,
    "torch_dtype": "bfloat16",
    "transformers_version": "4.43.1",
    "use_cache": True,
    "use_sliding_window": False,
    "vocab_size": 151936,
}
#: --tiny: same family, toy widths, for the CPU rehearsal of control flow.
TINY = {
    **QWEN25_1P5B, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "vocab_size": 512, "rope_theta": 10000.0,
}

MAX_NEW = 64
#: prompt lengths of the first wave; 330 appears twice — the twins.
PROMPT_LENS = (40, 96, 200, 330, 330, 450, 600, 700)
TINY_PROMPT_LENS = (5, 9, 20, 33, 33, 40, 21, 12)
#: wave index resent after the wave, for the prefix-cache hit
RESEND = 1
#: Every distinct prompt of the wave also runs through the serial
#: reference afterwards. --tiny (f32) needs all 64 tokens of all of them.
#: On the chip the logits are bf16: random weights give 151,936
#: near-Gaussian values whose top sits near 3.8, where one bf16 step is
#: 1/64, and the top-1/top-2 gap is within a few steps every 4-10 tokens.
#: Two correct programs that round differently part there (the reference
#: parts from its own teacher-forced logits there). So on the chip: the
#: engine agrees on at least the first MIN_AGREE tokens of at least two
#: prompts, and wherever it parts, the token it chose instead lies within
#: NEAR_TIE_ULPS bf16 steps of the top in the reference's own
#: teacher-forced logits. Measured partings sit at 0-5 steps, and two
#: programs for the same logits differ by 1-2; a wrong token (a fault in
#: a kernel or in the engine) lies some 240 steps down on average.
MIN_AGREE = 8
NEAR_TIE_ULPS = 24

_ALNUM = (
    "0123456789ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz"
)


def emit(obj: dict) -> dict:
    print(json.dumps(obj), flush=True)
    return obj


class PhaseFailed(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# checkpoint + tokenizer, written by the JAX-free parent from --seed
# ---------------------------------------------------------------------------


def token_code(i: int) -> str:
    """Token id -> its fixed 3-character text, and back (code_token): the
    smoke's tokenizer.json maps every id to one, so a prompt's text IS its
    ids and a stream's text reads back as the ids the engine emitted."""
    return _ALNUM[i // 3844] + _ALNUM[i // 62 % 62] + _ALNUM[i % 62]


def code_tokens(text: str) -> list[int]:
    assert len(text) % 3 == 0, len(text)
    return [
        _ALNUM.index(text[j]) * 3844 + _ALNUM.index(text[j + 1]) * 62
        + _ALNUM.index(text[j + 2])
        for j in range(0, len(text), 3)
    ]


def _bf16(x):
    """float32 -> bfloat16, round-to-nearest-even, by bit arithmetic
    (ml_dtypes' astype is ~10x slower at these sizes)."""
    import ml_dtypes
    import numpy as np

    u = x.view(np.uint32)
    u = (u + 0x7FFF + ((u >> 16) & 1)) >> 16
    return u.astype(np.uint16).view(ml_dtypes.bfloat16)


def write_checkpoint(path: Path, config: dict, seed: int) -> float:
    """Seeded random Qwen2 checkpoint (bf16 safetensors, HF names) plus a
    tokenizer.json whose tokens are fixed-width codes. Returns seconds."""
    import numpy as np
    from safetensors.numpy import save_file

    t0 = time.perf_counter()
    path.mkdir(parents=True, exist_ok=True)
    dim = config["hidden_size"]
    ffn = config["intermediate_size"]
    hd = dim // config["num_attention_heads"]
    kv = config["num_key_value_heads"] * hd
    layers = config["num_hidden_layers"]
    vocab = config["vocab_size"]
    amp = 0.02 * 3 ** 0.5  # uniform with the published init's std

    def rand(rng, *shape):
        x = rng.random(shape, dtype=np.float32)
        x -= 0.5
        x *= 2 * amp
        return _bf16(x)

    def layer(args):
        i, seq = args
        rng = np.random.default_rng(seq)
        p = f"model.layers.{i}."
        ones = _bf16(np.ones((dim,), np.float32))
        return {
            p + "input_layernorm.weight": ones,
            p + "post_attention_layernorm.weight": ones,
            p + "self_attn.q_proj.weight": rand(rng, dim, dim),
            p + "self_attn.q_proj.bias": rand(rng, dim),
            p + "self_attn.k_proj.weight": rand(rng, kv, dim),
            p + "self_attn.k_proj.bias": rand(rng, kv),
            p + "self_attn.v_proj.weight": rand(rng, kv, dim),
            p + "self_attn.v_proj.bias": rand(rng, kv),
            p + "self_attn.o_proj.weight": rand(rng, dim, dim),
            p + "mlp.gate_proj.weight": rand(rng, ffn, dim),
            p + "mlp.up_proj.weight": rand(rng, ffn, dim),
            p + "mlp.down_proj.weight": rand(rng, dim, ffn),
        }

    seqs = np.random.SeedSequence(seed).spawn(layers + 1)
    tensors = {
        "model.embed_tokens.weight": rand(
            np.random.default_rng(seqs[-1]), vocab, dim
        ),
        "model.norm.weight": _bf16(np.ones((dim,), np.float32)),
    }
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        for part in pool.map(layer, enumerate(seqs[:-1])):
            tensors.update(part)
    save_file(tensors, str(path / "model.safetensors"))
    (path / "config.json").write_text(json.dumps(config, indent=1))
    (path / "tokenizer.json").write_text(json.dumps({
        "version": "1.0",
        "added_tokens": [],
        "pre_tokenizer": {
            "type": "Split", "pattern": {"Regex": "[0-9A-Za-z]{3}"},
            "behavior": "Isolated", "invert": False,
        },
        "model": {
            "type": "BPE", "ignore_merges": True, "merges": [],
            "vocab": {token_code(i): i for i in range(vocab)},
        },
    }))
    return time.perf_counter() - t0


# ---------------------------------------------------------------------------
# children and their reports
# ---------------------------------------------------------------------------

_REPORT = re.compile(r"dora_tpu\.backend (\w+): (\{.*\})\s*$")


def node_reports(workdir: Path, node: str) -> dict[str, dict]:
    """The ``dora_tpu.backend <kind>: {json}`` lines of one node's log
    (dora_tpu/backend.py:report) from the newest run under ``workdir``."""
    runs = sorted((workdir / "out").iterdir(), key=lambda p: p.stat().st_mtime)
    log = runs[-1] / f"log_{node}.txt"
    out: dict[str, dict] = {}
    for line in log.read_text(errors="replace").splitlines():
        m = _REPORT.search(line)
        if m:
            out[m.group(1)] = json.loads(m.group(2))
    if "device" not in out:
        raise PhaseFailed(f"node {node!r} never said where it ran ({log})")
    return out


def run_child(mode: str, args: list[str], timeout_s: float) -> dict:
    """One plain child on the chip; its last stdout line is its result."""
    cmd = [sys.executable, str(ROOT / "chip_smoke.py"), "--child", mode, *args]
    try:
        proc = subprocess.run(
            cmd, stdout=subprocess.PIPE, text=True, timeout=timeout_s,
            cwd=ROOT,
        )
    except subprocess.TimeoutExpired as e:
        raise PhaseFailed(f"{mode} child passed {timeout_s:.0f}s") from e
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    if proc.returncode != 0 or not lines:
        raise PhaseFailed(f"{mode} child exited {proc.returncode}")
    return json.loads(lines[-1])


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def agreed(a: list[int], b: list[int]) -> int:
    n = 0
    for x, y in zip(a, b):
        if x != y:
            break
        n += 1
    return n


# ---------------------------------------------------------------------------
# phase: facts
# ---------------------------------------------------------------------------


def child_facts(_args) -> dict:
    import statistics

    import jax
    import jax.numpy as jnp

    from dora_tpu import backend, profiling

    cache_dir = backend.init_compile_cache()
    device = backend.require_accelerator("chip_smoke facts")
    cache_entries = (
        len(os.listdir(cache_dir)) if cache_dir and os.path.isdir(cache_dir)
        else 0
    )
    dtype = backend.compute_dtype()
    tiny = device["platform"] != "tpu"
    n, steps = (128, 8) if tiny else (4096, 64)

    # Compile seconds: the same chained-matmul program twice, with jit's
    # in-memory caches dropped in between — the second compile is served
    # by the persistent cache if it works here.
    def chain(x):
        for _ in range(8):
            x = jnp.tanh(x @ x) * 0.5
        return x

    x = jnp.ones((n, n), dtype) * 0.01
    compile_s = []
    for _ in range(2):
        jax.clear_caches()
        t = time.perf_counter()
        compiled = jax.jit(chain).lower(x).compile()
        compile_s.append(time.perf_counter() - t)

    # Does block_until_ready wait for the device? Enqueue `steps` chained
    # programs and time (a) the enqueue alone, (b) enqueue +
    # block_until_ready, (c) enqueue + fetching a value to the host. If
    # it synchronizes, (b) is about (c) and no shorter than the time the
    # chip needs for that many FLOPs at its peak. (The enqueue alone is
    # no yardstick: the runtime bounds the programs in flight, so a long
    # chain's enqueue already waits for part of the work.)
    def timed(sync):
        y = x
        t = time.perf_counter()
        for _ in range(steps):
            y = compiled(y)
        enqueue = time.perf_counter() - t
        sync(y)
        return enqueue, time.perf_counter() - t

    timed(lambda y: float(y[0, 0]))  # warm
    enq_b, block_s = timed(lambda y: y.block_until_ready())
    enq_f, fetch_s = timed(lambda y: float(y[0, 0]))

    empty = jax.jit(lambda: jnp.float32(0))
    float(empty())
    rtt = []
    for _ in range(20):
        t = time.perf_counter()
        float(empty())
        rtt.append(time.perf_counter() - t)

    stats = jax.devices()[0].memory_stats() or {}
    peak = profiling.detect_peak_flops()
    floor_s = steps * 8 * 2 * n ** 3 / peak if peak else 0.0
    ok = bool(tiny or (peak > 0.0 and stats.get("bytes_limit")))
    return {
        "phase": "facts", "passed": ok, "device": device,
        "jax": jax.__version__,
        "compute_dtype": jnp.dtype(dtype).name,
        "pallas_interpret": backend.interpret(),
        "compile_cache_dir": cache_dir,
        "compile_cache_entries_at_start": cache_entries,
        "compile_s_first": round(compile_s[0], 4),
        "compile_s_again_from_cache": round(compile_s[1], 4),
        "chain_steps": steps, "chain_matmul_n": n,
        "enqueue_only_s": round(min(enq_b, enq_f), 5),
        "enqueue_plus_block_until_ready_s": round(block_s, 5),
        "enqueue_plus_host_fetch_s": round(fetch_s, 5),
        "chain_seconds_at_peak_flops": round(floor_s, 5),
        "block_until_ready_synchronizes": bool(
            block_s > 0.9 * fetch_s and block_s > 0.9 * floor_s
        ),
        "empty_dispatch_round_trip_s_median": statistics.median(rtt),
        "memory_stats_keys": sorted(stats),
        "bytes_limit": stats.get("bytes_limit"),
        "detect_peak_flops": peak,
    }


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------

DRIVER = """
import json
import sys
import threading
import time
import urllib.request

from dora_tpu.node import Node

PORT, MAX_NEW = {port}, {max_new}
prompts = json.load(open("prompts.json"))
node = Node()
t_start = time.perf_counter()


def ask(text):
    body = json.dumps({{
        "stream": True, "max_tokens": MAX_NEW,
        "messages": [{{"role": "user", "content": text}}],
    }}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{{PORT}}/v1/chat/completions",
        data=body, headers={{"Content-Type": "application/json"}},
    )
    out = {{"text": "", "finish": None, "first_s": None, "error": None}}
    deadline = time.time() + 120  # the HTTP front comes up in seconds
    while True:
        try:
            with urllib.request.urlopen(req, timeout={timeout}) as r:
                for raw in r:
                    line = raw.decode().strip()
                    if not line.startswith("data: ") or line == "data: [DONE]":
                        continue
                    choice = json.loads(line[6:])["choices"][0]
                    delta = choice["delta"].get("content", "")
                    if delta and out["first_s"] is None:
                        out["first_s"] = time.perf_counter() - t_start
                    out["text"] += delta
                    if choice.get("finish_reason"):
                        out["finish"] = choice["finish_reason"]
            break
        except ConnectionError as e:
            if time.time() > deadline:
                out["error"] = repr(e)
                break
            time.sleep(0.5)
        except Exception as e:
            out["error"] = repr(e)
            break
    out["done_s"] = time.perf_counter() - t_start
    return out


def wave(texts):
    results = [None] * len(texts)

    def run(i):
        results[i] = ask(texts[i])

    threads = [threading.Thread(target=run, args=(i,)) for i in range(len(texts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results


first = wave(prompts["wave"])
# A later resend of one prompt: its pages are in the prefix cache now.
resend = wave([prompts["wave"][prompts["resend"]]])
json.dump({{"wave": first, "resend": resend[0]}}, open("serve_result.json", "w"))
node.close()
"""


def phase_serve(out: Path, args) -> dict:
    import numpy as np
    import yaml

    from dora_tpu.daemon import run_dataflow

    t_phase = time.perf_counter()
    config = TINY if args.tiny else QWEN25_1P5B
    lens = TINY_PROMPT_LENS if args.tiny else PROMPT_LENS
    max_seq = 128 if args.tiny else 2048
    work = out / "serve"
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    ckpt = out / "checkpoint"
    ckpt_s = write_checkpoint(ckpt, config, args.seed)

    rng = np.random.default_rng(args.seed + 1)
    ids = [rng.integers(0, config["vocab_size"], size=n).tolist() for n in lens]
    twins = [i for i, n in enumerate(lens) if lens.count(n) > 1]
    ids[twins[1]] = ids[twins[0]]
    (work / "prompts.json").write_text(json.dumps({
        "wave": ["".join(map(token_code, p)) for p in ids],
        "resend": RESEND,
    }))
    port = free_port()
    (work / "driver.py").write_text(textwrap.dedent(DRIVER).format(
        port=port, max_new=MAX_NEW, timeout=args.timeout,
    ))
    spec = {"nodes": [
        {
            "id": "api",
            "path": "module:dora_tpu.nodehub.openai_server",
            "outputs": ["text"],
            "inputs": {"response": "llm/response"},
            "env": {
                "PORT": str(port), "MAX_REQUESTS": str(len(ids) + 1),
                "DORA_OPENAI_CONCURRENT": "1",
                "RESPONSE_TIMEOUT": str(int(args.timeout)),
            },
        },
        {
            # The one JAX process of this dataflow. Everything but the
            # checkpoint, the sequence length and the token cap is the
            # server's default: paged engine, 16 slots, K=8, prefix cache.
            "id": "llm",
            "path": "module:dora_tpu.nodehub.llm_server",
            "inputs": {"text": "api/text"},
            "outputs": ["response"],
            "env": {
                "DORA_HF_CHECKPOINT": str(ckpt),
                "DORA_MAX_SEQ": str(max_seq),
                "DORA_MAX_NEW_TOKENS": str(MAX_NEW),
            },
        },
        {"id": "driver", "path": "driver.py"},
    ]}
    (work / "dataflow.yml").write_text(yaml.safe_dump(spec))
    t0 = time.perf_counter()
    try:
        result = run_dataflow(work / "dataflow.yml", timeout_s=args.timeout)
    except TimeoutError as e:
        raise PhaseFailed(f"serve dataflow passed {args.timeout:.0f}s") from e
    dataflow_s = time.perf_counter() - t0
    if not result.is_ok():
        raise PhaseFailed(f"serve dataflow failed: {result.errors()}")
    reports = node_reports(work, "llm")
    got = json.loads((work / "serve_result.json").read_text())

    streams = []
    for i, r in enumerate(got["wave"] + [got["resend"]]):
        if r["error"] or r["finish"] is None:
            raise PhaseFailed(f"stream {i} did not finish: {r}")
        tokens = code_tokens(r["text"])
        if len(tokens) != MAX_NEW:
            raise PhaseFailed(
                f"stream {i}: {len(tokens)} tokens, wanted {MAX_NEW}"
            )
        streams.append(tokens)
    wave, resend = streams[:-1], streams[-1]
    if wave[twins[0]] != wave[twins[1]]:
        raise PhaseFailed(
            f"same-wave twin prompts parted after "
            f"{agreed(wave[twins[0]], wave[twins[1]])} tokens"
        )

    # The serial reference — only now, with the dataflow gone and the
    # chip free, in a child of its own, from the same checkpoint.
    referenced = [i for i in range(len(ids)) if i != twins[1]]
    (work / "reference_in.json").write_text(json.dumps({
        "checkpoint": str(ckpt), "max_seq": max_seq, "max_new": MAX_NEW,
        "prompts": [ids[i] for i in referenced],
        "engine": [wave[i] for i in referenced],
    }))
    ref = run_child(
        "reference", [str(work / "reference_in.json")], args.timeout
    )
    parted = [c["divergence"] for c in ref["compared"] if "divergence" in c]
    if args.tiny:
        ok = not parted
    else:
        ok = sum(c["agreed"] >= MIN_AGREE for c in ref["compared"]) >= 2 and all(
            d["engine_token_deficit_bf16_ulps"] <= NEAR_TIE_ULPS
            for d in parted
        )
    return {
        "phase": "serve", "passed": ok,
        "device": reports["device"], "reference_device": ref["device"],
        "model": {k: config[k] for k in (
            "num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "intermediate_size", "vocab_size",
        )},
        "engine": reports["engine_built"]["engine"],
        "streams": len(streams), "prompt_tokens": list(lens),
        "new_tokens_each": MAX_NEW,
        "finish_reasons": sorted({r["finish"] for r in got["wave"]}),
        "twins_identical": True,
        "reference_prompt_tokens": [lens[i] for i in referenced],
        "reference_agreed_of_%d" % MAX_NEW: [
            c["agreed"] for c in ref["compared"]
        ],
        "reference_divergence": [
            c.get("divergence") for c in ref["compared"]
        ],
        "prefix_cache_resend_matched": resend == wave[RESEND],
        "prefix_cache_resend_agreed": agreed(resend, wave[RESEND]),
        "first_token_s": [round(r["first_s"], 2) for r in got["wave"]],
        "wave_done_s": round(max(r["done_s"] for r in got["wave"]), 2),
        "resend_s": round(got["resend"]["done_s"] - max(
            r["done_s"] for r in got["wave"]), 2),
        "server_compiles": reports["compiles"],
        "reference_compile_s": ref["compile_s"],
        "hbm_after_engine_built": reports["engine_built"]["memory"],
        "checkpoint_write_s": round(ckpt_s, 1),
        "dataflow_s": round(dataflow_s, 1),
        "seconds": round(time.perf_counter() - t_phase, 1),
    }


def _chunk_first_token(params, cfg, prompt: list[int]) -> int:
    """A prompt's first generated token from one fused_paged_chunk_step
    call on fresh pools: pages 1.. granted in order, chunk right-padded to
    the engine's default 256 rows."""
    import jax
    import jax.numpy as jnp

    from dora_tpu.models.hf import qwen2

    chunk, page = min(256, cfg.max_seq), 16
    ids = jnp.zeros((chunk,), jnp.int32).at[: len(prompt)].set(
        jnp.asarray(prompt, jnp.int32)
    )
    table = jnp.zeros((cfg.max_seq // page,), jnp.int32).at[
        : chunk // page
    ].set(jnp.arange(1, chunk // page + 1))
    pools = qwen2.init_page_pool(cfg, chunk // page + 1, page)
    greedy, _ = jax.jit(
        lambda p, i, pl, pos, bt: qwen2.fused_paged_chunk_step(
            p, cfg, i, pl, pos, bt
        )
    )(params, ids, pools, jnp.asarray(0, jnp.int32), table)
    return int(greedy[len(prompt) - 1])


def child_reference(argv) -> dict:
    """Serial greedy reference (``qwen2.generate``, the path
    tests/test_paged_engine.py holds the engine to) from the same int8
    weights the server quantized. Without the bf16 sidecars: the paged
    kernels never read them, while the reference's dense prefill would
    (layers.matmul takes the sidecar for M > 32) — it would then run the
    float model over the prompt and the int8 model after it, and differ
    from the engine by the quantization error, not by a fault."""
    os.environ["DORA_INT8_DECODE"] = "1"  # as llm_server.main does
    os.environ["DORA_INT8_PURE"] = "1"
    import jax
    import jax.numpy as jnp

    from dora_tpu import backend, telemetry
    from dora_tpu.models.hf import qwen2

    backend.init_compile_cache()
    device = backend.require_accelerator("chip_smoke reference")
    telemetry.install_compile_listener()
    spec = json.loads(Path(argv[0]).read_text())
    cfg, params = qwen2.load(spec["checkpoint"], max_seq=spec["max_seq"])
    params = qwen2.quantize_decode(params, cfg)
    compared = []
    for prompt, engine in zip(spec["prompts"], spec["engine"]):
        ref = jax.device_get(qwen2.generate(
            params, cfg, jnp.asarray([prompt], jnp.int32), spec["max_new"]
        ))[0].tolist()
        n = agreed(ref, engine)
        row = {"prompt_tokens": len(prompt), "agreed": n}
        if n < len(ref):
            # Where they part: how close was the reference's own call?
            # Teacher-forced logits of the agreed prefix, right-padded to
            # one length so every parting shares one compiled program
            # (causal: the pad cannot reach back).
            seq = prompt + ref[:n]
            padded = jnp.zeros((1, cfg.max_seq), jnp.int32).at[0, : len(seq)].set(
                jnp.asarray(seq, jnp.int32)
            )
            logits = jax.device_get(
                qwen2.forward(params, cfg, padded)[0, len(seq) - 1]
            )
            order = logits.argsort()[::-1]
            top = float(logits[order[0]])
            ulp = 2.0 ** (math.floor(math.log2(abs(top))) - 7)  # bf16 step
            row["divergence"] = {
                "position": n,
                "reference_token": ref[n], "engine_token": engine[n],
                "reference_top1_logit": top,
                "reference_top1_top2_gap": top - float(logits[order[1]]),
                "reference_top1_top2_gap_bf16_ulps":
                    (top - float(logits[order[1]])) / ulp,
                "engine_token_deficit_bf16_ulps":
                    (top - float(logits[engine[n]])) / ulp,
                "engine_token_rank_in_reference": int(
                    (order == engine[n]).nonzero()[0][0]
                ),
                "reference_logit_std": float(logits.std()),
            }
            if n == 0 and len(prompt) <= 256:
                # The first token comes straight out of chunked prefill:
                # ask the chunk program alone, outside the engine, so a
                # kernel's arithmetic is told from the engine's logic.
                row["divergence"]["chunk_program_token"] = _chunk_first_token(
                    params, cfg, prompt
                )
        compared.append(row)
    return {
        "phase": "reference", "device": device, "compared": compared,
        "compile_s": round(telemetry.compile_seconds(), 2),
        "compiles": telemetry.compile_count(),
    }


# ---------------------------------------------------------------------------
# phase: frames
# ---------------------------------------------------------------------------


#: camera tick of the frames phase: slower than the model, so that after
#: the first tick's compile every frame is served and none is dropped.
TICK_MS = 100
#: outputs that must arrive past the compile, and are compared by --chips 4
TAIL = 32


def run_frames(out: Path, name: str, args, env: dict) -> tuple[dict, list]:
    """camera -> make_vlm -> sink (bench_vlm.bench_e2e). Returns the
    phase's row and the tokens of the last TAIL frames of the stream."""
    import bench_vlm

    work = out / name
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    # The stream has to outlive a cold compile of the whole VLM program:
    # two minutes of frames (a sender's shared memory outlives it by 10 s
    # only, so frames cannot simply wait in a deep queue).
    frames = 100 if args.tiny else 1200
    t0 = time.perf_counter()
    try:
        data = bench_vlm.bench_e2e(
            work, max_new=4, frames=frames,
            size="tiny" if args.tiny else "2b",
            env={"DORA_INT8_DECODE": "1", **env},
            tick_ms=TICK_MS, timeout_s=args.timeout,
        )
    except (RuntimeError, TimeoutError) as e:
        raise PhaseFailed(f"{name}: {e!r}") from e
    reports = node_reports(work, "vlm")
    tokens, gaps = data["tokens"][-TAIL:], data["gaps_ms"][-TAIL:]
    if len(tokens) < TAIL or any(len(t) != 4 for t in tokens):
        raise PhaseFailed(
            f"{name}: sink got {len(data['tokens'])} outputs of "
            f"{sorted({len(t) for t in tokens})} tokens past the compile, "
            f"wanted at least {TAIL} of 4"
        )
    return {
        "phase": name, "passed": True, "device": reports["device"],
        "model_size": "tiny" if args.tiny else "2b (VLMConfig.bench_2b)",
        "frames_sent": frames, "camera_tick_ms": TICK_MS,
        "frames_served": len(data["tokens"]), "tokens_per_frame": 4,
        "tier": reports["vlm_tier"],
        "first_tick_s_incl_compile":
            reports["first_tick"]["seconds_incl_compile"],
        "memory_per_device": reports["first_tick"]["memory"],
        "p50_gap_ms": data["p50_gap_ms"],
        # A frame dropped in the tail shows as a tail that spans more
        # ticks than it has gaps (a late frame does not: the next gap is
        # short by as much).
        "tail_frames_dropped": round(sum(gaps) / TICK_MS) - len(gaps),
        "seconds": round(time.perf_counter() - t0, 1),
    }, tokens


def phase_mesh(out: Path, args) -> list[dict]:
    """--chips 4: the same frames on one device and on
    DORA_MESH=dp=2,tp=2 — all four devices, and the widest mesh the
    fused tensor-parallel kernel tier shards at 2 KV heads (tp=4 leaves
    that tier, fused_tp.tp_compatible, and serves float weights)."""
    one, one_tokens = run_frames(out, "frames_one_device", args, {})
    emit(one)
    row, tokens = run_frames(
        out, "frames_mesh_dp2_tp2", args, {"DORA_MESH": "dp=2,tp=2"}
    )
    # Both streams end on the camera's last frame and neither dropped
    # one in its tail, so the last TAIL outputs are the same frames.
    per_frame = [agreed(a, b) for a, b in zip(tokens, one_tokens)]
    row["tail_frames_compared"] = len(per_frame)
    row["agreed_with_one_device_min_of_4"] = min(per_frame)
    row["frames_fully_identical"] = sum(n == 4 for n in per_frame)
    row["passed"] = (
        row["tail_frames_dropped"] == one["tail_frames_dropped"] == 0
        and min(per_frame) >= 1
        and row["tier"]["tier"] == "fused_tp"
    )
    return [one, emit(row)]


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--tiny", action="store_true",
                    help="toy widths, for the CPU rehearsal (never ok)")
    ap.add_argument("--timeout", type=float, default=900.0,
                    help="seconds allowed to each dataflow or child")
    ap.add_argument("--child", choices=("facts", "reference"))
    ap.add_argument("rest", nargs="*")
    args = ap.parse_args()

    if args.child:
        emit({"facts": child_facts, "reference": child_reference}[args.child](
            args.rest
        ))
        return 0

    # Nodes are spawned from the daemon with this environment: make the
    # checkout importable, and build the shared-memory library once up
    # front (g++, from native/shmem.cpp) so a machine without a compiler
    # fails here by name and not in some node's first large message.
    os.environ["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT)] + [p for p in [os.environ.get("PYTHONPATH")] if p]
    )
    from dora_tpu.native import build_native

    build_native()
    out = ROOT / "out" / "chip_smoke"  # out/ is git-ignored
    out.mkdir(parents=True, exist_ok=True)
    t_all = time.perf_counter()
    rows: list[dict] = []
    try:
        facts = emit(run_child("facts", [], args.timeout))
        rows.append(facts)
        if facts["device"]["platform"] != "tpu" and not args.tiny:
            raise PhaseFailed(
                f"JAX found {facts['device']}: no TPU, nothing to prove"
            )
        if facts["device"]["count"] != args.chips:
            raise PhaseFailed(
                f"--chips {args.chips} but JAX sees "
                f"{facts['device']['count']} device(s)"
            )
        if args.chips == 4:
            rows += phase_mesh(out, args)
        else:
            rows.append(emit(phase_serve(out, args)))
            rows.append(emit(run_frames(out, "frames", args, {})[0]))
    except PhaseFailed as e:
        for log in sorted(out.glob("*/out/*/log_*.txt")):
            tail = log.read_text(errors="replace").splitlines()[-15:]
            print(f"--- {log}", *tail, sep="\n", file=sys.stderr)
        emit({"ok": False, "error": str(e)})
        return 1
    emit({"phase": "total", "seconds": round(time.perf_counter() - t_all, 1)})
    devices = [r["device"] for r in rows] + [
        r["reference_device"] for r in rows if "reference_device" in r
    ]
    bad = [r["phase"] for r in rows if not r["passed"]]
    off_chip = [d for d in devices if d["platform"] != "tpu"]
    if bad or off_chip:
        emit({"ok": False, "failed_phases": bad, "not_on_tpu": off_chip})
        return 1
    device = {k: devices[-1][k] for k in ("platform", "kind", "count")}
    if any({k: d[k] for k in device} != device for d in devices):
        emit({"ok": False, "error": "phases disagree on the device",
              "devices": devices})
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
