"""VLM serving benchmarks on the chip (fails without one unless JAX_PLATFORMS=cpu).

BASELINE.md north star: camera → VLM (Qwen2-VL-2B shape) at >= 25 FPS
end-to-end on a v5e-1. Two modes:

  python bench_vlm.py model   # model-only: prefill, decode tok/s, MFU
  python bench_vlm.py e2e     # full dataflow FPS through the daemon

Prints one JSON line per metric.
``bench.py`` (the driver entry point) remains the single-line latency
bench — this harness is the TPU-throughput counterpart.

MFU accounting: analytic matmul FLOPs from the config (weights 2*m*n per
token plus attention 4*T*dim per layer), against peak
``DORA_TPU_PEAK_TFLOPS`` (default 197, TPU v5e bf16). Embedding gathers
and normalizations are excluded — the estimate is a lower bound.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time
from pathlib import Path

PEAK_TFLOPS = float(os.environ.get("DORA_TPU_PEAK_TFLOPS", "197"))
PEAK_HBM_GBS = float(os.environ.get("DORA_TPU_PEAK_HBM_GBS", "819"))  # v5e


def _emit(metric: str, value: float, unit: str, **extra) -> None:
    print(json.dumps({"metric": metric, "value": round(value, 3),
                      "unit": unit, **extra}), flush=True)


# ---------------------------------------------------------------------------
# analytic FLOPs (lower bound: matmuls only)
# ---------------------------------------------------------------------------


def lm_matmul_flops_per_token(cfg) -> float:
    """Weight-matmul FLOPs for one LM token (no attention scores)."""
    hd = cfg.head_dim
    per_layer = 2 * (
        cfg.dim * cfg.heads * hd          # wq
        + 2 * cfg.dim * cfg.kv_heads * hd  # wk, wv
        + cfg.heads * hd * cfg.dim         # wo
        + 3 * cfg.dim * cfg.ffn            # gate, up, down
    )
    return cfg.layers * per_layer + 2 * cfg.dim * cfg.vocab  # + lm_head


def lm_attention_flops(cfg, context: int) -> float:
    """Score+value FLOPs for one token attending over ``context`` keys."""
    return cfg.layers * 4.0 * context * cfg.dim


def vision_matmul_flops(cfg) -> float:
    """Vision tower FLOPs for one image (all patches)."""
    p = cfg.n_patches
    patch_dim = cfg.patch_size * cfg.patch_size * 3
    per_layer = 2 * (4 * cfg.vision_dim**2 + 3 * cfg.vision_dim * cfg.vision_ffn)
    attn = 4.0 * p * cfg.vision_dim  # per patch, full self-attention
    return p * (
        2 * patch_dim * cfg.vision_dim
        + cfg.vision_layers * per_layer
        + cfg.vision_layers * attn
        + 2 * cfg.vision_dim * cfg.dim
    )


# ---------------------------------------------------------------------------
# model-only bench
# ---------------------------------------------------------------------------


def _start(who: str):
    """Every model bench runs on the chip or says it does not: place the
    compile cache, refuse any backend but ``tpu`` unless
    ``JAX_PLATFORMS=cpu`` is set on purpose (dora_tpu/backend.py)."""
    from dora_tpu import backend

    backend.init_compile_cache()
    return backend.require_accelerator(who)


def _amortized_s(fn_scalar, n_iters: int, rounds: int = 3):
    """Median per-iteration seconds of a jit whose scalar output chains
    ``n_iters`` data-dependent repetitions of the workload. The scalar is
    fetched to the host, which waits for the device (so does
    ``block_until_ready`` on the v5e — chip_smoke.py "facts"); the
    ~1 ms dispatch+fetch round trip is amortized over the chain, not
    subtracted."""
    float(fn_scalar())  # compile
    samples = []
    for _ in range(rounds):
        t = time.perf_counter()
        float(fn_scalar())
        samples.append(time.perf_counter() - t)
    return statistics.median(samples) / n_iters


def bench_model(max_new: int = 64, prefill_iters: int = 16,
                generate_iters: int = 4) -> dict:
    import jax
    import jax.numpy as jnp

    from dora_tpu.models import vlm

    cfg = vlm.VLMConfig.bench_2b()
    print(f"# device {_start('bench_vlm model')}", file=sys.stderr)

    t0 = time.perf_counter()
    params = vlm.init_params(jax.random.PRNGKey(0), cfg)
    # Serving config: weights resident in bf16 (MXU-native), fp32 freed.
    cast = jax.jit(
        lambda p: jax.tree.map(lambda x: x.astype(jnp.bfloat16), p),
        donate_argnums=0,
    )
    params = cast(params)
    int8 = bool(os.environ.get("DORA_INT8_DECODE"))
    int4 = bool(os.environ.get("DORA_INT4_DECODE"))
    if int8 or int4:
        quantize = jax.jit(
            lambda p: vlm.quantize_decode(p), donate_argnums=0
        )
        params = quantize(params)
    n_params = vlm.param_count(params)
    print(f"# {n_params/1e9:.2f}B params in "
          f"{time.perf_counter()-t0:.1f}s", file=sys.stderr)

    image = jnp.zeros((1, cfg.image_size, cfg.image_size, 3), jnp.float32)
    prompt = jnp.arange(16, dtype=jnp.int32)[None] % cfg.vocab

    # Chain iterations with a data dependency (image perturbed by the
    # previous scalar) so XLA cannot hoist or CSE the repeated work.
    @jax.jit
    def prefill_chain(p, im, pr):
        def body(_, acc):
            logits, _, _ = vlm.prefill(p, cfg, im + acc * 1e-9, pr)
            return jnp.max(logits) * 1e-9
        return jax.lax.fori_loop(0, prefill_iters, body, jnp.float32(0))

    @jax.jit
    def generate_chain(p, im, pr):
        def body(_, acc):
            tokens = vlm.generate(p, cfg, im + acc * 1e-9, pr, max_new)
            return jnp.float32(jnp.max(tokens)) * 1e-9
        return jax.lax.fori_loop(0, generate_iters, body, jnp.float32(0))

    t0 = time.perf_counter()
    prefill_s = _amortized_s(
        lambda: prefill_chain(params, image, prompt), prefill_iters
    )
    print(f"# prefill bench (incl compile) {time.perf_counter()-t0:.1f}s",
          file=sys.stderr)
    t0 = time.perf_counter()
    generate_s = _amortized_s(
        lambda: generate_chain(params, image, prompt), generate_iters
    )
    print(f"# generate bench (incl compile) {time.perf_counter()-t0:.1f}s",
          file=sys.stderr)

    spec_s = spec_passes = None
    if os.environ.get("DORA_SPEC_DECODE"):
        # Speculative decode is a while_loop (iteration count is
        # data-dependent), so chain via Python: time one full generate
        # per fetch.
        def spec_once():
            # passes is an output of the decode while_loop, so fetching
            # it alone synchronizes the whole generation.
            _, passes = vlm.generate_speculative(
                params, cfg, image, prompt, max_new
            )
            return float(passes)

        spec_passes = spec_once()  # compile + pass count
        samples = []
        for _ in range(3):
            t = time.perf_counter()
            spec_once()
            samples.append(time.perf_counter() - t)
        spec_s = statistics.median(samples)
    decode_s = max(generate_s - prefill_s, 1e-9)
    tokens_per_s = max_new / decode_s

    # FLOPs: prefill processes image + patches+prompt tokens; each decode
    # token runs the full stack over a growing context.
    prefill_tokens = cfg.n_patches + int(prompt.shape[1])
    per_tok = lm_matmul_flops_per_token(cfg)
    prefill_flops = (
        vision_matmul_flops(cfg)
        + prefill_tokens * per_tok
        + sum(lm_attention_flops(cfg, t) for t in range(1, prefill_tokens + 1))
    )
    decode_flops = sum(
        per_tok + lm_attention_flops(cfg, prefill_tokens + i)
        for i in range(max_new)
    )
    peak = PEAK_TFLOPS * 1e12
    prefill_mfu = prefill_flops / prefill_s / peak
    decode_mfu = decode_flops / decode_s / peak
    fps = 1.0 / generate_s

    # Batch-1 decode is HBM-bandwidth-bound (every token streams the LM
    # weights once), so MBU — bytes of LM weights read per second against
    # peak HBM bandwidth — is the honest decode-efficiency number; MFU is
    # reported for completeness but ~0.3% is simply the batch-1 physics.
    # (embedding gather reads one row, not the table; lm_head is already
    # in the matmul count)
    bytes_per_param = 0.5 if int4 else (1.0 if int8 else 2.0)
    lm_param_bytes = bytes_per_param * (lm_matmul_flops_per_token(cfg) / 2)
    decode_mbu = lm_param_bytes * tokens_per_s / (PEAK_HBM_GBS * 1e9)

    tag = " int4" if int4 else (" int8" if int8 else "")
    _emit("vlm-2b prefill latency", prefill_s * 1e3, "ms",
          backend=backend, prefill_tokens=prefill_tokens)
    _emit(f"vlm-2b decode{tag} throughput", tokens_per_s, "tokens/s",
          backend=backend, max_new=max_new)
    _emit(f"vlm-2b decode{tag} MBU", decode_mbu * 100, "%",
          peak_hbm_gbs=PEAK_HBM_GBS)
    _emit("vlm-2b decode MFU", decode_mfu * 100, "%",
          peak_tflops=PEAK_TFLOPS)
    _emit("vlm-2b prefill MFU", prefill_mfu * 100, "%",
          peak_tflops=PEAK_TFLOPS)
    _emit(f"vlm-2b single-stream FPS ({max_new} new tokens)", fps, "fps",
          backend=backend)
    if spec_s is not None:
        spec_tok_s = max_new / max(spec_s - prefill_s, 1e-9)
        _emit(f"vlm-2b speculative decode{tag} throughput", spec_tok_s,
              "tokens/s", model_passes=spec_passes, max_new=max_new,
              note="greedy-exact prompt-lookup speculation")
    return {"fps": fps, "tokens_per_s": tokens_per_s,
            "decode_mfu": decode_mfu, "decode_mbu": decode_mbu,
            "prefill_ms": prefill_s * 1e3}


# ---------------------------------------------------------------------------
# end-to-end dataflow bench
# ---------------------------------------------------------------------------


def bench_e2e(tmp: Path, max_new: int = 4, frames: int = 100,
              size: str = "bench", env: dict | None = None,
              tick_ms: int = 20, timeout_s: float = 1800) -> dict:
    """camera -> VLM operator -> counting sink, through the real daemon.

    FPS = token outputs observed at the sink / wall time between first
    and last (excludes model compile, which gates the first output).
    ``env`` adds to the VLM node's environment (DORA_MESH for the
    multi-chip path). The camera is open-loop on a ``tick_ms`` timer and
    the VLM input is latest-wins (queue_size 1), so the stream must
    outlive the first tick's compile: frames sent before that are
    dropped, as a live camera's would be. With a tick slower than the
    model every later frame is served, so two runs agree frame by frame
    when aligned from the END of the stream (the sink keeps each
    frame's tokens and arrival gaps).
    The VLM node is the only process of the dataflow that touches JAX.
    """
    import textwrap

    import yaml

    from dora_tpu.daemon import run_dataflow

    image = 32 if size == "tiny" else 224  # VLMConfig.tiny / .bench_2b
    sink = tmp / "fps_sink.py"
    sink.write_text(textwrap.dedent("""
        import json
        import statistics
        import time

        from dora_tpu.node import Node

        stamps, tokens = [], []
        with Node() as node:
            for event in node:
                if event["type"] != "INPUT":
                    continue
                stamps.append(time.perf_counter())
                tokens.append(event["value"].to_pylist())
        assert len(stamps) >= 2, f"only {len(stamps)} outputs"
        # Steady state: the first outputs straddle the model's jit
        # compile, so measure after a warmup margin; keep the naive
        # first->last number for reference.
        warmup = min(5, len(stamps) - 2)
        window = stamps[warmup:]
        fps = (len(window) - 1) / (window[-1] - window[0])
        gaps = [b - a for a, b in zip(window, window[1:])]
        open("fps.json", "w").write(json.dumps({
            "fps": fps,
            "outputs": len(stamps),
            "measured_outputs": len(window),
            "p50_gap_ms": statistics.median(gaps) * 1e3,
            "fps_incl_warmup": (len(stamps) - 1) / (stamps[-1] - stamps[0]),
            "gaps_ms": [round(g * 1e3, 1) for g in gaps],
            "tokens": tokens,
        }))
    """))
    spec = {
        "nodes": [
            {
                "id": "camera",
                "path": "module:dora_tpu.nodehub.camera",
                "inputs": {"tick": f"dora/timer/millis/{tick_ms}"},
                "outputs": ["image"],
                "env": {
                    "IMAGE_WIDTH": str(image),
                    "IMAGE_HEIGHT": str(image),
                    "MAX_FRAMES": str(frames),
                },
            },
            {
                "id": "vlm",
                "operator": {
                    "jax": "dora_tpu.nodehub.ops:make_vlm",
                    "inputs": {
                        "image": {"source": "camera/image", "queue_size": 1}
                    },
                    "outputs": ["tokens"],
                },
                "env": {
                    "DORA_MODEL_SIZE": size,
                    "DORA_MAX_NEW_TOKENS": str(max_new),
                    "DORA_PARAM_DTYPE": "bfloat16",
                    # No JAX_PLATFORMS here: the runtime refuses any
                    # backend but the chip unless the caller's own
                    # environment says JAX_PLATFORMS=cpu
                    # (dora_tpu/backend.py).
                    # Serving levers under test ride through when set:
                    # int8 decode weights and async pipelined ticks.
                    **{
                        k: os.environ[k]
                        for k in (
                            "DORA_INT8_DECODE",
                            "DORA_INT8_PURE",
                            "DORA_PIPELINE_DEPTH",
                            "DORA_FETCH_EVERY",
                        )
                        if k in os.environ
                    },
                    **(env or {}),
                },
            },
            {
                "id": "sink",
                "path": "fps_sink.py",
                "inputs": {"tokens": "vlm/op/tokens"},
            },
        ]
    }
    df = tmp / "fps.yml"
    df.write_text(yaml.safe_dump(spec))
    result = run_dataflow(df, timeout_s=timeout_s)
    if not result.is_ok():
        raise RuntimeError(f"e2e bench failed: {result.errors()}")
    return json.loads((tmp / "fps.json").read_text())


def main() -> int:
    mode = sys.argv[1] if len(sys.argv) > 1 else "model"
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    if mode == "model":
        bench_model(max_new=int(os.environ.get("BENCH_MAX_NEW", "64")))
    elif mode == "e2e":
        import tempfile

        max_new = int(os.environ.get("BENCH_MAX_NEW", "4"))
        size = os.environ.get("DORA_MODEL_SIZE", "bench")
        with tempfile.TemporaryDirectory(prefix="dora-vlm-bench-") as tmp:
            data = bench_e2e(
                Path(tmp), max_new=max_new,
                frames=int(os.environ.get("BENCH_FRAMES", "100")), size=size,
            )
        _emit(
            f"camera->vlm-{size} end-to-end FPS ({max_new} new tokens/frame)",
            data["fps"], "fps", outputs=data["outputs"],
            measured_outputs=data["measured_outputs"],
            p50_gap_ms=round(data["p50_gap_ms"], 1),
            vs_baseline=data["fps"] / 25.0,  # north star: 25 FPS
        )
    else:
        raise SystemExit(f"unknown mode {mode!r} (model | e2e)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
