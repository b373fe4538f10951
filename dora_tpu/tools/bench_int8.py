"""Microbench: int8 dequant-matmul vs bf16 matmul on decode shapes.

Run on the TPU: python -m dora_tpu.tools.bench_int8
Each timing chains iterations with a data dependency and reduces to a
scalar fetched to the host.
"""

from __future__ import annotations

import statistics
import time

import jax
import jax.numpy as jnp

from dora_tpu.ops.int8_matmul import int8_matmul, quantize_int8

ITERS = 1024


def _time_scalar(fn, rounds: int = 5) -> float:
    float(fn())  # compile
    samples = []
    for _ in range(rounds):
        t = time.perf_counter()
        float(fn())
        samples.append(time.perf_counter() - t)
    return statistics.median(samples)


def bench_shape(m: int, k: int, n: int) -> None:
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (m, k), jnp.bfloat16)
    w = jax.random.normal(kw, (k, n), jnp.float32)
    wq = quantize_int8(w)
    w16 = w.astype(jnp.bfloat16)
    q, s = wq["int8"], wq["scale"]

    @jax.jit
    def chain_bf16(x, w):
        def body(_, acc):
            y = (x + acc * 1e-9) @ w
            return jnp.max(y).astype(jnp.float32) * 1e-9
        return jax.lax.fori_loop(0, ITERS, body, jnp.float32(0))

    @jax.jit
    def chain_int8(x, q, s):
        def body(_, acc):
            y = int8_matmul(x + acc.astype(x.dtype) * 1e-9, q, s)
            return jnp.max(y).astype(jnp.float32) * 1e-9
        return jax.lax.fori_loop(0, ITERS, body, jnp.float32(0))

    rtt = _time_scalar(jax.jit(lambda: jnp.float32(0)))
    t16 = (_time_scalar(lambda: chain_bf16(x, w16)) - rtt) / ITERS
    t8 = (_time_scalar(lambda: chain_int8(x, q, s)) - rtt) / ITERS
    gbs16 = k * n * 2 / t16 / 1e9
    gbs8 = k * n * 1 / t8 / 1e9
    print(
        f"[{m}x{k}x{n}] bf16 {t16*1e6:8.1f}us ({gbs16:6.1f} GB/s)  "
        f"int8 {t8*1e6:8.1f}us ({gbs8:6.1f} GB/s)  "
        f"speedup {t16/t8:5.2f}x",
        flush=True,
    )


if __name__ == "__main__":
    print(f"backend={jax.default_backend()}")
    bench_shape(16, 1536, 8960)    # ffn up (M padded to sublane anyway)
    bench_shape(16, 8960, 1536)    # ffn down
    bench_shape(16, 1536, 1536)    # attn qo
    bench_shape(16, 1536, 152064)  # lm_head
