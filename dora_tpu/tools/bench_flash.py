"""Flash-attention long-context bench on the live backend.

Run on the TPU: python -m dora_tpu.tools.bench_flash
Validates the VMEM-flat claim (T=8192/16384 compile and run with the
same footprint as T=2k) and reports achieved attention TFLOP/s. Timing
chains data-dependent iterations and fetches a scalar to the host.
"""

from __future__ import annotations

import statistics
import time

import jax
import jax.numpy as jnp

from dora_tpu.models import layers as L
from dora_tpu.ops import flash_attention


def _time_scalar(fn, rounds: int = 5) -> float:
    float(fn())
    samples = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        float(fn())
        samples.append(time.perf_counter() - t0)
    return statistics.median(samples)


def bench(t: int, h: int = 8, d: int = 128, causal: bool = True,
          iters: int = 8, check_parity: bool = False) -> None:
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (1, h, t, d), jnp.bfloat16)
    k = jax.random.normal(kk, (1, h, t, d), jnp.bfloat16)
    v = jax.random.normal(kv, (1, h, t, d), jnp.bfloat16)

    @jax.jit
    def chain(q, k, v):
        def body(_, acc):
            out = flash_attention(q + acc.astype(q.dtype) * 1e-9, k, v,
                                  causal=causal)
            return jnp.max(out).astype(jnp.float32) * 1e-9
        return jax.lax.fori_loop(0, iters, body, jnp.float32(0))

    rtt = _time_scalar(jax.jit(lambda: jnp.float32(0)))
    sec = max(_time_scalar(lambda: chain(q, k, v)) - rtt, 1e-9) / iters
    # scores + values matmuls; causal halves the live area
    flops = 4.0 * h * t * t * d * (0.5 if causal else 1.0)
    print(
        f"T={t:6d} causal={causal}  {sec*1e3:8.2f} ms  "
        f"{flops/sec/1e12:6.1f} TFLOP/s",
        flush=True,
    )
    if check_parity:
        ours = flash_attention(q, k, v, causal=causal)
        mask = L.causal_mask(t, t) if causal else None
        ref = L.attention(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), mask,
        )
        import numpy as np

        err = np.abs(
            np.asarray(ours, np.float32) - np.asarray(ref)
        ).max()
        print(f"         parity vs dense (f32 ref): max abs err {err:.3e}")


if __name__ == "__main__":
    print(f"backend={jax.default_backend()}")
    bench(2048, check_parity=True)
    bench(8192)
    bench(16384)
