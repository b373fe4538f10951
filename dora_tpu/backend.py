"""The one place that decides what this process runs on.

Two ways of running exist and nothing between them:

* **the chip** — the default. A process that will run JAX programs calls
  :func:`require_accelerator` once at start-up; it fails unless the
  backend is ``tpu``. Kernels compile through Mosaic, compute is bf16.
* **the CPU, for tests** — only when ``JAX_PLATFORMS=cpu`` is set
  explicitly (tests/conftest.py does). Pallas kernels run through the
  interpreter, compute is f32.

Everything that used to ask ``jax.default_backend()`` for itself —
compute dtype, the kernels' ``interpret=`` flag, flash-attention and
donation defaults — asks :func:`on_tpu` instead, so a test can steer the
whole program to the chip's lowering by patching that one function.

:func:`init_compile_cache` places JAX's persistent compilation cache:
wherever ``JAX_COMPILATION_CACHE_DIR`` points if it is set (then no
directory is set in code), else one fixed directory inside the checkout
(on the chip; the CPU-for-tests mode caches only where it is told to).

:func:`roomy` runs a program's first call — the one that traces, lowers
and compiles — where the depth of the caller's Python stack cannot make
it slower.
"""

from __future__ import annotations

import json
import logging
import os
from pathlib import Path

log = logging.getLogger("dora_tpu.backend")

#: fixed fallback cache location — the path is part of the cache key, so
#: it must never carry a pid, a timestamp or a tempfile name.
COMPILE_CACHE_DIR = Path(__file__).resolve().parent.parent / ".jax_cache"

_described = False


def report(kind: str, payload: dict) -> None:
    """One ``dora_tpu.backend <kind>: {json}`` line in this process's log.
    chip_smoke.py reads these back out of a node's log file to learn
    where the node really ran and what it held — the node owns the chip,
    so nobody else can ask the device."""
    log.warning("dora_tpu.backend %s: %s", kind, json.dumps(payload))


def memory_report() -> list[dict]:
    """Per-device allocator figures (``device.memory_stats()``)."""
    import jax

    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out.append({
            "id": d.id,
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit"),
        })
    return out


def on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def _cpu_on_purpose() -> bool:
    return os.environ.get("JAX_PLATFORMS", "").strip() == "cpu"


def interpret() -> bool:
    """``interpret=`` for every ``pallas_call`` in the repo."""
    return not on_tpu()


def partitioned_by_xla() -> bool:
    """True while tracing a program that XLA's SPMD pass will partition
    over a mesh (``jax.set_mesh`` around the jit, outside ``shard_map``).
    A Mosaic kernel cannot be partitioned automatically — the chip's
    compiler refuses it ("Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map") — so kernel call
    sites that have a plain-XLA twin ask this and take the twin."""
    import jax

    mesh = jax.sharding.get_abstract_mesh()
    return not mesh.empty and mesh.size > 1 and bool(mesh.auto_axes)


def compute_dtype():
    import jax.numpy as jnp

    return jnp.bfloat16 if on_tpu() else jnp.float32


def describe() -> dict:
    """What JAX reports about the device this process holds."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def require_accelerator(who: str) -> dict:
    """Fail at start-up unless this process may run where it is running.

    The CPU is allowed only when ``JAX_PLATFORMS=cpu`` was set on purpose;
    otherwise the backend must be ``tpu``. Logs platform, device kind,
    compute dtype and the kernels' interpret flag once per process and
    returns :func:`describe`."""
    global _described
    device = describe()
    if device["platform"] != "tpu" and not _cpu_on_purpose():
        raise RuntimeError(
            f"{who}: JAX backend is {device['platform']!r} "
            f"({device['kind']}), not 'tpu'. This program runs on the chip; "
            "set JAX_PLATFORMS=cpu explicitly to run it on the CPU "
            "(tests do: interpret-mode kernels, f32)."
        )
    if not _described:
        _described = True
        import jax
        import jax.numpy as jnp

        report("device", {
            "who": who, **device,
            "compute_dtype": jnp.dtype(compute_dtype()).name,
            "pallas_interpret": interpret(),
            "compile_cache": jax.config.jax_compilation_cache_dir,
        })
    return device


def init_compile_cache() -> str | None:
    """Turn the persistent compilation cache on; return its directory.

    Call once in every process that will compile, before the first jit.
    With ``JAX_COMPILATION_CACHE_DIR`` set JAX reads the directory itself
    and this sets none in code; otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`. The CPU-for-tests mode gets a cache only
    when one is placed from outside: its programs are toys, and XLA:CPU
    warns about machine features on every entry it loads back."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        if _cpu_on_purpose():
            return None
        jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    # Cache every program, however quick it was to compile: a serving
    # process compiles a handful of large programs and many tiny ones,
    # and a warm start wants all of them.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return placed or str(COMPILE_CACHE_DIR)


def roomy(call, *args):
    """``call(*args)``, with room for the Python frames below it.

    CPython (3.11 on) keeps a thread's frames in chunks of 16 KiB and hands
    a chunk back to the system the moment the frame that opened it returns.
    A loop that calls small functions from the last frame that still fits a
    chunk therefore maps, faults in and unmaps a chunk on EVERY call: some 6
    us a call on this sandbox's CPU against 0.03, more on a host whose many
    threads each take the unmap's shoot-down. Whether a hot level of JAX's
    tracer or lowering sits on such an edge depends on the BYTES of stack
    below it, so on the number of locals of every frame from ``main`` down
    to the jit call. On the v5e host the first call of K-EXAONE's window
    program (trace, lowering, a fetch from the compile cache) took 7.7 s
    from the serving loop as it stood, 12.4 s once a refactor made
    ``dispatch()``'s frame smaller, and 2.7 s from here, where the chunk
    is 2 MiB and stays until ``call`` returns: no edge below (PERF.md
    section 6, PR 46). About 15 us a call: for a program's FIRST
    call, where seconds are at stake, not for the steady state."""
    return call(*args)


#: A declared evaluation stack of 1 MiB (131,072 slots and a few): no
#: frame that size fits what is left of a 16 KiB chunk, so entering
#: ``roomy`` opens a chunk of its own, of 2 MiB, and the 1 MiB behind
#: its frame holds every frame below. The slots are never written: the
#: pages stay untouched.
roomy.__code__ = roomy.__code__.replace(co_stacksize=(1 << 17) + 8)
