"""Device utilization plane: HBM gauges, an analytic FLOPs model, and
on-demand deep profile capture.

Three tiers, cheapest first:

1. **Continuous gauges** — :class:`DeviceMonitor` samples
   ``device.memory_stats()`` (bytes in use / limit / peak; gracefully
   ``None`` on backends that expose no allocator stats, e.g. CPU) at the
   serving node's report cadence. Combined with the engine's attribution
   counters (``device_compute_ns`` etc., models/batch_engine) and the
   analytic per-token FLOPs model below, the server derives ``mfu`` and
   ``device_busy_fraction`` gauges that flow through ``ServingMetrics``
   → ``metrics_history`` → ``prom.py`` → ``dora-tpu top``.

2. **Window time attribution** — not in this module: the engine's step
   path splits each fused window's wall time into host-dispatch /
   device-compute / fetch via a ``block_until_ready`` between dispatch
   and the device->host read (see ``PagedBatchEngine.step``), gated on
   :func:`monitor_enabled` so the split costs nothing when off.

3. **Deep capture** — :func:`start_capture` / :func:`stop_capture` wrap
   ``jax.profiler`` behind the control plane's StartProfile/StopProfile
   messages. On the chip a profiler that fails is an error reply; only
   the CPU-for-tests mode substitutes a synthetic marker file.

The FLOPs model is deliberately analytic (config arithmetic, no device
introspection): it is hand-checkable in tests and identical on CPU stub
runs and real TPU runs, so the MFU plumbing is exercised by tier-1.

MFU here counts EMITTED tokens (useful work); a speculative window that
drafts ``K x (spec_k+1)`` positions but keeps 3 contributes 3 tokens of
useful FLOPs while ``device_busy_fraction`` still reflects the full
window's device time — the gap between the two gauges IS the rejected
tail (see KNOWN_ISSUES round 16).
"""

from __future__ import annotations

import json
import os
import time

from dora_tpu import backend


def monitor_enabled() -> bool:
    """``DORA_DEVICE_MONITOR`` gate for the utilization plane (gauges +
    attribution timing). Default ON — the bench ``profiling_ab`` leg
    holds its overhead ≤3%; set ``0`` to strip the hooks entirely."""
    return os.environ.get("DORA_DEVICE_MONITOR", "1") not in ("0", "false", "")


# ---------------------------------------------------------------------------
# analytic FLOPs model
# ---------------------------------------------------------------------------


def flops_per_token(
    *,
    dim: int,
    layers: int,
    heads: int,
    kv_heads: int,
    ffn: int,
    vocab: int,
) -> int:
    """Forward FLOPs to process ONE token through a Qwen2-shaped
    transformer (matmul 2·m·n·k arithmetic only; norms/rope/softmax are
    O(dim) noise at this granularity, and attention's context-length
    term is deliberately excluded so the number is a constant of the
    config — hand-checkable and position-independent).

    Per layer: q and o projections (``2·dim·dim`` each), k and v
    projections (``2·dim·kv_heads·head_dim`` each), and the SwiGLU FFN's
    three matmuls (``2·dim·ffn`` each). Plus one lm_head (``2·dim·vocab``).
    """
    head_dim = dim // heads
    per_layer = (
        2 * (2 * dim * dim)                   # q + o projections
        + 2 * (2 * dim * kv_heads * head_dim)  # k + v projections
        + 3 * (2 * dim * ffn)                  # SwiGLU: gate, up, down
    )
    return layers * per_layer + 2 * dim * vocab


def flops_per_token_config(cfg) -> int:
    """:func:`flops_per_token` from a model config object (anything with
    ``dim/layers/heads/kv_heads/ffn/vocab`` attributes, e.g.
    ``Qwen2Config``)."""
    return flops_per_token(
        dim=cfg.dim, layers=cfg.layers, heads=cfg.heads,
        kv_heads=cfg.kv_heads, ffn=cfg.ffn, vocab=cfg.vocab,
    )


def window_flops(*, flops_per_token: int, active: int, k: int,
                 spec_k: int = 0) -> int:
    """Device FLOPs one fused decode window dispatches: every active
    stream runs K ticks, each tick forwarding ``spec_k + 1`` positions
    (the draft + verify tail; 1 when speculation is off). Frozen rows
    still execute (the window masks their writes, not their compute), so
    this is dispatched work — useful work is emitted × flops_per_token."""
    return active * k * (spec_k + 1) * flops_per_token


#: Advertised peak dense FLOP/s by device-kind substring (bf16, the
#: serving dtype). Coarse on purpose: MFU is a utilization gauge, not a
#: benchmark — override with ``DORA_DEVICE_PEAK_FLOPS`` for exact math.
_PEAK_FLOPS_BY_KIND = (
    ("v6e", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def detect_peak_flops(device=None) -> float:
    """Peak FLOP/s for the device driving MFU's denominator.
    ``DORA_DEVICE_PEAK_FLOPS`` wins; else the device-kind table. On the
    chip a device the table does not know is an error, not a default
    (a utilization against a made-up peak is worse than none); in the
    CPU-for-tests mode it is 0.0 and MFU renders as a dash."""
    raw = os.environ.get("DORA_DEVICE_PEAK_FLOPS", "")
    if raw:
        return float(raw)
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = str(device.device_kind)
    for needle, peak in _PEAK_FLOPS_BY_KIND:
        if needle in kind.lower():
            return peak
    if backend.on_tpu():
        raise LookupError(
            f"no peak FLOP/s known for device kind {kind!r}: add it to "
            "profiling._PEAK_FLOPS_BY_KIND or set DORA_DEVICE_PEAK_FLOPS"
        )
    return 0.0


# ---------------------------------------------------------------------------
# tier 1: continuous device gauges
# ---------------------------------------------------------------------------


class DeviceMonitor:
    """Samples allocator stats off one device at the report cadence.

    ``memory()`` maps the backend's ``memory_stats()`` dict onto the
    three HBM gauges the metrics plane exports; every failure mode a
    backend can present — no method, method returns ``None``, method
    raises, keys absent (CPU, older plugins) — degrades to ``None``
    values, never an exception on the serving report path.
    """

    __slots__ = ("device",)

    def __init__(self, device=None):
        if device is None:
            try:
                import jax

                device = jax.devices()[0]
            except Exception:
                device = None
        self.device = device

    def memory(self) -> dict:
        """``{"used": int|None, "limit": int|None, "peak": int|None}``."""
        out = {"used": None, "limit": None, "peak": None}
        stats_fn = getattr(self.device, "memory_stats", None)
        if stats_fn is None:
            return out
        try:
            stats = stats_fn()
        except Exception:
            return out
        if not stats:
            return out
        out["used"] = stats.get("bytes_in_use")
        out["limit"] = stats.get("bytes_limit", stats.get("bytes_reservable_limit"))
        out["peak"] = stats.get("peak_bytes_in_use")
        return out

    def peak_flops(self) -> float:
        return detect_peak_flops(self.device)


# ---------------------------------------------------------------------------
# tier 3: on-demand deep capture (jax.profiler behind the control plane)
# ---------------------------------------------------------------------------


def profile_dir() -> str:
    """``DORA_PROFILE_DIR`` (capture artifact root; default under /tmp)."""
    return os.environ.get("DORA_PROFILE_DIR", "") or "/tmp/dora-tpu-profiles"


def start_capture(out_dir: str) -> str | None:
    """Start a ``jax.profiler`` trace into ``out_dir``. On the chip a
    profiler that cannot start raises — the caller turns that into an
    error reply. In the CPU-for-tests mode (no profiler plugin in the
    container) it returns the error string instead, and
    :func:`stop_capture` writes a synthetic marker so the control plane
    stays testable end to end."""
    import jax

    os.makedirs(out_dir, exist_ok=True)
    # No Python tracer (the default, level 1, records every Python call
    # of the serving loop): the loop's own ``loop.*`` annotations
    # (telemetry.LOOP_PHASES) say what the host does, on the device
    # planes' clock, and the capture stays small and quick to write.
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    try:
        jax.profiler.start_trace(out_dir, profiler_options=options)
        return None
    except Exception as exc:  # no profiler plugin / already active
        if backend.on_tpu():
            raise
        return f"{type(exc).__name__}: {exc}"


def stop_capture(out_dir: str, start_error: str | None = None) -> str:
    """Stop the capture and return the artifact path. On the chip a
    capture that fails to stop or left no file raises; the CPU-for-tests
    mode writes ``profile_synthetic.json`` (marked ``"synthetic": true``,
    with the reason) in its place."""
    import jax

    error = start_error
    if error is None:
        try:
            jax.profiler.stop_trace()
        except Exception as exc:
            if backend.on_tpu():
                raise
            error = f"{type(exc).__name__}: {exc}"
    if error is None and _has_capture_files(out_dir):
        return out_dir
    if backend.on_tpu():
        raise RuntimeError(f"profiler left no artifact under {out_dir}")
    os.makedirs(out_dir, exist_ok=True)
    marker = os.path.join(out_dir, "profile_synthetic.json")
    with open(marker, "w") as f:
        json.dump(
            {
                "synthetic": True,
                "reason": error or "profiler produced no artifact",
                "unix_time": time.time(),
            },
            f,
        )
    return marker


def _has_capture_files(out_dir: str) -> bool:
    for _root, _dirs, files in os.walk(out_dir):
        if files:
            return True
    return False
