"""Device-mesh construction and sharding helpers."""

from __future__ import annotations

from typing import Any

import numpy as np

AXIS_DP = "dp"  # data (batch)
AXIS_TP = "tp"  # tensor (heads / ffn hidden)
AXIS_SP = "sp"  # sequence (ring attention)


def make_mesh(dp: int = 1, tp: int = 1, sp: int = 1, devices=None):
    """Build a Mesh with named axes (dp, tp, sp). Axis sizes must multiply
    to the device count; pass dp=-1 to absorb the remainder into data
    parallelism."""
    import jax
    from jax.sharding import Mesh

    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if dp == -1:
        if n % (tp * sp):
            raise ValueError(f"{n} devices not divisible by tp*sp={tp * sp}")
        dp = n // (tp * sp)
    if dp * tp * sp != n:
        raise ValueError(f"dp*tp*sp={dp * tp * sp} != {n} devices")
    grid = np.array(devices).reshape(dp, tp, sp)
    return Mesh(grid, (AXIS_DP, AXIS_TP, AXIS_SP))


def named(mesh, *spec):
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec(*spec))


def shard(x, mesh, *spec):
    """Constrain (inside jit) or place (outside jit) ``x`` on the mesh."""
    import jax

    sharding = named(mesh, *spec)
    if isinstance(x, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(x, sharding)
    return jax.device_put(x, sharding)


def shard_params(params: Any, mesh, rules) -> Any:
    """Place a parameter pytree on the mesh.

    ``rules`` is a list of ``(name, spec)`` matched against the leaf's
    FINAL path component exactly (substring matching would silently catch
    look-alikes — 'embed' must not shard 'pos_embed'). First match wins;
    default is full replication. A matched leaf whose dimension does not
    divide the mesh axis falls back to replication instead of crashing —
    real checkpoint shapes (odd vocab sizes, 196-patch position tables)
    must serve on any mesh. A leaf that already sits on THIS mesh keeps
    its placement: a tree prepared for a per-rank kernel layout
    (parallel/fused_tp.prepare_decode_params) rides in operator state
    untouched.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def divisible(leaf, spec) -> bool:
        shape = getattr(leaf, "shape", ())
        for dim, axes in zip(shape, tuple(spec) + (None,) * len(shape)):
            if axes is None:
                continue
            # A dimension splits over the PRODUCT of its mesh axes.
            total = 1
            for axis in (axes if isinstance(axes, tuple) else (axes,)):
                total *= axis_sizes.get(axis, 1)
            if dim % total:
                return False
        return True

    def place(path, leaf):
        placed = getattr(leaf, "sharding", None)
        if isinstance(placed, NamedSharding) and placed.mesh == mesh:
            return leaf
        name = str(getattr(path[-1], "key", path[-1])) if path else ""
        for match, spec in rules:
            if name == match:
                if not divisible(leaf, spec):
                    break  # replicate: shape does not tile on this mesh
                return jax.device_put(leaf, NamedSharding(mesh, spec))
        return jax.device_put(leaf, NamedSharding(mesh, PartitionSpec()))

    return jax.tree_util.tree_map_with_path(place, params)
