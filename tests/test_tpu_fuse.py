"""TPU-tier unit tests: fusion compiler + fused executor (no daemon).

Covers graph lowering (intra-node SSA edges, topo order, external I/O
classification), tick triggering with latest-wins sampling, warm-up, and
state threading across jitted ticks.
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa
import pytest

from dora_tpu.core.descriptor import Descriptor
from dora_tpu.tpu.fuse import FusedExecutor, FusedGraph


def pipeline_descriptor(tmp_path) -> Descriptor:
    ops = tmp_path / "ops.py"
    ops.write_text(
        """
import jax.numpy as jnp

from dora_tpu.tpu.api import JaxOperator


def make_double():
    def step(state, inputs):
        return state, {"y": inputs["x"] * 2.0}
    return JaxOperator(step=step)


def make_plus():
    def step(state, inputs):
        count = state + 1
        return count, {"y": inputs["x"] + 1.0, "count": count}
    return JaxOperator(step=step, init_state=0)
"""
    )
    return Descriptor.parse(
        {
            "nodes": [
                {
                    "id": "source",
                    "path": "module:dora_tpu.nodehub.pyarrow_sender",
                    "outputs": ["data"],
                },
                {
                    "id": "pipeline",
                    "operators": [
                        {
                            "id": "double",
                            "jax": f"{tmp_path}/ops.py:make_double",
                            "inputs": {"x": "source/data"},
                            "outputs": ["y"],
                        },
                        {
                            "id": "plus",
                            "jax": f"{tmp_path}/ops.py:make_plus",
                            "inputs": {"x": "pipeline/double/y"},
                            "outputs": ["y", "count"],
                        },
                    ],
                },
                {
                    "id": "sink",
                    "path": "module:dora_tpu.nodehub.echo",
                    "inputs": {"in": "pipeline/plus/y"},
                    "outputs": ["echo"],
                },
            ]
        }
    )


def test_fused_graph_structure(tmp_path):
    descriptor = pipeline_descriptor(tmp_path)
    graph = FusedGraph.build(descriptor.node("pipeline"), descriptor)
    assert graph.topo == ["double", "plus"]
    assert graph.intra_edges == {("plus", "x"): ("double", "y")}
    assert graph.external_inputs == {"double/x"}
    # plus/y is consumed by sink; double/y only feeds the sibling (stays in
    # HBM); plus/count has no consumer at all (XLA DCEs it).
    assert graph.external_outputs == {"plus/y"}
    assert graph.trigger_inputs == {"double/x"}


def test_fused_executor_tick_and_state(tmp_path):
    descriptor = pipeline_descriptor(tmp_path)
    graph = FusedGraph.build(descriptor.node("pipeline"), descriptor)
    executor = FusedExecutor(graph)

    out = executor.on_event("double/x", pa.array([1.0, 2.0]), {})
    assert out is not None and set(out) == {"plus/y"}
    arr, meta = out["plus/y"]
    np.testing.assert_allclose(arr.to_numpy(), [3.0, 5.0])
    assert meta["shape"] == [2]

    # State threads across ticks (count increments inside the jit).
    executor.on_event("double/x", pa.array([0.0, 0.0]), {})
    assert int(np.asarray(executor.states["plus"])) == 2


def test_fused_cycle_detected(tmp_path):
    ops = tmp_path / "ops.py"
    ops.write_text(
        """
from dora_tpu.tpu.api import JaxOperator

def make_op():
    return JaxOperator(step=lambda s, i: (s, {"y": i["x"]}))
"""
    )
    descriptor = Descriptor.parse(
        {
            "nodes": [
                {
                    "id": "loop",
                    "operators": [
                        {
                            "id": "a",
                            "jax": f"{tmp_path}/ops.py:make_op",
                            "inputs": {"x": "loop/b/y"},
                            "outputs": ["y"],
                        },
                        {
                            "id": "b",
                            "jax": f"{tmp_path}/ops.py:make_op",
                            "inputs": {"x": "loop/a/y"},
                            "outputs": ["y"],
                        },
                    ],
                }
            ]
        }
    )
    with pytest.raises(ValueError, match="cycle"):
        FusedGraph.build(descriptor.node("loop"), descriptor)


def test_timer_trigger_warmup(tmp_path):
    """Timer inputs trigger ticks; data inputs are latest-wins sampled; no
    tick before every data input produced (warm-up)."""
    ops = tmp_path / "ops.py"
    ops.write_text(
        """
from dora_tpu.tpu.api import JaxOperator

def make_model():
    def step(state, inputs):
        return state + 1, {"out": inputs["frame"] * state}
    return JaxOperator(step=step, init_state=1)
"""
    )
    descriptor = Descriptor.parse(
        {
            "nodes": [
                {
                    "id": "cam",
                    "path": "module:dora_tpu.nodehub.pyarrow_sender",
                    "outputs": ["frame"],
                },
                {
                    "id": "model",
                    "operators": [
                        {
                            "id": "m",
                            "jax": f"{tmp_path}/ops.py:make_model",
                            "inputs": {
                                "frame": {"source": "cam/frame", "queue_size": 1},
                                "tick": "dora/timer/millis/100",
                            },
                            "outputs": ["out"],
                        }
                    ],
                },
                {
                    "id": "sink",
                    "path": "module:dora_tpu.nodehub.echo",
                    "inputs": {"in": "model/m/out"},
                    "outputs": ["echo"],
                },
            ]
        }
    )
    graph = FusedGraph.build(descriptor.node("model"), descriptor)
    assert graph.timer_inputs == {"m/tick"}
    assert graph.trigger_inputs == {"m/tick"}

    executor = FusedExecutor(graph)
    # Timer fires before any frame: warm-up, no tick.
    assert executor.on_event("m/tick", None, {}) is None
    # Frame arrives: not a trigger, no tick either.
    assert executor.on_event("m/frame", pa.array([2.0]), {}) is None
    # Next timer fires: tick with the latest frame.
    out = executor.on_event("m/tick", None, {})
    np.testing.assert_allclose(out["m/out"][0].to_numpy(), [2.0])
    # Frame is sampled latest-wins: a new frame replaces the old one.
    executor.on_event("m/frame", pa.array([5.0]), {})
    out = executor.on_event("m/tick", None, {})
    np.testing.assert_allclose(out["m/out"][0].to_numpy(), [10.0])


def test_fused_executor_on_mesh(tmp_path, monkeypatch):
    """DORA_MESH: the operator's sharding rules place its weights over the
    mesh (Megatron column-split here) and the fused step runs SPMD with
    XLA-inserted collectives — multi-chip serving inside one runtime node."""
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the virtual 8-device CPU mesh")

    ops = tmp_path / "ops.py"
    ops.write_text(
        """
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dora_tpu.tpu.api import JaxOperator


def make_matmul():
    w = jax.random.normal(jax.random.PRNGKey(0), (16, 32), jnp.float32)

    def step(state, inputs):
        return state, {"y": inputs["x"] @ state["w"]}

    return JaxOperator(
        step=step,
        init_state={"w": w},
        sharding=[("w", P(None, "tp"))],
    )
"""
    )
    descriptor = Descriptor.parse(
        {
            "nodes": [
                {
                    "id": "source",
                    "path": "module:dora_tpu.nodehub.pyarrow_sender",
                    "outputs": ["data"],
                },
                {
                    "id": "model",
                    "operators": [
                        {
                            "id": "mm",
                            "jax": f"{tmp_path}/ops.py:make_matmul",
                            "inputs": {"x": "source/data"},
                            "outputs": ["y"],
                        }
                    ],
                },
                {
                    "id": "sink",
                    "path": "module:dora_tpu.nodehub.echo",
                    "inputs": {"in": "model/mm/y"},
                    "outputs": ["echo"],
                },
            ]
        }
    )
    graph = FusedGraph.build(descriptor.node("model"), descriptor)

    monkeypatch.setenv("DORA_MESH", "dp=1,tp=8,sp=1")
    sharded = FusedExecutor(graph)
    assert sharded.mesh is not None
    w_sharding = sharded.states["mm"]["w"].sharding
    assert w_sharding.spec == jax.sharding.PartitionSpec(None, "tp")
    # 8-way column split: each device holds a [16, 4] shard.
    shard_shape = w_sharding.shard_shape((16, 32))
    assert shard_shape == (16, 4)

    x = pa.array([float(i) for i in range(16)])
    out_sharded = sharded.on_event("mm/x", x, {})["mm/y"][0].to_numpy()

    monkeypatch.delenv("DORA_MESH")
    dense = FusedExecutor(FusedGraph.build(descriptor.node("model"), descriptor))
    out_dense = dense.on_event("mm/x", x, {})["mm/y"][0].to_numpy()
    np.testing.assert_allclose(out_sharded, out_dense, rtol=1e-5)


def test_mesh_from_env_partial_spec(monkeypatch):
    """'tp=4' alone must work: unspecified dp absorbs the remaining
    devices instead of failing the axis-product check."""
    import jax

    from dora_tpu.tpu.fuse import mesh_from_env

    if len(jax.devices()) != 8:
        pytest.skip("needs the virtual 8-device CPU mesh")
    monkeypatch.setenv("DORA_MESH", "tp=4")
    assert dict(mesh_from_env().shape) == {"dp": 2, "tp": 4, "sp": 1}
    monkeypatch.setenv("DORA_MESH", "dp=2,tp=2,sp=2")
    assert dict(mesh_from_env().shape) == {"dp": 2, "tp": 2, "sp": 2}
    monkeypatch.delenv("DORA_MESH")
    assert mesh_from_env() is None


# ---------------------------------------------------------------------------
# pipelined (async) serving
# ---------------------------------------------------------------------------


def test_pipelined_executor_orders_and_flushes(tmp_path):
    """Async dispatch: outputs harvest in tick order, backpressure bounds
    in-flight ticks, and a blocking flush delivers the tail."""
    descriptor = pipeline_descriptor(tmp_path)
    graph = FusedGraph.build(descriptor.node("pipeline"), descriptor)
    executor = FusedExecutor(graph, pipeline_depth=2)
    assert executor.pipeline_depth == 2

    results = []
    for i in range(5):
        executor.on_event_async("double/x", pa.array([float(i)]), {})
        results.extend(executor.harvest())
    results.extend(executor.harvest(block=True))
    assert not executor._in_flight

    assert len(results) == 5
    values = [out["plus/y"][0].to_numpy()[0] for out in results]
    np.testing.assert_allclose(values, [2 * i + 1 for i in range(5)])
    # state threaded across all five ticks
    assert int(np.asarray(executor.states["plus"])) == 5


def test_pipelined_executor_warmup_and_non_trigger(tmp_path):
    """Async path honors warm-up (no tick before every required input) and
    non-trigger observation semantics."""
    descriptor = pipeline_descriptor(tmp_path)
    graph = FusedGraph.build(descriptor.node("pipeline"), descriptor)
    executor = FusedExecutor(graph, pipeline_depth=2)
    # unknown (non-trigger) event: records nothing, dispatches nothing
    executor.on_event_async("double/other", pa.array([1.0]), {})
    assert not executor._in_flight
    executor.on_event_async("double/x", pa.array([4.0]), {})
    out = executor.harvest(block=True)
    assert len(out) == 1
    np.testing.assert_allclose(out[0]["plus/y"][0].to_numpy(), [9.0])


def test_pipeline_depth_env(monkeypatch):
    from dora_tpu.tpu import fuse

    monkeypatch.setenv("DORA_PIPELINE_DEPTH", "3")
    assert fuse.pipeline_depth_from_env() == 3
    monkeypatch.delenv("DORA_PIPELINE_DEPTH")
    # CPU backend default: synchronous
    assert fuse.pipeline_depth_from_env() == 0


def test_fetch_ring_correctness_and_flush(tmp_path):
    """fetch_every=4: outputs still arrive complete, in tick order, with
    state threaded — and a partial group flushes on harvest(block) (and
    on the linger timer for sporadic streams)."""
    descriptor = pipeline_descriptor(tmp_path)
    graph = FusedGraph.build(descriptor.node("pipeline"), descriptor)
    executor = FusedExecutor(graph, pipeline_depth=2, fetch_every=4)

    results = []
    for i in range(6):  # one full group of 4 + a partial group of 2
        executor.on_event_async("double/x", pa.array([float(i)]), {})
        results.extend(executor.harvest())
    results.extend(executor.harvest(block=True))
    assert len(results) == 6
    values = [out["plus/y"][0].to_numpy()[0] for out in results]
    np.testing.assert_allclose(values, [2 * i + 1 for i in range(6)])
    assert int(np.asarray(executor.states["plus"])) == 6
    executor.close()


def test_fetch_ring_linger_timer_flushes_partial_group(tmp_path):
    import time

    descriptor = pipeline_descriptor(tmp_path)
    graph = FusedGraph.build(descriptor.node("pipeline"), descriptor)
    executor = FusedExecutor(graph, pipeline_depth=2, fetch_every=8)
    executor._linger_s = 0.05
    executor.on_event_async("double/x", pa.array([3.0]), {})
    assert executor.harvest() == []  # staged, not yet fetched
    deadline = time.monotonic() + 5
    results = []
    while not results and time.monotonic() < deadline:
        time.sleep(0.01)
        results = executor.harvest()
    assert len(results) == 1
    np.testing.assert_allclose(results[0]["plus/y"][0].to_numpy(), [7.0])
    executor.close()


def test_fetch_ring_amortizes_injected_latency(tmp_path, monkeypatch):
    """The round-4 weakness: FPS was hostage to per-frame fetch RTT.
    Inject +60 ms per fetch: the grouped ring (fetch_every=8) must push
    N frames per round trip, beating per-tick fetching by the group
    factor (within scheduling noise) — steady throughput decoupled from
    the latency term."""
    import time

    from dora_tpu.tpu import fuse

    real = fuse._fetch

    def slow_fetch(value):
        time.sleep(0.06)
        return real(value)

    monkeypatch.setattr(fuse, "_fetch", slow_fetch)
    descriptor = pipeline_descriptor(tmp_path)
    graph = FusedGraph.build(descriptor.node("pipeline"), descriptor)

    def run(fetch_every: int, ticks: int = 24) -> float:
        executor = FusedExecutor(
            graph, pipeline_depth=2, fetch_every=fetch_every
        )
        n = 0
        t0 = time.perf_counter()
        for i in range(ticks):
            executor.on_event_async("double/x", pa.array([float(i)]), {})
            n += len(executor.harvest())
        n += len(executor.harvest(block=True))
        dt = time.perf_counter() - t0
        assert n == ticks
        executor.close()
        return dt

    run(8, ticks=4)  # warm the jit/XLA cache out of the timed runs
    grouped = run(8)
    per_tick = run(1)
    # per-tick: 24 fetches / 3 pool workers ≥ 8 serial RTTs ≈ 0.48 s.
    # grouped: 3 group fetches (≈ 0.2 s even fully serialized by the
    # in-flight-ticks backpressure bound). Margin is loose (0.65) —
    # under full-suite load scheduling noise inflates both runs.
    assert per_tick > 0.4, per_tick
    assert grouped < per_tick * 0.65, (grouped, per_tick)
