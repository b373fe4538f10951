"""Real multi-process tensor plane: two OS processes form one
jax.distributed CPU mesh through the DORA_JAX_* env contract
(`dora_tpu/parallel/distributed.py`).

Reference parity: the reference scales across machines with a daemon per
machine over TCP (SURVEY §2.9); the TPU build's tensor plane additionally
spans hosts via jax.distributed. This test proves the env contract forms
a working global mesh: each process contributes 2 virtual CPU devices,
the 4-device global mesh runs a psum whose result every process must
agree on.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import os, sys
import jax
jax.config.update("jax_platforms", "cpu")

from dora_tpu.parallel.distributed import maybe_init_distributed, global_mesh

assert maybe_init_distributed(), "env contract not picked up"
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 4, jax.devices()

import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

mesh = global_mesh(dp=4)
x = jax.device_put(
    jnp.arange(8.0).reshape(4, 2),
    NamedSharding(mesh, P("dp", None)),
)
total = jax.jit(
    lambda v: jnp.sum(v), out_shardings=NamedSharding(mesh, P())
)(x)
print("RESULT", float(total), jax.process_index(), flush=True)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_global_mesh(tmp_path):
    port = _free_port()
    env_base = dict(os.environ)
    env_base.update(
        {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
            "DORA_JAX_COORDINATOR": f"127.0.0.1:{port}",
            "DORA_JAX_NUM_PROCESSES": "2",
            "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        }
    )
    procs = []
    for pid in range(2):
        env = dict(env_base)
        env["DORA_JAX_PROCESS_ID"] = str(pid)
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", WORKER],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
        )
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=180)
        assert p.returncode == 0, f"worker failed:\n{out}\n{err}"
        outs.append(out)
    # Every process must compute the same global sum over the 4-way
    # dp-sharded array (0+1+...+7 = 28).
    for out in outs:
        line = [ln for ln in out.splitlines() if ln.startswith("RESULT")][0]
        assert line.split()[1] == "28.0", out
