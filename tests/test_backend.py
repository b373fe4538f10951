"""The rules of dora_tpu/backend.py and what chip_smoke.py leans on:
one decision about the device, no silent fallback, a compile cache placed
from outside, weights passed to the engine's programs as arguments, and a
smoke-test parent that never touches JAX."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dora_tpu import backend

ROOT = Path(__file__).resolve().parent.parent


def _run(code: str, **env) -> subprocess.CompletedProcess:
    full = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(env)
    for k in [k for k, v in full.items() if v is None]:
        del full[k]
    return subprocess.run(
        [sys.executable, "-c", code], env=full, cwd=ROOT,
        capture_output=True, text=True, timeout=120,
    )


# -- one decision, in one place ---------------------------------------------


def test_on_tpu_steers_dtype_and_interpret(monkeypatch):
    from dora_tpu.models import layers

    assert backend.interpret() and layers.compute_dtype() == jnp.float32
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    assert not backend.interpret()
    assert layers.compute_dtype() == jnp.bfloat16
    assert layers.use_flash() is True


def test_no_module_asks_the_backend_for_itself():
    """Only backend.py may call jax.default_backend(): a second caller is
    a second decision, and the compile tests could no longer steer it."""
    offenders = [
        str(p.relative_to(ROOT))
        for p in (ROOT / "dora_tpu").rglob("*.py")
        if p.name != "backend.py" and "tools" not in p.parts
        and "default_backend()" in p.read_text()
    ]
    assert offenders == []


# -- no fallback that hides the device --------------------------------------


def test_cpu_is_allowed_only_on_purpose():
    ok = _run(
        "from dora_tpu import backend; print(backend.require_accelerator('t'))",
        JAX_PLATFORMS="cpu",
    )
    assert ok.returncode == 0, ok.stderr
    assert "'platform': 'cpu'" in ok.stdout
    # the once-per-process device line names dtype and interpret flag
    line = re.search(r"dora_tpu\.backend device: (\{.*\})", ok.stderr)
    assert json.loads(line.group(1))["pallas_interpret"] is True


@pytest.mark.parametrize("entry", [
    "from dora_tpu import backend; backend.require_accelerator('t')",
    "from dora_tpu.nodehub import llm_server; llm_server.main()",
], ids=["backend", "llm_server"])
def test_without_a_chip_and_without_saying_cpu_it_fails(entry):
    # JAX_PLATFORMS unset: JAX falls back to the CPU here by itself, and
    # the program must refuse that, naming the backend it found.
    bad = _run(entry, JAX_PLATFORMS=None, DORA_STUB_ENGINE="1")
    assert bad.returncode != 0
    assert "JAX backend is 'cpu'" in bad.stderr, bad.stderr[-2000:]


def test_int8_kv_refuses_at_construction_on_tpu(monkeypatch):
    from dora_tpu.models.hf import qwen2

    cfg, params = _tiny_model()
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    with pytest.raises(NotImplementedError, match="aligned to tiling"):
        qwen2.make_paged_engine(params, cfg, kv_int8=True)


def test_peak_flops_knows_the_v5e_and_refuses_the_unknown(monkeypatch):
    from dora_tpu import profiling

    class Dev:
        def __init__(self, kind):
            self.device_kind = kind

    monkeypatch.delenv("DORA_DEVICE_PEAK_FLOPS", raising=False)
    assert profiling.detect_peak_flops(Dev("TPU v5 lite")) == 197e12
    assert profiling.detect_peak_flops(Dev("cpu")) == 0.0  # explicit CPU mode
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    with pytest.raises(LookupError, match="TPU v9"):
        profiling.detect_peak_flops(Dev("TPU v9"))


def test_profiler_that_cannot_start_is_an_error_on_tpu(monkeypatch, tmp_path):
    from dora_tpu import profiling

    def boom(_dir, **_options):
        raise RuntimeError("no profiler here")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    # CPU-for-tests: the synthetic marker keeps the control plane testable.
    err = profiling.start_capture(str(tmp_path / "a"))
    assert "no profiler here" in err
    assert profiling.stop_capture(str(tmp_path / "a"), err).endswith(
        "profile_synthetic.json"
    )
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    with pytest.raises(RuntimeError, match="no profiler here"):
        profiling.start_capture(str(tmp_path / "b"))


# -- compile cache placed from outside --------------------------------------

_CACHE_PROBE = (
    "import jax, jax.numpy as jnp\n"
    "from dora_tpu import backend\n"
    "where = backend.init_compile_cache()\n"
    "jax.jit(lambda x: x * 2 + 1)(jnp.ones((8, 8))).block_until_ready()\n"
    "print(where, '|', jax.config.jax_compilation_cache_dir)\n"
)


def test_compile_cache_goes_where_the_environment_says(tmp_path):
    placed = tmp_path / "placed"
    out = _run(_CACHE_PROBE, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(placed))
    assert out.returncode == 0, out.stderr
    where, configured = out.stdout.strip().split(" | ")
    assert where == configured == str(placed)  # nothing else set in code
    assert any(placed.iterdir()), "the compiling process wrote no entry"


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(monkeypatch):
    assert backend.COMPILE_CACHE_DIR == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    # The CPU-for-tests mode caches only where it is told to.
    out = _run(_CACHE_PROBE, JAX_PLATFORMS="cpu")
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "None | None"
    # On the chip (steered) the default is the fixed path.
    seen = {}
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: seen.__setitem__(k, v)
    )
    assert backend.init_compile_cache() == str(ROOT / ".jax_cache")
    assert seen["jax_compilation_cache_dir"] == str(ROOT / ".jax_cache")


# -- weights are arguments, not constants -----------------------------------


def _tiny_model():
    from dora_tpu.models.hf import qwen2

    cfg = qwen2.Qwen2Config(
        vocab=256, dim=64, layers=2, heads=4, kv_heads=2, ffn=128,
        rope_theta=10000.0, norm_eps=1e-6, tie_embeddings=True, max_seq=32,
    )
    rng = np.random.default_rng(0)
    r = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.05, jnp.float32)
    kv = cfg.kv_heads * cfg.head_dim
    params = {
        "embed": r(cfg.vocab, cfg.dim), "out_norm": jnp.ones((cfg.dim,)),
        "blocks": {
            str(i): {
                "attn_norm": jnp.ones((cfg.dim,)), "ffn_norm": jnp.ones((cfg.dim,)),
                "wq": r(cfg.dim, cfg.dim), "wk": r(cfg.dim, kv),
                "wv": r(cfg.dim, kv), "wo": r(cfg.dim, cfg.dim),
                "w_gate": r(cfg.dim, cfg.ffn), "w_up": r(cfg.dim, cfg.ffn),
                "w_down": r(cfg.ffn, cfg.dim),
            }
            for i in range(cfg.layers)
        },
    }
    return cfg, qwen2.quantize_decode(params, cfg)


def _largest_constant(lowered_text: str) -> int:
    biggest = 0
    for m in re.finditer(r"stablehlo\.constant.*?: tensor<([0-9x]*)x?\w+>", lowered_text):
        dims = [int(d) for d in m.group(1).split("x") if d]
        biggest = max(biggest, int(np.prod(dims)) if dims else 1)
    return biggest


def _tiny_kimi(tmp_path):
    from test_kimi_k2 import TINY, write_checkpoint

    from dora_tpu.models.hf import kimi_k2

    write_checkpoint(tmp_path / "ckpt", TINY)
    return kimi_k2.load(tmp_path / "ckpt", max_seq=64)


def _tiny_falcon(tmp_path):
    from test_falcon_h1 import TINY, write_checkpoint

    from dora_tpu.models.hf import falcon_h1

    write_checkpoint(tmp_path / "ckpt", TINY)
    return falcon_h1.load(tmp_path / "ckpt", max_seq=64)


def _tiny_ouro(tmp_path):
    from test_ouro import TINY, write_checkpoint

    from dora_tpu.models.hf import ouro

    write_checkpoint(tmp_path / "ckpt", TINY)
    return ouro.load(tmp_path / "ckpt", max_seq=64)


def _tiny_exaone(tmp_path):
    from test_exaone_moe import TINY, write_checkpoint

    from dora_tpu.models.hf import exaone_moe

    write_checkpoint(tmp_path / "ckpt", TINY)
    return exaone_moe.load(tmp_path / "ckpt", max_seq=64)


def _tiny_glm5(tmp_path):
    from tests.glm5_next_tiny import TINY, write_checkpoint

    from dora_tpu.models.hf import glm5_next

    write_checkpoint(tmp_path / "ckpt", TINY)
    return glm5_next.load(tmp_path / "ckpt", max_seq=64)


def _tiny_keye(tmp_path):
    from test_keye_vl2 import TINY, write_checkpoint

    from dora_tpu.models.hf import keye_vl2

    write_checkpoint(tmp_path / "ckpt", TINY)
    return keye_vl2.load(tmp_path / "ckpt", max_seq=64)


def _tiny_zaya(tmp_path):
    from test_zaya import TINY, write_checkpoint

    from dora_tpu.models.hf import zaya

    write_checkpoint(tmp_path / "ckpt", TINY)
    return zaya.load(tmp_path / "ckpt", max_seq=64)


def _tiny_olmo(tmp_path):
    from tests.olmo_hybrid_tiny import TINY, write_checkpoint

    from dora_tpu.models.hf import olmo_hybrid

    write_checkpoint(tmp_path / "ckpt", TINY)
    return olmo_hybrid.load(tmp_path / "ckpt", max_seq=64)


def _tiny_kimi_linear(tmp_path):
    from tests.kimi_linear_tiny import TINY, write_checkpoint

    from dora_tpu.models.hf import kimi_linear

    write_checkpoint(tmp_path / "ckpt", TINY)
    return kimi_linear.load(tmp_path / "ckpt", max_seq=64)


_TINY = {"kimi_k2": _tiny_kimi, "falcon_h1": _tiny_falcon, "ouro": _tiny_ouro,
         "exaone_moe": _tiny_exaone, "glm5_next": _tiny_glm5,
         "keye_vl2": _tiny_keye, "zaya": _tiny_zaya,
         "olmo_hybrid": _tiny_olmo, "kimi_linear": _tiny_kimi_linear}


def _engine_programs(module_name, monkeypatch, tmp_path):
    """Run a request through the tiny engine of ``module_name`` and
    return what it jitted: program -> (abstract arguments of its first
    call, whether it was handed the parameter tree)."""
    import importlib

    module = importlib.import_module(f"dora_tpu.models.hf.{module_name}")
    cfg, params = (
        _tiny_model() if module_name == "qwen2" else _TINY[module_name](tmp_path)
    )
    seen: dict = {}
    real_jit = jax.jit

    def spy_jit(fn, **kw):
        jitted = real_jit(fn, **kw)

        def call(*args, **static):
            if static:  # a kernel wrapper imported meanwhile, not ours
                return jitted(*args, **static)
            if jitted not in seen:
                shapes = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
                    args,
                )
                seen[jitted] = (shapes, bool(args) and args[0] is params)
            return jitted(*args)

        return call

    monkeypatch.setattr(jax, "jit", spy_jit)
    engine = module.make_paged_engine(
        params, cfg, max_slots=2, page_size=8, chunk=16, window=2
    )
    monkeypatch.undo()
    engine.submit("r", [5, 7, 11], 4)
    for _ in range(20):
        engine.step()
        if not engine.active:
            break
    assert not engine.active
    assert sum(took for _shapes, took in seen.values()) == 2  # window, chunk
    return seen


@pytest.mark.parametrize(
    "module_name",
    ["qwen2", "kimi_k2", "falcon_h1", "ouro", "exaone_moe", "glm5_next",
     "keye_vl2", "zaya", "olmo_hybrid", "kimi_linear"])
def test_engine_programs_take_the_weights_as_arguments(
    module_name, monkeypatch, tmp_path
):
    """A closed-over array lowers to a stablehlo.constant — gigabytes of
    weights inside every executable and cache entry at full width. No
    program the engine jits (window, chunk, slot insert) may hold a
    weight-sized constant, and the window and chunk programs must be
    handed the parameter tree itself: the smallest weight matrix of
    either tiny model has 2048 elements, the largest legitimate
    constant (a rope table) 512."""
    seen = _engine_programs(module_name, monkeypatch, tmp_path)
    for jitted, (shapes, _took) in seen.items():
        text = jitted.lower(*shapes).as_text()
        assert "stablehlo" in text
        assert _largest_constant(text) < 1024, jitted


# -- weights reach the kernels as stored --------------------------------------


def _weight_copies(jaxpr) -> list[str]:
    """Equations of ``jaxpr`` (sub-programs included; a ``pallas_call``
    is one equation here, its body is not walked) that copy a quantized
    weight on its way to a kernel: a ``pad`` or ``concatenate`` with an
    int8 / packed-int4 operand."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("pad", "concatenate") and any(
            getattr(v.aval, "dtype", None) in (jnp.int8, jnp.uint8)
            for v in eqn.invars
        ):
            found.append(f"{eqn.primitive.name} {[v.aval for v in eqn.invars]}")
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _weight_copies(sub)
    return found


@pytest.mark.parametrize(
    "kernel,m,k,n",
    [
        # Qwen2.5-1.5B's head: 151,936 = 128 x 1187 columns, a decode tick
        ("lm_head_argmax", 16, 1536, 151936),
        # Kimi-K2's K = 7168 = 3.5 x 2048, a prefill chunk
        ("int8_matmul", 256, 7168, 4096),
        # its w_qkv_a: N = 2112 is 16.5 lanes, chunk and tick
        ("int8_matmul", 256, 7168, 2112),
        ("int8_matmul", 16, 7168, 2112),
    ],
)
def test_kernels_take_the_stored_weight(kernel, m, k, n):
    """No weight is copied inside a serving program: at the published
    widths that used to be padded in every tick or chunk, nothing of
    int8 is padded between the arguments and the ``pallas_call``."""
    from dora_tpu.ops.decode_block import lm_head_argmax
    from dora_tpu.ops.int8_matmul import int8_matmul

    s = jax.ShapeDtypeStruct
    x, w, scale = s((m, k), jnp.float32), s((k, n), jnp.int8), s((1, n), jnp.float32)
    if kernel == "lm_head_argmax":
        traced = jax.make_jaxpr(lm_head_argmax)(x, s((k,), jnp.float32), w, scale)
    else:
        traced = jax.make_jaxpr(int8_matmul)(x, w, scale)
    assert "pallas_call" in str(traced)
    assert _weight_copies(traced.jaxpr) == []


@pytest.mark.parametrize(
    "module_name",
    ["qwen2", "kimi_k2", "falcon_h1", "ouro", "exaone_moe", "glm5_next",
     "keye_vl2", "zaya", "olmo_hybrid", "kimi_linear"])
def test_engine_programs_copy_no_weight(module_name, monkeypatch, tmp_path):
    """The same walk over the window and chunk programs as the engine
    jits them (tiny models: vocab 256 and 128 are no multiple of the
    head's 2048-column tile, hidden 64 is no lane multiple)."""
    seen = _engine_programs(module_name, monkeypatch, tmp_path)
    for jitted, (shapes, took) in seen.items():
        if took:  # the window and the chunk program, the helper saw both
            traced = jax.make_jaxpr(jitted)(*shapes)
            assert "pallas_call" in str(traced)
            assert _weight_copies(traced.jaxpr) == [], jitted


def _pool_writers(jaxpr, shape) -> list[str]:
    """Equations of ``jaxpr`` (sub-programs included) that produce an
    array of a page pool's ``shape`` and are neither a ``pallas_call``
    (which aliases the pool in and out) nor control flow that carries it
    (walked instead): a scatter, a ``dynamic_update_slice``, a
    ``select`` or a ``copy`` of the whole pool."""
    found = []
    for eqn in jaxpr.eqns:
        subs = list(jax.core.jaxprs_in_params(eqn.params))
        if eqn.primitive.name == "pallas_call":
            continue
        if subs:
            for sub in subs:
                found += _pool_writers(sub, shape)
        elif any(getattr(v.aval, "shape", None) == shape for v in eqn.outvars):
            found.append(eqn.primitive.name)
    return found


@pytest.mark.parametrize("module_name", ["qwen2", "ouro"])
def test_engine_programs_copy_no_pool(module_name, monkeypatch, tmp_path):
    """Between the donated pools and the kernels that alias them nothing
    makes a pool-sized array: not in the window's scan, and not in the
    looped model's pass loop inside it, whose pools are ``passes`` deep
    and ride two carries."""
    seen = _engine_programs(module_name, monkeypatch, tmp_path)
    for jitted, (shapes, took) in seen.items():
        if took:
            pool = shapes[2]["0"]["k"].shape  # params, ids / tokens, pools
            traced = jax.make_jaxpr(jitted)(*shapes)
            assert "pallas_call" in str(traced)
            assert _pool_writers(traced.jaxpr, pool) == [], jitted


def _calls_and_pool_gathers(jaxpr, shape, inside_while=False):
    """(names of the jitted kernel wrappers called, sub-programs
    included; ``gather`` equations over an array of ``shape`` that sit
    inside a ``while``)."""
    calls, gathers = [], []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("jit", "pjit"):
            calls.append(eqn.params["name"])
        if name == "gather" and inside_while and (
                getattr(eqn.invars[0].aval, "shape", None) == shape):
            gathers.append(str(eqn.invars[0].aval))
        if name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            c, g = _calls_and_pool_gathers(
                sub, shape, inside_while or name == "while")
            calls += c
            gathers += g
    return calls, gathers


def test_exaone_window_reads_the_global_pages_through_the_sweep(
        monkeypatch, tmp_path):
    """The decode window holds one ``attention_paged_rows_step`` a global
    layer (the tiny model has one) and no block loop: nothing gathers a
    ``[P, page, 2 * KV * hd]`` pool inside a ``while``. The chunk
    program keeps its loop (one stream, to its own position), which is
    what the same walk finds there."""
    seen = _engine_programs("exaone_moe", monkeypatch, tmp_path)
    found = {}
    for jitted, (shapes, took) in seen.items():
        if took:
            (pool,) = [leaf["kv"].shape for leaf in shapes[2].values()]
            calls, gathers = _calls_and_pool_gathers(
                jax.make_jaxpr(jitted)(*shapes).jaxpr, pool)
            found[calls.count("attention_paged_rows_step")] = gathers
    assert found[1] == [], "the window gathers pool pages in a loop"
    assert len(found[0]) == 1, "the chunk's block loop: one gather of pages"


# -- the smoke test's parent stays off JAX ----------------------------------


def _leaf():
    return None


def _faults_a_call(depth: int, calls: int = 100) -> float:
    """Minor page faults a call of a trivial function, called ``calls``
    times from ``depth`` frames further down this thread's stack."""
    import resource

    if depth:
        return _faults_a_call(depth - 1, calls)
    before = resource.getrusage(resource.RUSAGE_THREAD).ru_minflt
    for _ in range(calls):
        _leaf()
    return (resource.getrusage(resource.RUSAGE_THREAD).ru_minflt - before) / calls


def _chunk_edges(roomy: bool, depths=range(0, 600)) -> list[int]:
    """The depths at which every call faults a page in, seen from a
    thread of its own: its frame stack starts empty, whatever called."""
    import threading

    def scan():
        return [d for d in depths if _faults_a_call(d) >= 0.9]

    out: list = []
    thread = threading.Thread(
        target=lambda: out.append(backend.roomy(scan) if roomy else scan())
    )
    thread.start()
    thread.join()
    return out[0]


def test_roomy_takes_the_chunk_edge_of_the_frame_stack_out_from_under_a_call():
    """CPython keeps a thread's frames in 16 KiB chunks and unmaps a
    chunk when the frame that opened it returns: from some depth, every
    call of a small function maps and faults in a chunk. ``backend.roomy``
    opens one chunk that holds everything below it: no depth has the
    edge. Counts of page faults, not seconds."""
    if not hasattr(__import__("resource"), "RUSAGE_THREAD"):
        pytest.skip("no per-thread rusage on this platform")
    assert backend.roomy(lambda a, b: a + b, 2, 3) == 5  # operands go through
    edges = _chunk_edges(roomy=False)
    if not edges:
        pytest.skip("this interpreter or kernel shows no chunk edge to take away")
    # plain: an edge every 16 KiB of frames; roomy: none in 1 MiB of them
    assert _chunk_edges(roomy=True) == [], edges


def test_chip_smoke_parent_never_imports_jax():
    out = _run(
        "import chip_smoke, sys; assert 'jax' not in sys.modules; "
        "import bench_vlm, dora_tpu.daemon, dora_tpu.native; "
        "assert 'jax' not in sys.modules",
        JAX_PLATFORMS="cpu",
    )
    assert out.returncode == 0, out.stderr


def test_chip_smoke_refuses_to_pass_off_the_chip():
    """With the CPU it exits non-zero after its first child reports the
    platform, and prints no ok line — in seconds, not after a CPU grind
    through a 28-layer model."""
    out = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")], cwd=ROOT,
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and "no TPU" in last["error"]


def test_chip_smoke_token_codes_round_trip():
    import chip_smoke

    ids = [0, 61, 62, 3843, 3844, 151935]
    text = "".join(map(chip_smoke.token_code, ids))
    assert chip_smoke.code_tokens(text) == ids
    assert chip_smoke.agreed([1, 2, 3, 4], [1, 2, 9, 4]) == 2
