"""The seam between the engine and a model (models/paged_model.py), and
the import rule that keeps it one: a file under ``models/hf/`` takes what
it shares with another from BELOW (``models/*.py``, ``ops/``), never from
a file beside it.

(a) the import graph of ``models/hf/``, read from the sources; (b) the
counters' host side over a wrap and a nested tree; (c) the pool's bytes
rule at the three cells that use it; (d) the refusal of knobs by name.
"""

from __future__ import annotations

import ast
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from dora_tpu.models import paged_model as PM

HF = Path(PM.__file__).parent / "hf"
#: every module under models/hf/ but the package file
FILES = sorted(p.stem for p in HF.glob("*.py") if p.stem != "__init__")
#: the nine files whose engines ``build_engine`` builds
BUILT = ("kimi_k2", "falcon_h1", "ouro", "exaone_moe", "glm5_next", "keye_vl2",
         "zaya", "olmo_hybrid", "kimi_linear")
#: a tower over a whole text model sits ABOVE that model's file (the
#: frames tier, ROADMAP debt 2); nothing else may look sideways
WRAPS = {"internvl": {"qwen2"}, "qwen2_vl": {"qwen2"}}


def imported(path: Path) -> set[str]:
    """Every module a file imports, at any depth of it, absolute."""
    found = set()
    package = "dora_tpu.models.hf"
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            found.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:  # relative: from . import x, from .x import y
                up = package.rsplit(".", node.level - 1)[0]
                base = f"{up}.{base}".rstrip(".")
            found.add(base)
            # ``from dora_tpu.models.hf import kimi_k2`` names a module too
            found.update(f"{base}.{a.name}" for a in node.names)
    return found


# -- (a) no model file imports another ------------------------------------------


@pytest.mark.parametrize("name", FILES)
def test_a_model_file_imports_no_other_model_file(name):
    """``loader`` is the one shared file of the directory; a
    ``<model>_reference`` is its model's own float32 twin and reads that
    model's config class; :data:`WRAPS` are the towers."""
    allowed = {"loader", name} | WRAPS.get(name, set())
    if name.endswith("_reference"):
        allowed.add(name.removesuffix("_reference"))
    beside = {
        m.split(".")[3] for m in imported(HF / f"{name}.py")
        if m.startswith("dora_tpu.models.hf.") and m.split(".")[3] in FILES
    }
    assert beside <= allowed, (
        f"models/hf/{name}.py imports {sorted(beside - allowed)}: what two "
        f"model files share lives below them (models/moe.py, layers.py, "
        f"paged_model.py, paged_window.py)")


@pytest.mark.parametrize("name", BUILT)
def test_the_window_comes_from_its_own_module_not_the_frames_tier(name):
    mods = imported(HF / f"{name}.py")
    assert "dora_tpu.models.paged_window" in mods
    assert not any(m.startswith("dora_tpu.models.vlm") for m in mods)
    # and the engine is built in one place
    assert not any(m.startswith("dora_tpu.models.batch_engine") for m in mods)
    calls = {
        n.func.attr for n in ast.walk(ast.parse((HF / f"{name}.py").read_text()))
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)}
    assert "build_engine" in calls


# -- (b) the counters' host side -------------------------------------------------


def i32(value: int):
    """``value`` modulo 2^32 as the int32 the device holds."""
    return jnp.asarray(np.uint32(value % 2 ** 32).astype(np.int32))


@pytest.mark.parametrize("first,second", [
    (5, 12),                          # no wrap
    (2 ** 31 - 3, 2 ** 31 + 4),       # past the sign bit
    (2 ** 32 - 2, 2 ** 32 + 9),       # past 2^32: the device reads 9
    (2 ** 32 - 2, 2 ** 33 - 3),       # all but a whole turn in one read
])
def test_gained_is_the_difference_modulo_2_to_the_32(first, second):
    counters = PM.DeviceCounters({"n": i32(0)})
    counters.device = {"n": i32(first)}
    assert counters.gained()["n"] == first
    counters.device = {"n": i32(second)}
    assert counters.gained()["n"] == second - first
    assert counters.totals["n"] == second and counters.totals["n"].dtype == np.int64


def test_gained_walks_a_nested_tree_and_keeps_its_shape():
    tree = {"moe": {"tokens": i32(0), "expert_tokens": jnp.zeros((2, 3), jnp.int32)},
            "swa": {"ticks": i32(0)}}
    counters = PM.DeviceCounters(tree)
    assert counters.totals["moe"]["expert_tokens"].shape == (2, 3)
    per = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    for turn in (1, 2):
        counters.device = {
            "moe": {"tokens": i32(7 * turn), "expert_tokens": per * turn},
            "swa": {"ticks": i32(2 ** 32 - 1 + turn)}}
        got = counters.gained()
        assert got["moe"]["tokens"] == 7 and got["swa"]["ticks"] == (
            2 ** 32 if turn == 1 else 1) % 2 ** 32
        assert (got["moe"]["expert_tokens"] == np.asarray(per)).all()
    assert counters.totals["moe"]["tokens"] == 14
    assert (counters.totals["moe"]["expert_tokens"] == 2 * np.asarray(per)).all()
    assert counters.totals["swa"]["ticks"] == 1  # 2^32 reads as a whole turn: 0


# -- (c) the pool's default size is one rule in bytes ---------------------------

V5E = 16_909_336_064  # bytes_limit of a 16 GB v5e


@pytest.mark.parametrize("cell,page_bytes,used,max_seq,multiple,want", [
    # 192 entries x 2 x 16 x 128 x 2 B x 16 rows; (16.91 - 2.77 - 4.29) GB
    # over it = 391, down to a multiple of ouro.POOL_PAGE_MULTIPLE
    ("ouro-2p6b.loop-chat-16", 25_165_824, 2_770_000_000, 2048, 8, 384),
    # 8,192 B and 1,088 B a token: every slot reaches max_seq
    ("k-exaone-236b-ep8.mixed-len-16", 16 * 8192, 6_200_000_000, 16384, 1,
     16 * 16384 // 16 + 1),
    ("glm-5p3-flash-ep8.long-ctx-16", 16 * 1088, 5_000_000_000, 16384, 1,
     16 * 16384 // 16 + 1),
    # 26,112 B a token: 6.85 GB of (16.91 - 1.4 - 4.29) GB, still every slot
    ("keye-vl2-30b-ep8.video-qa-16", 16 * 26112, 1_400_000_000, 16384, 1,
     16 * 16384 // 16 + 1),
    # 20,480 B a token: 2.68 GB of (16.91 - 5.0 - 4.29) GB, every slot
    ("zaya1-8b-pp2.reason-16", 16 * 20480, 5_000_000_000, 8192, 1,
     16 * 8192 // 16 + 1),
    # 61,440 B a token; in use: 4.49 GB of weights, 16 slots' state and 35
    # snapshots of 27.4 MB: 6.72 GB of pages, memory's bound and not the cap
    ("olmo-hybrid-7b-pp2.sessions-16", 16 * 61440,
     4_490_000_000 + (16 + 35) * 27_371_520, 12288, 1, 6844),
])
def test_pages_that_fit_gives_each_cell_its_pool(cell, page_bytes, used,
                                                 max_seq, multiple, want):
    assert PM.pages_that_fit(page_bytes, V5E, used, 16, max_seq, 16,
                             multiple=multiple) == want


def test_pages_that_fit_never_goes_under_two_streams_and_the_cpu_has_its_own():
    assert PM.pages_that_fit(25_165_824, 8 << 30, 2_770_000_000, 16, 2048, 16,
                             multiple=8) == 2 * 2048 // 16
    # the CPU reports no memory figures
    assert PM.default_num_pages(25_165_824, 16, 2048, 16, multiple=8) == 4 * 2048 // 16


def test_the_five_models_rules_are_that_one(monkeypatch):
    from dora_tpu.models.hf import exaone_moe, glm5_next, keye_vl2, ouro, zaya

    seen = []
    monkeypatch.setattr(PM, "default_num_pages",
                        lambda *a, **kw: seen.append((a, kw)) or 99)

    class Cfg:
        max_seq, kv_bytes_per_token = 2048, 1000

    for module in (ouro, exaone_moe, glm5_next, keye_vl2, zaya):
        assert module.default_num_pages(Cfg, 16, 16) == 99
    assert [a for a, _ in seen] == [(16_000, 16, 2048, 16)] * 5
    assert [kw for _, kw in seen] == [
        {"multiple": ouro.POOL_PAGE_MULTIPLE}, {}, {}, {}, {}]


# -- (d) knobs the model does not offer are refused by name ----------------------

KNOBS = {"DORA_KV_INT8": "no int8 pages here", "DORA_SPEC_K": "no verify pass",
         "DORA_LORA_DIR": "no adapters"}


@pytest.mark.parametrize("knob", sorted(KNOBS))
def test_build_engine_refuses_a_knob_by_name_before_it_builds_anything(
        monkeypatch, knob):
    monkeypatch.setenv(knob, "1")
    with pytest.raises(NotImplementedError) as err:
        PM.build_engine(
            "toy", None, None, window_program=None, chunk_step=None,
            donate_window=(), donate_chunk=(), init_page_pool=None,
            counters=None, report=None, not_offered=KNOBS, flops_per_token=0.0,
            max_slots=1, eos=None, page_size=8, chunk=8, num_pages=4,
            window=None, prefix_cache=None, prefix_cache_pages=None)
    assert str(err.value) == f"toy: {knob} is not offered: {KNOBS[knob]}"


def test_a_built_engine_goes_when_its_last_holder_lets_go(monkeypatch):
    """No reference cycle through the engine: a cache audit builds one
    inside a function and the reference that runs next needs the pool's
    memory back at once, not at the collector's next pass (on the chip
    Ouro's pool is 9.66 GB of 16)."""
    import gc
    import weakref

    class Engine:
        def __init__(self, **kw):
            self.kw, self.allocator = kw, object()

        def snapshot_stats(self):
            return {}

    monkeypatch.setattr(PM, "PagedBatchEngine", Engine)
    for knob in KNOBS:
        monkeypatch.delenv(knob, raising=False)
    gc.disable()
    try:
        engine = PM.build_engine(
            "toy", type("Cfg", (), {"max_seq": 64}), {"w": 1},
            window_program=lambda p, k, *a: a, chunk_step=lambda p, *a: a,
            donate_window=(), donate_chunk=(), init_page_pool=dict,
            counters={"n": i32(3)}, report=lambda totals, engine: {
                "n": int(totals["n"]), "engine": engine},
            not_offered=KNOBS, flops_per_token=1.0, max_slots=1, eos=None,
            page_size=8, chunk=8, num_pages=4, window=2, prefix_cache=None,
            prefix_cache_pages=None)
        assert engine.model_counters() == {"n": 3, "engine": engine}
        gone = weakref.ref(engine)
        del engine
        assert gone() is None
    finally:
        gc.enable()


@pytest.mark.parametrize("value", ["", "0"])
def test_a_knob_set_to_nothing_is_not_a_request(monkeypatch, value):
    """``DORA_SPEC_K=0`` in a deployment's environment refuses nothing:
    the refusal is passed and the build goes on (to this toy's first
    fault, its missing counters)."""
    monkeypatch.setenv("DORA_SPEC_K", value)
    with pytest.raises(AttributeError):
        PM.build_engine(
            "toy", None, None, window_program=None, chunk_step=None,
            donate_window=(), donate_chunk=(), init_page_pool=None,
            counters=[object()], report=None, not_offered=KNOBS,
            flops_per_token=0.0, max_slots=1, eos=None, page_size=8, chunk=8,
            num_pages=4, window=None, prefix_cache=None,
            prefix_cache_pages=None)
