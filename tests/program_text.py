"""What the server lowers, as a table of hashes: the check that a change
to shared code left every model's programs alone (ROADMAP debt 21 (b)).

Not a test module. For each of the ten serving model files a tiny
checkpoint is written, loaded and served as ``llm_server.make_engine``
builds it, one stream runs one chunk and two windows, and every module
JAX lowered on the way (``jax_dump_ir_to``: the StableHLO of each jit,
the eager one-op programs among them) is reduced to ``sha256`` of its
text with debug locations stripped, followed by the sorted operation
names its name locations held (the ``jax.named_scope`` paths a trace is
reduced by live only there). Two trees whose tables are equal lower the
same programs under the same names, wherever their source lines moved.

    python -m tests.program_text --out FILE                 # the ten, tiny, CPU
    python -m tests.program_text --chip-compile --out FILE  # real widths, v5e

``--chip-compile`` runs ``tests/test_chip_compile.py`` in this process
and hashes what it lowers for the described v5e. A Mosaic kernel's
serialized body carries its call stack's file, line and column, so its
debug info is stripped ahead of ``mosaic-serde`` (in this tool only).
Compare two trees with ``diff`` on the two files; unpack both at one
path if the comparison is to say anything about a compile cache.
"""

from __future__ import annotations

import argparse
import contextlib
import hashlib
import json
import os
import re
import sys
import tempfile
from pathlib import Path
from unittest import mock

#: the engine's size in a tiny run: ``llm_server.make_engine`` reads these
TINY_ENV = {
    "DORA_BATCH_SLOTS": "3", "DORA_PAGE_SIZE": "8", "DORA_PREFILL_CHUNK": "32",
    "DORA_MULTISTEP_K": "4", "DORA_INT8_DECODE": "1",
}
MAX_SEQ = 128

#: an operation's name path (``"jit(program)/while/body/moe_router/dot_general"``)
#: or an argument's name (``"args[2]['0']['kv']"``); never a stack frame's
#: function name (``"build_engine"(#loc4)``) or file (``"a.py":1:2``)
_NAME_LOC = re.compile(r'loc\("(jit\([^"]*)"|loc\("([^"]*)"\)')


def strip_locations(text: str) -> str:
    """MLIR text without ``#loc`` lines and ``loc(...)`` attributes
    (parentheses nest: ``loc(callsite("f"("a.py":1:2) at ...))``)."""
    out, i = [], 0
    for m in re.finditer(r"\bloc\(", text):
        if m.start() < i:  # inside one already dropped
            continue
        out.append(text[i:m.start()])
        depth, k, quoted = 1, m.end(), False
        while depth:
            c = text[k]
            if c == '"' and text[k - 1] != "\\":
                quoted = not quoted
            elif not quoted:
                depth += (c == "(") - (c == ")")
            k += 1
        i = k
    out.append(text[i:])
    lines = "".join(out).splitlines()
    return "\n".join(
        line.rstrip() for line in lines if not line.startswith("#loc"))


def module_hash(text: str) -> str:
    names = sorted({a or b for a, b in _NAME_LOC.findall(text)})
    return hashlib.sha256(
        (strip_locations(text) + "\n" + "\n".join(names)).encode()).hexdigest()


def table_of(dump_dir: Path) -> dict[str, list[str]]:
    """``{module name: sorted hashes}`` of a ``jax_dump_ir_to`` directory
    (a name is lowered more than once for more than one shape)."""
    table: dict[str, list[str]] = {}
    for path in sorted(dump_dir.glob("*.mlir")):
        name = re.sub(r"^jax_ir\d+_|_compile$", "", path.stem)
        table.setdefault(name, []).append(module_hash(path.read_text()))
    return {name: sorted(hashes) for name, hashes in sorted(table.items())}


@contextlib.contextmanager
def dumped(dump_dir: Path):
    """Everything lowered inside goes to ``dump_dir``; nothing lowered
    before is reused (the jit caches are dropped on the way in)."""
    import jax

    jax.clear_caches()
    jax.config.update("jax_dump_ir_to", str(dump_dir))
    try:
        yield
    finally:
        jax.config.update("jax_dump_ir_to", None)


def _qwen2_checkpoint(path: Path) -> None:
    import torch
    from transformers import Qwen2Config, Qwen2ForCausalLM

    torch.manual_seed(0)
    Qwen2ForCausalLM(Qwen2Config(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=MAX_SEQ, tie_word_embeddings=False,
    )).save_pretrained(path, safe_serialization=True)


def write_checkpoint(name: str, path: Path) -> None:
    """The tests' own tiny checkpoint of model file ``name``."""
    if name == "qwen2":
        return _qwen2_checkpoint(path)
    import importlib

    try:
        tiny = importlib.import_module(f"tests.{name}_tiny")
    except ImportError:  # the others keep theirs in the test module
        tiny = importlib.import_module(f"tests.test_{name}")
    tiny.write_checkpoint(path, tiny.TINY)


def serve_tiny(name: str, checkpoint: Path) -> None:
    """Load ``checkpoint`` and serve one stream as ``llm_server`` would:
    a prompt of 20 tokens (one chunk) and 7 new ones (two windows of 4)."""
    from dora_tpu.nodehub import llm_server

    module = llm_server.model_module(
        json.loads((checkpoint / "config.json").read_text())["model_type"])
    cfg, params = module.load(checkpoint, max_seq=MAX_SEQ)
    engine = llm_server.make_engine(
        module.quantize_decode(params, cfg), cfg, module=module)
    engine.submit("r", list(range(1, 21)), 7)
    for _ in range(50):
        if any(done for _, _, done in engine.step()):
            return
    raise RuntimeError(f"{name}: the stream never finished")


MODELS = ("qwen2", "kimi_k2", "falcon_h1", "ouro", "exaone_moe", "glm5_next",
          "keye_vl2", "zaya", "olmo_hybrid", "kimi_linear")


def tiny_tables(models=MODELS) -> dict[str, dict[str, list[str]]]:
    tables = {}
    with mock.patch.dict(os.environ, TINY_ENV):
        for name in models:
            with tempfile.TemporaryDirectory() as tmp:
                checkpoint, dump = Path(tmp, "ckpt"), Path(tmp, "ir")
                write_checkpoint(name, checkpoint)
                with dumped(dump):
                    serve_tiny(name, checkpoint)
                tables[name] = table_of(dump)
    return tables


def strip_kernel_locations() -> None:
    """From here on a Mosaic kernel is serialized without debug info."""
    from jax._src import tpu_custom_call as tcc
    from jaxlib.mlir.passmanager import PassManager

    serialize = tcc._lower_mosaic_module_to_asm

    def stripped(module, **kw):
        with module.context:
            PassManager.parse("builtin.module(strip-debuginfo)").run(
                module.operation)
        return serialize(module, **kw)

    tcc._lower_mosaic_module_to_asm = stripped


def chip_compile_table() -> dict[str, list[str]]:
    import pytest

    strip_kernel_locations()
    with tempfile.TemporaryDirectory() as tmp:
        with dumped(Path(tmp)):
            rc = pytest.main([
                str(Path(__file__).with_name("test_chip_compile.py")), "-q",
                "-p", "no:cacheprovider", "-p", "no:randomly"])
        if rc:
            raise SystemExit(f"tests/test_chip_compile.py: exit {rc}")
        return table_of(Path(tmp))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True, type=Path)
    ap.add_argument("--chip-compile", action="store_true")
    ap.add_argument("--models", nargs="*", default=list(MODELS))
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    tables = (
        {"test_chip_compile": chip_compile_table()} if args.chip_compile
        else tiny_tables(args.models))
    args.out.write_text(json.dumps(tables, indent=1, sort_keys=True) + "\n")
    for name, table in tables.items():
        print(f"{name}: {sum(map(len, table.values()))} modules, "
              f"{len(table)} names", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
