"""driver (outside) -> openai_server -> llm_server, serving a
``kimi_linear`` checkpoint: the same two nodes, queues and profiling
control as ``graphs/openai_llm.py`` (whose functions this module reuses),
with the rank-0 shard checkpoint of ``lib/checkpoint_kimi_linear.py``. ``llm_server``
picks the model module from the checkpoint's ``model_type``; a program
that cannot serve the type fails at start-up, before it is ready.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

from checkpoint_kimi_linear import write_checkpoint  # benchmark/lib, on run.py's path

_spec = importlib.util.spec_from_file_location(
    "bench_graphs_openai_llm_for_kimi_linear", Path(__file__).with_name("openai_llm.py")
)
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

MODEL_NODE = _base.MODEL_NODE
QUEUE = _base.QUEUE
PROFILE_BY = _base.PROFILE_BY
ready = _base.ready
memory_peak_bytes = _base.memory_peak_bytes
# this module's private copy of openai_llm, with this checkpoint writer
_base.write_checkpoint = write_checkpoint
build = _base.build
