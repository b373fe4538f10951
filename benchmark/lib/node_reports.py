"""What a node said about itself: the ``dora_tpu.backend <kind>: {json}``
lines of its log (``dora_tpu/backend.py:report``; reader copied from
``chip_smoke.py:node_reports``, PR 21, keeping every line of a kind).
The node owns the chip, so nobody else can ask the device."""

from __future__ import annotations

import json
import re
from pathlib import Path

_REPORT = re.compile(r"dora_tpu\.backend (\w+): (\{.*\})\s*$")


def node_log(workdir: Path, node: str) -> Path | None:
    out = workdir / "out"
    if not out.is_dir():
        return None
    runs = sorted(out.iterdir(), key=lambda p: p.stat().st_mtime)
    return runs[-1] / f"log_{node}.txt" if runs else None


def parse(text: str) -> dict[str, list[dict]]:
    found: dict[str, list[dict]] = {}
    for line in text.splitlines():
        m = _REPORT.search(line)
        if m:
            try:
                found.setdefault(m.group(1), []).append(json.loads(m.group(2)))
            except ValueError:
                pass
    return found


def node_reports(workdir: Path, node: str) -> dict[str, list[dict]]:
    log = node_log(workdir, node)
    if log is None or not log.exists():
        return {}
    return parse(log.read_text(errors="replace"))
