"""Per-layer metrics: one reader per file, found by the metric's name.

``metrics/<name>.py`` defines ``read(ctx) -> float | None`` for the
per-layer metric ``<name>`` of ``BENCHMARK.json``.  ``ctx`` holds:

* ``trace``     the reduced device trace of the traced steps
  (``chipbench.trace.Reduced``);
* ``steps``     how many window steps the trace covers;
* ``hlo``       the compiled step's HLO text;
* ``cell``, ``dims`` and ``device`` (platform, kind, count).

A reader that finds nothing to read returns None, and the metric is left
out of the result line.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent


def load(name: str, base: Path = HERE):
    path = base / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def wanted(cell_name: str, benchmark: Path = ROOT / "BENCHMARK.json") -> list:
    """The per-layer metric entries that a cell reports."""
    spec = json.loads(benchmark.read_text())
    return [m for m in spec["per_layer"]
            if cell_name in m.get("workloads", [cell_name])]


def read_all(ctx: dict, cell_name: str, benchmark: Path = ROOT / "BENCHMARK.json",
             base: Path = HERE) -> dict:
    out = {}
    for m in wanted(cell_name, benchmark):
        v = load(m["name"], base).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
