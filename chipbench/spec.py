"""What one cell is, read from files found by name.

``BENCHMARK.json`` (at the checkout root) names each cell's configuration
and traffic.  Everything else sits in a file of its own under this
directory, so a later change adds a cell, a configuration or a traffic mix
as a new file plus a new entry and edits nothing that is there:

* ``configs/<config>.json``  the model as run: the registry arch it starts
  from, the ``ArchConfig`` fields it overrides, and the published sizes
  (Hugging Face key names) that the plain reference is built from;
* ``traffic/<traffic>.json`` the trainer flags, mesh, sequence length,
  global batch and batch-ring size;
* ``limits/<cell>.json``     the limit of each number compared against the
  reference, with the readings it was set from.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
BENCHMARK = ROOT / "BENCHMARK.json"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # configs/<config>.json
    traffic: dict         # traffic/<traffic>.json
    limits: dict          # limits/<cell>.json ({} before calibration)

    @property
    def dp(self) -> int:
        return int(self.traffic["dp"])

    @property
    def tp(self) -> int:
        return int(self.traffic["tp"])

    @property
    def seq_len(self) -> int:
        return int(self.traffic["seq_len"])

    @property
    def global_batch(self) -> int:
        return int(self.traffic["global_batch"])

    @property
    def tokens_per_step(self) -> int:
        return self.seq_len * self.global_batch


def load_cell(name: str, benchmark: Path = BENCHMARK, base: Path = HERE) -> Cell:
    """The cell ``name`` of ``benchmark``, with its files under ``base``."""
    spec = load_json(benchmark)
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in spec["workloads"])
        raise SystemExit(f"no workload {name!r} in {benchmark} ({known})")
    conf = next(c for c in spec["configs"] if c["name"] == entry["config"])
    config = load_json(base / "configs" / f"{conf['name']}.json")
    traffic = load_json(base / "traffic" / f"{entry['traffic']}.json")
    lim_path = base / "limits" / f"{name}.json"
    limits = load_json(lim_path) if lim_path.exists() else {}
    cell = Cell(name=name, chips=int(entry["chips"]), config=config,
                traffic=traffic, limits=limits)
    if cell.dp * cell.tp != cell.chips:
        raise SystemExit(f"{name}: dp {cell.dp} x tp {cell.tp} is not its "
                         f"{cell.chips} chips")
    return cell


def trainer_argv(cell: Cell) -> list[str]:
    """The ``repro.launch.train`` flags this cell runs with."""
    return (["--arch", cell.config["arch"],
             "--dp", str(cell.dp), "--tp", str(cell.tp),
             "--seq-len", str(cell.seq_len),
             "--global-batch", str(cell.global_batch),
             "--microbatch", str(cell.traffic.get("microbatch", 1))]
            + list(cell.traffic["flags"]))
