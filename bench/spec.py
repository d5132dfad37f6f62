"""Find a cell's parts by name.

``BENCHMARK.json`` at the root of the checkout names every cell, its
configuration and its traffic mix, and every metric. Each part is a
file of its own, found from that name:

* a configuration: the file its ``configs`` entry names
  (``bench/configs/<config>.json``);
* a traffic mix: ``bench/traffic/<traffic>.json``, parameters that the
  one generator (``bench.generator``) reads;
* a metric: ``bench/metrics/<metric>.py``, a reader with one function
  ``read(run)`` that returns the number, or None where the run has
  nothing for it to read.

Adding a configuration, a mix or a metric is adding its file and its
entry; no file that exists has to change.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, Optional

__all__ = ["Cell", "Metric", "load_cell", "load_reader", "root_dir"]


def root_dir() -> Path:
    """The checkout: the directory that holds ``BENCHMARK.json``."""
    return Path(__file__).resolve().parents[1]


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple
    per_layer: tuple
    root: Path


def _applies(entry: dict, cell: str) -> bool:
    cells = entry.get("workloads")
    return cells is None or cell in cells


def load_cell(name: str, root: Optional[Path] = None) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its
    configuration, traffic mix and the metrics it reports."""
    root = root_dir() if root is None else Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"it has {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic"
                          / f"{cell['traffic']}.json").read_text())

    def metrics(kind: str) -> tuple:
        return tuple(Metric(m["name"], m["unit"])
                     for m in bench[kind] if _applies(m, name))

    return Cell(name=name, chips=int(cell["chips"]), config=config,
                traffic=traffic, end_to_end=metrics("end_to_end"),
                per_layer=metrics("per_layer"), root=root)


def load_reader(metric: str, root: Optional[Path] = None) -> Callable:
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``.
    Metric names hold dots, so the file is loaded by its path."""
    root = root_dir() if root is None else Path(root)
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no reader for metric {metric!r} at {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read_metrics(metrics, run, root: Optional[Path] = None) -> Dict[str, dict]:
    """``{name: {"value", "unit"}}`` of every metric whose reader found
    something to read in ``run``."""
    out: Dict[str, dict] = {}
    for m in metrics:
        value = load_reader(m.name, root)(run)
        if value is not None:
            out[m.name] = {"value": value, "unit": m.unit}
    return out
