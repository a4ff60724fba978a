"""Find a cell and everything it names, by name, from files.

``BENCHMARK.json`` (at the checkout's root) lists configurations, cells
(``workloads``) and metrics.  Each configuration is a JSON file of sizes whose
``reference`` names a module in ``families/``; each cell's ``traffic`` names
``traffic/<traffic>.json``; each per-layer metric is read by
``metrics/<metric>.py``.  A new configuration, cell or metric is a new file
plus an entry, never an edit of a file that is already there.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]          # benchmarks/chip
REPO = ROOT.parents[1]                              # the checkout
TRAFFIC = ROOT / "traffic"
METRICS = ROOT / "metrics"
FAMILIES = ROOT / "families"


class SpecError(Exception):
    """A cell, configuration, traffic mix or metric that cannot be resolved."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]          # the configuration file's contents
    traffic_name: str
    traffic: Dict[str, Any]         # the traffic file's contents
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise SpecError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: Path, what: str) -> Dict[str, Any]:
    if not path.is_file():
        raise SpecError(f"{what}: no file {path}")
    with open(path) as f:
        return json.load(f)


def load_benchmark(path: Optional[Path] = None) -> Dict[str, Any]:
    return _read_json(Path(path) if path else REPO / "BENCHMARK.json",
                      "BENCHMARK.json")


def family(name: str):
    """The plain reference and size arithmetic of an architecture family."""
    path = FAMILIES / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"unknown reference family {name!r} (no {path})")
    return _load_module(path, f"chipbench_family_{name}")


def metric_reader(name: str):
    """``read(ctx) -> float | None`` of one per-layer metric."""
    path = METRICS / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"per-layer metric {name!r} has no reader {path}")
    mod = _load_module(path, "chipbench_metric_" + name.replace(".", "_"))
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"{path} defines no read(ctx)")
    return mod.read


def traffic(name: str) -> Dict[str, Any]:
    return _read_json(TRAFFIC / f"{name}.json", f"traffic {name!r}")


def _applies(metric: Dict[str, Any], cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def resolve(bench: Dict[str, Any], cell_name: str) -> Cell:
    cells = {w["name"]: w for w in bench.get("workloads", [])}
    if cell_name not in cells:
        raise SpecError(f"unknown workload {cell_name!r}; known: {sorted(cells)}")
    w = cells[cell_name]
    configs = {c["name"]: c for c in bench.get("configs", [])}
    if w["config"] not in configs:
        raise SpecError(f"workload {cell_name!r} names unknown configuration "
                        f"{w['config']!r}")
    centry = configs[w["config"]]
    config = _read_json(REPO / centry["file"], f"configuration {w['config']!r}")
    family(config["reference"])                     # must exist
    e2e = [m for m in bench.get("end_to_end", [])
           if "workloads" not in m or cell_name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench.get("per_layer", [])
                 if _applies(m, cell_name, e2e_names)]
    for m in bench.get("per_layer", []):
        unknown = set(m.get("workloads", [])) - set(cells)
        if unknown:
            raise SpecError(f"metric {m['name']!r} names unknown workloads "
                            f"{sorted(unknown)}")
    for m in per_layer:
        metric_reader(m["name"])                    # must exist
    return Cell(name=cell_name, chips=int(w["chips"]),
                config_name=w["config"], config=config,
                traffic_name=w["traffic"], traffic=traffic(w["traffic"]),
                end_to_end=e2e, per_layer=per_layer)


def runner(name: str):
    """The module that runs a traffic file's ``kind`` of cell."""
    import importlib

    if name not in ("train", "serve"):
        raise SpecError(f"unknown traffic kind {name!r}")
    return importlib.import_module(f"chipbench.{name}")
