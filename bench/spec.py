"""What a cell is, read from files found by name.

``BENCHMARK.json`` names the cell's configuration, traffic mix, chips and
metrics; ``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``
and ``bench/workloads/<cell>.json`` hold the rest, and the configuration's
``model_type`` names its family, ``bench/families/<model_type>.py``: the
equations of its weights, reference forward and work count.  Adding a
cell, or a configuration of a new family, is adding files and entries:
nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

from bench import check

ROOT = Path(__file__).resolve().parent.parent


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    workload: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: Path
    family: ModuleType  # bench/families/<model_type>.py


def load_family(root: Path, config: Dict[str, Any]) -> ModuleType:
    """``bench/families/<model_type>.py`` under ``root``, for the
    configuration's ``model_type``; refused where there is no such file."""
    model_type = config["model_type"]
    path = root / "bench" / "families" / f"{model_type}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{config['name']}: model_type {model_type!r} has no "
                                f"family file {path}")
    spec = importlib.util.spec_from_file_location(f"bench_family_{model_type}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _listed(metric: Dict[str, Any], cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    entry = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    bench_dir = root / "bench"
    config = load_json(root / configs[entry["config"]]["file"])
    traffic = load_json(bench_dir / "traffic" / f"{entry['traffic']}.json")
    workload = load_json(bench_dir / "workloads" / f"{name}.json")
    family = load_family(root, config)
    unknown = set(workload.get("check", {}).get("limits", {})) - set(check.readers(family))
    if unknown:
        raise ValueError(f"{name}: check.limits names {sorted(unknown)}, a reading of "
                         f"neither bench/check.py nor {family.__file__}")
    e2e = [m for m in bench["end_to_end"] if _listed(m, name)]
    e2e_names = [m["name"] for m in e2e]
    # a per-layer metric without a list follows the metric it moves
    per_layer = [m for m in bench["per_layer"]
                 if (_listed(m, name) if "workloads" in m else m["moves"] in e2e_names)]
    return Cell(name, int(entry["chips"]), config, traffic, workload, e2e,
                per_layer, root, family)
