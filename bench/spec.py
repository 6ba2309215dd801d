"""What a cell is, read from files found by name.

``BENCHMARK.json`` names the cell's configuration, traffic mix, chips and
metrics; ``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``
and ``bench/workloads/<cell>.json`` hold the rest.  Adding a cell is
adding files and entries: nothing here names one.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any, Dict, List

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
    e2e = [m for m in bench["end_to_end"] if _listed(m, name)]
    e2e_names = [m["name"] for m in e2e]
    # a per-layer metric without a list follows the metric it moves
    per_layer = [m for m in bench["per_layer"]
                 if (_listed(m, name) if "workloads" in m else m["moves"] in e2e_names)]
    return Cell(name, int(entry["chips"]), config, traffic, workload, e2e,
                per_layer, root)
