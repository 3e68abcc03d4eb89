"""Find a cell's configuration, traffic mix, limits and metrics by name.

Everything that belongs to one configuration, mix or metric is a file of
its own, found by the name that ``BENCHMARK.json`` gives:

    chipbench/configs/<config>.json      sizes as run, with the source's
    chipbench/reference/<family>.py      the plain reference of the family
    chipbench/costs/<family>.py          its operations and bytes per step
    chipbench/traffic/<traffic>.json     slots, buckets, max_seq
    chipbench/limits/<cell>.json         the limit of each number compared
    chipbench/metrics/<metric>.py        one reader per per-layer metric
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from types import ModuleType
from typing import Any, Dict, List

HERE = pathlib.Path(__file__).resolve().parent
CHECKOUT = HERE.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _load_json(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path) -> ModuleType:
    """Import a file by path (metric names hold dots, so no package import)."""
    spec = importlib.util.spec_from_file_location(f"chipbench_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family_module(family: str) -> ModuleType:
    return load_module(HERE / "reference" / f"{family}.py")


def metric_reader(name: str) -> ModuleType:
    return load_module(HERE / "metrics" / f"{name}.py")


def _for_cell(metrics: List[Dict[str, Any]], cell: str) -> List[Dict[str, Any]]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(workload: str) -> Cell:
    bench = _load_json(CHECKOUT / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: {sorted(entries)}")
    entry = entries[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    return Cell(
        name=workload,
        chips=int(entry["chips"]),
        config=_load_json(CHECKOUT / configs[entry["config"]]["file"]),
        traffic=_load_json(HERE / "traffic" / f"{entry['traffic']}.json"),
        limits=_load_json(HERE / "limits" / f"{workload}.json"),
        end_to_end=_for_cell(bench["end_to_end"], workload),
        per_layer=_for_cell(bench["per_layer"], workload),
    )
