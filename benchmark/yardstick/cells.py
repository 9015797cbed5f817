"""Finds a cell's files by the names in ``BENCHMARK.json``: its
configuration (the file the config entry names), its traffic mix
(``benchmark/traffic/<traffic>.json``), the contract families the mix
names (``benchmark/families/<family>.py``) and the readers of its
per-layer metrics (``benchmark/metrics/<name>.py``)."""

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    def reader(self, metric: str) -> Callable:
        return load("metrics", metric).read


def load(kind: str, name: str):
    """The module ``benchmark/<kind>/<name>.py``."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise KeyError(f"no {path}")
    spec = importlib.util.spec_from_file_location(f"{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _load(path: str) -> dict:
    with open(path) as handle:
        return json.load(handle)


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def resolve(name: str, root: str = ROOT) -> Cell:
    spec = _load(os.path.join(root, "BENCHMARK.json"))
    cells: Dict[str, dict] = {cell["name"]: cell for cell in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    config = next(c for c in spec["configs"] if c["name"] == cell["config"])
    return Cell(
        name=name,
        chips=cell["chips"],
        config=_load(os.path.join(root, config["file"])),
        traffic=_load(os.path.join(BENCH_DIR, "traffic",
                                   f"{cell['traffic']}.json")),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
    )
