"""Find a cell, its configuration, its traffic mix and its metrics by
name: ``BENCHMARK.json`` at the root of the checkout names them, and each
lives in a file of its own under ``chipbench/``."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(RuntimeError):
    """A cell, configuration, traffic mix or metric cannot be found."""


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    kind: str                      # end_to_end | per_layer
    read: Callable                 # read(run) -> float | None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]         # configs/<config>.json
    traffic: Dict[str, Any]        # workloads/<cell>.json
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _load_json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def _reader(name: str, bench_dir: str = BENCH_DIR) -> Callable:
    path = os.path.join(bench_dir, "metrics", name + ".py")
    if not os.path.exists(path):
        raise SpecError(f"no reader for metric {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(entry: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files,
    found under ``<root>/chipbench/`` by name."""
    bench_dir = os.path.join(root, os.path.basename(BENCH_DIR))
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = configs[w["config"]]
    config = _load_json(os.path.join(root, conf["file"]))
    traffic = _load_json(os.path.join(bench_dir, "workloads",
                                      w["traffic"] + ".json"))

    def metrics(kind):
        return [Metric(m["name"], m["unit"], kind,
                       _reader(m["name"], bench_dir))
                for m in bench[kind] if _applies(m, name)]

    return Cell(name, int(w["chips"]), config, traffic,
                metrics("end_to_end"), metrics("per_layer"))
