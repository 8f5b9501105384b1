"""Resolves a cell of ``BENCHMARK.json`` to its files, by name alone.

A cell names a configuration (``bench/configs/<config>.json``), a traffic
mix (``bench/traffic/<traffic>.json``) and, through the metric entries that
list it (or list no cells), its metrics, each read by
``bench/metrics/<metric>.py``. Its correctness limits are
``bench/limits/<workload>.json``. Adding a cell is adding those files and
an entry: nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import math
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    config: str
    traffic: str
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]
    limits: dict

    def metrics(self, trace: bool) -> List[dict]:
        return self.per_layer if trace else self.end_to_end


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(name: str, bench: dict = None, bench_dir: Path = BENCH_DIR
            ) -> Cell:
    bench = bench if bench is not None else load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    for kind, path in (("config", bench_dir / "configs" / f"{w['config']}.json"),
                       ("traffic", bench_dir / "traffic" / f"{w['traffic']}.json"),
                       ("limits", bench_dir / "limits" / f"{name}.json")):
        if not path.is_file():
            raise FileNotFoundError(f"{name}: {kind} file {path} is missing")
    with open(bench_dir / "limits" / f"{name}.json") as f:
        limits = json.load(f)
    return Cell(name=name, config=w["config"], traffic=w["traffic"],
                chips=w["chips"],
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)],
                limits=limits)


def reader(metric: str, bench_dir: Path = BENCH_DIR):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = bench_dir / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(cell: Cell, run, trace: bool,
                 bench_dir: Path = BENCH_DIR) -> Dict[str, dict]:
    """Every metric of the cell for this kind of run; a reader that finds
    nothing to read returns None, and the metric is left out, as is one
    that is not finite (a tail of requests that never finished)."""
    out = {}
    for m in cell.metrics(trace):
        value = reader(m["name"], bench_dir)(run)
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
