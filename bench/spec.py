"""Finds what ``BENCHMARK.json`` names: cells, configurations, traffic mixes and
per-layer metric readers, each in a file of its own.

  configuration  bench/configs/<config>.json
  traffic mix    bench/traffic/<traffic>.json
  metric reader  bench/metrics/<name>.py, where <name> is the metric's name up to
                 its first "." (``doc_score_roofline.bulk`` -> doc_score_roofline.py);
                 it defines ``read(ctx) -> float | None``

A later cell, mix or metric is added by adding its file and its entry.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
BENCHMARK = ROOT / "BENCHMARK.json"


def load_benchmark(path: Path = BENCHMARK) -> dict:
    with open(path) as f:
        return json.load(f)


def workload(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bm['workloads']]}")


def config_path(name: str) -> Path:
    return BENCH / "configs" / f"{name}.json"


def traffic_path(name: str) -> Path:
    return BENCH / "traffic" / f"{name}.json"


def reader_path(metric: str) -> Path:
    return BENCH / "metrics" / f"{metric.split('.')[0]}.py"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(metric: str):
    path = reader_path(metric)
    spec = importlib.util.spec_from_file_location(f"bench.metrics.{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bm: dict, cell: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` metrics that ``cell`` reports."""
    return [m for m in bm[kind] if cell in m.get("workloads", [cell])]
