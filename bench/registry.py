"""Finds a cell's parts by name, so that a cell, configuration, traffic
mix or metric is added by adding files alone.

  BENCHMARK.json            cells (``workloads``) and metrics
  bench/cells/<cell>.json   load (loop, rate or concurrency), window
                            tail, and the correctness sample and limit
  bench/configs/<config>.json   widths, engine settings, provenance
  bench/traffic/<mix>.json  parameters for bench/traffic.py
  bench/metrics/<metric>.py ``read(ctx)`` -> a number, or None
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def workload(name: str, spec: dict) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in spec['workloads']]}")


def cell(name: str, base: Path = BENCH) -> dict:
    return load_json(base / "cells" / f"{name}.json")


def config(name: str, base: Path = BENCH) -> dict:
    return load_json(base / "configs" / f"{name}.json")


def traffic(name: str, base: Path = BENCH) -> dict:
    return load_json(base / "traffic" / f"{name}.json")


def metrics_for(spec: dict, cell_name: str, per_layer: bool) -> list:
    """The metrics a run of ``cell_name`` reports: end-to-end ones with
    ``--trace 0``, per-layer ones with ``--trace 1``; a metric with a
    ``workloads`` list belongs to those cells only."""
    group = spec["per_layer" if per_layer else "end_to_end"]
    return [m for m in group
            if cell_name in m.get("workloads", [cell_name])]


def reader(metric: str, base: Path = BENCH):
    """The ``read`` function of bench/metrics/<metric>.py."""
    path = base / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
