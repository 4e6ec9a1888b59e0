"""Finds a cell's parts by name, so that a cell, configuration, traffic
mix or metric is added by adding files alone, and so is a configuration
of a new architecture, with its block.

  BENCHMARK.json            cells (``workloads``) and metrics
  bench/cells/<cell>.json   load (loop, rate or concurrency), window
                            tail, and the correctness sample and limit
  bench/configs/<config>.json   widths, engine settings, provenance, and
                            the name of its block
  bench/blocks/<block>.py   weights, program mapping, reference and work
                            counts of one kind of model (the contract is
                            bench/blocks/__init__.py's docstring)
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


def _load(kind: str, name: str, path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, base: Path = BENCH):
    """The ``read`` function of bench/metrics/<metric>.py."""
    return _load("metric", metric, base / "metrics" / f"{metric}.py").read


def block(name: str, base: Path = BENCH):
    """The module ``blocks/<name>.py`` under ``base``, or else among the
    benchmark's own blocks, after checking that it gives every function
    of the contract (``bench.blocks.CONTRACT``)."""
    from bench.blocks import CONTRACT

    paths = list(dict.fromkeys([base / "blocks" / f"{name}.py",
                                BENCH / "blocks" / f"{name}.py"]))
    path = next((p for p in paths if p.is_file()), None)
    if path is None:
        raise FileNotFoundError(f"block {name!r}: no file "
                                + " or ".join(map(str, paths)))
    mod = _load("block", name, path)
    missing = [f for f in CONTRACT if not callable(getattr(mod, f, None))]
    if missing:
        raise AttributeError(f"block {name!r} ({path}) lacks {missing}")
    return mod
