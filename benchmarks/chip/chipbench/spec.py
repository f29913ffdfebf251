"""Finds a cell's configuration, traffic mix and metric readers by name.

``BENCHMARK.json`` at the checkout's root names the cells. A configuration
file is the one its ``configs`` entry names; a traffic mix is
``traffic/<name>.json``; an open-loop cell's offered rate is
``cells/<cell name>.json``; and a per-layer metric's reader is
``metrics/<name>.py`` beside this package. Adding any of them is adding a
file and an entry.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _named(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    return _named(bench["workloads"], name, "workload")


def load_config(bench: dict, name: str, root: Path = ROOT) -> dict:
    with open(root / _named(bench["configs"], name, "config")["file"]) as f:
        return json.load(f)


def load_traffic(name: str) -> dict:
    with open(BENCH_DIR / "traffic" / f"{name}.json") as f:
        return json.load(f)


def cell_rate(cell: dict):
    """The offered rate of an open-loop cell (``rate_per_s`` in
    ``cells/<cell name>.json``); None for a cell whose mix has no rate."""
    path = BENCH_DIR / "cells" / f"{cell['name']}.json"
    if load_traffic(cell["traffic"])["arrivals"]["process"] != "poisson":
        return None
    with open(path) as f:
        return float(json.load(f)["rate_per_s"])


def metrics_of(bench: dict, cell_name: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def load_reader(name: str):
    """``read(run)`` of ``metrics/<name>.py``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
