"""Where a cell's parts are found, by the names ``BENCHMARK.json`` gives.

Data files are read from ``DATA_ROOT`` (the checkout's root); code (the
generators and the metric readers) is imported from this package by name.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

DATA_ROOT = Path(__file__).resolve().parents[1]
PACKAGE = Path(__file__).resolve().parent


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(DATA_ROOT / "BENCHMARK.json")


def load_cell(workload: str) -> dict:
    """Everything one cell needs: its ``BENCHMARK.json`` entry, the
    configuration, the traffic mix, the cell file (fixed rate, correctness
    limit) and the metric entries that apply to it."""
    bench = benchmark()
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(entries)}")
    entry = entries[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    root = DATA_ROOT / "chipbench"

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "name": workload,
        "entry": entry,
        "config": _json(DATA_ROOT / configs[entry["config"]]["file"]),
        "traffic": _json(root / "traffic" / f"{entry['traffic']}.json"),
        "cell": _json(root / "cells" / f"{workload}.json"),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module (names may hold dots)."""
    key = f"chipbench.{kind}.{name}"
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(
        key, PACKAGE / kind / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    sys.modules[key] = module
    spec.loader.exec_module(module)
    return module
