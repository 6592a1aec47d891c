"""Finds a cell's files by the names in ``BENCHMARK.json``: the
configuration in ``configs/<config>.json``, the traffic mix in
``mixes/<traffic>.json`` and each metric's reader in
``metrics/<metric>.py``.  Adding a cell adds files and an entry, and no
code."""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _json(path: str) -> dict:
    with open(path, "r", encoding="utf-8") as fh:
        return json.load(fh)


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json")


def config(name: str, base: str = HERE) -> dict:
    return _json(os.path.join(base, "configs", name + ".json"))


def mix(name: str, base: str = HERE) -> dict:
    return _json(os.path.join(base, "mixes", name + ".json"))


def metrics_for(bench: dict, cell_name: str, traced: bool) -> List[dict]:
    """The cell's end-to-end metrics, or with a trace its per-layer ones."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group
            if cell_name in m.get("workloads", [cell_name])]


def reader(name: str, base: str = HERE) -> Callable:
    path = os.path.join(base, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "hadar_bench_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(kind: str, base: str = HERE) -> Dict:
    table = _json(os.path.join(base, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]
