"""BENCHMARK.json against the files the harness finds by name."""
import os
import re

import pytest

import hadar_bench_path  # noqa: F401  (benchmarks/ on the path)

from hadar_bench import registry

BENCH = registry.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_files_load_by_name(cell):
    cfg = registry.config(cell["config"])
    mix = registry.mix(cell["traffic"])
    assert cfg["name"] == cell["config"] and mix["name"] == cell["traffic"]
    assert mix["policy"]["kind"] in ("hadar_events", "hadare")
    assert cell["chips"] == 1
    e2e = {m["name"] for m in registry.metrics_for(BENCH, cell["name"],
                                                   False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert registry.metrics_for(BENCH, cell["name"], True)


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    assert callable(registry.reader(metric["name"]))
    assert NAME.match(metric["name"])


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in BENCH["configs"]]
    for c in BENCH["configs"]:
        assert os.path.isfile(os.path.join(registry.ROOT, c["file"]))
        assert registry.config(c["name"])["source"] == c["source"]
    assert {w["config"] for w in BENCH["workloads"]} == set(names)
    layers = {m["name"]: m["layer"] for m in BENCH["per_layer"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])
    assert all(0.0 < m["bound"] <= 0.25 for m in BENCH["end_to_end"])
    assert len(layers) == len(BENCH["per_layer"])


def test_unknown_device_kind_is_an_error():
    assert registry.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        registry.peaks("cpu")
