"""The four compile metrics of PR 37 (``xla_compile_s``, ``xla_lower_s``,
``xla_cache_load_s``, ``xla_compiles``): their files load and name readers
that exist, ``registry_count`` reads a histogram's count, and on a program
that keeps no such histogram (the parent of PR 37) every reader returns
nothing rather than raising."""
import json
import os
from types import SimpleNamespace

import pytest

from perfbench import traffic
from perfbench.run import plugin
from perfbench.readers import registry_count

NAMES = ("xla_compile_s", "xla_lower_s", "xla_cache_load_s", "xla_compiles")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class Registry:
    def __init__(self, hists):
        self.hists = hists

    def snapshot(self):
        return {"counters": {}, "gauges": {}, "histograms": dict(self.hists)}


def records(registry):
    return [{"trace": None},
            {"trace": SimpleNamespace(spans=[], metrics=registry)}]


def test_registry_count_reads_the_count():
    reg = Registry({"xla.compile_ms": {"count": 61, "sum": 36000.0},
                    "xla.cache_load_ms": {"count": 0, "sum": 0.0}})
    metric = {"reader": "registry_count", "histogram": "xla.compile_ms"}
    assert registry_count.read(metric, {"records": records(reg)}) == 61
    assert registry_count.read({"histogram": "xla.cache_load_ms"},
                               {"records": records(reg)}) == 0


def test_registry_count_returns_nothing_where_there_is_nothing_to_read():
    metric = {"histogram": "xla.compile_ms"}
    reg = Registry({"query.execute_ms": {"count": 3, "sum": 3.0}})
    assert registry_count.read(metric, {"records": records(reg)}) is None
    assert registry_count.read(metric, {"records": [{"trace": None}]}) is None
    assert registry_count.read(metric, {"records": []}) is None


@pytest.mark.parametrize("name", NAMES)
def test_metric_file_loads_and_reads(name):
    metric = traffic.load("metrics", name)
    assert metric["name"] == name
    reader = plugin("readers", metric["reader"])
    full = Registry({"xla.compile_ms": {"count": 4, "sum": 2500.0},
                     "xla.lower_ms": {"count": 9, "sum": 1200.0},
                     "xla.cache_load_ms": {"count": 0, "sum": 0.0}})
    value = reader.read(metric, {"records": records(full)})
    assert value == {"xla_compile_s": pytest.approx(2.5),
                     "xla_lower_s": pytest.approx(1.2),
                     "xla_cache_load_s": 0.0, "xla_compiles": 4}[name]
    parent = Registry({"load.encode_ms": {"count": 1, "sum": 5.0}})
    assert reader.read(metric, {"records": records(parent)}) is None


def test_benchmark_lists_each_metric_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {e["name"]: e for e in bench["per_layer"]}
    for name in NAMES:
        entry = entries[name]
        assert entry["layer"] == "Rung compile and compile cache"
        assert entry["moves"] == "setup_s" and "workloads" not in entry
        assert entry["source"] == "program_counter"
