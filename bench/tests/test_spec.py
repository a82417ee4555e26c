"""The loader finds configurations, mixes, readers and peaks by name and
refuses names it has no file for."""

from __future__ import annotations

import json

import pytest

import run as bench_run
from spec import Benchmark, SpecError
from conftest import ROOT


@pytest.fixture(scope="module")
def bench():
    return Benchmark(ROOT)


def test_every_cell_resolves(bench):
    for cell in bench.data["workloads"]:
        cfg = bench.config(cell["config"])
        mix = bench.traffic(cell["traffic"])
        assert cfg["dataset"]["n_vertices"] > 0
        assert mix["rate"] > 0
        bench.reference(cfg["reference"])
        for kind in ("end_to_end", "per_layer"):
            for m in bench.metrics_for(cell["name"], kind):
                assert callable(bench.reader(m["name"]))


def test_dotted_name_uses_base_reader(bench):
    assert (bench.reader("filter_ms_per_query.sat").__module__
            == bench.reader("filter_ms_per_query").__module__)


@pytest.mark.parametrize("lookup", [
    lambda b: b.cell("no.such.cell"),
    lambda b: b.config("no_such_config"),
    lambda b: b.traffic("no_such_mix"),
    lambda b: b.reader("no_such_metric"),
    lambda b: b.reader("no_such_metric.sat"),
    lambda b: b.roofline("no_such_kernel"),
    lambda b: b.reference("no_such_reference"),
    lambda b: b.peaks("TPU v99"),
    lambda b: b.peaks("cpu"),
    lambda b: bench_run.new_store(
        {"store": {"kind": "OutOfCoreGraphStore", "degree_cap": 4}}, None),
])
def test_unknown_names_are_refused(bench, lookup):
    with pytest.raises(SpecError):
        lookup(bench)


def test_peaks_for_v5e(bench):
    peaks = bench.peaks("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["ops_per_s"] == 197e12


def test_missing_benchmark_file(tmp_path):
    with pytest.raises(SpecError):
        Benchmark(tmp_path)


def test_per_layer_metrics_report_what_they_move(bench):
    """Every cell that reports a per-layer metric reports the end-to-end
    metric that it moves."""
    cells = [c["name"] for c in bench.data["workloads"]]
    for m in bench.data["per_layer"]:
        for cell in m.get("workloads", cells):
            e2e = {x["name"] for x in bench.metrics_for(cell, "end_to_end")}
            assert m["moves"] in e2e, (m["name"], cell)


def test_benchmark_json_keys():
    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(data) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
