"""The comparison that decides ``correct``: a sound run passes it, and a run
whose timed path is broken underneath, or the control, fails it.

Each test drives a whole run of a tiny cell on the CPU (the harness's look
for a chip is the only step skipped) with one fault planted in the system
under test: an answer altered where it is produced, an index update that
leaves its state unchanged, and an update batch of which half is left out.
The cells run on one chip, so there is no exchange between chips to leave
out."""

from __future__ import annotations

import time

import numpy as np
import pytest

import control
import run as bench_run
from spec import Benchmark

SEED = 2**33 + 5
SECONDS = 3.0


def run(root, cell, trace=False):
    bench = Benchmark(root)
    return bench_run.run_cell(bench, bench.cell(cell), SEED, SECONDS, trace,
                              t_start=time.perf_counter())


@pytest.mark.parametrize("cell", ["hprd.read", "hprd.churn"])
def test_sound_run_is_correct(tiny_root, cell):
    out = run(tiny_root, cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert {"setup_s", "query_mean_ms"} <= set(out["metrics"])


def test_traced_run_reads_spans(tiny_root):
    out = run(tiny_root, "hprd.churn", trace=True)
    assert out["correct"], out["checks"]
    for name in ("queue_wait_ms_p95", "filter_ms_per_query",
                 "finalize_ms_per_query", "enumerate_ms_per_query",
                 "mutation_ms_p95", "compiles_in_window"):
        assert name in out["metrics"], name


def test_declared_metric_without_reading_fails(tiny_root, monkeypatch):
    monkeypatch.setattr(Benchmark, "reader",
                        lambda self, name: (lambda ctx: None))
    with pytest.raises(bench_run.MetricMissing):
        run(tiny_root, "hprd.read")


def altered_answers(monkeypatch):
    from repro.core import engine

    search = engine.device_join_search

    def drop_last_row(*a, **k):
        emb = search(*a, **k)
        return emb[:-1] if len(emb) else emb

    monkeypatch.setattr(engine, "device_join_search", drop_last_row)


def unchanged_index(monkeypatch):
    from repro.core.incremental import IncrementalIndex

    monkeypatch.setattr(IncrementalIndex, "apply_batch",
                        lambda self, store, applied: None)


def half_batch(monkeypatch):
    from repro.graphs.store import BaseGraphStore

    for name in ("add_edges", "remove_edges"):
        call = getattr(BaseGraphStore, name)

        def first_half(self, edges, *rest, _call=call):
            k = max(1, len(edges) // 2)
            return _call(self, np.asarray(edges)[:k],
                         *(np.asarray(r)[:k] for r in rest if r is not None))

        monkeypatch.setattr(BaseGraphStore, name, first_half)


@pytest.mark.parametrize("cell,fault", [
    ("hprd.read", altered_answers),
    ("hprd.churn", altered_answers),
    ("hprd.churn", unchanged_index),
    ("hprd.churn", half_batch),
])
def test_planted_fault_is_not_correct(tiny_root, monkeypatch, cell, fault):
    fault(monkeypatch)
    out = run(tiny_root, cell)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", ["hprd.read", "hprd.churn"])
def test_control_is_not_correct(tiny_root, cell):
    bench = Benchmark(tiny_root)
    readings = control.control_readings(bench, bench.cell(cell), SEED,
                                        SECONDS)
    assert readings["wrong_results"] > 0
    if cell == "hprd.churn":
        assert readings["log_digest_err"] > 1e-4
