"""Work counts of the kernels at fixed shapes: counted from the job."""

from __future__ import annotations

import pytest

from spec import Benchmark
from conftest import ROOT


@pytest.fixture(scope="module")
def bench():
    return Benchmark(ROOT)


def test_embed_join_count_level(bench):
    w = bench.roofline("embed_join")
    call = {"kind": "count", "rows": 100, "cands": 50, "tests": 2,
            "width": 3, "n": 1000}
    ops, byts = w.work(call)
    assert ops == 100 * 50 * (2 + 3)
    # table slice, candidates, one label per (parent vertex, candidate),
    # one count per row
    assert byts == 4 * (100 * 3 + 50 + 200 * 50 + 100)


def test_embed_join_labels_bounded_by_vertices(bench):
    w = bench.roofline("embed_join")
    call = {"kind": "count", "rows": 4096, "cands": 128, "tests": 4,
            "width": 10, "n": 512}
    _, byts = w.work(call)
    assert byts == 4 * (4096 * 10 + 128 + 512 * 128 + 4096)


def test_embed_join_next_table(bench):
    w = bench.roofline("embed_join")
    assert w.work({"kind": "gather", "survivors": 70, "width": 5}) == \
        (0, 4 * 70 * 6)


def test_cni_update(bench):
    w = bench.roofline("cni_update")
    ops, byts = w.work({"kind": "update", "rows": 256, "labels": 44})
    assert ops == 2 * 256 * 44
    assert byts == 4 * (3 * 256 * 44 + 2 * 256)
    assert w.PROGRAMS == ("cni_update",)


def test_share_bound_and_value(bench):
    """The share is the larger of the two times over the device time."""
    import run as bench_run
    from readers import roofline_share
    from trace_reduce import TraceSummary

    ctx = bench_run.RunContext(
        trace=TraceSummary(window_s=1.0, busy_s=0.5, n_devices=1,
                           program_s={"cni_update": 1e-3}),
        peaks={"ops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        profile_window=(0.0, 10.0),
        kernel_calls={"cni_update": [
            {"kind": "update", "t": 1.0, "rows": 1000, "labels": 10},
            {"kind": "update", "t": 11.0, "rows": 9999, "labels": 10}]},
        log=lambda _m: None)
    share = roofline_share(ctx, "cni_update", bench.roofline("cni_update"))
    byts = 4 * (3 * 1000 * 10 + 2 * 1000)
    assert share == pytest.approx(100 * (byts / 1e9) / 1e-3)
