"""Readers of the admission and enumeration metrics, on a synthetic run
context."""

from __future__ import annotations

from types import SimpleNamespace

import pytest

from conftest import ROOT
from driver import RequestRecord
from run import RunContext
from spec import Benchmark
from trace_reduce import TraceSummary
from trace_spans import SpanTraceSummary

from repro import obsv


@pytest.fixture(scope="module")
def reader():
    return Benchmark(ROOT).reader


def span(name, start_s, dur_ms):
    s = obsv.Span(name, 1, 1, None, int(start_s * 1e9))
    s.end_ns = s.start_ns + int(dur_ms * 1e6)
    return s


def test_admit_ms_is_the_mean_of_the_window_admissions(reader):
    read = reader("admit_ms_per_query")
    assert reader("admit_ms_per_query.sat").__module__ == read.__module__
    ctx = RunContext(window_t0=100.0, seconds=50.0, spans=[
        span("service.admit", 101.0, 1500.0),
        span("service.admit", 140.0, 2500.0),
        span("service.admit", 99.0, 9000.0),       # before the window
        span("service.prefilter", 101.1, 1400.0),  # another name
    ])
    assert read(ctx) == pytest.approx(2000.0)
    assert read(RunContext(window_t0=0.0, seconds=50.0, spans=[])) is None


def test_idle_in_admit_share_reads_zero_not_none(reader):
    read = reader("device_idle_in_admit_share")
    assert (reader("device_idle_in_admit_share.sat").__module__
            == read.__module__)

    def summary(idle):
        return SpanTraceSummary(window_s=80.0, busy_s=60.0, n_devices=1,
                                idle_s_by_annotation=idle)

    assert read(RunContext(trace=summary(
        {"service.admit": 16.0, "service.prefilter": 15.0}))) \
        == pytest.approx(20.0)
    # a trace with no idle time in admission reads 0, not nothing
    assert read(RunContext(trace=summary({"bench.tick": 1.0}))) == 0.0
    assert read(RunContext()) is None
    # a profile reduced by trace_reduce alone carries no such field
    assert read(RunContext(trace=TraceSummary(80.0, 60.0, 1))) is None


def record(host_syncs=None, *, done=1.0, rejected=False):
    enum = {} if host_syncs is None else {"host_syncs": host_syncs}
    stats = None if rejected else SimpleNamespace(extras={"enum": enum})
    return RequestRecord(qid=0, due=0.5, done=done, rejected=rejected,
                         stats=stats)


def test_host_syncs_mean_over_answered_requests(reader):
    read = reader("enum_host_syncs_per_query")
    ctx = RunContext(requests=[
        record(10), record(22),
        record(done=None, rejected=True),   # refused at submission
        record(99, done=None),              # never answered
    ])
    assert read(ctx) == pytest.approx(16.0)
    # a program whose report has no counter reads nothing
    assert read(RunContext(requests=[record(), record(rejected=True)])) \
        is None
