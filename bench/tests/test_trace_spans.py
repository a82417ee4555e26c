"""Idle gaps named by the program's own spans, and device-idle time inside
each span name (``trace_spans``), over the reduction ``trace_reduce`` gives."""

from __future__ import annotations

import pytest

import trace_reduce
import trace_spans

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


def busy(*spans):
    return [ev(DEV, "XLA Ops", f"fusion.{i}", s, e - s)
            for i, (s, e) in enumerate(spans)]


def nested_trace():
    """``bench.tick`` ⊃ ``service.tick`` ⊃ ``service.admit`` (200-5000) ⊃
    ``service.prefilter`` (300-4000), then ``bench.sleep``; a finalize span
    after the benchmark's last annotation.  Idle gaps: 250-4100 (the
    prefilter and a little of admission on both sides, as on the chip),
    4300-5300 (straddles the end of admission), 9000-9500 (in the tick,
    after admission) and 10500-11000 (in the sleep)."""
    return [
        ev(HOST, "python", "bench.tick", 0, 10000),
        ev(HOST, "python", "service.tick", 100, 9800),
        ev(HOST, "python", "service.admit", 200, 4800),
        ev(HOST, "python", "service.prefilter", 300, 3700),
        ev(HOST, "python", "bench.sleep", 10000, 2000),
        ev(HOST, "python", "service.finalize", 13000, 1000),
        *busy((0, 250), (4100, 4300), (5300, 9000), (9500, 10500),
              (11000, 12000), (13000, 13500)),
    ]


def test_gaps_named_by_the_innermost_program_span():
    s = trace_spans.reduce_events(nested_trace())
    assert s.idle_gaps == [
        # 3700 in the prefilter, 150 in admission around it; the tick and
        # admission overlap the whole gap, the prefilter holds it
        ["service.prefilter", pytest.approx(3850e-9)],
        # 700 in admission, 300 in the tick after it
        ["service.admit", pytest.approx(1000e-9)],
        ["service.tick", pytest.approx(500e-9)],
        ["bench.sleep", pytest.approx(500e-9)],   # no program span there
    ]


def test_gap_inside_one_span_is_named_by_it():
    # device work cuts 2000-3500 out of the first gap, inside the prefilter
    events = nested_trace() + busy((1000, 2000), (3500, 4100))
    gaps = trace_spans.reduce_events(events).idle_gaps
    assert gaps[0] == ["service.prefilter", pytest.approx(1500e-9)]


def test_program_span_outside_the_window_does_not_widen_it():
    events = nested_trace()
    s = trace_spans.reduce_events(events)
    assert s.window_s == pytest.approx(12000e-9)
    base = trace_reduce.reduce_events(
        [e for e in events if not e["name"].startswith("service.")])
    assert (s.window_s, s.busy_s, s.n_devices, s.program_s, s.device_ops) \
        == (base.window_s, base.busy_s, base.n_devices, base.program_s,
            base.device_ops)


def test_idle_by_annotation_counts_nested_names_inclusively():
    idle = trace_spans.reduce_events(nested_trace()).idle_s_by_annotation
    assert idle == {
        "bench.tick": pytest.approx(5350e-9),
        "service.tick": pytest.approx(5350e-9),
        "service.admit": pytest.approx(4550e-9),
        "service.prefilter": pytest.approx(3700e-9),
        "bench.sleep": pytest.approx(500e-9),
    }


def test_repeated_name_counts_its_union_once():
    events = nested_trace() + [
        ev(HOST, "python", "service.admit", 250, 1000)]  # inside the first
    idle = trace_spans.reduce_events(events).idle_s_by_annotation
    assert idle["service.admit"] == pytest.approx(4550e-9)


def test_trace_without_program_spans_reduces_as_before():
    """A trace of a program that forwards no span: exactly the fields and
    names ``trace_reduce`` gives."""
    events = [e for e in nested_trace()
              if not e["name"].startswith("service.")]
    base = trace_reduce.reduce_events(events)
    s = trace_spans.reduce_events(events)
    assert {k: getattr(s, k) for k in vars(base)} == vars(base)
    assert [g[0] for g in s.idle_gaps] == ["bench.tick"] * 3 + ["bench.sleep"]
    assert set(s.idle_s_by_annotation) == {"bench.tick", "bench.sleep"}
    assert trace_spans.reduce_events(
        [ev(HOST, "python", "service.tick", 0, 10)]) is None


def test_recorded_trace_holds_the_program_spans(tmp_path):
    """Recorded here, on the CPU: live ``obsv`` spans reach the profiler
    under their bare names, nested inside the benchmark's annotation;
    ``trace_reduce`` alone keeps only the benchmark's."""
    import jax
    import jax.numpy as jnp

    from repro import obsv

    f = jax.jit(lambda x: (x * 2.0).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with obsv.tracing():
        with jax.profiler.TraceAnnotation("bench.tick"):
            with obsv.span("service.admit", slot=3):
                with obsv.span("service.prefilter"):
                    f(x).block_until_ready()
        obsv.span_at("service.queue_wait", 0.0, 1.0)  # not forwarded
    jax.profiler.stop_trace()
    path = str(sorted(tmp_path.rglob("*.xplane.pb"))[-1])
    events = {e["name"]: e for e in trace_spans.read_xplane(path)}
    assert {"bench.tick", "service.admit", "service.prefilter"} <= set(events)
    assert "service.queue_wait" not in events
    tick, admit, pre = (events[n] for n in
                        ("bench.tick", "service.admit", "service.prefilter"))
    assert tick["start_ns"] <= admit["start_ns"] <= pre["start_ns"]
    assert (pre["start_ns"] + pre["dur_ns"]
            <= admit["start_ns"] + admit["dur_ns"]
            <= tick["start_ns"] + tick["dur_ns"])
    assert {e["name"] for e in trace_reduce.read_xplane(path)} == {"bench.tick"}
