"""Reduction of profiler events to busy time, program time and gaps."""

from __future__ import annotations

import pytest

from trace_reduce import program_name, reduce_events

DEV = "/device:TPU:0"


def ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


def test_busy_idle_programs_and_gaps():
    events = [
        ev("/host:CPU", "python", "bench.tick", 0, 1000),
        ev("/host:CPU", "python", "bench.sleep", 1000, 1000),
        ev(DEV, "XLA Modules", "jit__device_join_count(12)", 100, 300),
        ev(DEV, "XLA Ops", "fusion.1", 100, 200),
        ev(DEV, "XLA Ops", "custom-call.2", 250, 150),   # overlaps
        ev(DEV, "XLA Modules", "jit_cni_update(3)", 1500, 100),
        ev(DEV, "XLA Ops", "custom-call.9", 1500, 100),
        ev(DEV, "XLA Ops", "copy.1", 2500, 100),          # outside window
    ]
    s = reduce_events(events)
    assert s.window_s == pytest.approx(2000e-9)
    assert s.busy_s == pytest.approx(400e-9)
    assert s.program_s == {"_device_join_count": pytest.approx(300e-9),
                           "cni_update": pytest.approx(100e-9)}
    assert s.device_ops[0] == ["fusion.1", pytest.approx(200e-9)]
    # gaps: 0-100 (tick), 400-1500 (600 in the tick, 500 in the sleep),
    # 1600-2000 (sleep); each is named by the annotation it overlaps most
    assert s.idle_gaps == [["bench.tick", pytest.approx(1100e-9)],
                           ["bench.sleep", pytest.approx(400e-9)],
                           ["bench.tick", pytest.approx(100e-9)]]


def test_no_device_work_reads_nothing():
    assert reduce_events([ev("/host:CPU", "python", "bench.tick", 0, 10)]) \
        is None
    assert reduce_events([ev(DEV, "XLA Ops", "fusion", 0, 10)]) is None


def test_program_name():
    assert program_name("jit__device_join_emit(123)") == "_device_join_emit"
    assert program_name("jit_cni_update") == "cni_update"


def test_recorded_trace(tmp_path):
    """A trace recorded here, on the CPU: the benchmark's annotations are
    read back with their times; the CPU backend has no device plane, so
    device work is placed inside the first annotation to reduce it."""
    import jax
    import jax.numpy as jnp

    from trace_reduce import read_xplane

    f = jax.jit(lambda x: (x * 2.0).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for _ in range(2):
        with jax.profiler.TraceAnnotation("bench.tick"):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.sleep"):
            pass
    jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    events = read_xplane(str(path))
    names = [e["name"] for e in events]
    assert names.count("bench.tick") == 2 and names.count("bench.sleep") == 2
    assert reduce_events(events) is None  # no device plane on the CPU
    tick = min((e for e in events if e["name"] == "bench.tick"),
               key=lambda e: e["start_ns"])
    events.append(ev(DEV, "XLA Ops", "fusion", tick["start_ns"],
                     tick["dur_ns"] / 2))
    s = reduce_events(events)
    assert s.n_devices == 1 and 0 < s.busy_s < s.window_s
    assert s.busy_s == pytest.approx(tick["dur_ns"] / 2 / 1e9)
    assert s.idle_gaps and s.idle_gaps[0][0].startswith("bench.")
