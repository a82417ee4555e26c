"""The open-loop driver times each request from its due time."""

from __future__ import annotations

import pytest

from driver import latencies, percentile, run_window


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class StallingService:
    """Answers every request on the next tick; each tick takes ``tick_s``
    of the fake clock, and the first tick stalls for ``stall_s``."""

    def __init__(self, clock, tick_s=0.01, stall_s=0.5):
        self.clock, self.tick_s, self.stall_s = clock, tick_s, stall_s
        self.queue, self.n_active, self.rid = [], 0, 0

    def submit(self, query, cap):
        self.rid += 1
        self.queue.append(self.rid)
        return self.rid

    def tick(self):
        self.clock.t += self.stall_s or self.tick_s
        self.stall_s = 0.0
        done, self.queue = self.queue, []
        return [(rid, [], None) for rid in done]


def test_latency_runs_from_due_time():
    clock = FakeClock()
    svc = StallingService(clock)
    arrivals = [0.0, 0.1, 0.2, 0.3, 1.0]
    res = run_window(svc, ["q"], arrivals, [0] * 5, [], 2.0, cap=10,
                     apply_update=None, clock=clock, sleep=clock.sleep)
    lat = [r.latency for r in res.requests]
    # the first tick stalls 0.5 s: requests due during the stall are
    # submitted late and charged the wait from their due time
    assert lat[0] == pytest.approx(0.5)
    assert lat[1] == pytest.approx(0.51 - 0.1)
    assert lat[2] == pytest.approx(0.51 - 0.2)
    assert lat[3] == pytest.approx(0.51 - 0.3)
    assert lat[4] == pytest.approx(0.01)
    assert res.late[1] == pytest.approx(0.4)
    assert all(r.done is not None for r in res.requests)


def test_updates_and_rejections():
    clock = FakeClock()
    svc = StallingService(clock, stall_s=0.0)

    class Full(Exception):
        pass

    def submit(query, cap):
        raise Full()

    svc.submit = submit

    class Batch:
        def __init__(self, due):
            self.due = due

    landed = []
    res = run_window(svc, ["q"], [0.0, 0.5], [0, 0], [Batch(0.25)], 1.0,
                     cap=1, apply_update=lambda b: landed.append(b) or 7,
                     rejected=(Full,), clock=clock, sleep=clock.sleep)
    assert [r.rejected for r in res.requests] == [True, True]
    assert landed and res.updates[0].epoch == 7
    assert res.updates[0].started == pytest.approx(0.25)
    # a request without an answer sits at the drain's end
    assert latencies(res) == [pytest.approx(res.drained_at),
                              pytest.approx(res.drained_at - 0.5)]


def test_unanswered_request_counts_in_tail():
    clock = FakeClock()

    class Never(StallingService):
        def tick(self):
            self.clock.t += 1.0
            return []

    svc = Never(clock)
    svc.n_active = 1
    res = run_window(svc, ["q"], [0.0], [0], [], 0.5, cap=1,
                     apply_update=None, drain_s=3.0, clock=clock,
                     sleep=clock.sleep)
    assert res.requests[0].done is None
    assert latencies(res)[0] >= 3.0


def test_percentile_nearest_rank():
    vals = list(range(1, 101))
    assert percentile(vals, 50) == 50
    assert percentile(vals, 95) == 95
    assert percentile([3.0], 95) == 3.0


def test_requests_due_during_the_last_tick_are_offered():
    """A tick that runs past the close leaves requests due in the window
    unsubmitted; they are offered after it, late, and count as attempted."""
    clock = FakeClock()
    svc = StallingService(clock, tick_s=0.01, stall_s=1.5)
    res = run_window(svc, ["q"], [0.0, 0.6, 0.9], [0, 0, 0], [], 1.0, cap=1,
                     apply_update=None, clock=clock, sleep=clock.sleep)
    assert len(res.requests) == 3
    assert res.requests[1].submitted == pytest.approx(1.5)
    assert res.requests[2].latency == pytest.approx(1.51 - 0.9)


def test_qps_is_all_answers_over_the_time_to_the_last():
    import importlib.util
    from pathlib import Path

    path = Path(__file__).resolve().parents[1] / "metrics" / "qps.py"
    spec = importlib.util.spec_from_file_location("qps_reader", path)
    qps = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(qps)
    clock = FakeClock()
    svc = StallingService(clock, tick_s=0.25, stall_s=0.0)
    res = run_window(svc, ["q"], [0.0, 0.1, 0.2, 0.9], [0] * 4, [], 1.0,
                     cap=1, apply_update=None, clock=clock, sleep=clock.sleep)

    class Ctx:
        requests = res.requests

    last = max(r.done for r in res.requests)
    assert last > 1.0
    assert qps.read(Ctx) == pytest.approx(4 / last)


def test_schedule_keeps_the_work_and_shuffles_the_order():
    """Every seed offers the same arrivals (a mix with ``arrivals_seed``)
    or the same gaps (one without), and each query equally often."""
    import numpy as np

    import datagen

    fixed = {"rate": 0.32, "arrivals_seed": 17}
    a1, p1 = datagen.schedule(fixed, 50.0, 2**31 + 11, 8)
    a2, p2 = datagen.schedule(fixed, 50.0, 5, 8)
    assert len(a1) == 16 and np.array_equal(a1, a2)
    assert not np.array_equal(p1, p2)
    assert sorted(p1) == sorted(p2) == sorted(list(range(8)) * 2)
    free = {"rate": 0.32}
    b1, _ = datagen.schedule(free, 50.0, 2**31 + 11, 8)
    b2, _ = datagen.schedule(free, 50.0, 5, 8)
    assert len(b1) == len(b2) == 16 and not np.array_equal(b1, b2)
