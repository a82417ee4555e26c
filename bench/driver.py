"""Open-loop driver: offers a fixed schedule of queries and update batches
to a query service and times each from when it fell due.

On every loop it submits every request that has fallen due, lands every
update batch that has fallen due, and calls ``svc.tick()`` once; with
nothing in flight it sleeps until the next due event.  A request's latency
runs from its due time to the return of the tick that hands back its
result, so a stall is charged to every request that waited behind it.
After the window closes no more work is offered, and ticking continues
until every request due in the window has its answer, for at most
``drain_s`` seconds.

The service needs ``submit(query, max_embeddings) -> rid``, ``tick() ->
[(rid, embeddings, stats)]``, ``queue`` and ``n_active``.  A rejected
submission raises one of ``rejected``; it counts as attempted and failed.
"""

from __future__ import annotations

import contextlib
import math
import time
from dataclasses import dataclass, field


@dataclass
class RequestRecord:
    qid: int                     # index into the query pool
    due: float                   # seconds after the window opened
    submitted: float | None = None
    rid: int | None = None
    done: float | None = None
    rejected: bool = False
    embeddings: object = None
    stats: object = None

    @property
    def latency(self) -> float | None:
        return None if self.done is None else self.done - self.due


@dataclass
class UpdateRecord:
    due: float
    started: float | None = None
    done: float | None = None
    epoch: int | None = None     # service epoch once the batch has landed


@dataclass
class WindowResult:
    requests: list[RequestRecord]
    updates: list[UpdateRecord]
    seconds: float               # the window's length as driven
    drained_at: float            # when the last answer came, or the drain gave up
    t0: float = 0.0              # clock() when the window opened
    ticks: int = 0
    late: list = field(default_factory=list)  # submit lateness per request


def no_annotation(_name):
    return contextlib.nullcontext()


def run_window(svc, pool, arrivals, picks, updates, seconds, *, cap,
               apply_update, rejected=(), annotate=no_annotation, hooks=(),
               drain_s=60.0, clock=time.perf_counter,
               sleep=time.sleep) -> WindowResult:
    """Drive one measured window.

    ``arrivals``/``picks``: due time and pool index of each request.
    ``updates``: objects with ``.due``; ``apply_update(batch)`` lands one
    and returns the service epoch after it.  ``hooks``: ``(time, fn)``
    pairs called once when due (the traced run starts and stops the
    profiler with them)."""
    reqs = [RequestRecord(int(q), float(t)) for t, q in zip(arrivals, picks)]
    upds = [UpdateRecord(float(b.due)) for b in updates]
    hooks = sorted(hooks, key=lambda h: h[0])
    by_rid: dict[int, RequestRecord] = {}
    i_req = i_upd = i_hook = ticks = 0
    t0 = clock()

    def offer(now):
        """Submit every request and land every update due by ``now``."""
        nonlocal i_req, i_upd
        if i_req < len(reqs) and reqs[i_req].due <= now:
            with annotate("bench.submit"):
                while i_req < len(reqs) and reqs[i_req].due <= now:
                    rec = reqs[i_req]
                    rec.submitted = clock() - t0
                    try:
                        rec.rid = svc.submit(pool[rec.qid], cap)
                        by_rid[rec.rid] = rec
                    except rejected:
                        rec.rejected = True
                    i_req += 1
        while i_upd < len(upds) and upds[i_upd].due <= now:
            rec = upds[i_upd]
            with annotate("bench.update"):
                rec.started = clock() - t0
                rec.epoch = apply_update(updates[i_upd])
                rec.done = clock() - t0
            i_upd += 1

    def take(finished, now):
        for rid, emb, stats in finished:
            rec = by_rid.pop(rid)
            rec.done, rec.embeddings, rec.stats = now, emb, stats

    while True:
        now = clock() - t0
        if now >= seconds:
            break
        while i_hook < len(hooks) and hooks[i_hook][0] <= now:
            hooks[i_hook][1]()
            i_hook += 1
        offer(now)
        if svc.queue or svc.n_active:
            with annotate("bench.tick"):
                finished = svc.tick()
            ticks += 1
            take(finished, clock() - t0)
            continue
        nxt = min([seconds]
                  + ([reqs[i_req].due] if i_req < len(reqs) else [])
                  + ([upds[i_upd].due] if i_upd < len(upds) else [])
                  + ([hooks[i_hook][0]] if i_hook < len(hooks) else []))
        wait = nxt - (clock() - t0)
        if wait > 0:
            with annotate("bench.sleep"):
                sleep(wait)
    # what fell due while the last tick ran is offered now, late: it was
    # due in the window, so it is attempted and timed from its due time
    offer(seconds)
    window = clock() - t0
    while by_rid and clock() - t0 < window + drain_s:
        with annotate("bench.drain"):
            finished = svc.tick()
        take(finished, clock() - t0)
        ticks += 1
    while i_hook < len(hooks):  # a hook past the window still runs
        hooks[i_hook][1]()
        i_hook += 1
    sent = reqs[:i_req]
    return WindowResult(
        requests=sent, updates=upds[:i_upd], seconds=window,
        drained_at=clock() - t0, t0=t0, ticks=ticks,
        late=[r.submitted - r.due for r in sent],
    )


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (``q`` in 0..100) of a non-empty list."""
    vals = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[k - 1]


def latencies(res: WindowResult) -> list[float]:
    """Every request's latency; one never answered (rejected, or still
    unanswered when the drain gave up) counts as answered at the drain's
    end, so it sits in the tail."""
    return [r.latency if r.latency is not None else res.drained_at - r.due
            for r in res.requests]
