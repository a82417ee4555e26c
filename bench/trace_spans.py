"""The program's own spans in a profiler trace: idle gaps named by them,
and device-idle time inside each.

The program forwards every live ``obsv`` span to the profiler as a
``TraceAnnotation`` of its name (``service.tick``, ``service.admit``,
``service.prefilter``, ``service.filter_round``, ``service.finalize``,
``query.*``), so they lie on the profiler's host plane, on the device's
clock.  This module adds them to what ``trace_reduce`` reads, without
changing it:

* ``read_xplane`` keeps the host events whose names start with
  ``PROGRAM_PREFIXES`` besides what ``trace_reduce.read_xplane`` keeps;
* ``reduce_events`` returns ``trace_reduce.reduce_events`` of the same
  trace without the program's events (the window, busy time, programs and
  operations are therefore exactly the same), with two changes:

  - each idle gap is named by the program span that holds most of it,
    each instant of the gap held by the innermost (shortest) program span
    open then, the shortest span on a tie; a gap that no program span
    overlaps keeps the ``bench.*`` name ``trace_reduce`` gives it.  (By
    plain overlap an enclosing ``service.tick`` would name every gap a
    tick holds: a gap reaches a little past the admission inside it on
    both sides, on the chip as in the tests.)
  - ``idle_s_by_annotation``: for each host annotation name, the
    device-idle seconds on the first device that fall inside the union of
    that name's intervals (nested names each count their full overlap).

A trace of a program that forwards no span gives exactly
``trace_reduce``'s summary, names included, and ``idle_s_by_annotation``
holds the ``bench.*`` names alone.

``bench/run.py`` reduces its profile with ``trace_reduce`` alone: taking
these two functions in its place is an edit of the harness, left to a
benchmark change, and ``device_idle_in_admit_share`` reads nothing before
it (``PERF.md``, section 7).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import trace_reduce
from trace_reduce import ANNOTATION_PREFIX, DEVICE_PLANE, TraceSummary, _union

PROGRAM_PREFIXES = ("service.", "query.")
HOST_PREFIXES = (ANNOTATION_PREFIX,) + PROGRAM_PREFIXES


@dataclass
class SpanTraceSummary(TraceSummary):
    idle_s_by_annotation: dict = field(default_factory=dict)  # name -> s


def _is_program(e) -> bool:
    return (e["name"].startswith(PROGRAM_PREFIXES)
            and not DEVICE_PLANE.match(e["plane"]))


def read_xplane(path: str) -> list[dict]:
    """``trace_reduce.read_xplane`` plus the program's host spans."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            for ev in line.events:
                if not device and not ev.name.startswith(HOST_PREFIXES):
                    continue
                out.append({
                    "plane": plane.name, "line": line.name, "name": ev.name,
                    "start_ns": float(ev.start_ns),
                    "dur_ns": float(ev.duration_ns),
                })
    return out


def _first_device_gaps(events, t0, t1):
    """Idle intervals of the first device plane inside [t0, t1], by the
    rule ``trace_reduce.reduce_events`` applies."""
    dev = [e for e in events if DEVICE_PLANE.match(e["plane"])]
    first = sorted({e["plane"] for e in dev})[0]
    on = [e for e in dev if e["plane"] == first]
    ops = ([e for e in on if e["line"] == trace_reduce.OPS_LINE]
           or [e for e in on if e["line"] == trace_reduce.MODULES_LINE]
           or on)
    clipped = [(max(e["start_ns"], t0), min(e["start_ns"] + e["dur_ns"], t1))
               for e in ops]
    gaps, prev = [], t0
    for s, e in _union([(s, e) for s, e in clipped if e > s]) + [[t1, t1]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    return gaps


def _overlap_s(a, b) -> float:
    """Seconds shared by two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total / 1e9


def _holder(gs, ge, spans):
    """Name of the span that holds most of [gs, ge], an instant being held
    by the shortest span open then; ``None`` when no span overlaps it."""
    over = sorted((n for n in spans if n["start_ns"] < ge
                   and n["start_ns"] + n["dur_ns"] > gs),
                  key=lambda n: n["dur_ns"])
    cuts = sorted({gs, ge} | {t for n in over
                              for t in (n["start_ns"], n["start_ns"] + n["dur_ns"])
                              if gs < t < ge})
    held = [0.0] * len(over)
    for lo, hi in zip(cuts, cuts[1:]):
        for i, n in enumerate(over):
            if n["start_ns"] <= lo and hi <= n["start_ns"] + n["dur_ns"]:
                held[i] += hi - lo
                break
    if not any(held):
        return None
    # the first of the longest holdings is the shortest span: over is sorted
    return over[held.index(max(held))]["name"]


def reduce_events(events: list[dict]) -> SpanTraceSummary | None:
    """``None`` when ``trace_reduce`` reads nothing in the trace."""
    program = [e for e in events if _is_program(e)]
    base = trace_reduce.reduce_events(
        [e for e in events if not _is_program(e)])
    if base is None:
        return None
    out = SpanTraceSummary(**vars(base))
    notes = [e for e in events if e["name"].startswith(ANNOTATION_PREFIX)
             and not DEVICE_PLANE.match(e["plane"])]
    t0 = min(e["start_ns"] for e in notes)
    t1 = max(e["start_ns"] + e["dur_ns"] for e in notes)
    gaps = _first_device_gaps(events, t0, t1)
    # the same gaps, in the same order, as the base summary names
    top = sorted(gaps, key=lambda g: g[0] - g[1])[:trace_reduce.TOP]
    out.idle_gaps = [[_holder(gs, ge, program) or label, secs]
                     for (gs, ge), (label, secs) in zip(top, base.idle_gaps)]
    by_name: dict = {}
    for n in notes + program:
        s, e = max(n["start_ns"], t0), min(n["start_ns"] + n["dur_ns"], t1)
        if e > s:
            by_name.setdefault(n["name"], []).append((s, e))
    out.idle_s_by_annotation = {
        name: _overlap_s(_union(spans), gaps)
        for name, spans in by_name.items()}
    return out
