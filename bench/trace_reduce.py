"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

A trace is first flattened to plain event records (``read_xplane``), so the
reduction itself (``reduce_events``) runs on lists of dicts and is tested on
a small recorded trace without JAX.  Device work is read from each device
plane: ``XLA Ops`` events give busy time (the union of their intervals) and
the top operations, ``XLA Modules`` events give the device time of each
compiled program; a plane without either line counts every event it holds
as busy.  Host events named ``bench.*`` are the benchmark's own
``TraceAnnotation``s; they bound the traced window and label idle gaps.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+")
ANNOTATION_PREFIX = "bench."
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
TOP = 10


def program_name(name: str) -> str:
    """``jit__device_join_count(123)`` -> ``_device_join_count``."""
    name = re.sub(r"\(\d+\)$", "", name.strip())
    return name[4:] if name.startswith("jit_") else name


def read_xplane(path: str) -> list[dict]:
    """The events the reduction needs, from one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            for ev in line.events:
                if not device and not ev.name.startswith(ANNOTATION_PREFIX):
                    continue
                out.append({
                    "plane": plane.name, "line": line.name, "name": ev.name,
                    "start_ns": float(ev.start_ns),
                    "dur_ns": float(ev.duration_ns),
                })
    return out


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # mean over the device planes
    n_devices: int
    program_s: dict = field(default_factory=dict)  # program -> device s
    device_ops: list = field(default_factory=list)  # [[op, s]], top 10
    idle_gaps: list = field(default_factory=list)   # [[host label, s]]


def reduce_events(events: list[dict]) -> TraceSummary | None:
    """``None`` when the trace holds no device operation in its window."""
    notes = [e for e in events if e["name"].startswith(ANNOTATION_PREFIX)
             and not DEVICE_PLANE.match(e["plane"])]
    dev = [e for e in events if DEVICE_PLANE.match(e["plane"])]
    if not notes or not dev:
        return None
    t0 = min(e["start_ns"] for e in notes)
    t1 = max(e["start_ns"] + e["dur_ns"] for e in notes)
    planes = sorted({e["plane"] for e in dev})
    busy, program_s, op_s = [], {}, {}
    first_union = None
    for plane in planes:
        on = [e for e in dev if e["plane"] == plane]
        ops = ([e for e in on if e["line"] == OPS_LINE]
               or [e for e in on if e["line"] == MODULES_LINE] or on)
        clipped = [(max(e["start_ns"], t0), min(e["start_ns"] + e["dur_ns"], t1))
                   for e in ops]
        union = _union([(s, e) for s, e in clipped if e > s])
        if first_union is None:
            first_union = union
        busy.append(sum(e - s for s, e in union))
        for e in on:
            s, end = max(e["start_ns"], t0), min(e["start_ns"] + e["dur_ns"], t1)
            if end <= s:
                continue
            if e["line"] == MODULES_LINE:
                key = program_name(e["name"])
                program_s[key] = program_s.get(key, 0.0) + (end - s) / 1e9
            elif e["line"] == OPS_LINE:
                op_s[e["name"]] = op_s.get(e["name"], 0.0) + (end - s) / 1e9
    if not any(busy):
        return None
    # idle gaps on the first device, each named by the host annotation
    # that overlaps it most
    gaps, prev = [], t0
    for s, e in first_union + [[t1, t1]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    labelled = []
    for gs, ge in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        best, label = 0.0, "none"
        for n in notes:
            ov = min(ge, n["start_ns"] + n["dur_ns"]) - max(gs, n["start_ns"])
            if ov > best:
                best, label = ov, n["name"]
        labelled.append([label, (ge - gs) / 1e9])
    top_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:TOP]
    return TraceSummary(
        window_s=(t1 - t0) / 1e9,
        busy_s=sum(busy) / len(busy) / 1e9,
        n_devices=len(planes),
        program_s=program_s,
        device_ops=[[k, v] for k, v in top_ops],
        idle_gaps=labelled,
    )
