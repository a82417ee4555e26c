"""Enumeration: two-phase device-resident join vs the chunked host join.

The device-residency claim behind ``core.search.device_join_search``
(DESIGN.md §11-§12): keeping the partial-embedding table on device across
expansion rounds removes the per-level table round-trips and host
compaction of ``bfs_join_search``, and — since the prealloc-combine
rework — sizes every level's output buffer *exactly* from a count pass
plus prefix scan, so no level can overflow and no host fallback exists.
Rows:

    enum/host_join       — bfs_join_search on the standard workload
    enum/device_join     — device_join_search, same inputs; derived field
                           carries the per-phase split (count/scan/emit)
    enum/speedup         — derived acceptance metric (expect > 1x on CPU;
                           the margin is the TPU story, where the scan
                           also stays on-device)
    enum/parity_canary   — device rows must equal host rows *bit-for-bit*
                           (same embeddings, same order) and the device
                           path must report host_levels == 0
    enum/overflow_regime — a workload whose join tables outgrow the old
                           fixed device buffer (1 << 12 rows): the regime
                           that used to drop to the chunked host fallback
                           per level.  Baseline is the host join (what the
                           fallback effectively ran); the two-phase path
                           must beat it while staying fully on the device
                           path.  The derived field carries the memory
                           ceiling: exact emit rows vs the true survivor
                           count vs the pow2 cap a grow-and-retry design
                           would have allocated.
    enum/sharded_D=<d>   — mesh-partitioned enumeration
                           (sharded_device_join_search, DESIGN.md §13) on
                           the overflow workload at 1/2/4 forced host
                           devices, each in its own subprocess (the
                           shard_benches.py harness idiom).  The derived
                           field carries shard telemetry: per-shard emit
                           extremes, rebalance rounds / moved rows /
                           cost, and per-level rebalance timings.
    enum/sharded_parity_D=<d> — hard canary per device count: sharded rows
                           must equal the single-device two-phase rows
                           bit-for-bit (truncation prefix included)
    enum/sharded_speedup — max-D sharded time vs 1-device sharded time.
                           On a single-core CPU host the virtual devices
                           share one core, so ~1x here is expected; the
                           ≥1.5x acceptance target is for hosts where
                           shards map to real parallel silicon.
    enum/trace_overhead  — the same join with obsv tracing disabled vs
                           enabled; the derived field carries both times
                           and the enabled/disabled ratio (the disabled
                           path is the <3%-overhead CI canary)
    enum/prometheus_canary — a registry fed from this bench must render
                           exposition text the in-repo checker
                           (obsv.parse_prometheus) accepts; hard-asserted
                           in smoke mode

The standard workload (few labels → large candidate sets, mid-size join
tables) sits in the regime where the host path's numpy levels are
compute-bound — the device path's fused validity wins even on CPU.

``run_all(smoke=True)`` is the CI canary: tiny graph, one repetition —
enough to catch jit-trace, parity, or fallback-resurrection breakage on
every push.  Smoke mode *hard-asserts* bit parity and ``host_levels == 0``
rather than just annotating the row.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np

from repro.core import ilgf
from repro.core.search import (
    bfs_join_search,
    device_join_search,
)
from repro.graphs import random_labeled_graph, random_walk_query
from repro.graphs.csr import induced_subgraph

_SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

# the fixed table capacity the pre-two-phase enumerator shipped with; any
# level outgrowing it used to fall back to a chunked host join
_LEGACY_TABLE_CAP = 1 << 12


def _bench(fn, *, reps: int, warmup: int = 1):
    for _ in range(warmup):
        fn()
    return min(
        (lambda t0: (fn(), time.perf_counter() - t0)[1])(time.perf_counter())
        for _ in range(reps)
    )


def _search_inputs(v, e, n_labels, u, *, seed=2, sparse=True):
    g = random_labeled_graph(v, e, n_labels, n_edge_labels=1, seed=seed)
    q = random_walk_query(g, u, sparse=sparse, seed=seed + 10)
    res = ilgf(g, q)
    alive = np.asarray(res.alive)
    sub, _ = induced_subgraph(g, alive)
    cand = np.asarray(res.candidates)[alive]
    return sub, q, cand


def _phase_fields(report: dict) -> str:
    return (
        f"count_us={report['count_seconds'] * 1e6:.0f};"
        f"scan_us={report['scan_seconds'] * 1e6:.0f};"
        f"emit_us={report['emit_seconds'] * 1e6:.0f};"
        f"scan_path={report['scan_path']}"
    )


def _ceiling_fields(report: dict) -> str:
    true_rows = report["max_table_rows"]
    pow2 = 1 << max(true_rows - 1, 1).bit_length() if true_rows else 0
    return (
        f"emit_rows={report['max_emit_rows']};true_rows={true_rows};"
        f"pow2_cap={pow2}"
    )


def bench_device_vs_host(rows: list, *, smoke: bool = False):
    if smoke:
        v, e, u, reps = 200, 1100, 4, 1
    else:
        v, e, u, reps = 600, 3500, 4, 5
    sub, q, cand = _search_inputs(v, e, 2, u)

    host = bfs_join_search(sub, q, cand)
    report: dict = {}
    dev = device_join_search(sub, q, cand, report=report)
    parity = bool(np.array_equal(host, dev))
    no_fallback = report["host_levels"] == 0
    if smoke:
        assert parity, "enum smoke: device rows != host rows"
        assert no_fallback, "enum smoke: host fallback resurrected"

    t_host = _bench(lambda: bfs_join_search(sub, q, cand), reps=reps)
    # timed without a report dict: phase-level block_until_ready is only
    # paid when telemetry is requested
    t_dev = _bench(lambda: device_join_search(sub, q, cand), reps=reps)
    n_emb = host.shape[0]
    rows.append((
        "enum/host_join", t_host * 1e6,
        f"emb={n_emb};emb_per_s={n_emb / t_host:.0f}",
    ))
    rows.append((
        "enum/device_join", t_dev * 1e6,
        f"emb={n_emb};emb_per_s={n_emb / t_dev:.0f};"
        f"rounds={report['device_rounds']};{_phase_fields(report)}",
    ))
    rows.append((
        "enum/speedup", 0.0,
        f"device_vs_host={t_host / t_dev:.2f}x",
    ))
    rows.append((
        "enum/parity_canary", 0.0,
        "ok" if parity and no_fallback
        else "MISMATCH — device rows != host rows or fallback fired",
    ))


def bench_overflow_regime(rows: list, *, smoke: bool = False):
    """Tables past the old fixed cap: two-phase must beat the host join."""
    if smoke:
        v, e, u, reps = 220, 1400, 5, 1
    else:
        v, e, u, reps = 600, 3500, 5, 3
    sub, q, cand = _search_inputs(v, e, 2, u)
    host = bfs_join_search(sub, q, cand)
    report: dict = {}
    dev = device_join_search(sub, q, cand, report=report)
    same = bool(np.array_equal(host, dev))  # bit-order contract holds too
    on_device = report["host_levels"] == 0
    overflowed_legacy = report["max_table_rows"] > _LEGACY_TABLE_CAP
    if smoke:
        assert same, "enum overflow smoke: device rows != host rows"
        assert on_device, "enum overflow smoke: host fallback resurrected"
    t_host = _bench(lambda: bfs_join_search(sub, q, cand), reps=reps)
    t_dev = _bench(lambda: device_join_search(sub, q, cand), reps=reps)
    status = "ok" if same and on_device else "MISMATCH or fallback fired"
    if not overflowed_legacy:
        status += ";below_legacy_cap"  # workload too small to prove regime
    rows.append((
        "enum/overflow_regime", t_dev * 1e6,
        (f"vs_host_fallback={t_host / t_dev:.2f}x;"
         f"{_ceiling_fields(report)};{_phase_fields(report)};{status}"),
    ))


# child for the mesh-partitioned rows: one subprocess per device count
# (the only way to vary the virtual-device count under one harness run —
# the shard_benches.py idiom), hard-asserting bit parity before timing
_SHARDED_CHILD = textwrap.dedent(
    """
    import json, os, time
    import numpy as np
    import jax

    from repro.core import ilgf
    from repro.core.distributed import device_mesh
    from repro.core.search import device_join_search, \\
        sharded_device_join_search
    from repro.graphs import random_labeled_graph, random_walk_query
    from repro.graphs.csr import induced_subgraph

    d = int(os.environ["ENUM_BENCH_DEVICES"])
    smoke = os.environ.get("ENUM_BENCH_SMOKE") == "1"
    assert len(jax.devices()) == d, jax.devices()
    mesh = device_mesh(d)

    if smoke:
        v, e, u, reps = 220, 1400, 5, 1
    else:
        v, e, u, reps = 600, 3500, 5, 3
    g = random_labeled_graph(v, e, 2, n_edge_labels=1, seed=2)
    q = random_walk_query(g, u, sparse=True, seed=12)
    res = ilgf(g, q)
    alive = np.asarray(res.alive)
    sub, _ = induced_subgraph(g, alive)
    cand = np.asarray(res.candidates)[alive]

    ref = device_join_search(sub, q, cand)
    report = {}
    sh = sharded_device_join_search(sub, q, cand, mesh=mesh, report=report)
    parity = bool(np.array_equal(ref, sh))
    trunc = bool(np.array_equal(
        device_join_search(sub, q, cand, max_embeddings=7),
        sharded_device_join_search(sub, q, cand, mesh=mesh,
                                   max_embeddings=7),
    ))
    assert parity and trunc, "sharded enum parity canary failed"
    assert report["host_levels"] == 0

    def timed(fn):
        fn()  # warmup (trace + compile)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    t_sh = timed(
        lambda: sharded_device_join_search(sub, q, cand, mesh=mesh)
    )
    print(json.dumps({
        "devices": d, "t_sharded": t_sh, "parity": parity and trunc,
        "emb": int(ref.shape[0]),
        "max_table_rows": report["max_table_rows"],
        "emit_rows_max": report["emit_rows_max"],
        "emit_rows_min": report["emit_rows_min"],
        "rebalance_rounds": report["rebalance_rounds"],
        "rebalance_rows_moved": report["rebalance_rows_moved"],
        "rebalance_seconds": report["rebalance_seconds"],
        "levels": report["levels"],
    }))
    """
)


# the mesh children run on forced CPU host devices, never on the chip:
# every row they produce says so
_CPU_REHEARSAL = "platform=cpu_forced_devices"


def _run_sharded_child(devices: int, smoke: bool) -> dict:
    env = dict(os.environ)
    # the parent may hold the accelerator; a chip serves one process
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}"
    )
    env["ENUM_BENCH_DEVICES"] = str(devices)
    env["ENUM_BENCH_SMOKE"] = "1" if smoke else "0"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED_CHILD],
        env=env, capture_output=True, text=True, timeout=1800,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"sharded enum bench child (D={devices}) failed:\n"
            f"{out.stderr[-2000:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def bench_sharded(rows: list, *, smoke: bool = False,
                  device_counts=(1, 2, 4)):
    """Mesh-partitioned enumeration rows (overflow workload, DESIGN.md §13).

    Each device count is a subprocess with that many forced host devices;
    the child hard-asserts bit parity (full table and truncation prefix)
    against the single-device two-phase join before any timing, so a
    MISMATCH row can only appear if the canary logic itself is broken.
    Per-level rebalance timings travel in the JSON detail field.
    """
    times: dict[int, float] = {}
    for d in device_counts:
        r = _run_sharded_child(d, smoke)
        times[d] = r["t_sharded"]
        level_detail = ";".join(
            f"L{lv['level']}:rows={max(lv['emit_rows'])}"
            + (f",rebal_us={lv['rebalance_seconds'] * 1e6:.0f}"
               if lv["rebalanced"] else "")
            for lv in r["levels"]
        )
        rows.append((
            f"enum/sharded_D={d}", r["t_sharded"] * 1e6,
            (f"emb={r['emb']};true_rows={r['max_table_rows']};"
             f"emit_shard_max={r['emit_rows_max']};"
             f"emit_shard_min={r['emit_rows_min']};"
             f"rebal_rounds={r['rebalance_rounds']};"
             f"rebal_moved={r['rebalance_rows_moved']};"
             f"rebal_us={r['rebalance_seconds'] * 1e6:.0f};"
             f"{level_detail};{_CPU_REHEARSAL}"),
        ))
        rows.append((
            f"enum/sharded_parity_D={d}", 0.0,
            ("ok" if r["parity"] else "MISMATCH") + f";{_CPU_REHEARSAL}",
        ))
    d_max_count = max(device_counts)
    rows.append((
        "enum/sharded_speedup", 0.0,
        f"D={d_max_count}_vs_D=1="
        f"{times[1] / times[d_max_count]:.2f}x;{_CPU_REHEARSAL}",
    ))


def bench_trace_overhead(rows: list, *, smoke: bool = False):
    """Observability canaries (docs/OBSERVABILITY.md).

    ``enum/trace_overhead`` times the same two-phase join with tracing
    disabled vs enabled — the disabled path must stay free (instrumented
    sites cost one global ``None`` check), and the enabled-vs-disabled
    ratio is the recorded cost of span capture itself.
    ``enum/prometheus_canary`` renders a registry fed from this bench and
    runs it through the in-repo exposition checker.
    """
    from repro import obsv

    if smoke:
        v, e, u, reps = 200, 1100, 4, 3
    else:
        v, e, u, reps = 600, 3500, 4, 5
    sub, q, cand = _search_inputs(v, e, 2, u)
    t_off = _bench(lambda: device_join_search(sub, q, cand), reps=reps)
    with obsv.tracing() as tracer:
        t_on = _bench(lambda: device_join_search(sub, q, cand), reps=reps)
    rows.append((
        "enum/trace_overhead", t_off * 1e6,
        (f"disabled_us={t_off * 1e6:.0f};enabled_us={t_on * 1e6:.0f};"
         f"enabled_vs_disabled={t_on / t_off:.3f}x;"
         f"spans={len(tracer.spans)}"),
    ))

    reg = obsv.MetricsRegistry()
    h = reg.histogram("repro_bench_enum_seconds", "enum bench wall time",
                      start=1e-6, factor=4.0, count=12)
    h.observe(t_off, tracing="disabled")
    h.observe(t_on, tracing="enabled")
    reg.counter("repro_bench_enum_runs_total", "bench invocations").inc(
        2 * (reps + 1)
    )
    try:
        obsv.parse_prometheus(reg.render_prometheus())
        status = "ok"
    except ValueError as err:  # pragma: no cover - canary trip wire
        status = f"INVALID:{err}"
    if smoke:
        assert status == "ok", status
    rows.append(("enum/prometheus_canary", 0.0, status))


def run_all(*, smoke: bool = False) -> list:
    rows: list = []
    bench_device_vs_host(rows, smoke=smoke)
    bench_overflow_regime(rows, smoke=smoke)
    bench_sharded(rows, smoke=smoke)
    bench_trace_overhead(rows, smoke=smoke)
    return rows
