#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload hprd.read --seed 7 --seconds 50 --trace 0

The cell (``BENCHMARK.json``) names a configuration, the deployment of the
CNI query service (``bench/configs/``), and a traffic mix
(``bench/traffic/``).  The run makes the data graph, the query pool and
(where the mix fixes it) the arrival schedule from their own seeds, and
from ``--seed`` the order of queries and the update stream
(``bench/datagen.py``); builds a
``GraphStore`` with an ``IncrementalIndex`` behind a ``GraphQueryService``;
warms up by serving every query of the pool once (and, with updates, by
landing the whole update stream on a scratch store, which compiles every
index-update shape the window will see); then offers the traffic in an
open loop for ``--seconds`` seconds through ``submit``/``tick``/
``remove_edges``/``add_edges``.  Afterwards every answer due in the window
is compared with the plain reference on the epoch it was pinned to
(``bench/check.py``).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` the
per-layer ones, read from the service's spans and from a profiler trace of
the whole window and its drain.  The last line of stdout is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: each number compared
with its limit, also printed as the last lines of stderr).

The run needs a TPU with as many chips as the cell names: without one it
exits non-zero and prints no result.  JAX's compilation cache lives in
``.jax_cache`` at the root of the checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import check  # noqa: E402
import datagen  # noqa: E402
from driver import latencies, no_annotation, run_window  # noqa: E402
from spec import Benchmark, SpecError  # noqa: E402
from trace_reduce import read_xplane, reduce_events  # noqa: E402

DRAIN_SECONDS = 120.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def stage(name: str, t0: float) -> float:
    """Log how long a set-up stage took; returns the clock."""
    t = time.perf_counter()
    log(f"setup: {name} {t - t0:.3f} s")
    return t


class MetricMissing(RuntimeError):
    """A metric that BENCHMARK.json declares for the cell read nothing."""


class RunContext:
    """What a metric reader may read (``bench/metrics/*.py``): the driver's
    records (``requests``, ``updates``, ``result``), ``seconds``,
    ``setup_s``, ``window_t0`` (perf_counter at the window's start),
    ``compiles_in_window``, and in a traced run ``spans`` (the service's
    obsv spans), ``trace`` (the reduced profile), ``profile_window``,
    ``kernel_calls`` (job shapes per kernel) and ``peaks``."""

    def __init__(self, **kw):
        self.spans = None
        self.trace = None
        self.kernel_calls = {}
        self.profile_window = None
        self.peaks = None
        self.compiles_in_window = 0
        self.__dict__.update(kw)


STORES = ("GraphStore",)


def new_store(cfg: dict, g):
    """The configuration's store (``store.kind``) over ``g``, with its
    degree cap and an ``IncrementalIndex`` on the cni_update kernel."""
    from repro.core.incremental import IncrementalIndex
    from repro.graphs.store import GraphStore

    kind = cfg["store"]["kind"]
    if kind not in STORES:
        raise SpecError(f"store kind {kind!r} is not one the harness builds "
                        f"({', '.join(STORES)})")
    store = GraphStore.from_graph(g)
    store.degree_cap = int(cfg["store"]["degree_cap"])
    store.attach_index(IncrementalIndex(use_kernel=True))
    return store


def build_system(cfg: dict, graph):
    """The system under test, built from the run's own data."""
    from repro.graphs.csr import build_graph
    from repro.serve.graph_service import GraphQueryService, GraphServiceConfig

    g = build_graph(graph.n_vertices, graph.vlabels,
                    np.stack([graph.lo, graph.hi], 1), graph.elabels)
    store = new_store(cfg, g)
    svc = GraphQueryService(store, GraphServiceConfig(
        max_query_vertices=int(cfg["max_query_vertices"]), **cfg["service"]))
    return g, store, svc


def query_graphs(pool):
    from repro.graphs.csr import build_graph

    return [build_graph(q.n_vertices, q.vlabels, q.edges, q.elabels)
            for q in pool]


def replay_updates(cfg: dict, g, batches) -> None:
    """Land the whole update stream on a scratch store built as the served
    one, so every index-update shape is compiled in set-up."""
    store = new_store(cfg, g)
    for b in batches:
        store.remove_edges(b.deletes)
        store.add_edges(b.inserts, b.insert_labels)


def record_kernel_calls(calls: dict):
    """Wrap the program's embed_join and cni_update entry points to record
    each call's job shape and host time (traced run only).  Device scalars
    are kept as they are and read after the window."""
    from repro.core import search
    from repro.kernels.cni_update import ops as cni_ops

    count, gather, update = (search._device_join_count,
                             search._device_join_emit_gather, cni_ops.cni_update)
    calls.update(embed_join=[], cni_update=[])

    def count_rec(table, n_rows, cand, n_cand, elab, q_pos, *a, **k):
        calls["embed_join"].append({
            "kind": "count", "t": time.perf_counter(), "rows": n_rows,
            "cands": n_cand, "tests": int(q_pos.shape[0]),
            "width": int(table.shape[1]), "n": int(elab.shape[0])})
        return count(table, n_rows, cand, n_cand, elab, q_pos, *a, **k)

    def gather_rec(table, cand, idx_map, n_keep, **k):
        calls["embed_join"].append({
            "kind": "gather", "t": time.perf_counter(), "survivors": n_keep,
            "width": int(table.shape[1])})
        return gather(table, cand, idx_map, n_keep, **k)

    def update_rec(rows, delta, **k):
        calls["cni_update"].append({
            "kind": "update", "t": time.perf_counter(),
            "rows": int(rows.shape[0]), "labels": int(rows.shape[1])})
        return update(rows, delta, **k)

    search._device_join_count = count_rec
    search._device_join_emit_gather = gather_rec
    cni_ops.cni_update = update_rec

    def restore():
        search._device_join_count = count
        search._device_join_emit_gather = gather
        cni_ops.cni_update = update

    return restore


def resolve_scalars(calls: dict) -> None:
    for recs in calls.values():
        for r in recs:
            for key in ("rows", "cands", "survivors"):
                if key in r and not isinstance(r[key], int):
                    r[key] = int(r[key])


def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_memory(jax, n: int) -> int:
    peaks = []
    for d in jax.devices()[:n]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class Setup:
    """A cell's inputs and its system under test, built and warmed up."""

    def __init__(self, bench: Benchmark, cell: dict, seed: int,
                 seconds: float):
        t = time.perf_counter()
        self.cfg = bench.config(cell["config"])
        self.mix = bench.traffic(cell["traffic"])
        self.ref = bench.reference(self.cfg["reference"])
        self.graph = datagen.make_graph(self.cfg["dataset"])
        self.pool = datagen.make_pool(self.graph, self.graph.adjacency(),
                                      self.mix["pool"])
        self.arrivals, self.picks = datagen.schedule(self.mix, seconds, seed,
                                                     len(self.pool))
        self.degree_cap = int(self.cfg["store"]["degree_cap"])
        if int(self.graph.degrees().max()) > self.degree_cap:
            raise SpecError("the data graph's maximum degree is past the "
                            "store's degree cap")
        if max(self.mix["pool"]["sizes"]) > int(self.cfg["max_query_vertices"]):
            raise SpecError("the mix's queries are larger than the "
                            "configuration's max_query_vertices")
        self.batches = (
            datagen.update_stream(self.graph, self.mix["updates"], seconds,
                                  seed, self.degree_cap)
            if self.mix.get("updates") else [])
        self.cap = int(self.mix["max_embeddings"])
        t = stage("inputs", t)
        self.g, self.store, self.svc = build_system(self.cfg, self.graph)
        if self.svc.d_max != self.degree_cap:
            raise RuntimeError(
                f"service degree cap {self.svc.d_max} != the store's "
                f"degree cap {self.degree_cap}")
        self.qgraphs = query_graphs(self.pool)
        # warm-up: every query of the pool once, and the update stream on
        # a scratch store
        todo = list(self.qgraphs)
        room = min(self.svc.cfg.max_slots,
                   self.svc.cfg.max_queue_depth or self.svc.cfg.max_slots)
        while todo or self.svc.queue or self.svc.n_active:
            while todo and len(self.svc.queue) < room:
                self.svc.submit(todo.pop(), self.cap)
            self.svc.tick()
        t = stage("build and warm-up of the pool", t)
        if self.batches:
            replay_updates(self.cfg, self.g, self.batches)
            stage("warm-up of the update stream", t)

    def apply_update(self, b) -> int:
        self.svc.remove_edges(b.deletes)
        self.svc.add_edges(b.inserts, b.insert_labels)
        return self.svc.epoch


def run_cell(bench: Benchmark, cell: dict, seed: int, seconds: float,
             trace: bool, *, t_start: float) -> dict:
    """One run of one cell; returns the result object (not yet printed)."""
    import jax
    import jax.monitoring

    from repro import obsv
    from repro.serve.graph_service import AdmissionRejected

    su = Setup(bench, cell, seed, seconds)
    svc, batches, cap = su.svc, su.batches, su.cap
    base_epoch = svc.epoch

    in_window = [False]
    compiles = [0]
    compiled = {}

    def on_duration(event, _secs, fun_name=None, **_kw):
        if event == COMPILE_EVENT and in_window[0]:
            compiles[0] += 1
            compiled[fun_name] = compiled.get(fun_name, 0) + 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    kernel_calls: dict = {}
    hooks, annotate, restore = (), no_annotation, None
    prof_dir = None
    if trace:
        prof_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        window = {}

        def start():
            jax.profiler.start_trace(prof_dir, profiler_options=opts)
            window["t0"] = time.perf_counter()

        def stop():
            window["t1"] = time.perf_counter()
            jax.profiler.stop_trace()

        # the whole window is profiled: a tick can take seconds, so a
        # shorter part may hold no call of a kernel the cell runs
        hooks = ((0.0, start), (seconds, stop))
        annotate = jax.profiler.TraceAnnotation
        restore = record_kernel_calls(kernel_calls)
        tracer = obsv.Tracer()
        obsv.set_tracer(tracer)

    gc.collect()
    setup_s = time.perf_counter() - t_start
    in_window[0] = True
    res = run_window(
        svc, su.qgraphs, su.arrivals, su.picks, batches, seconds, cap=cap,
        apply_update=su.apply_update, rejected=(AdmissionRejected,),
        annotate=annotate, hooks=hooks,
        drain_s=DRAIN_SECONDS)
    in_window[0] = False
    memory_peak = peak_memory(jax, int(cell["chips"]))

    ctx = RunContext(
        roofline=bench.roofline, log=log, kernel_calls=kernel_calls,
        seconds=res.seconds, requests=res.requests, updates=res.updates,
        setup_s=setup_s, window_t0=res.t0, compiles_in_window=compiles[0],
        result=res)
    if trace:
        obsv.set_tracer(None)
        restore()
        resolve_scalars(kernel_calls)
        ctx.spans = tracer.spans
        ctx.profile_window = (window["t0"], window["t1"])
        files = sorted(Path(prof_dir).rglob("*.xplane.pb"))
        ctx.trace = reduce_events(read_xplane(str(files[-1]))) if files else None
        shutil.rmtree(prof_dir, ignore_errors=True)
        if ctx.trace is not None:
            ctx.peaks = bench.peaks(jax.devices()[0].device_kind)

    index = None
    if batches:
        idx = su.store.index
        index = {"counts": idx.counts.copy(), "deg": idx.deg.copy(),
                 "cni_u64": idx.cni_u64.copy(), "cni_log": idx.cni_log.copy(),
                 "max_p": idx.max_p}
    svc.shutdown(drain=False)
    su.svc = su.store = su.g = svc = None
    gc.collect()

    readings, final_graph = check.compare(
        su.ref, su.graph, su.pool, batches, res, base_epoch=base_epoch,
        cap=cap,
        answer=lambda r: (r.embeddings, r.stats.extras["service"]["epoch"]))
    if index is not None:
        max_p = check.index_max_p(
            su.degree_cap, len(np.unique(su.graph.vlabels)),
            int(su.cfg["store"]["max_p_cap"]))
        readings.update(check.compare_index(su.ref, final_graph, index,
                                            max_p=max_p))
    limits = check.limits(index is not None)

    kind = "per_layer" if trace else "end_to_end"
    metrics, missing = {}, []
    for m in bench.metrics_for(cell["name"], kind):
        value = bench.reader(m["name"])(ctx)
        if value is None:
            missing.append(m["name"])
        else:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if missing:
        # BENCHMARK.json declares each of these for this cell: a reader
        # that finds nothing here is broken, not quiet
        raise MetricMissing(f"no reading for {', '.join(missing)} in "
                            f"{cell['name']}")

    failed = sum(r.rejected or r.done is None for r in res.requests)
    device = device_info(jax)
    device["memory_peak_bytes"] = memory_peak
    out = {"correct": check.verdict(readings, limits),
           "attempted": len(res.requests), "failed": failed,
           "metrics": metrics, "device": device}
    if trace and ctx.trace is not None:
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        out["breakdown"] = {"device_ops": ctx.trace.device_ops,
                            "idle_gaps": ctx.trace.idle_gaps}
    log("latencies (s, in order of due time): " + " ".join(
        f"{x:.3f}" for x in latencies(res)))
    late = sorted(res.late)
    if late:
        log(f"driver lateness over {len(late)} submissions: "
            f"median {late[len(late) // 2] * 1e3:.3f} ms, "
            f"max {late[-1] * 1e3:.3f} ms; ticks {res.ticks}; "
            f"updates {len(res.updates)}; window {res.seconds:.3f} s; "
            f"drained at {res.drained_at:.3f} s; compiles in window "
            f"{compiles[0]} {sorted(compiled.items())[:20]}")
    out["checks"] = {k: {"value": readings[k], "limit": limits[k]}
                     for k in limits}
    return out


def use_compile_cache(jax) -> str:
    """JAX's persistent cache at a fixed path inside the checkout; every
    program is kept, however fast it compiled."""
    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = Benchmark(ROOT)
        cell = bench.cell(args.workload)
    except SpecError as err:
        log(f"bench: {err}")
        return 2
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        log(f"bench: the system under test is not at {src / 'repro'}")
        return 2
    sys.path.insert(0, str(src))
    # the program's own cache rule reads this variable: it takes ours
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    use_compile_cache(jax)
    dev = device_info(jax)
    if dev["platform"] != "tpu" or dev["count"] < int(cell["chips"]):
        log(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); JAX "
            f"finds {dev['count']} {dev['platform']} device(s)")
        return 1
    try:
        bench.peaks(dev["kind"])
    except SpecError as err:
        log(f"bench: {err}")
        return 1
    try:
        out = run_cell(bench, cell, args.seed, args.seconds, bool(args.trace),
                       t_start=T_START)
    except (SpecError, MetricMissing) as err:
        log(f"bench: {err}")
        return 1
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
