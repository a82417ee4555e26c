"""Unified observability subsystem (repro/obsv): tracing, metrics, reports.

Three layers of contract:

* unit — ``Tracer`` span trees (nesting, trace ids, detached roots,
  retroactive spans, Chrome export), ``MetricsRegistry`` instruments and
  the in-repo Prometheus exposition checker, and the typed ``Report``
  Mapping/validation semantics;
* sweep — **every** engine exit path (normal, filter-killed, all-pruned,
  zero-embedding, single-vertex, truncated, sharded, out-of-core) must
  leave a complete *closed* span tree and schema-valid typed reports,
  property-tested over random workloads;
* end-to-end — one query through a ``GraphQueryService`` on an
  ``OutOfCoreGraphStore`` yields a single per-request trace (queue-wait →
  admit → rounds → finalize → enumeration → chunk fetches) exportable as
  valid Perfetto JSON, plus Prometheus-parseable service metrics.
"""

import json

import numpy as np
import pytest
from hypothesis import given, settings

from strategies import graph_query_seeds, seeded_graph_and_query

from repro import obsv
from repro.core.engine import SubgraphQueryEngine
from repro.core.planner import QueryPlanner
from repro.core.search import empty_enum_report
from repro.graphs import random_labeled_graph, random_walk_query
from repro.graphs.csr import build_graph
from repro.graphs.ooc import OutOfCoreGraphStore
from repro.serve import GraphQueryService, GraphServiceConfig


# ---------------------------------------------------------------------------
# tracer unit tests
# ---------------------------------------------------------------------------


class TestTracer:
    def test_nesting_and_trace_ids(self):
        tr = obsv.Tracer()
        with tr.span("a") as a:
            with tr.span("b") as b:
                assert b.parent_id == a.span_id
                assert b.trace_id == a.trace_id
        with tr.span("c") as c:
            assert c.parent_id is None
            assert c.trace_id != a.trace_id  # new root = new trace
        assert not tr.open_spans
        assert [s.name for s in tr.roots()] == ["a", "c"]
        assert tr.children_of(a) == [b]
        assert all(s.closed and s.duration_ns >= 0 for s in tr.spans)

    def test_detached_root_spans_many_scopes(self):
        tr = obsv.Tracer()
        root = tr.start_span("request", detached=True, rid=7)
        assert not tr.open_spans  # detached spans stay off the stack
        with tr.activate(root):
            with tr.span("tick1") as t1:
                pass
        with tr.activate(root):
            with tr.span("tick2") as t2:
                pass
        tr.end_span(root)
        assert t1.parent_id == t2.parent_id == root.span_id
        assert {s.trace_id for s in tr.spans} == {root.trace_id}

    def test_span_at_retroactive(self):
        import time

        tr = obsv.Tracer()
        t0 = time.perf_counter()
        t1 = t0 + 0.25
        with tr.span("parent") as p:
            s = tr.span_at("queued", t0, t1, rid=1)
        assert s.parent_id == p.span_id
        assert s.closed
        assert abs(s.duration_ns - 0.25e9) < 1e4

    def test_out_of_order_end_tolerated(self):
        tr = obsv.Tracer()
        a = tr.start_span("a")
        b = tr.start_span("b")
        tr.end_span(a)  # not the stack top
        tr.end_span(b)
        assert not tr.open_spans
        with pytest.raises(ValueError, match="already ended"):
            tr.end_span(a)

    def test_chrome_trace_export(self):
        tr = obsv.Tracer()
        with tr.span("q", n=3):
            with tr.span("q.inner", arr=np.arange(2)):
                pass
        doc = json.loads(json.dumps(tr.to_chrome_trace()))  # serializable
        events = doc["traceEvents"]
        assert len(events) == 2
        assert all(e["ph"] == "X" for e in events)
        assert events == sorted(events, key=lambda e: e["ts"])
        by_name = {e["name"]: e for e in events}
        assert by_name["q"]["args"]["n"] == 3
        assert isinstance(by_name["q.inner"]["args"]["arr"], str)  # repr'd
        assert by_name["q.inner"]["pid"] == by_name["q"]["pid"]
        assert by_name["q.inner"]["cat"] == "q"

    def test_write_chrome_trace(self, tmp_path):
        tr = obsv.Tracer()
        with tr.span("x"):
            pass
        path = tmp_path / "trace.json"
        tr.write_chrome_trace(str(path))
        assert json.loads(path.read_text())["traceEvents"]

    def test_disabled_module_helpers_are_noops(self):
        assert not obsv.enabled()
        assert obsv.span("anything", k=1) is obsv.NOOP_SPAN
        assert obsv.span_at("x", 0.0, 1.0) is None
        assert obsv.start_detached("x") is None
        with obsv.activate(None) as s:
            assert s is None
        obsv.end(None)  # no-op, no raise

    def test_mirror_copies_exact_interval(self):
        tr = obsv.Tracer()
        root = tr.start_span("request", detached=True)
        with tr.span("round") as live:
            pass
        copy = tr.mirror(live, parent=root, shared=True)
        assert (copy.start_ns, copy.end_ns) == (live.start_ns, live.end_ns)
        assert copy.name == "round" and copy.parent_id == root.span_id
        assert copy.attrs == {"shared": True} and copy in tr.spans
        assert obsv.mirror(live, parent=root) is None  # no tracer installed
        with obsv.tracing():
            assert obsv.mirror(obsv.NOOP_SPAN) is None

    def test_tracing_scope_installs_and_restores(self):
        assert obsv.get_tracer() is None
        with obsv.tracing() as tr:
            assert obsv.get_tracer() is tr
            with obsv.span("inside"):
                pass
            with obsv.tracing() as inner:
                assert obsv.get_tracer() is inner
            assert obsv.get_tracer() is tr  # nested scope restored us
        assert obsv.get_tracer() is None
        assert tr.names() == {"inside"}


@pytest.fixture
def annotations(monkeypatch):
    """Stands in for ``jax.profiler.TraceAnnotation``: logs each annotation
    entered, with its keywords, and each one left."""
    import contextlib

    import jax.profiler

    log = []

    @contextlib.contextmanager
    def annotation(name, **kwargs):
        log.append(("enter", name, kwargs))
        try:
            yield
        finally:
            log.append(("exit", name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", annotation)
    return log


class TestProfilerAnnotations:
    def test_each_live_span_enters_one_bare_annotation(self, annotations):
        with obsv.tracing() as tr:
            with obsv.span("service.tick", active=2):
                with obsv.span("service.admit", slot=0):
                    pass
        assert annotations == [
            ("enter", "service.tick", {}), ("enter", "service.admit", {}),
            ("exit", "service.admit"), ("exit", "service.tick"),
        ]
        assert tr.names() == {"service.tick", "service.admit"}

    def test_annotation_left_when_the_span_raises(self, annotations):
        with obsv.tracing() as tr:
            with pytest.raises(RuntimeError):
                with obsv.span("service.finalize"):
                    raise RuntimeError("boom")
        assert annotations == [("enter", "service.finalize", {}),
                               ("exit", "service.finalize")]
        assert [s.name for s in tr.spans] == ["service.finalize"]

    def test_retroactive_detached_and_mirrored_spans_stay_out(
            self, annotations):
        import time

        with obsv.tracing():
            root = obsv.start_detached("service.request")
            with obsv.activate(root):
                obsv.span_at("service.queue_wait", time.perf_counter(),
                             time.perf_counter())
            with obsv.span("service.filter_round") as live:
                pass
            obsv.mirror(live, parent=root)
            obsv.end(root)
        assert annotations == [("enter", "service.filter_round", {}),
                               ("exit", "service.filter_round")]

    def test_no_tracer_builds_no_annotation(self, annotations):
        assert obsv.span("service.tick", active=1) is obsv.NOOP_SPAN
        with obsv.span("service.admit"):
            pass
        assert annotations == []


# ---------------------------------------------------------------------------
# metrics unit tests
# ---------------------------------------------------------------------------


class TestMetrics:
    def test_counter_and_labels(self):
        reg = obsv.MetricsRegistry()
        c = reg.counter("repro_test_total", "help text")
        c.inc()
        c.inc(4, status="ok")
        c.inc(1, status="bad")
        snap = reg.snapshot()["repro_test_total"]
        assert snap["series"][()] == 1
        assert snap["series"][(("status", "ok"),)] == 4
        with pytest.raises(ValueError):
            c.inc(-1)
        # get-or-create returns the same instrument; kind conflicts raise
        assert reg.counter("repro_test_total", "help text") is c
        with pytest.raises(ValueError):
            reg.gauge("repro_test_total", "different kind")

    def test_histogram_bucketing(self):
        reg = obsv.MetricsRegistry()
        h = reg.histogram("repro_lat_seconds", "latency",
                          start=1e-3, factor=10.0, count=3)
        # bounds: 1ms, 10ms, 100ms, +Inf
        for v in (5e-4, 5e-3, 5e-2, 5.0):
            h.observe(v)
        snap = reg.snapshot()["repro_lat_seconds"]["series"][()]
        assert snap["cumulative"] == [1, 2, 3, 4]
        assert snap["count"] == 4
        assert snap["sum"] == pytest.approx(5e-4 + 5e-3 + 5e-2 + 5.0)

    def test_render_parses_and_roundtrips(self):
        reg = obsv.MetricsRegistry()
        reg.counter("repro_c_total", 'escaping "quotes" and \\ ok').inc(
            2, path="a\\b", msg='say "hi"'
        )
        reg.gauge("repro_g", "a gauge").set(-1.5)
        h = reg.histogram("repro_h_seconds", "hist")
        h.observe(0.02, stage="x")
        h.observe(123.0, stage="x")  # overflow bucket
        text = reg.render_prometheus()
        fams = obsv.parse_prometheus(text)
        assert set(fams) == {"repro_c_total", "repro_g", "repro_h_seconds"}
        assert fams["repro_h_seconds"]["type"] == "histogram"

    @pytest.mark.parametrize("bad", [
        "no help or type\nrepro_x 1\n",
        "# HELP repro_x h\n# TYPE repro_x counter\nrepro_x notanumber\n",
        # histogram whose +Inf bucket disagrees with _count
        ("# HELP repro_h h\n# TYPE repro_h histogram\n"
         'repro_h_bucket{le="1.0"} 1\nrepro_h_bucket{le="+Inf"} 1\n'
         "repro_h_sum 1.0\nrepro_h_count 2\n"),
        # non-monotone cumulative buckets
        ("# HELP repro_h h\n# TYPE repro_h histogram\n"
         'repro_h_bucket{le="1.0"} 3\nrepro_h_bucket{le="2.0"} 2\n'
         'repro_h_bucket{le="+Inf"} 3\n'
         "repro_h_sum 1.0\nrepro_h_count 3\n"),
    ])
    def test_parser_rejects_malformed_exposition(self, bad):
        with pytest.raises(ValueError):
            obsv.parse_prometheus(bad)


# ---------------------------------------------------------------------------
# typed report unit tests
# ---------------------------------------------------------------------------


class TestReports:
    def test_enum_report_matches_legacy_schema(self):
        # the plain-dict schema searchers fill is generated from the typed
        # report, so the two can never drift
        legacy = empty_enum_report()
        rep = obsv.EnumReport.empty()
        assert list(rep.keys()) == list(legacy.keys())
        assert rep == legacy          # Mapping equality vs plain dict
        assert dict(rep) == legacy
        assert rep["host_levels"] == 0

    def test_from_dict_rejects_missing_and_unknown(self):
        d = empty_enum_report()
        d.pop("scan_path")
        with pytest.raises(ValueError, match="missing.*scan_path"):
            obsv.EnumReport.from_dict(d)
        d = empty_enum_report()
        d["bogus"] = 1
        with pytest.raises(ValueError, match="unknown.*bogus"):
            obsv.EnumReport.from_dict(d)

    def test_validate_type_errors(self):
        d = empty_enum_report()
        d["device_rounds"] = "three"
        with pytest.raises(ValueError, match="device_rounds"):
            obsv.EnumReport.from_dict(d)
        d = empty_enum_report()
        d["host_syncs"] = 2.5
        with pytest.raises(ValueError, match="host_syncs"):
            obsv.EnumReport.from_dict(d)
        d = empty_enum_report()
        d.pop("host_syncs")
        with pytest.raises(ValueError, match="missing.*host_syncs"):
            obsv.EnumReport.from_dict(d)
        d = empty_enum_report()
        d["scan_path"] = "gpu"
        with pytest.raises(ValueError, match="scan_path"):
            obsv.EnumReport.from_dict(d)

    def test_numpy_scalars_normalized(self):
        rep = obsv.ServiceReport(
            slot=np.int32(2), epoch=np.int64(0),
            queue_seconds=np.float64(0.5),
        ).validate()
        assert type(rep["slot"]) is int
        assert json.loads(json.dumps(rep.to_dict()))["slot"] == 2

    def test_ooc_merge_semantics(self):
        a = obsv.OocReport(
            chunks_read=2, cache_hits=1, cache_misses=1, bytes_read=100,
            n_chunks=8, edges_fetched=40, peak_resident_bytes=100,
            resident_budget_bytes=1000, fetch_seconds=0.1,
        )
        b = obsv.OocReport(
            chunks_read=3, cache_hits=3, cache_misses=0, bytes_read=50,
            n_chunks=8, edges_fetched=10, peak_resident_bytes=160,
            resident_budget_bytes=1000, fetch_seconds=0.2, partial=True,
        )
        m = a.merge(b)
        assert m["chunks_read"] == 5 and m["fetches"] == 2
        assert m["bytes_read"] == 150
        assert m["peak_resident_bytes"] == 160   # gauge: replaced
        assert m["partial"] is True              # sticky once set
        assert a["chunks_read"] == 2             # merge never mutates

    def test_plan_skipped_contract(self):
        rep = obsv.PlanReport.skipped()
        assert rep["source"] == "skipped" and rep["order"] == ()
        rep.validate()

    def test_validate_extras_flags_untyped_dicts(self):
        obsv.validate_extras({"enum": obsv.EnumReport.empty(), "shards": 2})
        with pytest.raises(ValueError, match="enum"):
            obsv.validate_extras({"enum": empty_enum_report()})


# ---------------------------------------------------------------------------
# exit-path sweep: closed span tree + valid typed reports on every path
# ---------------------------------------------------------------------------


def _zero_embedding_pair():
    # survives ILGF (filters ignore edge labels) but the el=1 edge does not
    # exist in the data graph → zero embeddings out of the enumerator
    data = build_graph(3, [0, 1, 0], [(0, 1), (1, 2)], elabels=[0, 0])
    q = build_graph(3, [0, 1, 0], [(0, 1), (1, 2)], elabels=[0, 1])
    return data, q


def _checked_query(data, q, *, max_embeddings=None, **engine_kwargs):
    """Run one traced query and assert the full observability contract."""
    eng = SubgraphQueryEngine(data, enumerator="device",
                              planner=QueryPlanner.for_data(data),
                              **engine_kwargs)
    with obsv.tracing() as tr:
        emb, stats = eng.query(q, max_embeddings=max_embeddings)
    assert not tr.open_spans, f"open spans leaked: {tr.open_spans}"
    assert all(s.closed for s in tr.spans)
    names = tr.names()
    assert "query" in names and "query.filter" in names
    root = [s for s in tr.roots() if s.name == "query"]
    assert len(root) == 1
    assert {s.trace_id for s in tr.spans} == {root[0].trace_id}
    json.dumps(tr.to_chrome_trace())  # exportable
    obsv.validate_extras(stats.extras)
    assert isinstance(stats.extras["enum"], obsv.EnumReport)
    assert isinstance(stats.extras["plan"], obsv.PlanReport)
    assert stats.extras["enum"]["host_levels"] == 0
    syncs = stats.extras["enum"]["host_syncs"]
    assert type(syncs) is int and syncs >= 0
    if stats.extras["enum"]["device_rounds"]:
        # one validity bitmask a level at least, then the emit sync
        assert syncs >= stats.extras["enum"]["device_rounds"] + 1
    return emb, stats, tr


def test_exit_path_normal():
    g, q = seeded_graph_and_query(5)
    emb, stats, tr = _checked_query(g, q)
    assert emb.shape[0] > 0
    assert "query.enumerate" in tr.names()
    assert "enum.emit" in tr.names()
    assert stats.extras["plan"]["source"] != "skipped"


def test_exit_path_filter_killed():
    g, _ = seeded_graph_and_query(5)
    # labels 98/99 never occur in the data graph → ILGF kills everything
    q = build_graph(3, [99, 98, 99], [(0, 1), (1, 2)])
    emb, stats, tr = _checked_query(g, q)
    assert emb.shape[0] == 0
    assert stats.extras["enum"] == obsv.EnumReport.empty()
    assert stats.extras["plan"]["source"] == "skipped"
    assert "query.enumerate" not in tr.names()  # killed before enumeration


def test_exit_path_zero_embeddings():
    data, q = _zero_embedding_pair()
    emb, stats, _ = _checked_query(data, q)
    assert emb.shape[0] == 0
    assert stats.vertices_after > 0  # the filter did NOT kill it


def test_exit_path_single_vertex_query():
    g, _ = seeded_graph_and_query(5)
    q = build_graph(1, [int(np.asarray(g.vlabels)[0])], [])
    emb, stats, _ = _checked_query(g, q)
    assert emb.shape == (emb.shape[0], 1) and emb.shape[0] > 0


def test_exit_path_truncated():
    g, q = seeded_graph_and_query(5)
    emb, stats, _ = _checked_query(g, q, max_embeddings=1)
    assert emb.shape[0] == 1


def test_exit_path_sharded():
    from repro.core.distributed import device_mesh

    g, q = seeded_graph_and_query(5)
    emb, stats, tr = _checked_query(g, q, mesh=device_mesh())
    assert emb.shape[0] > 0
    assert stats.extras["enum"]["enum_shards"] >= 1
    assert stats.extras["enum"]["levels"]


def test_exit_path_ooc():
    g, q = seeded_graph_and_query(5)
    store = OutOfCoreGraphStore.from_graph(g, chunk_edges=64)
    emb, stats, tr = _checked_query(store.snapshot(), q)
    ref, _ = SubgraphQueryEngine(g, enumerator="device").query(q)
    np.testing.assert_array_equal(np.asarray(emb), np.asarray(ref))
    assert isinstance(stats.extras["ooc"], obsv.OocReport)
    assert stats.extras["ooc"]["chunks_read"] > 0
    assert {"ooc.fetch", "ooc.manifest", "ooc.chunk"} <= tr.names()


@given(seed=graph_query_seeds())
@settings(max_examples=15, deadline=None)
def test_exit_path_property_random_workloads(seed):
    """Any random workload leaves a closed tree + schema-valid reports."""
    g, q = seeded_graph_and_query(seed)
    emb, stats, tr = _checked_query(g, q)
    assert stats.n_embeddings == emb.shape[0]
    # report equals the legacy plain-dict schema key-for-key
    assert set(stats.extras["enum"].keys()) == set(empty_enum_report())


def test_batch_engine_spans_and_report():
    from repro.core import BatchQueryEngine

    g, _ = seeded_graph_and_query(5)
    queries = [random_walk_query(g, 4, seed=900 + i) for i in range(3)]
    eng = BatchQueryEngine(g)
    with obsv.tracing() as tr:
        results = eng.query_batch(queries)
    assert not tr.open_spans
    assert {"batch.bucket", "batch.round", "batch.retire"} <= tr.names()
    for _, stats in results:
        obsv.validate_extras(stats.extras)
        rep = stats.extras["batch"]
        assert isinstance(rep, obsv.BatchReport)
        assert len(rep["bucket"]) == 3 and rep["batch_size"] >= 1


# ---------------------------------------------------------------------------
# end-to-end: service on an out-of-core store → one trace + metrics export
# ---------------------------------------------------------------------------


def test_service_ooc_single_trace_and_metrics(tmp_path):
    g = random_labeled_graph(150, 500, 4, seed=7)
    q = random_walk_query(g, 4, seed=8)
    store = OutOfCoreGraphStore.from_graph(
        g, storage_dir=str(tmp_path / "store"), chunk_edges=64
    )
    svc = GraphQueryService(store, GraphServiceConfig(
        enumerator="device", plan_queries=True,
    ))
    with obsv.tracing() as tr:
        rid = svc.submit(q)
        done = svc.run_to_completion()
    assert not tr.open_spans
    (rid2, emb, stats), = done
    assert rid2 == rid

    svc_rep = stats.extras["service"]
    assert isinstance(svc_rep, obsv.ServiceReport)
    assert svc_rep["queue_seconds"] >= 0 and svc_rep["rounds"] >= 1
    obsv.validate_extras(stats.extras)

    # the whole request lifetime is ONE trace: queue-wait → admit →
    # epoch-pin → chunk fetch → peeling rounds → finalize → enumeration
    roots = [s for s in tr.roots() if s.name == "service.request"]
    assert len(roots) == 1
    assert roots[0].trace_id == svc_rep["trace_id"]
    in_trace = {s.name for s in tr.spans if s.trace_id == roots[0].trace_id}
    assert {
        "service.request", "service.queue_wait", "service.admit",
        "service.filter_round", "service.finalize",
        "ooc.fetch", "ooc.manifest", "ooc.chunk",
        "query.plan", "query.enumerate", "enum.count", "enum.emit",
    } <= in_trace

    # valid Perfetto JSON: object format, complete events, sorted ts
    doc = json.loads(json.dumps(tr.to_chrome_trace()))
    events = doc["traceEvents"]
    assert events and all(
        e["ph"] == "X" and e["dur"] >= 0 and isinstance(e["pid"], int)
        for e in events
    )
    assert [e["ts"] for e in events] == sorted(e["ts"] for e in events)

    # metrics surface: snapshot + valid exposition text with histograms
    snap = svc.metrics_snapshot()
    assert snap["repro_service_requests_total"]["series"][
        (("status", "completed"),)
    ] == 1
    assert snap["repro_service_embeddings_total"]["series"][()] == len(emb)
    assert snap["repro_ooc_chunks_read_total"]["series"][()] > 0
    fams = obsv.parse_prometheus(svc.metrics_text())
    assert fams["repro_service_queue_wait_seconds"]["type"] == "histogram"
    assert fams["repro_service_stage_seconds"]["type"] == "histogram"
    assert fams["repro_process_peak_rss_bytes"]["type"] == "gauge"

    finished, cancelled = svc.shutdown()
    assert not cancelled


def test_service_untraced_results_identical(tmp_path):
    """Tracing must be observational: identical rows with and without."""
    g = random_labeled_graph(150, 500, 4, seed=7)
    q = random_walk_query(g, 4, seed=8)

    def run():
        store = OutOfCoreGraphStore.from_graph(g, chunk_edges=64)
        svc = GraphQueryService(store, GraphServiceConfig(
            enumerator="device",
        ))
        svc.submit(q)
        return svc.run_to_completion()[0][1]

    plain = run()
    with obsv.tracing():
        traced = run()
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(traced))


def test_host_syncs_hand_count_on_the_host_scan_path():
    """A 3-vertex path on the host-assisted scan: each of the 2 levels
    syncs once for its one validity bitmask and once after emit (the
    report asks for phase timings); then the table is read back."""
    from repro.core.search import device_join_search

    # data: 0-1-2-3 path with labels a b a b; query: a-b-a
    data = build_graph(4, [0, 1, 0, 1], [(0, 1), (1, 2), (2, 3)])
    q = build_graph(3, [0, 1, 0], [(0, 1), (1, 2)])
    cand = (np.asarray(data.vlabels)[:, None]
            == np.asarray(q.vlabels)[None, :])
    report = {}
    emb = device_join_search(data, q, cand, order=[0, 1, 2],
                             use_kernel=False, report=report)
    assert {tuple(r) for r in emb.tolist()} == {(0, 1, 2), (2, 1, 0)}
    assert report["scan_path"] == "host" and report["device_rounds"] == 2
    assert report["host_syncs"] == 2 * (1 + 1) + 1
    obsv.EnumReport.from_dict(report)


def test_service_filter_round_live_and_mirrored(tmp_path):
    """Through the service: each fused dispatch is one live
    ``service.filter_round`` under ``service.tick``, every request's copy
    carries its exact interval (so deduplicating by interval counts each
    dispatch once), and the store prefilter is a child of admission."""
    from repro.core.incremental import IncrementalIndex
    from repro.graphs.store import GraphStore

    g = random_labeled_graph(150, 500, 4, seed=7)
    store = GraphStore.from_graph(g)
    store.attach_index(IncrementalIndex())
    svc = GraphQueryService(store, GraphServiceConfig(enumerator="device"))
    with obsv.tracing() as tr:
        for i in range(2):
            svc.submit(random_walk_query(g, 4, seed=8 + i))
        done = svc.run_to_completion()
    assert len(done) == 2 and not tr.open_spans
    by_id = {s.span_id: s for s in tr.spans}
    rounds = [s for s in tr.spans if s.name == "service.filter_round"]
    live = [s for s in rounds if by_id[s.parent_id].name == "service.tick"]
    copies = [s for s in rounds
              if by_id[s.parent_id].name == "service.request"]
    assert live and len(live) + len(copies) == len(rounds)
    assert all({"epoch", "group"} <= set(s.attrs) for s in live)
    intervals = {(s.start_ns, s.end_ns) for s in live}
    assert len(intervals) == len(live)
    assert {(s.start_ns, s.end_ns) for s in copies} == intervals
    assert sum(s.attrs["group"] for s in live) == len(copies)
    admits = [s for s in tr.spans if s.name == "service.admit"]
    pre = [s for s in tr.spans if s.name == "service.prefilter"]
    assert len(pre) == len(admits) == 2
    assert {by_id[s.parent_id].name for s in pre} == {"service.admit"}
    assert "service.epoch_pin" not in tr.names()
    svc.shutdown()
