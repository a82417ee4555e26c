"""Batched multi-query engine == sequential engine, per query.

The contract (batch_engine.py): ``BatchQueryEngine.query_batch`` over a
heterogeneous batch returns, for every query, exactly the embedding set the
sequential ``SubgraphQueryEngine.query`` produces — including degenerate
members of the same batch (all-pruned queries, filter-surviving queries with
zero embeddings).  Also covers the slot-scheduled serving front-end.
"""

import functools

import jax
import numpy as np
import pytest

from repro.core import BatchQueryEngine, SubgraphQueryEngine
from repro.core.batch_engine import bucket_key, ceil_pow2
from repro.graphs import random_labeled_graph, random_walk_query
from repro.graphs.csr import build_graph
from strategies import emb_set as _emb_set


def _assert_batch_matches_sequential(data, queries, *, variant="cni",
                                     max_batch=32):
    seq = SubgraphQueryEngine(data, filter_variant=variant)
    bat = BatchQueryEngine(data, filter_variant=variant,
                           max_batch=max_batch)
    results = bat.query_batch(queries)
    assert len(results) == len(queries)
    for i, q in enumerate(queries):
        e_seq, _ = seq.query(q)
        e_bat, s_bat = results[i]
        assert e_bat.shape[1] == q.n_vertices
        assert _emb_set(e_seq) == _emb_set(e_bat), f"query {i} diverged"
        assert s_bat.n_embeddings == e_bat.shape[0]


def _all_pruned_query():
    # labels 98/99 never occur in the random data graphs below (labels < 32)
    return build_graph(3, [99, 98, 99], [(0, 1), (1, 2)])


def _zero_embedding_query():
    # survives ILGF (filters ignore edge labels) but has no embedding in
    # _zero_embedding_data: the el=1 edge does not exist there
    return build_graph(3, [0, 1, 0], [(0, 1), (1, 2)], elabels=[0, 1])


def _zero_embedding_data():
    return build_graph(3, [0, 1, 0], [(0, 1), (1, 2)], elabels=[0, 0])


# the full B=32 sweep covers the same mixed-batch parity assertion as B=12
# at ~3x the sequential-verification cost — slow tier (ISSUE 5 runtime audit)
@pytest.mark.parametrize("n_queries", [
    12, pytest.param(32, marks=pytest.mark.slow),
])
def test_batch_of_mixed_queries_matches_sequential(n_queries):
    g = random_labeled_graph(250, 900, 6, n_edge_labels=2, seed=3)
    rng = np.random.default_rng(7)
    queries = [
        random_walk_query(g, int(rng.integers(4, 9)),
                          sparse=bool(i % 2), seed=400 + i)
        for i in range(n_queries - 2)
    ]
    queries.insert(5, _all_pruned_query())
    queries.insert(min(20, len(queries)), _all_pruned_query())
    assert len(queries) == n_queries
    _assert_batch_matches_sequential(g, queries)


def test_all_pruned_and_zero_embedding_in_same_batch():
    g = _zero_embedding_data()
    queries = [
        _zero_embedding_query(),         # survives filter, 0 embeddings
        _all_pruned_query(),             # filter empties the graph
        build_graph(2, [0, 1], [(0, 1)], elabels=[0]),  # 2 embeddings
    ]
    bat = BatchQueryEngine(g)
    results = bat.query_batch(queries)
    (e0, s0), (e1, s1), (e2, s2) = results
    assert e0.shape == (0, 3) and s0.vertices_after == 3
    assert e1.shape == (0, 3) and s1.vertices_after == 0
    assert _emb_set(e2) == {(0, 1), (2, 1)}
    _assert_batch_matches_sequential(g, queries)


@pytest.mark.parametrize("variant", ["cni", "cni_log", "nlf", "label_degree",
                                     "mnd_nlf"])
def test_batch_matches_sequential_all_variants(variant):
    g = random_labeled_graph(150, 500, 4, n_edge_labels=2, seed=11)
    queries = [
        random_walk_query(g, 4 + (i % 3), sparse=i % 2 == 0, seed=600 + i)
        for i in range(6)
    ]
    _assert_batch_matches_sequential(g, queries, variant=variant)


def test_small_max_batch_chunks_and_buckets():
    """Chunking (max_batch < n_queries) must not change any result.

    8 queries of sizes 3-4 still land in two distinct buckets (their label
    alphabets split 2 vs 3-4) AND force a descending-pow2 chunk split under
    max_batch=4 (the 6-query bucket runs as chunks of 4 then 2) — the same
    chunk/bucket interactions the original 12-query sweep hit, at ~60% of
    the sequential-verification cost (ISSUE 5 runtime audit)."""
    g = random_labeled_graph(200, 700, 5, n_edge_labels=2, seed=5)
    queries = [
        random_walk_query(g, 3 + (i % 2), sparse=bool(i % 2), seed=70 + i)
        for i in range(8)
    ]
    _assert_batch_matches_sequential(g, queries, max_batch=4)
    # heterogeneous sizes must land in pow2-padded buckets
    eng = BatchQueryEngine(g)
    keys = {bucket_key(q, eng.d_max) for q in queries}
    assert all(k[2] == ceil_pow2(k[2]) for k in keys)
    assert len(keys) > 1


def test_batch_stats_report_bucket_and_rounds():
    g = random_labeled_graph(120, 400, 4, seed=9)
    queries = [random_walk_query(g, 5, sparse=True, seed=90 + i)
               for i in range(4)]
    bat = BatchQueryEngine(g)
    for emb, stats in bat.query_batch(queries):
        assert stats.ilgf_iterations >= 1
        assert stats.extras["batch"]["batch_size"] == 4
        assert stats.vertices_before == g.n_vertices


def test_lockstep_fixed_point_matches_per_query_ilgf():
    """The one-dispatch lockstep API reaches the same per-query fixed point
    as the sequential ILGF (extra rounds past a query's own convergence are
    idempotent)."""
    from repro.core import ilgf
    from repro.core.batch_engine import (
        batched_ilgf_fixed_point, stack_queries,
    )
    from repro.core.cni import default_max_p
    from repro.graphs.csr import max_degree

    g = random_labeled_graph(150, 500, 4, n_edge_labels=2, seed=31)
    queries = [random_walk_query(g, 4 + i, sparse=True, seed=900 + i)
               for i in range(3)]
    d_max = max(1, max_degree(g))
    u_pad, l_pad = 8, 4
    max_p = default_max_p(d_max, l_pad)
    qb = stack_queries(queries, g, d_max, max_p, u_pad, l_pad, 4)
    alive, cand, rounds = batched_ilgf_fixed_point(
        g, qb, n_labels=l_pad, d_max=d_max, max_p=max_p,
        variant="cni", max_iters=1000,
    )
    alive = np.asarray(alive)
    for b, q in enumerate(queries):
        ref = np.asarray(ilgf(g, q, d_max=d_max).alive)
        # the batched run uses a (possibly) larger shared max_p — its clip is
        # weaker, so its fixed point can only be a superset of the reference
        assert not np.any(ref & ~alive[b])
    assert not alive[3].any()  # spare slot stays inert


def test_graph_service_matches_sequential():
    from repro.serve import GraphQueryService, GraphServiceConfig

    g = random_labeled_graph(200, 700, 5, n_edge_labels=2, seed=13)
    rng = np.random.default_rng(17)
    queries = [
        random_walk_query(g, int(rng.integers(4, 8)),
                          sparse=bool(i % 2), seed=800 + i)
        for i in range(10)
    ]
    svc = GraphQueryService(
        g, GraphServiceConfig(max_slots=3, max_query_vertices=8,
                              max_query_labels=8),
    )
    rids = [svc.submit(q) for q in queries]
    done = {rid: emb for rid, emb, _ in svc.run_to_completion()}
    assert sorted(done) == sorted(rids)
    seq = SubgraphQueryEngine(g)
    for rid, q in zip(rids, queries):
        e_seq, _ = seq.query(q)
        assert _emb_set(e_seq) == _emb_set(done[rid])


def test_graph_service_rejects_oversize():
    from repro.serve import GraphQueryService, GraphServiceConfig

    g = random_labeled_graph(100, 300, 4, seed=1)
    svc = GraphQueryService(
        g, GraphServiceConfig(max_slots=2, max_query_vertices=4,
                              max_query_labels=4),
    )
    big = random_walk_query(g, 8, sparse=True, seed=2)
    with pytest.raises(ValueError):
        svc.submit(big)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _padded_match(g, ords, q, n_labels, d_max, max_p, alive):
    """The padded encode's candidate grid: every vertex's CNI over d_max
    positions (``make_digest`` → ``cni_from_counts``)."""
    from repro.core import filters as flt
    from repro.core.labels import counts_matrix_from_ords

    counts = counts_matrix_from_ords(g, ords, n_labels, alive)
    return flt.cni_match(flt.make_digest(counts, ords, d_max, max_p),
                         q.digest)


def _padded_fixed_point(g, ords, q, n_labels, d_max, max_p, alive):
    """Peel with the padded encode until no row changes: (alive,
    candidates, rounds), counted as the engines count them."""
    rounds = 0
    while True:
        new = alive & _padded_match(g, ords, q, n_labels, d_max, max_p,
                                    alive).any(-1)
        rounds += 1
        if not bool((new != alive).any()):
            break
        alive = new
    match = _padded_match(g, ords, q, n_labels, d_max, max_p, alive)
    return alive, match & alive[..., None], rounds


def _power_law_slots():
    """A seeded power-law graph whose hubs saturate, 6 queries stacked into
    8 slots (2 inert), and a partial starting mask."""
    from repro.core.batch_engine import stack_queries
    from repro.core.cni import default_max_p
    from repro.graphs import power_law_graph
    from repro.graphs.csr import max_degree

    g = power_law_graph(400, 6, 4, seed=5, gamma=2.1)
    queries = [random_walk_query(g, 3 + i % 4, sparse=bool(i % 2),
                                 seed=1400 + i) for i in range(6)]
    d_max = max(1, max_degree(g))
    l_pad = 4
    max_p = default_max_p(d_max, l_pad)
    qb = stack_queries(queries, g, d_max, max_p, 8, l_pad, 8)
    rng = np.random.default_rng(14)
    alive = (np.asarray(qb.ords) > 0) & (rng.random(qb.ords.shape) < 0.9)
    return g, queries, qb, alive, l_pad, d_max, max_p


@pytest.mark.parametrize("filt", ["round", "batched_fixed_point",
                                  "sequential", "one_shot"])
def test_filters_equal_padded_encode(filt):
    """Every filter that runs the exact CNI over the edge records gives the
    padded encode's results bit for bit on a graph whose hubs saturate."""
    import jax.numpy as jnp

    from repro.core.batch_engine import (
        batched_ilgf_fixed_point, batched_ilgf_round,
    )
    from repro.core.cni import SAT64, default_max_p, limb_to_u64_np
    from repro.core.filters import make_digest
    from repro.core.ilgf import ilgf, one_shot_filter, prepare_query
    from repro.core.labels import (
        build_label_map, counts_matrix_from_ords, ord_of,
    )

    g, queries, qb, alive, l_pad, d_max, max_p = _power_law_slots()
    counts = counts_matrix_from_ords(g, qb.ords, l_pad, jnp.asarray(alive))
    cni = make_digest(counts, qb.ords, d_max, max_p).cni
    assert (limb_to_u64_np(cni.hi, cni.lo) == SAT64).any()
    kw = dict(n_labels=l_pad, d_max=d_max, max_p=max_p)
    if filt == "round":
        alive = jnp.asarray(alive)
        for _ in range(3):
            got = batched_ilgf_round(g, qb, alive, variant="cni", **kw)
            want_alive = alive & _padded_match(
                g, qb.ords, qb, l_pad, d_max, max_p, alive).any(-1)
            want = (want_alive,
                    _padded_match(g, qb.ords, qb, l_pad, d_max, max_p, alive)
                    & want_alive[..., None],
                    (want_alive != alive).any(-1))
            for a, b in zip(got, want):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            alive = got[0]
        return
    if filt == "batched_fixed_point":
        got = batched_ilgf_fixed_point(g, qb, variant="cni", max_iters=1000,
                                       **kw)
        want = _padded_fixed_point(g, qb.ords, qb, l_pad, d_max, max_p,
                                   qb.ords > 0)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        return
    for q in queries[:3]:
        n_labels = build_label_map(q).n_labels
        mp = default_max_p(d_max, n_labels)
        pq = prepare_query(q, d_max, mp)
        ords = ord_of(pq.label_map, g.vlabels)
        if filt == "sequential":
            got = ilgf(g, q, variant="cni", d_max=d_max)
            want = _padded_fixed_point(g, ords, pq, n_labels, d_max, mp,
                                       ords > 0)
        else:
            got = one_shot_filter(g, q, variant="cni", d_max=d_max)
            match = _padded_match(g, ords, pq, n_labels, d_max, mp, ords > 0)
            cand = match.any(-1) & (ords > 0)
            want = (cand, match & cand[:, None], 1)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_round_lowers_without_padded_expansion():
    """At the service's widths (8 slots, d_max 1,024) the lowered exact
    round holds no array of B·V·d_max elements, while the padded encode's
    round does: the per-vertex d_max expansion cannot come back unnoticed."""
    import re

    import jax.numpy as jnp

    from repro.core.batch_engine import batched_ilgf_round
    from repro.core.cni import default_max_p

    g, _, qb, alive, l_pad, _, _ = _power_law_slots()
    b, v, d_max = 8, g.n_vertices, 1024
    max_p = default_max_p(d_max, l_pad)
    kw = dict(n_labels=l_pad, d_max=d_max, max_p=max_p)
    alive = jnp.asarray(alive)

    def sizes(lowered):
        return {int(np.prod([int(x) for x in m.group(1).split("x")]))
                for m in re.finditer(r"tensor<(\d+(?:x\d+)*)x\w+>",
                                     lowered.as_text())}

    exact = sizes(batched_ilgf_round.lower(g, qb, alive, variant="cni", **kw))
    padded = sizes(_padded_match.lower(g, qb.ords, qb, l_pad, d_max, max_p,
                                       alive))
    assert b * v * d_max in padded
    table = (d_max + 1) * (max_p + 1)  # the Pascal limb tables
    assert max(exact - {table}) < b * v * d_max, sorted(exact)[-4:]
