"""CNI encoding: bijection, monotonicity, saturation soundness (Theorem 1,
Lemmas 3-5 of the paper + the DESIGN.md §1 corrections)."""

import math

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.cni import (
    SAT64,
    _pascal_table_np,
    cni_exact_py,
    cni_from_counts,
    cni_from_edges,
    cni_log_from_counts,
    default_max_p,
    limb_to_u64_np,
)


def _cni_u64(counts_row, d_max, max_p):
    c = jnp.asarray(np.asarray(counts_row, np.int32)[None, :])
    v = cni_from_counts(c, d_max, max_p)
    return int(limb_to_u64_np(v.hi, v.lo)[0])


class TestPascalTable:
    def test_exact_small(self):
        t = _pascal_table_np(10, 60)
        for q in range(1, 11):
            for p in range(1, 61):
                assert int(t[q, p]) == math.comb(q + p - 1, q)

    def test_zero_convention(self):
        t = _pascal_table_np(6, 20)
        assert (t[1:, 0] == 0).all()

    def test_saturation_sticky_monotone(self):
        t = _pascal_table_np(40, 2000)
        # rows are monotone nondecreasing in p even where saturated
        for q in range(1, 41):
            row = t[q].astype(np.float64)
            assert (np.diff(row) >= 0).all()
        assert (t <= SAT64).all()

    def test_terms_bounded_by_sat_at_service_widths(self):
        # the edge-record encode's order-free sum rests on every term being
        # at most SAT64 (DESIGN.md §3), at the widths the service runs
        assert (_pascal_table_np(1024, 4096) <= SAT64).all()


class TestBijection:
    @given(
        st.lists(st.integers(min_value=0, max_value=3), min_size=4, max_size=4)
    )
    @settings(max_examples=200, deadline=None)
    def test_matches_arbitrary_precision_oracle(self, counts):
        L, D = 4, 12
        labels = [l for l, c in enumerate(counts, start=1) for _ in range(c)]
        expect = cni_exact_py(labels)
        got = _cni_u64(counts, D, default_max_p(D, L))
        assert got == expect

    def test_injective_below_saturation(self):
        # all count vectors with small sums must encode distinctly unless the
        # multisets are equal — Theorem 1 restricted to equal-degree rows
        L, D = 3, 8
        seen = {}
        for a in range(4):
            for b in range(4):
                for c in range(4):
                    key = _cni_u64([a, b, c], D, default_max_p(D, L))
                    deg = a + b + c
                    if (deg, key) in seen:
                        assert seen[(deg, key)] == (a, b, c), (
                            "collision at equal degree"
                        )
                    seen[(deg, key)] = (a, b, c)


class TestMonotonicity:
    """Lemma 3: multiset inclusion ⇒ CNI(v) >= CNI(u) (descending order)."""

    @given(
        st.lists(st.integers(min_value=0, max_value=4), min_size=5, max_size=5),
        st.integers(min_value=0, max_value=4),
    )
    @settings(max_examples=300, deadline=None)
    def test_superset_has_geq_cni(self, base, extra_label):
        L, D = 5, 32
        sup = list(base)
        sup[extra_label] += 1
        mp = default_max_p(D, L)
        assert _cni_u64(sup, D, mp) >= _cni_u64(base, D, mp)

    @given(
        st.lists(st.integers(min_value=0, max_value=3), min_size=4, max_size=4),
        st.lists(st.integers(min_value=0, max_value=2), min_size=4, max_size=4),
    )
    @settings(max_examples=300, deadline=None)
    def test_componentwise_domination(self, base, delta):
        L, D = 4, 24
        sup = [b + d for b, d in zip(base, delta)]
        mp = default_max_p(D, L)
        assert _cni_u64(sup, D, mp) >= _cni_u64(base, D, mp)

    @given(
        st.lists(st.integers(min_value=1, max_value=6), min_size=1, max_size=6),
        st.integers(min_value=1, max_value=6),
    )
    @settings(max_examples=200, deadline=None)
    def test_descending_gives_per_term_domination(self, base, extra):
        """DESIGN.md §1: with *descending* prefix sums, inserting a label makes
        every positional term weakly larger — the property that keeps the
        filter sound even under the clipped (min(p, max_p)) Pascal table.
        (Ascending order only guarantees aggregate monotonicity via the
        dominant last term, which clipping can in principle defeat.)"""

        def terms_desc(labels):
            xs = sorted(labels, reverse=True)
            out, s = [], 0
            for j, x in enumerate(xs, start=1):
                s += x
                out.append(math.comb(j + s - 1, j))
            return out

        t_base = terms_desc(base)
        t_sup = terms_desc(base + [extra])
        assert len(t_sup) == len(t_base) + 1
        for a, b in zip(t_base, t_sup):
            assert b >= a, (base, extra, t_base, t_sup)


class TestLogSpace:
    @given(
        st.lists(st.integers(min_value=0, max_value=4), min_size=5, max_size=5),
        st.integers(min_value=0, max_value=4),
    )
    @settings(max_examples=200, deadline=None)
    def test_log_monotone_with_tolerance(self, base, extra):
        L, D = 5, 32
        sup = list(base)
        sup[extra] += 1
        mp = default_max_p(D, L)
        both = jnp.asarray(np.asarray([base, sup], np.int32))
        vals = cni_log_from_counts(both, D, mp)
        lo, hi = float(vals[0]), float(vals[1])
        if not np.isfinite(lo):
            return  # empty base row
        assert hi >= lo - 1e-4 * max(1.0, abs(lo))

    def test_equal_multisets_equal_logs(self):
        c = jnp.asarray(np.asarray([[2, 0, 1], [2, 0, 1]], np.int32))
        v = cni_log_from_counts(c, 8, default_max_p(8, 3))
        assert float(v[0]) == float(v[1])


class TestSaturationSoundness:
    def test_saturated_compare_is_weak_not_wrong(self):
        # giant counts saturate; superset must still compare >= (never <)
        L, D = 4, 64
        mp = default_max_p(D, L)
        base = [10, 10, 10, 10]
        sup = [10, 10, 10, 11]
        assert _cni_u64(sup, D, mp) >= _cni_u64(base, D, mp)

    def test_paper_running_example_k2(self):
        # Appendix C worked example: cni_2(u1) = ħ(1,3) + ħ(2,4) = 3 + 10 ...
        # the paper says 7 using ħ(1,3)=3? C(3,1)=3, ħ(2,4)=C(5,2)=10 → 13.
        # The paper's arithmetic ("= 7") is internally inconsistent; we pin
        # our (correct) formula instead: labels {3, 1} descending = [3, 1].
        assert cni_exact_py([3, 1]) == math.comb(3, 1) + math.comb(5, 2)


def _edge_case(name):
    """(graph, ords (..., V), alive (..., V) or None, n_labels, d_max,
    max_p) for one edge-record encode case."""
    from repro.graphs import power_law_graph
    from repro.graphs.csr import Graph, build_graph, max_degree

    rng = np.random.default_rng(sum(map(ord, name)))
    if name == "isolated":
        # vertices 5..11 have no edge at all; 0 is a hub of degree 4
        g = build_graph(12, np.zeros(12, np.int32),
                        [(0, 1), (0, 2), (0, 3), (0, 4), (1, 2), (3, 4)])
    elif name == "d_max_past_2_16":
        # a star whose hub saturates, plus a ring; d_max ≥ 2^16 takes the
        # 8-bit chunk sums
        ring = [(i, i % 59 + 1) for i in range(1, 60)]
        g = build_graph(60, np.zeros(60, np.int32),
                        [(0, i) for i in range(1, 60)] + ring)
        ords = rng.integers(1, 4, size=60).astype(np.int32)
        return g, ords, None, 3, 1 << 16, 40
    else:
        g = power_law_graph(300, 8, 5, seed=1, gamma=2.1)
    if name == "unsorted_src":
        perm = rng.permutation(g.n_directed_edges)
        g = Graph(vlabels=g.vlabels, src=g.src[perm], dst=g.dst[perm],
                  elabels=g.elabels[perm])
    n = g.n_vertices
    d_max = max(1, max_degree(g))
    if name == "batched_inert_partial":
        ords = rng.integers(0, 6, size=(3, 2, n)).astype(np.int32)
        ords[1, 0] = 0  # an inert slot
        alive = rng.random((3, 2, n)) < 0.7
        alive[2, 1] = True
        return g, ords, alive, 8, d_max, default_max_p(d_max, 8)
    ords = rng.integers(1, 6, size=n).astype(np.int32)
    max_p = 16 if name == "max_p_clip" else default_max_p(d_max, 5)
    return g, ords, None, 5, d_max, max_p


@pytest.mark.parametrize("case", [
    "power_law_hub", "max_p_clip", "batched_inert_partial", "isolated",
    "unsorted_src", "d_max_past_2_16",
])
def test_edge_encode_equals_padded(case):
    """The filter round's edge-record encode gives the padded encode's bits
    (hi and lo) on every row, and the paper's exact value below saturation
    wherever neither the max_p clip nor d_max cut the row."""
    from repro.core.labels import counts_matrix_from_ords

    g, ords, alive, n_labels, d_max, max_p = _edge_case(case)
    counts = counts_matrix_from_ords(
        g, jnp.asarray(ords), n_labels,
        None if alive is None else jnp.asarray(alive))
    want = cni_from_counts(counts, d_max, max_p)
    got = cni_from_edges(counts, g.src, d_max, max_p)
    np.testing.assert_array_equal(np.asarray(got.hi), np.asarray(want.hi))
    np.testing.assert_array_equal(np.asarray(got.lo), np.asarray(want.lo))

    rows = np.asarray(counts).reshape(-1, n_labels)
    vals = limb_to_u64_np(got.hi, got.lo).reshape(-1)
    weight = rows @ np.arange(1, n_labels + 1)  # the row's last prefix sum
    exact = (vals < SAT64) & (weight <= max_p) & (rows.sum(1) <= d_max)
    for row, val in zip(rows[exact], vals[exact]):
        labels = [lab for lab, c in enumerate(row, start=1) for _ in range(c)]
        assert int(val) == cni_exact_py(labels)
    n_sat = int((vals == SAT64).sum())
    n_clipped = int(((vals < SAT64) & (weight > max_p)).sum())
    if case in ("power_law_hub", "unsorted_src", "d_max_past_2_16"):
        assert n_sat > 0, "the hub rows must saturate"
    if case == "max_p_clip":
        assert n_clipped > 0, "some unsaturated prefix must pass max_p"
    if case == "batched_inert_partial":
        assert not vals.reshape(ords.shape)[1, 0].any()  # inert slot: 0
    if case == "isolated":
        assert not vals[5:].any() and exact.sum() == rows.shape[0]
