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
    _log_hbar_np,
    _pascal_table_np,
    cni_exact_py,
    cni_from_counts,
    cni_from_counts_np,
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


def _padded_cni_np(counts, d_max, max_p):
    """The padded host encode, kept as the oracle: a dense (N, d_max, L)
    descending expansion, d_max positions per row."""
    counts = np.asarray(counts)
    n, L = counts.shape
    deg_all = counts.sum(axis=1).astype(np.int32)
    if n == 0 or d_max <= 0:
        return np.zeros(n, np.uint64), np.full(n, -np.inf, np.float32), deg_all
    table = _pascal_table_np(d_max, max_p)
    log_t = _log_hbar_np(d_max, max_p)
    ccum = np.cumsum(counts[:, ::-1], axis=1)
    posr = np.arange(d_max)
    idx = (ccum[:, None, :] <= posr[None, :, None]).sum(-1)
    deg = ccum[:, -1]
    valid = posr[None, :] < deg[:, None]
    lab = np.where(valid, np.maximum(L - idx, 0), 0)
    prefix = np.minimum(np.cumsum(lab, axis=1), max_p)
    q_idx = np.arange(1, d_max + 1)
    terms = np.where(valid, table[q_idx[None, :], prefix], 0)
    shadow_total = np.cumsum(terms.astype(np.float64), axis=1)[:, -1]
    cni_u64 = terms.sum(axis=1, dtype=np.uint64)
    for v in np.nonzero(shadow_total >= float(SAT64) * 0.5)[0]:
        acc = 0
        for j in range(1, min(int(deg[v]), d_max) + 1):
            acc = min(acc + int(table[j, prefix[v, j - 1]]), int(SAT64))
        cni_u64[v] = acc
    log_terms = np.where(valid, log_t[q_idx[None, :], prefix], -np.inf)
    log_terms = log_terms.astype(np.float32)
    m = log_terms.max(axis=1, initial=-np.inf)
    m_safe = np.where(np.isfinite(m), m, np.float32(0.0))
    s = np.sum(np.where(valid, np.exp(log_terms - m_safe[:, None]), 0.0),
               axis=1, dtype=np.float32)
    cni_log = np.where(deg > 0, m_safe + np.log(np.maximum(s, np.float32(1e-30))),
                       -np.inf).astype(np.float32)
    return cni_u64, cni_log, deg_all


def _counts_case(name):
    """(counts (N, L), d_max, max_p) for one host-encode case."""
    rng = np.random.default_rng(sum(map(ord, name)))
    if name == "empty":
        return np.zeros((0, 7), np.int32), 8, 56
    sparse = (rng.integers(0, 4, size=(40, 9))
              * (rng.random((40, 9)) < 0.4)).astype(np.int32)
    if name == "d_max_zero":
        return sparse, 0, 16
    if name == "all_zero_rows":
        sparse[::3] = 0
        return sparse, 8, default_max_p(8, 9)
    if name == "deg_past_d_max":
        return sparse * 3, 4, default_max_p(4, 9)
    if name == "max_p_clip":
        return sparse, 16, 12
    if name == "saturation":
        # one label per row, its count swept through the band where the
        # row's sum passes SAT64/2 and SAT64; then dense hub rows past it
        label = rng.integers(0, 307, size=600)
        sweep = np.zeros((600, 307), np.int32)
        sweep[np.arange(600), label] = rng.integers(1, 60, size=600)
        hubs = rng.integers(0, 5, size=(40, 307)).astype(np.int32)
        return np.concatenate([sweep, hubs]), 1024, default_max_p(1024, 307)
    # a restricted alphabet of a seeded power-law graph, as admission
    # gathers it from the store index's count matrix
    from repro.graphs import power_law_graph

    g = power_law_graph(2000, 8, 307, seed=3, gamma=2.1)
    vl = np.asarray(g.vlabels)
    full = np.zeros((g.n_vertices, 307), np.int32)
    np.add.at(full, (np.asarray(g.src), vl[np.asarray(g.dst)]), 1)
    alphabet = np.sort(rng.choice(307, size=12, replace=False))
    d_max = 1 << int(np.ceil(np.log2(max(2, full.sum(1).max()))))
    return full[:, alphabet], d_max, default_max_p(d_max, alphabet.size)


_COUNTS_CASES = ["empty", "d_max_zero", "all_zero_rows", "deg_past_d_max",
                 "max_p_clip", "saturation", "power_law_alphabet"]


@pytest.mark.parametrize("case", _COUNTS_CASES)
def test_host_encode_equals_padded(case):
    """The host encode over nonzero count runs gives the padded encode's
    ``cni_u64`` and ``deg`` bit for bit, and its ``cni_log`` to float32
    rounding of the row sum."""
    counts, d_max, max_p = _counts_case(case)
    want = _padded_cni_np(counts, d_max, max_p)
    got = cni_from_counts_np(counts, d_max, max_p)
    assert got[0].dtype == np.uint64 and got[1].dtype == np.float32
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[2], want[2])
    np.testing.assert_allclose(got[1], want[1], rtol=1e-6, atol=0)
    u64 = got[0]
    if case == "saturation":
        assert (u64 == SAT64).sum() > 0, "some rows must saturate"
        band = (u64 >= SAT64 // np.uint64(2)) & (u64 < SAT64)
        assert band.sum() > 0, "some rows must replay without saturating"
    if case == "deg_past_d_max":
        assert (want[2] > d_max).any()
    if case == "max_p_clip":
        weight = counts @ np.arange(1, counts.shape[1] + 1)  # ord = col + 1
        assert (weight > max_p).any()
    if case == "all_zero_rows":
        assert not u64[::3].any() and np.isneginf(got[1][::3]).all()
    if case == "power_law_alphabet":
        assert (want[2] > 0).sum() > 50


@pytest.mark.parametrize("case", ["saturation", "power_law_alphabet"])
def test_host_encode_row_local(case):
    """Encoding a random subset of rows gives those rows of the full
    encode, bitwise in all three outputs."""
    counts, d_max, max_p = _counts_case(case)
    full = cni_from_counts_np(counts, d_max, max_p)
    rows = np.random.default_rng(5).permutation(counts.shape[0])[:97]
    sub = cni_from_counts_np(counts[rows], d_max, max_p)
    for got, want in zip(sub, full):
        np.testing.assert_array_equal(got, want[rows])
