"""Compact Neighborhood Index (the paper's §3.1, Theorem 1).

``cni(u) = Σ_{j=1..k} ħ(j, x_1+…+x_j)`` with ``ħ(q,p) = C(q+p-1, q)`` is the
combinatorial-number-system bijection ℕ^k → ℕ over the vertex's neighbor-label
sequence.  Two deliberate engineering deviations from the paper, both argued
in DESIGN.md §1/§3:

* **Descending label order.**  Lemma 3 (monotonicity of the CNI under
  neighborhood multiset inclusion) only holds when the prefix sums run over
  labels sorted in *descending* ord() order; the paper's proof sketch
  implicitly assumes the shared labels form a prefix.  We sort descending.

* **Saturating fixed-width arithmetic.**  ħ explodes combinatorially, and TPUs
  have no 64-bit integer datapath, so the exact path uses *saturating
  double-uint32 limb* arithmetic.  min(·, SAT) and saturating-add are
  monotone, hence every comparison the filter makes remains *sound* (a
  saturated CNI can only make the filter weaker, never prune a true match).
  Below saturation the encoding is the paper's exact bijection (tested).

A float32 log-space variant (``logsumexp`` of ``lgamma``-based log-binomials)
is provided as the TPU-kernel fast path; it compares with an ε tolerance.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# Saturation threshold for the exact limb path: 2^62 keeps the uint64 host
# precompute comfortably exact below SAT while remaining monotone above.
SAT64 = np.uint64(1) << np.uint64(62)
_SAT_HI = jnp.uint32((SAT64 >> np.uint64(32)) & np.uint64(0xFFFFFFFF))
_SAT_LO = jnp.uint32(SAT64 & np.uint64(0xFFFFFFFF))
# log-space twin of SAT64: log digests at/above this are treated as
# saturated by the ε-tolerant filter (same pass-through degeneracy as the
# limb path), which is what lets the incremental index keep a sticky
# canonical value for saturated hubs instead of re-encoding them.
LOG_SAT64 = float(62 * np.log(2.0))


class CniValue(NamedTuple):
    """Two-limb saturating CNI (hi, lo), each uint32."""

    hi: jnp.ndarray
    lo: jnp.ndarray


# ---------------------------------------------------------------------------
# Pascal table for ħ(q, p) = C(q+p-1, q), saturating at SAT64.
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=8)
def _pascal_table_np(max_q: int, max_p: int) -> np.ndarray:
    """(max_q+1, max_p+1) uint64 table of ħ(q,p), saturated at SAT64.

    Row recurrence: ħ(q, p) = ħ(q, p-1) + ħ(q-1, p)  ⇒  row q is the prefix
    sum of row q-1.  A float shadow detects overflow; saturation is sticky
    and monotone, so the device-side filter stays sound (DESIGN.md §3).
    """
    sat_f = float(SAT64)
    # Row 0: ħ(0,p) = 1 for p>=1; index 0 pinned to 0 so that
    # row_q = cumsum(row_{q-1}) realizes ħ(q,p) = Σ_{p'=1..p} ħ(q-1,p').
    row_u = np.ones(max_p + 1, dtype=np.uint64)
    row_u[0] = 0
    row_f = row_u.astype(np.float64)
    table = np.zeros((max_q + 1, max_p + 1), dtype=np.uint64)
    table[0] = row_u
    for q in range(1, max_q + 1):
        nxt_f = np.cumsum(row_f)
        nxt_u = np.cumsum(row_u, dtype=np.uint64)
        sat = nxt_f >= sat_f
        nxt_u[sat] = SAT64
        nxt_f[sat] = sat_f  # sticky: keep shadows finite but saturated
        table[q] = nxt_u
        row_u, row_f = nxt_u, nxt_f
    return table


@functools.lru_cache(maxsize=8)
def _pascal_limbs_np(max_q: int, max_p: int):
    t = _pascal_table_np(max_q, max_p)
    hi = (t >> np.uint64(32)).astype(np.uint32)
    lo = (t & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return hi, lo


def pascal_table_limbs(max_q: int, max_p: int):
    """(hi, lo) uint32 limb tables for ħ.  Host-cached as numpy; converted at
    every call site so jit traces see fresh constants (no tracer leaks)."""
    hi, lo = _pascal_limbs_np(max_q, max_p)
    return jnp.asarray(hi), jnp.asarray(lo)


@functools.lru_cache(maxsize=8)
def _log_hbar_np(max_q: int, max_p: int) -> np.ndarray:
    q = np.arange(max_q + 1, dtype=np.float64)[:, None]
    p = np.arange(max_p + 1, dtype=np.float64)[None, :]
    from scipy.special import gammaln  # host-only precompute

    with np.errstate(divide="ignore", invalid="ignore"):
        val = gammaln(q + p) - gammaln(q + 1.0) - gammaln(np.maximum(p, 1e-9))
    val = np.where(p < 0.5, -np.inf, val)  # ħ(q, 0) := 0
    return val.astype(np.float32)


def log_hbar_table(max_q: int, max_p: int) -> jnp.ndarray:
    """float32 table of log ħ(q,p) (−inf at the ħ=0 convention points)."""
    return jnp.asarray(_log_hbar_np(max_q, max_p))


# ---------------------------------------------------------------------------
# Saturating limb arithmetic (uint32 pairs).  All ops element-wise on arrays.
# ---------------------------------------------------------------------------


def limb_add(ah, al, bh, bl):
    lo = al + bl
    carry = (lo < al).astype(jnp.uint32)
    hi1 = ah + bh
    ov1 = hi1 < ah
    hi = hi1 + carry
    ov2 = hi < hi1
    overflow = ov1 | ov2
    # also saturate if result exceeds SAT64 (keeps equality semantics sticky)
    over_sat = (hi > _SAT_HI) | ((hi == _SAT_HI) & (lo > _SAT_LO))
    sat = overflow | over_sat
    hi = jnp.where(sat, _SAT_HI, hi)
    lo = jnp.where(sat, _SAT_LO, lo)
    return hi, lo


def limb_ge(ah, al, bh, bl):
    return (ah > bh) | ((ah == bh) & (al >= bl))


def limb_eq(ah, al, bh, bl):
    return (ah == bh) & (al == bl)


def limb_is_saturated(ah, al):
    return (ah == _SAT_HI) & (al == _SAT_LO)


def limb_to_u64_np(hi, lo) -> np.ndarray:
    return (np.asarray(hi, dtype=np.uint64) << np.uint64(32)) | np.asarray(
        lo, dtype=np.uint64
    )


# ---------------------------------------------------------------------------
# CNI from a label-count matrix.
# ---------------------------------------------------------------------------


def default_max_p(d_max: int, n_labels: int, cap: int = 4096) -> int:
    """Static bound on prefix sums fed to the ħ table.

    Prefix sums are clipped to ``max_p`` before the table gather:
    ``min(p, max_p)`` is monotone, so clipping (like saturation) only
    *weakens* the filter — never unsound — while keeping the Pascal table
    O(d_max · max_p) instead of O(d_max² · L).
    """
    return int(min(d_max * max(n_labels, 1), cap))


def _descending_positions(counts: jnp.ndarray, d_max: int):
    """Expand count rows into descending ord()-value sequences.

    counts: (V, L) with counts[v, l] = multiplicity of ord value (l+1).
    Returns (labels_at_pos (V, D), prefix_sums (V, D), deg (V,)).
    Positions >= deg hold label 0 / repeated final prefix sum.
    """
    assert counts.ndim == 2
    L = counts.shape[-1]
    desc = counts[..., ::-1]  # index i ↔ ord value L-i
    ccum = jnp.cumsum(desc, axis=-1)  # (V, L)
    pos = jnp.arange(d_max, dtype=counts.dtype)
    # label at position j: first i with ccum[i] > j  ⇒ ord value L - idx
    idx = jax.vmap(lambda row: jnp.searchsorted(row, pos, side="right"))(ccum)
    lab = jnp.maximum(L - idx, 0).astype(jnp.int32)
    deg = ccum[..., -1]
    valid = pos[None, :] < deg[:, None]
    lab = jnp.where(valid, lab, 0)
    prefix = jnp.cumsum(lab, axis=-1)
    return lab, prefix, deg


def cni_from_counts(counts: jnp.ndarray, d_max: int, max_p: int) -> CniValue:
    """Exact (saturating two-limb) CNI for each count row.

    counts: (..., L) int32 — any leading batch shape; the CNI is computed per
    row.  d_max: static max degree (rows with more neighbors must not occur —
    callers size d_max from the graph).  max_p: static bound on prefix sums
    (d_max * L suffices).
    """
    batch_shape = counts.shape[:-1]
    counts = counts.reshape((-1, counts.shape[-1]))
    hi_t, lo_t = pascal_table_limbs(d_max, max_p)
    _, prefix, deg = _descending_positions(counts, d_max)
    q = jnp.arange(1, d_max + 1, dtype=jnp.int32)  # (D,)
    p = jnp.clip(prefix, 0, max_p)  # (V, D)
    term_hi = hi_t[q[None, :], p]  # (V, D)
    term_lo = lo_t[q[None, :], p]
    valid = jnp.arange(d_max)[None, :] < deg[:, None]
    term_hi = jnp.where(valid, term_hi, 0).astype(jnp.uint32)
    term_lo = jnp.where(valid, term_lo, 0).astype(jnp.uint32)

    def body(i, acc):
        ah, al = acc
        return limb_add(ah, al, term_hi[:, i], term_lo[:, i])

    init = (
        jnp.zeros(counts.shape[0], dtype=jnp.uint32),
        jnp.zeros(counts.shape[0], dtype=jnp.uint32),
    )
    hi, lo = jax.lax.fori_loop(0, d_max, body, init)
    return CniValue(hi=hi.reshape(batch_shape), lo=lo.reshape(batch_shape))


def _edge_positions(src: jnp.ndarray, n_vertices: int):
    """One CNI position per directed edge record: (vertex (E,), rank (E,)).

    The records of vertex v take the ranks 0 … deg_G(v)−1, so the pairs
    cover every position a count row built from these edges can fill.
    ``src`` need not be sorted: the pairs come from its sorted copy, which
    also leaves ``vertex`` ascending.
    """
    vertex = jnp.sort(src.astype(jnp.int32))
    deg_g = jnp.zeros(n_vertices, jnp.int32).at[vertex].add(
        1, indices_are_sorted=True)
    start = jnp.cumsum(deg_g) - deg_g
    rank = jnp.arange(vertex.shape[0], dtype=jnp.int32) - start[vertex]
    return vertex, rank


def cni_from_edges(counts: jnp.ndarray, src: jnp.ndarray, d_max: int,
                   max_p: int) -> CniValue:
    """``cni_from_counts`` bit for bit, encoded over edge records.

    counts: (..., V, L) int32, counted over the directed edge records whose
    sources are ``src`` (E,), so no row holds more than its vertex's records
    and ``_edge_positions`` covers every valid position.  The work is
    O(B·E·L) for B = prod(leading dims) instead of the padded encode's
    O(B·V·d_max) (DESIGN.md §3, "Encoding over edge records").
    """
    batch_shape = counts.shape[:-2]
    n, L = counts.shape[-2:]
    counts = counts.reshape((-1, n, L))
    vertex, j = _edge_positions(src, n)
    # row of descending cumulative counts at each position: block i (ord
    # value L−i) holds the positions ccum[i-1] … ccum[i]−1
    ccum = jnp.cumsum(counts[..., ::-1], axis=-1)[:, vertex, :]  # (B, E, L)
    desc = jnp.diff(ccum, axis=-1, prepend=0)
    done = ccum <= j[None, :, None]  # blocks that end at or before j
    label = L - done.sum(-1)
    block_start = jnp.where(done, desc, 0).sum(-1)
    ords = jnp.arange(L, 0, -1, dtype=counts.dtype)
    prefix = (jnp.where(done, desc * ords, 0).sum(-1)
              + label * (j - block_start + 1))
    valid = (j < ccum[..., -1]) & (j < d_max)

    hi_t, lo_t = pascal_table_limbs(d_max, max_p)
    q = jnp.minimum(j + 1, d_max)[None, :]
    p = jnp.clip(prefix, 0, max_p)
    terms = (jnp.where(valid, lo_t[q, p], 0).astype(jnp.uint32),
             jnp.where(valid, hi_t[q, p], 0).astype(jnp.uint32))

    # Every term is ≤ SAT64 = 2^62, so the sticky ``limb_add`` chain never
    # wraps 2^64 and ends at min(Σ terms, SAT64): an exact sum in any order
    # gives the same bits.  Sum ``bits``-wide chunks of the two limbs per
    # vertex in uint32 (at most d_max terms each, so no chunk sum wraps),
    # then propagate the carries.
    if d_max >= 1 << 24:
        raise ValueError(f"d_max {d_max} >= 2^24: chunk sums would wrap")
    bits = 16 if d_max < 1 << 16 else 8
    mask = jnp.uint32((1 << bits) - 1)
    per_limb = 32 // bits
    chunks = jnp.stack([(t >> (bits * k)) & mask
                        for t in terms for k in range(per_limb)], axis=-1)
    sums = jnp.zeros((counts.shape[0], n, 2 * per_limb), jnp.uint32)
    sums = sums.at[:, vertex, :].add(chunks, indices_are_sorted=True)
    carry = jnp.zeros(sums.shape[:-1], jnp.uint32)
    limbs = [jnp.zeros_like(carry), jnp.zeros_like(carry)]  # lo, hi
    for k in range(2 * per_limb):
        t = sums[..., k] + carry
        limbs[k // per_limb] |= (t & mask) << (bits * (k % per_limb))
        carry = t >> bits
    lo, hi = limbs
    sat = (carry > 0) | (hi >= _SAT_HI)  # Σ ≥ 2^64, or Σ ≥ 2^62 = SAT64
    hi = jnp.where(sat, _SAT_HI, hi)
    lo = jnp.where(sat, _SAT_LO, lo)
    return CniValue(hi=hi.reshape(batch_shape + (n,)),
                    lo=lo.reshape(batch_shape + (n,)))


def cni_log_from_counts(counts: jnp.ndarray, d_max: int, max_p: int) -> jnp.ndarray:
    """float32 log-space CNI (the TPU-kernel fast path): logsumexp of terms.

    counts: (..., L) — any leading batch shape, per-row like the exact path.
    """
    batch_shape = counts.shape[:-1]
    counts = counts.reshape((-1, counts.shape[-1]))
    log_t = log_hbar_table(d_max, max_p)
    _, prefix, deg = _descending_positions(counts, d_max)
    q = jnp.arange(1, d_max + 1, dtype=jnp.int32)
    p = jnp.clip(prefix, 0, max_p)
    terms = log_t[q[None, :], p]  # (V, D)
    valid = jnp.arange(d_max)[None, :] < deg[:, None]
    terms = jnp.where(valid, terms, -jnp.inf)
    m = jnp.max(terms, axis=-1)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    s = jnp.sum(jnp.where(valid, jnp.exp(terms - m_safe[:, None]), 0.0), axis=-1)
    out = m_safe + jnp.log(jnp.maximum(s, 1e-30))
    return jnp.where(deg > 0, out, -jnp.inf).reshape(batch_shape)


def cni_from_counts_np(counts: np.ndarray, d_max: int, max_p: int):
    """Host (numpy) twin of the device encode: (N, L) count rows ->
    (cni_u64 (N,), cni_log (N,) f32, deg (N,) int32).

    Mirrors the device semantics *exactly* — same saturated Pascal table,
    same ``min(p, max_p)`` clip, same sticky ``min(acc + term, SAT64)``
    saturating add — so host-maintained digests (batch assembly, the
    incremental store index) compare bit-identically against device digests.
    Rows whose float64 term-sum shadow stays safely below SAT64 take a plain
    uint64 sum (provably equal: partial sums are monotone, so no saturating
    add can have fired); only near/over-saturation rows replay the sticky
    saturating accumulation.
    """
    counts = np.asarray(counts)
    n, L = counts.shape
    deg_all = counts.sum(axis=1).astype(np.int32)
    if n == 0 or d_max <= 0:
        return (
            np.zeros(n, np.uint64),
            np.full(n, -np.inf, np.float32),
            deg_all,
        )
    table = _pascal_table_np(d_max, max_p)  # uint64, saturated at SAT64
    log_t = _log_hbar_np(d_max, max_p)
    sat = int(SAT64)

    # vectorized descending expansion across all rows (the numpy twin of
    # _descending_positions): label at position j = first ccum bin > j
    desc = counts[:, ::-1]
    ccum = np.cumsum(desc, axis=1)                              # (N, L)
    posr = np.arange(d_max)
    idx = (ccum[:, None, :] <= posr[None, :, None]).sum(-1)     # (N, D)
    lab = np.maximum(L - idx, 0)
    deg = ccum[:, -1]
    valid = posr[None, :] < deg[:, None]
    lab = np.where(valid, lab, 0)
    prefix = np.minimum(np.cumsum(lab, axis=1), max_p)          # (N, D)
    q_idx = np.arange(1, d_max + 1)
    terms = np.where(valid, table[q_idx[None, :], prefix], 0)   # uint64

    shadow_total = np.cumsum(terms.astype(np.float64), axis=1)[:, -1]
    cni_u64 = terms.sum(axis=1, dtype=np.uint64)
    for v in np.nonzero(shadow_total >= float(SAT64) * 0.5)[0]:
        # near/over saturation: replay the device's sticky saturating adds
        acc = 0
        for j in range(1, min(int(deg[v]), d_max) + 1):
            acc = min(acc + int(table[j, prefix[v, j - 1]]), sat)
        cni_u64[v] = acc

    log_terms = np.where(valid, log_t[q_idx[None, :], prefix], -np.inf)
    log_terms = log_terms.astype(np.float32)
    m = log_terms.max(axis=1, initial=-np.inf)
    m_safe = np.where(np.isfinite(m), m, np.float32(0.0))
    s = np.sum(
        np.where(valid, np.exp(log_terms - m_safe[:, None]), 0.0),
        axis=1, dtype=np.float32,
    )
    cni_log = np.where(
        deg > 0,
        m_safe + np.log(np.maximum(s, np.float32(1e-30))),
        -np.inf,
    ).astype(np.float32)
    return cni_u64, cni_log, deg_all


def cni_exact_py(labels: list[int]) -> int:
    """Arbitrary-precision host oracle of the paper's formula (descending)."""
    import math

    xs = sorted((int(x) for x in labels if int(x) > 0), reverse=True)
    total = 0
    s = 0
    for j, x in enumerate(xs, start=1):
        s += x
        total += math.comb(j + s - 1, j)
    return total
