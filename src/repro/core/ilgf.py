"""Iterative Local-Global Filtering (the paper's Algorithm 2).

The paper removes one vertex at a time and incrementally patches its
neighbors' degrees/CNIs.  On TPU we run the *data-parallel peeling* form:
every round removes **all** currently-unmatchable vertices at once and
rebuilds the (masked) counts matrix with one segment-sum.  The two processes
reach the same fixed point: the removal operator is monotone (removing a
vertex can only shrink neighbors' digests, which can only enable further
removals, never disable one), so the closure is order-independent —
this is the standard confluence argument for peeling/k-core algorithms.

The fixed point is exactly the paper's "filtered data graph": every surviving
vertex cniMatch-es at least one query vertex *in the surviving induced
subgraph*.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import filters as flt
from repro.core.cni import cni_from_edges, default_max_p
from repro.core.labels import LabelMap, build_label_map, counts_matrix, ord_of
from repro.graphs.csr import Graph, max_degree


class IlgfResult(NamedTuple):
    alive: jnp.ndarray       # (V,) bool — surviving data vertices
    candidates: jnp.ndarray  # (V, U) bool — C(u) columns (Alg. 2 lines 20-25)
    iterations: jnp.ndarray  # scalar int32 — peeling rounds until fixed point


class QueryDigest(NamedTuple):
    label_map: LabelMap
    counts: jnp.ndarray
    digest: flt.VertexDigest
    mnd: jnp.ndarray  # (U,) maximum neighbor degree (CFL-match baseline)


def prepare_query(query: Graph, d_max: int, max_p: int) -> QueryDigest:
    label_map = build_label_map(query)
    q_counts = counts_matrix(query, label_map)
    q_digest = flt.make_digest(q_counts, ord_of(label_map, query.vlabels),
                               d_max, max_p)
    q_mnd = flt.mnd_values(q_counts, q_digest.deg, query.src, query.dst,
                           query.vlabels.shape[0])
    return QueryDigest(label_map, q_counts, q_digest, q_mnd)


def match_matrix(variant: str, counts: jnp.ndarray, ords: jnp.ndarray,
                 q: QueryDigest, g: Graph, alive: jnp.ndarray,
                 d_max: int, max_p: int) -> jnp.ndarray:
    """(..., V, U) candidate matrix under the chosen filter family.

    Accepts an optional leading batch dim on every per-query array (counts
    (B, V, L), ords/alive (B, V), query digest fields (B, U)); ``q`` only
    needs ``counts`` / ``digest`` / ``mnd`` attributes, so the batched engine
    passes its own stacked digest.  ``counts`` counts ``g``'s edge records
    (``counts_matrix*`` over ``g``): the ``cni`` variant encodes the data
    side's CNI over those records (``cni.cni_from_edges``).
    """
    if variant == "nlf":
        return flt.nlf_match(counts, q.counts, ords, q.digest.ord_label)
    if variant == "label_degree":
        deg = counts.sum(-1).astype(jnp.int32)
        do = ords[..., :, None]
        lab = (do == q.digest.ord_label[..., None, :]) & (do > 0)
        return lab & (deg[..., :, None] >= q.digest.deg[..., None, :])
    if variant == "mnd_nlf":  # CFL-match's Algorithm 1: MND gate then NLF
        deg = counts.sum(-1).astype(jnp.int32)
        mnd_d = flt.mnd_values(counts, deg, g.src, g.dst,
                               g.vlabels.shape[0], alive)
        gate = flt.mnd_match(mnd_d, q.mnd, ords, q.digest.ord_label)
        return gate & flt.nlf_match(counts, q.counts, ords, q.digest.ord_label)
    if variant == "cni":
        # the data side's exact CNI over g's edge records (``counts`` counts
        # them); the exact filter reads no log digest
        digest = flt.VertexDigest(
            ord_label=ords.astype(jnp.int32),
            deg=counts.sum(-1).astype(jnp.int32),
            cni=cni_from_edges(counts, g.src, d_max, max_p),
            cni_log=None,
        )
        return flt.cni_match(digest, q.digest)
    digest = flt.make_digest(counts, ords, d_max, max_p)
    if variant == "cni_log":
        return flt.cni_match_log(digest, q.digest)
    raise ValueError(f"unknown filter variant: {variant}")


def encoded_positions(variant: str, n_rows: int, g: Graph, d_max: int, *,
                      padded: bool = False) -> int:
    """CNI positions one ``match_matrix`` call encodes for ``n_rows`` count
    rows per data vertex: one per directed edge record on the exact path,
    ``d_max`` per vertex on the log path (and, with ``padded``, on the
    per-shard ``distributed.local_match_matrix``), none for the others."""
    if variant == "cni" and not padded:
        return n_rows * g.n_directed_edges
    if variant in ("cni", "cni_log"):
        return n_rows * g.n_vertices * d_max
    return 0


@functools.partial(jax.jit, static_argnames=("d_max", "max_p", "variant",
                                             "max_iters"))
def _ilgf_jit(g: Graph, q: QueryDigest, ords: jnp.ndarray,
              alive0: jnp.ndarray, *, d_max: int, max_p: int, variant: str,
              max_iters: int) -> IlgfResult:
    def round_fn(state):
        alive, _, it = state
        counts = counts_matrix(g, q.label_map, alive)
        match = match_matrix(variant, counts, ords, q, g, alive, d_max, max_p)
        cand = jnp.any(match, axis=-1)
        new_alive = alive & cand
        changed = jnp.any(new_alive != alive)
        return new_alive, changed, it + 1

    def cond_fn(state):
        _, changed, it = state
        return changed & (it < max_iters)

    state = (alive0, jnp.asarray(True), jnp.asarray(0, jnp.int32))
    alive, _, iters = jax.lax.while_loop(cond_fn, round_fn, state)
    # final candidate sets over the fixed-point graph (Alg. 2 lines 20-25)
    counts = counts_matrix(g, q.label_map, alive)
    match = match_matrix(variant, counts, ords, q, g, alive, d_max, max_p)
    candidates = match & alive[:, None]
    return IlgfResult(alive=alive, candidates=candidates, iterations=iters)


def ilgf(data: Graph, query: Graph, *, variant: str = "cni",
         d_max: int | None = None, max_p: int | None = None,
         max_iters: int = 1_000, alive0=None, mesh=None,
         shard_axis: str = "data") -> IlgfResult:
    """Run ILGF to its fixed point.  Returns alive mask + candidate columns.

    ``variant``:
      * ``cni``          — the paper (exact saturating-limb CNI filter)
      * ``cni_log``      — the paper, float32 log-space fast path
      * ``nlf``          — NLF baseline (CFL-match / TurboISO filter)
      * ``label_degree`` — Ullmann-era baseline

    ``alive0``: optional (V,) bool starting mask — a *sound* pre-filter
    (e.g. ``incremental.store_prefilter`` from maintained store digests)
    that lets the fixed point start past round one.  Peeling is monotone, so
    any sound starting superset reaches a fixed point whose search results
    are identical.

    ``mesh``: optional ``jax.sharding.Mesh`` — runs the *vertex-partitioned*
    fixed point (``core/distributed.py``) over the mesh's ``shard_axis``
    instead of the single-device loop.  Bit-identical results; see
    DESIGN.md §9.
    """
    if mesh is not None:
        from repro.core.distributed import distributed_ilgf

        return distributed_ilgf(
            data, query, mesh, axis=shard_axis, variant=variant,
            d_max=d_max, max_p=max_p, alive0=alive0, max_iters=max_iters,
        )
    if d_max is None:
        d_max = max(1, max_degree(data))
    label_map = build_label_map(query)
    if max_p is None:
        max_p = default_max_p(d_max, label_map.n_labels)
    q = prepare_query(query, d_max, max_p)
    ords = ord_of(q.label_map, data.vlabels)
    if alive0 is None:
        alive0 = ords > 0  # Lemma 1 applied up front
    else:
        alive0 = jnp.asarray(alive0) & (ords > 0)
    return _ilgf_jit(data, q, ords, alive0, d_max=d_max, max_p=max_p,
                     variant=variant, max_iters=max_iters)


def one_shot_filter(data: Graph, query: Graph, *, variant: str = "cni",
                    d_max: int | None = None) -> IlgfResult:
    """Single (non-iterated) filtering pass — for pruning-power comparisons."""
    if d_max is None:
        d_max = max(1, max_degree(data))
    label_map = build_label_map(query)
    max_p = default_max_p(d_max, label_map.n_labels)
    q = prepare_query(query, d_max, max_p)
    ords = ord_of(q.label_map, data.vlabels)
    counts = counts_matrix(data, q.label_map, ords > 0)
    match = match_matrix(variant, counts, ords, q, data, ords > 0, d_max, max_p)
    cand = jnp.any(match, axis=1) & (ords > 0)
    return IlgfResult(alive=cand, candidates=match & cand[:, None],
                      iterations=jnp.asarray(1, jnp.int32))
