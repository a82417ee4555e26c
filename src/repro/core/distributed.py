"""Distributed CNI engine: the mesh/partition authority + sharded execution.

This module is the **single source of truth for how the vertex axis maps
onto devices**.  Every layer that shards anything — the partitioned graph
store (``graphs/store.py::ShardedGraphStore``), the per-shard incremental
index (``core/incremental.py::ShardedIncrementalIndex``), the single-query
and batched ILGF fixed points, and the serving front-end — consumes the same
three primitives defined here:

* ``vertex_partition(V, n_shards)`` → :class:`PartitionPlan`: contiguous
  equal slices of a padded vertex axis, shard *i* owning rows
  ``[i·v_local, (i+1)·v_local)``.  The pad rows carry ord 0 / alive False,
  which are exact no-ops for counts, digests, and matching.
* ``device_mesh(n_shards)`` → a cached 1-D :class:`jax.sharding.Mesh` over
  the ``data`` axis (CPU hosts get virtual devices via
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
* ``shard_edges(src, dst, plan)`` → per-shard directed edge buckets, each
  edge living with the owner of its *source* endpoint, so every shard can
  build the count rows of exactly its owned vertices locally.

Scaling story (DESIGN.md §3/§6/§9): per ILGF round every shard filters its
own vertex slice *locally* — counts, digests and cniMatch are embarrassingly
parallel — and the only cross-shard traffic is one ``all_gather`` of the
(1 bit/vertex) removal mask plus one ``psum`` of the per-shard alive counts.
The count all-reduce is what makes the *retirement decision* globally
consistent: peeling is monotone (alive sets only shrink), so the global
alive count is stationary exactly at the fixed point, and every shard stops
on the same round.  That is the distributed translation of the paper's
"CNIs are cheap to update after each local pruning": the global effect of a
removal is conveyed by one broadcast bit, not by shipping neighborhoods.

The join search shards the partial-embedding table rows, expands locally
against a replicated filtered graph (small by construction after ILGF), and
rebalances rows with an ``all_to_all`` round-robin every step — straggler
mitigation for skewed candidate distributions.

Everything is expressed with ``shard_map`` + ``jax.lax`` collectives, so the
same code drives 8 host devices (tests) or a 512-chip production mesh.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import filters as flt
from repro.core.cni import default_max_p
from repro.core.ilgf import IlgfResult, prepare_query
from repro.core.labels import build_label_map, ord_of
from repro.graphs.csr import Graph, max_degree


def shard_map_nocheck(*, mesh, in_specs, out_specs):
    """``jax.shard_map`` decorator with replication (vma) checks off."""
    return functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )


# ---------------------------------------------------------------------------
# Partition authority: one plan shared by store, index, engines, service.
# ---------------------------------------------------------------------------


class PartitionPlan(NamedTuple):
    """Contiguous vertex partition: shard i owns ``[i*v_local, (i+1)*v_local)``.

    ``v_pad`` rounds the vertex axis up to a multiple of ``n_shards`` so the
    device arrays split evenly; pad vertices (ids ≥ ``n_vertices``) never
    carry labels, edges, or alive bits.  All fields are plain ints, so the
    plan is hashable and usable as a jit-cache key.
    """

    n_shards: int
    n_vertices: int
    v_pad: int
    v_local: int

    def owner(self, v):
        """Owner shard of vertex id(s) ``v`` (host-side, numpy-friendly)."""
        return np.asarray(v) // self.v_local

    def bounds(self, shard: int) -> tuple[int, int]:
        """Owned range ``[lo, hi)`` of real (unpadded) vertex ids.

        Both ends clamp to ``n_vertices``: a trailing shard that owns only
        padding gets an empty (never inverted) range.
        """
        lo = min(shard * self.v_local, self.n_vertices)
        return lo, min((shard + 1) * self.v_local, self.n_vertices)


def vertex_partition(n_vertices: int, n_shards: int) -> PartitionPlan:
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    v_pad = -(-max(1, n_vertices) // n_shards) * n_shards
    return PartitionPlan(n_shards, int(n_vertices), v_pad, v_pad // n_shards)


@functools.lru_cache(maxsize=None)
def device_mesh(n_shards: int | None = None, axis: str = "data") -> Mesh:
    """1-D device mesh over ``axis`` (defaults to every visible device).

    Cached per (count, axis): the mesh participates in jit-trace cache keys,
    so all callers must share one instance.  Multi-device CPU runs come from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (tests, CI).
    """
    devices = jax.devices()
    if n_shards is None:
        n_shards = len(devices)
    if n_shards > len(devices):
        raise ValueError(
            f"requested {n_shards} shards but only {len(devices)} devices "
            "are visible (set --xla_force_host_platform_device_count)"
        )
    return Mesh(np.asarray(devices[:n_shards]), (axis,))


class ShardedEdges(NamedTuple):
    """Per-shard directed edge buckets: row i holds the edges whose source
    vertex shard i owns, padded to a common length."""

    edge_src: jnp.ndarray  # (D, Epad) int32
    edge_dst: jnp.ndarray  # (D, Epad) int32
    edge_ok: jnp.ndarray   # (D, Epad) bool — padding mask


def shard_edges(src, dst, plan: PartitionPlan) -> ShardedEdges:
    """Bucket directed (symmetrized) edges by the owner shard of ``src``.

    Each undirected edge appears twice in the symmetrized list, so the
    (u→w) direction lands on owner(u) and (w→u) on owner(w) — the host-side
    materialization of the owner/ghost boundary exchange: a cross-shard edge
    is present in both endpoint owners' buckets, each in the direction that
    feeds its *owned* count row.  Host arrays; ``prepare_sharded_edges``
    places bucket i on the device that owns shard i.
    """
    src = np.asarray(src, dtype=np.int32)
    dst = np.asarray(dst, dtype=np.int32)
    owner = src // plan.v_local
    buckets = [np.flatnonzero(owner == i) for i in range(plan.n_shards)]
    e_pad = max(1, max((b.size for b in buckets), default=1))
    es = np.zeros((plan.n_shards, e_pad), dtype=np.int32)
    ed = np.zeros((plan.n_shards, e_pad), dtype=np.int32)
    ok = np.zeros((plan.n_shards, e_pad), dtype=bool)
    for i, b in enumerate(buckets):
        es[i, : b.size] = src[b]
        ed[i, : b.size] = dst[b]
        ok[i, : b.size] = True
    return ShardedEdges(es, ed, ok)


def prepare_sharded_edges(data, mesh: Mesh, axis: str = "data"):
    """Normalize any graph-like input to (ShardedEdges, PartitionPlan, Graph).

    Accepts ``Graph | GraphStore | ShardedGraphStore | GraphSnapshot``.  A
    snapshot from a :class:`~repro.graphs.store.ShardedGraphStore` whose
    logical shard count matches the mesh reuses the store's per-shard
    canonical tables (symmetrized on the fly); anything else buckets the
    snapshot graph's edge list — an O(E) host pass.
    """
    from repro.graphs.store import as_snapshot

    snap = as_snapshot(data)
    g = snap.graph
    plan = vertex_partition(g.n_vertices, mesh.shape[axis])
    tables = snap.shards
    if tables is not None and len(tables) == plan.n_shards:
        # the store already owner-bucketed the (lo -> hi) direction: table i
        # holds exactly the canonical edges owner(lo) == i.  Only the
        # reverse (hi -> lo) directions — the ghost/boundary flow back to
        # owner(hi) — still need routing, and intra-shard reverses route to
        # the same table, so one partition pass over the hi endpoints
        # replaces the full O(D·E) re-bucket of the fallback below.
        fwd = [(t[0].astype(np.int32), t[1].astype(np.int32))
               for t in tables]
        rev_src = [[] for _ in range(plan.n_shards)]
        rev_dst = [[] for _ in range(plan.n_shards)]
        for f_lo, f_hi in fwd:
            owner_hi = f_hi // plan.v_local
            for i in np.unique(owner_hi):
                m = owner_hi == i
                rev_src[i].append(f_hi[m])
                rev_dst[i].append(f_lo[m])
        srcs = [np.concatenate([fwd[i][0]] + rev_src[i])
                for i in range(plan.n_shards)]
        dsts = [np.concatenate([fwd[i][1]] + rev_dst[i])
                for i in range(plan.n_shards)]
        e_pad = max(1, max(s.size for s in srcs))
        es = np.zeros((plan.n_shards, e_pad), dtype=np.int32)
        ed = np.zeros((plan.n_shards, e_pad), dtype=np.int32)
        ok = np.zeros((plan.n_shards, e_pad), dtype=bool)
        for i in range(plan.n_shards):
            k = srcs[i].size
            es[i, :k] = srcs[i]
            ed[i, :k] = dsts[i]
            ok[i, :k] = True
        se = ShardedEdges(es, ed, ok)
    else:
        se = shard_edges(np.asarray(g.src), np.asarray(g.dst), plan)
    # bucket i lives on the device that owns shard i, not on device 0
    rows = NamedSharding(mesh, P(axis))
    return ShardedEdges(*(jax.device_put(x, rows) for x in se)), plan, g


# ---------------------------------------------------------------------------
# Local (per-shard) filtering building blocks.
# ---------------------------------------------------------------------------


def _local_counts(edge_src, edge_dst, edge_ok, ords, alive, v_lo, v_local, L):
    """Counts rows for the local vertex slice from the local edge bucket."""
    ord_dst = ords[edge_dst]
    ok = edge_ok & (ord_dst > 0) & (ords[edge_src] > 0)
    ok = ok & alive[edge_dst] & alive[edge_src]
    idx = (edge_src - v_lo).astype(jnp.int32) * L + jnp.maximum(ord_dst - 1, 0)
    flat = jnp.zeros((v_local * L,), jnp.int32)
    flat = flat.at[idx].add(ok.astype(jnp.int32))
    return flat.reshape(v_local, L)


def local_match_matrix(variant: str, counts, my_ords, q, d_max: int,
                       max_p: int):
    """(..., Vl, U) candidate grid over a *local vertex slice*.

    The per-shard twin of ``ilgf.match_matrix``: every supported variant
    needs only the slice's own count rows plus the replicated query digest,
    so no collective runs inside a filtering round.  ``mnd_nlf`` is the one
    family that inspects *neighbor* digests (maximum neighbor degree) and
    would need a per-round halo exchange — it is not offered on the sharded
    path (use the single-device engine or the sound ``nlf`` superset).
    """
    if variant == "nlf":
        return flt.nlf_match(counts, q.counts, my_ords, q.digest.ord_label)
    if variant == "label_degree":
        deg = counts.sum(-1).astype(jnp.int32)
        do = my_ords[..., :, None]
        lab = (do == q.digest.ord_label[..., None, :]) & (do > 0)
        return lab & (deg[..., :, None] >= q.digest.deg[..., None, :])
    digest = flt.make_digest(counts, my_ords, d_max, max_p)
    if variant == "cni":
        return flt.cni_match(digest, q.digest)
    if variant == "cni_log":
        return flt.cni_match_log(digest, q.digest)
    raise ValueError(
        f"filter variant {variant!r} is not supported on the sharded path "
        "(mnd_nlf needs neighbor digests — a per-round halo exchange; see "
        "DESIGN.md §9)"
    )


# ---------------------------------------------------------------------------
# Single-query partitioned ILGF fixed point.
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _distributed_ilgf_fn(mesh: Mesh, axis: str, v_local: int, n_labels: int,
                         d_max: int, max_p: int, variant: str,
                         max_iters: int):
    """Build (and cache) the jitted partitioned fixed point for one static
    config — repeat queries over the same mesh/shape revisit the trace."""
    L = n_labels

    def fn(ords, edge_src, edge_dst, edge_ok, alive_init, q):
        @shard_map_nocheck(
            mesh=mesh,
            in_specs=(P(), P(axis), P(axis), P(axis), P(), P()),
            out_specs=(P(), P(axis), P()),
        )
        def run(ords, edge_src, edge_dst, edge_ok, alive0, q):
            my = jax.lax.axis_index(axis)
            v_lo = my.astype(jnp.int32) * v_local
            es, ed, eo = edge_src[0], edge_dst[0], edge_ok[0]

            def local_match(alive):
                counts = _local_counts(es, ed, eo, ords, alive, v_lo,
                                       v_local, L)
                my_ords = jax.lax.dynamic_slice(ords, (v_lo,), (v_local,))
                return local_match_matrix(variant, counts, my_ords, q,
                                          d_max, max_p)

            def body(state):
                alive, _, it = state
                match = local_match(alive)
                my_alive = jax.lax.dynamic_slice(alive, (v_lo,), (v_local,))
                new_local = my_alive & jnp.any(match, axis=1)
                # two collectives per round: the 1-bit/vertex mask broadcast
                # and the alive-count all-reduce that decides global
                # retirement — peeling is monotone (no vertex is ever
                # revived), so the global count is stationary iff the mask
                # is, and every shard agrees on the same stopping round
                new_alive = jax.lax.all_gather(new_local, axis, tiled=True)
                n_old = jax.lax.psum(my_alive.sum(dtype=jnp.int32), axis)
                n_now = jax.lax.psum(new_local.sum(dtype=jnp.int32), axis)
                return new_alive, n_now != n_old, it + 1

            def cond(state):
                _, changed, it = state
                return changed & (it < max_iters)

            state = (alive0, jnp.asarray(True), jnp.asarray(0, jnp.int32))
            alive, _, iters = jax.lax.while_loop(cond, body, state)
            final_match = local_match(alive)
            my_alive = jax.lax.dynamic_slice(alive, (v_lo,), (v_local,))
            cand_local = final_match & my_alive[:, None]
            return alive, cand_local, iters

        return run(ords, edge_src, edge_dst, edge_ok, alive_init, q)

    return jax.jit(fn)


def distributed_ilgf(
    data,
    query: Graph,
    mesh: Mesh | None = None,
    *,
    axis: str = "data",
    variant: str = "cni",
    d_max: int | None = None,
    max_p: int | None = None,
    alive0=None,
    max_iters: int = 1_000,
    prepared=None,
) -> IlgfResult:
    """ILGF fixed point on a vertex-partitioned graph.  Matches ``ilgf``
    bit-for-bit: same alive mask, same candidate columns, same round count.

    ``data`` may be a Graph, GraphStore, ShardedGraphStore, or
    GraphSnapshot; ``alive0`` is an optional sound starting mask (e.g. the
    store-digest prefilter), padded/broadcast here.  Per round each shard
    peels its own slice; one ``all_gather`` broadcasts the new mask and one
    ``psum`` of per-shard alive counts decides retirement globally —
    monotonicity makes count-stationarity equivalent to mask-stationarity.

    ``prepared``: optional ``(ShardedEdges, PartitionPlan, Graph)`` from a
    prior ``prepare_sharded_edges`` call — engines serving many queries
    over one graph bucket once and reuse.
    """
    if mesh is None:
        mesh = device_mesh(axis=axis)
    se, plan, g = (
        prepared if prepared is not None
        else prepare_sharded_edges(data, mesh, axis)
    )
    if d_max is None:
        d_max = max(1, max_degree(g))
    label_map = build_label_map(query)
    L = label_map.n_labels
    if max_p is None:
        max_p = default_max_p(d_max, L)
    q = prepare_query(query, d_max, max_p)

    ords = np.zeros(plan.v_pad, dtype=np.int32)
    ords[: g.n_vertices] = np.asarray(ord_of(label_map, g.vlabels))
    a0 = ords > 0
    if alive0 is not None:
        a0[: g.n_vertices] &= np.asarray(alive0, dtype=bool)

    fn = _distributed_ilgf_fn(mesh, axis, plan.v_local, L, d_max, max_p,
                              variant, max_iters)
    alive, cand, iters = fn(
        jnp.asarray(ords), se.edge_src, se.edge_dst, se.edge_ok,
        jnp.asarray(a0), q,
    )
    n = g.n_vertices
    return IlgfResult(alive=alive[:n], candidates=cand[:n], iterations=iters)


# ---------------------------------------------------------------------------
# Batched sharded peeling round (batch engine / serving tick unit).
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _sharded_round_fn(mesh: Mesh, axis: str, plan: PartitionPlan,
                      n_labels: int, d_max: int, max_p: int, variant: str):
    """Build (and cache) the jitted sharded round for one static config.

    Keyed on hashables only — the mesh object, the partition plan, and the
    filter config — so serving ticks and batch-engine rounds revisit the
    same trace instead of re-tracing per call (``device_mesh`` returns a
    cached mesh precisely so it can participate in this key).
    """
    v_local, v_pad = plan.v_local, plan.v_pad
    L = n_labels

    def fn(edge_src, edge_dst, edge_ok, qb, alive):
        s, v = alive.shape
        pad = v_pad - v
        ords = jnp.pad(qb.ords, ((0, 0), (0, pad)))
        alive_p = jnp.pad(alive, ((0, 0), (0, pad)))

        @shard_map_nocheck(
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P(), P(), P()),
            out_specs=(P(), P(), P()),
        )
        def run(edge_src, edge_dst, edge_ok, ords, qb, alive):
            my = jax.lax.axis_index(axis)
            v_lo = my.astype(jnp.int32) * v_local
            es, ed, eo = edge_src[0], edge_dst[0], edge_ok[0]

            # per-slot local counts for the owned vertex slice: one scatter
            # over (S, E_local) edge records with per-slot flat offsets
            ord_dst = ords[:, ed]                      # (S, El)
            ok = (
                eo[None, :] & (ord_dst > 0) & (ords[:, es] > 0)
                & alive[:, ed] & alive[:, es]
            )
            idx = (es - v_lo).astype(jnp.int32)[None, :] * L + jnp.maximum(
                ord_dst - 1, 0
            )
            flat = jnp.zeros((s, v_local * L), jnp.int32)
            flat = flat.at[
                jnp.arange(s, dtype=jnp.int32)[:, None], idx
            ].add(ok.astype(jnp.int32))
            counts = flat.reshape(s, v_local, L)

            my_ords = jax.lax.dynamic_slice(ords, (0, v_lo), (s, v_local))
            match = local_match_matrix(variant, counts, my_ords, qb, d_max,
                                       max_p)
            my_alive = jax.lax.dynamic_slice(alive, (0, v_lo), (s, v_local))
            new_local = my_alive & jnp.any(match, axis=-1)
            cand_local = match & new_local[..., None]
            # collectives: mask broadcast + per-slot alive-count all-reduce
            new_alive = jax.lax.all_gather(new_local, axis, axis=1,
                                           tiled=True)
            cand = jax.lax.all_gather(cand_local, axis, axis=1, tiled=True)
            n_old = jax.lax.psum(
                my_alive.sum(axis=-1, dtype=jnp.int32), axis
            )
            n_now = jax.lax.psum(
                new_local.sum(axis=-1, dtype=jnp.int32), axis
            )
            return new_alive, cand, n_now != n_old

        new_alive, cand, changed = run(
            edge_src, edge_dst, edge_ok, ords, qb, alive_p
        )
        return new_alive[:, :v], cand[:, :v], changed

    return jax.jit(fn)


def sharded_batched_ilgf_round(
    se: ShardedEdges,
    plan: PartitionPlan,
    qb,
    alive: jnp.ndarray,
    *,
    mesh: Mesh,
    axis: str = "data",
    n_labels: int,
    d_max: int,
    max_p: int,
    variant: str,
):
    """One batched peeling round under ``shard_map`` — the drop-in sharded
    twin of ``batch_engine.batched_ilgf_round`` (same signature contract:
    returns ``(new_alive (S, V), candidates (S, V, U), changed (S,))``, with
    candidate columns final for any slot whose ``changed`` is False).

    The vertex axis is partitioned per ``plan``; the batch axis is
    replicated.  Bit-identical to the single-device round: each shard
    encodes digests for exactly its owned slice from exactly the rows the
    single-device scatter would produce, and retirement is decided by the
    all-reduced alive counts (sound by monotonicity).
    """
    fn = _sharded_round_fn(mesh, axis, plan, n_labels, d_max, max_p, variant)
    return fn(se.edge_src, se.edge_dst, se.edge_ok, qb, alive)


# ---------------------------------------------------------------------------
# Distributed join search with all_to_all rebalancing.
# ---------------------------------------------------------------------------


def distributed_join_step(
    mesh: Mesh,
    axis: str,
    table: jnp.ndarray,      # (D, cap, t) sharded rows
    n_rows: jnp.ndarray,     # (D, 1) valid-row counts
    cand_list: jnp.ndarray,  # (C,) replicated candidates for u_t
    elab_matrix: jnp.ndarray,  # (N, N) replicated
    q_nbr_pos: jnp.ndarray,
    q_nbr_lab: jnp.ndarray,
    q_nbr_valid: jnp.ndarray,
    cand_valid: jnp.ndarray,
    cap: int,
):
    """One distributed expansion: local join, local compaction, round-robin
    all_to_all rebalance.  Returns (new_table, new_counts, overflowed)."""
    n_shards = mesh.shape[axis]
    t = table.shape[-1]

    @shard_map_nocheck(
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(), P(), P(), P(), P(), P()),
        out_specs=(P(axis), P(axis), P()),
    )
    def step(table, n_rows, cand_list, elab, qp, ql, qv, cv):
        tab = table[0]          # (cap, t)
        rows_valid = jnp.arange(cap) < n_rows[0, 0]
        mapped = tab[:, qp]     # (cap, J)
        got = elab[mapped[:, :, None], cand_list[None, None, :]]  # (cap, J, C)
        lab_ok = (got == ql[None, :, None]) | ~qv[None, :, None]
        adj_ok = jnp.all(lab_ok, axis=1)
        inj_ok = jnp.all(tab[:, :, None] != cand_list[None, None, :], axis=1)
        valid = adj_ok & inj_ok & rows_valid[:, None] & cv[None, :]  # (cap, C)

        flat = valid.reshape(-1)
        n_new = jnp.sum(flat)
        pos = jnp.cumsum(flat) - 1  # compaction targets
        r_idx = jnp.arange(flat.shape[0]) // valid.shape[1]
        c_idx = jnp.arange(flat.shape[0]) % valid.shape[1]
        write_pos = jnp.where(flat & (pos < cap), pos, cap)  # cap = scratch row
        new_tab = jnp.zeros((cap + 1, t + 1), jnp.int32)
        rows = jnp.concatenate(
            [tab[r_idx], cand_list[c_idx][:, None]], axis=1
        )
        new_tab = new_tab.at[write_pos].set(rows)
        new_tab = new_tab[:cap]
        overflow = n_new > cap

        # round-robin rebalance: deal local rows into n_shards piles
        per = cap // n_shards
        n_local = jnp.minimum(n_new, cap)
        piles = new_tab[: per * n_shards].reshape(n_shards, per, t + 1)
        pile_counts = jnp.clip(
            n_local - jnp.arange(n_shards) * per, 0, per
        ).astype(jnp.int32)
        shuffled = jax.lax.all_to_all(
            piles, axis, split_axis=0, concat_axis=0, tiled=True
        )
        counts_in = jax.lax.all_to_all(
            pile_counts.reshape(n_shards, 1), axis, split_axis=0,
            concat_axis=0, tiled=True,
        )  # (n_shards, 1)
        # compact received piles
        recv = shuffled.reshape(n_shards * per, t + 1)
        recv_valid = (
            jnp.arange(per)[None, :] < counts_in.reshape(n_shards)[:, None]
        ).reshape(-1)
        rpos = jnp.where(recv_valid, jnp.cumsum(recv_valid) - 1, cap)
        out = jnp.zeros((cap + 1, t + 1), jnp.int32)
        out = out.at[rpos].set(recv)
        out = out[:cap]
        total = jnp.sum(recv_valid).astype(jnp.int32)
        any_overflow = jax.lax.all_gather(overflow, axis).any()
        return out[None], total.reshape(1, 1), any_overflow

    return step(
        table, n_rows, cand_list, elab_matrix, q_nbr_pos, q_nbr_lab,
        q_nbr_valid, cand_valid,
    )


def distributed_join_search(
    data: Graph,
    query: Graph,
    candidates: np.ndarray,
    mesh: Mesh,
    *,
    axis: str = "data",
    cap: int = 4096,
    order=None,
):
    """Enumerate embeddings with sharded tables.  Returns (emb, overflowed).

    ``cap`` rows per shard; overflow is reported (callers fall back to the
    chunked host loop — in production, re-run with a bigger cap/mesh).
    ``order``: explicit matching order (any permutation; defaults to the
    shared greedy rule, like the host searchers).
    """
    from repro.core.search import (
        _as_order,
        _dense_edge_labels,
        _host_adjacency,
        greedy_matching_order,
    )

    cand = np.asarray(candidates)
    n_q = query.vlabels.shape[0]
    n_shards = mesh.shape[axis]
    assert cap % n_shards == 0, "cap must divide evenly across shards"
    q_adj = _host_adjacency(query)
    elab_matrix = jnp.asarray(_dense_edge_labels(data, data.n_vertices))

    if order is None:
        order = greedy_matching_order(cand.sum(axis=0), q_adj)
    else:
        order = _as_order(order, n_q)
    pos_of = {u: i for i, u in enumerate(order)}

    seeds = np.nonzero(cand[:, order[0]])[0].astype(np.int32)
    table = np.zeros((n_shards, cap, 1), dtype=np.int32)
    n_rows = np.zeros((n_shards, 1), dtype=np.int32)
    for i in range(n_shards):
        mine = seeds[i::n_shards]
        table[i, : mine.size, 0] = mine
        n_rows[i, 0] = mine.size

    table_j = jnp.asarray(table)
    rows_j = jnp.asarray(n_rows)
    overflowed = False
    for t in range(1, n_q):
        u = order[t]
        cand_ids = np.nonzero(cand[:, u])[0].astype(np.int32)
        nbrs = [(pos_of[w], el) for w, el in q_adj.get(u, {}).items() if pos_of[w] < t]
        j = max(1, len(nbrs))
        q_pos = np.zeros(j, dtype=np.int32)
        q_lab = np.zeros(j, dtype=np.int32)
        q_val = np.zeros(j, dtype=bool)
        for k, (p_, el) in enumerate(nbrs):
            q_pos[k], q_lab[k], q_val[k] = p_, el, True
        c = max(1, cand_ids.size)
        cand_pad = np.zeros(c, dtype=np.int32)
        cand_pad[: cand_ids.size] = cand_ids
        cand_ok = np.zeros(c, dtype=bool)
        cand_ok[: cand_ids.size] = True

        table_j, rows_j, ovf = distributed_join_step(
            mesh, axis, table_j, rows_j,
            jnp.asarray(cand_pad), elab_matrix,
            jnp.asarray(q_pos), jnp.asarray(q_lab), jnp.asarray(q_val),
            jnp.asarray(cand_ok), cap,
        )
        overflowed = overflowed or bool(ovf)

    table = np.asarray(table_j)
    rows = np.asarray(rows_j)
    parts = [table[i, : rows[i, 0]] for i in range(n_shards)]
    flat = np.concatenate(parts, axis=0) if parts else np.zeros((0, n_q))
    out = np.zeros((flat.shape[0], n_q), dtype=np.int64)
    for i, u in enumerate(order):
        out[:, u] = flat[:, i]
    return out, overflowed


# ---------------------------------------------------------------------------
# Mesh-partitioned two-phase enumeration (DESIGN.md §13).
#
# The partial-embedding table is partitioned *by row* into one contiguous
# block per shard, in shard order — so the global row order (the bit-order
# contract every searcher shares) is simply the concatenation of the
# per-shard live prefixes.  Each phase of the PR 6 count → scan → emit join
# runs per shard under shard_map against replicated candidate / edge-label
# slices; the count phase's exact per-row output sizes drive both the
# deterministic shard-offset prefix (per-shard totals → host exclusive
# scan, the enumeration twin of the ILGF psum/all_gather retirement
# exchange) and the greedy row rebalancer (core/search.py), whose row
# moves run through the ``all_gather``-based exchange collective below.
# ---------------------------------------------------------------------------


# per-slice (R·C·J) validity-cell budget inside a shard body — same bound
# (and same rationale) as core/search.py::_DEVICE_JOIN_CELLS
_ENUM_CELLS = 1 << 24


def _enum_rows_per(c_pad: int, j: int) -> int:
    rows = _ENUM_CELLS // max(1, c_pad * j)
    rows = max(256, 1 << max(0, rows.bit_length() - 1))
    return min(rows, 4096)


def enum_row_blocks(weights, n_shards: int) -> np.ndarray:
    """Contiguous weighted row split: boundaries ``(n_shards + 1,)``.

    Greedily cuts the row sequence at the ideal cumulative-weight quantiles
    (``i · total / n_shards``), never splitting a row — the atom is a parent
    row together with *all* its children, which is what keeps shard blocks
    contiguous in the global row order.  Deterministic: equal prefix sums
    always cut at the smallest row index.  With unit weights this is the
    balanced equal-rows partition used to seed the table.
    """
    w = np.asarray(weights, dtype=np.int64).reshape(-1)
    n_rows = int(w.size)
    bounds = np.zeros(n_shards + 1, dtype=np.int64)
    bounds[n_shards] = n_rows
    if n_rows == 0 or n_shards == 1:
        return bounds
    prefix = np.cumsum(w)
    total = int(prefix[-1])
    if total == 0:
        # all-zero weights: fall back to equal row counts
        bounds[1:n_shards] = [
            (i * n_rows) // n_shards for i in range(1, n_shards)
        ]
        return bounds
    targets = np.arange(1, n_shards, dtype=np.float64) * (total / n_shards)
    cuts = np.searchsorted(prefix, targets, side="left") + 1
    bounds[1:n_shards] = np.minimum(cuts, n_rows)
    return np.maximum.accumulate(bounds)


@functools.lru_cache(maxsize=None)
def _enum_count_fn(mesh: Mesh, axis: str, pcap: int, c_pad: int, j: int,
                   use_kernel: bool):
    """Per-shard count phase: ``(D, pcap, t)`` table → per-row survivor
    counts, their local exclusive scan, and the per-shard total (the only
    value the host pulls when no rebalance triggers)."""
    rows_per = _enum_rows_per(c_pad, j)

    def fn(table, n_rows, cand, n_cand, elab, qp, ql, qv):
        @shard_map_nocheck(
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(), P(), P(), P(), P(), P()),
            out_specs=(P(axis), P(axis), P(axis)),
        )
        def run(table, n_rows, cand, n_cand, elab, qp, ql, qv):
            from repro.kernels.embed_join.ops import embed_join_count_raw

            tab = table[0]                     # (pcap, t)
            nr = n_rows[0, 0]
            elab_cols = elab[:, cand]          # (N, c_pad)
            cv = jnp.arange(c_pad) < n_cand
            parts = []
            for lo in range(0, pcap, rows_per):
                sl = tab[lo : lo + rows_per]
                rv = (jnp.arange(sl.shape[0]) + lo) < nr
                parts.append(embed_join_count_raw(
                    sl, rv, cand, cv, elab_cols, qp, ql, qv,
                    use_kernel=use_kernel,
                ))
            counts = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            row_off = jnp.cumsum(counts) - counts
            total = counts.sum(dtype=jnp.int32)
            return counts[None], row_off[None], total.reshape(1)

        return run(table, n_rows, cand, n_cand, elab, qp, ql, qv)

    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _enum_valid_fn(mesh: Mesh, axis: str, pcap: int, c_pad: int, j: int):
    """Per-shard validity grids for the host-assisted (XLA-CPU) scan route:
    only the 1-byte masks cross back — numpy's ``nonzero`` then plays the
    count + scan phases at once, exactly as on the single-device path."""
    rows_per = _enum_rows_per(c_pad, j)

    def fn(table, n_rows, cand, n_cand, elab, qp, ql, qv):
        @shard_map_nocheck(
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(), P(), P(), P(), P(), P()),
            out_specs=P(axis),
        )
        def run(table, n_rows, cand, n_cand, elab, qp, ql, qv):
            from repro.kernels.embed_join.ops import embed_join_raw

            tab = table[0]
            nr = n_rows[0, 0]
            elab_cols = elab[:, cand]
            cv = jnp.arange(c_pad) < n_cand
            parts = []
            for lo in range(0, pcap, rows_per):
                sl = tab[lo : lo + rows_per]
                rv = (jnp.arange(sl.shape[0]) + lo) < nr
                parts.append(embed_join_raw(
                    sl, rv, cand, cv, elab_cols, qp, ql, qv,
                    use_kernel=False,
                ))
            valid = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            return valid[None]

        return run(table, n_rows, cand, n_cand, elab, qp, ql, qv)

    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _enum_emit_fn(mesh: Mesh, axis: str, pcap: int, out_cap: int,
                  c_pad: int, j: int, use_kernel: bool):
    """Per-shard emit phase: scatter survivors into the shard's exactly
    sized (lane-aligned, uniform across shards) output block and decode the
    cell-id map into the next table slice in the same dispatch."""
    rows_per = _enum_rows_per(c_pad, j)

    def fn(table, n_rows, row_off, n_keep, cand, n_cand, elab, qp, ql, qv):
        @shard_map_nocheck(
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P(axis),
                      P(), P(), P(), P(), P(), P()),
            out_specs=P(axis),
        )
        def run(table, n_rows, row_off, n_keep, cand, n_cand, elab,
                qp, ql, qv):
            from repro.kernels.embed_join.ops import embed_join_emit_raw

            tab = table[0]
            nr = n_rows[0, 0]
            ro = row_off[0]
            nk = n_keep[0, 0]
            elab_cols = elab[:, cand]
            cv = jnp.arange(c_pad) < n_cand
            idx_map = jnp.zeros(out_cap, jnp.int32)
            for lo in range(0, pcap, rows_per):
                sl = tab[lo : lo + rows_per]
                rv = (jnp.arange(sl.shape[0]) + lo) < nr
                idx_map = embed_join_emit_raw(
                    idx_map, sl, rv, cand, cv, elab_cols, qp, ql, qv,
                    ro[lo : lo + sl.shape[0]], jnp.asarray(lo, jnp.int32),
                    use_kernel=use_kernel,
                )
            r_i = idx_map // c_pad
            c_i = idx_map - r_i * c_pad
            new = jnp.concatenate([tab[r_i], cand[c_i][:, None]], axis=1)
            ok = jnp.arange(out_cap) < nk
            return jnp.where(ok[:, None], new, 0)[None]

        return run(table, n_rows, row_off, n_keep, cand, n_cand, elab,
                   qp, ql, qv)

    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _enum_gather_fn(mesh: Mesh, axis: str):
    """Per-shard survivor gather for the host-assisted route: the uploaded
    index vectors address only shard-local rows, the table never crosses."""

    def fn(table, cand, r_idx, c_idx, n_keep):
        @shard_map_nocheck(
            mesh=mesh,
            in_specs=(P(axis), P(), P(axis), P(axis), P(axis)),
            out_specs=P(axis),
        )
        def run(table, cand, r_idx, c_idx, n_keep):
            tab = table[0]
            out_cap = r_idx.shape[1]
            new = jnp.concatenate(
                [tab[r_idx[0]], cand[c_idx[0]][:, None]], axis=1
            )
            ok = jnp.arange(out_cap) < n_keep[0, 0]
            return jnp.where(ok[:, None], new, 0)[None]

        return run(table, cand, r_idx, c_idx, n_keep)

    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _enum_exchange_fn(mesh: Mesh, axis: str, pcap_new: int):
    """Row-exchange collective behind the count-driven rebalancer.

    Repartitions the globally ordered row sequence (shard ``d`` owns global
    rows ``[old_off[d], old_off[d+1])``) onto new contiguous blocks: every
    shard gathers the table (one ``all_gather`` — the boundary-exchange
    idiom of the peeling rounds, here over rows instead of masks) and
    slices out exactly its new block by global row id.  Order-preserving by
    construction, which is what keeps rebalancing invisible to the
    bit-order contract.
    """
    n_shards = mesh.shape[axis]

    def fn(table, old_off, new_start, new_size):
        @shard_map_nocheck(
            mesh=mesh,
            in_specs=(P(axis), P(), P(), P()),
            out_specs=P(axis),
        )
        def run(table, old_off, new_start, new_size):
            me = jax.lax.axis_index(axis)
            tab = table[0]                                 # (pcap_old, t)
            pcap_old = tab.shape[0]
            gathered = jax.lax.all_gather(tab, axis)       # (D, pcap_old, t)
            g = new_start[me] + jnp.arange(pcap_new, dtype=jnp.int32)
            s = jnp.clip(
                jnp.searchsorted(old_off[1:], g, side="right"),
                0, n_shards - 1,
            )
            r = jnp.clip(g - old_off[s], 0, pcap_old - 1)
            rows = gathered[s, r]
            ok = jnp.arange(pcap_new) < new_size[me]
            return jnp.where(ok[:, None], rows, 0)[None]

        return run(table, old_off, new_start, new_size)

    return jax.jit(fn)
