"""Subgraph search over the ILGF-filtered graph (the paper's §3.3).

Three engines:

* ``host_dfs_search`` — Ullmann's recursive DFS (Algorithm 4/5) verbatim,
  in numpy.  This is the exactness oracle for tests and the faithful
  reproduction of the paper's search step.

* ``bfs_join_search`` — the TPU-native adaptation (DESIGN.md §3): a
  breadth-first *vectorized join*.  Partial embeddings live in a
  (rows × matched-so-far) table; one expansion step joins the table against
  the next query vertex's candidate list with a single batched
  adjacency/edge-label/injectivity test (MXU/VPU-friendly), then compacts
  survivors.  The jitted inner step has fixed shapes; a host loop chunks
  tables that outgrow the buffer (bounded memory, no recursion), and the
  result rows round-trip through the host every level.

* ``device_join_search`` — the device-resident variant (DESIGN.md §11-§12):
  the partial-embedding table lives on device across rounds, and each
  round is a two-phase GSI-style Prealloc-Combine join: a *count* pass
  (the ``kernels/embed_join`` count kernel on TPU, its jnp oracle
  elsewhere) sizes the output, an exclusive *scan* over the per-row counts
  assigns slots (on-device cumsum on the kernel path; host-assisted on
  XLA-CPU, where device scans are sequential), and an *emit* pass scatters
  each survivor into its slot in an exactly-sized lane-aligned buffer.
  Only a per-round scalar (the survivor total) syncs to the host, the
  buffer grows to the true survivor count — overflow is impossible, so
  there is no host-join fallback — and high-cardinality levels stay on
  device.

All three enumerate exactly the same embeddings (tested), under *any* valid
matching order — enumeration is order-invariant because every step checks
full adjacency/edge-label/injectivity constraints.  By default the order
follows the candidate-cardinality greedy rule (smallest |C(u)| first,
connected; ``greedy_matching_order``) — a global-pruning heuristic
consistent with the paper's discussion (§2.2) — and callers may pass an
explicit ``order`` (the cost-based planner, core/planner.py, does).
"""

from __future__ import annotations

import functools
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obsv
from repro.graphs.csr import Graph

# ---------------------------------------------------------------------------
# Matching order.
# ---------------------------------------------------------------------------


def greedy_matching_order(sizes, adj) -> list[int]:
    """Candidate-cardinality greedy matching order (§2.2 heuristic).

    Start at the smallest candidate set, then repeatedly take the
    smallest-|C(u)| vertex connected to the prefix (falling back to any
    remaining vertex only when the query is disconnected).  This is the
    single shared implementation of the rule both search engines used to
    inline — deduplicated, and *fixed* to break cardinality ties by
    smallest vertex id explicitly instead of inheriting whatever order a
    Python set happens to iterate in (identical in practice for small int
    sets, but now guaranteed, so orders are stable across interpreters).
    The planner (core/planner.py) reuses it as the no-stats fallback.

    ``sizes``: (U,) per-query-vertex candidate cardinalities;
    ``adj``: ``{u: {w: edge_label}}`` query adjacency.
    """
    sizes = np.asarray(sizes)
    n_q = int(sizes.shape[0])
    order: list[int] = [int(np.argmin(sizes))]
    remaining = [u for u in range(n_q) if u != order[0]]
    while remaining:
        connected = [u for u in remaining
                     if any(w in adj.get(u, {}) for w in order)]
        pool = connected if connected else remaining
        nxt = min(pool, key=lambda u: (sizes[u], u))
        order.append(nxt)
        remaining.remove(nxt)
    return order


def _as_order(order: Sequence[int], n_q: int) -> list[int]:
    """Validate a caller-supplied matching order (any permutation is legal)."""
    o = [int(u) for u in order]
    if sorted(o) != list(range(n_q)):
        raise ValueError(
            f"matching order must be a permutation of range({n_q}), got {o}"
        )
    return o


# ---------------------------------------------------------------------------
# Host DFS oracle (Ullmann subroutine, Algorithms 4-5).
# ---------------------------------------------------------------------------


def _host_adjacency(g: Graph):
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    elab = np.asarray(g.elabels)
    adj: dict[int, dict[int, int]] = {}
    for s, t, e in zip(src, dst, elab):
        adj.setdefault(int(s), {})[int(t)] = int(e)
    return adj


def host_dfs_search(
    data: Graph,
    query: Graph,
    candidates: np.ndarray,
    *,
    order: Sequence[int] | None = None,
    max_embeddings: int | None = None,
) -> np.ndarray:
    """All embeddings (rows = mappings, columns = query vertices).

    ``candidates``: (V, U) bool — C(u) columns from ILGF.  ``order``: an
    explicit matching order (any permutation of the query vertices; the
    planner supplies one); defaults to the greedy rule.
    """
    cand = np.asarray(candidates)
    n_q = query.vlabels.shape[0]
    d_adj = _host_adjacency(data)
    q_adj = _host_adjacency(query)

    if order is None:
        order = greedy_matching_order(cand.sum(axis=0), q_adj)
    else:
        order = _as_order(order, n_q)

    results: list[list[int]] = []
    mapping = [-1] * n_q
    used: set[int] = set()

    def neighbor_check(u: int, v: int) -> bool:
        # Algorithm 5: every matched query-neighbor must map to a data
        # neighbor with a matching edge label.
        for u2, el in q_adj.get(u, {}).items():
            v2 = mapping[u2]
            if v2 >= 0:
                got = d_adj.get(v, {}).get(v2)
                if got is None or got != el:
                    return False
        return True

    def rec(depth: int) -> bool:
        if max_embeddings is not None and len(results) >= max_embeddings:
            return True
        if depth == n_q:
            results.append(list(mapping))
            return False
        u = order[depth]
        for v in np.nonzero(cand[:, u])[0]:
            v = int(v)
            if v in used:
                continue
            if neighbor_check(u, v):
                mapping[u] = v
                used.add(v)
                if rec(depth + 1):
                    return True
                used.discard(v)
                mapping[u] = -1
        return False

    rec(0)
    return np.asarray(results, dtype=np.int64).reshape(-1, n_q)


# ---------------------------------------------------------------------------
# TPU breadth-first join engine.
# ---------------------------------------------------------------------------


def _dense_edge_labels(g: Graph, n: int) -> np.ndarray:
    """(n, n) int32 matrix: edge label, or -1 if no edge."""
    m = -np.ones((n, n), dtype=np.int32)
    m[np.asarray(g.src), np.asarray(g.dst)] = np.asarray(g.elabels)
    return m


@functools.partial(jax.jit, static_argnames=("n_prev",))
def _expand_step(
    table: jnp.ndarray,       # (R, n_prev) int32 partial embeddings
    row_valid: jnp.ndarray,   # (R,) bool
    cand_list: jnp.ndarray,   # (C,) int32 candidate data vertices for u_t
    cand_valid: jnp.ndarray,  # (C,) bool
    elab_matrix: jnp.ndarray,  # (N, N) int32 data edge labels (-1 = none)
    q_nbr_pos: jnp.ndarray,   # (J,) int32 positions (<t) of matched q-neighbors
    q_nbr_lab: jnp.ndarray,   # (J,) int32 required edge labels
    q_nbr_valid: jnp.ndarray,  # (J,) bool
    n_prev: int,
):
    """One join step: (R × C) validity matrix.

    valid[r, c] ⇔ row r valid ∧ cand c valid
                  ∧ ∀ matched q-neighbors j: elab(data)[table[r, pos_j], cand_c] == lab_j
                  ∧ cand_c ∉ table[r, :]        (injectivity)
    """
    # adjacency + edge-label checks: gather (R, J) mapped neighbor ids
    mapped = jnp.take_along_axis(
        table, jnp.broadcast_to(q_nbr_pos[None, :], (table.shape[0], q_nbr_pos.shape[0])),
        axis=1,
    )  # (R, J)
    got = elab_matrix[mapped[:, :, None], cand_list[None, None, :]]  # (R, J, C)
    lab_ok = got == q_nbr_lab[None, :, None]
    lab_ok = lab_ok | ~q_nbr_valid[None, :, None]
    adj_ok = jnp.all(lab_ok, axis=1)  # (R, C)
    inj_ok = jnp.all(table[:, :, None] != cand_list[None, None, :], axis=1)
    valid = adj_ok & inj_ok & row_valid[:, None] & cand_valid[None, :]
    return valid


def _expand_step_np(chunk, cand_ids, elab_np, q_pos, q_lab, q_val):
    """Numpy twin of _expand_step for small (R·C·J) frontiers.

    Tiny join levels are dominated by host→device transfer overhead, not
    compute — evaluating them directly in numpy keeps the device for the
    large tables where the jitted kernel actually wins.
    """
    mapped = chunk[:, q_pos]                                   # (R, J)
    got = elab_np[mapped[:, :, None], cand_ids[None, None, :]]  # (R, J, C)
    lab_ok = (got == q_lab[None, :, None]) | ~q_val[None, :, None]
    adj_ok = lab_ok.all(axis=1)                                # (R, C)
    inj_ok = (chunk[:, :, None] != cand_ids[None, None, :]).all(axis=1)
    return adj_ok & inj_ok


# below this many (R·C·J) cells a join level runs on host numpy
_HOST_JOIN_CELLS = 1 << 18


def _level_constraints(q_adj, pos_of, u: int, t: int):
    """Matched-neighbor constraint arrays for join level ``t`` (vertex u).

    Returns (q_pos, q_lab, q_val): positions (< t) of already-matched query
    neighbors, their required edge labels, and a validity mask (at least one
    inert row is kept so shapes never collapse to zero)."""
    nbrs = [(pos_of[w], el) for w, el in q_adj.get(u, {}).items()
            if pos_of[w] < t]
    j = max(1, len(nbrs))
    q_pos = np.zeros(j, dtype=np.int32)
    q_lab = np.zeros(j, dtype=np.int32)
    q_val = np.zeros(j, dtype=bool)
    for k, (p, el) in enumerate(nbrs):
        q_pos[k], q_lab[k], q_val[k] = p, el, True
    return q_pos, q_lab, q_val


def _host_join_level(table, cand_ids, elab_np, elab_matrix,
                     q_pos, q_lab, q_val, chunk_rows: int, t: int):
    """One chunked host join level (the classic bfs_join inner loop).

    Returns ``(new_table, elab_matrix)`` — the survivor table of width
    ``t + 1`` and the lazily-created device copy of the edge-label matrix
    (made on the first chunk large enough for the jitted path)."""
    new_rows: list[np.ndarray] = []
    c_pad = int(2 ** np.ceil(np.log2(max(cand_ids.size, 1))))
    cand_pad = np.zeros(c_pad, dtype=np.int32)
    cand_pad[: cand_ids.size] = cand_ids
    cand_ok = np.zeros(c_pad, dtype=bool)
    cand_ok[: cand_ids.size] = True

    for lo in range(0, table.shape[0], chunk_rows):
        chunk = table[lo : lo + chunk_rows]
        r = chunk.shape[0]
        if r * cand_ids.size * q_pos.size <= _HOST_JOIN_CELLS:
            valid_np = _expand_step_np(
                chunk, cand_ids, elab_np, q_pos, q_lab, q_val
            )
            r_idx, c_idx = np.nonzero(valid_np)
            if r_idx.size:
                new_rows.append(np.concatenate(
                    [chunk[r_idx], cand_ids[c_idx][:, None]], axis=1
                ))
            continue
        # pad rows to the next power of two so _expand_step revisits
        # O(log chunk_rows) traces instead of one per exact row count
        r_pad = int(2 ** np.ceil(np.log2(max(r, 1))))
        if r_pad > r:
            chunk = np.concatenate(
                [chunk, np.zeros((r_pad - r, chunk.shape[1]), chunk.dtype)]
            )
        if elab_matrix is None:
            elab_matrix = jnp.asarray(elab_np)
        valid = _expand_step(
            jnp.asarray(chunk),
            jnp.arange(r_pad) < r,
            jnp.asarray(cand_pad),
            jnp.asarray(cand_ok),
            elab_matrix,
            jnp.asarray(q_pos),
            jnp.asarray(q_lab),
            jnp.asarray(q_val),
            t,
        )
        r_idx, c_idx = np.nonzero(np.asarray(valid))
        if r_idx.size:
            rows = np.concatenate(
                [chunk[r_idx], cand_pad[c_idx][:, None]], axis=1
            )
            new_rows.append(rows)
    new_table = (
        np.concatenate(new_rows, axis=0)
        if new_rows
        else np.zeros((0, t + 1), dtype=np.int32)
    )
    return new_table, elab_matrix


def bfs_join_search(
    data: Graph,
    query: Graph,
    candidates: np.ndarray,
    *,
    order: Sequence[int] | None = None,
    chunk_rows: int = 8192,
    max_embeddings: int | None = None,
) -> np.ndarray:
    """Enumerate all embeddings with the vectorized join plan.

    Host-side orchestration keeps the result set (it is host data by
    definition); every *large* O(R·C·J) validity evaluation is jitted, and
    small levels run directly in numpy (transfer-overhead-bound regime).
    ``order``: explicit matching order (see ``host_dfs_search``).
    """
    cand = np.asarray(candidates)
    n_q = query.vlabels.shape[0]
    n_d = data.vlabels.shape[0]
    q_adj = _host_adjacency(query)
    elab_np = _dense_edge_labels(data, n_d)
    elab_matrix = None  # device copy made lazily on first jitted level

    if order is None:
        order = greedy_matching_order(cand.sum(axis=0), q_adj)
    else:
        order = _as_order(order, n_q)
    pos_of = {u: i for i, u in enumerate(order)}

    # seed table with u_0's candidates
    table = np.nonzero(cand[:, order[0]])[0].astype(np.int32).reshape(-1, 1)

    for t in range(1, n_q):
        u = order[t]
        cand_ids = np.nonzero(cand[:, u])[0].astype(np.int32)
        q_pos, q_lab, q_val = _level_constraints(q_adj, pos_of, u, t)
        if table.shape[0] == 0 or cand_ids.size == 0:
            return np.zeros((0, n_q), dtype=np.int64)
        table, elab_matrix = _host_join_level(
            table, cand_ids, elab_np, elab_matrix,
            q_pos, q_lab, q_val, chunk_rows, t,
        )
    # truncation happens after the final level (covers single-vertex
    # queries, whose seed table never enters the loop)
    if max_embeddings is not None and table.shape[0] > max_embeddings:
        table = table[:max_embeddings]
    return _restore_query_order(table, order)


def _restore_query_order(table: np.ndarray, order: Sequence[int]) -> np.ndarray:
    """Table columns are in matching order; restore query-vertex order."""
    n_q = len(order)
    out = np.zeros((table.shape[0], n_q), dtype=np.int64)
    for i, u in enumerate(order):
        out[:, u] = table[:, i]
    return out


# ---------------------------------------------------------------------------
# Device-resident join engine (DESIGN.md §11-§12).
# ---------------------------------------------------------------------------


# per-dispatch (R·C·J) validity-cell budget: bounds the grid (and its
# (R, J, C) gather intermediate) exactly like chunk_rows bounds the host path
_DEVICE_JOIN_CELLS = 1 << 24


def _align_rows(n: int) -> int:
    """Lane-aligned (multiple-of-128) row allocation for ``n`` live rows.

    The two-phase join sizes every table buffer to the *true* survivor
    count rounded up to the VPU lane width — at most 127 inert rows ride
    along, versus the up-to-2x waste (and overflow fallback) of the old
    pow2 capacity cap."""
    return max(128, -(-int(n) // 128) * 128)


def empty_enum_report() -> dict:
    """The zeroed two-phase telemetry schema the device joins fill.

    Every exit path (empty seed set, single-vertex query, truncation,
    filter-killed queries) leaves exactly these keys in ``report`` /
    ``stats.extras["enum"]``:

    * ``device_rounds`` — expansion rounds executed (all on device);
    * ``host_levels``   — always 0 since the chunked host fallback was
      removed (kept so dashboards and the CI canary can assert on it);
    * ``count_seconds`` / ``scan_seconds`` / ``emit_seconds`` — per-phase
      wall-clock totals across rounds;
    * ``max_table_rows`` — peak true survivor count over all levels,
      summed across shards;
    * ``max_emit_rows``  — peak allocated emit-buffer rows (lane-aligned
      exact sizing, × ``enum_shards`` uniform SPMD blocks when sharded);
    * ``scan_path``     — ``"device"`` (kernel path: on-device cumsum) or
      ``"host"`` (XLA-CPU: host-assisted scan), ``None`` if no round ran;
    * ``enum_shards``   — mesh shards the table was partitioned over
      (1 = single-device ``device_join_search``, 0 = no enumeration ran);
    * ``emit_rows_max`` / ``emit_rows_min`` — per-shard emitted-row
      extremes at the heaviest level (their gap is the residual load
      imbalance the rebalancer could not remove; equal when
      ``enum_shards == 1``);
    * ``rebalance_rounds`` / ``rebalance_rows_moved`` /
      ``rebalance_seconds`` — count-driven rebalancer activity
      (levels repartitioned, parent rows exchanged, wall-clock cost);
    * ``levels``        — per-level records ``{"level", "emit_rows":
      [per-shard rows], "rebalanced", "rebalance_seconds"}`` backing the
      bench JSON's per-level rebalance timings;
    * ``host_syncs``    — points where the join's host code blocked on a
      device value, counted where each one ran:

      - the per-phase ``block_until_ready`` after count (single-device
        kernel path) and after emit (every path), taken only with
        ``report`` — the phase timings need them;
      - the survivor total of a level: the scalar ``int(inclusive[-1])``
        (single-device kernel path) or the (D,) per-shard totals
        ``np.asarray(totals_j)`` (sharded kernel path);
      - the (D, pcap) counts the sharded rebalancer pulls to recut;
      - each validity bitmask of the host-assisted scan:
        ``np.asarray(valid)`` per row slice, ``np.asarray(valid_j)`` per
        level when sharded;
      - the final table readback (not taken when a level leaves no row).
    """
    # generated from the typed schema of record (obsv.reports.EnumReport)
    # so the searcher-side plain dict and the stats.extras dataclass can
    # never drift apart
    return obsv.EnumReport.empty().to_dict()


def _level_record(level: int, emit_rows, *, rebalanced: bool = False,
                  rebalance_seconds: float = 0.0) -> dict:
    """One ``stats["levels"]`` entry (see ``empty_enum_report``)."""
    return {
        "level": level,
        "emit_rows": [int(x) for x in emit_rows],
        "rebalanced": rebalanced,
        "rebalance_seconds": rebalance_seconds,
    }


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def _device_join_valid(
    table: jnp.ndarray,      # (R, T) int32 — pow2-padded embedding rows
    n_rows: jnp.ndarray,     # () int32 — live rows (prefix of the buffer)
    cand: jnp.ndarray,       # (C,) int32 — pow2-padded candidate list
    n_cand: jnp.ndarray,     # () int32 — live candidates
    elab_matrix: jnp.ndarray,  # (N, N) int32 data edge labels (−1 = none)
    q_pos: jnp.ndarray,      # (J,) int32
    q_lab: jnp.ndarray,      # (J,) int32
    q_val: jnp.ndarray,      # (J,) bool
    *,
    use_kernel: bool,
):
    """(R, C) bool validity grid for one expansion round, in one dispatch.

    ``use_kernel=True`` routes through the fused Pallas embed-join kernel
    (its BlockSpecs tile the candidate-restricted (N, C) adjacency view);
    otherwise the oracle math runs as the same two-axis gather the chunked
    host fallback jits (``_expand_step``), so both regimes share one
    validity implementation."""
    r = table.shape[0]
    c = cand.shape[0]
    row_valid = jnp.arange(r) < n_rows
    cand_valid = jnp.arange(c) < n_cand
    if use_kernel:
        from repro.kernels.embed_join.ops import embed_join

        elab_cols = elab_matrix[:, cand]
        return embed_join(
            table, row_valid, cand, cand_valid, elab_cols,
            q_pos, q_lab, q_val, use_kernel=True,
        )
    return _expand_step(
        table, row_valid, cand, cand_valid, elab_matrix,
        q_pos, q_lab, q_val, table.shape[1],
    )


@functools.partial(jax.jit, static_argnames=("out_cap",))
def _device_join_gather(
    table: jnp.ndarray,   # (R, T) int32 — resident old table
    cand: jnp.ndarray,    # (C,) int32
    r_idx: jnp.ndarray,   # (out_cap,) int32 — survivor rows (host-compacted)
    c_idx: jnp.ndarray,   # (out_cap,) int32 — survivor candidates
    n_keep: jnp.ndarray,  # () int32
    *,
    out_cap: int,
):
    """Build the next pow2-padded table by gathering from the resident one."""
    new_table = jnp.concatenate(
        [table[r_idx], cand[c_idx][:, None]], axis=1
    )
    slot_ok = jnp.arange(out_cap) < n_keep
    return jnp.where(slot_ok[:, None], new_table, 0)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def _device_join_count(
    table: jnp.ndarray,      # (R, T) int32 — table slice
    n_rows: jnp.ndarray,     # () int32 — live rows in this slice
    cand: jnp.ndarray,       # (C,) int32
    n_cand: jnp.ndarray,     # () int32
    elab_matrix: jnp.ndarray,  # (N, N) int32
    q_pos: jnp.ndarray,
    q_lab: jnp.ndarray,
    q_val: jnp.ndarray,
    *,
    use_kernel: bool,
):
    """(R,) int32 per-row survivor counts — the *count* pass, no writes.

    On the kernel path the row-sum folds inside the Pallas grid loop
    (``embed_join_count``) so the (R, C) grid never materializes; the
    oracle reduces the same ref grid the emit pass re-evaluates."""
    from repro.kernels.embed_join.ops import embed_join_count

    r = table.shape[0]
    c = cand.shape[0]
    row_valid = jnp.arange(r) < n_rows
    cand_valid = jnp.arange(c) < n_cand
    elab_cols = elab_matrix[:, cand]
    return embed_join_count(
        table, row_valid, cand, cand_valid, elab_cols,
        q_pos, q_lab, q_val, use_kernel=use_kernel,
    )


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def _device_join_emit(
    idx_map: jnp.ndarray,    # (out_cap,) int32 — slot → flat cell id
    table: jnp.ndarray,      # (R, T) int32 — table slice
    n_rows: jnp.ndarray,     # () int32 — live rows in this slice
    cand: jnp.ndarray,       # (C,) int32
    n_cand: jnp.ndarray,     # () int32
    elab_matrix: jnp.ndarray,  # (N, N) int32
    q_pos: jnp.ndarray,
    q_lab: jnp.ndarray,
    q_val: jnp.ndarray,
    row_off: jnp.ndarray,    # (R,) int32 — this slice's exclusive-scan slots
    row_base: jnp.ndarray,   # () int32 — slice's first row in the table
    *,
    use_kernel: bool,
):
    """One *emit* slice: scatter survivors into their exact output slots.

    Each survivor (r, c) lands at ``row_off[r] + rank-within-row`` — the
    flat row-major survivor order, i.e. exactly the host engine's
    chunk-sequential ``np.nonzero`` order, which is what keeps
    ``max_embeddings`` truncation bit-identical across engines."""
    from repro.kernels.embed_join.ops import embed_join_emit

    r = table.shape[0]
    c = cand.shape[0]
    row_valid = jnp.arange(r) < n_rows
    cand_valid = jnp.arange(c) < n_cand
    elab_cols = elab_matrix[:, cand]
    return embed_join_emit(
        idx_map, table, row_valid, cand, cand_valid, elab_cols,
        q_pos, q_lab, q_val, row_off, row_base, use_kernel=use_kernel,
    )


@functools.partial(jax.jit, static_argnames=("out_cap",))
def _device_join_emit_gather(
    table: jnp.ndarray,    # (R, T) int32 — resident old table
    cand: jnp.ndarray,     # (C,) int32
    idx_map: jnp.ndarray,  # (out_cap,) int32 — flat cell id per slot
    n_keep: jnp.ndarray,   # () int32 — true survivor total
    *,
    out_cap: int,
):
    """Decode the emitted cell-id map and build the exactly-sized table.

    ``idx_map`` slots past ``n_keep`` hold the zero-init value (cell 0 —
    a valid address, junk data) and are zeroed by the slot mask; they are
    only the ≤ 127 lane-alignment rows."""
    c = cand.shape[0]
    r_idx = idx_map // c
    c_idx = idx_map - r_idx * c
    return _device_join_gather(
        table, cand, r_idx, c_idx, n_keep, out_cap=out_cap
    )


def device_join_search(
    data: Graph,
    query: Graph,
    candidates: np.ndarray,
    *,
    order: Sequence[int] | None = None,
    max_embeddings: int | None = None,
    use_kernel: bool | None = None,
    report: dict | None = None,
) -> np.ndarray:
    """Enumerate all embeddings with the two-phase device-resident join.

    Bit-identical to ``bfs_join_search`` (same embeddings, same row order,
    any valid ``order``), but the partial-embedding table stays on device
    between rounds and every level runs as a GSI-style Prealloc-Combine
    join (DESIGN.md §12):

    1. **count** — per-row survivor counts from the fused validity grid
       (cell-budgeted dispatches; the Pallas count kernel folds the
       row-sum in-core on TPU), no table writes;
    2. **scan**  — an exclusive prefix sum over the counts turns them into
       output slots.  Backend-adaptive: on the kernel path the cumsum runs
       on device and only the *total* syncs back as one scalar; on XLA-CPU
       — where device scans lower to sequential code — the per-slice
       validity bitmask comes back and numpy performs the scan (the
       host-assisted compaction machinery, DESIGN.md §11);
    3. **emit**  — survivors scatter into their prefix-summed slots in an
       exactly-sized, lane-aligned (multiple-of-128) output buffer.

    Because the emit buffer is sized to the *true* survivor count,
    overflow is impossible and the per-level chunked-host-join fallback of
    the original engine is gone: every level of every workload runs on
    device, memory tracks the real table size (≤ 127 alignment rows of
    slack), and high-cardinality levels — precisely where the old engine
    abandoned the device — stay fused.

    ``use_kernel``: None = auto (Pallas kernels + on-device scan on TPU,
    oracle + host-assisted scan elsewhere); True forces the kernel path
    (interpret mode off-TPU — parity testing); False forces the oracle.
    ``report``: optional dict filled with the ``empty_enum_report()``
    telemetry schema (phase timings, exact-sizing ceilings); phase timings
    force a device sync per phase, so pass ``report=None`` on
    latency-critical calls.  (The old capacity knobs ``device_rows`` /
    ``chunk_rows``, deprecated when the two-phase join removed the buffer
    cap, are gone.)

    With an active ``obsv`` tracer, each level emits ``enum.count`` /
    ``enum.scan`` / ``enum.emit`` spans carrying a ``level`` attribute.
    """
    cand = np.asarray(candidates)
    n_q = query.vlabels.shape[0]
    n_d = data.vlabels.shape[0]
    q_adj = _host_adjacency(query)
    # lane-aligned vertex axis: filtered graphs of nearby sizes share one
    # compiled program per level shape (ids >= n_d are never addressed)
    elab_np = _dense_edge_labels(data, _align_rows(n_d))
    elab_dev = None

    if order is None:
        order = greedy_matching_order(cand.sum(axis=0), q_adj)
    else:
        order = _as_order(order, n_q)
    pos_of = {u: i for i, u in enumerate(order)}

    kernel_on = (use_kernel if use_kernel is not None
                 else jax.default_backend() == "tpu")
    stats = empty_enum_report()
    stats["enum_shards"] = 1
    stats["scan_path"] = "device" if kernel_on else "host"
    if report is not None:
        report.update(stats)

    seed_ids = np.nonzero(cand[:, order[0]])[0].astype(np.int32)
    n_rows = int(seed_ids.size)
    r0 = _align_rows(n_rows)
    table_dev = jnp.asarray(
        np.pad(seed_ids, (0, r0 - n_rows)).reshape(r0, 1)
    )
    stats["max_table_rows"] = n_rows
    stats["max_emit_rows"] = r0
    stats["emit_rows_max"] = n_rows
    stats["emit_rows_min"] = n_rows

    for t in range(1, n_q):
        u = order[t]
        cand_ids = np.nonzero(cand[:, u])[0].astype(np.int32)
        if n_rows == 0 or cand_ids.size == 0:
            if report is not None:
                report.update(stats)
            return np.zeros((0, n_q), dtype=np.int64)
        q_pos, q_lab, q_val = _level_constraints(q_adj, pos_of, u, t)

        # lane-aligned candidate pad (multiple of 128): ≤ 127 wasted
        # columns per round instead of pow2's up-to-2x, at a bounded
        # cost in extra trace shapes
        c_pad = max(128, -(-cand_ids.size // 128) * 128)
        if elab_dev is None:
            elab_dev = jnp.asarray(elab_np)
        j = int(q_pos.size)
        cand_dev = jnp.asarray(
            np.pad(cand_ids, (0, c_pad - cand_ids.size))
        )
        n_cand_dev = jnp.asarray(cand_ids.size, jnp.int32)
        qp, ql, qv = map(jnp.asarray, (q_pos, q_lab, q_val))
        stats["device_rounds"] += 1

        # cell-budgeted row slices bound each dispatch's (R, C, J) grid;
        # the table allocation is a multiple of 128, so every clipped
        # slice shape stays lane-aligned
        rows_per = _DEVICE_JOIN_CELLS // max(1, c_pad * j)
        rows_per = max(256, 1 << max(0, rows_per.bit_length() - 1))
        rows_per = min(rows_per, 4096)
        active = table_dev

        if kernel_on:
            # -- count: fused kernel dispatches, only (R,) ints produced
            t0 = time.perf_counter()
            parts = []
            for lo in range(0, n_rows, rows_per):
                sl = active[lo : lo + rows_per]
                n_live = jnp.asarray(min(n_rows - lo, rows_per), jnp.int32)
                parts.append(_device_join_count(
                    sl, n_live, cand_dev, n_cand_dev, elab_dev,
                    qp, ql, qv, use_kernel=True,
                ))
            counts = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            if report is not None:
                counts.block_until_ready()
                stats["host_syncs"] += 1
            t1 = time.perf_counter()
            stats["count_seconds"] += t1 - t0
            obsv.span_at("enum.count", t0, t1, level=t, rows=n_rows)

            # -- scan: on-device exclusive prefix sum; one scalar syncs
            t0 = time.perf_counter()
            inclusive = jnp.cumsum(counts)
            row_off = inclusive - counts
            total = int(inclusive[-1])
            stats["host_syncs"] += 1
            t1 = time.perf_counter()
            stats["scan_seconds"] += t1 - t0
            obsv.span_at("enum.scan", t0, t1, level=t)

            if total == 0:
                table_dev = jnp.zeros((1, t + 1), jnp.int32)
                n_rows = 0
                stats["levels"].append(_level_record(t, [0]))
                continue

            # -- emit: scatter survivors into the exactly-sized buffer
            t0 = time.perf_counter()
            out_cap = _align_rows(total)
            idx_map = jnp.zeros(out_cap, jnp.int32)
            for lo in range(0, n_rows, rows_per):
                sl = active[lo : lo + rows_per]
                n_live = jnp.asarray(min(n_rows - lo, rows_per), jnp.int32)
                idx_map = _device_join_emit(
                    idx_map, sl, n_live, cand_dev, n_cand_dev, elab_dev,
                    qp, ql, qv, row_off[lo : lo + sl.shape[0]],
                    jnp.asarray(lo, jnp.int32), use_kernel=True,
                )
            table_dev = _device_join_emit_gather(
                active, cand_dev, idx_map,
                jnp.asarray(total, jnp.int32), out_cap=out_cap,
            )
            if report is not None:
                table_dev.block_until_ready()
                stats["host_syncs"] += 1
            t1 = time.perf_counter()
            stats["emit_seconds"] += t1 - t0
            obsv.span_at("enum.emit", t0, t1, level=t, rows=total)
        else:
            # host-assisted scan (XLA-CPU): the validity grid is evaluated
            # in cell-budgeted fused dispatches and only the 1-byte
            # bitmask comes back; numpy's nonzero *is* the count + scan
            # (survivor indices arrive already in flat row-major order)
            t0 = time.perf_counter()
            r_list, c_list = [], []
            for lo in range(0, n_rows, rows_per):
                sl = active[lo : lo + rows_per]
                n_live = min(n_rows - lo, rows_per)
                valid = _device_join_valid(
                    sl, jnp.asarray(n_live, jnp.int32), cand_dev,
                    n_cand_dev, elab_dev, qp, ql, qv, use_kernel=False,
                )
                ri, ci = np.nonzero(np.asarray(valid))
                stats["host_syncs"] += 1
                if ri.size:
                    r_list.append(ri.astype(np.int32) + np.int32(lo))
                    c_list.append(ci.astype(np.int32))
            t1 = time.perf_counter()
            stats["count_seconds"] += t1 - t0
            obsv.span_at("enum.count", t0, t1, level=t, rows=n_rows)

            t0 = time.perf_counter()
            total = sum(r.size for r in r_list)
            if total == 0:
                t1 = time.perf_counter()
                stats["scan_seconds"] += t1 - t0
                obsv.span_at("enum.scan", t0, t1, level=t)
                table_dev = jnp.zeros((1, t + 1), jnp.int32)
                n_rows = 0
                stats["levels"].append(_level_record(t, [0]))
                continue
            out_cap = _align_rows(total)
            r_idx = np.zeros(out_cap, np.int32)
            c_idx = np.zeros(out_cap, np.int32)
            r_idx[:total] = np.concatenate(r_list)
            c_idx[:total] = np.concatenate(c_list)
            t1 = time.perf_counter()
            stats["scan_seconds"] += t1 - t0
            obsv.span_at("enum.scan", t0, t1, level=t)

            # emit: index upload + one on-device gather into the
            # exactly-sized buffer — the table itself never crosses
            t0 = time.perf_counter()
            table_dev = _device_join_gather(
                active, cand_dev, jnp.asarray(r_idx), jnp.asarray(c_idx),
                jnp.asarray(total, jnp.int32), out_cap=out_cap,
            )
            if report is not None:
                table_dev.block_until_ready()
                stats["host_syncs"] += 1
            t1 = time.perf_counter()
            stats["emit_seconds"] += t1 - t0
            obsv.span_at("enum.emit", t0, t1, level=t, rows=total)

        n_rows = total
        stats["max_table_rows"] = max(stats["max_table_rows"], total)
        stats["max_emit_rows"] = max(stats["max_emit_rows"], out_cap)
        stats["levels"].append(_level_record(t, [total]))
        if total > stats["emit_rows_max"]:
            stats["emit_rows_max"] = total
            stats["emit_rows_min"] = total

    n_keep = n_rows
    if max_embeddings is not None:
        n_keep = min(n_keep, max_embeddings)
    table = np.asarray(table_dev[:n_keep])
    stats["host_syncs"] += 1
    if report is not None:
        report.update(stats)
    return _restore_query_order(table, order)


# ---------------------------------------------------------------------------
# Mesh-partitioned device enumeration (DESIGN.md §13).
# ---------------------------------------------------------------------------


def sharded_device_join_search(
    data: Graph,
    query: Graph,
    candidates: np.ndarray,
    *,
    mesh,
    axis: str = "data",
    order: Sequence[int] | None = None,
    max_embeddings: int | None = None,
    use_kernel: bool | None = None,
    report: dict | None = None,
    rebalance_threshold: float = 1.25,
) -> np.ndarray:
    """``device_join_search`` partitioned across a device mesh.

    Bit-identical to the single-device two-phase join (same rows, same
    order, same ``max_embeddings`` truncation prefix) at any shard count:
    the partial-embedding table is split by row into one *contiguous
    block per shard, in shard order* — children of contiguous parents are
    contiguous in the global flat row-major survivor order, so
    concatenating the per-shard live prefixes reproduces the
    single-device row order exactly, level after level.  Each count →
    scan → emit phase runs per shard under ``shard_map``
    (core/distributed.py) against replicated candidate / edge-label
    slices; the only per-level host sync on the kernel path is the (D,)
    per-shard survivor totals, which double as the deterministic
    shard-offset prefix for the next level's global row numbering.

    Because the count phase prices every parent row's emit for free, a
    **count-driven rebalancer** runs between count and emit: when the
    heaviest shard's emit total exceeds ``rebalance_threshold ×`` the
    mean, parent rows are recut into weight-balanced contiguous blocks
    (``enum_row_blocks``) and exchanged with one ``all_gather``
    collective — order-preserving, so rebalancing is invisible to the
    bit-order contract.  Balanced blocks are also what keep the uniform
    SPMD buffer shapes (every shard allocates the max block's rows)
    tight instead of skew-inflated.

    ``mesh`` / ``axis``: the device mesh and axis name to shard over
    (``core.distributed.device_mesh``).  ``use_kernel`` / ``report`` as
    in ``device_join_search``; the report additionally carries the shard
    fields of ``empty_enum_report()``.
    """
    from repro.core.distributed import (
        _enum_count_fn,
        _enum_emit_fn,
        _enum_exchange_fn,
        _enum_gather_fn,
        _enum_valid_fn,
        enum_row_blocks,
    )

    n_shards = int(mesh.shape[axis])
    cand = np.asarray(candidates)
    n_q = query.vlabels.shape[0]
    n_d = data.vlabels.shape[0]
    q_adj = _host_adjacency(query)
    # lane-aligned vertex axis: filtered graphs of nearby sizes share one
    # compiled program per level shape (ids >= n_d are never addressed)
    elab_np = _dense_edge_labels(data, _align_rows(n_d))
    elab_dev = None

    if order is None:
        order = greedy_matching_order(cand.sum(axis=0), q_adj)
    else:
        order = _as_order(order, n_q)
    pos_of = {u: i for i, u in enumerate(order)}

    kernel_on = (use_kernel if use_kernel is not None
                 else jax.default_backend() == "tpu")
    stats = empty_enum_report()
    stats["enum_shards"] = n_shards
    stats["scan_path"] = "device" if kernel_on else "host"
    if report is not None:
        report.update(stats)

    # seed: equal-rows contiguous blocks of u_0's candidate list
    seed_ids = np.nonzero(cand[:, order[0]])[0].astype(np.int32)
    total = int(seed_ids.size)
    bounds = enum_row_blocks(np.ones(total, np.int64), n_shards)
    sizes = np.diff(bounds).astype(np.int64)
    pcap = _align_rows(int(sizes.max()))
    table_h = np.zeros((n_shards, pcap, 1), np.int32)
    for i in range(n_shards):
        table_h[i, : sizes[i], 0] = seed_ids[bounds[i] : bounds[i + 1]]
    table_j = table_h  # device placement happens on the first sharded call
    n_rows_j = jnp.asarray(sizes.reshape(n_shards, 1).astype(np.int32))
    stats["max_table_rows"] = total
    stats["max_emit_rows"] = n_shards * pcap
    stats["emit_rows_max"] = int(sizes.max())
    stats["emit_rows_min"] = int(sizes.min())

    for t in range(1, n_q):
        u = order[t]
        cand_ids = np.nonzero(cand[:, u])[0].astype(np.int32)
        if total == 0 or cand_ids.size == 0:
            if report is not None:
                report.update(stats)
            return np.zeros((0, n_q), dtype=np.int64)
        q_pos, q_lab, q_val = _level_constraints(q_adj, pos_of, u, t)
        j = int(q_pos.size)
        c_pad = max(128, -(-cand_ids.size // 128) * 128)
        if elab_dev is None:
            elab_dev = jnp.asarray(elab_np)
        cand_dev = jnp.asarray(np.pad(cand_ids, (0, c_pad - cand_ids.size)))
        n_cand_dev = jnp.asarray(cand_ids.size, jnp.int32)
        qp, ql, qv = map(jnp.asarray, (q_pos, q_lab, q_val))
        stats["device_rounds"] += 1
        rebalanced = False
        rebal_dt = 0.0

        if kernel_on:
            # -- count (scan fused on device): only (D,) totals sync back
            t0 = time.perf_counter()
            count_fn = _enum_count_fn(mesh, axis, pcap, c_pad, j, True)
            counts_j, row_off_j, totals_j = count_fn(
                table_j, n_rows_j, cand_dev, n_cand_dev, elab_dev,
                qp, ql, qv,
            )
            shard_tot = np.asarray(totals_j).astype(np.int64)
            stats["host_syncs"] += 1
            t1 = time.perf_counter()
            stats["count_seconds"] += t1 - t0
            obsv.span_at("enum.count", t0, t1, level=t, rows=total,
                         shards=n_shards)

            t0 = time.perf_counter()
            new_total = int(shard_tot.sum())
            if new_total == 0:
                t1 = time.perf_counter()
                stats["scan_seconds"] += t1 - t0
                obsv.span_at("enum.scan", t0, t1, level=t)
                total = 0
                sizes = np.zeros(n_shards, np.int64)
                stats["levels"].append(_level_record(t, [0] * n_shards))
                continue

            # -- rebalance: recut parents by exact child weights when the
            # heaviest shard's emit exceeds the threshold over the mean
            if (n_shards > 1
                    and shard_tot.max() * n_shards
                    > rebalance_threshold * new_total):
                t_r = time.perf_counter()
                counts_h = np.asarray(counts_j)  # (D, pcap) — pulled only now
                stats["host_syncs"] += 1
                weights = np.concatenate(
                    [counts_h[i, : sizes[i]] for i in range(n_shards)]
                )
                new_bounds = enum_row_blocks(weights, n_shards)
                if not np.array_equal(new_bounds, bounds):
                    new_sizes = np.diff(new_bounds).astype(np.int64)
                    pcap_new = _align_rows(int(new_sizes.max()))
                    exchange_fn = _enum_exchange_fn(mesh, axis, pcap_new)
                    table_j = exchange_fn(
                        table_j,
                        jnp.asarray(bounds.astype(np.int32)),
                        jnp.asarray(new_bounds[:-1].astype(np.int32)),
                        jnp.asarray(new_sizes.astype(np.int32)),
                    )
                    # host re-derives per-shard counts/offsets from the
                    # global weights — no device recount needed
                    row_off_h = np.zeros((n_shards, pcap_new), np.int32)
                    for i in range(n_shards):
                        w = weights[new_bounds[i] : new_bounds[i + 1]]
                        row_off_h[i, : w.size] = np.cumsum(w) - w
                        shard_tot[i] = w.sum()
                    row_off_j = jnp.asarray(row_off_h)
                    moved = int(sum(
                        max(0, new_sizes[i]
                            - max(0, min(new_bounds[i + 1], bounds[i + 1])
                                  - max(new_bounds[i], bounds[i])))
                        for i in range(n_shards)
                    ))
                    bounds, sizes, pcap = new_bounds, new_sizes, pcap_new
                    n_rows_j = jnp.asarray(
                        sizes.reshape(n_shards, 1).astype(np.int32)
                    )
                    rebalanced = True
                    rebal_dt = time.perf_counter() - t_r
                    stats["rebalance_rounds"] += 1
                    stats["rebalance_rows_moved"] += moved
                    stats["rebalance_seconds"] += rebal_dt
                    obsv.span_at("enum.rebalance", t_r, t_r + rebal_dt,
                                 level=t, rows_moved=moved)
            t1 = time.perf_counter()
            stats["scan_seconds"] += t1 - t0 - rebal_dt
            obsv.span_at("enum.scan", t0, t1, level=t)

            # -- emit: uniform exactly-sized shard blocks
            t0 = time.perf_counter()
            out_cap = _align_rows(int(shard_tot.max()))
            emit_fn = _enum_emit_fn(mesh, axis, pcap, out_cap, c_pad, j, True)
            table_j = emit_fn(
                table_j, n_rows_j, row_off_j,
                jnp.asarray(shard_tot.reshape(n_shards, 1).astype(np.int32)),
                cand_dev, n_cand_dev, elab_dev, qp, ql, qv,
            )
            if report is not None:
                table_j.block_until_ready()
                stats["host_syncs"] += 1
            t1 = time.perf_counter()
            stats["emit_seconds"] += t1 - t0
            obsv.span_at("enum.emit", t0, t1, level=t, rows=new_total)
        else:
            # host-assisted scan: per-shard validity bitmasks cross back
            # (same bytes as the single-device path), numpy's nonzero is
            # the count + scan, and rebalancing recuts the grids on host
            t0 = time.perf_counter()
            valid_fn = _enum_valid_fn(mesh, axis, pcap, c_pad, j)
            valid_j = valid_fn(
                table_j, n_rows_j, cand_dev, n_cand_dev, elab_dev,
                qp, ql, qv,
            )
            valid_h = np.asarray(valid_j)  # (D, pcap, c_pad) bool
            stats["host_syncs"] += 1
            t1 = time.perf_counter()
            stats["count_seconds"] += t1 - t0
            obsv.span_at("enum.count", t0, t1, level=t, rows=total,
                         shards=n_shards)

            t0 = time.perf_counter()
            counts_rows = valid_h.sum(axis=2, dtype=np.int64)  # (D, pcap)
            shard_tot = counts_rows.sum(axis=1)
            new_total = int(shard_tot.sum())
            if new_total == 0:
                t1 = time.perf_counter()
                stats["scan_seconds"] += t1 - t0
                obsv.span_at("enum.scan", t0, t1, level=t)
                total = 0
                sizes = np.zeros(n_shards, np.int64)
                stats["levels"].append(_level_record(t, [0] * n_shards))
                continue

            grids = [valid_h[i, : sizes[i]] for i in range(n_shards)]
            if (n_shards > 1
                    and shard_tot.max() * n_shards
                    > rebalance_threshold * new_total):
                t_r = time.perf_counter()
                weights = np.concatenate(
                    [counts_rows[i, : sizes[i]] for i in range(n_shards)]
                )
                new_bounds = enum_row_blocks(weights, n_shards)
                if not np.array_equal(new_bounds, bounds):
                    new_sizes = np.diff(new_bounds).astype(np.int64)
                    pcap_new = _align_rows(int(new_sizes.max()))
                    exchange_fn = _enum_exchange_fn(mesh, axis, pcap_new)
                    table_j = exchange_fn(
                        table_j,
                        jnp.asarray(bounds.astype(np.int32)),
                        jnp.asarray(new_bounds[:-1].astype(np.int32)),
                        jnp.asarray(new_sizes.astype(np.int32)),
                    )
                    global_valid = np.concatenate(grids, axis=0)
                    grids = [
                        global_valid[new_bounds[i] : new_bounds[i + 1]]
                        for i in range(n_shards)
                    ]
                    moved = int(sum(
                        max(0, new_sizes[i]
                            - max(0, min(new_bounds[i + 1], bounds[i + 1])
                                  - max(new_bounds[i], bounds[i])))
                        for i in range(n_shards)
                    ))
                    bounds, sizes, pcap = new_bounds, new_sizes, pcap_new
                    n_rows_j = jnp.asarray(
                        sizes.reshape(n_shards, 1).astype(np.int32)
                    )
                    shard_tot = np.asarray(
                        [g.sum(dtype=np.int64) for g in grids]
                    )
                    rebalanced = True
                    rebal_dt = time.perf_counter() - t_r
                    stats["rebalance_rounds"] += 1
                    stats["rebalance_rows_moved"] += moved
                    stats["rebalance_seconds"] += rebal_dt
                    obsv.span_at("enum.rebalance", t_r, t_r + rebal_dt,
                                 level=t, rows_moved=moved)

            out_cap = _align_rows(int(shard_tot.max()))
            r_idx_h = np.zeros((n_shards, out_cap), np.int32)
            c_idx_h = np.zeros((n_shards, out_cap), np.int32)
            for i in range(n_shards):
                ri, ci = np.nonzero(grids[i])  # flat row-major per shard
                r_idx_h[i, : ri.size] = ri
                c_idx_h[i, : ci.size] = ci
            t1 = time.perf_counter()
            stats["scan_seconds"] += t1 - t0 - rebal_dt
            obsv.span_at("enum.scan", t0, t1, level=t)

            # emit: index upload + one sharded gather, table never crosses
            t0 = time.perf_counter()
            gather_fn = _enum_gather_fn(mesh, axis)
            table_j = gather_fn(
                table_j, cand_dev, jnp.asarray(r_idx_h),
                jnp.asarray(c_idx_h),
                jnp.asarray(shard_tot.reshape(n_shards, 1).astype(np.int32)),
            )
            if report is not None:
                table_j.block_until_ready()
                stats["host_syncs"] += 1
            t1 = time.perf_counter()
            stats["emit_seconds"] += t1 - t0
            obsv.span_at("enum.emit", t0, t1, level=t, rows=new_total)

        # advance: children become the next level's contiguous blocks
        sizes = shard_tot.astype(np.int64)
        bounds = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
        total = new_total
        pcap = out_cap
        n_rows_j = jnp.asarray(sizes.reshape(n_shards, 1).astype(np.int32))
        stats["max_table_rows"] = max(stats["max_table_rows"], total)
        stats["max_emit_rows"] = max(
            stats["max_emit_rows"], n_shards * out_cap
        )
        stats["levels"].append(_level_record(
            t, sizes, rebalanced=rebalanced, rebalance_seconds=rebal_dt
        ))
        if int(sizes.max()) > stats["emit_rows_max"]:
            stats["emit_rows_max"] = int(sizes.max())
            stats["emit_rows_min"] = int(sizes.min())

    # assembly: concatenating live prefixes in shard order IS the global
    # row order (contiguous-block invariant), so truncation is a prefix
    n_keep = total
    if max_embeddings is not None:
        n_keep = min(n_keep, max_embeddings)
    if total == 0:
        flat = np.zeros((0, n_q), np.int32)
    else:
        table_out = np.asarray(table_j)
        stats["host_syncs"] += 1
        flat = np.concatenate(
            [table_out[i, : sizes[i]] for i in range(n_shards)], axis=0
        )[:n_keep]
    if report is not None:
        report.update(stats)
    return _restore_query_order(flat, order)


def embeddings_equal(a: np.ndarray, b: np.ndarray) -> bool:
    """Set equality of embedding tables (row order independent)."""
    if a.shape != b.shape:
        return False
    if a.size == 0:
        return True
    sa = {tuple(r) for r in a.tolist()}
    sb = {tuple(r) for r in b.tolist()}
    return sa == sb
