"""Incrementally-maintained CNI index over a mutable graph store.

The paper's §3.4 claim — the encoding "can be computed and updated
incrementally" — operationalized: ``IncrementalIndex`` keeps the per-vertex
label-count matrix ``K[v, l]`` and the CNI digests (exact saturating-limb
*and* float32 log-space) as persistent state over the store's **global label
universe** (every raw vertex label; the vertex set is fixed, so the universe
is too).  Applying an edge batch is a count-vector delta:

* **Counts are invertible.**  Insert/delete of edge (u, w) adds/subtracts 1
  from ``K[u, col(ℓ(w))]`` and ``K[w, col(ℓ(u))]`` — an exact scatter-add
  either way.

* **Digests re-encode only the touched frontier.**  The CNI of an untouched
  vertex is untouched (its count row didn't change) — that is the whole
  point of the index.  Touched vertices re-encode their row with the same
  descending-ord, saturating-limb semantics as ``cni.py``
  (``cni_from_counts_np``, device-bit-exact), over the nonzero count runs
  only: O(|frontier| · L + Σ_frontier min(deg, d_max)) instead of a
  re-encode of all V rows.

* **Saturation semantics** (DESIGN.md §8):
  - insert-only touches of an already-*saturated* digest are **skipped
    outright**: the CNI is monotone under neighborhood growth (Lemma 3) and
    saturation is sticky, so the digest provably stays SAT64 — zero work,
    tracked in ``stats.saturated_skips``.
  - a delete touching a saturated digest cannot be applied arithmetically —
    ``min(x, SAT)`` destroyed the information needed to subtract — so it
    triggers the tracked per-vertex **recompute fallback**
    (``stats.saturated_recomputes``), re-encoding from the (always exact)
    count row.

Engines consume the index through ``store_prefilter`` / ``gathered_counts``:
a query's round-0 candidate mask comes from the maintained counts (a column
gather; no O(E) scatter over the edge list), and a query whose label
alphabet *is* the universe reuses the maintained digests without any
re-encode at all.

``ShardedIncrementalIndex`` is the vertex-partitioned twin: per-shard
count/digest slices maintained under a boundary-exchange routing of update
records (DESIGN.md §9), bit-identical to the flat index after merging.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import NamedTuple

import numpy as np

from repro import obsv
from repro.core import filters as flt
from repro.core.cni import (
    LOG_SAT64,
    SAT64,
    CniValue,
    cni_from_counts_np,
    default_max_p,
)
from repro.core.batch_engine import ceil_pow2
from repro.core.stats import GraphStats
from repro.graphs.store import EdgeBatch, GraphStore


@dataclass
class IndexStats:
    applied_batches: int = 0
    edges_inserted: int = 0
    edges_deleted: int = 0
    touched_vertices: int = 0
    reencoded_vertices: int = 0
    saturated_skips: int = 0        # saturated digest + insert-only: no work
    saturated_recomputes: int = 0   # saturated digest + delete: forced re-encode
    full_rebuilds: int = 0          # d_max overflow (auto-grown table)
    boundary_exchanged: int = 0     # cross-shard records routed to both owners
    extras: dict = field(default_factory=dict)


class IndexSnapshot(NamedTuple):
    """Frozen (read-only copy) index state at one store epoch.

    Travels inside ``GraphSnapshot.index`` so queries pinned to an epoch
    filter against exactly that epoch's digests.
    """

    epoch: int
    universe: np.ndarray   # (Lu,) sorted unique raw vertex labels
    vlabels: np.ndarray    # (V,) raw vertex labels (shared ref; immutable)
    counts: np.ndarray     # (V, Lu) int32
    deg: np.ndarray        # (V,) int32
    cni_u64: np.ndarray    # (V,) uint64 exact saturating CNI (universe ords)
    cni_log: np.ndarray    # (V,) float32 log-space CNI (universe ords)
    d_max: int
    max_p: int
    stats: object = None   # frozen core.stats.GraphStats (planner input)


class IncrementalIndex:
    """Persistent label-count matrix + CNI digest state for a GraphStore.

    Attach with ``store.attach_index(IncrementalIndex())`` — the store then
    calls ``apply_batch`` with exactly the records that changed the edge
    set.  ``d_max`` is the static Pascal-table bound: fixed when the store
    has a ``degree_cap``, otherwise auto-grown (pow2) with a tracked full
    rebuild when an insert exceeds it.
    """

    def __init__(self, *, d_max: int | None = None, use_kernel: bool = False):
        self._d_max_arg = d_max
        self.use_kernel = use_kernel
        self.stats = IndexStats()
        self.graph_stats: GraphStats | None = None  # set by rebuild()
        self._epoch = -1  # set by rebuild()

    # -- (re)build -----------------------------------------------------------

    def rebuild(self, store: GraphStore) -> None:
        """Full build from the store's current edge set (O(V·L + E))."""
        self.universe = np.unique(store.vlabels)
        self.vlabels = store.vlabels
        v = store.n_vertices
        lu = int(self.universe.size)
        if self._d_max_arg is not None:
            self.d_max = int(self._d_max_arg)
        elif store.degree_cap is not None:
            self.d_max = int(store.degree_cap)
        else:
            self.d_max = ceil_pow2(max(4, store.max_degree))
        self.max_p = default_max_p(self.d_max, lu)
        self._col = {int(l): i for i, l in enumerate(self.universe)}
        counts = np.zeros((v, lu), np.int32)
        col_of = np.searchsorted(self.universe, self.vlabels)
        # stores with a disk-resident edge table (graphs/ooc.py) stream the
        # build chunk by chunk — counts accumulate identically, but the full
        # edge list is never materialized in memory
        chunks = getattr(store, "iter_alive_edge_chunks", None)
        blocks = chunks() if chunks is not None else [store.alive_edges()]
        for lo, hi, _lab in blocks:
            if lo.size:
                np.add.at(counts, (lo, col_of[hi]), 1)
                np.add.at(counts, (hi, col_of[lo]), 1)
        self.counts = counts
        self._encode_all()
        # planner statistics ride along: label histogram is static (the
        # vertex set is), edge-dependent aggregates rebuild with the counts
        self.graph_stats = GraphStats.from_store(store)
        self._epoch = store.epoch

    @staticmethod
    def _canonical_log(u64: np.ndarray, log: np.ndarray) -> np.ndarray:
        """Sticky canonical log value for limb-saturated rows.

        The float log digest has no intrinsic saturation, so the
        insert-skip fast path would leave it stale on saturated hubs; the
        filter (``cni_match_log``) treats values at/above ``LOG_SAT64`` as
        pass-through, making this canonicalization exact — and it keeps
        incremental and from-scratch index states bit-identical.
        """
        return np.where(u64 == SAT64, np.float32(LOG_SAT64), log).astype(
            np.float32
        )

    def _encode_all(self) -> None:
        u64, log, deg = cni_from_counts_np(self.counts, self.d_max, self.max_p)
        self.cni_u64 = u64
        self.cni_log = self._canonical_log(u64, log)
        self.deg = deg

    # -- incremental maintenance --------------------------------------------

    def apply_batch(self, store: GraphStore, applied: EdgeBatch) -> None:
        """Fold one applied batch into counts + digests (frontier only)."""
        st = self.stats
        st.applied_batches += 1
        lo = applied.src
        hi = applied.dst
        sign = np.where(applied.insert, 1, -1).astype(np.int32)
        st.edges_inserted += int(applied.insert.sum())
        st.edges_deleted += int((~applied.insert).sum())

        col_of = np.searchsorted(self.universe, self.vlabels)
        self._fold_graph_stats(store, col_of, lo, hi, sign)
        np.add.at(self.counts, (lo, col_of[hi]), sign)
        np.add.at(self.counts, (hi, col_of[lo]), sign)

        frontier = np.unique(np.concatenate([lo, hi]))
        st.touched_vertices += int(frontier.size)
        new_deg = self.counts[frontier].sum(axis=1).astype(np.int32)
        if new_deg.size and int(new_deg.max()) > self.d_max:
            # static table bound exceeded: auto-grow (pow2) + full re-encode
            self.d_max = ceil_pow2(int(new_deg.max()))
            self.max_p = default_max_p(self.d_max, int(self.universe.size))
            self._encode_all()
            st.full_rebuilds += 1
            self._epoch = store.epoch
            return
        self.deg[frontier] = new_deg

        # partition the frontier by saturation semantics
        sat = self.cni_u64[frontier] == SAT64
        dec = np.zeros(frontier.size, dtype=bool)  # any count decrease?
        if not applied.insert.all():
            dec_ids = np.unique(
                np.concatenate([lo[~applied.insert], hi[~applied.insert]])
            )
            dec[np.searchsorted(frontier, dec_ids)] = True
        skip = sat & ~dec          # stays saturated: provably no change
        st.saturated_skips += int(skip.sum())
        st.saturated_recomputes += int((sat & dec).sum())
        redo = frontier[~skip]
        st.reencoded_vertices += int(redo.size)
        if redo.size:
            self._reencode(redo)
        self._epoch = store.epoch

    def _fold_graph_stats(self, store, col_of, lo, hi, sign) -> None:
        """Fold applied records into the planner statistics (core/stats.py).

        An O(1)-per-record by-product of the count-delta pass: the column
        ids are already in hand, so the label-pair frequencies and degree
        mass update without touching the edge table.
        """
        if self.graph_stats is not None:
            self.graph_stats.apply_records(
                col_of[lo], col_of[hi], sign, epoch=store.epoch
            )

    def _encode_rows(self, sub: np.ndarray):
        """(k, Lu) count rows -> (u64, canonical log) digest rows."""
        u64, log, _ = cni_from_counts_np(sub, self.d_max, self.max_p)
        if self.use_kernel:
            # device frontier kernel recomputes the log digests (the TPU
            # fast path); exact limbs stay host-side (no 64-bit datapath)
            from repro.kernels.cni_update.ops import cni_update

            _, log_k, _ = cni_update(
                sub, np.zeros_like(sub), d_max=self.d_max, max_p=self.max_p
            )
            log = np.asarray(log_k)
        return u64, self._canonical_log(u64, log)

    def _reencode(self, rows: np.ndarray) -> None:
        u64, log = self._encode_rows(self.counts[rows])
        self.cni_u64[rows] = u64
        self.cni_log[rows] = log

    # -- durable snapshots ---------------------------------------------------

    def checkpoint_state(self):
        """(leaves, meta) capturing the maintained state exactly — a warm
        restore skips the O(V·L + E) rebuild.  The planner's ``GraphStats``
        rides along under a ``stats_`` leaf prefix."""
        leaves = {
            "universe": self.universe,
            "vlabels": self.vlabels,
            "counts": self.counts,   # sharded: merged-copy property
            "deg": self.deg,
            "cni_u64": self.cni_u64,
            "cni_log": self.cni_log,
        }
        meta = {
            "type": type(self).__name__,
            "d_max": int(self.d_max),
            "d_max_arg": self._d_max_arg,
            "max_p": int(self.max_p),
            "epoch": int(self._epoch),
            "use_kernel": bool(self.use_kernel),
            "stats": None,
        }
        if self.graph_stats is not None:
            s_leaves, s_meta = self.graph_stats.checkpoint_state()
            leaves.update({f"stats_{k}": v for k, v in s_leaves.items()})
            meta["stats"] = s_meta
        return leaves, meta

    @classmethod
    def from_checkpoint_state(cls, leaves, meta, *, store=None):
        """Rebuild the maintained state from ``checkpoint_state()`` output
        (validated against itself; the store argument is unused here but
        required by the sharded twin, which needs its partition plan)."""
        from repro.checkpoint import CheckpointError

        for k in ("universe", "vlabels", "counts", "deg", "cni_u64",
                  "cni_log"):
            if k not in leaves:
                raise CheckpointError(f"index snapshot is missing leaf {k!r}")
        idx = cls(d_max=None, use_kernel=bool(meta.get("use_kernel", False)))
        idx._d_max_arg = meta.get("d_max_arg")
        idx.universe = np.asarray(leaves["universe"])
        idx.vlabels = np.asarray(leaves["vlabels"], dtype=np.int32)
        idx.d_max = int(meta["d_max"])
        idx.max_p = int(meta["max_p"])
        idx._col = {int(l): i for i, l in enumerate(idx.universe)}
        v, lu = int(idx.vlabels.size), int(idx.universe.size)
        counts = np.asarray(leaves["counts"], dtype=np.int32)
        if counts.shape != (v, lu):
            raise CheckpointError(
                f"index snapshot counts shape {counts.shape} disagrees with "
                f"(V, Lu) = ({v}, {lu})"
            )
        idx.counts = counts
        idx.deg = np.asarray(leaves["deg"], dtype=np.int32)
        idx.cni_u64 = np.asarray(leaves["cni_u64"], dtype=np.uint64)
        idx.cni_log = np.asarray(leaves["cni_log"], dtype=np.float32)
        for name in ("deg", "cni_u64", "cni_log"):
            if getattr(idx, name).shape != (v,):
                raise CheckpointError(
                    f"index snapshot {name} shape "
                    f"{getattr(idx, name).shape} disagrees with V={v}"
                )
        idx._epoch = int(meta["epoch"])
        if meta.get("stats") is not None:
            idx.graph_stats = GraphStats.from_checkpoint_state(
                {k[len("stats_"):]: val for k, val in leaves.items()
                 if k.startswith("stats_")},
                meta["stats"],
            )
        return idx

    # -- views ---------------------------------------------------------------

    def freeze(self) -> IndexSnapshot:
        return IndexSnapshot(
            epoch=self._epoch,
            universe=self.universe,
            vlabels=self.vlabels,
            counts=self.counts.copy(),
            deg=self.deg.copy(),
            cni_u64=self.cni_u64.copy(),
            cni_log=self.cni_log.copy(),
            d_max=self.d_max,
            max_p=self.max_p,
            stats=(self.graph_stats.copy()
                   if self.graph_stats is not None else None),
        )


# ---------------------------------------------------------------------------
# Vertex-partitioned maintenance.
# ---------------------------------------------------------------------------


class ShardState(NamedTuple):
    """One shard's slice of the maintained index state (read-only view)."""

    shard: int
    v_base: int            # first owned vertex id
    counts: np.ndarray     # (n_owned, Lu) int32
    deg: np.ndarray        # (n_owned,) int32
    cni_u64: np.ndarray    # (n_owned,) uint64
    cni_log: np.ndarray    # (n_owned,) float32


class ShardedIncrementalIndex(IncrementalIndex):
    """Per-shard counts + CNI digests with a boundary-exchange update step.

    State is held as one array set per shard — each shard owns exactly the
    contiguous vertex slice the partition authority
    (``core/distributed.py::vertex_partition``) assigns it, normally taken
    from the attached ``ShardedGraphStore``'s plan.  Applying a batch routes
    every record to the owner shard(s) of its endpoints:

    * an intra-shard edge (both endpoints owned by shard *s*) is a purely
      local ±1 on two of *s*'s count rows;
    * a **cross-shard** edge (u, w) is exchanged to *both* owners — owner(u)
      folds it into row u, owner(w) into row w — tracked in
      ``stats.boundary_exchanged``.  This mirrors what a multi-host
      deployment ships over the wire per update batch: exactly the boundary
      records, nothing else (DESIGN.md §9).

    Per shard the frontier re-encode and the saturation semantics (§8 skip /
    recompute rules) are the row-wise rules of the base class, so the merged
    state is **bit-identical** to an unsharded ``IncrementalIndex`` fed the
    same batches; ``freeze()`` returns a plain merged ``IndexSnapshot`` so
    every digest consumer (``store_prefilter`` / ``store_digest`` / the
    engines) works unchanged.
    """

    def __init__(self, *, n_shards: int | None = None, d_max: int | None = None,
                 use_kernel: bool = False):
        super().__init__(d_max=d_max, use_kernel=use_kernel)
        self._n_shards_arg = n_shards
        self._plan = None

    # -- (re)build -----------------------------------------------------------

    def rebuild(self, store) -> None:
        from repro.core.distributed import vertex_partition

        plan = getattr(store, "plan", None)
        if plan is None or (
            self._n_shards_arg is not None
            and plan.n_shards != self._n_shards_arg
        ):
            plan = vertex_partition(store.n_vertices,
                                    self._n_shards_arg or 1)
        self._plan = plan
        super().rebuild(store)  # global build (exact), then slice per shard
        self._split_state()

    def _split_state(self) -> None:
        self._sh_counts, self._sh_deg = [], []
        self._sh_u64, self._sh_log = [], []
        for s in range(self._plan.n_shards):
            lo, hi = self._plan.bounds(s)
            self._sh_counts.append(self.__dict__["counts"][lo:hi].copy())
            self._sh_deg.append(self.__dict__["deg"][lo:hi].copy())
            self._sh_u64.append(self.__dict__["cni_u64"][lo:hi].copy())
            self._sh_log.append(self.__dict__["cni_log"][lo:hi].copy())
        # per-shard arrays are now the authoritative state; drop the plain
        # attributes the base rebuild wrote so the merged properties below
        # take over (data descriptors only yield to __dict__ explicitly)
        for name in ("counts", "deg", "cni_u64", "cni_log"):
            self.__dict__.pop(name, None)

    def _merged_or_plain(self, name: str, parts: str):
        if name in self.__dict__:  # mid-rebuild: base class still building
            return self.__dict__[name]
        return np.concatenate(getattr(self, parts), axis=0)

    # merged read-only views (freeze, parity tests); during the base class's
    # rebuild the plain attributes it assigns win via __dict__
    @property
    def counts(self):
        return self._merged_or_plain("counts", "_sh_counts")

    @counts.setter
    def counts(self, v):
        self.__dict__["counts"] = v

    @property
    def deg(self):
        return self._merged_or_plain("deg", "_sh_deg")

    @deg.setter
    def deg(self, v):
        self.__dict__["deg"] = v

    @property
    def cni_u64(self):
        return self._merged_or_plain("cni_u64", "_sh_u64")

    @cni_u64.setter
    def cni_u64(self, v):
        self.__dict__["cni_u64"] = v

    @property
    def cni_log(self):
        return self._merged_or_plain("cni_log", "_sh_log")

    @cni_log.setter
    def cni_log(self, v):
        self.__dict__["cni_log"] = v

    # the base class's in-place mutators write through ``self.counts`` etc.;
    # after _split_state those properties return throwaway concat copies, so
    # an inherited mutator would silently update nothing — fail loudly
    # instead (every live path is overridden to go through the shard slices)
    def _encode_all(self) -> None:
        if hasattr(self, "_sh_counts") and "counts" not in self.__dict__:
            raise RuntimeError(
                "ShardedIncrementalIndex state is per-shard; mutate through "
                "apply_batch/rebuild, not the flat-array encoders"
            )
        super()._encode_all()

    def _reencode(self, rows: np.ndarray) -> None:
        raise RuntimeError(
            "ShardedIncrementalIndex state is per-shard; mutate through "
            "apply_batch/rebuild, not the flat-array encoders"
        )

    # -- durable snapshots ---------------------------------------------------

    def checkpoint_state(self):
        """Merged-state snapshot + the shard count; restore re-splits along
        the restored store's partition plan (bit-identical — the merged
        arrays *are* the authoritative per-shard slices concatenated)."""
        leaves, meta = super().checkpoint_state()
        meta["n_shards"] = int(self._plan.n_shards)
        return leaves, meta

    @classmethod
    def from_checkpoint_state(cls, leaves, meta, *, store=None):
        from repro.checkpoint import CheckpointError

        plan = getattr(store, "plan", None)
        if plan is None:
            raise CheckpointError(
                "sharded index restore needs the restored ShardedGraphStore "
                "(its partition plan) passed as store="
            )
        if int(plan.n_shards) != int(meta["n_shards"]):
            raise CheckpointError(
                f"index snapshot has n_shards={meta['n_shards']} but the "
                f"store plan has {plan.n_shards}"
            )
        idx = super().from_checkpoint_state(leaves, meta, store=store)
        idx._n_shards_arg = int(meta["n_shards"])
        idx._plan = plan
        idx._split_state()
        return idx

    def shard_state(self, s: int) -> ShardState:
        return ShardState(
            shard=s,
            v_base=self._plan.bounds(s)[0],
            counts=self._sh_counts[s],
            deg=self._sh_deg[s],
            cni_u64=self._sh_u64[s],
            cni_log=self._sh_log[s],
        )

    # -- incremental maintenance --------------------------------------------

    def apply_batch(self, store, applied: EdgeBatch) -> None:
        """Route one applied batch per owner shard (boundary exchange), then
        run the base class's frontier/saturation rules per shard slice."""
        st = self.stats
        st.applied_batches += 1
        lo = applied.src
        hi = applied.dst
        sign = np.where(applied.insert, 1, -1).astype(np.int32)
        st.edges_inserted += int(applied.insert.sum())
        st.edges_deleted += int((~applied.insert).sum())

        v_local = self._plan.v_local
        own_lo = lo // v_local
        own_hi = hi // v_local
        st.boundary_exchanged += int((own_lo != own_hi).sum())
        col_of = np.searchsorted(self.universe, self.vlabels)
        # planner stats are global aggregates — fold once, not per shard
        self._fold_graph_stats(store, col_of, lo, hi, sign)

        # ---- exchange + count deltas: each shard folds in exactly the
        # records that touch a row it owns --------------------------------
        touched: list[np.ndarray] = []
        dec_local: list[np.ndarray] = []
        for s in range(self._plan.n_shards):
            base = self._plan.bounds(s)[0]
            m1 = own_lo == s
            m2 = own_hi == s
            rows = np.concatenate([lo[m1] - base, hi[m2] - base])
            cols = np.concatenate([col_of[hi[m1]], col_of[lo[m2]]])
            sg = np.concatenate([sign[m1], sign[m2]])
            np.add.at(self._sh_counts[s], (rows, cols), sg)
            touched.append(np.unique(rows))
            dec_local.append(np.unique(rows[sg < 0]))
            st.touched_vertices += int(touched[-1].size)

        # ---- d_max overflow: grow once, re-encode every shard -------------
        new_degs = [
            self._sh_counts[s][touched[s]].sum(axis=1).astype(np.int32)
            for s in range(self._plan.n_shards)
        ]
        max_new = max((int(d.max()) for d in new_degs if d.size), default=0)
        if max_new > self.d_max:
            self.d_max = ceil_pow2(max_new)
            self.max_p = default_max_p(self.d_max, int(self.universe.size))
            for s in range(self._plan.n_shards):
                u64, log = self._encode_rows(self._sh_counts[s])
                self._sh_u64[s] = u64
                self._sh_log[s] = log
                self._sh_deg[s] = (
                    self._sh_counts[s].sum(axis=1).astype(np.int32)
                )
            st.full_rebuilds += 1
            self._epoch = store.epoch
            return

        # ---- per-shard frontier re-encode under the §8 saturation rules ---
        for s in range(self._plan.n_shards):
            frontier = touched[s]
            if not frontier.size:
                continue
            self._sh_deg[s][frontier] = new_degs[s]
            sat = self._sh_u64[s][frontier] == SAT64
            dec = np.zeros(frontier.size, dtype=bool)
            if dec_local[s].size:
                dec[np.searchsorted(frontier, dec_local[s])] = True
            skip = sat & ~dec          # stays saturated: provably no change
            st.saturated_skips += int(skip.sum())
            st.saturated_recomputes += int((sat & dec).sum())
            redo = frontier[~skip]
            st.reencoded_vertices += int(redo.size)
            if redo.size:
                u64, log = self._encode_rows(self._sh_counts[s][redo])
                self._sh_u64[s][redo] = u64
                self._sh_log[s][redo] = log
        self._epoch = store.epoch

    # -- views ---------------------------------------------------------------

    def freeze(self) -> IndexSnapshot:
        """Merged (cross-shard) snapshot — consumers see one flat index."""
        return IndexSnapshot(
            epoch=self._epoch,
            universe=self.universe,
            vlabels=self.vlabels,
            counts=self.counts,   # concatenating properties already copy
            deg=self.deg,
            cni_u64=self.cni_u64,
            cni_log=self.cni_log,
            d_max=self.d_max,
            max_p=self.max_p,
            stats=(self.graph_stats.copy()
                   if self.graph_stats is not None else None),
        )


# ---------------------------------------------------------------------------
# Query-side consumption: precomputed digests instead of per-query recompute.
# ---------------------------------------------------------------------------


def query_columns(universe: np.ndarray, query_labels: np.ndarray):
    """Map a query's sorted unique labels onto universe column ids.

    Returns (cols (Lq,) int64, present (Lq,) bool) — labels absent from the
    universe have no data-side counts anywhere (their columns are zero).
    """
    cols = np.searchsorted(universe, query_labels)
    cols_c = np.clip(cols, 0, max(0, universe.size - 1))
    present = (
        universe[cols_c] == query_labels if universe.size else
        np.zeros(query_labels.shape, bool)
    )
    return cols_c, present


def gathered_counts(idx: IndexSnapshot, query_labels: np.ndarray) -> np.ndarray:
    """Round-0 per-query counts (V, Lq) from the maintained universe matrix.

    Column gather instead of the O(E) edge scatter ``counts_matrix`` runs —
    exactly equal to ``counts_matrix(g, label_map)`` at the same epoch
    because the universe covers every neighbor label.
    """
    cols, present = query_columns(idx.universe, query_labels)
    out = np.zeros((idx.counts.shape[0], query_labels.size), np.int32)
    if present.any():
        out[:, present] = idx.counts[:, cols[present]]
    return out


def store_digest(idx: IndexSnapshot, query_labels: np.ndarray,
                 ords: np.ndarray | None = None):
    """Data-side VertexDigest for a query alphabet, from index state.

    Full-universe alphabets reuse the maintained digests verbatim (zero
    encode work); restricted alphabets re-encode from the gathered counts
    with the *index's* (d_max, max_p) so comparisons against a query digest
    encoded the same way stay device-bit-exact.  Returns (digest, counts_q,
    ords_data, encoded_positions) with numpy-backed fields, where
    ``encoded_positions`` counts the positions the host encode produced (0
    for the full universe).  ``ords`` may pass in the data-side ord() values
    when the caller already computed them.
    """
    vlab = idx.vlabels
    if ords is None:
        pos = np.clip(np.searchsorted(query_labels, vlab), 0,
                      max(0, query_labels.size - 1))
        ords = np.where(
            query_labels.size and (query_labels[pos] == vlab), pos + 1, 0
        ).astype(np.int32)
    counts_q = gathered_counts(idx, query_labels)
    if query_labels.size == idx.universe.size and np.array_equal(
        query_labels, idx.universe
    ):
        u64, log = idx.cni_u64, idx.cni_log
        deg = idx.deg
        encoded = 0
    else:
        u64, log, deg = cni_from_counts_np(counts_q, idx.d_max, idx.max_p)
        encoded = int(np.minimum(deg, idx.d_max).sum())
    digest = flt.VertexDigest(
        ord_label=ords,
        deg=deg,
        cni=CniValue(
            hi=(u64 >> np.uint64(32)).astype(np.uint32),
            lo=(u64 & np.uint64(0xFFFFFFFF)).astype(np.uint32),
        ),
        cni_log=log,
    )
    return digest, counts_q, ords, encoded


def store_prefilter(idx: IndexSnapshot, query, *, variant: str = "cni",
                    digest_cache: dict | None = None, span=obsv.NOOP_SPAN):
    """One filtering pass from precomputed store digests: (V,) bool alive0.

    The store-backed replacement for the first ILGF round: no edge scatter,
    no full-graph digest encode.  Sound for every variant (all comparisons
    are monotone under the index's clip/saturation params); the ILGF fixed
    point then proceeds from this mask.  ``mnd_nlf`` needs per-edge maxima
    the counts matrix cannot provide — it falls back to the label filter.

    ``digest_cache``: optional dict the caller owns; the data-side digest
    (the encode over restricted count rows) is memoized per query
    alphabet, so a batch of same-alphabet queries encodes it once.

    ``span``: the caller's span; it gets ``encoded_positions``, the
    positions the data-side host encode produced (0 on a reused digest).
    """
    from repro.core.batch_engine import prepare_padded_query

    q_vlab = np.asarray(query.vlabels)
    query_labels = np.unique(q_vlab)
    u_q = int(q_vlab.shape[0])
    ords_data, q_counts, q_digest, _q_mnd = prepare_padded_query(
        query, idx.vlabels, idx.d_max, idx.max_p,
        u_pad=u_q, l_pad=int(query_labels.size),
    )
    key = query_labels.tobytes()
    cached = digest_cache.get(key) if digest_cache is not None else None
    encoded = 0
    if cached is None:
        *cached, encoded = store_digest(idx, query_labels, ords=ords_data)
        if digest_cache is not None:
            digest_cache[key] = cached
    span.set_attrs(encoded_positions=encoded)
    data_digest, counts_q, ords = cached
    if variant == "cni":
        match = flt.cni_match(data_digest, q_digest)
    elif variant == "cni_log":
        match = flt.cni_match_log(data_digest, q_digest)
    elif variant == "nlf":
        match = flt.nlf_match(counts_q, q_counts, ords, q_digest.ord_label)
    elif variant == "label_degree":
        lab = (ords[:, None] == q_digest.ord_label[None, :]) & (ords[:, None] > 0)
        match = lab & (data_digest.deg[:, None] >= q_digest.deg[None, :])
    else:  # mnd_nlf and future variants: label filter only (sound superset)
        match = (ords[:, None] == q_digest.ord_label[None, :]) & (
            ords[:, None] > 0
        )
    return np.asarray(match).any(axis=1) & (ords > 0)
