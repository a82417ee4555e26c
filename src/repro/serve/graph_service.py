"""Request-queue front-end for subgraph queries over a *mutable* graph.

Modeled on the continuous-batching slot scheduler in serve/engine.py: a fixed
pool of ``max_slots`` query slots with *static* padded shapes
``(S, V)`` / ``(S, U_cap, L_cap)``, so the whole service runs on a handful of
jit traces of ``batched_ilgf_round``:

* ``submit`` enqueues a query; ``_admit`` moves queued queries into free
  slots (building their padded digest rows and splicing them into the slot
  arrays with ``.at[slot].set``).  When the backing ``GraphStore`` carries an
  incremental index, the slot's starting alive mask is the store-digest
  prefilter — the maintained counts/CNIs replace the first peeling round.
* ``tick()`` = one batched ILGF peeling round **per distinct pinned epoch**
  among the active slots (normally one).  A slot whose alive mask did not
  change has reached its fixed point — its candidate columns are final, so
  the (host-side, per-query) search runs, the result is emitted, and the
  slot frees immediately for the next queued query.
* ``add_edges`` / ``remove_edges`` mutate the store *between* ticks.  Each
  in-flight request is pinned to the snapshot epoch it was admitted on:
  its rounds, candidates, and search all run against that immutable
  snapshot, so results are exactly the fixed point of the graph the query
  started on — no torn reads while the graph churns underneath.  Newly
  admitted queries pin the latest epoch.  Snapshots are refcounted and
  released when their last pinned query finishes.
* ``shutdown()`` drains (or cancels) active slots and **reports every
  queued-but-unstarted request as cancelled** — nothing is silently
  dropped.  An exhausted drain (``max_ticks`` spent with slots still
  active) cancels-and-reports the leftovers under the same contract.
* **Admission control** (DESIGN.md §15): the queue is bounded
  (``max_queue_depth``), per-tenant quotas cap a single tenant's
  queued+active load, and free slots admit by (priority desc, deadline
  asc, FIFO) instead of plain FIFO.  Overload backpressures with the
  *typed* ``AdmissionRejected`` (recorded in ``rejections`` + the
  ``repro_service_rejected_total`` counter) — never a silent drop — and
  queued requests whose deadline lapses expire into ``expired`` with the
  same reporting discipline.
* **Durable snapshots** (serve/persist.py): with
  ``GraphServiceConfig(checkpoint_dir=…)`` the store + incremental index
  persist through the keep-last-k ``CheckpointManager`` every
  ``checkpoint_every`` epochs; ``GraphQueryService.restore`` warm-starts
  a service from the newest committed snapshot after a crash.
* **Sharded operation** is transparent: the backing store may be a
  ``ShardedGraphStore`` (same epoch/pin/mutation contract), and setting
  ``GraphServiceConfig(mesh=…)`` runs each tick's peeling round
  vertex-partitioned under ``shard_map``
  (``core/distributed.py::sharded_batched_ilgf_round``) with bit-identical
  results — per-epoch shard buckets are prepared once and cached alongside
  the snapshot.

This is the serving analogue of the ROADMAP north star: many concurrent
user queries amortize one fused device dispatch per round while the data
graph takes live updates and the vertex axis scales across devices.
"""

from __future__ import annotations

import dataclasses
import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obsv
from repro.core import filters as flt
from repro.core.batch_engine import (
    BatchedQueries,
    batched_ilgf_round,
    prepare_padded_query,
)
from repro.core.cni import CniValue, default_max_p
from repro.core.engine import QueryStats, search_filtered
from repro.core.ilgf import encoded_positions
from repro.graphs.csr import Graph, max_degree, to_host
from repro.graphs.io import ChunkIOError
from repro.graphs.store import BaseGraphStore, GraphSnapshot, as_snapshot


from repro.configs.cni_engine import CONFIG as _ENGINE_CONFIG


@dataclasses.dataclass
class GraphServiceConfig:
    """Slot shapes default to the repo-wide engine preset (configs/
    cni_engine.py) so service deployments and the batch engine agree."""

    max_slots: int = _ENGINE_CONFIG.service_slots
    max_query_vertices: int = _ENGINE_CONFIG.service_max_query_vertices
    max_query_labels: int = _ENGINE_CONFIG.service_max_query_labels
    filter_variant: str = _ENGINE_CONFIG.filter_variant
    khop: int = _ENGINE_CONFIG.khop
    searcher: str = _ENGINE_CONFIG.searcher
    # "host" | "device": device-resident two-phase (count → scan → emit)
    # join enumeration (DESIGN.md §11-§12) — bit-identical embeddings, the
    # embedding table stays on device between rounds and every level's emit
    # buffer is sized to the true survivor count (no host-fallback path).
    # Snapshot-aware: each finalize enumerates against the request's pinned
    # epoch either way, and records the ``empty_enum_report()`` phase
    # telemetry in that result's ``stats.extras["enum"]``.
    enumerator: str = _ENGINE_CONFIG.enumerator
    search_vertex_cap: int = 8192
    max_rounds_per_query: int = 1_000  # safety valve: finalize early (sound)
    # optional device mesh: ticks run the vertex-partitioned peeling round
    # (core/distributed.py) instead of the single-device one — bit-identical
    # results, sharded work.  A ShardedGraphStore whose plan matches the
    # mesh contributes its per-shard tables directly.  With
    # enumerator="device", finalize also enumerates mesh-partitioned
    # (DESIGN.md §13): the embedding table row-shards across the mesh with
    # count-driven rebalancing, per epoch-pinned snapshot, still
    # bit-identical.
    mesh: object = None
    shard_axis: str = _ENGINE_CONFIG.distributed_axis
    # cost-based matching orders (core/planner.py): one QueryPlanner — hence
    # one epoch-aware PlanCache — shared across every tick and slot, so
    # repeat queries skip planning entirely.  ``planner`` overrides with a
    # caller-owned instance (e.g. shared with batch/sequential engines
    # serving the same store); with ``plan_queries=False`` (default) search
    # uses the built-in greedy rule, byte-identical to the pre-planner
    # service.
    plan_queries: bool = False
    planner: object = None
    # admission control (DESIGN.md §15).  ``max_queue_depth`` bounds the
    # submit queue (None = unbounded, the legacy behavior); over-depth
    # submissions raise the typed ``AdmissionRejected``.  ``tenant_quota``
    # caps one tenant's queued+active requests (None = no per-tenant cap).
    max_queue_depth: int | None = 1024
    tenant_quota: int | None = None
    # durable snapshots (serve/persist.py): set a directory to persist the
    # store + incremental index through the keep-last-k CheckpointManager —
    # at construction (base state) and every ``checkpoint_every`` epochs
    # after a mutation.  ``GraphQueryService.restore(dir)`` warm-starts.
    checkpoint_dir: str | None = None
    checkpoint_every: int = 1
    checkpoint_keep: int = 3
    checkpoint_async: bool = True


class AdmissionRejected(RuntimeError):
    """Typed backpressure from ``submit`` — the request was *not* enqueued.

    ``reason`` is machine-readable (``"queue_full"`` | ``"tenant_quota"``);
    ``rid`` identifies the rejection in ``GraphQueryService.rejections``.
    Callers should retry after draining or shed load; the service never
    silently drops work to shed it for them.
    """

    def __init__(self, message: str, *, rid: int, reason: str, tenant: str):
        super().__init__(message)
        self.rid = rid
        self.reason = reason
        self.tenant = tenant


class DrainTimeout(RuntimeError):
    """``run_to_completion`` exhausted ``max_ticks`` with work remaining.

    The triples finished before the timeout ride on ``err.finished`` — an
    incomplete drain is an *error carrying partial results*, no longer a
    partial list indistinguishable from success.
    """

    def __init__(self, message: str, *, finished: list):
        super().__init__(message)
        self.finished = finished


class RejectedRequest(NamedTuple):
    """One admission rejection — recorded, never silently dropped."""

    rid: int
    reason: str   # "queue_full" | "tenant_quota"
    tenant: str


@dataclasses.dataclass
class _Request:
    rid: int
    query: Graph
    max_embeddings: Optional[int]
    submitted_at: float
    rounds: int = 0
    slot: int = -1
    epoch: int = -1
    span: object = None  # obsv.Span root, open from admit to finalize
    tenant: str = "default"
    priority: int = 0
    deadline: Optional[float] = None  # absolute perf_counter() time


class CancelledRequest(NamedTuple):
    """A request the service gave up on — reported, never silently dropped.

    ``ooc``: the pinned epoch's accumulated chunk-IO telemetry
    (``obsv.OocReport``) for requests cancelled *after* admission on an
    out-of-core store; ``None`` for never-admitted requests (no epoch, no
    IO done on their behalf).
    """

    rid: int
    reason: str
    queued_seconds: float
    ooc: object = None


class FailedRequest(NamedTuple):
    """A request that died on the fail-closed path (e.g. ``ChunkIOError``).

    Appended to ``GraphQueryService.failures`` *before* the typed error
    propagates, so queue-wait and the partial chunk-IO telemetry
    (``obsv.OocReport`` with ``partial=True``, when available) survive the
    exception instead of vanishing with the freed slot.
    """

    rid: int
    reason: str
    queued_seconds: float
    ooc: object = None


class _EpochEntry(NamedTuple):
    snapshot: GraphSnapshot
    host_graph: Graph  # numpy-backed twin for the search side
    sharded: Optional[tuple] = None  # (ShardedEdges, PartitionPlan) when meshed


class GraphQueryService:
    """Continuous-batching subgraph-query service over one mutable graph.

    ``data`` may be a ``Graph`` (static service, mutations raise), a
    ``GraphStore`` / ``ShardedGraphStore`` (live updates via
    ``add_edges``/``remove_edges``), or a ``GraphSnapshot``.
    """

    def __init__(self, data, cfg: GraphServiceConfig | None = None):
        self.store: BaseGraphStore | None = (
            data if isinstance(data, BaseGraphStore) else None
        )
        snap = as_snapshot(data)
        self.data = snap.graph
        self.cfg = cfg or GraphServiceConfig()
        self._ooc = getattr(snap, "ooc", None)
        if self._ooc is not None and self.cfg.mesh is not None:
            raise ValueError(
                "out-of-core stores run single-host: the chunk prefilter "
                "fetches a per-epoch restricted edge set that is not "
                "mesh-partitioned; drop GraphServiceConfig.mesh"
            )
        if self._ooc is not None and snap.index is None:
            raise ValueError(
                "OutOfCoreGraphStore needs an attached incremental index — "
                "its digests drive the chunk prefilter (construct the store "
                "with index='auto')"
            )
        if self.store is not None and self.store.degree_cap is not None:
            self.d_max = int(self.store.degree_cap)
        elif self._ooc is not None:
            # the snapshot graph of an out-of-core store is edge-empty on
            # purpose; its resident degree vector carries the true bound
            # (max_degree(snap.graph) would report 0 → wrong digests)
            self.d_max = int(self._ooc.d_max)
            if self.store is not None:
                self.store.degree_cap = self.d_max
        else:
            self.d_max = max(1, max_degree(snap.graph))
            if self.store is not None:
                # impose the service's static table bound as the store's
                # degree_cap: apply() then rejects over-cap batches
                # *atomically*, before any state mutates — an uncapped store
                # could otherwise commit an update the slot shapes can't
                # encode soundly
                self.store.degree_cap = self.d_max
        self.max_p = default_max_p(self.d_max, self.cfg.max_query_labels)
        s = self.cfg.max_slots
        u = self.cfg.max_query_vertices
        l = self.cfg.max_query_labels
        v = snap.graph.n_vertices
        self.n_vertices = v
        self._ords = jnp.zeros((s, v), jnp.int32)
        self._counts = jnp.zeros((s, u, l), jnp.int32)
        self._digest = flt.VertexDigest(
            ord_label=jnp.zeros((s, u), jnp.int32),
            deg=jnp.zeros((s, u), jnp.int32),
            cni=CniValue(
                hi=jnp.zeros((s, u), jnp.uint32),
                lo=jnp.zeros((s, u), jnp.uint32),
            ),
            cni_log=jnp.full((s, u), -jnp.inf, jnp.float32),
        )
        self._mnd = jnp.zeros((s, u), jnp.int32)
        self._alive = jnp.zeros((s, v), bool)
        self.active: list[Optional[_Request]] = [None] * s
        self.queue: list[_Request] = []
        self._rid = 0
        self._epochs: dict[int, _EpochEntry] = {}
        # out-of-core bookkeeping, keyed by pinned epoch: the union of every
        # admitted slot's prefilter seed (the restricted graph must cover all
        # of them), and the accumulated chunk-fetch telemetry for results
        self._ooc_cover: dict[int, np.ndarray] = {}
        self._ooc_tel: dict[int, obsv.OocReport] = {}
        self._shutting_down = False
        self.failures: list[FailedRequest] = []
        self.rejections: list[RejectedRequest] = []
        self.expired: list[CancelledRequest] = []
        # Always-on service metrics (negligible cost: plain dict/bisect
        # updates on the host path).  Scrape via ``metrics_text()``.
        self.metrics = obsv.MetricsRegistry()
        m = self.metrics
        self._m_queue_wait = m.histogram(
            "repro_service_queue_wait_seconds",
            "Submit-to-admission wait per request",
            start=1e-5, factor=4.0, count=14,
        )
        self._m_stage = m.histogram(
            "repro_service_stage_seconds",
            "Per-stage latency (label stage: filter|plan|enumerate|total)",
            start=1e-5, factor=4.0, count=14,
        )
        self._m_requests = m.counter(
            "repro_service_requests_total",
            "Requests by terminal status (completed|failed|cancelled)",
        )
        self._m_ticks = m.counter(
            "repro_service_ticks_total", "Scheduler ticks run"
        )
        self._m_admitted = m.counter(
            "repro_service_admitted_total", "Requests admitted into slots"
        )
        self._m_embeddings = m.counter(
            "repro_service_embeddings_total", "Embeddings emitted to callers"
        )
        self._m_rounds = m.counter(
            "repro_service_rounds_total", "Batched peeling rounds dispatched"
        )
        self._m_active = m.gauge(
            "repro_service_active_slots", "Currently occupied query slots"
        )
        self._m_rejected = m.counter(
            "repro_service_rejected_total",
            "Admission rejections by reason (queue_full|tenant_quota)",
        )
        self._m_deadline_miss = m.counter(
            "repro_service_deadline_missed_total",
            "Requests expired in queue or completed past their deadline",
        )
        self._m_queue_depth = m.gauge(
            "repro_service_queue_depth", "Currently queued requests"
        )
        self._m_queue_depth_hist = m.histogram(
            "repro_service_queue_depth_ticks",
            "Queue depth sampled at each scheduler tick",
            start=1.0, factor=2.0, count=16,
        )
        self._m_ckpts = m.counter(
            "repro_service_checkpoints_total", "Durable snapshots written"
        )
        self._m_ooc_chunks = m.counter(
            "repro_ooc_chunks_read_total",
            "Chunk accesses during restricted fetches",
        )
        self._m_ooc_bytes = m.counter(
            "repro_ooc_bytes_read_total", "Bytes read from chunk files"
        )
        self._m_ooc_hits = m.counter(
            "repro_ooc_cache_hits_total", "Chunk-cache hits"
        )
        self._m_ooc_misses = m.counter(
            "repro_ooc_cache_misses_total", "Chunk-cache misses (disk reads)"
        )
        self._m_hit_ratio = m.gauge(
            "repro_ooc_cache_hit_ratio",
            "Lifetime chunk-cache hit ratio of the backing store",
        )
        self._m_rss = m.gauge(
            "repro_process_peak_rss_bytes",
            "Host-level canary: process peak resident set size",
        )
        self.planner = None
        if self.cfg.planner is not None:
            self.planner = self.cfg.planner
        elif self.cfg.plan_queries:
            from repro.core.planner import QueryPlanner

            # prefer the live store (its index's maintained GraphStats track
            # mutations, so the plan cache invalidates on real drift)
            self.planner = QueryPlanner.for_data(
                self.store if self.store is not None else snap
            )
        self._ckpt = None
        self._ckpt_last_epoch: int | None = None
        if self.cfg.checkpoint_dir is not None:
            if self.store is None:
                raise ValueError(
                    "checkpoint_dir needs a store-backed service — an "
                    "immutable Graph has no durable state to snapshot"
                )
            from repro.serve.persist import ServiceCheckpointer

            self._ckpt = ServiceCheckpointer(
                self.cfg.checkpoint_dir,
                keep=self.cfg.checkpoint_keep,
                async_write=self.cfg.checkpoint_async,
            )
            # the base state is durable from construction: a crash before
            # the first post-mutation save still restores something real
            self._ckpt_last_epoch = self._ckpt.save(self.store)
            self._m_ckpts.inc()
        self._cache_epoch(snap)

    @classmethod
    def restore(cls, directory: str,
                cfg: "GraphServiceConfig | None" = None, *,
                storage_dir: str | None = None) -> "GraphQueryService":
        """Warm-start a service from the newest durable snapshot.

        Rebuilds the store + incremental index (+ planner stats) from the
        latest committed step under ``directory`` and constructs a service
        over them — no index rebuild, same epoch, same digests.  Raises
        the typed ``CheckpointError`` when the directory holds no committed
        snapshot or the snapshot fails validation (truncated/partial
        directories fail closed).  ``storage_dir`` relocates an
        out-of-core snapshot's chunk-directory root.  Unless ``cfg`` says
        otherwise, the restored service keeps checkpointing into the same
        directory.
        """
        from repro.checkpoint import CheckpointError
        from repro.serve.persist import ServiceCheckpointer

        step, store = ServiceCheckpointer(directory).restore_latest(
            storage_dir=storage_dir
        )
        if store is None:
            raise CheckpointError(
                f"{directory} holds no committed service snapshot"
            )
        cfg = cfg if cfg is not None else GraphServiceConfig()
        if cfg.checkpoint_dir is None:
            cfg = dataclasses.replace(cfg, checkpoint_dir=directory)
        return cls(store, cfg)

    # -- epoch/snapshot management -------------------------------------------

    def _cache_epoch(self, snap: GraphSnapshot) -> _EpochEntry:
        entry = self._epochs.get(snap.epoch)
        if entry is None:
            sharded = None
            if self.cfg.mesh is not None:
                # partition this epoch's edge set once; every tick on the
                # epoch reuses the buckets (and the cached round trace)
                from repro.core.distributed import prepare_sharded_edges

                sharded = prepare_sharded_edges(
                    snap, self.cfg.mesh, self.cfg.shard_axis
                )[:2]
            entry = _EpochEntry(snapshot=snap, host_graph=to_host(snap.graph),
                                sharded=sharded)
            self._epochs[snap.epoch] = entry
        return entry

    def _pin_current(self) -> _EpochEntry:
        if self.store is not None:
            return self._cache_epoch(self.store.pin())
        return self._epochs[min(self._epochs)]

    def _release_epoch(self, epoch: int) -> None:
        if self.store is None:
            return
        self.store.release(epoch)
        self._gc_epochs()

    def _gc_epochs(self) -> None:
        """Drop cached epochs no in-flight request pins (keep the latest)."""
        pinned = {r.epoch for r in self.active if r is not None}
        for ep in list(self._epochs):
            if ep not in pinned and ep != self.epoch:
                self._epochs.pop(ep)
        for d in (self._ooc_cover, self._ooc_tel):
            for ep in list(d):
                if ep not in self._epochs:
                    del d[ep]

    def _ensure_ooc_cover(self, epoch: int, alive_row: np.ndarray) -> None:
        """Grow the epoch's restricted graph to cover one more seed mask.

        The cached ``_EpochEntry`` graph for an out-of-core epoch holds only
        the edges among the union of the prefilter seeds admitted so far.
        Coverage is monotone: per-slot alive masks only shrink under peeling
        and stay within their seed, so a superset edge fetch is always exact
        (``counts_matrix_from_ords`` masks both endpoints by alive).  A
        refetch replaces the entry — subsequent ticks and finalizes on the
        epoch read the wider graph, which agrees with the old one on every
        previously covered slot.
        """
        entry = self._epochs[epoch]
        cover = self._ooc_cover.get(epoch)
        if cover is not None and not np.any(alive_row & ~cover):
            return
        new_cover = alive_row.copy() if cover is None else (cover | alive_row)
        restricted, tel = entry.snapshot.ooc.fetch_restricted(new_cover)
        self._ooc_cover[epoch] = new_cover
        # ``tel`` is a typed obsv.OocReport (fetches=1); merge() sums the
        # counters and carries the point-in-time gauges forward, so the
        # per-epoch aggregate stays a validated report.
        agg = self._ooc_tel.get(epoch)
        self._ooc_tel[epoch] = tel if agg is None else agg.merge(tel)
        self._m_ooc_chunks.inc(tel.chunks_read)
        self._m_ooc_bytes.inc(tel.bytes_read)
        self._m_ooc_hits.inc(tel.cache_hits)
        self._m_ooc_misses.inc(tel.cache_misses)
        self._epochs[epoch] = _EpochEntry(
            snapshot=entry.snapshot._replace(graph=restricted),
            host_graph=to_host(restricted),
            sharded=None,
        )

    # -- public API ----------------------------------------------------------

    def submit(self, query: Graph,
               max_embeddings: int | None = None, *,
               tenant: str = "default", priority: int = 0,
               deadline_seconds: float | None = None) -> int:
        """Enqueue a query; returns its request id.

        Rejects queries that exceed the service's static slot shapes — size
        the caps from the workload, or route oversize queries to a
        ``BatchQueryEngine`` with per-bucket shapes.

        Admission control: a full queue (``max_queue_depth``) or an
        over-quota tenant (``tenant_quota``) raises the typed
        ``AdmissionRejected`` (also recorded in ``rejections``) — bounded
        backpressure, never a silent drop.  ``priority`` (higher first)
        and ``deadline_seconds`` (sooner first; lapsed-in-queue requests
        expire into ``expired``) shape the slot-admission order.
        """
        if self._shutting_down:
            raise RuntimeError("service is shut down; no new submissions")
        query = to_host(query)
        n_labels = int(np.unique(query.vlabels).size)
        if query.n_vertices > self.cfg.max_query_vertices:
            raise ValueError(
                f"query has {query.n_vertices} vertices > service cap "
                f"{self.cfg.max_query_vertices}"
            )
        if n_labels > self.cfg.max_query_labels:
            raise ValueError(
                f"query has {n_labels} labels > service cap "
                f"{self.cfg.max_query_labels}"
            )
        self._rid += 1
        if (self.cfg.max_queue_depth is not None
                and len(self.queue) >= self.cfg.max_queue_depth):
            raise self._reject(
                self._rid, "queue_full", tenant,
                f"queue depth {len(self.queue)} is at max_queue_depth="
                f"{self.cfg.max_queue_depth}; tick/drain and retry",
            )
        if self.cfg.tenant_quota is not None:
            load = sum(r.tenant == tenant for r in self.queue) + sum(
                r is not None and r.tenant == tenant for r in self.active
            )
            if load >= self.cfg.tenant_quota:
                raise self._reject(
                    self._rid, "tenant_quota", tenant,
                    f"tenant {tenant!r} has {load} queued+active requests "
                    f">= tenant_quota={self.cfg.tenant_quota}",
                )
        now = time.perf_counter()
        self.queue.append(_Request(
            self._rid, query, max_embeddings, now,
            tenant=tenant, priority=int(priority),
            deadline=(now + float(deadline_seconds)
                      if deadline_seconds is not None else None),
        ))
        self._m_queue_depth.set(len(self.queue))
        return self._rid

    def _reject(self, rid: int, reason: str, tenant: str,
                message: str) -> AdmissionRejected:
        self.rejections.append(RejectedRequest(rid, reason, tenant))
        self._m_rejected.inc(1, reason=reason)
        return AdmissionRejected(message, rid=rid, reason=reason,
                                 tenant=tenant)

    def add_edges(self, edges, elabels=None):
        """Insert edges into the backing store (between ticks).

        In-flight queries keep filtering against their pinned epochs; only
        queries admitted after this call see the new edges.
        """
        return self._mutate("add_edges", edges, elabels)

    def remove_edges(self, edges):
        """Delete edges from the backing store (between ticks)."""
        return self._mutate("remove_edges", edges)

    def _mutate(self, op: str, edges, elabels=None):
        if self.store is None:
            raise RuntimeError(
                "service was constructed from an immutable Graph; build it "
                "from a GraphStore to take live updates"
            )
        if getattr(self, "_read_only", False):
            raise RuntimeError(
                "this service is a read replica; route mutations through "
                "the router's writer (serve/replicas.py)"
            )
        if op == "add_edges":
            res = self.store.add_edges(edges, elabels)
        else:
            res = self.store.remove_edges(edges)
        # unreachable when degree_cap <= d_max (apply validates atomically);
        # guards a store whose cap was widened behind the service's back.
        # A real raise, not an assert: this invariant protects result
        # soundness (slot digests are encoded against d_max) and must hold
        # under ``python -O`` too.
        if self.store.max_degree > self.d_max:
            raise RuntimeError(
                f"store max degree {self.store.max_degree} exceeds the "
                f"service's static d_max={self.d_max}"
            )
        self._maybe_checkpoint()
        self._gc_epochs()
        return res

    def _maybe_checkpoint(self) -> None:
        if self._ckpt is None:
            return
        if self.epoch - self._ckpt_last_epoch >= self.cfg.checkpoint_every:
            self._ckpt.save(self.store)
            self._ckpt_last_epoch = self.epoch
            self._m_ckpts.inc()

    def checkpoint_now(self) -> int:
        """Force a durable snapshot of the current epoch; returns the step."""
        if self._ckpt is None:
            raise RuntimeError(
                "no checkpoint_dir configured on this service"
            )
        step = self._ckpt.save(self.store)
        self._ckpt_last_epoch = self.epoch
        self._m_ckpts.inc()
        return step

    def wait_for_checkpoints(self) -> None:
        """Block until the in-flight async snapshot write commits.

        Re-raises a failed write as ``CheckpointError`` — the async-write
        contract of ``CheckpointManager`` surfaces here.
        """
        if self._ckpt is not None:
            self._ckpt.wait()

    def tick(self) -> list[tuple[int, np.ndarray, QueryStats]]:
        """One scheduler step = one batched peeling round per pinned epoch.

        Returns finished (rid, embeddings, stats) triples (possibly empty).
        Normally all active slots share one epoch (one fused dispatch);
        after a mutation, old and new queries coexist on their own epochs
        until the old ones drain.
        """
        self._m_ticks.inc()
        self._m_queue_depth_hist.observe(float(len(self.queue)))
        self._m_queue_depth.set(len(self.queue))
        with obsv.span("service.tick", active=self.n_active,
                       queued=len(self.queue)):
            return self._tick()

    def _tick(self) -> list[tuple[int, np.ndarray, QueryStats]]:
        self._admit()
        live = [r for r in self.active if r is not None]
        if not live:
            return []
        finished = []
        alive_merged = self._alive
        for epoch in sorted({r.epoch for r in live}):
            group = [r for r in live if r.epoch == epoch]
            mask_np = np.zeros(self.cfg.max_slots, bool)
            for r in group:
                mask_np[r.slot] = True
            mask = jnp.asarray(mask_np)
            entry = self._epochs[epoch]
            with obsv.span("service.filter_round", epoch=epoch,
                           group=len(group)) as round_span:
                # slots outside this epoch group are made inert for the
                # dispatch (zero ords ⇒ empty alive ⇒ no work), so one
                # trace serves all
                qb = BatchedQueries(
                    ords=jnp.where(mask[:, None], self._ords, 0),
                    counts=self._counts, digest=self._digest, mnd=self._mnd,
                )
                if entry.sharded is not None:
                    from repro.core.distributed import (
                        sharded_batched_ilgf_round,
                    )

                    se, plan = entry.sharded
                    new_alive, cand, changed = sharded_batched_ilgf_round(
                        se, plan, qb, self._alive & mask[:, None],
                        mesh=self.cfg.mesh, axis=self.cfg.shard_axis,
                        n_labels=self.cfg.max_query_labels,
                        d_max=self.d_max, max_p=self.max_p,
                        variant=self.cfg.filter_variant,
                    )
                else:
                    new_alive, cand, changed = batched_ilgf_round(
                        entry.snapshot.graph, qb,
                        self._alive & mask[:, None],
                        n_labels=self.cfg.max_query_labels,
                        d_max=self.d_max, max_p=self.max_p,
                        variant=self.cfg.filter_variant,
                    )
                round_span.set_attrs(encoded_positions=encoded_positions(
                    self.cfg.filter_variant, self.cfg.max_slots,
                    entry.snapshot.graph, self.d_max,
                    padded=entry.sharded is not None,
                ))
                converged = ~np.asarray(changed)
            alive_merged = jnp.where(mask[:, None], new_alive, alive_merged)
            self._m_rounds.inc()
            for req in group:
                req.rounds += 1
                # one fused dispatch serves the whole epoch group; the
                # shared round is mirrored, with the live span's exact
                # interval, into each member's request trace (flagged
                # ``shared`` so durations aren't summed naively)
                obsv.mirror(round_span, parent=req.span, round=req.rounds,
                            epoch=epoch, shared=len(group) > 1)
                if (converged[req.slot]
                        or req.rounds >= self.cfg.max_rounds_per_query):
                    finished.append(self._finalize(req, new_alive, cand))
                    self._free(req.slot)
        self._alive = alive_merged
        return finished

    def run_to_completion(self, max_ticks: int = 100_000):
        """Drain queue + slots; returns all finished triples.

        Raises ``DrainTimeout`` when ``max_ticks`` is exhausted with
        requests still queued or in flight — the triples that did finish
        ride on ``err.finished``, so an incomplete drain is never
        indistinguishable from success.
        """
        done = []
        for _ in range(max_ticks):
            done.extend(self.tick())
            if not self.queue and all(a is None for a in self.active):
                return done
        if not self.queue and all(a is None for a in self.active):
            return done
        raise DrainTimeout(
            f"run_to_completion: {len(self.queue)} queued and "
            f"{self.n_active} in-flight requests remain after "
            f"{max_ticks} ticks",
            finished=done,
        )

    def shutdown(self, *, drain: bool = True, max_ticks: int = 100_000):
        """Stop the service: returns ``(finished, cancelled)``.

        ``drain=True`` finishes every already-admitted (in-slot) query
        first; queued-but-unstarted requests are *always* cancelled and
        reported — never silently dropped.  ``drain=False`` also cancels
        the in-flight slots.  A drain that exhausts ``max_ticks`` with
        slots still active cancels-and-reports the leftovers (reason
        ``"shutdown drain exhausted"``) instead of leaking them.  With a
        ``checkpoint_dir``, the final state is persisted and the write is
        waited on before returning.  ``submit`` raises afterwards.
        """
        self._shutting_down = True  # _admit is disabled from here on
        finished: list = []
        cancelled: list[CancelledRequest] = []
        if drain:
            for _ in range(max_ticks):
                if all(a is None for a in self.active):
                    break
                finished.extend(self.tick())
        now = time.perf_counter()
        reason = ("shutdown drain exhausted" if drain
                  else "shutdown before completion")
        for req in [r for r in self.active if r is not None]:
            # the partial work done on the request's behalf is not lost:
            # its epoch's accumulated chunk-IO telemetry rides along
            cancelled.append(CancelledRequest(
                req.rid, reason,
                now - req.submitted_at,
                ooc=self._ooc_tel.get(req.epoch),
            ))
            if req.span is not None:
                req.span.set_attrs(cancelled=True)
                obsv.end(req.span)
            self._free(req.slot)
        for req in self.queue:
            cancelled.append(CancelledRequest(
                req.rid, "shutdown before admission",
                now - req.submitted_at,
            ))
        self.queue.clear()
        self._m_requests.inc(len(cancelled), status="cancelled")
        if self._ckpt is not None:
            if self._ckpt_last_epoch != self.epoch:
                self._ckpt.save(self.store)
                self._ckpt_last_epoch = self.epoch
                self._m_ckpts.inc()
            self._ckpt.wait()
        return finished, cancelled

    def metrics_snapshot(self) -> dict:
        """Point-in-time value of every registered metric (plain dict)."""
        self._refresh_gauges()
        return self.metrics.snapshot()

    def metrics_text(self) -> str:
        """Render the registry in Prometheus exposition format."""
        self._refresh_gauges()
        return self.metrics.render_prometheus()

    def _refresh_gauges(self) -> None:
        self._m_active.set(self.n_active)
        self._m_queue_depth.set(len(self.queue))
        if self._ooc is not None:
            cache = self._ooc.cache
            acc = cache.hits + cache.misses
            self._m_hit_ratio.set(cache.hits / acc if acc else 0.0)
        try:
            import resource

            # ru_maxrss is KiB on Linux
            self._m_rss.set(
                resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
            )
        except Exception:  # pragma: no cover - platforms without getrusage
            pass

    @property
    def n_active(self) -> int:
        return sum(a is not None for a in self.active)

    @property
    def epoch(self) -> int:
        return self.store.epoch if self.store is not None else 0

    # -- internals -----------------------------------------------------------

    def _expire_queued(self, now: float) -> None:
        """Expire queued requests whose deadline already lapsed — reported
        in ``expired`` (and the deadline-miss counter), never silently
        dropped, and never admitted into a slot they can't meet."""
        keep: list[_Request] = []
        for r in self.queue:
            if r.deadline is not None and now >= r.deadline:
                self.expired.append(CancelledRequest(
                    r.rid, "deadline expired before admission",
                    now - r.submitted_at,
                ))
                self._m_deadline_miss.inc()
                self._m_requests.inc(1, status="expired")
            else:
                keep.append(r)
        self.queue[:] = keep

    def _pick_queued(self) -> _Request:
        """Admission order: priority desc, then deadline asc (undeadlined
        last), then FIFO — a stable total order over the queue."""
        i = min(
            range(len(self.queue)),
            key=lambda j: (
                -self.queue[j].priority,
                self.queue[j].deadline
                if self.queue[j].deadline is not None else float("inf"),
                self.queue[j].submitted_at,
            ),
        )
        return self.queue.pop(i)

    def _admit(self):
        if self._shutting_down:
            return
        self._expire_queued(time.perf_counter())
        for slot in range(self.cfg.max_slots):
            if self.active[slot] is None and self.queue:
                req = self._pick_queued()
                req.slot = slot
                now = time.perf_counter()
                queue_s = now - req.submitted_at
                self._m_queue_wait.observe(queue_s)
                self._m_admitted.inc()
                # One detached root span per request: it stays open across
                # ticks until finalize/cancel, so the whole lifetime —
                # queue-wait, admission, every peeling round's tick, and the
                # finalize search — lands in a single per-request trace tree.
                req.span = obsv.start_detached("service.request", rid=req.rid)
                obsv.span_at("service.queue_wait", req.submitted_at, now,
                             parent=req.span, rid=req.rid)
                with obsv.activate(req.span), \
                        obsv.span("service.admit", slot=slot) as admit_span:
                    entry = self._pin_current()
                    req.epoch = entry.snapshot.epoch
                    admit_span.set_attrs(epoch=req.epoch)
                    self.active[slot] = req
                    ords, counts, digest, mnd = prepare_padded_query(
                        req.query, entry.host_graph.vlabels, self.d_max,
                        self.max_p, self.cfg.max_query_vertices,
                        self.cfg.max_query_labels,
                    )
                    alive_row = ords > 0
                    if entry.snapshot.index is not None:
                        # maintained store digests stand in for round one
                        from repro.core.incremental import store_prefilter

                        with obsv.span("service.prefilter"):
                            alive_row = alive_row & store_prefilter(
                                entry.snapshot.index, req.query,
                                variant=self.cfg.filter_variant,
                            )
                    if entry.snapshot.ooc is not None:
                        # fetch (or widen) this epoch's restricted edge set
                        # so it covers the new slot's seed.  Fail closed: a
                        # chunk I/O failure frees the slot — releasing the
                        # epoch pin — and surfaces the typed error to the
                        # caller; the service stays usable for subsequent
                        # submissions.  The request's queue-wait and the
                        # fetch's partial IO telemetry are recorded in
                        # ``self.failures`` first, not lost with the slot.
                        try:
                            self._ensure_ooc_cover(
                                req.epoch, np.asarray(alive_row, dtype=bool)
                            )
                        except ChunkIOError as err:
                            tel = getattr(err, "tel", None)
                            prior = self._ooc_tel.get(req.epoch)
                            if prior is not None and tel is not None:
                                tel = prior.merge(tel)
                            elif tel is None:
                                tel = prior
                            self.failures.append(FailedRequest(
                                req.rid, str(err), queue_s, ooc=tel,
                            ))
                            self._m_requests.inc(1, status="failed")
                            if req.span is not None:
                                req.span.set_attrs(failed=True)
                                obsv.end(req.span)
                            self._free(slot)
                            raise
                    self._ords = self._ords.at[slot].set(ords)
                    self._counts = self._counts.at[slot].set(counts)
                    self._digest = jax.tree_util.tree_map(
                        lambda acc, row: acc.at[slot].set(row),
                        self._digest, digest,
                    )
                    self._mnd = self._mnd.at[slot].set(mnd)
                    self._alive = self._alive.at[slot].set(
                        jnp.asarray(alive_row)
                    )

    def _finalize(self, req: _Request, alive, cand):
        u_q = req.query.n_vertices
        alive_np = np.asarray(alive[req.slot])
        cand_np = np.asarray(cand[req.slot])[:, :u_q]
        stats = QueryStats(
            vertices_before=self.n_vertices,
            ilgf_iterations=req.rounds,
        )
        deadline_missed = (req.deadline is not None
                           and time.perf_counter() > req.deadline)
        if deadline_missed:
            self._m_deadline_miss.inc()
        stats.extras["service"] = obsv.ServiceReport(
            slot=req.slot,
            epoch=req.epoch,
            queue_seconds=time.perf_counter() - req.submitted_at,
            rounds=req.rounds,
            trace_id=req.span.trace_id if req.span is not None else None,
            tenant=req.tenant,
            priority=req.priority,
            deadline_missed=deadline_missed,
        ).validate()
        if req.epoch in self._ooc_tel:
            # the accumulated (typed, Mapping-compatible) epoch report —
            # reports are never mutated in place, so sharing is safe
            stats.extras["ooc"] = self._ooc_tel[req.epoch]
        t0 = time.perf_counter()
        with obsv.activate(req.span), \
                obsv.span("service.finalize", rid=req.rid, rounds=req.rounds):
            emb = search_filtered(
                self._epochs[req.epoch].host_graph, req.query, alive_np,
                cand_np, stats,
                khop=self.cfg.khop,
                searcher=self.cfg.searcher,
                search_vertex_cap=self.cfg.search_vertex_cap,
                max_embeddings=req.max_embeddings,
                planner=self.planner,
                enumerator=self.cfg.enumerator,
                mesh=self.cfg.mesh,
                shard_axis=self.cfg.shard_axis,
            )
        if req.span is not None:
            req.span.set_attrs(n_embeddings=len(emb), rounds=req.rounds)
            obsv.end(req.span)
        self._m_requests.inc(1, status="completed")
        self._m_embeddings.inc(len(emb))
        self._m_stage.observe(stats.filter_seconds, stage="filter")
        plan = stats.extras.get("plan")
        if plan is not None:
            self._m_stage.observe(float(plan["plan_seconds"]), stage="plan")
        self._m_stage.observe(stats.search_seconds, stage="enumerate")
        self._m_stage.observe(time.perf_counter() - t0, stage="total")
        return req.rid, emb, stats

    def _free(self, slot: int):
        req = self.active[slot]
        self.active[slot] = None
        if req is not None and req.epoch >= 0:
            self._release_epoch(req.epoch)
        self._ords = self._ords.at[slot].set(0)
        self._alive = self._alive.at[slot].set(False)
