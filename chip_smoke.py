#!/usr/bin/env python3
"""Smoke run of the CNI subgraph-query service on a TPU.

    python chip_smoke.py [--seed N]             # one chip: the served path
    python chip_smoke.py --chips 4 [--seed N]   # four chips: the mesh path

One chip.  The HUMAN stand-in at the paper's size (4,675 vertices, 86,282
edges drawn before duplicates drop, 44 labels) goes into a ``GraphStore`` with an ``IncrementalIndex``
whose frontier re-encode runs the ``cni_update`` kernel, served by a
``GraphQueryService`` that enumerates on the device (the ``embed_join``
count and grid kernels).  Sixteen random-walk queries of 4-8 vertices, half
dense and half sparse, are served twice (cold, then warm); every result must
have taken the kernel route and equal ``host_dfs_search`` on the snapshot it
was pinned to.  Three insert/delete batches then land between ticks while
the queries are served again; each result is checked against the snapshot
of its own epoch, the final epoch also against a fresh engine, and the
maintained digests against an index rebuilt from scratch.

Four chips (``--chips 4``).  Only the mesh path and what it is compared
with: a ``ShardedGraphStore`` with a ``ShardedIncrementalIndex`` behind a
service on a four-device mesh, against the one-device service in the same
process, on the same graph, queries and mutation batches.  Results must be
bit-identical, and the sharded state must sit on all four chips.

Everything runs in this one process.  The script exits non-zero, printing
no result, when JAX finds no TPU, when the ``repro`` package is not beside
it, or when any check fails.  Its last line of stdout is the JSON object
``{"ok": true, "device": {...}}``.  The wall times it prints are smoke
timings (the cold one includes compilation), not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

N_QUERIES = 16
QUERY_SIZES = (4, 5, 6, 7, 8)
# the per-query embedding cap of the field's benchmark (Sun & Luo,
# "In-Memory Subgraph Matching: An In-depth Study", SIGMOD 2020)
MAX_EMBEDDINGS = 100_000
UPDATE_BATCHES = 3
BATCH_EDGES = 512


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def log(message: str) -> None:
    print(message, flush=True)


def device_info(jax) -> dict:
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def make_queries(g, seed: int):
    from repro.graphs.generators import random_walk_query

    return [
        random_walk_query(
            g, QUERY_SIZES[i % len(QUERY_SIZES)], sparse=bool(i % 2),
            seed=seed * 1000 + i,
        )
        for i in range(N_QUERIES)
    ]


def capped_updates(store, batch):
    """Split an insert/delete batch into the service's two calls, dropping
    inserts that would push a vertex past the store's degree cap (the
    service pins the cap to the starting maximum degree)."""
    import numpy as np

    dels = np.stack([batch.src[~batch.insert], batch.dst[~batch.insert]], 1)
    deg = store.degrees()
    np.subtract.at(deg, dels.reshape(-1), 1)
    keep = []
    for i in np.flatnonzero(batch.insert):
        a, b = int(batch.src[i]), int(batch.dst[i])
        if deg[a] < store.degree_cap and deg[b] < store.degree_cap:
            deg[a] += 1
            deg[b] += 1
            keep.append(i)
    keep = np.asarray(keep, dtype=np.int64)
    ins = np.stack([batch.src[keep], batch.dst[keep]], 1)
    return dels, ins, batch.elabels[keep]


def serve(svcs, queries, batches=()):
    """Submit every query to every service, land each batch after one tick,
    drain.  Returns one ``{query index: (embeddings, stats)}`` per service
    and the snapshots pinned at each epoch a request could be admitted at."""
    rid_to_q = [
        {svc.submit(q, MAX_EMBEDDINGS): i for i, q in enumerate(queries)}
        for svc in svcs
    ]
    snaps = {}

    def pin_epoch():
        snap = svcs[0].store.pin()
        snaps[snap.epoch] = snap
        for svc in svcs[1:]:
            check(svc.epoch == snap.epoch, "services diverged in epoch")

    pin_epoch()
    done = [[] for _ in svcs]
    for batch in batches:
        dels, ins, labs = capped_updates(svcs[0].store, batch)
        for k, svc in enumerate(svcs):
            done[k] += svc.tick()
            svc.remove_edges(dels)
            svc.add_edges(ins, labs)
        pin_epoch()
    for k, svc in enumerate(svcs):
        done[k] += svc.run_to_completion()
    out = []
    for k, finished in enumerate(done):
        res = {rid_to_q[k][rid]: (emb, stats) for rid, emb, stats in finished}
        check(len(res) == len(queries),
              f"service {k} returned {len(res)} of {len(queries)} queries")
        out.append(res)
    return out, snaps


def check_oracle(results, queries, snaps):
    """Every result took the kernel route and equals ``host_dfs_search``
    (label-only candidates, so no filter stands between the two) on the
    snapshot it was pinned to."""
    import numpy as np

    from repro.core.search import host_dfs_search
    from repro.graphs.csr import to_host

    hosts = {}
    for i, q in enumerate(queries):
        emb, stats = results[i]
        path = stats.extras["enum"]["scan_path"]
        check(path == "device", f"query {i} enumerated on the {path!r} route")
        epoch = stats.extras["service"]["epoch"]
        check(epoch in snaps, f"query {i} pinned to unknown epoch {epoch}")
        if epoch not in hosts:
            hosts[epoch] = to_host(snaps[epoch].graph)
        data = hosts[epoch]
        cand = (np.asarray(data.vlabels)[:, None]
                == np.asarray(q.vlabels)[None, :])
        ref = {tuple(r) for r in host_dfs_search(data, q, cand).tolist()}
        got = {tuple(r) for r in np.asarray(emb).tolist()}
        check(len(got) == len(emb), f"query {i}: duplicate embeddings")
        if len(emb) < MAX_EMBEDDINGS:
            check(got == ref,
                  f"query {i} (epoch {epoch}): {len(got)} embeddings, "
                  f"the DFS oracle finds {len(ref)}")
        else:
            check(got <= ref and len(ref) >= MAX_EMBEDDINGS,
                  f"query {i}: truncated result is not within the oracle's")


def check_digests(maintained, final_graph):
    """Maintained index state == a scratch rebuild on the final snapshot.

    Counts, degrees and exact digests must be equal; the log digests the
    kernel re-encoded must agree with the host encoder to the tolerance
    the repo's kernel parity tests use."""
    import numpy as np

    from repro.core.incremental import IncrementalIndex
    from repro.graphs.store import GraphStore

    fresh = GraphStore.from_graph(final_graph)
    fresh.attach_index(IncrementalIndex(d_max=maintained.d_max))
    ref = fresh.index
    check(np.array_equal(maintained.counts, ref.counts), "counts differ")
    check(np.array_equal(maintained.deg, ref.deg), "degrees differ")
    check(np.array_equal(maintained.cni_u64, ref.cni_u64),
          "exact CNI digests differ")
    lm, lr = np.asarray(maintained.cni_log), np.asarray(ref.cni_log)
    fin = np.isfinite(lr)
    check(np.array_equal(np.isfinite(lm), fin), "log digest support differs")
    check(np.allclose(lm[fin], lr[fin], rtol=1e-5, atol=1e-5),
          f"log digests differ: max |d| = {np.abs(lm[fin] - lr[fin]).max()}")
    check(maintained.stats.reencoded_vertices > 0,
          "no frontier was re-encoded by the index kernel")


def timed(label: str, fn):
    t0 = time.perf_counter()
    out = fn()
    log(f"smoke timing (not a benchmark number): {label}: "
        f"{time.perf_counter() - t0:.3f} s")
    return out


def one_chip(seed: int) -> None:
    from repro.core.engine import SubgraphQueryEngine
    from repro.core.incremental import IncrementalIndex
    from repro.graphs.datasets import paper_dataset
    from repro.graphs.generators import random_update_batches
    from repro.graphs.store import GraphStore
    from repro.serve.graph_service import GraphQueryService, GraphServiceConfig

    g = paper_dataset("HUMAN", seed=seed)
    log(f"HUMAN stand-in: {g.n_vertices} vertices, "
        f"{g.n_directed_edges // 2} edges")
    store = GraphStore.from_graph(g)
    store.attach_index(IncrementalIndex(use_kernel=True))
    svc = GraphQueryService(store, GraphServiceConfig(enumerator="device"))
    queries = make_queries(g, seed)

    for label in ("cold serve of 16 queries", "warm serve of 16 queries"):
        (res,), snaps = timed(label, lambda: serve([svc], queries))
        check_oracle(res, queries, snaps)
        n_emb = sum(len(res[i][0]) for i in res)
        log(f"{label}: {n_emb} embeddings, all equal to the DFS oracle")

    batches = random_update_batches(
        g, UPDATE_BATCHES, BATCH_EDGES, delete_frac=0.5, seed=seed + 1
    )
    (res,), snaps = timed("serve under 3 update batches",
                          lambda: serve([svc], queries, batches))
    check_oracle(res, queries, snaps)
    log(f"served under updates across epochs {sorted(snaps)}")

    (res,), snaps = timed("serve on the final epoch",
                          lambda: serve([svc], queries))
    check_oracle(res, queries, snaps)
    final = snaps[svc.epoch]
    fresh = SubgraphQueryEngine(final)
    for i, q in enumerate(queries):
        ref, _ = fresh.query(q, max_embeddings=MAX_EMBEDDINGS)
        check({tuple(r) for r in res[i][0].tolist()}
              == {tuple(r) for r in ref.tolist()},
              f"query {i} differs from a fresh engine on the final epoch")
    check_digests(store.index, final.graph)
    log("final epoch matches a fresh engine; maintained digests match a "
        "scratch rebuild")
    svc.shutdown()


def four_chips(seed: int) -> None:
    import numpy as np

    from repro.core.distributed import device_mesh
    from repro.core.incremental import IncrementalIndex, ShardedIncrementalIndex
    from repro.graphs.datasets import paper_dataset
    from repro.graphs.generators import random_update_batches
    from repro.graphs.store import GraphStore, ShardedGraphStore
    from repro.serve.graph_service import GraphQueryService, GraphServiceConfig

    g = paper_dataset("HUMAN", seed=seed)
    one = GraphStore.from_graph(g)
    one.attach_index(IncrementalIndex(use_kernel=True))
    sharded = ShardedGraphStore.from_graph(g, n_shards=4)
    sharded.attach_index(ShardedIncrementalIndex(n_shards=4, use_kernel=True))
    mesh = device_mesh(4)
    svc_one = GraphQueryService(one, GraphServiceConfig(enumerator="device"))
    svc_mesh = GraphQueryService(
        sharded, GraphServiceConfig(mesh=mesh, enumerator="device")
    )
    queries = make_queries(g, seed)
    batches = random_update_batches(
        g, UPDATE_BATCHES, BATCH_EDGES, delete_frac=0.5, seed=seed + 1
    )

    for label, bs in (("serve", ()), ("serve under 3 update batches", batches)):
        (r_one, r_mesh), snaps = timed(
            f"{label}, one device and four-chip mesh",
            lambda: serve([svc_one, svc_mesh], queries, bs),
        )
        check_oracle(r_one, queries, snaps)
        for i in range(len(queries)):
            check(np.array_equal(r_one[i][0], r_mesh[i][0]),
                  f"{label}: query {i} differs between one device and mesh")
            enum = r_mesh[i][1].extras["enum"]
            check(enum["scan_path"] == "device",
                  f"query {i}: mesh enumeration took the host route")
            check(enum["enum_shards"] == 4,
                  f"query {i}: enumeration ran on {enum['enum_shards']} "
                  "shards")
        log(f"{label}: mesh results bit-identical to one device")

    # the sharded state really lives on all four chips
    entry = svc_mesh._epochs[svc_mesh.epoch]
    check(entry.sharded is not None, "mesh service holds no sharded edges")
    for name, arr in zip(entry.sharded[0]._fields, entry.sharded[0]):
        devs = {s.device for s in arr.addressable_shards}
        check(len(devs) == 4, f"edge table {name} spans {len(devs)} devices")
    per_chip = [int(np.asarray(s.data).sum())
                for s in entry.sharded[0].edge_ok.addressable_shards]
    check(min(per_chip) > 0, f"a chip holds no edges: {per_chip}")
    log(f"sharded edge tables on 4 chips, edges per chip {per_chip}")

    a, b = one.index, sharded.index
    check(np.array_equal(a.counts, b.counts)
          and np.array_equal(a.cni_u64, b.cni_u64)
          and np.allclose(a.cni_log, b.cni_log, rtol=1e-5, atol=1e-5),
          "sharded index digests differ from the one-device index")
    log("sharded index digests match the one-device index")
    svc_one.shutdown()
    svc_mesh.shutdown()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the graph, queries and update batches")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the served path; 4: the mesh path only")
    args = ap.parse_args(argv)

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("chip_smoke: the repro package is not beside this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax

    from repro.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()  # before the first compile
    dev = device_info(jax)
    log(f"platform={dev['platform']} device_kind={dev['kind']} "
        f"count={dev['count']}")
    if dev["platform"] != "tpu":
        print("chip_smoke: JAX finds no TPU; there is no CPU fallback",
              file=sys.stderr)
        return 1
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX finds {dev['count']}", file=sys.stderr)
        return 1
    log(f"compile cache: {cache_dir}")

    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
