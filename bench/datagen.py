"""Everything a run is fed, made from its seed: the data graph, the query
pool, the arrival schedule and the update stream.

Plain numpy; nothing here imports the system under test.  The graph and
query generators follow the CNI paper's setup (§4.1): a labeled graph with
the dataset's vertex, edge and label counts, either Erdős–Rényi or with a
power-law degree law (Chung-Lu), and connected random-walk query subgraphs,
dense (induced) or sparse (a spanning tree plus a few edges, average degree
at most 3).

Seeds: every stream is ``default_rng([seed mod 2**64, stream])``, so any
whole number is a seed and the streams are independent of each other.  The
data graph comes from the configuration's own ``dataset.seed`` and the query
pool from the mix's ``pool.seed``: like a dataset file and a query set, they
are the same in every run, so every run serves the same shapes.  The run's
``--seed`` draws the query picks and the update stream, and the arrivals
unless the mix fixes them with an ``arrivals_seed`` of its own.  Arrivals
and query picks keep the same multiset for every seed (only the order
changes), so seeds shuffle the work rather than change its amount.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

STREAM_GRAPH, STREAM_POOL, STREAM_ARRIVALS, STREAM_UPDATES = range(4)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), stream])


@dataclass
class DataGraph:
    """An undirected labeled graph as canonical records (lo < hi), unique."""

    n_vertices: int
    vlabels: np.ndarray  # (V,) int64
    lo: np.ndarray       # (E,) int64
    hi: np.ndarray       # (E,) int64
    elabels: np.ndarray  # (E,) int64

    def degrees(self) -> np.ndarray:
        return np.bincount(np.concatenate([self.lo, self.hi]),
                           minlength=self.n_vertices)

    def adjacency(self) -> list[dict[int, int]]:
        """Per vertex: neighbor -> edge label."""
        adj: list[dict[int, int]] = [dict() for _ in range(self.n_vertices)]
        for a, b, e in zip(self.lo.tolist(), self.hi.tolist(),
                           self.elabels.tolist()):
            adj[a][b] = e
            adj[b][a] = e
        return adj


def _draw_labels(rng, n: int, n_labels: int, dist: str) -> np.ndarray:
    if dist == "uniform":
        return rng.integers(0, n_labels, size=n)
    if dist == "gaussian":
        raw = rng.normal(loc=n_labels / 2.0, scale=max(1.0, n_labels / 6.0),
                         size=n)
        return np.clip(np.round(raw), 0, n_labels - 1).astype(np.int64)
    raise ValueError(f"unknown label distribution {dist!r}")


def _edge_keys(rng, n: int, m: int, p) -> np.ndarray:
    """The first ``m`` distinct canonical edges ``lo * n + hi`` of a stream
    of endpoint pairs, each endpoint drawn with probabilities ``p``
    (uniform when ``None``), self-loops dropped."""
    keys = np.zeros(0, np.int64)
    while True:
        k = int(1.2 * (m - keys.size)) + 64
        a = rng.choice(n, size=k, p=p)
        b = rng.choice(n, size=k, p=p)
        keep = a != b
        a, b = a[keep], b[keep]
        keys = np.concatenate([keys, np.minimum(a, b) * n + np.maximum(a, b)])
        _, first = np.unique(keys, return_index=True)
        first.sort()
        keys = keys[first]
        if keys.size >= m:
            return keys[:m]


def make_graph(spec: dict) -> DataGraph:
    """The configuration's data graph, from its ``seed``: ``n_edges``
    distinct edges between ``n_vertices`` labeled vertices.

    ``degrees: "uniform"`` draws both endpoints uniformly (Erdős–Rényi);
    ``degrees: "power_law"`` draws each endpoint in proportion to a weight
    from a Pareto law with tail exponent ``exponent`` (Chung-Lu), so the
    degrees follow a power law with the same mean."""
    rng = rng_for(int(spec["seed"]), STREAM_GRAPH)
    n, m = int(spec["n_vertices"]), int(spec["n_edges"])
    vlabels = _draw_labels(rng, n, int(spec["n_labels"]),
                           spec.get("label_dist", "uniform"))
    law = spec.get("degrees", "uniform")
    if law == "uniform":
        p = None
    elif law == "power_law":
        w = (1.0 - rng.random(n)) ** (-1.0 / (float(spec["exponent"]) - 1.0))
        p = w / w.sum()
    else:
        raise ValueError(f"unknown degree law {law!r}")
    keys = _edge_keys(rng, n, m, p)
    elab = rng.integers(0, max(1, int(spec.get("n_edge_labels", 1))),
                        size=keys.size)
    return DataGraph(n, vlabels.astype(np.int64), keys // n, keys % n,
                     elab.astype(np.int64))


@dataclass
class Query:
    """A query graph: vertex labels and canonical edge records."""

    vlabels: np.ndarray  # (U,) int64
    edges: np.ndarray    # (k, 2) int64, lo < hi
    elabels: np.ndarray  # (k,) int64
    dense: bool

    @property
    def n_vertices(self) -> int:
        return int(self.vlabels.size)


def random_walk_query(adj: list[dict[int, int]], vlabels: np.ndarray,
                      n_q: int, *, dense: bool, rng) -> Query:
    """Connected query of exactly ``n_q`` vertices from a random walk.

    Dense: the induced subgraph of the walked vertices.  Sparse: a BFS
    spanning tree plus extra edges up to 1.5 edges per vertex."""
    n = len(adj)
    for _ in range(100):
        cur = int(rng.integers(0, n))
        if not adj[cur]:
            continue
        walked, seen = [cur], {cur}
        for _ in range(200 * n_q):
            if len(walked) == n_q:
                break
            nbrs = list(adj[cur])
            cur = nbrs[int(rng.integers(0, len(nbrs)))]
            if cur not in seen:
                seen.add(cur)
                walked.append(cur)
        if len(walked) == n_q:
            break
    else:
        raise ValueError(f"no connected {n_q}-vertex walk in the graph")
    pos = {v: i for i, v in enumerate(walked)}
    edges, elabs = [], []
    for v in walked:
        for w, e in adj[v].items():
            if w in pos and pos[v] < pos[w]:
                edges.append((pos[v], pos[w]))
                elabs.append(e)
    if not dense and len(edges) > int(1.5 * n_q):
        tree, seen, stack = [], {0}, [0]
        inc = {i: [] for i in range(n_q)}
        for k, (a, b) in enumerate(edges):
            inc[a].append((b, k))
            inc[b].append((a, k))
        while stack:
            v = stack.pop()
            for w, k in inc[v]:
                if w not in seen:
                    seen.add(w)
                    tree.append(k)
                    stack.append(w)
        rest = [k for k in range(len(edges)) if k not in set(tree)]
        rng.shuffle(rest)
        keep = sorted(tree + rest[: int(1.5 * n_q) - len(tree)])
        edges = [edges[k] for k in keep]
        elabs = [elabs[k] for k in keep]
    return Query(vlabels[np.asarray(walked)].astype(np.int64),
                 np.asarray(edges, np.int64).reshape(-1, 2),
                 np.asarray(elabs, np.int64), dense)


def make_pool(graph: DataGraph, adj, pool: dict) -> list[Query]:
    """``per_size`` queries of each size in ``sizes``, half dense, half
    sparse, in a fixed order: size by size, dense and sparse alternating;
    drawn from the pool's own ``seed``."""
    rng = rng_for(int(pool["seed"]), STREAM_POOL)
    out = []
    for n_q in pool["sizes"]:
        for i in range(int(pool["per_size"])):
            out.append(random_walk_query(adj, graph.vlabels, int(n_q),
                                         dense=i % 2 == 0, rng=rng))
    return out


def arrival_times(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Open-loop arrivals at ``rate`` per second over ``seconds``.

    The gaps are the ``n = round(rate * seconds)`` mid-quantiles of the
    exponential distribution with that rate, in an order drawn from the
    seed: Poisson-like arrivals whose multiset of gaps, and so whose count
    and mean rate, is the same for every seed."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    rng_for(seed, STREAM_ARRIVALS).shuffle(gaps)
    t = np.cumsum(gaps) - gaps[0]
    return t[t < seconds]


def query_picks(n_arrivals: int, pool_size: int, seed: int) -> np.ndarray:
    """Pool index per arrival: each pass over the pool in a fresh seeded
    order, so every query is sent equally often (to within one)."""
    rng = rng_for(seed, STREAM_ARRIVALS + 16)
    passes = -(-n_arrivals // pool_size)
    return np.concatenate([rng.permutation(pool_size)
                           for _ in range(passes)])[:n_arrivals]


def schedule(mix: dict, seconds: float, seed: int,
             pool_size: int) -> tuple[np.ndarray, np.ndarray]:
    """Due times and pool picks of a window's requests.  The arrival times
    come from the mix's ``arrivals_seed`` where it has one (a fixed
    schedule, as a replayed trace is), else from the run's seed; the picks
    always come from the run's seed."""
    arrivals = arrival_times(float(mix["rate"]), seconds,
                             int(mix.get("arrivals_seed", seed)))
    return arrivals, query_picks(len(arrivals), pool_size, seed)


@dataclass
class UpdateBatch:
    due: float
    deletes: np.ndarray      # (k, 2) int64 canonical records
    inserts: np.ndarray      # (k, 2) int64 canonical records
    insert_labels: np.ndarray


def update_stream(graph: DataGraph, upd: dict, seconds: float, seed: int,
                  degree_cap: int) -> list[UpdateBatch]:
    """Batches at a fixed period: ``edges // 2`` deletes of live edges
    drawn uniformly, then as many inserts of fresh non-edges whose two
    endpoints stay within the store's degree cap.  Every batch keeps the
    edge count unchanged.  Batch ``k`` is due at ``(k + 0.5) / rate``."""
    rng = rng_for(seed, STREAM_UPDATES)
    n = graph.n_vertices
    half = int(upd["edges"]) // 2
    n_el = max(1, int(upd.get("n_edge_labels", 1)))
    rate = float(upd["rate"])
    live = {(int(a), int(b)) for a, b in zip(graph.lo, graph.hi)}
    live_list = list(live)
    deg = graph.degrees().astype(np.int64)
    out = []
    for k in range(int(seconds * rate)):
        dels = []
        for _ in range(half):
            i = int(rng.integers(0, len(live_list)))
            e = live_list[i]
            live_list[i] = live_list[-1]
            live_list.pop()
            live.discard(e)
            deg[list(e)] -= 1
            dels.append(e)
        gone = set(dels)
        ins = []
        while len(ins) < half:
            a, b = (int(x) for x in rng.integers(0, n, size=2))
            e = (min(a, b), max(a, b))
            if (a == b or e in live or e in gone or deg[a] >= degree_cap
                    or deg[b] >= degree_cap):
                continue
            live.add(e)
            live_list.append(e)
            deg[[a, b]] += 1
            ins.append(e)
        out.append(UpdateBatch(
            due=(k + 0.5) / rate,
            deletes=np.asarray(dels, np.int64).reshape(-1, 2),
            inserts=np.asarray(ins, np.int64).reshape(-1, 2),
            insert_labels=rng.integers(0, n_el, size=len(ins)),
        ))
    return out
