"""Plain reference for labeled subgraph isomorphism on a mutable graph.

Imports nothing of the system under test.  A query's answer on a graph is
the set of injective maps of its vertices to data vertices that keep every
vertex label, and map every query edge to a data edge with the same edge
label.  A result capped at ``cap`` rows holds ``min(cap, total)`` distinct
such maps.

Also the plain Compact Neighborhood Index of every vertex (the CNI paper,
§3.1, Theorem 1) for the check of a maintained index:
``cni(v) = sum_{j=1..deg} C(j + p_j - 1, j)``, where ``p_j`` is the sum of
the first ``j`` neighbor label ordinals taken in descending order, each
ordinal being the label's 1-based rank among the graph's labels.  The
configuration states two bounds of the encoding: ``p_j`` is clipped to
``max_p = min(d_max * n_labels, 4096)`` and the sum saturates at 2**62,
whose log digest is ``62 ln 2``.  Exact sums are Python integers and log
digests float64 (``math.lgamma``).
"""

from __future__ import annotations

import math

import numpy as np

SAT = 1 << 62
LOG_SAT = 62 * math.log(2.0)


class EpochGraph:
    """The data graph at one epoch, as adjacency dicts, mutated in place."""

    def __init__(self, n_vertices, vlabels, lo, hi, elabels):
        self.vlabels = [int(x) for x in vlabels]
        self.adj: list[dict[int, int]] = [dict() for _ in range(n_vertices)]
        for a, b, e in zip(np.asarray(lo).tolist(), np.asarray(hi).tolist(),
                           np.asarray(elabels).tolist()):
            self.adj[a][b] = e
            self.adj[b][a] = e
        self.by_label: dict[int, list[int]] = {}
        for v, lab in enumerate(self.vlabels):
            self.by_label.setdefault(lab, []).append(v)

    def remove(self, edges) -> None:
        for a, b in np.asarray(edges).tolist():
            del self.adj[a][b]
            del self.adj[b][a]

    def insert(self, edges, elabels) -> None:
        for (a, b), e in zip(np.asarray(edges).tolist(),
                             np.asarray(elabels).tolist()):
            self.adj[a][b] = e
            self.adj[b][a] = e


def _query_adjacency(n_q, edges, elabels):
    qadj: list[dict[int, int]] = [dict() for _ in range(n_q)]
    for (a, b), e in zip(np.asarray(edges).tolist(),
                         np.asarray(elabels).tolist()):
        qadj[a][b] = e
        qadj[b][a] = e
    return qadj


def embeddings(g: EpochGraph, qlabels, qedges, qelabels,
               cap: int) -> list[tuple]:
    """Up to ``cap`` embeddings, each a tuple of data vertices in query
    vertex order, by plain backtracking."""
    qlabels = [int(x) for x in qlabels]
    n_q = len(qlabels)
    qadj = _query_adjacency(n_q, qedges, qelabels)
    # connected order from the rarest label; each later vertex is reached
    # from an earlier, already matched neighbor
    start = min(range(n_q), key=lambda u: len(g.by_label.get(qlabels[u], ())))
    order, seen = [start], {start}
    while len(order) < n_q:
        nxt = min((u for u in range(n_q) if u not in seen
                   and any(w in seen for w in qadj[u])),
                  key=lambda u: len(g.by_label.get(qlabels[u], ())))
        order.append(nxt)
        seen.add(nxt)
    pos = {u: i for i, u in enumerate(order)}
    back = [[(w, e) for w, e in qadj[u].items() if pos[w] < pos[u]]
            for u in order]
    image = [-1] * n_q
    used: set[int] = set()
    found: list[tuple] = []

    def extend(depth: int) -> bool:
        if depth == n_q:
            found.append(tuple(image))
            return len(found) >= cap
        u = order[depth]
        lab = qlabels[u]
        if back[depth]:
            anchor, _ = back[depth][0]
            cands = g.adj[image[anchor]].keys()
        else:
            cands = g.by_label.get(lab, ())
        for v in cands:
            if g.vlabels[v] != lab or v in used:
                continue
            if all(g.adj[v].get(image[w]) == e for w, e in back[depth]):
                image[u] = v
                used.add(v)
                stop = extend(depth + 1)
                used.discard(v)
                if stop:
                    return True
        image[u] = -1
        return False

    extend(0)
    return found


def invalid_rows(g: EpochGraph, qlabels, qedges, qelabels,
                 rows: np.ndarray) -> int:
    """Rows that are not embeddings of the query on ``g``, plus repeats."""
    qlabels = [int(x) for x in qlabels]
    qedges = np.asarray(qedges).tolist()
    qelabels = np.asarray(qelabels).tolist()
    bad, seen = 0, set()
    for row in np.asarray(rows).tolist():
        t = tuple(row)
        ok = (t not in seen and len(set(t)) == len(t)
              and all(0 <= v < len(g.vlabels) for v in t))
        seen.add(t)
        ok = ok and all(g.vlabels[v] == lab for v, lab in zip(t, qlabels))
        ok = ok and all(g.adj[t[a]].get(t[b]) == e
                        for (a, b), e in zip(qedges, qelabels))
        bad += not ok
    return bad


def label_counts(g: EpochGraph, universe: np.ndarray) -> np.ndarray:
    """(V, L) neighbor-label counts, columns in ascending label order."""
    col = {int(lab): i for i, lab in enumerate(universe)}
    out = np.zeros((len(g.adj), len(universe)), np.int64)
    for v, nbrs in enumerate(g.adj):
        for w in nbrs:
            out[v, col[g.vlabels[w]]] += 1
    return out


def cni_digests(counts: np.ndarray, max_p: int):
    """Exact (saturated) CNI and its log digest for every count row."""
    n, n_labels = counts.shape
    exact = np.zeros(n, np.uint64)
    logs = np.full(n, -np.inf)
    for v in range(n):
        ords = []
        for col in range(n_labels - 1, -1, -1):
            ords += [col + 1] * int(counts[v, col])
        total, terms, prefix = 0, [], 0
        for j, o in enumerate(ords, start=1):
            prefix += o
            p = min(prefix, max_p)
            total += math.comb(j + p - 1, j)
            terms.append(math.lgamma(j + p) - math.lgamma(j + 1)
                         - math.lgamma(p))
        if not ords:
            continue
        if total >= SAT:
            exact[v] = SAT
            logs[v] = LOG_SAT
        else:
            exact[v] = total
            m = max(terms)
            logs[v] = m + math.log(sum(math.exp(t - m) for t in terms))
    return exact, logs
