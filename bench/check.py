"""The comparison that decides ``correct``.

Every answer due in the window is held against the plain reference on the
graph of the epoch it was pinned to:

* ``wrong_results``: answers that are not exactly the reference's set of
  embeddings (a row that is no embedding, a repeated row, or a count other
  than ``min(cap, total)``), or whose pinned epoch is not one the graph
  passed through between the request's submission and its answer
  (snapshot isolation).  Limit 0.
* ``unanswered``: requests due in the window that had no answer when the
  drain gave up.  Rejections are counted as failed, not here.  Limit 0.

With updates, the maintained index at the end is held against the
reference's rebuild from the final edge set:

* ``index_mismatch``: vertices whose neighbor-label counts, degree or exact
  (saturated) CNI differ.  Limit 0.
* ``log_digest_err``: the largest relative error of a maintained float32
  log digest against the reference's float64 one, over the vertices whose
  CNI is below saturation (a saturated digest is one constant on both
  sides and tests nothing); with no such vertex it reads ``SUPPORT_DIFFERS``.
  Limit from the readings in PERF.md.
"""

from __future__ import annotations

import numpy as np

# Largest relative error of a maintained log digest (float32) against the
# float64 reference.  Set from the program's and the bfloat16 control's
# readings (PERF.md, section 7, says where each was read).
LOG_DIGEST_LIMIT = 1e-4
# reading of a log digest that is finite on one side only
SUPPORT_DIFFERS = 1e9


def epoch_index(base_epoch: int, updates) -> dict[int, int]:
    """Service epoch -> number of update batches landed by then."""
    out = {base_epoch: 0}
    for k, u in enumerate(updates, start=1):
        out[u.epoch] = k
    return out


def landed_before(updates, t: float) -> int:
    return sum(1 for u in updates if u.done is not None and u.done <= t)


def compare(ref, graph, pool, batches, res, *, base_epoch, cap, answer=None):
    """Count wrong and unanswered answers of one window.

    ``ref``: the reference module; ``graph``/``pool``/``batches``: what the
    run was fed; ``res``: the driver's ``WindowResult``.  ``answer(rec)``
    gives a request's ``(embeddings, pinned epoch)``; the control passes
    its own."""
    epochs = epoch_index(base_epoch, res.updates)
    wrong = unanswered = 0
    todo: dict[int, list] = {}
    for rec in res.requests:
        if rec.rejected:
            continue
        if rec.done is None:
            unanswered += 1
            continue
        emb, epoch = answer(rec)
        k = epochs.get(epoch)
        if (k is None or k < landed_before(res.updates, rec.submitted)
                or k > landed_before(res.updates, rec.done)):
            wrong += 1
            continue
        todo.setdefault(k, []).append((rec.qid, emb))
    g = ref.EpochGraph(graph.n_vertices, graph.vlabels, graph.lo, graph.hi,
                       graph.elabels)
    for k in range(len(res.updates) + 1):
        if k:
            g.remove(batches[k - 1].deletes)
            g.insert(batches[k - 1].inserts, batches[k - 1].insert_labels)
        counts: dict[int, int] = {}
        for qid, emb in todo.get(k, ()):
            q = pool[qid]
            if qid not in counts:
                counts[qid] = len(ref.embeddings(g, q.vlabels, q.edges,
                                                 q.elabels, cap))
            rows = np.asarray(emb).reshape(-1, q.n_vertices)
            if (rows.shape[0] != counts[qid]
                    or ref.invalid_rows(g, q.vlabels, q.edges, q.elabels,
                                        rows)):
                wrong += 1
    return {"wrong_results": wrong, "unanswered": unanswered}, g


def compare_index(ref, g, index, *, max_p: int) -> dict:
    """Maintained index (``counts``, ``deg``, ``cni_u64``, ``cni_log`` as
    numpy, and its ``max_p``) against the reference's rebuild of the final
    edge set with the configuration's ``max_p``.  A maintained index on
    another ``max_p`` mismatches at every vertex."""
    universe = np.unique(np.asarray(g.vlabels))
    counts = ref.label_counts(g, universe)
    exact, logs = ref.cni_digests(counts, max_p)
    bad = ((np.asarray(index["counts"]) != counts).any(axis=1)
           | (np.asarray(index["deg"]) != counts.sum(axis=1))
           | (np.asarray(index["cni_u64"]).astype(np.uint64) != exact))
    if int(index["max_p"]) != max_p:
        bad[:] = True
    return {"index_mismatch": int(bad.sum()),
            "log_digest_err": log_digest_err(index["cni_log"], logs,
                                             exact == ref.SAT)}


def index_max_p(max_degree: int, n_labels: int, cap: int) -> int:
    """The encoding's prefix bound: the Pascal table's degree bound is the
    starting maximum degree rounded up to a power of two (at least 4)."""
    d = 4
    while d < max_degree:
        d *= 2
    return min(d * n_labels, cap)


def log_digest_err(got, want, saturated) -> float:
    """Over the digests below saturation (``saturated`` False)."""
    got = np.asarray(got, np.float64)
    fin = np.isfinite(want)
    if not np.array_equal(np.isfinite(got), fin):
        return SUPPORT_DIFFERS
    live = fin & ~np.asarray(saturated, bool)
    if not live.any():
        return SUPPORT_DIFFERS
    return float(np.max(np.abs(got[live] - want[live])
                        / np.maximum(1.0, np.abs(want[live]))))


def limits(with_index: bool) -> dict:
    out = {"wrong_results": 0, "unanswered": 0}
    if with_index:
        out.update(index_mismatch=0, log_digest_err=LOG_DIGEST_LIMIT)
    return out


def verdict(readings: dict, lim: dict) -> bool:
    return all(readings[k] <= lim[k] for k in lim)
