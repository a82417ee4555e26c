#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``.

    python bench/control.py --workload hprd.churn --seeds 11 12 13

The plain reference is put in the program's place and made to break the
exactness the configuration states: it answers every request with only
the first embedding it finds (an existence check where the configuration
asks for every embedding up to the cap).  Each request is answered on the
epoch current at its due time, at the cell's own size and load (the same
data, pool, arrivals and update stream as a run of that seed).  The answers
then go through the run's own comparison (``check.compare``), which has to
find them wrong.

With updates, the index's log digest is also read at the next precision
below the configuration's float32: the reference digests rounded to
bfloat16, against the float64 reference.

Prints one JSON line per seed with the numbers compared.  The benchmark's
own runs never run this; it needs no accelerator.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import check  # noqa: E402
import datagen  # noqa: E402
from driver import RequestRecord, UpdateRecord, WindowResult  # noqa: E402
from spec import Benchmark  # noqa: E402


def control_readings(bench: Benchmark, cell: dict, seed: int,
                     seconds: float) -> dict:
    cfg = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    ref = bench.reference(cfg["reference"])
    graph = datagen.make_graph(cfg["dataset"])
    pool = datagen.make_pool(graph, graph.adjacency(), mix["pool"])
    arrivals, picks = datagen.schedule(mix, seconds, seed, len(pool))
    degree_cap = int(cfg["store"]["degree_cap"])
    batches = (datagen.update_stream(graph, mix["updates"], seconds, seed,
                                     degree_cap)
               if mix.get("updates") else [])
    cap = int(mix["max_embeddings"])

    # an idealised schedule: each batch lands, and each request is answered,
    # the moment it falls due; batch k makes epoch 2k (deletes, then inserts)
    upds = [UpdateRecord(b.due, b.due, b.due, 2 * (k + 1))
            for k, b in enumerate(batches)]
    reqs = [RequestRecord(int(q), float(t), float(t), i, float(t))
            for i, (t, q) in enumerate(zip(arrivals, picks))]
    res = WindowResult(reqs, upds, seconds, seconds)
    g = ref.EpochGraph(graph.n_vertices, graph.vlabels, graph.lo, graph.hi,
                       graph.elabels)
    answers, k = {}, 0
    for rec in sorted(reqs, key=lambda r: r.due):
        while k < len(batches) and batches[k].due <= rec.due:
            g.remove(batches[k].deletes)
            g.insert(batches[k].inserts, batches[k].insert_labels)
            k += 1
        q = pool[rec.qid]
        rows = ref.embeddings(g, q.vlabels, q.edges, q.elabels, cap)[:1]
        answers[rec.rid] = (np.asarray(rows, np.int64).reshape(-1, q.n_vertices),
                            2 * k)
    readings, final = check.compare(
        ref, graph, pool, batches, res, base_epoch=0, cap=cap,
        answer=lambda r: answers[r.rid])
    if batches:
        import ml_dtypes

        counts = ref.label_counts(final, np.unique(graph.vlabels))
        exact, logs = ref.cni_digests(counts, check.index_max_p(
            degree_cap, len(np.unique(graph.vlabels)),
            int(cfg["store"]["max_p_cap"])))
        low = logs.astype(ml_dtypes.bfloat16).astype(np.float64)
        readings["log_digest_err"] = check.log_digest_err(
            low, logs, exact == ref.SAT)
    return readings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: the benchmark's)")
    args = ap.parse_args(argv)
    bench = Benchmark(BENCH.parent)
    cell = bench.cell(args.workload)
    seconds = args.seconds or float(bench.data["run_seconds"])
    for seed in args.seeds:
        readings = control_readings(bench, cell, seed, seconds)
        limits = check.limits("log_digest_err" in readings)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "readings": readings,
                          "correct": check.verdict(readings, limits)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
