"""Sharded-engine scaling: partitioned ILGF vs the single-device path.

Each device count runs in its **own subprocess** with
``XLA_FLAGS=--xla_force_host_platform_device_count=<D>`` — the only way to
vary the virtual-device count under one harness invocation, and exactly how
CI exercises the sharded path on CPU-only runners.  Rows:

    shard/ilgf_D=<d>    — vertex-partitioned ILGF fixed point, one query
    shard/round_D=<d>   — one sharded batched peeling round (B slots)
    shard/parity_D=<d>  — derived ok/MISMATCH: sharded alive mask, candidate
                          columns, and round count bit-equal to ``ilgf``

On a multi-core CPU host the virtual devices share the same silicon, so the
interesting signal is that per-round cost stays ~flat while per-device work
drops 1/D (the collective is one bitmask + one count all-reduce); real
scaling shows on accelerator meshes where shards map to separate chips.

``run_all(smoke=True)`` is the CI canary: tiny graph, one repetition.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

_SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

_CHILD = textwrap.dedent(
    """
    import json, os, time
    import numpy as np
    import jax

    from repro.core.batch_engine import stack_queries
    from repro.core.cni import default_max_p
    from repro.core.distributed import (
        device_mesh, distributed_ilgf, prepare_sharded_edges,
        sharded_batched_ilgf_round,
    )
    from repro.core.ilgf import ilgf
    from repro.graphs import random_labeled_graph, random_walk_query
    from repro.graphs.csr import max_degree, to_host

    d = int(os.environ["SHARD_BENCH_DEVICES"])
    smoke = os.environ.get("SHARD_BENCH_SMOKE") == "1"
    assert len(jax.devices()) == d, jax.devices()

    if smoke:
        n_v, n_e, b, reps = 384, 1200, 4, 2
    else:
        n_v, n_e, b, reps = 4096, 16384, 8, 5
    g = random_labeled_graph(n_v, n_e, 8, n_edge_labels=2, seed=0)
    q = random_walk_query(g, 5, sparse=True, seed=1)
    mesh = device_mesh(d)

    def timed(fn):
        fn()  # warmup (trace + compile)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    ref = ilgf(g, q)
    res = distributed_ilgf(g, q, mesh)
    parity = (
        (np.asarray(ref.alive) == np.asarray(res.alive)).all()
        and (np.asarray(ref.candidates) == np.asarray(res.candidates)).all()
        and int(ref.iterations) == int(res.iterations)
    )
    t_ilgf = timed(
        lambda: np.asarray(distributed_ilgf(g, q, mesh).alive)
    )

    d_max = max(1, max_degree(g))
    l_pad = 8
    max_p = default_max_p(d_max, l_pad)
    qs = [random_walk_query(g, 4, seed=10 + i) for i in range(b)]
    qb = stack_queries(qs, to_host(g), d_max, max_p, 8, l_pad, b)
    alive = qb.ords > 0
    se, plan, _ = prepare_sharded_edges(g, mesh)

    def one_round():
        a, c, ch = sharded_batched_ilgf_round(
            se, plan, qb, alive, mesh=mesh, n_labels=l_pad,
            d_max=d_max, max_p=max_p, variant="cni",
        )
        np.asarray(ch)

    t_round = timed(one_round)
    print(json.dumps({
        "devices": d, "t_ilgf": t_ilgf, "t_round": t_round,
        "iters": int(res.iterations), "parity": bool(parity),
        "n_v": n_v, "n_e": n_e, "batch": b,
    }))
    """
)


# the children run on forced CPU host devices, never on the chip: every
# row they produce says so
_CPU_REHEARSAL = "platform=cpu_forced_devices"


def _run_child(devices: int, smoke: bool) -> dict:
    env = dict(os.environ)
    # the parent may hold the accelerator; a chip serves one process
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}"
    )
    env["SHARD_BENCH_DEVICES"] = str(devices)
    env["SHARD_BENCH_SMOKE"] = "1" if smoke else "0"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", _CHILD],
        env=env, capture_output=True, text=True, timeout=1800,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"shard bench child (D={devices}) failed:\n{out.stderr[-2000:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def run_all(*, smoke: bool = False, device_counts=(1, 2, 4)) -> list:
    rows: list = []
    for d in device_counts:
        r = _run_child(d, smoke)
        rows.append((
            f"shard/ilgf_D={d}", r["t_ilgf"] * 1e6,
            f"V={r['n_v']};E={r['n_e']};iters={r['iters']};{_CPU_REHEARSAL}",
        ))
        rows.append((
            f"shard/round_D={d}", r["t_round"] * 1e6,
            f"B={r['batch']};{_CPU_REHEARSAL}",
        ))
        rows.append((
            f"shard/parity_D={d}", 0.0,
            ("ok" if r["parity"] else "MISMATCH") + f";{_CPU_REHEARSAL}",
        ))
    return rows
