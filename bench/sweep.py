#!/usr/bin/env python3
"""Find the rate a cell's system sustains: a load sweep on the chip.

    python bench/sweep.py --workload hprd.read --seed 3 --steps 0.7 0.8 0.9 1.0

One process builds the cell's system and warms it up as a run does, then
measures its capacity in a closed loop (the queue kept at ``--backlog``
requests for ``--seconds``), then offers open-loop traffic at each
``--steps`` multiple of that capacity for ``--seconds`` each, draining
between steps.  Update batches of the cell's mix land throughout.  Each
step prints one JSON line: offered and completed rate, latency median and
95th percentile, and the backlog left when the step closed.  The rates a
mix file states are set from such a sweep; runs never search for one.

``--profile-dump DIR`` also profiles a short window and writes what the
trace holds (planes, lines, busiest event names) and a small slice of its
events to ``DIR``, for checking the trace reduction against a real trace.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import datagen  # noqa: E402
import run as bench_run  # noqa: E402
from driver import latencies, percentile, run_window  # noqa: E402
from spec import Benchmark  # noqa: E402


def closed_loop(svc, qgraphs, picks, seconds, backlog, cap, batches,
                apply_update):
    """Completions per second with ``backlog`` requests always waiting."""
    t0 = time.perf_counter()
    done = sent = i_upd = 0
    while time.perf_counter() - t0 < seconds:
        while i_upd < len(batches) and batches[i_upd].due <= time.perf_counter() - t0:
            apply_update(batches[i_upd])
            i_upd += 1
        while len(svc.queue) + svc.n_active < backlog:
            svc.submit(qgraphs[picks[sent % len(picks)]], cap)
            sent += 1
        done += len(svc.tick())
    rate = done / (time.perf_counter() - t0)
    svc.run_to_completion()
    return rate


def profile_dump(svc, qgraphs, picks, cap, out_dir: Path, jax):
    from trace_reduce import read_xplane, reduce_events

    from jax.profiler import ProfileData

    d = tempfile.mkdtemp(prefix="sweep_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    arrivals = datagen.arrival_times(4.0, 3.0, 11)
    jax.profiler.start_trace(d, profiler_options=opts)
    run_window(svc, qgraphs, arrivals, picks[: len(arrivals)], [], 3.0,
               cap=cap, apply_update=None,
               annotate=jax.profiler.TraceAnnotation)
    jax.profiler.stop_trace()
    path = str(sorted(Path(d).rglob("*.xplane.pb"))[-1])
    summary = {"bytes": os.path.getsize(path), "planes": []}
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            names = {}
            first = last = None
            for ev in line.events:
                names[ev.name] = names.get(ev.name, 0) + ev.duration_ns
                first = ev.start_ns if first is None else min(first, ev.start_ns)
                last = ev.start_ns if last is None else max(last, ev.start_ns)
            top = sorted(names.items(), key=lambda kv: -kv[1])[:25]
            lines.append({"line": line.name, "n_names": len(names),
                          "first_ns": first, "last_ns": last, "top": top})
        summary["planes"].append({"plane": plane.name, "lines": lines})
    events = read_xplane(path)
    red = reduce_events(events)
    summary["reduced"] = None if red is None else red.__dict__
    (out_dir / "trace_summary.json").write_text(json.dumps(summary, indent=1))
    # a small slice of the flattened events, to check the reduction by hand
    notes = sorted(e["start_ns"] for e in events if e["name"].startswith("bench."))
    mid = notes[len(notes) // 2]
    small = [e for e in events if mid <= e["start_ns"] <= mid + 50e6]
    (out_dir / "trace_small.json").write_text(json.dumps(small))
    print(json.dumps({"profile": red.__dict__ if red else None}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--backlog", type=int, default=16)
    ap.add_argument("--closed-seconds", type=float, default=None,
                    help="length of the closed-loop capacity step "
                         "(default --seconds)")
    ap.add_argument("--steps", type=float, nargs="*", default=[0.8, 1.0])
    ap.add_argument("--profile-dump", default=None)
    args = ap.parse_args(argv)

    bench = Benchmark(bench_run.ROOT)
    cell = bench.cell(args.workload)
    sys.path.insert(0, str(bench_run.ROOT / "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(bench_run.ROOT / ".jax_cache")
    import jax

    bench_run.use_compile_cache(jax)
    dev = bench_run.device_info(jax)
    if dev["platform"] != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 1
    from repro.serve.graph_service import AdmissionRejected

    closed_s = args.closed_seconds or args.seconds
    total = closed_s + args.seconds * (1 + len(args.steps)) + 120
    t = time.perf_counter()
    su = bench_run.Setup(bench, cell, args.seed, total)
    svc, qgraphs, cap, batches = su.svc, su.qgraphs, su.cap, su.batches
    print(json.dumps({"workload": args.workload, "device": dev,
                      "setup_s": time.perf_counter() - T_START,
                      "build_and_warmup_s": time.perf_counter() - t}),
          flush=True)

    used = [0]

    def apply_update(b):
        used[0] += 1
        return su.apply_update(b)

    def next_batches(seconds):
        """The unused part of the stream, due from 0 in this step."""
        if not batches:
            return []
        rate = float(su.mix["updates"]["rate"])
        n = int(seconds * rate)
        out = batches[used[0]: used[0] + n]
        for k, b in enumerate(out):
            b.due = (k + 0.5) / rate
        return out

    picks = datagen.query_picks(100000, len(su.pool), args.seed)
    mu = closed_loop(svc, qgraphs, picks, closed_s, args.backlog, cap,
                     next_batches(closed_s), apply_update)
    print(json.dumps({"capacity_qps": mu, "backlog": args.backlog,
                      "seconds": closed_s}), flush=True)
    for step in args.steps if mu > 0 else ():
        rate = step * mu
        arrivals = datagen.arrival_times(rate, args.seconds, args.seed + 1)
        res = run_window(svc, qgraphs, arrivals,
                         picks[: len(arrivals)], next_batches(args.seconds),
                         args.seconds, cap=cap, apply_update=apply_update,
                         rejected=(AdmissionRejected,), drain_s=120.0)
        lat = latencies(res)
        in_time = [r for r in res.requests
                   if r.done is not None and r.done <= res.seconds]
        left = len(res.requests) - len(in_time)
        print(json.dumps({
            "step": step, "offered_qps": rate,
            "completed_qps": len(in_time) / res.seconds,
            "p50_ms": percentile(lat, 50) * 1e3,
            "p95_ms": percentile(lat, 95) * 1e3,
            "left_at_close": left, "n": len(res.requests),
            "updates": len(res.updates),
            "update_p95_ms": (percentile([u.done - u.due for u in res.updates], 95)
                              * 1e3 if res.updates else None),
            "late_max_ms": max(res.late) * 1e3 if res.late else None,
        }), flush=True)
    if args.profile_dump:
        out = Path(args.profile_dump)
        out.mkdir(parents=True, exist_ok=True)
        profile_dump(svc, qgraphs, picks, cap, out, jax)
    svc.shutdown(drain=False)
    return 0


if __name__ == "__main__":
    sys.exit(main())
