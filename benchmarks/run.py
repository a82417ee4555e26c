"""Benchmark harness: one section per paper table/figure + roofline report.

    PYTHONPATH=src python -m benchmarks.run [--smoke] [--section NAME]
                                            [--json PATH]

Prints ``name,us_per_call,derived`` CSV rows.  Sections:
    graph    — the paper's experiments (Figs 7-11 analogues, §4)
    batch    — batched multi-query + serving throughput (batch_engine)
    update   — dynamic-graph store: incremental index maintenance throughput
    planner  — cost-based matching orders vs greedy + plan-cache hit rate
    enum     — two-phase device-resident join enumeration vs the chunked
               host join (incl. bit-parity canary and the overflow regime
               that used to require a host fallback), plus the
               mesh-partitioned enumerator at 1/2/4 forced host devices
               (subprocess per device count, hard parity canary,
               per-level rebalance timings in the JSON artifact)
    shard    — vertex-partitioned engine scaling across 1/2/4 devices
               (each device count in a subprocess with
               ``--xla_force_host_platform_device_count``)
    ooc      — out-of-core disk tier vs the in-memory engine: overlap
               regime with a hard bit-parity canary, plus a graph ~10-20x
               the resident chunk-cache budget (prefiltered chunk access,
               cache high-water vs cap in the derived column)
    serve    — admission-controlled service saturation: 10x-overload waves
               against the bounded submit path (queue depth must stay
               under max_queue_depth, excess surfaces as typed
               rejections), per-stage queue/filter/search/e2e p50+p99,
               and the durable-snapshot overhead on the mutation path
    kernels  — kernel-path microbenchmarks
    roofline — derived terms from the dry-run artifacts (if present)

``--smoke`` shrinks the selected sections to tiny regression canaries for
CI (``--smoke`` alone = batch + update + planner + enum + ooc + serve
canaries on every push — the enum canary hard-asserts bit parity and
host_levels == 0, the serve canary hard-asserts the queue-depth bound; the
shard canary runs as its own CI step via ``--section shard --smoke``, and
enum also keeps a dedicated step for its per-phase JSON artifact).
``--json PATH`` additionally writes the emitted rows as a JSON list —
CI uploads these as ``BENCH_*.json`` workflow artifacts so the smoke
trajectory is inspectable per commit.  ``--trace PATH`` runs the
selected sections under an active ``obsv`` tracer and writes the
resulting span tree as Chrome/Perfetto trace JSON (``TRACE_*.json`` in
CI) next to the bench rows — load it in https://ui.perfetto.dev.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys

_COLLECTED: list[tuple[str, float, str]] = []


def _emit(rows):
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    _COLLECTED.extend(rows)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--section", default="all",
                    choices=["all", "graph", "batch", "update", "planner",
                             "enum", "ooc", "serve", "shard", "kernels",
                             "roofline"])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny canary benches only (CI jit-regression check)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as JSON (CI workflow artifact)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="run sections under an obsv tracer and write the "
                         "span tree as Chrome/Perfetto trace JSON")
    args = ap.parse_args()

    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    tracer_cm = contextlib.nullcontext(None)
    if args.trace:
        from repro import obsv

        tracer_cm = obsv.tracing()
    with tracer_cm as tracer:
        _run_sections(args)
    if args.trace:
        tracer.write_chrome_trace(args.trace)
        print(f"wrote {len(tracer.spans)} spans to {args.trace}",
              file=sys.stderr)


def _run_sections(args) -> None:
    print("name,us_per_call,derived")
    if args.smoke:
        if args.section in ("all", "batch"):
            from benchmarks.batch_benches import run_all as batch_all

            _emit(batch_all(smoke=True))
        if args.section in ("all", "update"):
            from benchmarks.update_benches import run_all as update_all

            _emit(update_all(smoke=True))
        if args.section in ("all", "planner"):
            from benchmarks.planner_benches import run_all as planner_all

            _emit(planner_all(smoke=True))
        if args.section in ("all", "enum"):
            from benchmarks.enum_benches import run_all as enum_all

            _emit(enum_all(smoke=True))
        if args.section in ("all", "ooc"):
            from benchmarks.ooc_benches import run_all as ooc_all

            _emit(ooc_all(smoke=True))
        if args.section in ("all", "serve"):
            from benchmarks.serve_benches import run_all as serve_all

            _emit(serve_all(smoke=True))
        if args.section == "shard":  # opt-in: spawns one process per D
            from benchmarks.shard_benches import run_all as shard_all

            _emit(shard_all(smoke=True))
        _write_json(args.json)
        return
    if args.section in ("all", "batch"):
        from benchmarks.batch_benches import run_all as batch_all

        _emit(batch_all())
    if args.section in ("all", "update"):
        from benchmarks.update_benches import run_all as update_all

        _emit(update_all())
    if args.section in ("all", "planner"):
        from benchmarks.planner_benches import run_all as planner_all

        _emit(planner_all())
    if args.section in ("all", "enum"):
        from benchmarks.enum_benches import run_all as enum_all

        _emit(enum_all())
    if args.section in ("all", "ooc"):
        from benchmarks.ooc_benches import run_all as ooc_all

        _emit(ooc_all())
    if args.section in ("all", "serve"):
        from benchmarks.serve_benches import run_all as serve_all

        _emit(serve_all())
    if args.section in ("all", "shard"):
        from benchmarks.shard_benches import run_all as shard_all

        _emit(shard_all())
    if args.section in ("all", "graph"):
        from benchmarks.graph_benches import run_all as graph_all

        _emit(graph_all())
    if args.section in ("all", "kernels"):
        from benchmarks.kernel_benches import run_all as kernel_all

        _emit(kernel_all())
    if args.section in ("all", "roofline"):
        try:
            from repro.launch.roofline import analyze_record, load_records

            rows = []
            for rec in load_records("pod_16x16"):
                if rec.get("status") != "ok":
                    continue
                a = analyze_record(rec)
                dom_s = max(a["compute_s"], a["memory_s"], a["collective_s"])
                rows.append((
                    f"roofline/{rec['arch']}/{rec['shape']}",
                    dom_s * 1e6,
                    f"dominant={a['dominant']};frac={a['roofline_fraction']:.3f};"
                    f"useful={a['useful_ratio']:.2f}",
                ))
            _emit(rows)
        except Exception as e:  # noqa: BLE001 — roofline needs dry-run files
            print(f"roofline/unavailable,0.0,{e}", file=sys.stderr)
    _write_json(args.json)


def _write_json(path: str | None) -> None:
    if not path:
        return
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(
            [
                {"name": name, "us_per_call": us, "derived": derived}
                for name, us, derived in _COLLECTED
            ],
            fh,
            indent=2,
        )
    print(f"wrote {len(_COLLECTED)} rows to {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
