"""Helpers shared by the metric readers in ``metrics/``."""

from __future__ import annotations

from driver import percentile


def spans_in_window(ctx, name: str):
    """Closed spans called ``name`` that started inside the window."""
    lo = ctx.window_t0 * 1e9
    hi = (ctx.window_t0 + ctx.seconds) * 1e9
    return [s for s in ctx.spans or () if s.name == name
            and s.end_ns is not None and lo <= s.start_ns < hi]


def mean_ms(durations_ns):
    n = len(durations_ns)
    return sum(durations_ns) / n / 1e6 if n else None


def p95_ms(seconds):
    return percentile(seconds, 95) * 1e3 if seconds else None


def roofline_share(ctx, bench_kernel: str, work):
    """Percent of the kernel's device time that the chip's peaks would need
    for the job: ``max(ops / peak ops, bytes / peak bandwidth)`` over the
    device time of the kernel's programs, both inside the profiled part.
    ``None`` when the profile holds no such program or call."""
    if ctx.trace is None or ctx.peaks is None or ctx.profile_window is None:
        return None
    t0, t1 = ctx.profile_window
    calls = [c for c in ctx.kernel_calls.get(bench_kernel, ())
             if t0 <= c["t"] <= t1]
    dev_s = sum(ctx.trace.program_s.get(p, 0.0) for p in work.PROGRAMS)
    if not calls or dev_s <= 0:
        return None
    ops = byts = 0
    for c in calls:
        o, b = work.work(c)
        ops += o
        byts += b
    t_ops = ops / ctx.peaks["ops_per_s"]
    t_bytes = byts / ctx.peaks["hbm_bytes_per_s"]
    ctx.log(f"{bench_kernel}_roofline: {len(calls)} calls, {ops} ops, "
            f"{byts} bytes, device {dev_s:.6f} s, bound by "
            f"{'operations' if t_ops >= t_bytes else 'bytes'}")
    return 100.0 * max(t_ops, t_bytes) / dev_s
