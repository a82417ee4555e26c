"""Median latency, due time to answer, over every request of the window."""

from driver import latencies, percentile


def read(ctx):
    lat = latencies(ctx.result)
    return percentile(lat, 50) * 1e3 if lat else None
