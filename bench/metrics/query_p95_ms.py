"""95th percentile latency, due time to answer, over every request of the
window; a request without an answer sits in the tail."""

from driver import latencies, percentile


def read(ctx):
    lat = latencies(ctx.result)
    return percentile(lat, 95) * 1e3 if lat else None
