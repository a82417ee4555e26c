"""Mean latency, due time to answer, over every request of the window; a
request without an answer counts as answered at the drain's end."""

from driver import latencies


def read(ctx):
    lat = latencies(ctx.result)
    return sum(lat) / len(lat) * 1e3 if lat else None
