"""Mean number of points at which a query's join blocked the host on a
device value (``EnumReport.host_syncs``), over the window's answered
requests whose report carries the counter."""


def read(ctx):
    syncs = [r.stats.extras["enum"]["host_syncs"] for r in ctx.requests
             if r.done is not None and not r.rejected
             and "host_syncs" in r.stats.extras.get("enum", ())]
    return sum(syncs) / len(syncs) if syncs else None
