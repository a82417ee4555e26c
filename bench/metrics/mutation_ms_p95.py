"""95th percentile time inside one update batch's two calls
(``remove_edges`` then ``add_edges``), timed by the benchmark."""

from readers import p95_ms


def read(ctx):
    return p95_ms([u.done - u.started for u in ctx.updates])
