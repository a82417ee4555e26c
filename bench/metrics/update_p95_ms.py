"""95th percentile, over the window's update batches, of the time from a
batch's due time until both of its calls (deletes, inserts) returned."""

from readers import p95_ms


def read(ctx):
    return p95_ms([u.done - u.due for u in ctx.updates])
