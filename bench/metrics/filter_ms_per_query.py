"""Time of the batched ILGF filter rounds per finalized query: each
dispatch counted once (its span is mirrored into every member request),
over the queries finalized in the window."""

from readers import spans_in_window


def read(ctx):
    rounds = {(s.start_ns, s.end_ns)
              for s in spans_in_window(ctx, "service.filter_round")}
    done = len(spans_in_window(ctx, "service.finalize"))
    if not rounds or not done:
        return None
    return sum(e - s for s, e in rounds) / done / 1e6
