"""Mean duration of a query's enumeration: ``query.enumerate`` spans."""

from readers import mean_ms, spans_in_window


def read(ctx):
    return mean_ms([s.duration_ns for s in
                    spans_in_window(ctx, "query.enumerate")])
