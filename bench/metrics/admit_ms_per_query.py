"""Mean duration of a request's admission: ``service.admit`` spans (the
epoch pin, the padded query, the store prefilter's host digest and the
slot's device rows)."""

from readers import mean_ms, spans_in_window


def read(ctx):
    return mean_ms([s.duration_ns for s in
                    spans_in_window(ctx, "service.admit")])
