"""95th percentile of the service's submit-to-admission wait
(``service.queue_wait`` spans)."""

from readers import p95_ms, spans_in_window


def read(ctx):
    spans = spans_in_window(ctx, "service.queue_wait")
    return p95_ms([s.duration_ns / 1e9 for s in spans])
