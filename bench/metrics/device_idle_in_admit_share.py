"""Percent of the profiled window in which the first device idled while
the host was inside ``service.admit``: ``idle_s_by_annotation`` of a
profile reduced by ``trace_spans``.  ``0.0`` when such a profile holds no
idle time in admission; ``None`` without one."""


def read(ctx):
    idle = getattr(ctx.trace, "idle_s_by_annotation", None)
    if idle is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * idle.get("service.admit", 0.0) / ctx.trace.window_s
