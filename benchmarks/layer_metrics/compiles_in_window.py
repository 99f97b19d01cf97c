"""Programs compiled and caches missed inside the measured window
(`engine.segment.compile` and the `*_cache.miss` counters): expected 0."""


def read(ctx):
    return ctx["compile_count"](ctx["snap_end"]) \
        - ctx["compile_count"](ctx["snap_start"])
