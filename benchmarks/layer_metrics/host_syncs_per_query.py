"""Executor: deliberate host syncs (`engine.host_sync`) per query of the
window.  A count: it repeats exactly."""


def read(ctx):
    queries = sum(dt is not None for _, _, dt in ctx["loop"].samples)
    if not queries:
        return None
    delta = ctx["snap_end"]["counters"].get("engine.host_sync", 0) \
        - ctx["snap_start"]["counters"].get("engine.host_sync", 0)
    return delta / queries
