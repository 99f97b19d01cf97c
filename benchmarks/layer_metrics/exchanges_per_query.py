"""Exchange: exchanges executed per query of the window — growth of
`engine.exchange.shuffles` + `engine.exchange.broadcasts`.  A count: it
repeats exactly (the plan's `verify.plan_exchanges`)."""

COUNTERS = ("engine.exchange.shuffles", "engine.exchange.broadcasts")


def growth_per_query(ctx, counters):
    """Growth of the sum of ``counters`` from `snap_start` to `snap_end`
    over the window's queries; None where the program has none of them."""
    queries = sum(dt is not None for _, _, dt in ctx["loop"].samples)
    c0, c1 = ctx["snap_start"]["counters"], ctx["snap_end"]["counters"]
    if not queries or not any(k in c1 for k in counters):
        return None
    return sum(c1.get(k, 0) - c0.get(k, 0) for k in counters) / queries


def read(ctx):
    return growth_per_query(ctx, COUNTERS)
