"""Exchange: bytes per query that cross the interconnect — growth of
`engine.exchange.wire_bytes`: every padded slot of a hash exchange's
all-to-all and `ndev - 1` copies of a broadcast's table, live rows or
not.  A count of shapes: it repeats exactly."""

from layer_metrics.exchanges_per_query import growth_per_query


def read(ctx):
    return growth_per_query(ctx, ("engine.exchange.wire_bytes",))
