"""Exchange: host wall time per query inside the exchanges — growth of the
process-wide histograms `engine.exchange.hash_s` and
`engine.exchange.broadcast_s` (`op_scope(..., timed=True)` around each
exchange's own work, its two `engine.sync_wait` spans included, its child's
execution not) over the window's queries.  A program without these spans
gives nothing to read."""

import span_reduce      # benchmarks/ is on the path of every reader

SPANS = ("engine.exchange.hash", "engine.exchange.broadcast")


def read(ctx):
    queries = sum(dt is not None for _, _, dt in ctx["loop"].samples)
    grown = [span_reduce.hist_growth(ctx, f"{name}_s") for name in SPANS]
    if not queries or not any(count for _, count in grown):
        return None
    return sum(seconds for seconds, _ in grown) / queries * 1e3
