"""Executor: the dimension side of a streamed query, paid before its first
fact chunk is asked for — growth of the process-wide histogram
`engine.precompute_s` (every scan-independent subtree run once: the
dimension scans' decode and staging, their filters, the builds; inside
`engine.execute`, before `engine.stream` opens) over the window's
completed queries.  A program without the span gives nothing to read."""

import span_reduce      # benchmarks/ is on the path of every reader


def read(ctx):
    queries = sum(dt is not None for _, _, dt in ctx["loop"].samples)
    seconds, runs = span_reduce.hist_growth(ctx, "engine.precompute_s")
    if not queries or not runs:
        return None
    return seconds / queries * 1e3
