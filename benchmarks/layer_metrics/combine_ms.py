"""Executor: host wall time per query inside the merges of the streamed
partial aggregates — growth of the process-wide histogram
`engine.combine_s` (one observation per launch of the merge program: the
folds of a long stream and the final merge) over the window's completed
queries.  Dispatch time: the sizing waits before a merge are
`engine.sync_wait`'s.  A program whose `engine.combine` span is not timed
gives nothing to read."""

import span_reduce      # benchmarks/ is on the path of every reader


def read(ctx):
    queries = sum(dt is not None for _, _, dt in ctx["loop"].samples)
    seconds, merges = span_reduce.hist_growth(ctx, "engine.combine_s")
    if not queries or not merges:
        return None
    return seconds / queries * 1e3
