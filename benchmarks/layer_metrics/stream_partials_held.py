"""Executor: the most padded per-chunk partial aggregates a streamed
query held on the device at once — growth of the process-wide histogram
`engine.stream.partials_held` (one observation per streamed query), sum
over count: the mean over the window's queries.  Bounded by the merge
program's arity where the stream folds as it runs; the number of chunks
where it does not.  A program without the histogram gives nothing to
read."""

import span_reduce      # benchmarks/ is on the path of every reader


def read(ctx):
    total, queries = span_reduce.hist_growth(
        ctx, "engine.stream.partials_held")
    return total / queries if queries else None
