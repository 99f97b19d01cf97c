"""Executor: host self time of the eager tail after the stream — merge of
the partial aggregates, small join, final group-by, sort — per query:
`engine.post_stream_s` (end of the chunk loop to the end of `execute`)
minus `engine.sync_wait_s` (blocked on the device, `sync_wait_ms`).  Exact
while the fused chunk loop never syncs, so that every sync of the plan
lies inside the tail; mean over the window's last queries."""

import statistics

import span_reduce      # benchmarks/ is on the path of every reader


def _tail_s(query):
    tail, count = span_reduce.hist(query, "engine.post_stream_s")
    if not count:
        return None
    return tail - span_reduce.hist(query, "engine.sync_wait_s")[0]


def read(ctx):
    tails = span_reduce.per_query_ms(ctx, _tail_s)
    return statistics.fmean(tails) if tails else None
