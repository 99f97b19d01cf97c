"""Streaming scan: what the chunk loop waits for its first chunk (thread
start + first decode + first staging; `engine.stream.first_wait_s`).  The
median over the window's last queries, because the wait has two modes per
process (24 and 57 ms on the v5e host) and a mean would lie between."""

import statistics

import span_reduce      # benchmarks/ is on the path of every reader


def read(ctx):
    waits = span_reduce.per_query_ms(
        ctx, lambda q: span_reduce.hist(
            q, "engine.stream.first_wait_s")[0] or None)
    return statistics.median(waits) if waits else None
