"""Executor: how long a query's host blocks on the device at its
deliberate syncs (`engine.sync_wait_s`, one span per `engine.host_sync`),
mean over the window's last queries.  Large against `post_stream_ms`: the
device was the longer side of the stream; near 0: the host was."""

import statistics

import span_reduce      # benchmarks/ is on the path of every reader


def _wait_s(query):
    seconds, count = span_reduce.hist(query, "engine.sync_wait_s")
    return seconds if count else None


def read(ctx):
    waits = span_reduce.per_query_ms(ctx, _wait_s)
    return statistics.fmean(waits) if waits else None
