"""Executor: host self time of the tail after the stream, per streamed
query — growth of `engine.post_stream_s` (end of the chunk loop to the
end of `execute`) minus growth of `engine.post_stream.sync_wait_s` (the
`engine.sync_wait` seconds stamped after the stream's end, one observation
per query), over the tails observed.  Where the stream itself never waits
this is `post_stream_ms`; where a long stream folds, that reader also
subtracts the folds' waits — which lie inside the stream — and reads low,
and this one does not: it is never negative.  A program without the second
histogram gives nothing to read."""

import span_reduce      # benchmarks/ is on the path of every reader


def read(ctx):
    tail_s, tails = span_reduce.hist_growth(ctx, "engine.post_stream_s")
    wait_s, waits = span_reduce.hist_growth(
        ctx, "engine.post_stream.sync_wait_s")
    if not tails or not waits:
        return None
    return (tail_s - wait_s) / tails * 1e3
