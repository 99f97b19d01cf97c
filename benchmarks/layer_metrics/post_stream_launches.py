"""Executor: programs the eager tail launches per query — executions whose
launch lies between the end of an `engine.stream` host span and the end of
the `engine.execute` span around it, over such intervals in the traced
stretch (`span_reduce.post_stream_launches`).  A count of the device
trace: what a fused tail would bring down."""

import span_reduce      # benchmarks/ is on the path of every reader


def read(ctx):
    path = span_reduce.xplane_of(ctx)
    return span_reduce.post_stream_launches(path) if path else None
