"""Streaming scan, the producer thread's second half: pack, the one
`device_put` and the unpack's dispatch of one chunk (`io.scan.stage_s`,
sum over count), over the window's last queries.  The put is
asynchronous: this is host time, not the link's."""

import span_reduce      # benchmarks/ is on the path of every reader


def read(ctx):
    return span_reduce.per_occurrence_ms(ctx, "io.scan.stage")
