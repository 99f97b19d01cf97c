"""Scheduler: how many plans the server really runs at once — growth of
the histogram `engine.sched.live_sessions` (observed once per admission
with the number of live sessions, the new one included), sum over count:
the mean over the window's admissions.  1.0 with four clients would mean
the bridge serialises them.  A program without the histogram gives
nothing to read."""

import span_reduce      # benchmarks/ is on the path of every reader


def read(ctx):
    total, admissions = span_reduce.hist_growth(
        ctx, "engine.sched.live_sessions")
    return total / admissions if admissions else None
