"""Scheduler: what a query waits at the fair-share gate — growth of the
process-wide histogram `engine.sched.gate_wait_s` (one observation per
chunk boundary at which a session really blocked: its round's credits
spent while another live session still held some) over the window's
completed queries.  A window whose sessions never blocked reads 0; a
server that admitted nothing through the scheduler in the window
(`engine.sched.admitted` did not grow) has no gate: nothing to read."""

import span_reduce      # benchmarks/ is on the path of every reader


def read(ctx):
    queries = sum(dt is not None for _, _, dt in ctx["loop"].samples)
    c0, c1 = ctx["snap_start"]["counters"], ctx["snap_end"]["counters"]
    name = "engine.sched.admitted"
    if not queries or c1.get(name, 0) <= c0.get(name, 0):
        return None
    seconds, _ = span_reduce.hist_growth(ctx, "engine.sched.gate_wait_s")
    return seconds / queries * 1e3
