"""Client / bridge: per query, how long the server's connections stood
waiting for their client between a reply and the next request — growth of
the process-wide histogram `bridge.conn.idle_s` (reply written -> the next
request read, on a connection that has served a request; all of a query's
requests — execute, export, free, release — on all connections) over the
window's completed queries.  In a closed loop that is the client's own
work between requests plus the socket, measured from the server's side.
The wait after a metrics poll is no turnaround and is not observed, so the
harness's own connection, idle for the whole window, is not in it.  A
program without the span gives nothing to read."""

import span_reduce      # benchmarks/ is on the path of every reader


def read(ctx):
    queries = sum(dt is not None for _, _, dt in ctx["loop"].samples)
    seconds, waits = span_reduce.hist_growth(ctx, "bridge.conn.idle_s")
    if not queries or not waits:
        return None
    return seconds / queries * 1e3
