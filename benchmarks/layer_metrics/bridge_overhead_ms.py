"""Client / bridge layer: what a query costs outside the server's own
execution.  Median over the window's last queries of (the client's
latency for execute_plan + export_host) - (that query's server `wall_s`,
OP_METRICS `queries`).  The server keeps its last 32 summaries; a single
closed-loop client sends in order, so the i-th last summary of the
client's trace is the i-th last sample."""

import statistics


def read(ctx):
    trace_ids = {c.trace_id for c in ctx["loop"].clients}
    walls = [q["wall_s"] for q in ctx["snap_end"].get("queries", ())
             if q.get("trace_id") in trace_ids]
    if len(trace_ids) != 1 or not walls:
        return None         # several clients interleave: no order to match
    lat = [dt for _, _, dt in ctx["loop"].samples if dt is not None]
    n = min(len(walls), len(lat))
    if n == 0 or len(lat) != len(ctx["loop"].samples):
        return None
    return statistics.median(
        (c - s) * 1e3 for c, s in zip(lat[-n:], walls[-n:]))
