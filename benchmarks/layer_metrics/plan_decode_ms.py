"""Client / bridge: what the server spends, per query, turning a
PLAN_EXECUTE payload into a verified plan — growth of the process-wide
histogram `bridge.plan.decode_s` (`op_scope(..., timed=True)` around
deserialize + verify, before the query's `wall_s` starts) over the window's
completed queries.  The span of this name is what `breakdown.idle_gaps`
often names a whole inter-query gap by; this is its own share.  A program
whose span is not timed gives nothing to read."""

import span_reduce      # benchmarks/ is on the path of every reader


def read(ctx):
    queries = sum(dt is not None for _, _, dt in ctx["loop"].samples)
    seconds, decodes = span_reduce.hist_growth(ctx, "bridge.plan.decode_s")
    if not queries or not decodes:
        return None
    return seconds / queries * 1e3
