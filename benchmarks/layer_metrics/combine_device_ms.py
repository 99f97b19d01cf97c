"""Fused chunk segment: device time per query of the merge program's
executions — those launched under the host scope `engine.combine`
(`trace_reduce.scope_s_per_query`, as `segment_device_ms` reads the chunk
program's scope).  No launch under that scope in the traced stretch:
nothing to read."""

import trace_reduce     # benchmarks/ is on the path of every reader

SCOPE = "engine.combine"


def read(ctx):
    s = trace_reduce.scope_s_per_query(ctx["trace"], SCOPE, ctx["loop"])
    return None if s is None else s * 1e3
