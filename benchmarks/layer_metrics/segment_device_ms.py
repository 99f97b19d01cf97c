"""Fused chunk segment: device time per query of the program executions
launched under the host scope `engine.fused_segment`
(`trace_reduce.scope_s_per_query`: their share of the traced stretch times
the window's mean time per query)."""

import trace_reduce     # benchmarks/ is on the path of every reader

SCOPE = "engine.fused_segment"


def read(ctx):
    s = trace_reduce.scope_s_per_query(ctx["trace"], SCOPE, ctx["loop"])
    return None if s is None else s * 1e3
