"""Client / bridge, the server's side: per query, the time the server
spends on the window's requests outside the queries themselves — frame
I/O, deserialize, verify, export, release.  Growth of the process-wide
`bridge.op.<name>_s` timers (header received -> reply written; without
`bridge.op.metrics`, which the benchmark's own snapshots cause) minus the
growth of `engine.query.wall_s`, over the queries.  Process-wide growth:
any number of clients."""

import span_reduce      # benchmarks/ is on the path of every reader

OWN_OPS = ("bridge.op.metrics_s",)


def read(ctx):
    wall_s, queries = span_reduce.hist_growth(ctx, "engine.query.wall_s")
    ops = [k for k in ctx["snap_end"].get("histograms", {})
           if k.startswith("bridge.op.") and k.endswith("_s")
           and k not in OWN_OPS]
    if not queries or not ops:
        return None
    op_s = sum(span_reduce.hist_growth(ctx, k)[0] for k in ops)
    return (op_s - wall_s) / queries * 1e3
