"""Relational kernels: the share of the HBM roofline the fused chunk
segment reaches.  Bytes its work needs per query — the query module's
`chunk_bytes_needed` (the chunk's columns in, the partial aggregate out,
whatever implements it) times the segment's executions per query
(`engine.segment.replay` + `.compile` over the window's queries) — over
the chip's published HBM rate, over the segment's device time per query
(`segment_device_ms`'s arithmetic).  Memory-bound: the work is compare,
hash and add over 8-byte columns, far under the FLOP peak's share."""

import trace_reduce     # benchmarks/ is on the path of every reader

SCOPE = "engine.fused_segment"


def read(ctx):
    device_s = trace_reduce.scope_s_per_query(ctx["trace"], SCOPE,
                                              ctx["loop"])
    rows = ctx["snap_end"]["histograms"].get("engine.stream.chunk_rows")
    c0, c1 = ctx["snap_start"]["counters"], ctx["snap_end"]["counters"]
    runs = sum(c1.get(k, 0) - c0.get(k, 0)
               for k in ("engine.segment.replay", "engine.segment.compile"))
    if device_s is None or not runs or not rows or not rows["count"]:
        return None
    kind = ctx["device"]["kind"]
    if kind not in ctx["peaks"]:
        raise KeyError(f"no published peak for device kind {kind!r} in "
                       "peaks.json")
    queries = sum(dt is not None for _, _, dt in ctx["loop"].samples)
    need = ctx["cell"].query.chunk_bytes_needed(
        rows["sum"] / rows["count"], ctx["cell"].rows(rehearsal=False)) \
        * runs / queries
    return need / ctx["peaks"][kind]["hbm_bytes_per_s"] / device_s * 100.0
