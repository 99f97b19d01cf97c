"""Relational kernels: the share of the HBM roofline the rank probe
reaches inside the fused chunk program.  Bytes it needs per query — the
query module's `probe_bytes_needed` (the probe keys in, the build's keys
and payload once, the matched row, its payload and the match mask out,
whatever implements it) times the rank probes run per query (the growth of
`engine.probe.rank` over the window's queries) — over the chip's published
HBM rate, over the device time per query of the ops the chunk program runs
under the probe's own scope, `engine.fused_segment/probe_rank` of the
trace's op table (their share of the traced stretch times the window's
seconds per query).  The segment runs on one chip, so one chip's peak is
the divisor.  Nothing to read where no chunk program probed by rank."""

SCOPE = "engine.fused_segment/probe_rank"


def read(ctx):
    reduced, loop = ctx["trace"], ctx["loop"]
    queries = sum(dt is not None for _, _, dt in loop.samples)
    c0, c1 = ctx["snap_start"]["counters"], ctx["snap_end"]["counters"]
    probes = c1.get("engine.probe.rank", 0) - c0.get("engine.probe.rank", 0)
    rows = ctx["snap_end"]["histograms"].get("engine.stream.chunk_rows")
    query = ctx["cell"].query
    if not reduced or not queries or probes <= 0 or not rows \
            or not rows["count"] or not hasattr(query, "probe_bytes_needed"):
        return None
    op_s = sum(s for k, s in reduced["ops"].items()
               if k == SCOPE or k.startswith(SCOPE + "/"))
    if op_s <= 0:
        return None
    device_s = op_s / reduced["window_s"] * (loop.t_end - loop.t_start) \
        / queries
    kind = ctx["device"]["kind"]
    if kind not in ctx["peaks"]:
        raise KeyError(f"no published peak for device kind {kind!r} in "
                       "peaks.json")
    need = query.probe_bytes_needed(
        rows["sum"] / rows["count"], ctx["cell"].rows(rehearsal=False)) \
        * probes / queries
    return need / ctx["peaks"][kind]["hbm_bytes_per_s"] / device_s * 100.0
