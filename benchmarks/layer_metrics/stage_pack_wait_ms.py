"""Streaming scan: per chunk, how long the producer's pack stood NOT
running — growth of the process-wide histogram `io.scan.stage.pack_s`,
wall seconds (`sum`) minus the thread's CPU seconds (`cpu_sum`, which a
timed span records beside them), over its count.  The pack is pure host
copying, so what is left of its wall time is the wait for the
interpreter's lock next to the serve threads.  `span_reduce.hist_growth`
returns sum and count only: `cpu_sum`'s growth is taken from the two
snapshots here.  A program whose histogram has no `cpu_sum` gives nothing
to read."""

import span_reduce      # benchmarks/ is on the path of every reader

NAME = "io.scan.stage.pack_s"


def _cpu_sum(snapshot):
    h = snapshot.get("histograms", {}).get(NAME)
    return None if h is None else h.get("cpu_sum")


def read(ctx):
    wall_s, packs = span_reduce.hist_growth(ctx, NAME)
    cpu_end = _cpu_sum(ctx["snap_end"])
    if not packs or cpu_end is None:
        return None
    cpu_s = cpu_end - (_cpu_sum(ctx["snap_start"]) or 0.0)
    return (wall_s - cpu_s) / packs * 1e3
