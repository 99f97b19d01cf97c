"""Scheduler: the share of the traced stretch during which at least two
queries stream at once.  A query streams while its serve thread is inside
`engine.stream` (reader open -> closed); the profiler keeps only spans
that began AND ended inside the stretch, and with four clients a query
outlasts most of it, so the whole-query span is there for a query in
four.  Its children always are — `engine.stream.first_wait`,
`engine.stream.wait_reader` and `engine.fused_segment` once per chunk,
`engine.sched.gate_wait` where a session blocked — and between them the
chunk loop runs a few lines of Python.  So a thread counts as streaming
over the union of these spans on its own line of the host plane, clipped
to the launcher's `bench.trace_window`.  Near 100 with four clients: the
chunk loops interleave; near 0: something serialises the plans."""

import span_reduce      # benchmarks/ is on the path of every reader
import trace_reduce

SPANS = {"engine.stream", "engine.stream.first_wait",
         "engine.stream.wait_reader", "engine.fused_segment",
         "engine.sched.gate_wait"}


def _merged(spans, lo, hi):
    """One thread's spans, which may overlap or nest, clipped to (lo, hi)
    and merged into disjoint (start, end) intervals."""
    out = []
    for start, end in sorted((max(s, lo), min(e, hi)) for s, e in spans):
        if start >= end:
            continue
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def overlap_share(per_thread, window, depth=2):
    """``per_thread``: for each thread its (start, end) spans; the share
    of ``window`` (lo, hi) during which at least ``depth`` threads are
    inside one of theirs."""
    lo, hi = window
    edges = sorted(edge for spans in per_thread
                   for start, end in _merged(spans, lo, hi)
                   for edge in ((start, 1), (end, -1)))
    covered = threads = 0
    since = lo
    for t, step in edges:               # at one t a stop sorts first
        if threads >= depth:
            covered += t - since
        threads += step
        since = t
    return covered / (hi - lo)


def read(ctx):
    path = span_reduce.xplane_of(ctx)
    if not path:
        return None
    hosts = [p for p in trace_reduce.read_planes(path)
             if p.name == "/host:CPU"]
    if not hosts:
        return None
    window = trace_reduce._host_spans(hosts[0])[2]
    per_line: dict = {}
    for spans in span_reduce.named_spans(hosts[0], SPANS).values():
        for line, start, end in spans:
            per_line.setdefault(line, []).append((start, end))
    if window is None or not per_line:
        return None
    return overlap_share(list(per_line.values()), window) * 100.0
