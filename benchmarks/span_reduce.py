"""What the span readers share: the window's queries out of OP_METRICS, and
program launches counted inside an interval that two host spans bound.

The program's `op_scope(name, timed=True)` leaves each span twice: as the
histogram ``<name>_s`` in the bound query's summary (and process-wide),
and, under ``SRJT_TRACE=1``, as a host span of that name in the profiler's
trace.  The first half of this file reads the summaries, the second the
trace; `trace_reduce.py` is used, not edited.  A program that has no such
span (every commit before the spans were added) gives every function here
nothing to find, and the reader on top returns None.
"""

from __future__ import annotations

import bisect
import glob
import os

import trace_reduce     # benchmarks/ is on the path of every reader

STREAM_SPAN = "engine.stream"
EXECUTE_SPAN = "engine.execute"


# -- the window's queries, from OP_METRICS ---------------------------------------

def window_queries(ctx: dict) -> list:
    """The summaries (the server keeps its last 32) of the loop's own
    queries, by its clients' trace ids: warm-up and metrics calls are out."""
    trace_ids = {c.trace_id for c in ctx["loop"].clients}
    return [q for q in ctx["snap_end"].get("queries", ())
            if q.get("trace_id") in trace_ids]


def hist(query: dict, name: str) -> tuple:
    """(sum, count) of one query's histogram ``name``; (0.0, 0) if absent."""
    h = query.get("histograms", {}).get(name)
    return (h["sum"], h["count"]) if h else (0.0, 0)


def per_occurrence_ms(ctx: dict, name: str) -> float | None:
    """Mean milliseconds of one occurrence of span ``name`` over the
    window's queries: sum of ``<name>_s`` over its count."""
    seconds = count = 0
    for q in window_queries(ctx):
        s, n = hist(q, f"{name}_s")
        seconds += s
        count += n
    return seconds / count * 1e3 if count else None


def per_query_ms(ctx: dict, value) -> list:
    """``value(query)`` -> seconds or None, in milliseconds, for each of the
    window's queries that has one."""
    out = (value(q) for q in window_queries(ctx))
    return [v * 1e3 for v in out if v is not None]


def hist_growth(ctx: dict, name: str) -> tuple:
    """(sum, count) by which the process-wide histogram ``name`` grew from
    `snap_start` to `snap_end`: any number of clients, any number of queries."""
    h0 = ctx["snap_start"].get("histograms", {}).get(name)
    h1 = ctx["snap_end"].get("histograms", {}).get(name)
    if not h1:
        return 0.0, 0
    return (h1["sum"] - (h0["sum"] if h0 else 0.0),
            h1["count"] - (h0["count"] if h0 else 0))


# -- launches inside a derived interval, from the profiler's trace ---------------

def intervals_after(inner: list, outer: list) -> list:
    """``inner``, ``outer``: (line, start, end) spans.  For every outer span
    that holds inner spans on its own line (thread): (end of the last of
    them, end of the outer span) — what the outer span did after its last
    inner one."""
    out = []
    for line, o_start, o_end in outer:
        ends = [e for ln, s, e in inner
                if ln == line and o_start <= s and e <= o_end]
        if ends:
            out.append((max(ends), o_end))
    return sorted(out)


def launches_per_interval(intervals: list, launch_times: list,
                          window: tuple | None = None) -> float | None:
    """Launches whose time lies inside one of ``intervals`` (disjoint), over
    the number of intervals; only intervals that lie whole inside ``window``
    count.  None when there is no such interval."""
    if window is not None:
        intervals = [(s, e) for s, e in intervals
                     if window[0] <= s and e <= window[1]]
    if not intervals:
        return None
    times = sorted(launch_times)
    inside = sum(bisect.bisect_right(times, e) - bisect.bisect_left(times, s)
                 for s, e in intervals)
    return inside / len(intervals)


def named_spans(plane, names: set) -> dict:
    """name -> [(line, start_ps, end_ps)] of the host plane's events with
    one of ``names`` (`trace_reduce._host_spans` keeps no line)."""
    if not plane.events_meta:
        plane.load_metadata(())
    wanted = {mid: md["name"] for mid, md in plane.events_meta.items()
              if md["name"] in names}
    out: dict = {n: [] for n in names}
    for line, (_, base, events) in enumerate(plane.lines()):
        for span in events:
            mid, start, dur, _ = plane.event(span, base, False)
            if mid in wanted:
                out[wanted[mid]].append((line, start, start + dur))
    return out


def post_stream_launches(xplane_path: str) -> float | None:
    """Program executions launched between the end of an `engine.stream`
    span and the end of the `engine.execute` span around it, per such
    interval inside the traced window.  The producer thread has ended by
    then, so with one client every launch in the interval is the tail's."""
    hosts = [p for p in trace_reduce.read_planes(xplane_path)
             if p.name == "/host:CPU"]
    if not hosts:
        return None
    _, launches, window = trace_reduce._host_spans(hosts[0])
    spans = named_spans(hosts[0], {STREAM_SPAN, EXECUTE_SPAN})
    return launches_per_interval(
        intervals_after(spans[STREAM_SPAN], spans[EXECUTE_SPAN]),
        [t for t, _ in launches], window)


def xplane_of(ctx: dict) -> str | None:
    """The traced stretch's `.xplane.pb`, where `reduce_dir` found it."""
    doc = ctx.get("trace_doc")
    if not ctx.get("trace") or not doc:
        return None
    paths = sorted(glob.glob(os.path.join(
        doc["log_dir"], "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None
