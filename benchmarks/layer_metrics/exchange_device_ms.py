"""Exchange: device time per query of the program executions launched
while an `engine.exchange.hash` or `engine.exchange.broadcast` host span
was open — at any depth: the counts program is launched under the
`engine.sync_wait` span inside, the shuffle under `shuffle_table_padded`,
so `trace_reduce`'s innermost-scope table splits them — summed over the
chips (a `shard_map` program runs on each, and is counted on each): pad,
bucket pack, all-to-all, counts.  Their share of the traced stretch times
the window's mean time per query (`trace_reduce.scope_s_per_query`'s
arithmetic, on this reader's own sum).  A program without these spans
gives nothing to read."""

import bisect

import span_reduce      # benchmarks/ is on the path of every reader
import trace_reduce

SPANS = {"engine.exchange.hash", "engine.exchange.broadcast"}


def exchange_device_s(xplane_path):
    """(device seconds of the executions launched inside an exchange span,
    window seconds) of one trace; None where there is no such span."""
    planes = trace_reduce.read_planes(xplane_path)
    hosts = [p for p in planes if p.name == "/host:CPU"]
    if not hosts:
        return None
    _, launches, window = trace_reduce._host_spans(hosts[0])
    named = span_reduce.named_spans(hosts[0], SPANS)
    spans = sorted((s, e) for name in SPANS for _, s, e in named[name])
    if not spans or window is None:
        return None
    starts = [s for s, _ in spans]      # exchange spans never overlap

    def inside(t):
        i = bisect.bisect_right(starts, t) - 1
        return i >= 0 and t < spans[i][1]

    run_ids = {run_id for t, run_id in launches if inside(t)}
    lo, hi = window
    total = 0
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        plane.load_metadata(())
        for name, base, events in plane.lines():
            if name != "XLA Modules":
                continue
            for ev in events:
                _, start, dur, stats = plane.event(ev, base, True)
                if stats.get("run_id") in run_ids and lo <= start \
                        and start + dur <= hi:
                    total += dur
    return total / 1e12, (hi - lo) / 1e12


def read(ctx):
    path = span_reduce.xplane_of(ctx)
    found = exchange_device_s(path) if path else None
    if found is None:
        return None
    device_s, window_s = found
    s = trace_reduce.scope_s_per_query(
        {"scopes": {"exchange": device_s}, "window_s": window_s},
        "exchange", ctx["loop"])
    return None if s is None else s * 1e3
