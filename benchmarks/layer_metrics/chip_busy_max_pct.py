"""Device: busy share of the traced stretch on the BUSIEST chip — per
device plane of the trace (`/device:TPU:<n>`), the union of its op
intervals inside the launcher's `bench.trace_window` span over that
window.  `device_idle_pct` is the mean over the planes that ran anything;
this and `chip_busy_min_pct` say how the mesh shares the work.  A plane
with no op in the window reads 0."""

import span_reduce      # benchmarks/ is on the path of every reader
import trace_reduce


def chip_busy_pcts(ctx):
    """Busy share, in percent, of every device plane of the traced stretch
    (plane order); None without a trace, a device plane or a window span."""
    path = span_reduce.xplane_of(ctx)
    if not path:
        return None
    planes = trace_reduce.read_planes(path)
    hosts = [p for p in planes if p.name == "/host:CPU"]
    devices = [p for p in planes if p.name.startswith("/device:TPU:")]
    window = trace_reduce._host_spans(hosts[0])[2] if hosts else None
    if not devices or window is None:
        return None
    lo, hi = window
    shares = []
    for plane in devices:
        plane.load_metadata(())
        lines = {name: (base, events) for name, base, events in plane.lines()}
        base, events = lines.get("XLA Ops") or lines.get("XLA Modules") \
            or (0, ())
        intervals = [(s, s + d) for _, s, d, _ in
                     (plane.event(e, base, False) for e in events)]
        shares.append(trace_reduce._union_s(intervals, lo, hi)
                      / ((hi - lo) / 1e12) * 100.0)
    return shares


def read(ctx):
    shares = chip_busy_pcts(ctx)
    return max(shares) if shares else None
