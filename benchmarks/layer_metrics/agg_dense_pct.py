"""Fused chunk segment: the share of the window's chunk-program launches with
a keyed aggregate whose aggregate took the dense form — the growth of
`engine.agg.dense` over that of `engine.agg.dense` + `engine.agg.sorted`, in
%.  Per such launch exactly one of the two grows, by the form the program
was compiled in: a masked reduction over the key's footer range, or the
sort.  100 where the group key's domain is small and known, 0 where it is
not; a fall names the demotion that would explain a rise of
`segment_device_ms`.  A program without the counters (the parent's) reads
nothing."""


def read(ctx):
    c0, c1 = ctx["snap_start"]["counters"], ctx["snap_end"]["counters"]
    dense, sorted_ = (c1.get(k, 0) - c0.get(k, 0)
                      for k in ("engine.agg.dense", "engine.agg.sorted"))
    if dense + sorted_ <= 0:
        return None
    return dense / (dense + sorted_) * 100.0
