"""Fused chunk segment: the share of the window's build-row chunks whose
live rows were compacted before the aggregate's scatter-add — the growth
of `engine.agg.build_sparse` over that of `engine.agg.build_sparse` +
`engine.agg.build_full`, in %.  Per chunk of a streamed aggregate in the
build-row form exactly one of the two grows, by the branch its program
took: at most `ops/aggregate.py::BUILD_SPARSE_MAX_ROWS` live rows are
compacted and scattered, more scatter every row of the chunk.  100 while
the joins keep few rows of a chunk; a fall names chunks that scatter every
row, which would explain a rise of `segment_device_ms`.  A program without
the counters (the parent's) reads nothing."""


def read(ctx):
    c0, c1 = ctx["snap_start"]["counters"], ctx["snap_end"]["counters"]
    sparse, full = (c1.get(k, 0) - c0.get(k, 0)
                    for k in ("engine.agg.build_sparse",
                              "engine.agg.build_full"))
    if sparse + full <= 0:
        return None
    return sparse / (sparse + full) * 100.0
