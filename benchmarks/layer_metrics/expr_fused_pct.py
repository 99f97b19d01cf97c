"""Fused chunk segment: the share of the window's expression nodes
(comparisons, booleans, arithmetic; `engine/expr.py::count_nodes`) that ran
compiled into a chunk program — the growth of `engine.expr.fused` over
that of `engine.expr.fused` + `engine.expr.eager`, in %.  100 while the
arithmetic stays in the program; a fall names the demotion that would
explain a fall of `fact_rows_per_s`.  A program without the counters (the
parent's) reads nothing."""


def read(ctx):
    c0, c1 = ctx["snap_start"]["counters"], ctx["snap_end"]["counters"]
    fused, eager = (c1.get(k, 0) - c0.get(k, 0)
                    for k in ("engine.expr.fused", "engine.expr.eager"))
    if fused + eager <= 0:
        return None
    return fused / (fused + eager) * 100.0
