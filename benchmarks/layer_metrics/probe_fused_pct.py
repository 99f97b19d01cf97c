"""Fused chunk segment: the share of the window's streamed probe joins that
ran inside a chunk program — the growth of `engine.probe.compare` +
`engine.probe.rank` over that of those two + `engine.probe.interp`, in %.
Per chunk each probe join of the chain grows exactly one of the three: a
chunk program probed it by one of its two methods, or the interpreted loop
joined it eagerly (a build that holds a key twice — or, for a build not
keyed by one integer column, two keys of one 32-bit hash — vetoes the
program).  100 while the build stays in the chunk program; a fall names
the veto.  A program without `engine.probe.interp` (the parent's) reads
nothing where it ran no probe in a program."""


def read(ctx):
    c0, c1 = ctx["snap_start"]["counters"], ctx["snap_end"]["counters"]
    fused, interp = (
        sum(c1.get(k, 0) - c0.get(k, 0) for k in keys)
        for keys in (("engine.probe.compare", "engine.probe.rank"),
                     ("engine.probe.interp",)))
    if fused + interp <= 0:
        return None
    return fused / (fused + interp) * 100.0
