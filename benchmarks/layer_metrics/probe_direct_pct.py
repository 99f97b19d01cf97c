"""Relational kernels: the share of the window's rank probes that looked
their keys up in a direct-address table — the growth of
`engine.probe.direct` over that of `engine.probe.rank`, in %.  A rank probe
of a build keyed by one integer column whose live keys span at most
`ops/join.py::DIRECT_MAX_SLOTS` reads its build row by one gather; any
other rank probe searches the sorted keys or merge-ranks hashes.  100
while the build's span fits the table; a fall names a build that no longer
does.  A process whose counters never held `engine.probe.direct` (a
program without the table, or one whose builds never fit it) reads
nothing."""


def read(ctx):
    c0, c1 = ctx["snap_start"]["counters"], ctx["snap_end"]["counters"]
    if "engine.probe.direct" not in c1:
        return None
    rank = c1.get("engine.probe.rank", 0) - c0.get("engine.probe.rank", 0)
    if rank <= 0:
        return None
    direct = c1["engine.probe.direct"] - c0.get("engine.probe.direct", 0)
    return direct / rank * 100.0
