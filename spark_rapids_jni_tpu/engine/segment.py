"""Whole-stage segment fusion: compile plan chains into single XLA programs.

PR 1's executor interprets the optimized DAG node-by-node: every Filter
materializes a compacted intermediate (eval + nonzero + gather + one host
sync), every Project dispatches, and the Aggregate on top re-reads it all.
Flare's result (PAPERS.md, arxiv 1703.08219) is that whole-stage native
compilation of exactly these chains is the dominant win for Spark-style
plans.  The TPU translation:

- A **segment** is a maximal Filter/Project chain, optionally rooted by a
  decomposable Aggregate, between pipeline breakers (Scan, Join, Sort,
  Limit, exchange).  Breakers materialize; segments must not.
- Each segment traces ONCE into one ``jax.jit`` callable over the input
  ``Table`` pytree.  Filters never compact inside the program — they AND
  into a live-row mask (the static-shape discipline every padded op here
  already follows), Projects are metadata-only selects, and an Aggregate
  root feeds the mask straight into ``groupby_padded(row_mask=...)``.
  Intermediates therefore never materialize: one fused program, one
  dispatch, at most one host sync at the segment boundary.
- On the streamed path a Join whose build side is scan-independent is NOT
  a breaker (``build_stream_segment``): the prepared build (hash + stable
  sort, cached in ``engine.cache.BUILD_CACHE``) enters the program as a
  pytree input and each probe chunk masks/selects at probe-row shape —
  filter -> project -> probe-join -> partial-agg runs as one traced
  callable per chunk with zero per-chunk host syncs.  How a probe row
  finds its build row is ``ops.join.probe_method``'s choice, from the
  build's row count: a broadcast compare of the keys for a small build
  (no hash, sort or gather in the program), the rank probe above it (for
  one integer key a gather from the build's direct-address table, or a
  search of its sorted keys; else a merge-rank of their hashes);
  ``engine.probe.compare`` / ``engine.probe.rank`` count the joins that
  took each, per chunk launch (``engine.probe.direct`` the rank probes by
  table), and ``engine.probe.interp`` those of a chunk the interpreter ran
  instead.
- Compiled segments live in a process-wide LRU keyed by
  ``(segment fingerprint, input shape-class)`` with hit/miss/eviction
  counters in ``utils.tracing`` (``engine.segment_cache.*``).  The
  shape-class is the (row-bucket, schema) signature: chunked scans pad
  rows to power-of-two buckets (io/staging.py), so every same-schema chunk
  re-enters the same compiled executable instead of retracing.
- The merge of a streamed aggregate's padded partials is one more entry of
  that cache (``CompiledCombine``): cut, concatenate and combine group-by
  in ONE launch, compiled once per (capacity, power-of-two bucket of the
  partial count) — and the count never passes ``COMBINE_ARITY``: a longer
  stream folds its partials as it runs (``StreamedPartials``), so neither
  what the device holds nor any program grows with the stream.
"""

from __future__ import annotations

import functools
import hashlib
import threading
import time
from collections import OrderedDict
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import Column, Table
from ..dtypes import TypeId
from ..utils import metrics, timeline
from ..utils.config import config
from ..utils.tracing import op_scope
from .plan import (STREAM_COMBINE, Aggregate, Filter, Join, Limit, PlanNode,
                   Project, Sort, TopK, depends_on, expr_columns, topo_nodes)

#: chain members fusable into a segment body (everything else is a
#: breaker).  Exchange is deliberately NOT here: an exchange re-places
#: rows across devices, so it must materialize its input — but a
#: broadcast Exchange on a join's build side stays scan-independent, so
#: ``build_stream_segment`` still fuses the probe side around it.
_FUSABLE = (Filter, Project)

#: join types the streamed probe-join program supports (output stays at
#: probe-row shape: semi masks, inner gathers one build row per probe row)
_FUSABLE_JOINS = ("inner", "semi")


# -- segment extraction ----------------------------------------------------

def parent_counts(root: PlanNode) -> dict:
    """id(node) -> number of parents in the DAG (shared nodes must
    materialize once, so they terminate segment growth)."""
    counts: dict = {}
    for n in topo_nodes(root):
        for c in n.children():
            counts[id(c)] = counts.get(id(c), 0) + 1
    return counts


def _agg_fusable(agg: Aggregate) -> bool:
    """Every op on groupby's traced path (an aggregate with no keys is a
    masked reduction there: ``ops.aggregate._keyless_padded``)."""
    from ..ops.aggregate import _FAST_OPS
    return all(op in _FAST_OPS for _, op in agg.aggs)


def _node_sig(nd: PlanNode) -> tuple:
    """What of a node a program compiled over it depends on (its inputs
    excluded): the unit of ``Segment`` / ``Tail`` fingerprints."""
    if isinstance(nd, Filter):
        return ("filter", nd.predicate)
    if isinstance(nd, Join):
        return ("join", tuple(nd.left_keys), tuple(nd.right_keys), nd.how)
    if isinstance(nd, Project):
        return ("project", tuple(nd.columns))
    if isinstance(nd, Aggregate):
        return ("aggregate", tuple(nd.keys), tuple(nd.aggs), tuple(nd.names))
    if isinstance(nd, Limit):
        return ("limit", nd.n)
    return (type(nd).__name__.lower(), tuple(nd.keys),
            getattr(nd, "n", None))     # Sort, TopK


class Segment:
    """One fusable chain: ``input -> chain (bottom-up) [-> agg]``.

    On the streamed path the chain may contain ``Join`` nodes whose build
    side is scan-independent (``build_stream_segment``); their prepared
    builds enter the jitted program as extra pytree inputs."""

    __slots__ = ("chain", "agg", "input", "_fp")

    def __init__(self, chain: tuple, agg: Optional[Aggregate],
                 input_node: PlanNode):
        self.chain = chain          # Filter/Project/Join nodes, exec order
        self.agg = agg              # optional Aggregate root
        self.input = input_node     # breaker output the segment consumes
        self._fp: Optional[str] = None

    def nodes(self) -> tuple:
        return self.chain + ((self.agg,) if self.agg is not None else ())

    def joins(self) -> tuple:
        """Join nodes in the chain, execution order."""
        return tuple(nd for nd in self.chain if isinstance(nd, Join))

    def fingerprint(self) -> str:
        """Structure-only identity (the plan-cache analog, input excluded):
        equal chains over different inputs share compiled executables."""
        if self._fp is None:
            sig = [_node_sig(nd) for nd in self.nodes()]
            self._fp = hashlib.sha256(repr(tuple(sig)).encode()).hexdigest()
        return self._fp

    def exprs(self) -> int:
        """Expression nodes the program computes (``expr.count_nodes``):
        its filters' predicates and its Projects' computed columns."""
        from .expr import count_nodes
        return sum(count_nodes(nd.predicate) if isinstance(nd, Filter)
                   else sum(count_nodes(e) for _, e in nd.computed)
                   if isinstance(nd, Project) else 0 for nd in self.chain)

    def columns_used(self) -> set:
        """Columns the program computes on: filtered on, computed from
        (a Project's expressions) or aggregated — a plain name a Project
        passes through is not among them."""
        cols = set()
        for nd in self.chain:
            if isinstance(nd, Filter):
                cols |= expr_columns(nd.predicate)
            elif isinstance(nd, Project):
                for _, e in nd.computed:
                    cols |= expr_columns(e)
        if self.agg is not None:
            cols |= set(self.agg.keys)
            cols |= {c for c, _ in self.agg.aggs if c is not None}
        return cols


def build_segment(top: PlanNode, nparents: dict) -> Optional[Segment]:
    """The segment rooted at ``top``, or None when ``top`` can't root one.

    ``top`` itself is always included (it was requested); deeper nodes are
    absorbed only while they are Filter/Project with exactly one parent —
    a shared subtree must materialize once for its other consumers.
    """
    if isinstance(top, Aggregate):
        if not _agg_fusable(top):
            return None
        agg, cur, absorb_first = top, top.child, False
    elif isinstance(top, _FUSABLE):
        agg, cur, absorb_first = None, top, True
    else:
        return None
    chain = []
    while isinstance(cur, _FUSABLE) and \
            (absorb_first or nparents.get(id(cur), 1) == 1):
        absorb_first = False
        chain.append(cur)
        cur = cur.child
    return Segment(tuple(reversed(chain)), agg, cur)


def build_stream_segment(agg: Aggregate, scan: PlanNode,
                         nparents: dict) -> Optional[Segment]:
    """The streamed-path segment under ``agg``: like ``build_segment``, but
    an inner/semi Join whose build (right) side is scan-independent is
    absorbed instead of breaking — the chain continues down the probe
    (left) side toward the scan, and the prepared build becomes a pytree
    input of the jitted chunk program.
    """
    if not _agg_fusable(agg):
        return None
    dep: dict = {}
    chain = []
    cur = agg.child
    while True:
        if isinstance(cur, _FUSABLE) and nparents.get(id(cur), 1) == 1:
            chain.append(cur)
            cur = cur.child
        elif (isinstance(cur, Join)
              and nparents.get(id(cur), 1) == 1
              and cur.how in _FUSABLE_JOINS
              and depends_on(cur.left, scan, dep)
              and not depends_on(cur.right, scan, dep)):
            chain.append(cur)
            cur = cur.left
        else:
            break
    return Segment(tuple(reversed(chain)), agg, cur)


def worthwhile(seg: Segment, streaming: bool = False) -> bool:
    """Fusion must beat the interpreter to be worth a compile: a lone
    Project is a metadata select and a bare Aggregate already runs as one
    compiled program — except on the streaming path, where a fused agg
    segment is what lets per-chunk partials stay padded on device (no
    per-chunk host sync), so any agg root qualifies there."""
    if seg.agg is not None:
        return streaming or len(seg.chain) >= 1
    return len(seg.chain) >= 2 and \
        any(isinstance(nd, Filter) for nd in seg.chain)


def runtime_eligible(seg: Segment, table: Table) -> bool:
    """Static fusability said yes; the actual input schema gets the veto:
    computed-on columns must be 1-D fixed-width (strings may pass THROUGH
    a segment untouched, but can't be filtered on, computed from or
    aggregated)."""
    return stream_runtime_eligible(seg, table, ())


def _needed_after(seg: Segment, pos: int) -> frozenset:
    """Column names referenced by chain nodes at index >= ``pos`` plus the
    agg root — the set an inner join in the chain must materialize from
    the build side (everything else on the right is dead weight)."""
    need = set()
    for nd in seg.chain[pos:]:
        if isinstance(nd, Filter):
            need |= expr_columns(nd.predicate)
        elif isinstance(nd, Join):
            need |= set(nd.left_keys)
        else:
            for _, e in nd.items:
                need |= expr_columns(e)
    if seg.agg is not None:
        need |= set(seg.agg.keys)
        need |= {c for c, _ in seg.agg.aggs if c is not None}
    return frozenset(need)


def _join_out_name(name: str, left_names) -> str:
    """Inner-join output name for a right payload column (the executor's
    ``_r``-suffix collision rule)."""
    return name + "_r" if name in left_names else name


def stream_runtime_eligible(seg: Segment, table: Table,
                            builds: tuple) -> bool:
    """The actual input schema's veto of a segment: walks the chain
    tracking the available name -> Column mapping (chunk columns, computed
    columns, then gathered build payloads), vetoing strings / non-1-D
    buffers in any computed-on or gathered position.  A string reaching an
    arithmetic node demotes the segment: the interpreter then meets it."""
    if seg.agg is not None and table.num_rows == 0:
        return False  # empty-input agg: let groupby's host path handle it

    def ok(c) -> bool:     # None: a computed column, fixed-width by making
        return c is None or not (c.dtype.is_string or c.data is None
                                 or c.data.ndim != 1)

    try:
        avail = {nm: table.column(nm) for nm in (table.names or [])}
        ji = 0
        for i, nd in enumerate(seg.chain):
            if isinstance(nd, Filter):
                for name in expr_columns(nd.predicate):
                    if not ok(avail[name]):
                        return False
            elif isinstance(nd, Project):
                for _, e in nd.computed:
                    if not all(ok(avail[c]) for c in expr_columns(e)):
                        return False
                avail = {nm: None if e[0] != "col" else avail[e[1]]
                         for nm, e in nd.items}
            else:  # Join
                b = builds[ji]
                ji += 1
                for k in nd.left_keys:
                    if not ok(avail[k]):
                        return False
                bcols = {nm: b.column(nm) for nm in (b.names or [])}
                for k in nd.right_keys:
                    if not ok(bcols[k]):
                        return False
                if nd.how == "inner":
                    lnames = set(avail)
                    needed = _needed_after(seg, i + 1)
                    for nm in (b.names or []):
                        if nm in nd.right_keys:
                            continue
                        out_nm = _join_out_name(nm, lnames)
                        if out_nm in needed:
                            if not ok(bcols[nm]):
                                return False
                            avail[out_nm] = bcols[nm]
        if seg.agg is not None:
            for name in set(seg.agg.keys) | \
                    {c for c, _ in seg.agg.aggs if c is not None}:
                if not ok(avail[name]):
                    return False
        return True
    except (KeyError, ValueError):
        return False


# -- compiled form ----------------------------------------------------------

def shape_class(table: Table) -> tuple:
    """The compile key of a Table input: row count (padded chunk bucket),
    names, and per-column (dtype, buffer shape, nullability) — everything
    jax.jit would retrace on."""
    return (
        table.num_rows,
        tuple(table.names) if table.names else None,
        tuple((c.dtype,
               None if c.data is None else (tuple(c.data.shape),
                                            c.data.dtype.str),
               c.validity is not None)
              for c in table.columns),
    )


def probe_methods(seg: Segment, builds: tuple, prepared: tuple = ()) \
        -> tuple:
    """``ops.join.probe_method`` of every Join of the chain, execution
    order, from the build Tables the chunk program is compiled for: the
    build's row count and its key columns (the probe side's keys are
    fixed-width by ``stream_runtime_eligible``) — ``"direct"`` for a rank
    probe whose prepared build (``prepared``) has a direct-address table
    (``PreparedBuild.direct``)."""
    from ..ops.join import probe_method
    methods = [probe_method(b.num_rows, [b.column(k) for k in j.right_keys])
               for j, b in zip(seg.joins(), builds)]
    for i, pb in enumerate(prepared):
        if methods[i] == "rank" and pb.direct is not None:
            methods[i] = "direct"
    return tuple(methods)


def _probe_join_node(nd: Join, pb, table: Table, live, needed):
    """One fused probe-join step at probe-row shape: mask ``live`` by the
    verified match, and (inner only) select the needed build payload
    columns at the matched build rows — by the probe's own method: a
    one-hot masked reduce beside the compare probe, a gather beside the
    rank probe.  No expansion, no host sync — the prepared build
    guarantees <= 1 candidate per probe row.  Returns ``(table, live,
    ri)``: ``ri`` each live row's build row (what the aggregate's
    build-row form adds into)."""
    from ..ops.join import (probe_join_prepared, probe_method,
                            select_build_rows)
    from ..ops.selection import gather_column
    lk = Table([table.column(k) for k in nd.left_keys])
    compare = probe_method(
        pb.nr, list(lk.columns) + list(pb.rk.columns)) == "compare"
    ri, matched = probe_join_prepared(lk, pb, left_live=live)
    live = live & matched
    if nd.how == "semi":
        return table, live, ri
    lnames = list(table.names or [])
    cols, names = list(table.columns), list(lnames)
    n = table.num_rows
    for nm, c in zip(pb.payload.names or [], pb.payload.columns):
        if nm in nd.right_keys:
            continue
        out_nm = _join_out_name(nm, lnames)
        if out_nm not in needed:
            continue
        if pb.nr == 0:  # dead rows only (live is all-False); typed zeros
            cols.append(Column(c.dtype, data=jnp.zeros((n,), c.data.dtype)))
        elif compare:   # payload is 1-D fixed-width: runtime eligibility
            cols.append(select_build_rows(c, ri))
        else:
            with op_scope("probe_rank"):
                cols.append(gather_column(c, ri))
        names.append(out_nm)
    return Table(cols, names), live, ri


def _passes_through(seg: Segment, name: str) -> bool:
    """Does column ``name`` of the segment's input reach its aggregate
    unchanged?  Filters and joins keep it (an inner join's payload of the
    same name is renamed ``_r``); every Project must pass it as it is."""
    return all(dict(nd.items).get(name) == ("col", name)
               for nd in seg.chain if isinstance(nd, Project))


def agg_domain(seg: Segment, file, groups, columns=None) -> Optional[tuple]:
    """``(lo, slots)`` of the dense form (``ops.aggregate.groupby_dense``)
    for the chunk program of ``seg`` streamed from row groups ``groups`` of
    the Parquet ``file`` (reading ``columns``, None: all), or None where
    the sort form stays.  The dense form needs ONE group key that is an
    integer column of the file reaching the aggregate unchanged,
    ``DENSE_OPS`` aggregations, and footer statistics of that column in
    every group, their range spanning at most ``DENSE_MAX_GROUPS`` slots.
    The statistics only choose the program: it checks the keys itself."""
    from ..ops.aggregate import DENSE_KEY_TYPES, DENSE_OPS, dense_slots
    agg = seg.agg
    if agg is None or len(agg.keys) != 1 or not groups \
            or any(op not in DENSE_OPS for _, op in agg.aggs):
        return None
    key = agg.keys[0]
    if key not in file.names or (columns is not None and key not in columns) \
            or not _passes_through(seg, key) \
            or file.schema[file.names.index(key)].dtype.id \
            not in DENSE_KEY_TYPES:
        return None
    stats = [file.group_stats(gi, key) for gi in groups]
    if any(st is None for st in stats):
        return None
    lo, hi = min(st[0] for st in stats), max(st[1] for st in stats)
    slots = dense_slots(lo, hi)
    return None if slots is None else (lo, slots)


#: the aggregations the build-row form adds up: additive, so a chunk's
#: totals and the stream's merge are sums of slots
BUILD_ROW_OPS = frozenset({"sum", "count", "count_all"})


def _is_float_expr(expr, floats: set) -> bool:
    """Can ``expr`` be a float: it reads a float column or a float
    literal (the arithmetic of integers and decimals is exact)."""
    if not isinstance(expr, tuple):
        return isinstance(expr, float)
    if expr[0] == "col":
        return expr[1] in floats
    if expr[0] == "lit":
        return isinstance(expr[1], float)
    return any(_is_float_expr(e, floats) for e in expr[1:])


def build_row_join(seg: Segment, table: Table, builds: tuple) \
        -> Optional[tuple]:
    """``(join index, key sources)`` where the chunk program's aggregate
    takes the build-row form (``ops.aggregate.groupby_build_rows``), else
    None.  The form needs a group that IS one build row: an inner join of
    the chain whose build is ranked on ONE integer key (``exact_keys``,
    above ``PROBE_COMPARE_MAX_BUILD``: the rank probe), a group key that is
    the join's probe key (equal to the build's on every joined row, of its
    dtype), every other group key a payload column of that build reaching
    the aggregate unchanged, and ``BUILD_ROW_OPS`` over integer or decimal
    inputs.  A group is then a build row: its totals add into that row's
    slot, no sort.  ``key sources``: per group key, the payload column it
    is, None for the join's key.  ``table``: the chunk (its names and
    dtypes); ``builds``: the chain's build Tables."""
    from ..ops.join import exact_keys, probe_method
    agg = seg.agg
    if agg is None or not agg.keys \
            or any(op not in BUILD_ROW_OPS for _, op in agg.aggs):
        return None
    try:
        origin = {nm: None for nm in (table.names or [])}
        floats = {nm for nm in origin if table.column(nm).dtype.id
                  in (TypeId.FLOAT32, TypeId.FLOAT64)}
        dtypes = {nm: table.column(nm).dtype for nm in origin}
        ji = 0
        for nd in seg.chain:
            if isinstance(nd, Project):
                floats = {nm for nm, e in nd.items
                          if _is_float_expr(e, floats)}
                origin = {nm: origin.get(e[1]) if e[0] == "col" else None
                          for nm, e in nd.items}
                dtypes = {nm: dtypes.get(e[1]) if e[0] == "col" else None
                          for nm, e in nd.items}
            elif isinstance(nd, Join):
                b = builds[ji]
                if nd.how == "inner":
                    lnames = set(origin)
                    for nm in (b.names or []):
                        if nm not in nd.right_keys:
                            out = _join_out_name(nm, lnames)
                            origin[out] = (ji, nm)
                            dtypes[out] = b.column(nm).dtype
                            if b.column(nm).dtype.id in (TypeId.FLOAT32,
                                                         TypeId.FLOAT64):
                                floats.add(out)
                    if len(nd.left_keys) == 1:
                        origin[nd.left_keys[0]] = (ji, None)
                ji += 1
        if any(c in floats for c, op in agg.aggs if op == "sum"):
            return None
        for ji, (j, b) in enumerate(zip(seg.joins(), builds)):
            keys = [b.column(k) for k in j.right_keys]
            if j.how != "inner" or not exact_keys(keys) \
                    or probe_method(b.num_rows, keys) != "rank" \
                    or dtypes.get(j.left_keys[0]) != keys[0].dtype:
                continue
            src = [origin.get(k) for k in agg.keys]
            if (ji, None) in src and all(o is not None and o[0] == ji
                                         for o in src):
                return ji, tuple(o[1] for o in src)
    except (KeyError, ValueError):
        return None
    return None


def _build_fn(seg: Segment, compiled: "CompiledSegment"):
    """The single program a segment traces into.

    ``fn(table, nvalid, prepared, lo)``: rows >= nvalid are padding (chunk
    buckets); ``prepared`` carries one ``PreparedBuild`` pytree per Join
    in the chain (execution order); ``lo`` is the key domain's low end
    where the aggregate takes the dense form (``compiled.dense_k`` slots),
    else None.  Map segments return (table, live, ovf); agg segments
    return padded partial aggregates + group-live mask + ovf — or, in the
    build-row form (``compiled.build_row``), ``(rows, aggregate Columns,
    ovf, sparse)`` over the build's rows, ``sparse`` 1 where the chunk's
    live rows were compacted before the scatter-add — all
    device-resident, zero host syncs.
    ``ovf`` is the program's overflow flag (``engine/expr.py``: an
    arithmetic node or a decimal sum outgrew int64's checked bound), None
    where it checks nothing.
    """
    from .expr import decimal_sums
    chain, agg = seg.chain, seg.agg
    needed = {i: _needed_after(seg, i + 1)
              for i, nd in enumerate(chain) if isinstance(nd, Join)}

    def fn(table: Table, nvalid, prepared=(), lo=None):
        from ..ops.aggregate import (groupby_build_rows, groupby_dense,
                                     groupby_padded)
        from .expr import any_flag, evaluate, project, sum_check
        compiled.traces += 1  # trace-time side effect: the no-recompile proof
        live = jnp.arange(table.num_rows, dtype=jnp.int32) < nvalid
        ovf: list = []
        ji = 0
        rows_of = []        # each join's build row per probe row
        for i, nd in enumerate(chain):
            if isinstance(nd, Filter):
                vals, valid, _ = evaluate(nd.predicate, table, ovf)
                m = jnp.asarray(vals, jnp.bool_)
                if valid is not None:
                    m = m & valid  # SQL semantics: NULL comparison drops
                live = live & m
            elif isinstance(nd, Join):
                table, live, ri = _probe_join_node(nd, prepared[ji], table,
                                                   live, needed[i])
                rows_of.append(ri)
                ji += 1
            elif nd.computed:
                table = project(table, nd.items, ovf)
            else:
                table = table.select(list(nd.columns))
        if agg is None:
            return table, live, any_flag(ovf)
        for c in decimal_sums(agg.aggs, table):
            sum_check(table.column(c), live, ovf)
        aggs = [(c, op) for c, op in agg.aggs]
        if compiled.build_row is not None:
            bj = compiled.build_row[0]
            rows, out_aggs, sparse = groupby_build_rows(
                table, aggs, live, rows_of[bj], prepared[bj].nr)
            return rows, tuple(out_aggs), any_flag(ovf), sparse
        if compiled.dense_k:
            out_keys, out_aggs, ngroups = groupby_dense(
                table, list(agg.keys), aggs, lo, compiled.dense_k,
                row_mask=live)
        else:
            out_keys, out_aggs, ngroups = groupby_padded(
                table, list(agg.keys), aggs, row_mask=live)
        npad = out_aggs[0].data.shape[0] if out_aggs else live.shape[0]
        glive = jnp.arange(npad, dtype=jnp.int32) < ngroups
        # dtypes are static metadata (CompiledSegment.key_dtypes); only the
        # buffers cross the jit boundary
        kdat = tuple(spec[2] for spec in out_keys)
        kval = tuple(spec[3] for spec in out_keys)
        return kdat, kval, tuple(out_aggs), glive, ngroups, any_flag(ovf)

    return fn


def _agg_form(segment: Segment, dense_k: Optional[int],
              build_row: Optional[tuple] = None) -> Optional[str]:
    """How a chunk program computes its keyed aggregate: ``dense/<slots>``,
    ``build`` (``build_row_join``) or ``sorted``; None where it has none
    (no aggregate, or no keys)."""
    if segment.agg is None or not segment.agg.keys:
        return None
    if build_row is not None:
        return "build"
    return f"dense/{dense_k}" if dense_k else "sorted"


class CompiledSegment:
    """One (segment, shape-class) entry: a jitted callable plus the trace
    counter tests use to prove chunks reuse one executable.  ``probes``
    is ``probe_methods`` of the chain's joins for this shape class: what
    the ``engine.probe.*`` counters and the span's stat report (a
    ``direct`` probe counts as ``rank`` and as ``direct``).
    ``dense_k``: the slots of the aggregate's dense form, None for the sort
    form; ``build_row``: ``build_row_join``'s answer where the aggregate
    takes the build-row form; ``agg_form`` what the ``engine.agg.*``
    counters and the span's stat report of it (None: no keyed aggregate,
    or not a chunk program)."""

    __slots__ = ("key", "segment", "key_dtypes", "jfn", "traces", "calls",
                 "probes", "exprs", "dense_k", "build_row", "agg_form")

    #: prefix of this program's compile-vs-replay events (``_tick``)
    counters = "engine.segment"

    def __init__(self, key: tuple, segment: Segment, key_dtypes: tuple,
                 probes: tuple = (), dense_k: Optional[int] = None,
                 build_row: Optional[tuple] = None):
        self.key = key
        self.segment = segment
        self.key_dtypes = key_dtypes
        self.probes = probes
        self.exprs = segment.exprs()
        self.dense_k = dense_k
        self.build_row = build_row
        self.agg_form = _agg_form(segment, dense_k, build_row)
        self.traces = 0
        self.calls = 0
        self.jfn = jax.jit(_build_fn(segment, self))

    def span_stats(self) -> dict:
        """Stats of the ``engine.fused_segment`` span around a launch: the
        expression nodes compiled into it, the joins' probe methods, the
        keyed aggregate's form."""
        out = {"exprs": self.exprs}
        if self.probes:
            compare = self.probes.count("compare")
            out["probe"] = f"{compare}/{len(self.probes) - compare}" \
                f"/{len(self.probes)}/{self.probes.count('direct')}"
        if self.agg_form:
            out["agg"] = self.agg_form
        return out

    def __call__(self, table: Table, nvalid=None, prepared=(), lo=None):
        nv = jnp.int32(table.num_rows if nvalid is None else nvalid)
        return self._launch(table, nv, tuple(prepared), lo)

    def _launch(self, *args):
        self.calls += 1
        if self.exprs:
            metrics.count("engine.expr.fused", self.exprs)
        compare = self.probes.count("compare")    # joins of the chain
        if compare:
            metrics.count("engine.probe.compare", compare)
        if len(self.probes) > compare:
            metrics.count("engine.probe.rank", len(self.probes) - compare)
        direct = self.probes.count("direct")      # rank probes by table
        if direct:
            metrics.count("engine.probe.direct", direct)
        if self.agg_form:
            metrics.count("engine.agg.dense" if self.dense_k
                          else "engine.agg.build" if self.build_row
                          else "engine.agg.sorted")
        if not metrics.enabled() and not timeline.enabled():
            return self.jfn(*args)
        # compile-vs-replay tagging: ``traces`` ticks inside the traced fn,
        # so a call that bumped it paid a trace+compile; otherwise it was a
        # dispatch-only replay.  Durations are host-side dispatch time
        # (jax stays async — no sync added here).
        tr0 = self.traces
        t0 = time.perf_counter()
        out = self.jfn(*args)
        dt = time.perf_counter() - t0
        compiled = self.traces > tr0
        timeline.complete(
            f"{self.counters}.{'compile' if compiled else 'replay'}", t0, dt)
        if metrics.enabled():
            self._tick(compiled, dt)
        return out

    def _tick(self, compiled: bool, dt: float) -> None:
        if compiled:
            metrics.count("engine.segment.compile")
            metrics.observe("engine.segment.trace_s", dt)
        else:
            metrics.count("engine.segment.replay")
            metrics.observe("engine.segment.replay_dispatch_s", dt)


def _build_decode_fn(seg: Segment, compiled: "CompiledSegment", geom):
    """Scan decode fused into the segment: ONE traced program that takes
    the compressed page planes (io/parquet.py DevicePageChunk wire form),
    decodes them on-device (ops/parquet_decode.py) and runs the segment
    chain on the result — decompress -> unpack -> filter/project/agg with
    no host boundary anywhere in between.  Page-table sizing is trace-time
    static (the geometry came from footer metadata), so the program adds
    ZERO deliberate host syncs over the plain segment."""
    from ..ops.parquet_decode import decode_table
    inner = _build_fn(seg, compiled)

    def fn(planes, nvalid, prepared=(), lo=None):
        return inner(decode_table(planes, geom), nvalid, prepared, lo)

    return fn


class CompiledDecodeSegment(CompiledSegment):
    """A CompiledSegment whose jitted program starts at the page planes.

    ``__call__`` is inherited: the executor always passes ``nvalid``
    explicitly (the planes pytree has no ``num_rows``), and the planes
    ride in the table slot."""

    __slots__ = ("geom",)

    def __init__(self, key: tuple, segment: Segment, key_dtypes: tuple,
                 geom, probes: tuple = (), dense_k: Optional[int] = None,
                 build_row: Optional[tuple] = None):
        self.key = key
        self.segment = segment
        self.key_dtypes = key_dtypes
        self.probes = probes
        self.exprs = segment.exprs()
        self.dense_k = dense_k
        self.build_row = build_row
        self.agg_form = _agg_form(segment, dense_k, build_row)
        self.traces = 0
        self.calls = 0
        self.geom = geom
        self.jfn = jax.jit(_build_decode_fn(segment, self, geom))


def _partial_slots(p) -> int:
    """Slots of one padded partial: the length of its columns."""
    return (p[0] + tuple(c.data for c in p[2]))[0].shape[0]


def _partial_class(p) -> tuple:
    """The compile key of one padded partial — everything jax.jit would
    retrace the merge on: slot count, key buffers, aggregate columns.  A
    chunk program's partial brings a mask of its live slots; a merged one
    (a fold's output, ``StreamedPartials``) its group count in that
    place, and is a class of its own."""
    kdat, _kval, out_aggs, glive = p[:4]
    return (glive.shape[0] if glive.ndim else ("merged", _partial_slots(p)),
            tuple(k.dtype.str for k in kdat),
            tuple((c.dtype, c.data.dtype.str, c.validity is not None)
                  for c in out_aggs))


def _build_combine_fn(agg: Aggregate, key_dtypes: tuple, cap: int,
                      compiled: "CompiledCombine"):
    """The single program the merge of the streamed partials traces into.

    ``fn(partials, nreal)``: ``partials`` is the bucketed tuple of
    ``(kdat, kval, out_aggs, glive, ovf)`` — ``glive`` a merged partial's
    group count where it is a scalar, ``ovf`` its overflow flag or None;
    entries >= ``nreal`` are filler (dead rows).  Slice every partial to
    ``cap``, concatenate, and run the combine ``groupby_padded`` under the
    live mask — still padded, zero host syncs.  The merge's own flag ORs
    the live partials' with its decimal sums' check (None where neither
    exists).
    """
    from .expr import any_flag, sum_check
    nk = len(agg.keys)
    knames = [f"k{i}" for i in range(nk)]
    anames = [f"a{j}" for j in range(len(agg.aggs))]
    combine = [(anames[j], STREAM_COMBINE[op])
               for j, (_, op) in enumerate(agg.aggs)]

    def cut(a):
        return a[:cap] if a.shape[0] > cap else a

    def live_slots(p):
        if p[3].ndim:
            return cut(p[3])
        # a merged partial: its groups are packed at the front
        return jnp.arange(min(cap, _partial_slots(p)),
                          dtype=jnp.int32) < p[3]

    def fn(partials, nreal):
        from ..ops.aggregate import groupby_padded
        compiled.traces += 1  # trace-time side effect, as in _build_fn
        key_cols = [
            Column(key_dtypes[i],
                   data=jnp.concatenate([cut(p[0][i]) for p in partials]),
                   validity=jnp.concatenate([cut(p[1][i])
                                             for p in partials]))
            for i in range(nk)]
        agg_cols = []
        for j in range(len(agg.aggs)):
            datas = [cut(p[2][j].data) for p in partials]
            valids = [None if p[2][j].validity is None
                      else cut(p[2][j].validity) for p in partials]
            validity = None if all(v is None for v in valids) else \
                jnp.concatenate([jnp.ones(d.shape[0], jnp.bool_)
                                 if v is None else v
                                 for d, v in zip(datas, valids)])
            agg_cols.append(Column(partials[0][2][j].dtype,
                                   data=jnp.concatenate(datas),
                                   validity=validity))
        live = jnp.concatenate([live_slots(p) & (np.int32(i) < nreal)
                                for i, p in enumerate(partials)])
        merged = Table(key_cols + agg_cols, knames + anames)
        ovf = [p[4] & (np.int32(i) < nreal) for i, p in enumerate(partials)
               if p[4] is not None]
        for (nm, op), col in zip(combine, agg_cols):
            if op == "sum" and col.dtype.is_decimal:
                sum_check(col, live, ovf)
        out_keys, out_aggs, ngroups = groupby_padded(
            merged, knames, combine, row_mask=live)
        kdat = tuple(spec[2] for spec in out_keys)
        kval = tuple(spec[3] for spec in out_keys)
        return kdat, kval, tuple(out_aggs), ngroups, any_flag(ovf)

    return fn


class CompiledCombine(CompiledSegment):
    """The merge program of one streamed aggregate: a SEGMENT_CACHE entry
    beside the chunk programs it merges, with counters of its own
    (``engine.combine.*``) so the chunk program's compile/replay counts
    stay the number of chunks."""

    __slots__ = ()

    counters = "engine.combine"

    def __init__(self, key: tuple, segment: Segment, key_dtypes: tuple,
                 cap: int):
        self.key = key
        self.segment = segment
        self.key_dtypes = key_dtypes
        self.probes = ()        # the merge probes nothing
        self.exprs = 0
        self.dense_k = self.build_row = self.agg_form = None  # not counted
        self.traces = 0
        self.calls = 0
        self.jfn = jax.jit(_build_combine_fn(segment.agg, key_dtypes, cap,
                                             self))

    def __call__(self, partials: tuple, nreal: int):
        return self._launch(partials, np.int32(nreal))

    def _tick(self, compiled: bool, dt: float) -> None:
        if compiled:
            metrics.count("engine.combine.compile")
            metrics.observe("engine.combine.trace_s", dt)
        else:
            metrics.count("engine.combine.replay")
            metrics.observe("engine.combine.replay_dispatch_s", dt)


def _dense_class(shape: tuple, dense_k: Optional[int],
                 build_row: Optional[tuple] = None) -> tuple:
    """A chunk program's shape class with its aggregate's form: the dense
    form's slot count, or the build-row form's join and key sources."""
    if dense_k is not None:
        shape = shape + (("dense", dense_k),)
    return shape if build_row is None else shape + (("build", build_row),)


def _resolve_dtype(name: str, table: Table, builds: tuple):
    """Dtype of an agg key that may come off a join's build side (raw name
    or with the ``_r`` collision suffix stripped)."""
    try:
        return table.column(name).dtype
    except (KeyError, ValueError):
        pass
    base = name[:-2] if name.endswith("_r") else name
    for b in builds:
        for cand in (name, base):
            try:
                return b.column(cand).dtype
            except (KeyError, ValueError):
                continue
    raise KeyError(name)


class SegmentCache:
    """LRU: (segment fingerprint, shape-class) -> CompiledSegment.

    The compiled-executable layer under ``PlanCache``: the plan cache
    dedups optimization by logical fingerprint; this cache dedups XLA
    executables by (structure, input shape).  Counters flow through
    ``utils.tracing`` as ``engine.segment_cache.{hit,miss,eviction}``.
    """

    def __init__(self, maxsize: Optional[int] = None):
        self._maxsize = None if maxsize is None else int(maxsize)
        self._lock = threading.Lock()
        self._entries: "OrderedDict[tuple, CompiledSegment]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    @property
    def maxsize(self) -> int:
        # config-resolved late so SRJT_SEGMENT_CACHE + refresh() take
        # effect on the live singleton (mirrors PlanCache)
        return self._maxsize if self._maxsize is not None \
            else config.segment_cache

    def _lookup(self, key: tuple, build) -> CompiledSegment:
        """The entry under ``key``; ``build()`` makes it on a miss, outside
        the lock (first store wins: a racer that built in parallel counts
        as a hit and its program is dropped)."""
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                metrics.count("engine.segment_cache.hit")
                return hit
        compiled = build()
        with self._lock:
            racer = self._entries.get(key)
            if racer is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                metrics.count("engine.segment_cache.hit")
                return racer
            self.misses += 1
            metrics.count("engine.segment_cache.miss")
            self._entries[key] = compiled
            while len(self._entries) > self.maxsize:
                self._entries.popitem(last=False)
                self.evictions += 1
                metrics.count("engine.segment_cache.eviction")
            return compiled

    def get(self, segment: Segment, table: Table, builds: tuple = (),
            dense_k: Optional[int] = None,
            build_row: Optional[tuple] = None,
            prepared: tuple = ()) -> CompiledSegment:
        """The chunk program of ``segment`` over ``table``'s shape class;
        ``dense_k``: its aggregate's dense form over that many key slots
        (``agg_domain``), ``build_row`` its build-row form
        (``build_row_join``), each a part of the shape class, as are the
        probe methods of the builds ``prepared`` for it."""
        probes = probe_methods(segment, builds, prepared)
        key = (segment.fingerprint(), _dense_class(shape_class(table),
                                                   dense_k, build_row),
               (tuple(shape_class(b) for b in builds), probes))

        def build():
            key_dtypes = () if segment.agg is None else tuple(
                _resolve_dtype(k, table, builds) for k in segment.agg.keys)
            return CompiledSegment(key, segment, key_dtypes, probes, dense_k,
                                   build_row)

        return self._lookup(key, build)

    def get_decode(self, segment: Segment, geom, builds: tuple = (),
                   dense_k: Optional[int] = None,
                   build_row: Optional[tuple] = None,
                   prepared: tuple = ()) -> CompiledDecodeSegment:
        """The fused scan-decode variant of :meth:`get`: keyed by
        (fingerprint, page geometry, build shapes and probe methods) — one
        executable per (plan segment, page-geometry bucket) class, shared by
        every chunk whose pages quantize to the same buckets."""
        probes = probe_methods(segment, builds, prepared)
        key = (segment.fingerprint(),
               _dense_class(("device_decode", geom), dense_k, build_row),
               (tuple(shape_class(b) for b in builds), probes))

        def build():
            from ..ops.parquet_decode import probe_table
            key_dtypes = () if segment.agg is None else tuple(
                _resolve_dtype(k, probe_table(geom), builds)
                for k in segment.agg.keys)
            return CompiledDecodeSegment(key, segment, key_dtypes, geom,
                                         probes, dense_k, build_row)

        return self._lookup(key, build)

    def get_combine(self, chunk: CompiledSegment, cap: int,
                    partials: tuple) -> CompiledCombine:
        """The merge program of a streamed aggregate (see
        ``combine_partials``): keyed by the segment's fingerprint under a
        tag of its own (the census of chunk shape classes in verify.py
        counts it apart), the capacity every partial is cut to, the key
        dtypes, and the class of every partial of the BUCKETED tuple — so
        a trace is always a miss here, and ``engine.segment_cache.miss``
        covers the merge's compiles as it covers the chunk program's."""
        key = (chunk.segment.fingerprint() + "+combine",
               (cap, chunk.key_dtypes,
                tuple(_partial_class(p) for p in partials)), ())
        return self._lookup(key, lambda: CompiledCombine(
            key, chunk.segment, chunk.key_dtypes, cap))

    def get_tail(self, tail: "Tail", part: "PaddedPartial",
                 dims: tuple) -> "CompiledTail":
        """The program of a ``tail`` stage (see ``run_tail``): keyed by the
        region's fingerprint under a tag of its own, the class of the
        padded partial it takes and the shape classes of its padded
        dimension inputs — power-of-two buckets all, so another seed's
        data compiles nothing."""
        key = (tail.fingerprint() + "+tail",
               (part.key_dtypes, _partial_class(part.parts)),
               tuple(shape_class(t) for t, _ in dims))
        return self._lookup(key, lambda: CompiledTail(
            key, tail, part.key_dtypes))

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> dict:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions,
                    "size": len(self._entries), "maxsize": self.maxsize}

    def snapshot_keys(self) -> list:
        """Current cache keys ``(fingerprint, shape_class, build_classes)``
        — the verifier's shape-class-explosion census reads this."""
        with self._lock:
            return list(self._entries)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()


#: process-wide compiled-segment cache (the executor's jit layer)
SEGMENT_CACHE = SegmentCache()


# -- boundary materialization ----------------------------------------------

def run_map_segment(compiled: CompiledSegment, table: Table,
                    nvalid=None) -> Table:
    """Fused chain then ONE compaction at the breaker boundary (the only
    host sync the whole chain pays, vs one per interpreted Filter)."""
    from ..ops.selection import apply_boolean_mask
    from .expr import raise_if_overflow
    out, live, ovf = compiled(table, nvalid)
    metrics.host_sync(label="segment-boundary-compaction")
    with op_scope("engine.sync_wait", timed=True,
                  label="segment-boundary-compaction"):
        raise_if_overflow(ovf)  # rides the fetch: a program with arithmetic
        return apply_boolean_mask(out, live)  # fetches the survivor count


def _compact_padded(key_dtypes, kdat, kval, out_aggs, ngroups,
                    names, ovf=None) -> Table:
    """groupby's padded->compact tail for fused outputs (fixed-width only,
    which runtime eligibility guarantees).  The overflow flag ``ovf``
    rides the group count's fetch; an aggregate with no keys (one group,
    known) fetches its columns and the flag in that one fetch instead."""
    from .expr import raise_if_overflow
    metrics.host_sync(label="groupby-compaction")
    if not key_dtypes:
        with op_scope("engine.sync_wait", timed=True,
                      label="groupby-compaction"):
            datas, valids, ovf = jax.device_get(
                (tuple(c.data for c in out_aggs),
                 tuple(c.validity for c in out_aggs), ovf))
        raise_if_overflow(ovf)
        return Table([Column(c.dtype, data=jnp.asarray(d[:1]),
                             validity=None if v is None
                             else jnp.asarray(v[:1]))
                      for c, d, v in zip(out_aggs, datas, valids)], names)
    with op_scope("engine.sync_wait", timed=True,
                  label="groupby-compaction"):
        ng, ovf = jax.device_get((ngroups, ovf))  # the one host sync
    raise_if_overflow(ovf)
    ng = int(ng)
    cols = []
    for dtype, data, valid in zip(key_dtypes, kdat, kval):
        v = np.asarray(valid)[:ng]
        cols.append(Column(dtype, data=jnp.asarray(np.asarray(data)[:ng]),
                           validity=jnp.asarray(v) if not v.all() else None))
    for c in out_aggs:
        data = jnp.asarray(np.asarray(c.data)[:ng])
        valid = None if c.validity is None else \
            jnp.asarray(np.asarray(c.validity)[:ng])
        cols.append(Column(c.dtype, data=data, validity=valid))
    return Table(cols, names)


def run_agg_segment(compiled: CompiledSegment, table: Table,
                    nvalid=None) -> Table:
    """Fused chain + aggregate, compacted to the final group rows."""
    agg = compiled.segment.agg
    kdat, kval, out_aggs, _glive, ngroups, ovf = compiled(table, nvalid)
    return _compact_padded(compiled.key_dtypes, kdat, kval, out_aggs,
                           ngroups, list(agg.keys) + list(agg.names), ovf)


@jax.jit
def _max_ngroups(ngroups: tuple):
    """The sizing reduce of a merge: one launch, one scalar."""
    return jnp.max(jnp.stack(ngroups))


#: the most partials one launch of the merge program takes: the bucket
#: the benchmark's 11- and 12-chunk streams compile.  A power of two.
COMBINE_ARITY = 16


def _merge_padded(partials: list, compiled: CompiledSegment, width: int,
                  sync_label: Optional[str], **span_stats) -> tuple:
    """Size and launch ONE merge of ``partials`` — ``[(kdat, kval,
    out_aggs, glive, ngroups, ovf), ...]``, at most ``width`` of them —
    and return the program's padded ``(kdat, kval, out_aggs, ngroups,
    ovf)``.

    A keyless aggregate's partials hold one slot each, known on the host:
    ``sync_label`` None, no sizing fetch, capacity 1.

    One host sync (the caller has counted it under ``sync_label``), the
    scalar ``max(ngroups)`` fetch that sizes the merge, and it matters:
    each chunk's partial is padded to the chunk's row bucket (e.g. 262,144
    slots for 12 live groups), and ``groupby_padded`` over num_chunks x
    bucket dead rows costs seconds.  Live groups are packed at the FRONT
    of the padded arrays (that is what the [:ngroups] compaction relies
    on), so slicing every partial to one power-of-two capacity >=
    max(ngroups) preserves every live group, keeps the merge's shape
    stable across runs (jit reuse), and shrinks it by ~bucket/cap.  The
    capacity is sized from what THESE partials hold, a merged one among
    them included, so a merge never drops a group.

    The tuple is filled up to ``width`` with repeats of the newest
    partial, which the program masks dead (``nreal``) — dead rows sort
    behind every live one and add to no group.
    """
    from ..ops.parquet_decode import bucket
    nreal = len(partials)
    filled = tuple(partials) + (partials[-1],) * (width - nreal)
    if sync_label is None:
        cap = 1
    else:
        # where the host waits until the device has drained every segment
        # streamed so far: the first fetch after their launches
        with op_scope("engine.sync_wait", timed=True, label=sync_label):
            maxng = int(_max_ngroups(tuple(p[4] for p in filled)))
        cap = bucket(maxng, 64)
    filled = tuple(p[:4] + (p[5],) for p in filled)
    merge = SEGMENT_CACHE.get_combine(compiled, cap, filled)
    with op_scope("engine.combine", timed=True, partials=nreal, cap=cap,
                  **span_stats):
        return merge(filled, nreal)


class StreamedPartials:
    """The padded partial aggregates of ONE streamed aggregate, as the
    chunk loop hands them in — straight off the fused agg program, still
    padded, never synced per chunk.

    A stream of at most ``COMBINE_ARITY`` chunks is merged once, after the
    stream (``finish``): two host syncs, the sizing fetch and the final
    ``ngroups`` of the compaction tail, and between them ONE launch of a
    program whose arity is the partial count's power-of-two bucket (11 and
    12 chunks share the 16-partial program).

    A longer stream FOLDS as it runs: when ``COMBINE_ARITY`` partials are
    pending and one more chunk is about to be launched (``make_room``),
    they are merged into one partial — ``COMBINE_ARITY`` x cap slots, its
    group count where a chunk's partial has its mask — which takes the
    first place of the next merge.  So the device never holds more than
    ``COMBINE_ARITY`` padded partials, and a stream of any length runs two
    merge programs: 16 padded partials (the first fold: the short
    stream's program), and 1 merged + 15 padded (every later fold and the
    final merge, filled with dead repeats).  Each fold pays its own sizing
    sync (``combine-fold-sizing``), which is what keeps it exact: a key
    that first shows late in the file, or a merged partial with more
    groups than any chunk had, raises THAT merge's capacity (another
    program, never a dropped group).  Sums, counts, minima and maxima
    merge associatively (``STREAM_COMBINE``), so folding changes no
    result but the last bits of a float sum whose order matters.

    An aggregate with no group keys has one-slot partials: neither a fold
    nor the final merge fetches a size, and the result's one fetch
    (``compact``) is the stream's only host sync, however long it is.
    The overflow flags of the partials ride every merge into that fetch.
    """

    __slots__ = ("pending", "compiled", "folds", "held")

    def __init__(self):
        self.pending: list = []     # a merged partial, if any, comes first
        self.compiled = None        # the chunk program of the newest
        self.folds = 0
        self.held = 0               # most padded partials held at once

    def __len__(self) -> int:
        return len(self.pending)

    def make_room(self) -> None:
        """Before a chunk program is launched: fold if the pending
        partials fill a merge."""
        if len(self.pending) < COMBINE_ARITY:
            return
        self.folds += 1
        metrics.count("engine.combine.folds")
        label = None
        if self.compiled.segment.agg.keys:
            label = "combine-fold-sizing"
            metrics.host_sync(label="combine-fold-sizing")
        kdat, kval, out_aggs, ngroups, ovf = _merge_padded(
            self.pending, self.compiled, COMBINE_ARITY, label,
            fold=self.folds, final=0)
        self.pending = [(kdat, kval, out_aggs, ngroups, ngroups, ovf)]

    def add(self, partial: tuple, compiled: CompiledSegment) -> None:
        self.pending.append(partial)
        self.compiled = compiled
        self.held = max(self.held, len(self.pending) - (self.folds > 0))

    def merge(self) -> "PaddedPartial":
        """The final merge, still padded: what a ``tail`` stage takes."""
        from ..ops.parquet_decode import bucket
        metrics.observe("engine.stream.partials_held", self.held)
        if self.folds:
            width, stats = COMBINE_ARITY, {"fold": self.folds + 1}
        else:
            width, stats = bucket(len(self.pending), 1), {}
        agg = self.compiled.segment.agg
        label = None
        if agg.keys:
            label = "combine-sizing"
            metrics.host_sync(label="combine-sizing")
        kdat, kval, out_aggs, ngroups, ovf = _merge_padded(
            self.pending, self.compiled, width, label, final=1, **stats)
        return PaddedPartial(self.compiled.key_dtypes, kdat, kval, out_aggs,
                             ngroups, list(agg.keys) + list(agg.names), ovf)

    def finish(self) -> Table:
        """The final merge and its compaction: the aggregate's Table."""
        return self.merge().compact()


@functools.partial(jax.jit, static_argnums=(2,))
def _add_build_rows(acc: tuple, part: tuple, checked: tuple) -> tuple:
    """``acc + part``, two build-row partials ``(rows, aggregate Columns,
    ovf, sparse)``: slot by slot, a sum's validity the OR of both (it has a
    value where either had one), ``sparse`` the count of chunks whose live
    rows were compacted.  ``checked``: the aggregates that are decimal
    sums, whose totals ``sum_check`` guards as the merge of the sort form
    guards its partial sums."""
    from .expr import any_flag, sum_check
    rows = acc[0] + part[0]
    aggs = tuple(Column(a.dtype, data=a.data + b.data,
                        validity=None if a.validity is None
                        else a.validity | b.validity)
                 for a, b in zip(acc[1], part[1]))
    ovf = [f for f in (acc[2], part[2]) if f is not None]
    for j in checked:
        sum_check(aggs[j], rows > 0, ovf)
    return rows, aggs, any_flag(ovf), acc[3] + part[3]


@jax.jit
def _count_build_rows(rows, sparse):
    """The build-row form's sizing reduce: ``[group count, compacted
    chunks]``, one fetch."""
    return jnp.stack([jnp.sum((rows > 0).astype(jnp.int32)), sparse])


@functools.partial(jax.jit, static_argnums=(2, 3))
def _build_row_groups(acc: tuple, pb, sources: tuple, cap: int) -> tuple:
    """The build-row form's merged partial as the sort form's merge leaves
    one: ``(kdat, kval, aggregate Columns, ngroups)`` over ``cap`` slots,
    the groups packed at the front in the build's key order (the order of
    the sort form's groups, whose first key is the join's).  A group's keys
    are its build row's: the payload column each of ``sources`` names, the
    build key for None.  A compaction by prefix count and scatter: no
    sort."""
    rows, aggs = acc[0], acc[1]
    present = jnp.take(rows > 0, pb.r_order)    # in the build's key order
    ngroups = jnp.sum(present.astype(jnp.int32))
    dest = jnp.where(present, jnp.cumsum(present.astype(jnp.int32)) - 1,
                     np.int32(cap))
    at = jnp.zeros((cap,), jnp.int32).at[dest].set(pb.r_order, mode="drop")
    kdat, kval = [], []
    for src in sources:
        c = pb.rk.columns[0] if src is None else pb.payload.column(src)
        kdat.append(jnp.take(c.data, at))
        kval.append(jnp.take(c.valid_mask(), at))
    out = tuple(Column(c.dtype, data=jnp.take(c.data, at),
                       validity=None if c.validity is None
                       else jnp.take(c.validity, at)) for c in aggs)
    return tuple(kdat), tuple(kval), out, ngroups


class BuildRowPartials:
    """``StreamedPartials`` for a chunk program whose aggregate takes the
    build-row form (``build_row_join``): each chunk's partial holds one
    slot per build row, so the stream's merge is a slot-by-slot sum, one
    small program per chunk (``_add_build_rows``), and nothing folds: the
    device holds the running total and one chunk's partial.  ``merge``
    then pays the sort form's sizing fetch (``combine-sizing``: how many
    build rows were joined, and in how many chunks the live rows were
    compacted before the scatter-add — ``engine.agg.build_sparse``, the
    others ``engine.agg.build_full``) and compacts them to the power-of-two
    bucket of that count (``_build_row_groups``) — the padded partial a
    ``tail`` takes, or ``finish`` compacts."""

    __slots__ = ("pb", "sources", "acc", "compiled", "chunks", "folds",
                 "held", "checked")

    def __init__(self, pb, sources: tuple):
        self.pb = pb                # the prepared build a group is a row of
        self.sources = sources      # build_row_join's key sources
        self.acc = None             # (rows, aggregate Columns, ovf, sparse)
        self.compiled = None
        self.chunks = 0
        self.folds = 0
        self.held = 0
        self.checked = ()

    def __len__(self) -> int:
        return self.chunks

    def make_room(self) -> None:
        """Nothing to fold: the running total takes every chunk."""

    def add(self, partial: tuple, compiled: CompiledSegment) -> None:
        if self.acc is None:
            agg = compiled.segment.agg
            self.checked = tuple(j for j, c in enumerate(partial[1])
                                 if agg.aggs[j][1] == "sum"
                                 and c.dtype.is_decimal)
            self.acc = partial
        else:
            with op_scope("engine.combine", timed=True, partials=2):
                self.acc = _add_build_rows(self.acc, partial, self.checked)
        self.compiled = compiled
        self.chunks += 1
        self.held = 1

    def merge(self) -> "PaddedPartial":
        """The stream's groups, still padded: what a ``tail`` stage
        takes."""
        from ..ops.parquet_decode import bucket
        metrics.observe("engine.stream.partials_held", self.held)
        metrics.host_sync(label="combine-sizing")
        with op_scope("engine.sync_wait", timed=True, label="combine-sizing"):
            ng, sparse = (int(v) for v in np.asarray(
                _count_build_rows(self.acc[0], self.acc[3])))
        # per chunk one of the two: its live rows compacted, or all
        # scattered
        metrics.count("engine.agg.build_sparse", sparse)
        metrics.count("engine.agg.build_full", self.chunks - sparse)
        cap = bucket(ng, 64)
        with op_scope("engine.combine", timed=True, partials=self.chunks,
                      cap=cap, final=1):
            kdat, kval, aggs, ngroups = _build_row_groups(
                self.acc, self.pb, self.sources, cap)
        agg = self.compiled.segment.agg
        return PaddedPartial(self.compiled.key_dtypes, kdat, kval, aggs,
                             ngroups, list(agg.keys) + list(agg.names),
                             self.acc[2])

    def finish(self) -> Table:
        """The final merge and its compaction: the aggregate's Table."""
        return self.merge().compact()


class PaddedPartial:
    """A streamed aggregate's merged result as its merge program left it:
    key buffers, their validity and the aggregate Columns at the merge's
    slot count, the live groups packed at the front, their number a device
    scalar nobody has fetched.  ``compact`` is the padded->compact tail
    (one sync); a ``tail`` stage takes the partial as it is."""

    __slots__ = ("key_dtypes", "kdat", "kval", "aggs", "ngroups", "names",
                 "ovf")

    def __init__(self, key_dtypes, kdat, kval, aggs, ngroups, names,
                 ovf=None):
        self.key_dtypes = tuple(key_dtypes)
        self.kdat = tuple(kdat)
        self.kval = tuple(kval)
        self.aggs = tuple(aggs)
        self.ngroups = ngroups
        self.names = list(names)
        self.ovf = ovf          # the overflow flag the merge left, or None

    @property
    def parts(self) -> tuple:
        """``(kdat, kval, aggs, ngroups)``: a merged partial as the merge
        and the tail programs take it."""
        return self.kdat, self.kval, self.aggs, self.ngroups

    @property
    def columns(self) -> tuple:
        """The padded columns (what ``table_nbytes`` sums)."""
        return tuple(Column(dt, data=d, validity=v) for dt, d, v in
                     zip(self.key_dtypes, self.kdat, self.kval)) + self.aggs

    @property
    def num_rows(self) -> int:
        """Slots, live and dead."""
        return _partial_slots(self.parts)

    def compact(self) -> Table:
        return _compact_padded(self.key_dtypes, self.kdat, self.kval,
                               self.aggs, self.ngroups, self.names, self.ovf)


def combine_partials(partials: list, compiled: CompiledSegment) -> Table:
    """Merge per-chunk padded partial aggregates — ``[(kdat, kval,
    out_aggs, glive, ngroups), ...]`` off ``compiled`` — into the final
    Table, as a stream that handed them in one by one would have
    (``StreamedPartials``)."""
    acc = StreamedPartials()
    for p in partials:
        acc.make_room()
        acc.add(p, compiled)
    return acc.finish()


# -- the tail: the operators above a streamed aggregate, as one program -----
#
# Above a ``stream-agg`` stage a plan still has a few small operators — join
# the store, group by manager, sort; or top-k of 150 brands — and interpreted
# they cost some fifty eager launches and as many host round trips for
# kernels of microseconds.  A ``Tail`` is that region, from the plan's root
# down to the streamed Aggregate: it takes the merged partial STILL PADDED
# (``PaddedPartial``) and runs every node over padded columns under one live
# mask — filters AND into it, joins probe at probe-row shape, group-bys are
# ``groupby_padded``, a sort puts dead rows last, a limit is a mask — in ONE
# jitted program compacted once, at its end (``run_tail``: one sync).

#: unary nodes a tail runs besides Join and Aggregate
_TAIL_UNARY = (Filter, Project, Sort, Limit, TopK)


class Tail:
    """``source`` (the ``stream-agg`` Aggregate) ``-> nodes`` (execution
    order, the plan's root last)."""

    __slots__ = ("nodes", "source", "_fp")

    def __init__(self, nodes: tuple, source: Aggregate):
        self.nodes = nodes
        self.source = source
        self._fp: Optional[str] = None

    def joins(self) -> tuple:
        """Join nodes of the region, execution order."""
        return tuple(nd for nd in self.nodes if isinstance(nd, Join))

    def fingerprint(self) -> str:
        """Structure-only identity, the inputs' names included."""
        if self._fp is None:
            sig = [("source", tuple(self.source.keys),
                    tuple(self.source.names))]
            sig += [_node_sig(nd) for nd in self.nodes]
            self._fp = hashlib.sha256(repr(tuple(sig)).encode()).hexdigest()
        return self._fp


def build_tail(root: PlanNode, source: Aggregate, scan: PlanNode,
               nparents: dict) -> Optional[Tail]:
    """The tail rooted at the plan's ``root``, or None: grow downward
    through Filter / Project / Sort / Limit / TopK, Aggregates of fast ops
    and inner / semi Joins whose right side does not depend on ``scan``,
    along the side that does, until ``source`` — the Aggregate that streams
    over ``scan``, which stays a stage of its own.  Any other node on the
    way (an Exchange, a cross join, a join fed from the right), or an
    interior node with a second parent, and there is no tail."""
    from .expr import is_arith
    if not source.keys:
        return None     # one row: nothing above it is worth a program
    dep: dict = {}
    chain = []
    cur = root
    while cur is not source:
        if cur is not root and nparents.get(id(cur), 1) != 1:
            return None
        if (isinstance(cur, Project) and cur.computed) or \
                (isinstance(cur, Filter) and is_arith(cur.predicate)):
            return None     # a tail moves columns; it computes none
        if isinstance(cur, _TAIL_UNARY):
            below = cur.child
        elif isinstance(cur, Aggregate) and _agg_fusable(cur):
            below = cur.child
        elif (isinstance(cur, Join) and cur.how in _FUSABLE_JOINS
              and depends_on(cur.left, scan, dep)
              and not depends_on(cur.right, scan, dep)):
            below = cur.left
        else:
            return None
        chain.append(cur)
        cur = below
    if not chain or nparents.get(id(cur), 1) != 1:
        return None
    return Tail(tuple(reversed(chain)), cur)


def _tail_col_ok(dt) -> bool:
    """Dtype gate of a column a tail carries (the static shadow and the run
    share it): every column is masked, gathered and fetched as one 1-D
    fixed-width buffer (DECIMAL128's is (n, 2) limbs)."""
    from ..dtypes import TypeId
    return dt.is_fixed_width and dt.id != TypeId.DECIMAL128


def tail_static_eligible(tail: Tail, schema) -> bool:
    """From ``schema(node) -> {name: DType} | None`` (the verifier's
    resolved view): False when a column the tail would carry — the source's
    output, an inner join's build side, a semi join's build keys — is not
    ``_tail_col_ok``.  Unknown schemas assume eligible; the run decides."""
    carried = [schema(tail.source)]
    for j in tail.joins():
        sch = schema(j.right)
        if sch is not None and j.how == "semi":
            sch = {k: sch.get(k) for k in j.right_keys}
        carried.append(sch)
    return all(dt is None or _tail_col_ok(dt)
               for sch in carried if sch is not None for dt in sch.values())


def tail_runtime_eligible(tail: Tail, part: "PaddedPartial",
                          dims: tuple) -> bool:
    """The actual inputs' veto (mirrors ``tail_static_eligible``)."""
    def ok(c: Column) -> bool:
        return _tail_col_ok(c.dtype) and c.data is not None \
            and c.data.ndim == 1

    try:
        cols = list(part.columns)
        for j, d in zip(tail.joins(), dims):
            cols += [d.column(k) for k in j.right_keys] \
                if j.how == "semi" else list(d.columns)
    except (KeyError, ValueError):
        return False
    return all(ok(c) for c in cols)


def _take(col: Column, idx) -> Column:
    """``col`` at ``idx`` (in bounds by construction), validity kept as it
    is: the tail tracks what the interpreted gathers would have made of it
    apart (``_build_tail_fn``)."""
    return Column(col.dtype, data=jnp.take(col.data, idx),
                  validity=None if col.validity is None
                  else jnp.take(col.validity, idx))


def _tail_join(nd: Join, cols: list, names: list, live, right: Table,
               rlive):
    """One join of a tail at probe-row shape: ``(cols, names, live,
    spill)``.  ``ops.join.probe_padded`` finds each live left row's build
    row; a semi join only masks, an inner join selects the build side's
    non-key columns at the matched rows (``_probe_join_node``'s two ways,
    by the probe's method) under the interpreted join's names."""
    from ..ops.join import probe_method, probe_padded, select_build_rows
    left = Table(cols, names)
    lk = Table([left.column(k) for k in nd.left_keys])
    rk = Table([right.column(k) for k in nd.right_keys])
    ri, matched, spill = probe_padded(lk, rk, live, rlive)
    live = live & matched
    compare = probe_method(
        right.num_rows, list(lk.columns) + list(rk.columns)) == "compare"
    if nd.how == "semi":
        # the compare probe asks only whether a build row matches
        return cols, names, live, (0 if compare else spill)
    cols, names = list(cols), list(names)
    lnames = list(names)
    for nm, c in zip(right.names, right.columns):
        if nm in nd.right_keys:
            continue
        cols.append(select_build_rows(c, ri) if compare else _take(c, ri))
        names.append(_join_out_name(nm, lnames))
    return cols, names, live, spill


#: a tail's top-k of at most this many rows selects them one by one
#: (``_select_first``) instead of sorting every slot
TOPK_SELECT_MAX = 16


def _select_first(words: list, live, n: int) -> tuple:
    """``(positions, found)`` of the first ``n`` live rows in the stable
    order of ``words`` (``ops.order.encode_keys``: most significant first,
    ascending): ``n`` rounds, each the lexicographic least row left — a
    masked min per word, the lowest position among the ties — so the
    order is the stable sort's, and no sort is compiled.  ``found`` is
    False past the last live row."""
    slots = live.shape[0]
    idx = jnp.arange(slots, dtype=jnp.int32)
    top = np.uint64(2**64 - 1)
    left, at, found = live, [], []
    for _ in range(n):
        cand = left
        for w in words:
            cand = cand & (w == jnp.min(jnp.where(cand, w, top)))
        p = jnp.min(jnp.where(cand, idx, np.int32(slots)))
        found.append(p < slots)
        at.append(jnp.minimum(p, np.int32(slots - 1)))
        left = left & (idx != p)
    return jnp.stack(at), jnp.stack(found)


def _build_tail_fn(tail: Tail, compiled: "CompiledTail"):
    """The single program a tail traces into.

    ``fn(part, dims)``: ``part`` is the padded partial's ``(kdat, kval,
    aggs, ngroups)``, ``dims`` one ``(padded Table, live rows)`` per Join
    of the region, execution order.  Returns ``(datas, valids, nlive,
    nulls, spill)``: the result's columns with the live rows packed at the
    front in result order, their number, and two kinds of evidence the one
    fetch brings along — ``nulls`` (per group key of an Aggregate: did it
    hold a null group when it was made; the interpreted group-by keeps a
    key's validity only then) and ``spill`` (``probe_padded``'s: > 0 and
    the result is not the join's).

    What the interpreted operators do to a column's VALIDITY is tracked
    beside the data, so the result has the buffers theirs has: every gather
    (filter, join, sort, limit) leaves a validity on every column, a
    group-by leaves its keys' only where a group is null and its
    aggregates' as the kernel made them.  ``vk`` holds, per column, None
    (no validity), True (kept) or an index into ``nulls``.
    """
    src = tail.source

    def fn(part, dims):
        from ..ops.aggregate import groupby_padded
        from ..ops.order import SortKey, encode_keys
        from ..ops.selection import nonzero_indices
        from .expr import evaluate
        compiled.traces += 1  # trace-time side effect, as in _build_fn
        kdat, kval, aggs, ngroups = part
        slots = kdat[0].shape[0]
        live = jnp.arange(slots, dtype=jnp.int32) < ngroups
        nulls: list = []

        def grouped(keys, aggs, glive) -> tuple:
            """``(cols, vk)`` of a group-by's padded output."""
            vk = []
            for c in keys:      # a key keeps its validity iff a group is null
                nulls.append(jnp.any(glive & ~c.validity))
                vk.append(len(nulls) - 1)
            return list(keys) + list(aggs), \
                vk + [None if c.validity is None else True for c in aggs]

        cols, vk = grouped(
            [Column(dt, data=d, validity=v) for dt, d, v in
             zip(compiled.key_dtypes, kdat, kval)], aggs, live)
        names = list(src.keys) + list(src.names)
        packed = True       # live rows at the front, in result order
        spill = jnp.int64(0)
        ji = 0

        def sort_by(keys):
            words = [(~live).astype(jnp.uint64)] + encode_keys(
                [SortKey(cols[names.index(c)], ascending=a)
                 for c, a in keys])
            return jnp.lexsort(tuple(reversed(words)))  # stable; dead last

        for nd in tail.nodes:
            if isinstance(nd, Filter):
                # no arithmetic here (``build_tail``): nothing to check
                vals, valid, _ = evaluate(nd.predicate, Table(cols, names),
                                          [])
                m = jnp.asarray(vals, jnp.bool_)
                if valid is not None:
                    m = m & valid  # SQL semantics: NULL comparison drops
                live, packed = live & m, False
                vk = [True] * len(cols)
            elif isinstance(nd, Project):
                at = [names.index(c) for c in nd.columns]
                cols = [cols[i] for i in at]
                vk = [vk[i] for i in at]
                names = list(nd.columns)
            elif isinstance(nd, Join):
                right, nvalid = dims[ji]
                ji += 1
                rlive = jnp.arange(right.num_rows, dtype=jnp.int32) < nvalid
                cols, names, live, sp = _tail_join(nd, cols, names, live,
                                                   right, rlive)
                spill, packed = spill + sp, False
                vk = [True] * len(cols)
            elif isinstance(nd, Aggregate):
                out_keys, out_aggs, ng = groupby_padded(
                    Table(cols, names), list(nd.keys),
                    [(c, op) for c, op in nd.aggs], row_mask=live)
                live = jnp.arange(live.shape[0], dtype=jnp.int32) < ng
                cols, vk = grouped(
                    [Column(s[1], data=s[2], validity=s[3])
                     for s in out_keys], out_aggs, live)
                names = list(nd.keys) + list(nd.names)
                packed = True
            elif isinstance(nd, TopK) and 0 < nd.n <= TOPK_SELECT_MAX \
                    and nd.n < live.shape[0]:
                order, live = _select_first(encode_keys(
                    [SortKey(cols[names.index(c)], ascending=a)
                     for c, a in nd.keys]), live, nd.n)
                cols = [_take(c, order) for c in cols]
                packed = True
                vk = [True] * len(cols)
            else:
                if isinstance(nd, (Sort, TopK)):
                    order = sort_by(nd.keys)
                    cols = [_take(c, order) for c in cols]
                    live, packed = jnp.take(live, order), True
                if isinstance(nd, (Limit, TopK)):
                    live = live & (jnp.cumsum(live.astype(jnp.int32))
                                   <= np.int32(min(nd.n, 2**31 - 1)))
                vk = [True] * len(cols)
        if not packed:
            order = nonzero_indices(live, count=live.shape[0])
            cols = [_take(c, order) for c in cols]
        # static at trace: what the host makes the result Table of
        compiled.out_names = tuple(names)
        compiled.out_dtypes = tuple(c.dtype for c in cols)
        compiled.out_vk = tuple(vk)
        return (tuple(c.data for c in cols),
                tuple(None if k is None else c.valid_mask()
                      for c, k in zip(cols, vk)),
                jnp.sum(live, dtype=jnp.int32), tuple(nulls), spill)

    return fn


class CompiledTail(CompiledSegment):
    """The program of one tail: a SEGMENT_CACHE entry keyed by the region's
    fingerprint and its inputs' shape classes.  A compile ticks
    ``engine.segment.compile`` like a chunk program's (the counter a
    measured window holds at 0); a replay has a counter of its own, so the
    chunk program's replays stay the number of chunks."""

    __slots__ = ("tail", "out_names", "out_dtypes", "out_vk")

    counters = "engine.tail"

    def __init__(self, key: tuple, tail: Tail, key_dtypes: tuple):
        self.key = key
        self.segment = None
        self.tail = tail
        self.key_dtypes = key_dtypes
        self.probes = ()        # counted by the chunk programs only
        self.dense_k = self.build_row = self.agg_form = None
        from .expr import count_nodes
        self.exprs = sum(count_nodes(nd.predicate) for nd in tail.nodes
                         if isinstance(nd, Filter))
        self.traces = 0
        self.calls = 0
        self.out_names = self.out_dtypes = self.out_vk = None
        self.jfn = jax.jit(_build_tail_fn(tail, self))

    def __call__(self, part: tuple, dims: tuple):
        return self._launch(part, dims)

    def _tick(self, compiled: bool, dt: float) -> None:
        if compiled:
            metrics.count("engine.segment.compile")
            metrics.observe("engine.tail.trace_s", dt)
        else:
            metrics.count("engine.tail.replay")


#: the fewest slots a tail's dimension input is padded to
TAIL_DIM_FLOOR = 8


@functools.partial(jax.jit, static_argnums=(1,))
def _pad_rows(table: Table, slots: int) -> Table:
    """``table`` zero-filled up to ``slots`` rows: one launch."""
    def pad(a):
        return None if a is None else \
            jnp.pad(a, ((0, slots - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))

    return Table([Column(c.dtype, data=pad(c.data),
                         validity=pad(c.validity)) for c in table.columns],
                 table.names)


def tail_dims(tail: Tail, tables: tuple) -> tuple:
    """Each Join's build Table as the program takes it: ``(the columns it
    carries, padded to their power-of-two row bucket; live rows)`` — so
    another seed's dimension of another size runs the same program."""
    from ..ops.parquet_decode import bucket
    dims = []
    for j, t in zip(tail.joins(), tables):
        if j.how == "semi":
            t = t.select(list(j.right_keys))
        slots = bucket(t.num_rows, TAIL_DIM_FLOOR)
        dims.append((t if slots == t.num_rows else _pad_rows(t, slots),
                     np.int32(t.num_rows)))
    return tuple(dims)


def run_tail(compiled: CompiledTail, part: PaddedPartial,
             dims: tuple) -> Optional[tuple]:
    """Launch the tail's program and compact its result: ``(Table, source
    groups)``, or None where a join found a second candidate for some row
    (``probe_padded``'s spill: the caller demotes).

    ONE deliberate host sync, ``tail-compaction``: the live row count, the
    result's columns and the program's evidence come in one batched fetch,
    and the result is made of the fetched buffers as they are — the rows
    are few, and a result's next stop is the wire."""
    from .expr import raise_if_overflow
    datas, valids, nlive, nulls, spill = compiled(part.parts, dims)
    metrics.host_sync(label="tail-compaction")
    with op_scope("engine.sync_wait", timed=True, label="tail-compaction"):
        datas, valids, nlive, nulls, spill, ngroups, ovf = jax.device_get(
            (datas, valids, nlive, nulls, spill, part.ngroups, part.ovf))
    raise_if_overflow(ovf)
    if int(spill):
        return None
    n = int(nlive)
    cols = []
    for dt, d, v, k in zip(compiled.out_dtypes, datas, valids,
                           compiled.out_vk):
        keep = k is True or (k is not None and bool(nulls[k]))
        cols.append(Column(dt, data=d[:n],
                           validity=v[:n] if keep else None))
    return Table(cols, list(compiled.out_names)), int(ngroups)


# -- whole-stage fusion: the exchange inside the program --------------------
#
# The segments above stop at pipeline breakers, and Exchange is the breaker
# that costs the most: the host orchestrates a two-phase shuffle (counts
# sync + compaction sync) BETWEEN the partial and final aggregate programs
# of a distributed group-by.  Flare's whole-stage result (PAPERS.md) says
# the stage should be ONE native program, so ``FusedStage`` lowers the
# optimizer's ``partial-agg -> hash Exchange -> final-agg`` sandwich into a
# single jit(shard_map(...)) callable: per-shard partial groupby, murmur3
# bucket scatter, one dense all_to_all, per-shard combine groupby — zero
# host round-trips between the three plan nodes.  Capacity sizing moves
# device-side (a static function of the shard shape, overflow-checked), so
# the whole stage pays exactly ONE deliberate host sync: the boundary
# compaction.  Flag-gated by SRJT_FUSE_EXCHANGE; the host-orchestrated
# path remains the fallback (runtime-ineligible schema, AQE probe routing,
# capacity overflow) with bit-exact row-multiset parity.

#: partial-side ops a fused stage supports: must both run on groupby's
#: fast traced path (ops.aggregate._FAST_OPS) and decompose into a merge
#: op (plan.STREAM_COMBINE keys) — the optimizer's sandwich
#: construction guarantees this; the detector re-checks for hand-built
#: plans
_FUSED_PARTIAL_OPS = frozenset({"sum", "count", "count_all", "min", "max"})
#: merge-side ops (the STREAM_COMBINE value set)
_FUSED_COMBINE_OPS = frozenset({"sum", "min", "max"})


class FusedStage:
    """One distributed stage — ``Aggregate(final) -> Exchange(hash) ->
    Aggregate(partial)`` — compiled as a single pjit program."""

    __slots__ = ("combine", "exchange", "partial", "_fp")

    def __init__(self, combine: Aggregate, exchange, partial: Aggregate):
        self.combine = combine
        self.exchange = exchange
        self.partial = partial
        self._fp: Optional[str] = None

    def sel_names(self) -> list:
        """Input columns the stage consumes: group keys then agg inputs."""
        out = list(self.combine.keys)
        for c, _ in self.partial.aggs:
            if c is not None and c not in out:
                out.append(c)
        return out

    def fingerprint(self) -> str:
        if self._fp is None:
            sig = ("fused-stage", tuple(self.combine.keys),
                   tuple(self.partial.aggs), tuple(self.partial.names),
                   tuple(self.combine.aggs), tuple(self.combine.names),
                   tuple(self.exchange.keys))
            self._fp = hashlib.sha256(repr(sig).encode()).hexdigest()
        return self._fp


def fused_sandwich(node) -> Optional[FusedStage]:
    """Detect the partial/final sandwich rooted at ``node`` (the same
    structural test as ``verify.decision_census``) plus op eligibility.
    Returns None when ``node`` cannot head a fused stage."""
    from .plan import Exchange
    if not isinstance(node, Aggregate):
        return None
    ex = node.child
    if not (isinstance(ex, Exchange) and ex.kind == "hash"):
        return None
    p = ex.child
    if not (isinstance(p, Aggregate) and p.keys
            and tuple(p.keys) == tuple(node.keys)
            and tuple(p.names) == tuple(node.names)):
        return None
    if not set(ex.keys) <= set(node.keys):
        return None  # the exchange must co-locate whole groups
    if len(node.aggs) != len(p.aggs):
        return None
    if any(op not in _FUSED_PARTIAL_OPS for _, op in p.aggs):
        return None
    if any(op not in _FUSED_COMBINE_OPS for _, op in node.aggs):
        return None
    return FusedStage(node, ex, p)


def _fused_col_ok(dt) -> bool:
    """Dtype gate shared by the static (verify) and runtime checks: stage
    columns cross the exchange as dense u32 word planes, so they must be
    1-D fixed-width (no strings/nested; DECIMAL128's (n, 2) limb buffer
    breaks the single-plane-per-word decomposition)."""
    return (dt.is_fixed_width and not dt.is_string and not dt.is_nested
            and not dt.is_decimal)


def fused_static_eligible(stage: FusedStage, schema=None) -> bool:
    """Schema-level eligibility from a name -> DType mapping (the
    verifier's resolved view).  Unknown columns assume eligible — the
    runtime check over the actual table has the final veto, and an
    ineligible stage falls back to the host-orchestrated path."""
    if schema is None:
        return True
    for nm in stage.sel_names():
        dt = schema.get(nm)
        if dt is not None and not _fused_col_ok(dt):
            return False
    return True


def fused_runtime_eligible(stage: FusedStage, table: Table) -> bool:
    """The actual input schema's veto (mirrors ``runtime_eligible``)."""
    try:
        for nm in stage.sel_names():
            c = table.column(nm)
            if not _fused_col_ok(c.dtype) or c.data is None \
                    or c.data.ndim != 1:
                return False
    except (KeyError, ValueError):
        return False
    return True


#: the fused stage's static per-shard live-group budget (a power of two)
FUSE_GROUPS = 4096


def fused_prefix(n_local: int) -> int:
    """Static per-shard live-group budget of the fused stage.

    The partial groupby packs its live groups to the FRONT of the padded
    output, so everything downstream of it — placement hashing, plane
    build, the pack sort, the all_to_all block, and the final combine —
    only needs to see a static PREFIX sized for the groups a shard can
    plausibly hold, not the shard's full row count.  Sizing that prefix
    from rows (the obvious static bound) makes the combine sort
    ``ndev * capacity`` mostly-dead slots and triples the stage's wall
    time on a 30k-row shard with 2k live groups, so the budget is
    ``FUSE_GROUPS`` instead (clamped by the row bound).  A shard that
    aggregates MORE live groups than the budget trips the same device-side
    psum'd overflow counter as a full exchange bucket, and the executor
    re-plans on the host-orchestrated path — a runtime fallback, never an
    error.
    """
    if n_local <= 0:
        return 1
    return min(n_local, FUSE_GROUPS)


def fused_capacity(prefix: int, ndev: int) -> int:
    """Static per-(src, dest) slot capacity of the in-program exchange.

    The host path sizes capacity from a counts pass — a deliberate host
    sync this fusion exists to delete — so capacity must be a static
    function of the compiled shape.  ``prefix`` (``fused_prefix``) bounds
    a shard's send volume and murmur3 spreads groups near-uniformly over
    destinations, so 2x the uniform share covers realistic imbalance; the
    psum'd overflow counter (fetched with the one boundary sync) detects
    the adversarial remainder and the executor falls back to the
    host-orchestrated exchange when it fires — a runtime re-plan, never
    an error.
    """
    from ..parallel.shuffle import cap_bucket
    return min(cap_bucket(2 * (-(-prefix // ndev))), cap_bucket(prefix))


def _build_fused_fn(stage: FusedStage, compiled: "CompiledFusedStage"):
    """The per-shard body of the fused stage, traced ONCE under
    ``jax.jit(shard_map(...))``: partial groupby -> murmur3 dest ->
    bucket pack -> all_to_all -> combine groupby, all device-resident.
    Registered in tools/srjt_lint.py TRACED_FUNCS and linted by
    ``verify.lint_fused_stage`` (no callbacks, no host concretization
    inside the collectives)."""
    from ..ops.aggregate import groupby_padded
    from ..ops.row_conversion import (_build_planes, _from_planes,
                                      fixed_width_layout)
    from ..parallel.shuffle import exchange_planes, partition_ids_specs

    partial, combine = stage.partial, stage.combine
    keys = list(combine.keys)
    nk = len(keys)
    sel = stage.sel_names()
    ndev, axis = compiled.ndev, compiled.axis
    capacity = compiled.capacity
    prefix = compiled.prefix

    def fn(datas, masks, n_valid):
        compiled.traces += 1  # trace-time side effect: no-recompile proof
        table = Table([Column(dt, data=d, validity=m)
                       for dt, d, m in zip(compiled.in_dtypes, datas,
                                           masks)], list(sel))
        n_local = datas[0].shape[0]
        shard = jax.lax.axis_index(axis).astype(jnp.int64)
        gid = shard * jnp.int64(n_local) + jnp.arange(n_local,
                                                      dtype=jnp.int64)
        live = gid < n_valid

        # 1) shard-local partial aggregate (live groups pack to the front)
        out_keys, out_aggs, ng1 = groupby_padded(
            table, keys, [(c, op) for c, op in partial.aggs],
            row_mask=live)
        # static prefix slice (fused_prefix): slots past the compiled
        # group budget can only hold dead padding — unless this shard
        # aggregated more live groups than the budget, which feeds the
        # same psum'd overflow defense as a full exchange bucket below.
        # Everything downstream is sized by `prefix`, not raw shard rows.
        pre_overflow = jnp.maximum(ng1 - jnp.int32(prefix), 0)
        if prefix < n_local:
            out_keys = [(s[0], s[1], s[2][:prefix],
                         None if s[3] is None else s[3][:prefix])
                        for s in out_keys]
            out_aggs = [Column(c.dtype, data=c.data[:prefix],
                               validity=None if c.validity is None
                               else c.validity[:prefix])
                        for c in out_aggs]
        glive = jnp.arange(prefix, dtype=jnp.int32) < ng1

        # 2) Spark-exact placement of each live group — the same
        #    partition_ids_specs the host exchange uses over fixed specs
        kcols = [Column(s[1], data=s[2], validity=s[3]) for s in out_keys]
        specs = tuple(("fixed", i, kcols[i].dtype) for i in range(nk))
        dest = partition_ids_specs(kcols, specs, ndev)

        # 3) partial rows -> word planes -> one dense all_to_all block
        layout = fixed_width_layout(
            [c.dtype for c in kcols] + [c.dtype for c in out_aggs])
        compiled.layout = layout  # static at trace: host wire attribution
        compiled.agg_dtypes = tuple(c.dtype for c in out_aggs)
        planes = _build_planes(
            layout,
            [c.data for c in kcols] + [c.data for c in out_aggs],
            [c.validity for c in kcols] + [c.validity for c in out_aggs])
        planes_in, rok, overflow = exchange_planes(
            planes, dest, glive, ndev, capacity, axis)

        # 4) received planes -> columns -> shard-local final combine
        datas_in, masks_in = _from_planes(layout, list(planes_in))
        recv = Table([Column(dt, data=d, validity=m)
                      for dt, d, m in zip(layout.schema, datas_in,
                                          masks_in)],
                     keys + list(partial.names))
        out_keys2, out_aggs2, ng2 = groupby_padded(
            recv, keys, [(c, op) for c, op in combine.aggs], row_mask=rok)

        # 5) stage outputs: padded combine results, plus the per-shard
        #    send-counts row (the attribution matrix rides the result
        #    fetch — no extra sync) and the psum'd overflow defense
        sent = jnp.zeros((ndev,), jnp.int32).at[
            jnp.where(glive, dest, jnp.int32(ndev))].add(1, mode="drop")
        kdat = tuple(s[2] for s in out_keys2)
        kval = tuple(s[3] for s in out_keys2)
        adat = tuple(c.data for c in out_aggs2)
        avalid = tuple(jnp.ones(c.data.shape[0], jnp.bool_)
                       if c.validity is None else c.validity
                       for c in out_aggs2)
        return (kdat, kval, adat, avalid, ng2[None], sent[None],
                jax.lax.psum(overflow + pre_overflow, axis))

    return fn


class CompiledFusedStage:
    """One (stage, input shape-class, mesh) entry: the whole distributed
    stage as one ``jax.jit(shard_map(...))`` callable, plus the trace
    counter that proves re-dispatches replay one executable."""

    __slots__ = ("key", "stage", "mesh", "axis", "ndev", "prefix",
                 "capacity", "in_dtypes", "key_dtypes", "layout",
                 "agg_dtypes", "traces", "calls", "jfn")

    def __init__(self, key: tuple, stage: FusedStage, mesh, axis: str,
                 in_dtypes: tuple, key_dtypes: tuple, n_local: int):
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import axis_size
        from ..parallel.shuffle import shard_map
        self.key = key
        self.stage = stage
        self.mesh = mesh
        self.axis = axis
        self.ndev = axis_size(mesh, axis)
        self.prefix = fused_prefix(n_local)
        self.capacity = fused_capacity(self.prefix, self.ndev)
        self.in_dtypes = in_dtypes
        self.key_dtypes = key_dtypes
        self.layout = None      # captured at trace time (_build_fused_fn)
        self.agg_dtypes = None  # likewise: groupby's widened output dtypes
        self.traces = 0
        self.calls = 0
        spec = P(axis)
        self.jfn = jax.jit(shard_map(
            _build_fused_fn(stage, self), mesh=mesh,
            in_specs=(spec, spec, P()),
            out_specs=(spec, spec, spec, spec, spec, spec, P()),
            check_vma=False))

    def __call__(self, datas, masks, n_valid):
        self.calls += 1
        if not metrics.enabled() and not timeline.enabled():
            return self.jfn(datas, masks, n_valid)
        tr0 = self.traces
        t0 = time.perf_counter()
        out = self.jfn(datas, masks, n_valid)
        dt = time.perf_counter() - t0
        kind = "compile" if self.traces > tr0 else "replay"
        timeline.complete(f"engine.fused_stage.{kind}", t0, dt)
        if metrics.enabled():
            metrics.count(f"engine.fused_stage.{kind}")
        return out


class FusedStageCache:
    """LRU: (stage fingerprint, input shape-class, ndev, axis) ->
    CompiledFusedStage.  A miss — a compile — is the counter
    ``engine.fused_stage_cache.miss`` (``stats()`` has hits and evictions:
    no reader asked for them as counters); sized by the same
    SRJT_SEGMENT_CACHE knob as the segment cache (both hold compiled
    executables keyed by shape-class)."""

    def __init__(self, maxsize: Optional[int] = None):
        self._maxsize = None if maxsize is None else int(maxsize)
        self._lock = threading.Lock()
        self._entries: "OrderedDict[tuple, CompiledFusedStage]" = \
            OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    @property
    def maxsize(self) -> int:
        return self._maxsize if self._maxsize is not None \
            else config.segment_cache

    def get(self, stage: FusedStage, padded: Table, mesh,
            axis: str) -> CompiledFusedStage:
        from ..parallel.mesh import axis_size
        ndev = axis_size(mesh, axis)
        # fused_prefix in the key: a program is sized for its group budget
        key = (stage.fingerprint(), shape_class(padded), ndev, axis,
               fused_prefix(padded.num_rows // ndev))
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                return hit
        in_dtypes = tuple(c.dtype for c in padded.columns)
        key_dtypes = tuple(padded.column(k).dtype
                           for k in stage.combine.keys)
        compiled = CompiledFusedStage(key, stage, mesh, axis, in_dtypes,
                                      key_dtypes,
                                      padded.num_rows // ndev)
        with self._lock:
            racer = self._entries.get(key)
            if racer is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                return racer
            self.misses += 1
            metrics.count("engine.fused_stage_cache.miss")
            self._entries[key] = compiled
            while len(self._entries) > self.maxsize:
                self._entries.popitem(last=False)
                self.evictions += 1
            return compiled

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> dict:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions,
                    "size": len(self._entries), "maxsize": self.maxsize}

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()


#: process-wide compiled fused-stage cache
FUSED_STAGE_CACHE = FusedStageCache()


def fused_pad(t: Table, ndev: int):
    """``pad_to_multiple`` with the degenerate-input synthesis: an empty
    table still runs the SAME one-sync program over ndev synthetic dead
    rows (groupby's fast path needs >= 1 row per shard; n_valid=0 masks
    every one of them out) — this is what makes ``verify.sync_budget``
    EXACT for the fused path where the host exchange used to early-out
    on empty inputs (PR 8 review).  Returns (padded Table, n_valid)."""
    from ..parallel.mesh import pad_to_multiple
    if t.num_rows == 0:
        return Table([Column(c.dtype,
                             data=jnp.zeros((ndev,),
                                            c.dtype.device_storage),
                             validity=jnp.zeros((ndev,), jnp.bool_))
                      for c in t.columns], list(t.names)), 0
    return pad_to_multiple(t, ndev)


def run_fused_stage(stage: FusedStage, table: Table, mesh,
                    axis: str, prepped=None):
    """Execute the whole distributed stage over ``table`` (the partial
    aggregate's INPUT).  Returns ``(result Table, info dict)`` on
    success or ``None`` when the static capacity overflowed (the caller
    falls back to the host-orchestrated path — a runtime re-plan).

    ``prepped`` is an optional ``(padded, nrows, sharded)`` triple from
    a caller that already padded and device-placed the stage input (the
    AQE counts probe does) — reusing it skips a second pad + per-column
    device_put round.

    Exactly ONE deliberate host sync for the entire stage: the boundary
    compaction fetch (per-shard group counts, overflow, the send-counts
    attribution matrix, and the output buffers all ride it) — vs the
    host-orchestrated path's four (two groupby compactions + the
    exchange's counts-sizing and compaction syncs).
    """
    from ..ops.order import SortKey, encode_keys
    from ..parallel.mesh import axis_size, shard_table

    ndev = axis_size(mesh, axis)
    if prepped is None:
        padded, nrows = fused_pad(table.select(stage.sel_names()), ndev)
        sharded = shard_table(padded, mesh, axis)
    else:
        padded, nrows, sharded = prepped
    compiled = FUSED_STAGE_CACHE.get(stage, padded, mesh, axis)
    datas = tuple(c.data for c in sharded.columns)
    masks = tuple(c.validity for c in sharded.columns)
    with timeline.span("engine.fused_stage.dispatch",
                       {"capacity": int(compiled.capacity),
                        "rows": int(table.num_rows)}):
        kdat, kval, adat, avalid, ngv, sent, overflow = compiled(
            datas, masks, jnp.int64(nrows))

    # the ONE deliberate host sync of the whole stage: everything below
    # reads buffers this fetch already forced to the host.  One batched
    # device_get (not per-plane np.asarray) so the transfers overlap
    # instead of serializing eleven blocking copies.
    metrics.host_sync(label="groupby-compaction")
    with op_scope("engine.sync_wait", timed=True,
                  label="groupby-compaction"):
        kdat, kval, adat, avalid, ngv, sent, overflow = jax.device_get(
            (kdat, kval, adat, avalid, ngv, sent, overflow))
    if int(overflow):
        metrics.count("engine.fused_stage.overflow_fallbacks")
        return None
    ng = np.asarray(ngv, dtype=np.int64)
    counts = np.asarray(sent, dtype=np.int64)
    ndv, cap = compiled.ndev, compiled.capacity
    stride = ndv * cap

    def compact(arr):
        a = np.asarray(arr)
        return np.concatenate([a[s * stride: s * stride + int(ng[s])]
                               for s in range(ndv)])

    kds = [compact(d) for d in kdat]
    kvs = [compact(v) for v in kval]
    ads = [compact(d) for d in adat]
    avs = [compact(v) for v in avalid]

    # canonical output order: ascending encoded key words — the order one
    # GLOBAL groupby (the host path) produces.  Hash placement makes the
    # per-shard key sets disjoint, so a stable global lexsort of the
    # per-shard sorted runs restores positional parity with the unfused
    # result, not just multiset parity.
    key_cols = [Column(dt, data=jnp.asarray(kd), validity=jnp.asarray(kv))
                for dt, kd, kv in zip(compiled.key_dtypes, kds, kvs)]
    words = [np.asarray(w)
             for w in encode_keys([SortKey(c) for c in key_cols])]
    order = np.lexsort(tuple(reversed(words))) if words else \
        np.arange(kds[0].shape[0] if kds else 0)

    cols = []
    for dt, kd, kv in zip(compiled.key_dtypes, kds, kvs):
        v = kv[order]
        cols.append(Column(dt, data=jnp.asarray(kd[order]),
                           validity=None if v.all() else jnp.asarray(v)))
    for dt, ad, av in zip(compiled.agg_dtypes, ads, avs):
        v = av[order]
        cols.append(Column(dt, data=jnp.asarray(ad[order]),
                           validity=None if v.all() else jnp.asarray(v)))
    out = Table(cols, list(stage.combine.keys) + list(stage.combine.names))
    metrics.count("engine.fused_stage.dispatches")
    row_size = compiled.layout.row_size
    info = {"capacity": cap, "ndev": ndv, "row_size": row_size,
            "wire_bytes": ndv * ndv * cap * row_size,
            "rows_matrix": counts,  # [src, dest], device-derived
            "wire_matrix": np.full((ndv, ndv), cap * row_size, np.int64),
            "in_rows": int(table.num_rows)}
    return out, info
