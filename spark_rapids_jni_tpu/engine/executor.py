"""Physical execution: walk an optimized plan DAG onto the ops/io layers.

One node type maps onto one existing engine entry point (Scan → io readers,
Join → ops.join, Aggregate → ops.aggregate.groupby, ...).  The interesting
path is streaming aggregation: when an ``Aggregate`` sits over exactly one
chunked parquet ``Scan`` (reachable through Filter/Project/Join nodes only),
the executor iterates ``ParquetChunkedReader`` and computes a partial
aggregate per chunk — the same bounded-working-set pattern the reference's
chunked-parquet north star exists for — then combines partials with a second
groupby.  Only decomposable ops (sum/count/count_all/min/max) stream; plans
with mean/var/etc fall back to a materialized scan.

``execute(plan, stats=...)`` fills a stats dict (row groups pruned/read,
chunk count, whether streaming engaged) so tests and the bridge metrics can
prove predicate pushdown actually pruned I/O.
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp

from ..columnar import Column, Table
from ..utils import metrics, timeline
from ..utils.errors import CancelToken, classify
from ..utils.memory import table_nbytes
from ..utils.tracing import op_scope
from .physical import PhysicalPlan, Stage, lower
from .plan import (STREAM_COMBINE, Aggregate, Exchange, Filter, Join, Limit,
                   PlanNode, Project, Scan, Sort, TopK, depends_on,
                   node_label)
from .recovery import RecoveryPolicy, query_cancel_token

_JOIN_FNS = None


def _join_fns():
    global _JOIN_FNS
    if _JOIN_FNS is None:
        from ..ops import join as j
        _JOIN_FNS = {
            "inner": j.inner_join, "left": j.left_join,
            "right": j.right_join, "full": j.full_join,
            "semi": j.left_semi_join, "anti": j.left_anti_join,
            "cross": j.cross_join,
        }
    return _JOIN_FNS


# -- expression evaluation (engine/expr.py) ---------------------------------

def _eager_exprs(*exprs) -> None:
    """Count the expression nodes the interpreter evaluates."""
    from .expr import count_nodes
    n = sum(count_nodes(e) for e in exprs)
    if n:
        metrics.count("engine.expr.eager", n)


def _filter_table(table: Table, predicate) -> Table:
    from ..ops.selection import apply_boolean_mask
    from .expr import any_flag, evaluate, raise_if_overflow
    _eager_exprs(predicate)
    ovf: list = []
    vals, valid, _ = evaluate(predicate, table, ovf)
    raise_if_overflow(any_flag(ovf))
    mask = jnp.asarray(vals, jnp.bool_)
    if valid is not None:
        mask = mask & valid  # SQL semantics: NULL comparisons drop the row
    return apply_boolean_mask(table, mask)


def _project_table(table: Table, node: Project) -> Table:
    """``Project`` interpreted: a select, or the computed columns too."""
    if not node.computed:
        return table.select(list(node.columns))
    from .expr import any_flag, project, raise_if_overflow
    _eager_exprs(*(e for _, e in node.computed))
    ovf: list = []
    out = project(table, node.items, ovf)
    raise_if_overflow(any_flag(ovf))
    return out


# -- execution stats -------------------------------------------------------

def new_stats() -> dict:
    return {"row_groups_pruned": 0, "row_groups_read": 0,
            "chunks": 0, "streamed": False, "nodes": 0,
            "fused_segments": 0, "pipelined": False, "topk": False,
            "exchanges": 0, "aqe_flips": 0, "aqe_splits": 0}


# -- execution context -----------------------------------------------------

class _ExecCtx:
    """Per-execute state.

    ``physical``: the plan being executed, lowered (engine/physical.py) —
    every handler runs the form ``physical.stage_at(node)`` names.
    ``prefetch``: chunked-scan pipeline depth — the producer thread decodes
    and stages chunk k+1..k+prefetch while chunk k computes (0 = serial).
    ``recovery``: the query's RecoveryPolicy (retry/degradation ladder +
    cancellation token), checked at every chunk boundary.
    ``stream_end``: ``perf_counter`` where the last streamed chunk loop
    ended; ``execute`` observes ``engine.post_stream_s`` from it, and
    ``engine.post_stream.sync_wait_s`` from ``stream_sync_s``, the query's
    ``engine.sync_wait_s`` at that moment.
    ``tail_source``: the ``stream-agg`` Aggregate whose merged partial the
    plan's ``tail`` stage asked for still padded; ``tail_demoted``: a veto
    demoted that stage, and its root runs the form it would have had.
    """

    __slots__ = ("physical", "prefetch", "recovery", "stream_end",
                 "stream_sync_s", "tail_source", "tail_demoted")

    def __init__(self, physical: PhysicalPlan, prefetch: int,
                 recovery: Optional[RecoveryPolicy] = None):
        self.physical = physical
        self.prefetch = max(0, int(prefetch))
        self.recovery = recovery if recovery is not None \
            else RecoveryPolicy()
        self.stream_end: Optional[float] = None
        self.stream_sync_s = 0.0
        self.tail_source: Optional[PlanNode] = None
        self.tail_demoted = False

    def stage_at(self, node: PlanNode) -> Stage:
        """The form ``node`` runs in now: the physical plan's, or what a
        demoted tail leaves its root."""
        st = self.physical.stage_at(node)
        return st.demoted if st.tail is not None and self.tail_demoted \
            else st


def _sync_wait_so_far() -> float:
    """The bound query's ``engine.sync_wait_s`` up to now."""
    qm = metrics.current()
    return qm.hist_sum("engine.sync_wait_s") if qm is not None else 0.0


# -- the walk --------------------------------------------------------------

def _exec_scan(scan: Scan, memo: dict, stats: dict, ctx: _ExecCtx) -> Table:
    if scan.format == "orc":
        from ..io import read_orc
        return read_orc(scan.path, list(scan.columns)
                        if scan.columns else None)
    cols = list(scan.columns) if scan.columns else None
    if scan.predicate is None and scan.chunk_bytes is None:
        from ..io import read_parquet
        return read_parquet(scan.path, cols)
    # pruning or chunking requested: go through the chunked reader so
    # footer-stats pruning applies, then materialize
    from ..io import ParquetChunkedReader
    from ..ops.selection import concat_tables
    reader = ParquetChunkedReader(
        scan.path, pass_read_limit=scan.chunk_bytes or (64 << 20),
        columns=cols, predicate=scan.predicate,
        cancel=ctx.recovery.cancel)
    parts = list(reader)
    stats["row_groups_pruned"] += reader.groups_pruned
    stats["row_groups_read"] += reader.groups_read
    if not parts:
        from ..io import ParquetFile
        return ParquetFile(scan.path).empty_table(cols)
    return parts[0] if len(parts) == 1 else concat_tables(parts)


def _groupby(table: Table, agg: Aggregate, aggs=None) -> Table:
    """``agg`` interpreted (``aggs``: other ``(column, op)`` pairs under its
    names, a merge's), a decimal sum checked for overflow first."""
    from ..ops.aggregate import groupby
    from .expr import any_flag, decimal_sums, raise_if_overflow, sum_check
    aggs = list(agg.aggs) if aggs is None else list(aggs)
    ovf: list = []
    for c in decimal_sums(aggs, table):
        sum_check(table.column(c), True, ovf)
    raise_if_overflow(any_flag(ovf))
    return groupby(table, list(agg.keys), aggs, names=list(agg.names))


def _apply(nd, t: Table) -> Table:
    """One Filter or Project node, interpreted."""
    return _filter_table(t, nd.predicate) if isinstance(nd, Filter) \
        else _project_table(t, nd)


def _interp_chain(seg, t: Table, stats: dict) -> Table:
    """Interpreter fallback for a segment whose input schema turned out
    runtime-ineligible (string filter columns, nested buffers): exactly the
    node-by-node semantics, just without re-entering the handlers."""
    for nd in seg.chain:
        t = _apply(nd, t)
    if seg.agg is not None:
        t = _groupby(t, seg.agg)
    return t


def _exec_segment(seg, memo: dict, stats: dict, ctx: _ExecCtx,
                  node: Optional[PlanNode] = None) -> Table:
    """Run one fused segment: materialize its input (a breaker boundary),
    then one jitted program over the whole chain."""
    from . import segment as sg
    inp = _exec(seg.input, memo, stats, ctx)
    # interior chain nodes never pass through _exec; keep the node count
    # meaning "plan nodes executed" either way
    stats["nodes"] += len(seg.chain) - (0 if seg.agg is not None else 1)
    qm = metrics.current()
    if qm is not None and node is not None \
            and all(c is not seg.input for c in node.children()):
        # the chain collapses into one program, so the segment root's
        # rows_in/bytes_in is the breaker-boundary input (unless the input
        # IS the direct child, which the _exec wrapper counts from memo)
        qm.node_add(id(node), node_label(node),
                    rows_in=inp.num_rows, bytes_in=table_nbytes(inp))
    if not sg.runtime_eligible(seg, inp):
        return _interp_chain(seg, inp, stats)
    compiled = sg.SEGMENT_CACHE.get(seg, inp)
    stats["fused_segments"] += 1
    with op_scope("engine.fused_segment"):
        if seg.agg is not None:
            return sg.run_agg_segment(compiled, inp)
        return sg.run_map_segment(compiled, inp)


def _exec_chain_node(node, memo: dict, stats: dict, ctx: _ExecCtx) -> Table:
    """A Filter or a Project: the root of a ``map`` stage, or itself."""
    st = ctx.stage_at(node)
    if st.kind == "map":
        return _exec_segment(st.segment, memo, stats, ctx, node)
    return _apply(node, _exec(node.child, memo, stats, ctx))


def _exec_join(node: Join, memo: dict, stats: dict, ctx: _ExecCtx) -> Table:
    left = _exec(node.left, memo, stats, ctx)
    right = _exec(node.right, memo, stats, ctx)
    if node.how == "cross":
        # keyless by definition (ops.cross_join takes no key lists);
        # found by the plan-space fuzzer — every Join(how="cross") plan
        # previously died here on a TypeError
        return _join_fns()["cross"](left, right)
    return _join_fns()[node.how](left, right, list(node.left_keys),
                                 list(node.right_keys))


def _exec_aggregate(node: Aggregate, memo: dict, stats: dict,
                    ctx: _ExecCtx) -> Table:
    if not node.keys:
        metrics.count("engine.agg.keyless")   # one row, however it runs
    st = ctx.stage_at(node)
    if st.scan is not None:  # stream-agg / stream-agg-interp
        # scan-independent subtrees go into the shared memo BEFORE the
        # stats snapshot: a degraded re-run finds them memoized and skips
        # them, so their counts must survive the restore below
        _precompute_independent(node.child, st.scan, memo, stats, ctx)
        snap = {k: (list(v) if isinstance(v, list) else v)
                for k, v in stats.items()}

        def restore():
            # drop a failed attempt's partial evidence (chunks, row-group
            # counts, fused_segments, chain nodes) so the re-run's
            # accounting isn't double-counted; lists re-copied so a
            # second restore starts from the clean snapshot too
            stats.clear()
            stats.update({k: (list(v) if isinstance(v, list) else v)
                          for k, v in snap.items()})

        try:
            return _exec_streamed(st, memo, stats, ctx)
        except Exception as e:
            # resource exhaustion on the fused/staged stream degrades to
            # the interpreted per-chunk path — the always-correct fallback
            # with a smaller device footprint (no padded shape buckets, no
            # staged double-buffering of device chunks)
            if not ctx.recovery.can_degrade(e):
                raise
            restore()
            if ctx.recovery.oom_retry_first("stream.fused", e):
                # session within its own budget: the pressure was a
                # neighbor's — one same-rung retry before degrading
                try:
                    return _exec_streamed(st, memo, stats, ctx)
                except Exception as e2:
                    if not ctx.recovery.can_degrade(e2):
                        raise
                    restore()
                    e = e2
            ctx.recovery.degrade("stream-interpreted", e, stats)
            return _exec_streamed(st, memo, stats, ctx, force_interp=True)
    if st.kind == "fused-stage":
        out = _try_fused_stage(node, st.stage, memo, stats, ctx)
        if out is not None:
            return out
        # the host-orchestrated form: a sandwich's combine sits directly
        # on its Exchange (a breaker), so it is the interpreted group-by
    elif st.kind == "agg":
        return _exec_segment(st.segment, memo, stats, ctx, node)
    return _groupby(_exec(node.child, memo, stats, ctx), node)


def _try_fused_stage(node: Aggregate, stage, memo: dict, stats: dict,
                     ctx: _ExecCtx) -> Optional[Table]:
    """Whole-stage fusion (engine/segment.py ``FusedStage``): run the
    ``partial-agg -> hash Exchange -> final-agg`` sandwich rooted at
    ``node`` as ONE pjit/shard_map program — partial groupby, bucket
    scatter, all_to_all, and combine groupby with zero host round-trips
    between the three plan nodes.  Returns the stage result, or None to
    fall through to the host-orchestrated path (ineligible schema, the
    AQE probe routed to the adaptive path, or capacity overflow —
    runtime re-plans, never errors)."""
    from ..utils.config import config
    from . import segment as sg

    ex, partial = stage.exchange, stage.partial
    ndev = ctx.physical.ndev
    inp = _exec(partial.child, memo, stats, ctx)
    if not sg.fused_runtime_eligible(stage, inp):
        return None
    from ..parallel.mesh import ROW_AXIS, make_mesh, shard_table
    mesh = make_mesh(ndev)

    prepped = None
    if config.aqe and getattr(ex, "_aqe_split", False):
        # AQE escape hatch: the skew-split rule fires AT the exchange
        # boundary this fusion erases, so a cheap counts probe picks
        # which program to dispatch — input-row skew at or under the
        # split threshold dispatches the fused program; anything hotter
        # routes to the host-orchestrated path where try_skew_split's
        # full machinery (deal, verify, ledger, pre-combine) still
        # fires.  Row skew upper-bounds partial-output group skew, so
        # the probe only ever errs TOWARD the adaptive path — it cannot
        # strand a hot key inside the fused program.
        from ..parallel import shuffle as sh
        from . import adaptive
        probed, n = sg.fused_pad(inp.select(stage.sel_names()), ndev)
        probed_sharded = shard_table(probed, mesh)
        metrics.host_sync(key=id(ex), label="exchange-counts-sizing")
        with op_scope("engine.sync_wait", timed=True,
                      label="exchange-counts-sizing"):
            counts = sh.partition_counts(probed_sharded, mesh,
                                         list(stage.combine.keys),
                                         n_valid_rows=n)
        prepped = (probed, n, probed_sharded)  # reused by the dispatch
        probe_skew = sh.device_load_stats(counts.sum(axis=0))["skew"]
        fused = probe_skew <= float(config.aqe_skew)
        adaptive.record_fused_dispatch(ctx.physical.root, ex, probe_skew,
                                       float(config.aqe_skew),
                                       "fused" if fused else "host")
        if not fused:
            metrics.count("engine.fused_stage.aqe_fallbacks")
            return None

    with op_scope("engine.fused_stage"):
        res = sg.run_fused_stage(stage, inp, mesh, ROW_AXIS,
                                 prepped=prepped)
    if res is None:
        return None  # static capacity overflowed: the host path re-plans
    out, info = res
    rows_mat = info["rows_matrix"]
    # the lowered Exchange still counts: the executed-exchange census
    # (stats vs verify.plan_exchanges) and the flight recorder see the
    # same events whether the exchange ran in-program or host-side
    stats["exchanges"] += 1
    stats["nodes"] += 2  # the bypassed Exchange + partial Aggregate
    from ..utils import blackbox
    blackbox.record("exchange", kind=ex.kind,
                    rows=int(rows_mat.sum()), in_program=True)
    wire = int(info["wire_bytes"])
    metrics.count("engine.exchange.shuffles")
    metrics.count("engine.exchange.wire_bytes", wire)
    qm = metrics.current()
    if qm is not None:
        qm.node_add(id(ex), node_label(ex), chunks=1, wire_bytes=wire)
    if metrics.enabled():
        from ..parallel import shuffle as sh
        # per-device attribution from the DEVICE-side counts output that
        # rode the result fetch — zero additional host syncs, and the
        # wire matrix sums to the engine.exchange.wire_bytes increment
        # above by construction (every padded slot crosses the wire)
        st = sh.device_load_stats(rows_mat.sum(axis=0))
        metrics.gauge_set("engine.exchange.skew", st["skew"])
        metrics.gauge_set("engine.exchange.straggler_share",
                          st["straggler_share"])
        metrics.gauge_set("engine.exchange.max_dev_rows",
                          st["max_dev_rows"])
        for d, r in enumerate(st["dev_rows"]):
            metrics.gauge_set(f"engine.exchange.dev{d}.rows", float(r))
            metrics.observe("engine.exchange.dev_rows", r)
        if qm is not None:
            qm.node_set(id(ex), node_label(ex),
                        skew=st["skew"],
                        straggler_share=st["straggler_share"],
                        max_dev_rows=st["max_dev_rows"],
                        cap_rows=info["ndev"] * info["capacity"],
                        dev_rows=st["dev_rows"],
                        rows_matrix=rows_mat.tolist(),
                        wire_matrix=info["wire_matrix"].tolist(),
                        in_program=True)
            qm.node_set(id(node), node_label(node), in_program=True)
    return out


def _exec_sort(node: Sort, memo: dict, stats: dict, ctx: _ExecCtx) -> Table:
    from ..ops.order import SortKey
    from ..ops.selection import sort_table
    t = _exec(node.child, memo, stats, ctx)
    return sort_table(t, [SortKey(t[c], ascending=a) for c, a in node.keys])


def _exec_limit(node: Limit, memo: dict, stats: dict,
                ctx: _ExecCtx) -> Table:
    from ..ops.selection import slice_table
    t = _exec(node.child, memo, stats, ctx)
    return slice_table(t, 0, min(node.n, t.num_rows))


def _exec_tail(node: PlanNode, memo: dict, stats: dict,
               ctx: _ExecCtx) -> Table:
    """The plan's root as a ``tail`` stage (engine/segment.py ``Tail``):
    every operator above the streamed aggregate in ONE program.

    The ``stream-agg`` stage under it hands over its merged partial still
    padded (no ``groupby-compaction``), each join's build side is padded to
    its row bucket, and ``segment.run_tail`` launches the program and
    compacts once (``tail-compaction``).  Per streamed query exactly one of
    ``engine.tail.compiled`` / ``engine.tail.interp`` ticks.  A veto — the
    static ``schema`` shadow or the inputs' own, a stream that came back a
    Table (``stream-interpreted``, ``empty-stream``), a build row matched
    twice (``non-unique-build``, known with the fetch) — demotes to the
    forms the nodes have without a tail: the partial is compacted as it
    always was and the root's own handler walks the region."""
    from . import segment as sg
    st = ctx.physical.stage_at(node)
    tail = st.tail
    src = tail.source
    part = None
    veto = "schema" if st.vetoed else None
    if veto is None:
        ctx.tail_source = src
        part = _exec(src, memo, stats, ctx)
        if not isinstance(part, sg.PaddedPartial):
            part = None
            veto = "stream-interpreted" if stats["chunks"] \
                else "empty-stream"
    builds: tuple = ()
    if veto is None:
        builds = tuple(_exec(j.right, memo, stats, ctx)
                       for j in tail.joins())
        if not sg.tail_runtime_eligible(tail, part, builds):
            veto = "schema"
    qm = metrics.current()
    with op_scope("engine.tail", timed=True, nodes=len(tail.nodes),
                  cap=part.num_rows if part is not None else 0) as sp:
        if veto is None:
            dims = sg.tail_dims(tail, builds)
            done = sg.run_tail(sg.SEGMENT_CACHE.get_tail(tail, part, dims),
                               part, dims)
            if done is None:
                veto = "non-unique-build"
            else:
                out, ngroups = done
                metrics.count("engine.tail.compiled")
                # interior nodes never pass through _exec (cf. _exec_segment)
                stats["nodes"] += len(tail.nodes) - 1
                if qm is not None:
                    qm.node_set(id(src), node_label(src), rows_out=ngroups)
                    for n in tail.nodes:
                        qm.node_set(id(n), node_label(n), in_program=True)
                    qm.node_set(id(node), node_label(node),
                                tail_nodes=len(tail.nodes),
                                tail_cap=part.num_rows)
                    if all(c is not src for c in node.children()):
                        qm.node_add(id(node), node_label(node),
                                    rows_in=ngroups)
                return out
        metrics.count("engine.tail.interp")
        sp.stat(veto=veto)
        ctx.tail_demoted = True
        if part is not None:
            t = memo[id(src)] = part.compact()
            if qm is not None:
                qm.node_set(id(src), node_label(src), rows_out=t.num_rows,
                            bytes_out=table_nbytes(t))
        return _EXEC_DISPATCH[type(node)](node, memo, stats, ctx)


#: per-chunk row budget for the streamed hash exchange — bounds the
#: device-resident working set of one shuffle dispatch
_EXCHANGE_CHUNK_ROWS = 1 << 16


def _exec_exchange(node: Exchange, memo: dict, stats: dict,
                   ctx: _ExecCtx) -> Table:
    """Data movement as a plan node: replicate (broadcast) or re-place
    (hash shuffle) the child's rows across the device mesh.  Output row
    ORDER is not preserved by the hash kind — exchanges only feed
    order-insensitive consumers (joins, aggregates).

    Resource exhaustion walks a degradation ladder, each rung logged and
    counted (engine/recovery.py): full capacity → halved chunk capacity →
    spilled shuffle (parallel/spill.py, host-buffered passes) →
    passthrough.  The last rung is content-equivalent — ``_hash_exchange``
    returns the full concatenated table either way, so eliding it loses
    only device placement, which downstream ops recompute from data.
    Transient dispatch failures retry under the policy's backoff first."""
    child = _exec(node.child, memo, stats, ctx)
    # counted before any degenerate early-out (1 device, 0 rows) so the
    # executed count always equals the static verify.plan_exchanges census
    # — ci/premerge.sh compares the two on the smoke artifact
    stats["exchanges"] += 1
    from ..utils import blackbox
    blackbox.record("exchange", kind=node.kind, rows=child.num_rows)
    if ctx.stage_at(node).kind == "exchange-broadcast":
        return _broadcast_exchange(node, child)
    if getattr(node, "_aqe_flip", False):
        from ..utils.config import config
        if config.aqe:
            # AQE rule 1 (engine/adaptive.py): the build side is already
            # materialized, so its TRUE row count is known before the
            # shuffle runs — flip the planned hash exchange to broadcast
            # when it lands under the runtime threshold.  The Exchange
            # NODE stays the same object (census, spans, and ledger paths
            # all keyed on it); only the physical op changes.
            from . import adaptive
            if adaptive.try_broadcast_flip(node, child, ctx.physical.root,
                                           stats):
                return _broadcast_exchange(node, child)
    rp = ctx.recovery
    try:
        return rp.retry("exchange.dispatch",
                        lambda: _hash_exchange(node, child, ctx, stats))
    except Exception as e:
        if not rp.can_degrade(e):
            raise
        if rp.oom_retry_first("exchange.dispatch", e):
            # the session's own footprint fits its budget, so this OOM is
            # neighbor pressure — one full-capacity retry before stepping
            # down (the old behavior resumes if it fails again)
            try:
                return _hash_exchange(node, child, ctx, stats)
            except Exception as e2:
                if not rp.can_degrade(e2):
                    raise
                e = e2
        rp.degrade("exchange-halved", e, stats)
    try:
        return _hash_exchange(node, child, ctx, stats,
                              chunk_rows=_EXCHANGE_CHUNK_ROWS // 2)
    except Exception as e:
        if not rp.can_degrade(e):
            raise
        rp.degrade("exchange-spilled", e, stats)
    try:
        return _spilled_exchange(node, child, ctx)
    except Exception as e:
        if not rp.can_degrade(e):
            raise
        rp.degrade("exchange-passthrough", e, stats)
        return child


def _broadcast_exchange(node: Exchange, table: Table) -> Table:
    from ..parallel.mesh import broadcast_table, make_mesh
    ndev = len(jax.devices())
    wire = table_nbytes(table) * max(0, ndev - 1)
    metrics.count("engine.exchange.broadcasts")
    metrics.count("engine.exchange.wire_bytes", wire)
    qm = metrics.current()
    if qm is not None:
        qm.node_add(id(node), node_label(node), wire_bytes=wire)
        # a replicate is structurally balanced: every device receives the
        # whole build side, so the skew columns render 1.0 by construction
        # — but the REPLICATION itself is the cost (ndev-1 copies of the
        # build cross the wire), so replica_bytes reports it where skew
        # cannot: the AQE flip rule and the profile store read it to see
        # broadcast cost, not just shuffle skew
        qm.node_set(id(node), node_label(node), skew=1.0,
                    straggler_share=0.0, max_dev_rows=table.num_rows,
                    dev_rows=[table.num_rows] * ndev,
                    replica_bytes=wire)
    if ndev <= 1:
        return table
    # the exchange's own work, not its child's: the replicated puts
    with op_scope("engine.exchange.broadcast", timed=True,
                  rows=int(table.num_rows), wire_bytes=int(wire)):
        return broadcast_table(table, make_mesh(ndev))


def _hash_exchange(node: Exchange, table: Table, ctx: _ExecCtx,
                   stats: Optional[dict] = None,
                   chunk_rows: int = _EXCHANGE_CHUNK_ROWS) -> Table:
    """Streamed two-phase hash shuffle of ``table`` over the full mesh.

    Chunks of ``_EXCHANGE_CHUNK_ROWS`` stream through
    ``shuffle_chunks_pipelined`` (dispatch-ahead overlap keyed to the
    engine's prefetch depth).  Exactly two deliberate host syncs per
    exchange, matching ``verify.sync_budget``: one counts-sizing fetch
    (phase 1 — global when multi-chunk OR when the AQE skew rule needs
    the whole matrix, inside ``shuffle_table_padded`` otherwise) and one
    ok-mask compaction fetch at the end.
    """
    if ctx.stage_at(node).kind == "exchange-identity":
        return table
    # the exchange's own work, not its child's: staging, both shuffle
    # phases, and the two engine.sync_wait spans nested inside
    # (hash_s >= the sum of its two labelled sync_wait_s)
    nchunks = max(1, -(-table.num_rows // chunk_rows))  # 0 rows: one pass
    with op_scope("engine.exchange.hash", timed=True,
                  rows=int(table.num_rows), chunks=int(nchunks)):
        return _hash_exchange_mesh(node, table, ctx, stats, chunk_rows,
                                   nchunks, ctx.physical.ndev)


def _hash_exchange_mesh(node: Exchange, table: Table, ctx: _ExecCtx,
                        stats: Optional[dict], chunk_rows: int,
                        nchunks: int, ndev: int) -> Table:
    """``_hash_exchange`` over ``ndev`` > 1 devices, inside its span."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from ..columnar import Column
    from ..ops.row_conversion import fixed_width_layout
    from ..ops.selection import slice_table
    from ..parallel import shuffle as sh
    from ..parallel.mesh import (ROW_AXIS, make_mesh, pad_to_multiple,
                                 shard_table)

    # NO empty-input early-out: a zero-row exchange runs the same
    # counts + payload passes over zero-filled shards (every helper
    # below has a sound n == 0 branch), so the runtime host-sync count
    # equals verify.sync_budget's static charge EXACTLY — the PR 8
    # review's empty-input upper-bound discrepancy, closed

    plan = None
    keys = list(node.keys)
    key_specs = None
    if any(c.dtype.is_string for c in table.columns):
        # strings cross the exchange in padded-bucket form, exploded ONCE
        # globally so every chunk shares one layout (and one compiled
        # program).  Placement hashes the ORIGINAL UTF-8 bytes (Spark
        # UTF8String murmur3, reconstructed on device from the exploded
        # words via "string" key specs) — width-independent and identical
        # to Scan.partitioned_by / shuffle_table_padded placement, so
        # co-partitioning claims over string keys stay meaningful
        from ..parallel.stringplane import (explode_strings,
                                            reassemble_strings)
        table, plan = explode_strings(table)
        key_specs = sh.key_specs_for(table, keys, plan)

    mesh = make_mesh(ndev)
    rows = table.num_rows
    row_spec = NamedSharding(mesh, PartitionSpec(ROW_AXIS))
    layout = fixed_width_layout(table.dtypes())

    def staged(t):
        padded, n = pad_to_multiple(t, ndev)
        live = jax.device_put(jnp.arange(padded.num_rows) < n, row_spec)
        return shard_table(padded, mesh), live

    aqe_split = False
    if getattr(node, "_aqe_split", False):
        from ..utils.config import config
        aqe_split = bool(config.aqe)
    if stats is None:
        stats = new_stats()  # direct callers without a query stats dict
    split = split_entry = None
    combine = False

    capacity = None
    counts = None
    if nchunks > 1 or aqe_split:
        # phase 1 once, globally, so one counts sync sizes one compiled
        # shuffle program for the entire stream (the AQE skew rule also
        # needs the whole matrix up front, so it hoists this pass even
        # for a single chunk — same whitelisted sync, same label).  A
        # chunk's contiguous shard can straddle one whole-table shard
        # boundary (chunk shards are never longer than table shards), so
        # its per-(src, dest) count is bounded by the SUM of two adjacent
        # whole-table pair counts — size the shared capacity at 2x the
        # global max (one power-of-two bucket up), which that bound can
        # never exceed
        padded, _ = pad_to_multiple(table, ndev)
        metrics.host_sync(key=id(node), label="exchange-counts-sizing")
        with op_scope("engine.sync_wait", timed=True,
                      label="exchange-counts-sizing"):
            counts = sh.partition_counts(shard_table(padded, mesh), mesh,
                                         keys, n_valid_rows=rows,
                                         key_specs=key_specs)
    if aqe_split and counts is not None:
        # AQE rule 2 (engine/adaptive.py): when the measured matrix shows
        # skew over SRJT_AQE_SKEW, hot destinations' rows are re-dealt
        # round-robin inside the shuffle kernel; capacity comes from the
        # post-split projection instead of the raw max
        from . import adaptive
        split, cap_need, split_entry, combine = adaptive.try_skew_split(
            node, counts, ndev, ctx.physical.root, stats)
    if counts is not None:
        if split is not None:
            # projected per-(src, dest) max post-split; multi-chunk pays
            # the same straddle bound (two shard pieces, each dealing its
            # own hot share — at most one extra row per ceil)
            capacity = sh.cap_bucket(2 * cap_need + 2) if nchunks > 1 \
                else sh.cap_bucket(cap_need)
        else:
            capacity = sh.cap_bucket(2 * int(counts.max())) if nchunks > 1 \
                else sh.cap_bucket(int(counts.max()))

    def chunk_stream():
        for i in range(nchunks):
            ctx.recovery.checkpoint()
            lo = i * chunk_rows
            yield staged(slice_table(table, lo,
                                     min(rows - lo, chunk_rows)))

    tl = timeline.enabled()
    fbase = timeline.new_flow_base() if tl else 0
    outs = []
    for ci, item in enumerate(sh.shuffle_chunks_pipelined(
            chunk_stream(), mesh, keys, capacity=capacity,
            depth=max(1, ctx.prefetch), key_specs=key_specs,
            split=split)):
        if tl:
            # flow arrow tails at dispatch — one flow per (chunk,
            # dest device); heads land on the device lanes at receipt
            for d in range(ndev):
                timeline.flow_start("engine.exchange.chunk",
                                    fbase + ci * ndev + d,
                                    {"chunk": ci})
        outs.append(item)

    # one deliberate barrier: the ok masks reach the host and the padded
    # receive slots compact to live rows (distributed.py's compact idiom)
    metrics.host_sync(key=id(node), label="exchange-compaction")
    # per-(src, dest) attribution rides the ok masks ALREADY fetched for
    # compaction — zero additional syncs.  Receive layout of the global ok
    # vector is [dest, src, slot] (all_to_all splits the send grid's dest
    # axis across shards); transpose to conventional [src, dest] accounting
    attrib = metrics.enabled() or tl
    rows_mat = np.zeros((ndev, ndev), np.int64) if attrib else None
    wire_mat = np.zeros((ndev, ndev), np.int64) if attrib else None
    cap_rows = 0                        # receive slots per destination
    dev_cum = np.zeros(ndev, np.int64)  # cumulative per-device rows (tl)
    wire = 0
    buf = [[] for _ in table.columns]
    bufv = [[] for _ in table.columns]
    # the span is the fetch: ok masks and every column come to the host
    # here, compacted as they arrive
    with op_scope("engine.sync_wait", timed=True,
                  label="exchange-compaction"):
        for ci, (out, ok, ovf) in enumerate(outs):
            if int(np.asarray(ovf)):
                raise RuntimeError(
                    "hash exchange overflow despite counts-sized capacity")
            wire += out.num_rows * layout.row_size  # every slot crosses the wire
            keep = np.asarray(ok)
            t_c0 = time.perf_counter()
            for i, c in enumerate(out.columns):
                buf[i].append(np.asarray(c.data)[keep])
                bufv[i].append(np.ones(int(keep.sum()), bool)
                               if c.validity is None
                               else np.asarray(c.validity)[keep])
            if attrib:
                cap_c = out.num_rows // (ndev * ndev)
                okm = keep.reshape(ndev, ndev, cap_c)
                rows_mat += okm.sum(axis=2).T
                wire_mat += cap_c * layout.row_size  # every slot, per pair
                cap_rows += ndev * cap_c
                if tl:
                    dur = time.perf_counter() - t_c0
                    chunk_dev = okm.sum(axis=(1, 2))
                    dev_cum += chunk_dev
                    for d in range(ndev):
                        timeline.complete("engine.exchange.recv", t_c0, dur,
                                          {"chunk": ci,
                                           "rows": int(chunk_dev[d])}, dev=d)
                        timeline.flow_finish("engine.exchange.chunk",
                                             fbase + ci * ndev + d, dev=d)
                        timeline.counter("engine.exchange.dev_rows",
                                         int(dev_cum[d]), dev=d)
    metrics.count("engine.exchange.shuffles")
    metrics.count("engine.exchange.wire_bytes", wire)
    qm = metrics.current()
    if qm is not None:
        qm.node_add(id(node), node_label(node), chunks=nchunks,
                    wire_bytes=wire)
    if metrics.enabled() and rows_mat is not None:
        st = sh.device_load_stats(rows_mat.sum(axis=0))
        metrics.gauge_set("engine.exchange.skew", st["skew"])
        metrics.gauge_set("engine.exchange.straggler_share",
                          st["straggler_share"])
        metrics.gauge_set("engine.exchange.max_dev_rows",
                          st["max_dev_rows"])
        for d, r in enumerate(st["dev_rows"]):
            metrics.gauge_set(f"engine.exchange.dev{d}.rows", float(r))
            metrics.observe("engine.exchange.dev_rows", r)
        if qm is not None:
            qm.node_set(id(node), node_label(node),
                        skew=st["skew"],
                        straggler_share=st["straggler_share"],
                        max_dev_rows=st["max_dev_rows"],
                        cap_rows=cap_rows,
                        dev_rows=st["dev_rows"],
                        rows_matrix=rows_mat.tolist(),
                        wire_matrix=wire_mat.tolist())
        if split_entry is not None and split is not None:
            # the attribution matrix already measured the post-split
            # placement — fold the proof the split worked into its
            # ledger entry (EXPLAIN renders measured_skew -> post_skew)
            from . import adaptive
            adaptive.update(split_entry, post_skew=st["skew"],
                            post_straggler_share=st["straggler_share"])
    cols = []
    for dt, ds, vs in zip(table.dtypes(), buf, bufv):
        v = np.concatenate(vs)
        cols.append(Column(dt, data=jnp.asarray(np.concatenate(ds)),
                           validity=None if v.all() else jnp.asarray(v)))
    result = Table(cols, table.names)
    if plan is not None:
        result = reassemble_strings(result, plan)
    if split is not None and combine:
        # AQE rule 2, merge half: the split scattered each hot key's rows
        # across devices, so re-combine per key over the merged output —
        # verified sound by try_skew_split (self-composable ops only)
        from . import adaptive
        result, did = adaptive.apply_precombine(node, result)
        if did:
            adaptive.update(split_entry, combined_rows=int(result.num_rows))
    return result


def _spilled_exchange(node: Exchange, table: Table, ctx: _ExecCtx) -> Table:
    """Degraded exchange via ``shuffle_table_spilled``: bounded device
    passes, host-resident result.  Row placement matches the padded path
    (Spark HashPartitioning over original UTF-8 bytes for string keys);
    output order is pass-major — exchanges only feed order-insensitive
    consumers, so the content multiset is what matters."""
    from ..parallel import shuffle as sh
    from ..parallel.mesh import make_mesh
    from ..parallel.spill import shuffle_table_spilled

    ndev = len(jax.devices())
    if ndev <= 1 or table.num_rows == 0:
        return table
    plan = None
    keys = list(node.keys)
    key_specs = None
    if any(c.dtype.is_string for c in table.columns):
        from ..parallel.stringplane import explode_strings, reassemble_strings
        table, plan = explode_strings(table)
        key_specs = sh.key_specs_for(table, keys, plan)
    # half the table's footprint as the pass budget: small exchanges run
    # one pass, oversize ones split — the degraded path exists because the
    # full-capacity dispatch just OOMed, so never size to the whole table.
    # A session memory budget clamps further: one tenant's spill ladder
    # must not size its passes as if it owned the whole device
    budget = max(1 << 20, table_nbytes(table) // 2)
    srem = ctx.recovery.session_budget_remaining()
    if srem is not None:
        budget = max(1 << 20, min(budget, srem))
    metrics.count("engine.exchange.spilled_reroutes")
    result = shuffle_table_spilled(table, make_mesh(ndev), keys,
                                   hbm_budget_bytes=budget,
                                   key_specs=key_specs)
    if plan is not None:
        result = reassemble_strings(result, plan)
    return result


def _exec(node: PlanNode, memo: dict, stats: dict, ctx: _ExecCtx) -> Table:
    if id(node) in memo:
        return memo[id(node)]
    handler = _EXEC_DISPATCH.get(type(node))
    if handler is None:
        raise TypeError(f"unknown plan node {type(node).__name__} "
                        f"(register it in executor._EXEC_DISPATCH)")
    if ctx.stage_at(node).kind == "tail":
        handler = _exec_tail    # the plan's root, whatever its type
    stats["nodes"] += 1
    qm = metrics.current()
    t0 = time.perf_counter() if qm is not None else 0.0
    with op_scope(f"engine.{node_label(node)}"):
        out = handler(node, memo, stats, ctx)
    if qm is not None:
        # rows_in/bytes_in from the memoized children: on the streamed
        # path the per-chunk re-walk resolves the scan from the chunk
        # overlay, so the accumulated totals ARE the per-chunk flow.
        # bytes are buffer-metadata sums (.nbytes) — no sync.
        qm.node_add(id(node), node_label(node),
                    calls=1, wall_s=time.perf_counter() - t0,
                    rows_out=out.num_rows,
                    bytes_out=table_nbytes(out),
                    rows_in=sum(memo[id(c)].num_rows
                                for c in node.children()
                                if id(c) in memo),
                    bytes_in=sum(table_nbytes(memo[id(c)])
                                 for c in node.children()
                                 if id(c) in memo))
    memo[id(node)] = out
    return out


def _precompute_independent(root: PlanNode, scan: Scan, memo: dict,
                            stats: dict, ctx: _ExecCtx) -> None:
    """Compute every scan-independent subtree once, into the shared memo,
    so per-chunk re-walks only redo scan-dependent nodes."""
    from .plan import topo_nodes
    dep: dict = {}
    # the dimension side of a streamed query — its scans (decode in place,
    # staging), filters and builds — paid before the first fact chunk is
    # asked for: inside `engine.execute`, before `engine.stream` opens
    with op_scope("engine.precompute", timed=True) as sp:
        before = stats["nodes"]
        for n in topo_nodes(root):
            if n is not root and not depends_on(n, scan, dep) \
                    and id(n) not in memo:
                _exec(n, memo, stats, ctx)
        sp.stat(nodes=stats["nodes"] - before)


def _get_builds(joins: tuple, build_tables: tuple) -> tuple:
    """The per-chunk BUILD_CACHE access: one ``get`` per join per chunk —
    the first chunk of a cold stream misses and pays the rank + sort,
    every later chunk hits (``hits == chunks - 1``)."""
    from .cache import BUILD_CACHE
    return tuple(
        BUILD_CACHE.get(j.fingerprint(), bt,
                        lambda j=j, bt=bt: _prepare_build(j, bt))
        for j, bt in zip(joins, build_tables))


def _prepare_build(j: Join, bt: Table):
    """A cache miss's ``prepare_build``, under its timed span: the build's
    rows, the probe method it will take and whether it is ranked on its
    keys themselves (``ops.join.exact_keys``)."""
    from ..ops.join import exact_keys, prepare_build, probe_method
    keys = [bt.column(k) for k in j.right_keys]
    with op_scope("engine.build.prepare", timed=True, rows=bt.num_rows,
                  method=probe_method(bt.num_rows, keys),
                  exact=int(exact_keys(keys))):
        return prepare_build(bt, list(j.right_keys))


def _exec_streamed(st: Stage, memo: dict, stats: dict, ctx: _ExecCtx,
                   force_interp: bool = False) -> Table:
    """Per-chunk partial aggregation over the one chunked scan of a
    ``stream-agg`` / ``stream-agg-interp`` stage.

    Three compounding upgrades over the PR 1 interpreter loop:

    - **Double-buffered pipeline** (``ctx.prefetch > 0``): the reader's
      producer thread host-decodes and stages chunk k+1 while the device
      computes chunk k — decode/transfer overlap, the tabular-format
      study's actual ingest lever.
    - **Fused chunk program** (``stream-agg``: the scan feeds the segment
      directly): each staged chunk arrives PADDED to a power-of-two row
      bucket, so one jitted segment (filters -> masked partial groupby)
      serves every chunk with zero per-chunk host syncs; padded partials
      accumulate on device and merge with ONE combine groupby at the end.
    - **Fused probe joins** (a Join in the stage's segment): a Join on the
      path whose build side is scan-independent joins the segment instead of
      breaking it — the build is ranked + sorted once per execution
      (``BUILD_CACHE``) and enters the chunk program as a pytree input.
      A non-unique build (a key held twice; for a build not keyed by one
      integer column, two keys of one 32-bit hash) or an ineligible schema
      falls back to the interpreted per-chunk loop, which still pipelines.
    """
    from ..ops.selection import concat_tables

    agg, scan = st.node, st.scan
    # the joins an interpreted chunk is probed through in the chunk
    # program's place (``engine.probe.interp``)
    probes = 0 if st.segment is None else len(st.segment.joins())
    # the scan-independent subtrees are in ``memo`` already:
    # ``_exec_aggregate`` precomputed them (``engine.precompute``)
    with op_scope("engine.stream", timed=True):
        reader, partials, fused = _stream_chunks(
            agg, scan, None if force_interp else st.segment, memo, stats,
            ctx, probes)
    # what follows, to the end of ``execute``, is ``engine.post_stream``:
    # the final merge of the partials (one program) and every operator
    # above it; the stream's own waits (a long stream's folds) are behind
    ctx.stream_end = time.perf_counter()
    ctx.stream_sync_s = _sync_wait_so_far()
    stats["row_groups_pruned"] += reader.groups_pruned
    stats["row_groups_read"] += reader.groups_read

    if fused:
        # a tail above takes the merged partial as the merge left it
        part = fused.merge()
        return part if ctx.tail_source is agg else part.compact()
    if not partials:
        # everything pruned/filtered: run the plan once on an empty chunk
        # so the output schema still comes out right (the reader's cached
        # footer serves the schema — no second file open/parse)
        sub = _ChunkMemo(memo)
        sub[id(scan)] = reader.file.empty_table(reader.columns)
        return _groupby(_exec(agg.child, sub, stats, ctx), agg)

    merged = partials[0] if len(partials) == 1 else concat_tables(partials)
    return _groupby(merged, agg, [(nm, STREAM_COMBINE[op])
                                  for nm, (_, op) in zip(agg.names, agg.aggs)])


def _stream_chunks(agg: Aggregate, scan: Scan, seg, memo: dict, stats: dict,
                   ctx: _ExecCtx, probes: int = 0) -> tuple:
    """The chunk loop of ``_exec_streamed``: reader open -> last chunk
    dispatched -> reader closed.  ``seg``: the stage's fused chunk
    segment, None to interpret each chunk, whose ``probes`` joins count
    ``engine.probe.interp``.  Returns ``(reader, partials, fused)``; at
    most one of ``partials`` (interpreted path: compacted Tables) and
    ``fused`` (fused path: the padded device partials, folded as the
    stream ran — ``segment.StreamedPartials``, or summed slot by slot in
    the aggregate's build-row form — ``segment.BuildRowPartials``) is
    filled."""
    from ..io import ParquetChunkedReader
    from ..utils.config import config
    from . import segment as sg

    cols = list(scan.columns) if scan.columns else None
    # footer, row-group pruning, the progress estimate: `engine.stream`'s
    # start -> the first chunk asked for
    with op_scope("engine.stream.open", timed=True) as sp:
        reader = ParquetChunkedReader(
            scan.path, pass_read_limit=scan.chunk_bytes,
            columns=cols, predicate=scan.predicate, prefetch=ctx.prefetch,
            cancel=ctx.recovery.cancel)
        stats["streamed"] = True
        stats["pipelined"] = ctx.prefetch > 0
        pqm = metrics.current()
        if pqm is not None:
            # live-progress denominator from footer metadata (no page
            # decode)
            pqm.progress_total(reader.footer_chunk_estimate())
        kept = reader.kept_groups()
        sp.stat(groups=reader.file.num_row_groups,
                pruned=reader.file.num_row_groups - len(kept))
        # the key domain the footer shows over the kept groups: the chunk
        # program's aggregate takes the dense form over it (None: sorted)
        domain = None if seg is None else \
            sg.agg_domain(seg, reader.file, kept, reader.columns)
        dense_k, lo = (None, None) if domain is None else \
            (domain[1], jnp.asarray(domain[0], jnp.int64))

    partials: list = []          # interpreted path: compacted Tables
    fused = sg.StreamedPartials()   # fused path: padded device partials
    build_row = None
    try:
        if seg is not None:
            joins = seg.joins()
            build_tables = tuple(memo[id(j.right)] for j in joins)
            device_mode = bool(config.device_decode)
            if device_mode:
                from ..ops import parquet_decode as pqd
                it = reader.iter_device()
            else:
                it = reader.iter_staged()
            # thread start + first decode + first staging, as the
            # consumer feels it
            with op_scope("engine.stream.first_wait", timed=True):
                first = next(it, None)
            veto = False
            first_preps: tuple = ()
            if first is not None:
                if device_mode:
                    # a 1-row probe table carries the geometry's schema so
                    # eligibility is decided WITHOUT decoding the chunk
                    probe = pqd.probe_table(first[1].geom) \
                        if first[0] == "dev" else first[1][0]
                else:
                    probe = first[0]
                if not sg.stream_runtime_eligible(seg, probe,
                                                  build_tables):
                    veto = True  # schema veto: strings/nested in compute
                else:
                    # this access stands in for chunk 1's per-chunk get
                    first_preps = _get_builds(joins, build_tables)
                    if any(not p.unique for p in first_preps):
                        # a probe row could have two candidates, so the
                        # <=1-candidate shape doesn't hold: a build keyed by
                        # one integer column holds a key twice; any other
                        # build, two keys share a 32-bit hash.  Interpret
                        veto = True
                    elif dense_k is None:
                        # a group that is one build row adds into its slot
                        build_row = sg.build_row_join(seg, probe,
                                                      build_tables)
                        if build_row is not None:
                            fused = sg.BuildRowPartials(
                                first_preps[build_row[0]], build_row[1])
            if veto:
                from ..ops.selection import slice_table
                seg = None
                items = _chain_one(first, it)
                if device_mode:
                    items = (_dev_item_host(i, reader) for i in items)
                for chunk, nvalid in items:
                    ctx.recovery.checkpoint()
                    if nvalid < chunk.num_rows:
                        chunk = slice_table(chunk, 0, nvalid)
                    partials.extend(_stream_partial(agg, scan, chunk, memo,
                                                    stats, ctx, probes))
            else:
                stats["nodes"] += len(seg.chain)  # agg counted by _exec
                qm = metrics.current()
                preps = first_preps
                dd = dd_entry = None
                if device_mode:
                    from ..utils.errors import (ResourceExhaustedError,
                                                TransientError, retry_call)
                    from . import adaptive
                    dd = {"device_chunks": 0, "host_chunks": 0, "rows": 0,
                          "link_bytes": 0, "uncompressed_bytes": 0,
                          "reasons": {}}
                    dd_entry = adaptive.record(
                        ctx.physical.root, {"kind": "scan:device_decode",
                                   "node": node_label(scan)})
                for item in _chain_one(first, it) \
                        if first is not None else ():
                    ctx.recovery.checkpoint()
                    stats["chunks"] += 1
                    tc0 = time.perf_counter() if qm is not None else 0.0
                    fused.make_room()   # a long stream folds here
                    if fused:  # chunks after the first hit the cache
                        preps = _get_builds(joins, build_tables)
                    if device_mode:
                        kind, payload, reason = item
                        planes = None
                        if kind == "dev":
                            try:
                                planes = retry_call(
                                    payload.to_device,
                                    "parquet.device_decode",
                                    cancel=ctx.recovery.cancel)
                            except (TransientError,
                                    ResourceExhaustedError, OSError):
                                # persistent link failure: this one group
                                # re-plans onto the host oracle (results
                                # identical); cancellation is not caught —
                                # QueryCancelledError unwinds as usual
                                metrics.count("io.device_decode.fallbacks")
                                kind, reason = "host", "transfer_error"
                                payload = _dev_item_host(item, reader)
                        if kind == "dev":
                            ctx.recovery.charge(payload.comp_bytes)
                            fused_compiled = sg.SEGMENT_CACHE.get_decode(
                                seg, payload.geom, build_tables, dense_k,
                                build_row, preps)
                            with op_scope("engine.fused_segment",
                                          **fused_compiled.span_stats()):
                                fused.add(fused_compiled(
                                    planes, payload.nrows, preps, lo),
                                    fused_compiled)
                            nvalid, padded = payload.nrows, 0
                            cb = payload.comp_bytes
                            dd["device_chunks"] += 1
                            dd["link_bytes"] += int(payload.comp_bytes)
                            dd["uncompressed_bytes"] += \
                                int(payload.unc_bytes)
                        else:
                            chunk, nvalid = payload
                            if reason is not None:
                                dd["reasons"][reason] = \
                                    dd["reasons"].get(reason, 0) + 1
                            dd["host_chunks"] += 1
                            cb = table_nbytes(chunk)
                            padded = chunk.num_rows - nvalid
                            ctx.recovery.charge(cb)
                            fused_compiled = sg.SEGMENT_CACHE.get(
                                seg, chunk, build_tables, dense_k, build_row,
                                preps)
                            with op_scope("engine.fused_segment",
                                          **fused_compiled.span_stats()):
                                fused.add(fused_compiled(
                                    chunk, nvalid, preps, lo),
                                    fused_compiled)
                    else:
                        chunk, nvalid = item
                        cb = table_nbytes(chunk)
                        padded = chunk.num_rows - nvalid
                        ctx.recovery.charge(cb)
                        fused_compiled = sg.SEGMENT_CACHE.get(
                            seg, chunk, build_tables, dense_k, build_row,
                            preps)
                        with op_scope("engine.fused_segment",
                                      **fused_compiled.span_stats()):
                            fused.add(fused_compiled(chunk, nvalid, preps,
                                                     lo), fused_compiled)
                    if qm is not None:
                        # per-chunk latency is dispatch time — the fused
                        # loop never syncs per chunk, by design
                        dt = time.perf_counter() - tc0
                        qm.node_add(id(agg), node_label(agg), chunks=1,
                                    rows_in=int(nvalid),
                                    bytes_in=cb,
                                    padded_rows=int(padded))
                        qm.progress_step(chunks=1, rows=int(nvalid),
                                         nbytes=cb)
                        metrics.observe("engine.stream.chunk_latency_s", dt)
                        metrics.observe("engine.stream.chunk_rows",
                                        int(nvalid))
                        metrics.mem_checkpoint()
                    if dd is not None:
                        dd["rows"] += int(nvalid)
                if fused:
                    stats["fused_segments"] += 1
                if dd is not None:
                    _finish_device_decode(dd, dd_entry, scan, qm)
        else:
            for chunk in reader:
                ctx.recovery.checkpoint()
                partials.extend(_stream_partial(agg, scan, chunk, memo,
                                                stats, ctx, probes))
    finally:
        # the last chunk is dispatched (its `wait_reader` saw the end
        # mark): what is left is the producer's join
        with op_scope("engine.stream.close", timed=True):
            reader.close()
    return reader, partials, fused


def _chain_one(first, rest):
    yield first
    yield from rest


def _dev_item_host(item, reader):
    """Normalize a device-stream item to ``(padded Table, nvalid)``.

    Host-fallback items pass through; device page chunks re-plan onto the
    host decoder, landing in the same staged shape class as any other
    fallback group.  A device group always fits one pass budget (oversized
    groups never planned as device chunks), so no re-slicing is needed.
    """
    kind, payload, _ = item
    if kind == "host":
        return payload
    return reader._stage_one(
        reader.file._decode_group(payload.gi, reader.columns))


def _finish_device_decode(dd: dict, dd_entry, scan: Scan, qm) -> None:
    """Stamp the stream's decode routing into ledger + query metrics.

    ``decode=`` is what EXPLAIN ANALYZE renders on the scan node; the
    link/uncompressed byte totals let it derive the wire-compression win
    without any extra bookkeeping."""
    from . import adaptive
    dev, host = dd["device_chunks"], dd["host_chunks"]
    choice = "device" if host == 0 and dev > 0 else \
        ("host" if dev == 0 else "mixed")
    adaptive.update(dd_entry, choice=choice, device_chunks=dev,
                    host_chunks=host, link_bytes=dd["link_bytes"],
                    uncompressed_bytes=dd["uncompressed_bytes"],
                    reasons=dict(dd["reasons"]))
    if qm is not None:
        qm.node_set(id(scan), node_label(scan), decode=choice,
                    rows_in=dd["rows"], rows_out=dd["rows"],
                    link_bytes=dd["link_bytes"],
                    unc_bytes=dd["uncompressed_bytes"])


class _ChunkMemo(dict):
    """Per-chunk memo overlay: scan-dependent results land here (a small
    dict rebuilt each chunk), scan-independent ones resolve from the
    shared base memo — replacing the old per-chunk ``dict(memo)`` copy,
    which was O(plan size) per chunk."""

    __slots__ = ("base",)

    def __init__(self, base: dict):
        super().__init__()
        self.base = base

    def __contains__(self, k):
        return dict.__contains__(self, k) or k in self.base

    def __getitem__(self, k):
        try:
            return dict.__getitem__(self, k)
        except KeyError:
            return self.base[k]


def _stream_partial(agg: Aggregate, scan: Scan, chunk: Table, memo: dict,
                    stats: dict, ctx: _ExecCtx, probes: int = 0) -> list:
    """Interpreted per-chunk partial: re-walk the scan-dependent subtree
    with the chunk standing in for the scan, then a compacting groupby.
    ``probes``: the joins of the stage's chunk segment, which the chunk
    meets on the way, counted as ``engine.probe.interp``."""
    stats["chunks"] += 1
    if probes:
        metrics.count("engine.probe.interp", probes)
    ctx.recovery.charge(table_nbytes(chunk))
    qm = metrics.current()
    tc0 = time.perf_counter() if qm is not None else 0.0
    sub = _ChunkMemo(memo)
    sub[id(scan)] = chunk
    t = _exec(agg.child, sub, stats, ctx)
    out = [_groupby(t, agg)] if t.num_rows else []
    if qm is not None:
        cb = table_nbytes(chunk)
        qm.node_add(id(agg), node_label(agg), chunks=1,
                    rows_in=chunk.num_rows,
                    bytes_in=cb)
        qm.progress_step(chunks=1, rows=chunk.num_rows, nbytes=cb)
        metrics.observe("engine.stream.chunk_latency_s",
                        time.perf_counter() - tc0)
        metrics.observe("engine.stream.chunk_rows", chunk.num_rows)
        metrics.mem_checkpoint()
    return out


def _exec_topk(node: TopK, memo: dict, stats: dict, ctx: _ExecCtx) -> Table:
    """ORDER BY ... LIMIT k without materializing the full table.

    As a ``stream-topk`` stage (the child streams over one chunked scan),
    each chunk's survivors are ranked by their order-preserving u64 key words
    (ops/order.py) plus a global arrival-index word — ties break by
    post-filter row order, which is chunk-geometry-invariant — and merged
    into a capacity-k device buffer: concat buffer-first, one lexsort, one
    gather.  The buffer is the answer, already sorted; memory stays
    O(k + chunk) however large the table.  Otherwise: full sort + slice.
    """
    from ..ops.order import SortKey
    from ..ops.selection import slice_table, sort_table

    scan = ctx.stage_at(node).scan
    if scan is None:
        t = _exec(node.child, memo, stats, ctx)
        t = sort_table(t, [SortKey(t[c], ascending=a)
                           for c, a in node.keys])
        return slice_table(t, 0, min(node.n, t.num_rows))

    from ..io import ParquetChunkedReader
    from ..ops.order import encode_keys
    from ..ops.selection import concat_tables, gather_table

    _precompute_independent(node.child, scan, memo, stats, ctx)

    cols = list(scan.columns) if scan.columns else None
    reader = ParquetChunkedReader(
        scan.path, pass_read_limit=scan.chunk_bytes,
        columns=cols, predicate=scan.predicate, prefetch=ctx.prefetch,
        cancel=ctx.recovery.cancel)
    stats["streamed"] = True
    stats["topk"] = True
    stats["pipelined"] = ctx.prefetch > 0

    buf: Optional[Table] = None   # current top rows (<= k), sorted
    buf_words: list = []          # their u64 sort words (incl. tiebreak)
    rows_seen = 0
    qm = metrics.current()
    if qm is not None:
        qm.progress_total(reader.footer_chunk_estimate())
    try:
        for chunk in reader:
            ctx.recovery.checkpoint()
            stats["chunks"] += 1
            ctx.recovery.charge(table_nbytes(chunk))
            tc0 = time.perf_counter() if qm is not None else 0.0
            if qm is not None:
                cb = table_nbytes(chunk)
                qm.node_add(id(node), node_label(node), chunks=1,
                            rows_in=chunk.num_rows,
                            bytes_in=cb)
                qm.progress_step(chunks=1, rows=chunk.num_rows, nbytes=cb)
            sub = _ChunkMemo(memo)
            sub[id(scan)] = chunk
            t = _exec(node.child, sub, stats, ctx)
            n = t.num_rows
            if n == 0:
                if qm is not None:
                    metrics.observe("engine.stream.chunk_latency_s",
                                    time.perf_counter() - tc0)
                continue
            words = encode_keys([SortKey(t[c], ascending=a)
                                 for c, a in node.keys])
            words.append(jnp.arange(n, dtype=jnp.uint64)
                         + jnp.uint64(rows_seen))
            rows_seen += n
            if buf is None:
                cand_t, cand_w = t, words
            else:
                cand_t = concat_tables([buf, t])
                cand_w = [jnp.concatenate([bw, w])
                          for bw, w in zip(buf_words, words)]
            order = jnp.lexsort(tuple(reversed(cand_w)))
            keep = order[:min(node.n, order.shape[0])]
            buf = gather_table(cand_t, keep)
            buf_words = [w[keep] for w in cand_w]
            if qm is not None:
                metrics.observe("engine.stream.chunk_latency_s",
                                time.perf_counter() - tc0)
                metrics.observe("engine.stream.chunk_rows", chunk.num_rows)
                metrics.mem_checkpoint()
    finally:
        reader.close()
    stats["row_groups_pruned"] += reader.groups_pruned
    stats["row_groups_read"] += reader.groups_read

    if buf is None:
        # nothing survived: one empty-chunk walk for the output schema
        from ..io import ParquetFile
        sub = _ChunkMemo(memo)
        sub[id(scan)] = ParquetFile(scan.path).empty_table(cols)
        return _exec(node.child, sub, stats, ctx)
    return buf


#: plan-node class -> handler; the verifier's exhaustiveness lint
#: (tools/srjt_lint.py) asserts every plan._NODE_TYPES class is here
_EXEC_DISPATCH = {
    Scan: _exec_scan,
    Filter: _exec_chain_node,
    Project: _exec_chain_node,
    Join: _exec_join,
    Aggregate: _exec_aggregate,
    Sort: _exec_sort,
    Limit: _exec_limit,
    TopK: _exec_topk,
    Exchange: _exec_exchange,
}


def _stamp_plan_feedback(physical: PhysicalPlan, qm) -> None:
    """Post-run estimate-vs-actual join: copy the optimizer's evidence
    (``_est_rows`` per node, the root's ``_decisions`` ledger) onto the
    query's spans so summaries, EXPLAIN ANALYZE, and the profile store
    carry ``est_rows``/``q_error`` per node and the decision ledger per
    query.  Pure host-side dict work over spans the executor already
    recorded; nodes without spans (fused-segment interiors) stay
    untouched — EXPLAIN falls back to the plan attribute for those."""
    from .plan import topo_nodes
    plan = physical.root
    for n in topo_nodes(plan):
        rec = qm.node_spans.get(id(n))
        if rec is None:
            continue
        fields = {"path": physical.stage_at(n).path}
        est = getattr(n, "_est_rows", None)
        if est is not None:
            fields["est_rows"] = int(est)
            fields["q_error"] = metrics.q_error(est, rec.get("rows_out"))
        qm.node_set(id(n), node_label(n), **fields)
    dec = getattr(plan, "_decisions", None)
    if dec:
        qm.set_decisions(dec)


def lowering_flags(fused: Optional[bool] = None) -> dict:
    """``physical.lower``'s keyword arguments for this process, now: the
    live ``config`` fields (``fused`` overrides ``config.fuse``) and the
    device count."""
    from ..utils.config import config
    return {"fuse": config.fuse if fused is None else bool(fused),
            "fuse_exchange": config.fuse_exchange,
            "ndev": len(jax.devices())}


def execute(plan: PlanNode | PhysicalPlan, stats: Optional[dict] = None,
            fused: Optional[bool] = None,
            prefetch: Optional[int] = None,
            cancel: Optional[CancelToken] = None,
            session=None) -> Table:
    """Run ``plan`` against the local io/ops layers; returns the result.

    ``plan`` is lowered once (``physical.lower`` under
    ``lowering_flags(fused)``) and every node runs the stage form the
    physical plan names; a caller that keeps a plan across executions
    (``cache.CompiledPlan``) passes the ``PhysicalPlan`` it kept instead
    (``fused`` then has no say: the flags it was lowered under hold).

    ``stats`` (optional dict) is updated in place with execution evidence:
    ``row_groups_pruned``/``row_groups_read`` (scan pruning), ``chunks``,
    ``streamed`` and ``pipelined`` (partial-aggregation path), ``nodes``
    executed, ``fused_segments`` compiled-segment runs, ``degradations``
    (ladder steps taken, engine/recovery.py).

    ``fused``/``prefetch`` override the ``SRJT_FUSE``/``SRJT_PREFETCH``
    config defaults for this execution (the tests compare the
    node-by-node interpreter against the fused pipeline this way).

    ``cancel`` (utils.errors.CancelToken) makes the execution cooperatively
    cancellable: chunk boundaries and the prefetch producer poll it, and a
    tripped token unwinds with ``QueryCancelledError``/``QueryTimeoutError``
    through the readers' ``close()`` machinery.  With no token given,
    ``SRJT_QUERY_TIMEOUT_S > 0`` installs a deadline-only token.

    ``session`` (engine.scheduler.QuerySession, optional) makes the
    execution a scheduled tenant: chunk boundaries become fair-share
    scheduling points, chunk bytes charge the session's memory budget,
    and the OOM ladder consults that budget before degrading
    (engine/recovery.py ``oom_retry_first``).  Unscheduled executions
    behave exactly as before.

    Failures are classified (utils.errors) on the way out: the query
    summary carries an ``outcome`` record and ``engine.errors.<kind>``
    ticks — EXPLAIN ANALYZE and the profile store render both.
    """
    from ..utils.config import config
    if stats is None:
        stats = new_stats()
    else:
        for k, v in new_stats().items():
            stats.setdefault(k, v)
    if cancel is None:
        cancel = query_cancel_token()
    recovery = RecoveryPolicy(cancel=cancel, session=session)
    physical = plan if isinstance(plan, PhysicalPlan) \
        else lower(plan, **lowering_flags(fused))
    plan = physical.root
    ctx = _ExecCtx(physical,
                   prefetch=config.prefetch if prefetch is None
                   else int(prefetch),
                   recovery=recovery)
    if config.aqe:
        # a cached optimized plan is re-executed object-identical: strip
        # the PREVIOUS run's adaptive ledger entries before this run
        # appends its own (ledger==census fuzz invariant)
        from . import adaptive
        adaptive.reset(plan)
    # one QueryMetrics per top-level execute (nested/re-entrant executes
    # attribute into the enclosing query); SRJT_METRICS=0 skips entirely.
    # The blackbox trace scope wraps it: re-entrant the same way, it binds
    # (or mints) the end-to-end trace_id and feeds the flight recorder —
    # which stays on even with the metrics layer off.
    from ..utils import blackbox
    with blackbox.query_scope(label=f"execute:{node_label(plan)}") as scope, \
            metrics.maybe_query(f"execute:{node_label(plan)}") as qm:
        tq = qm if qm is not None else metrics.current()
        if tq is not None and not tq.trace_id:
            tq.trace_id = scope.trace_id
        if config.profile_dir:
            # the profile store keys cross-run diffs by plan fingerprint;
            # stamp whichever query context covers this execute — the one
            # just opened, or a caller's (the bridge wraps PLAN_EXECUTE in
            # its own query). First plan wins under a multi-execute query.
            # Only pay the canonical-serialize cost when the store is on.
            cq = qm if qm is not None else metrics.current()
            if cq is not None and not cq.fingerprint:
                cq.fingerprint = plan.fingerprint()
                # the PRE-optimization fingerprint rides along so
                # profile.history can match runs of the same source plan
                # even when AQE warming changes the optimized shape
                sfp = getattr(plan, "_source_fingerprint", "")
                if sfp and not cq.source_fingerprint:
                    cq.source_fingerprint = sfp
        try:
            out = _exec(plan, {}, stats, ctx)
        except BaseException as e:
            kind, _ = classify(e)
            metrics.count(f"engine.errors.{kind}")
            oq = qm if qm is not None else metrics.current()
            if oq is not None:
                oq.set_outcome("error", kind=kind, error=str(e))
            # post-mortem bundle (SRJT_BLACKBOX_DIR): outcome is stamped,
            # so the bundle's query summary already says how it died; the
            # exception carries trace_id/bundle_path out to the bridge
            blackbox.post_mortem(f"engine.execute:{kind}", exc=e, qm=oq)
            raise
        oq = qm if qm is not None else metrics.current()
        if oq is not None:
            oq.set_outcome("ok")
            # estimate-vs-actual + decision-ledger handoff (optimizer
            # stamped the plan; spans now hold the actuals)
            _stamp_plan_feedback(physical, oq)
        if qm is not None:
            qm.note_stats(stats)
            # query-boundary device-memory sample: with the chunk-boundary
            # samples above, summary["memory"] carries live + high-water
            metrics.mem_checkpoint()
        if ctx.stream_end is not None:
            # the tail after the stream; it crosses call frames, so
            # it is a stamped interval and no ``with`` block
            metrics.observe("engine.post_stream_s",
                            time.perf_counter() - ctx.stream_end)
            # ... and how much of it the host stood blocked on the device:
            # the waits stamped after the stream's end, the folds' not
            metrics.observe("engine.post_stream.sync_wait_s",
                            _sync_wait_so_far() - ctx.stream_sync_s)
    return out
