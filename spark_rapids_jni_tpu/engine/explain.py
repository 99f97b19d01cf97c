"""EXPLAIN ANALYZE: execute a plan under a QueryMetrics and render the DAG.

The Spark-UI SQLMetrics analog for this engine: ``explain_analyze(plan)``
optimizes the plan, runs it inside its own ``utils.metrics.QueryMetrics``
context, and renders the optimized DAG as an indented tree where every node
line carries the span the executor recorded for it — calls, wall time, rows
in/out, chunk count, padded-row waste — plus a query-level footer with the
execution stats, per-query cache attribution (hits/misses the THIS query
caused, consistent with the flat ``tracing`` counters), host-sync count,
and stream histograms.

The report object keeps the structured form (``nodes``, ``summary``,
``result``) so tests and tools can assert on totals instead of scraping
the rendered text.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from ..columnar import Table
from ..utils import metrics
from .plan import (Aggregate, Exchange, Filter, Join, Limit, PlanNode,
                   Project, Scan, Sort, TopK, node_label)


def _describe_scan(node: Scan) -> str:
    bits = [repr(node.path)]
    if node.columns:
        bits.append(f"columns={list(node.columns)}")
    if node.predicate is not None:
        bits.append(f"predicate={node.predicate}")
    if node.chunk_bytes:
        bits.append(f"chunk_bytes={node.chunk_bytes}")
    return f"Scan({', '.join(bits)})"


#: plan-node class -> one-line logical description (the EXPLAIN half);
#: the exhaustiveness lint (tools/srjt_lint.py) asserts every
#: plan._NODE_TYPES class is here
_DESCRIBE = {
    Scan: _describe_scan,
    Filter: lambda n: f"Filter({n.predicate})",
    Project: lambda n: f"Project({list(n.columns)})",
    Join: lambda n: (f"Join(how={n.how!r}, {list(n.left_keys)} = "
                     f"{list(n.right_keys)})"),
    Aggregate: lambda n: (f"Aggregate(keys={list(n.keys)}, "
                          f"aggs={[(c, op) for c, op in n.aggs]})"),
    Sort: lambda n: f"Sort({list(n.keys)})",
    Limit: lambda n: f"Limit({n.n})",
    TopK: lambda n: f"TopK(n={n.n}, keys={list(n.keys)})",
    Exchange: lambda n: ("Exchange(broadcast)" if n.kind == "broadcast"
                         else f"Exchange(hash, keys={list(n.keys)})"),
}


def _describe(node: PlanNode) -> str:
    fn = _DESCRIBE.get(type(node))
    return fn(node) if fn is not None else type(node).__name__


def _throughput(span: dict) -> dict:
    """Derived per-node cost columns from a span's byte accounting:
    ``bytes_moved`` (in + out, fused-segment bytes already attributed to
    the segment root by the executor) and ``GBps`` over the node's host
    wall time (the device's roofline share is the benchmark's
    ``segment_roofline``, from the device trace)."""
    moved = int(span.get("bytes_in", 0)) + int(span.get("bytes_out", 0))
    wall = span.get("wall_s") or 0.0
    return {"bytes_moved": moved,
            "GBps": round(moved / wall / 1e9, 3)
            if moved and wall > 0 else None}


def _est_bits(span: Optional[dict], node: Optional[PlanNode]) -> list:
    """The cardinality-ledger columns: planner estimate + q-error.

    ``est_rows`` prefers the span (the executor stamps it post-run) and
    falls back to the optimizer's ``_est_rows`` plan attribute, so nodes
    a fused segment swallowed (no span) still show their estimate;
    unknown estimates render ``?`` rather than vanishing."""
    est = None if span is None else span.get("est_rows")
    if est is None and node is not None:
        est = getattr(node, "_est_rows", None)
    qe = None if span is None else span.get("q_error")
    if qe is None and est is not None and span is not None:
        qe = metrics.q_error(est, span.get("rows_out"))
    return [f"est_rows={'?' if est is None else est}",
            f"q_error={'?' if qe is None else format(qe, '.2f')}"]


def _annotate(span: Optional[dict],
              node: Optional[PlanNode] = None) -> str:
    """The ANALYZE half: bracketed span fields for one node line."""
    if span is None:
        return "[not executed " + " ".join(_est_bits(None, node)) + "]"
    bits = [f"calls={span['calls']}",
            f"wall={span['wall_s'] * 1e3:.2f}ms",
            f"rows_in={span['rows_in']}",
            f"rows_out={span['rows_out']}"]
    bits.extend(_est_bits(span, node))
    if span["chunks"]:
        bits.append(f"chunks={span['chunks']}")
    if span["padded_rows"]:
        bits.append(f"padded_waste={span['padded_rows']}")
    if span["host_syncs"]:
        bits.append(f"host_syncs={span['host_syncs']}")
    rf = _throughput(span)
    if rf["bytes_moved"]:
        bits.append(f"bytes_moved={rf['bytes_moved']}")
        if rf["GBps"] is not None:
            bits.append(f"GB/s={rf['GBps']:.3f}")
    wire = int(span.get("wire_bytes", 0))
    if wire:
        # exchange cost: wire bytes over this node's wall time
        bits.append(f"wire_bytes={wire}")
        wall = span.get("wall_s") or 0.0
        if wall > 0:
            bits.append(f"exch_GB/s={wire / wall / 1e9:.3f}")
    if span.get("decode"):
        # SRJT_DEVICE_DECODE routing verdict on a scan: which side decoded
        # the pages, what the link carried vs what the host path would
        # have shipped (link_ratio < 1 is the wire win)
        bits.append(f"decode={span['decode']}")
        link, unc = int(span.get("link_bytes", 0) or 0), \
            int(span.get("unc_bytes", 0) or 0)
        if link:
            bits.append(f"link_bytes={link}")
            if unc:
                bits.append(f"link_ratio={link / unc:.3f}")
    if span.get("in_program"):
        # the node ran INSIDE a fused whole-stage program (whole-stage
        # fusion, SRJT_FUSE_EXCHANGE: its collectives paid no host
        # round-trip of their own) or inside the plan's tail program
        bits.append("in_program=yes")
    if span.get("tail_nodes"):
        # the root of a ``tail`` stage: the nodes its one program ran
        # (this one included) and the slots of the padded partial it took
        bits.append(f"tail_nodes={span['tail_nodes']}")
        bits.append(f"tail_cap={span['tail_cap']}")
    if span.get("skew") is not None:
        # per-device exchange attribution (executor._hash_exchange /
        # _broadcast_exchange): destination-load balance + breakdown
        bits.append(f"skew={span['skew']:.2f}")
        if span.get("straggler_share") is not None:
            bits.append(f"straggler={span['straggler_share']:.2f}")
        if span.get("max_dev_rows") is not None:
            bits.append(f"max_dev_rows={span['max_dev_rows']}")
        if span.get("dev_rows"):
            bits.append(f"dev_rows={list(span['dev_rows'])}")
    return "[" + " ".join(bits) + "]"


def _decision_line(d: dict, actuals: dict) -> str:
    """One footer line for one optimizer-ledger entry, scored against the
    actual rows observed at the decision's node (when it executed).

    Runtime (``adaptive:*``) entries render their trigger verdict and the
    MEASURED value that fired (or declined) them — a flip shows the true
    build rows against the threshold and hash->broadcast; a skew split
    shows measured_skew -> post_skew, the proof the re-deal worked; a
    history-warmed entry shows est_before -> est_rows and the choice the
    prior run's actuals bought."""
    bits = [d.get("kind", "?")]
    path = d.get("path")
    if path:
        bits.append(f"path={path}")
    if "triggered" in d:
        bits.append("triggered=yes" if d.get("triggered") else "triggered=no")
    for k in ("side", "how", "exchange", "inner", "n", "keys", "aggs"):
        v = d.get(k)
        if v not in (None, [], ()):
            bits.append(f"{k}={','.join(map(str, v))}"
                        if isinstance(v, (list, tuple)) else f"{k}={v}")
    if d.get("before") is not None and d.get("after") is not None:
        bits.append(f"{d['before']}->{d['after']}")
    if "measured_rows" in d:
        bits.append(f"measured_rows={d['measured_rows']}")
    if "measured_skew" in d:
        bits.append(f"measured_skew={d['measured_skew']:.2f}")
    if d.get("post_skew") is not None:
        bits.append(f"post_skew={d['post_skew']:.2f}")
    if d.get("hot_devices"):
        bits.append("hot_devices=" + ",".join(map(str, d["hot_devices"])))
    if d.get("combine"):
        bits.append("combine=yes")
    if d.get("combined_rows") is not None:
        bits.append(f"combined_rows={d['combined_rows']}")
    if "est_before" in d:
        bits.append(f"est_before={d['est_before']}")
    if "est_rows" in d:
        e = d["est_rows"]
        bits.append(f"est_rows={'?' if e is None else e}")
    if d.get("choice"):
        bits.append(f"choice={d['choice']}")
    if d.get("prior_kind"):
        bits.append(f"prior_kind={d['prior_kind']}")
    if d.get("runs") is not None:
        bits.append(f"runs={d['runs']}")
    if "threshold" in d:
        bits.append(f"threshold={d['threshold']}")
    if d.get("verify_rejected"):
        bits.append("verify_rejected=yes")
    act = actuals.get(path) if path else None
    if act is not None:
        bits.append(f"actual_rows={act}")
        qe = metrics.q_error(d.get("est_rows"), act)
        if qe is not None:
            bits.append(f"q_error={qe:.2f}")
    return " ".join(bits)


@dataclass
class ExplainReport:
    """Structured EXPLAIN ANALYZE output; ``str(report)`` is the tree."""

    text: str
    nodes: list = field(default_factory=list)   # topo order, root last
    summary: dict = field(default_factory=dict)  # QueryMetrics.summary()
    result: Optional[Table] = None
    decisions: list = field(default_factory=list)  # optimizer ledger

    def __str__(self) -> str:
        return self.text

    @property
    def total_chunks(self) -> int:
        return sum(n["metrics"]["chunks"] for n in self.nodes
                   if n["metrics"] is not None)


def _render(root: PlanNode, spans: dict) -> str:
    lines: list[str] = []
    seen: set[int] = set()

    def walk(node: PlanNode, depth: int) -> None:
        pad = "  " * depth
        if id(node) in seen:
            lines.append(f"{pad}{type(node).__name__} (shared, see above)")
            return
        seen.add(id(node))
        lines.append(f"{pad}{_describe(node)}  "
                     f"{_annotate(spans.get(id(node)), node)}")
        for child in node.children():
            walk(child, depth + 1)

    walk(root, 0)
    return "\n".join(lines)


def explain_analyze(plan: PlanNode, stats: Optional[dict] = None,
                    fused: Optional[bool] = None,
                    prefetch: Optional[int] = None,
                    distribute: Optional[bool] = None,
                    result_cache: bool = False) -> ExplainReport:
    """Optimize + execute ``plan`` and report per-node metrics.

    ``fused``/``prefetch`` pass through to ``execute`` (so both executor
    modes can be profiled on the same plan); ``distribute`` passes through
    to ``optimize`` (so the distributed plan's decision ledger and
    exchange telemetry render in the same report).  With ``SRJT_METRICS=0``
    the plan still runs and the tree still renders, but node annotations
    and the summary are empty.

    ``result_cache=True`` routes through the result-set cache
    (``engine.cache.RESULT_CACHE``, active only when ``SRJT_RESULT_CACHE``
    sets a capacity): a repeat of this plan over unchanged input files
    serves the cached table without executing, and the report says so —
    a ``serving:result_cache choice=served_from_cache`` line in the
    footer and a matching entry in ``report.decisions``.  The serving
    entry is deliberately NOT stamped on ``plan._decisions``: the
    optimizer ledger must keep equaling ``verify.decision_census`` (it
    describes plan structure, not how a particular call was served).
    """
    from .executor import execute, new_stats
    from .optimizer import optimize

    opt = optimize(plan, distribute=distribute)
    if stats is None:
        stats = new_stats()
    qm = None
    serving: list = []
    with metrics.query(f"explain:{node_label(opt)}") as q:
        qm = q
        if q is not None:
            from ..utils.config import config
            if config.profile_dir:
                q.fingerprint = opt.fingerprint()
        out = version = None
        if result_cache:
            from .cache import RESULT_CACHE, data_version
            if RESULT_CACHE.enabled:
                fp = opt.fingerprint()
                version = data_version(opt)
                out = RESULT_CACHE.get(fp, version)
                if out is not None:
                    stats["served_from_cache"] = True
                    serving.append({"kind": "serving:result_cache",
                                    "choice": "served_from_cache",
                                    "fingerprint": fp[:12]})
        if out is None:
            out = execute(opt, stats, fused=fused, prefetch=prefetch)
            if version is not None:
                from .cache import RESULT_CACHE
                RESULT_CACHE.put(opt.fingerprint(), version, out)
        if q is not None:
            q.note_stats(stats)
    spans = dict(qm.node_spans) if qm is not None else {}
    summary = qm.summary() if qm is not None else {}

    from .plan import topo_nodes
    nodes = [{"label": node_label(n),
              "desc": _describe(n),
              "est_rows": getattr(n, "_est_rows", None),
              "metrics": None if id(n) not in spans else
              {**spans[id(n)], **_throughput(spans[id(n)])}}
             for n in topo_nodes(opt)]

    text = _render(opt, spans)
    if summary:
        foot = [f"-- query {summary['name']} "
                f"wall={summary['wall_s'] * 1e3:.2f}ms "
                f"nodes={stats['nodes']} chunks={stats['chunks']} "
                f"streamed={stats['streamed']} "
                f"fused_segments={stats['fused_segments']}"]
        if stats.get("exchanges"):
            foot[0] += f" exchanges={stats['exchanges']}"
        mem = summary.get("memory")
        if mem:
            foot.append(
                f"-- memory ({mem.get('source', 'census')}): "
                f"live={mem.get('live_bytes', 0)} "
                f"high_water={mem.get('high_water_bytes', 0)}")
        cache_counters = {k: v for k, v in summary["counters"].items()
                          if ".cache" in k or k == "engine.host_sync"}
        if cache_counters:
            foot.append("-- counters (this query): " + " ".join(
                f"{k}={v}" for k, v in sorted(cache_counters.items())))
        outcome = summary.get("outcome")
        degr = summary.get("degradations")
        if outcome or degr:
            line = "-- outcome: " + (outcome or {}).get("status", "ok")
            if (outcome or {}).get("kind"):
                line += f" kind={outcome['kind']}"
            if degr:
                line += " degraded=" + ",".join(
                    d.get("step", "?") for d in degr)
            foot.append(line)
        decisions = getattr(opt, "_decisions", None)
        if decisions:
            # the decision-ledger footer: one line per optimizer decision,
            # scored against the actual rows the decision's node saw.
            # verify.decision_census(opt) counts the same structural
            # entries statically — the tests assert the counts match.
            from .plan import node_paths
            actuals = {p: spans[i].get("rows_out")
                       for i, p in node_paths(opt).items() if i in spans}
            foot.append(f"-- decisions ({len(decisions)}):")
            for d in decisions:
                foot.append("--   " + _decision_line(d, actuals))
        if serving:
            # how THIS call was served (cache hit), kept out of the
            # optimizer ledger so ledger == decision_census still holds
            foot.append(f"-- serving ({len(serving)}):")
            for d in serving:
                foot.append("--   " + _decision_line(d, {}))
        text = text + "\n" + "\n".join(foot)
    return ExplainReport(text=text, nodes=nodes, summary=summary,
                         result=out,
                         decisions=[dict(d) for d in
                                    getattr(opt, "_decisions", None) or ()] +
                         serving)
