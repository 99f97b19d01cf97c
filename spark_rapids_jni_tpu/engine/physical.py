"""The physical plan: which stage form runs each node of an optimized plan.

``lower`` is the one owner of that choice: the executor runs the
``PhysicalPlan`` it returns (each handler asks ``stage_at(node)``),
``verify.sync_budget`` / ``lint_plan_artifacts`` budget and lint the same
stages, and a new stage form is added here and nowhere else.  It is a pure
function of the plan's shape, five flags and (optionally) footer schemas.
What only a run can observe stays with the executor: the schema vetoes, the
unique-build veto, the AQE probes, group-budget overflow and the OOM ladder
each demote a stage to the interpreted form at run time
(docs/ENGINE.md "The physical plan").  Kinds:

- ``stream-agg``: an Aggregate streamed over its one chunked scan as a fused
  chunk segment plus the ``CompiledCombine`` merge; ``stream-agg-interp``:
  streamed, but no worthwhile segment reaches the scan (per-chunk re-walk).
- ``stream-topk``: a TopK merged chunk by chunk over its one chunked scan.
- ``agg`` / ``map``: a fused Filter/Project chain with / without an
  Aggregate root, over a materialized input.
- ``fused-stage``: partial Aggregate -> hash Exchange -> final Aggregate as
  one shard_map program.
- ``tail``: the operators above a ``stream-agg`` stage, from the plan's root
  down to its Aggregate, as one program over the merged partial still
  padded, compacted once (``segment.Tail``).  One device only.
- ``exchange-identity`` / ``exchange-broadcast`` / ``exchange-hash``.
- ``interp``: every node no stage consumes runs node by node.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

from . import segment as sg
from .plan import (STREAM_COMBINE, Aggregate, Exchange, Filter, Join,
                   PlanNode, Project, Scan, TopK, depends_on, node_paths,
                   topo_nodes)

#: stage kind -> the whitelisted sync sites (``verify.SYNC_WHITELIST``) one
#: execution of the stage pays (``verify.sync_budget`` adds a fused stage's
#: AQE probe, a run-time choice; a ``stream-agg`` of more than
#: ``segment.COMBINE_ARITY`` chunks adds one ``combine-fold-sizing`` per
#: fold at run time, counted by ``engine.combine.folds``; one whose partial
#: a ``tail`` takes still padded does not compact it:
#: ``PhysicalPlan.sync_sites``)
SYNC_CHARGES = {
    "stream-agg": ("combine-sizing", "groupby-compaction"),
    "stream-agg-interp": (),
    "stream-topk": (),
    "agg": ("groupby-compaction",),
    "map": ("segment-boundary-compaction",),
    "fused-stage": ("groupby-compaction",),
    "tail": ("tail-compaction",),
    "exchange-identity": (),
    "exchange-broadcast": (),
    "exchange-hash": ("exchange-counts-sizing", "exchange-compaction"),
    "interp": (),
}


@dataclass(frozen=True, eq=False)
class Stage:
    """``kind`` run at ``node`` (its root, at ``path``), consuming ``nodes``
    (root last but in a fused stage), with the artifact the kind needs.
    ``vetoed``: footer schemas were given and say the executor's schema
    veto will demote this stage.  ``demoted`` (a ``tail`` only): the form
    its root takes when a veto demotes the tail."""

    kind: str
    node: PlanNode
    path: str
    nodes: tuple
    segment: Optional[sg.Segment] = None
    stage: Optional[sg.FusedStage] = None
    scan: Optional[Scan] = None
    tail: Optional[sg.Tail] = None
    demoted: Optional["Stage"] = None
    vetoed: bool = False


class PhysicalPlan:
    """``root`` lowered for ``ndev`` devices: ``stages`` in walk order
    (parents before children), one per node no other stage consumes — the
    forms a run takes when no veto fires."""

    __slots__ = ("root", "ndev", "stages", "_at")

    def __init__(self, root: PlanNode, ndev: int, stages: tuple, at: dict):
        self.root = root
        self.ndev = ndev
        self.stages = stages
        self._at = at

    def stage_at(self, node: PlanNode) -> Stage:
        """The stage ``node`` roots; for a node another stage consumed, the
        form it takes when a veto demotes that stage and the walk reaches
        the node after all."""
        return self._at[id(node)]

    def demotion(self, st: Stage) -> list:
        """What the walk runs in place of the ``tail`` stage ``st`` when a
        veto demotes it: its nodes' own forms, parents first."""
        out: list = []
        consumed: set = set()
        for n in reversed(st.nodes):
            own = st.demoted if n is st.node else self._at[id(n)]
            if id(n) not in consumed:
                out.append(own)
                consumed.update(id(m) for m in own.nodes)
        return out

    def run_stages(self) -> list:
        """``stages`` as a run takes them when every static veto fires: a
        vetoed sandwich is followed by what its demotion hands back to the
        walk, its exchange's and its partial's own stages; a vetoed tail by
        its nodes' own forms."""
        out: list = []
        for st in self.stages:
            out.append(st)
            if st.tail is not None and st.vetoed:
                out += self.demotion(st)
            elif st.stage is not None and st.vetoed:
                out += [self._at[id(n)] for n in st.nodes[1:]]
        return out

    def sync_sites(self, st: Stage) -> tuple:
        """The whitelisted sync sites one execution of ``st`` pays:
        ``SYNC_CHARGES`` of its kind — less ``combine-sizing`` for a
        ``stream-agg`` with no group keys, and less ``groupby-compaction``
        for the ``stream-agg`` whose merged partial a tail takes still
        padded (the tail's ``tail-compaction`` is the one fetch of both)."""
        sites = SYNC_CHARGES[st.kind]
        if st.kind == "stream-agg" and not st.node.keys:
            # one-slot partials: no merge is sized, the result's fetch is all
            sites = tuple(s for s in sites if s != "combine-sizing")
        top = self.stages[0]
        if top.tail is not None and not top.vetoed \
                and st.node is top.tail.source:
            sites = tuple(s for s in sites if s != "groupby-compaction")
        return sites


def _single_chunked_scan(root: PlanNode) -> Optional[Scan]:
    """The single chunked parquet Scan under ``root`` reachable through
    Filter/Project/Join nodes only (scan feeding exactly one join side) —
    the stream axis both partial aggregation and partial top-k need."""
    scans = [n for n in topo_nodes(root)
             if isinstance(n, Scan) and n.chunk_bytes
             and n.format == "parquet"]
    if len(scans) != 1:
        return None
    scan = scans[0]
    dep: dict = {}
    node = root
    while node is not scan:
        if isinstance(node, (Filter, Project)):
            node = node.child
        elif isinstance(node, Join):
            ld = depends_on(node.left, scan, dep)
            rd = depends_on(node.right, scan, dep)
            if ld and rd:
                return None  # scan on both sides: no single stream axis
            node = node.left if ld else node.right
        else:
            return None  # Sort/Limit/Aggregate between: not decomposable
    return scan


def _stream_scan_of(agg: Aggregate) -> Optional[Scan]:
    """The single chunked parquet Scan this Aggregate can stream over:
    every agg op decomposable and a ``_single_chunked_scan`` under the
    child (an aggregate with no keys streams one-row partials)."""
    if any(op not in STREAM_COMBINE for _, op in agg.aggs):
        return None
    return _single_chunked_scan(agg.child)


def lower(plan: PlanNode, *, fuse: bool, fuse_exchange: bool, ndev: int,
          resolver: Optional[Callable] = None) -> PhysicalPlan:
    """Choose the stage form of every node of an optimized ``plan``.

    ``fuse`` / ``fuse_exchange`` are the ``Config`` fields of those names
    (or ``execute(fused=...)``'s override), ``ndev`` the mesh size (a
    ``tail`` is a one-device form).  ``resolver`` (``node -> {name:
    DType} | None``, the verifier's schema inference) changes no form: it
    lets the static shadow of the run-time schema vetoes mark
    ``Stage.vetoed``; without it the run alone decides."""
    nparents = sg.parent_counts(plan)
    paths = node_paths(plan)

    def schema(node: PlanNode):
        return resolver(node) if resolver is not None else None

    def vetoed(seg: sg.Segment) -> bool:
        sch = schema(seg.input)
        if sch is None:
            return False
        used = set(seg.columns_used())
        for j in seg.joins():
            used |= set(j.left_keys)
        dts = [sch.get(name) for name in used]
        return any(dt is not None and (dt.is_string or dt.is_nested)
                   for dt in dts)

    def form(node: PlanNode) -> Stage:
        def mk(kind: str, nodes: tuple = (node,), **artifact) -> Stage:
            return Stage(kind, node, paths[id(node)], nodes, **artifact)

        if isinstance(node, Exchange):
            if node.kind == "broadcast":
                return mk("exchange-broadcast")
            # placement over one device is the identity
            return mk("exchange-hash" if ndev > 1 else "exchange-identity")
        if isinstance(node, TopK):
            scan = _single_chunked_scan(node.child) if node.n else None
            return mk("interp") if scan is None \
                else mk("stream-topk", scan=scan)
        if isinstance(node, Aggregate):
            scan = _stream_scan_of(node)
            if scan is not None:
                seg = sg.build_stream_segment(node, scan, nparents) \
                    if fuse else None
                if seg is not None and seg.input is scan \
                        and sg.worthwhile(seg, streaming=True):
                    return mk("stream-agg", seg.nodes(), segment=seg,
                              scan=scan, vetoed=vetoed(seg))
                return mk("stream-agg-interp", scan=scan)
            st = sg.fused_sandwich(node) \
                if fuse_exchange and ndev > 1 else None
            # shared interior nodes must materialize for their other parents
            if st is not None \
                    and nparents.get(id(st.exchange), 1) == 1 \
                    and nparents.get(id(st.partial), 1) == 1:
                return mk("fused-stage", (node, st.exchange, st.partial),
                          stage=st, vetoed=not sg.fused_static_eligible(
                              st, schema(st.partial.child)))
        if fuse and isinstance(node, (Aggregate, Filter, Project)):
            seg = sg.build_segment(node, nparents)
            if seg is not None and sg.worthwhile(seg):
                return mk("agg" if seg.agg is not None else "map",
                          seg.nodes(), segment=seg, vetoed=vetoed(seg))
        return mk("interp")

    at = {id(node): form(node) for node in topo_nodes(plan)}
    # the operators above the streamed aggregate, as one program: from the
    # plan's shape alone, on one device (an Exchange ends the region, and
    # so does a second chunked scan: ``_stream_scan_of`` finds none)
    streams = [st for st in at.values() if st.kind == "stream-agg"]
    if ndev == 1 and len(streams) == 1:
        src = streams[0]
        tail = sg.build_tail(plan, src.node, src.scan, nparents)
        if tail is not None:
            at[id(plan)] = Stage(
                "tail", plan, paths[id(plan)], tail.nodes, tail=tail,
                demoted=at[id(plan)], vetoed=src.vetoed or (
                    resolver is not None
                    and not sg.tail_static_eligible(tail, schema)))

    stages: list = []
    consumed: set = set()
    for node in reversed(topo_nodes(plan)):
        if id(node) not in consumed:
            st = at[id(node)]
            stages.append(st)
            consumed.update(id(n) for n in st.nodes)
    return PhysicalPlan(plan, ndev, tuple(stages), at)
