"""The physical plan (engine/physical.py): one owner of the stage-form choice.

- the seam: for every plan family the suite builds — the benchmark's q5-lite
  and q55-lite at their ``rehearsal_rows``, a top-k over a chunked scan, a
  string-keyed aggregate that must fall to the interpreter, the distributed
  shuffle / broadcast plans and the fused exchange stage on the suite's
  virtual devices, a plan with a shared interior node — with fusion on and
  off, the census of ``lower(...)`` kinds equals what the execution reports
  (``stats``, the ``engine.host_sync`` counter and labels, the spans), and
  ``verify.sync_budget`` charges exactly the syncs the run pays;
- the structure: every node belongs to exactly one stage, parents come
  first, ``engine/verify.py`` and ``engine/physical.py`` import nothing from
  ``engine/executor.py``, nobody imports an underscore name of it, and the
  executor derives no stage form of its own;
- the cache: a ``CompiledPlan`` lowers once per flag tuple.
"""

import ast
import collections
import importlib.util
import json
import os
import sys

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_jni_tpu.engine import (Aggregate, Filter, Join, Limit,
                                         PhysicalPlan, PlanCache, Project,
                                         Scan, Sort, col, execute, lit, lower,
                                         new_stats, optimize)
from spark_rapids_jni_tpu.engine.executor import lowering_flags
from spark_rapids_jni_tpu.engine.fuzz import _flags, stage_census
from spark_rapids_jni_tpu.engine.physical import SYNC_CHARGES
from spark_rapids_jni_tpu.engine.plan import topo_nodes
from spark_rapids_jni_tpu.engine.verify import (SYNC_WHITELIST,
                                                SchemaResolver, sync_budget,
                                                verify)
from spark_rapids_jni_tpu.utils import blackbox, metrics
from spark_rapids_jni_tpu.utils.config import config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
PKG = os.path.join(ROOT, "spark_rapids_jni_tpu")
N_FACT, N_DIM = 24_000, 40


def _bench_plan(root, config_name, traffic):
    """One of the benchmark's queries at its ``rehearsal_rows``."""
    def load(*parts):
        with open(os.path.join(BENCH, *parts)) as f:
            return json.load(f)

    cfg = load("configs", config_name + ".json")
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "physicaltest_" + cfg["query"],
        os.path.join(BENCH, "queries", cfg["query"] + ".py"))
    query = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(query)
    rows = {t: s["rows"] for t, s in cfg["tables"].items()}
    rows.update(cfg["rehearsal_rows"])
    paths = {}
    for name, df in query.tables(2147483901, rows).items():
        paths[name] = os.path.join(root, f"{config_name}.{name}.parquet")
        pq.write_table(
            pa.Table.from_pandas(df, preserve_index=False), paths[name],
            compression=cfg["storage"]["compression"],
            row_group_size=-(-len(df) // cfg["tables"][name]["row_groups"]))
    return query.plan(paths, load("traffic", traffic + ".json")["params"],
                      1 << 20)


@pytest.fixture(scope="module")
def plans(tmp_path_factory):
    """name -> (unoptimized plan, the config flags it is optimized and run
    under).  ``shared-interior`` is run as built: the optimizer's rebuilds
    would give each parent its own copy of the shared node."""
    root = str(tmp_path_factory.mktemp("physical"))
    rng = np.random.default_rng(32)
    k = rng.integers(0, N_DIM, N_FACT)
    fact = os.path.join(root, "fact.parquet")
    pq.write_table(pa.table({
        "k": pa.array(k, pa.int64()),
        "s": pa.array([f"s{i % 11}" for i in k]),
        "v": pa.array(rng.integers(0, 400, N_FACT) * 0.25, pa.float64())}),
        fact, row_group_size=4_000)
    dim = os.path.join(root, "dim.parquet")
    dk = np.arange(N_DIM, dtype=np.int64)
    pq.write_table(pa.table({"dk": pa.array(dk), "grp": pa.array(dk % 7)}),
                   dim)

    def chunked():
        return Scan(fact, chunk_bytes=100_000)

    def join_agg():
        return Aggregate(Join(chunked(), Scan(dim), ("k",), ("dk",), "inner"),
                         ("grp",), (("v", "sum"), ("v", "count")),
                         ("total", "n"))

    shared = Filter(Scan(fact), (">", col("v"), lit(10.0)))
    dist = {"distribute": True}
    return {
        "q5lite": (_bench_plan(root, "nds_q5lite_sf1", "year"), {}),
        "q55lite": (_bench_plan(root, "nds_q55lite_sf1", "nov1999"), {}),
        "topk-chunked": (Limit(Sort(Filter(chunked(),
                                           (">", col("v"), lit(50.0))),
                                    (("v", False), ("k", True))), 16), {}),
        "string-agg": (Aggregate(
            Filter(Project(Scan(fact), ("s", "v")),
                   (">", col("v"), lit(1.0))),
            ("s",), (("v", "sum"),), ("total",)), {}),
        "string-agg-chunked": (Aggregate(
            Filter(chunked(), (">", col("v"), lit(1.0))),
            ("s",), (("v", "sum"),), ("total",)), {}),
        "dist-shuffle": (join_agg(), {**dist, "broadcast_rows": 0}),
        "dist-broadcast": (join_agg(), {**dist, "broadcast_rows": 1 << 20}),
        "fused-exchange": (Aggregate(Scan(fact), ("k",),
                                     (("v", "sum"), ("v", "count")),
                                     ("total", "n")),
                           {**dist, "fuse_exchange": True}),
        "shared-interior": (Join(
            Aggregate(Project(shared, ("k", "v")), ("k",),
                      (("v", "sum"),), ("total",)),
            Aggregate(Filter(shared, ("<", col("k"), lit(20))), ("k",),
                      (("v", "max"),), ("top",)),
            ("k",), ("k",), "inner"), {}),
    }


FAMILIES = ("q5lite", "q55lite", "topk-chunked", "string-agg",
            "string-agg-chunked", "dist-shuffle", "dist-broadcast",
            "fused-exchange", "shared-interior")

#: stage kinds each family must lower to with fusion on (a lower bound on
#: the census: ``interp`` stages and the rest are free)
EXPECT = {
    "q5lite": {"stream-agg": 1},
    "q55lite": {"stream-agg": 1},
    "topk-chunked": {"stream-topk": 1},
    "string-agg": {"agg": 1},
    "string-agg-chunked": {"stream-agg": 1},
    "dist-shuffle": {"exchange-hash": 3},
    "dist-broadcast": {"stream-agg": 1, "exchange-hash": 1,
                       "exchange-broadcast": 1},
    "fused-exchange": {"fused-stage": 1},
    "shared-interior": {"agg": 2},
}
_FUSED_KINDS = ("stream-agg", "agg", "map")


def _optimized(plans, family):
    plan, flags = plans[family]
    if family == "shared-interior":
        return plan
    with _flags(**flags):
        return optimize(plan, distribute=flags.get("distribute", False))


def _lowered(opt) -> PhysicalPlan:
    resolver = SchemaResolver()
    return lower(opt, **lowering_flags(),
                 resolver=lambda n: verify(n, resolver))


def _run(opt):
    """(stats, the run's QueryMetrics, its host-sync labels) of one
    execution — labels from the flight recorder, the one place they are
    kept."""
    seq0 = max((e["seq"] for e in blackbox.tail()), default=0)
    stats = new_stats()
    with metrics.query("physical-plan") as qm:
        execute(opt, stats)
    labels = sorted(e["label"] for e in blackbox.tail()
                    if e["ev"] == "host_sync" and e["seq"] > seq0)
    return stats, qm, labels


@pytest.mark.parametrize("fuse", (True, False), ids=("fused", "interp"))
@pytest.mark.parametrize("family", FAMILIES)
def test_lowered_census_equals_what_ran(plans, family, fuse):
    opt = _optimized(plans, family)
    with _flags(fuse=fuse, **plans[family][1]):
        physical = _lowered(opt)
        budget = sync_budget(opt, cfg=config)
        stats, qm, labels = _run(opt)
    kinds = collections.Counter(st.kind for st in physical.stages)
    assert set(kinds) <= set(SYNC_CHARGES)
    for kind, n in EXPECT[family].items():
        if fuse or kind not in _FUSED_KINDS:
            assert kinds[kind] == n, (kind, dict(kinds))
    if not fuse:
        assert not any(kinds[k] for k in _FUSED_KINDS), dict(kinds)
        assert kinds["stream-agg-interp"] == EXPECT[family].get(
            "stream-agg", 0)
    # static == executed: the kinds that ran, the syncs they paid
    assert stage_census(physical, stats, qm) is None
    charged = sorted(e["site"] for e in budget if e["count"])
    assert set(charged) <= set(SYNC_WHITELIST)
    vetoed = [st for st in physical.stages if st.vetoed]
    assert [e["path"] for e in budget
            if e["site"] == "interpreted-fallback"] == \
        [st.path for st in vetoed]
    if family == "topk-chunked" and fuse:
        # the per-chunk re-walk runs no segment here (a lone Filter)
        assert kinds["map"] == 0
    assert labels == charged
    assert qm.counters.get("engine.host_sync", 0) == len(charged)


#: what each family lowers to for ONE device (the suite's process has
#: eight): the benchmark's two queries root a ``tail``, the others keep
#: their forms
ONE_DEVICE = {
    "q5lite": ("tail", ("join", "aggregate", "sort")),
    "q55lite": ("tail", ("topk",)),
    "topk-chunked": ("stream-topk", None),
    "string-agg": ("agg", None),
    "string-agg-chunked": ("stream-agg", None),
    "shared-interior": ("interp", None),
}


@pytest.mark.parametrize("family", sorted(ONE_DEVICE))
def test_one_device_census_equals_what_ran(plans, family):
    """The seam again, lowered for one device: a ``tail`` above the
    benchmark's streamed aggregates, and the run pays the two syncs the
    budget charges."""
    from spark_rapids_jni_tpu.engine.plan import node_label
    opt = _optimized(plans, family)
    kind, consumed = ONE_DEVICE[family]
    with _flags(fuse=True):
        resolver = SchemaResolver()
        physical = lower(opt, **{**lowering_flags(), "ndev": 1},
                         resolver=lambda n: verify(n, resolver))
        budget = sync_budget(opt, cfg=config, ndev=1)
        top = physical.stages[0]
        assert top.kind == kind and top.node is opt
        if consumed is not None:
            assert tuple(node_label(n) for n in top.nodes) == consumed
            assert top.demoted.node is opt and top.demoted.kind == "interp"
            assert physical.stage_at(top.tail.source).kind == "stream-agg"
        seq0 = max((e["seq"] for e in blackbox.tail()), default=0)
        stats = new_stats()
        with metrics.query("physical-plan") as qm:
            out = execute(physical, stats)
        labels = sorted(e["label"] for e in blackbox.tail()
                        if e["ev"] == "host_sync" and e["seq"] > seq0)
        assert stage_census(physical, stats, qm) is None
        assert labels == sorted(e["site"] for e in budget if e["count"])
        if kind == "tail":
            assert labels == ["combine-sizing", "tail-compaction"]
            assert qm.counters["engine.tail.compiled"] == 1
        # every node in exactly one stage, parents first, here too
        owner = collections.Counter(id(n) for st in physical.stages
                                    for n in st.nodes)
        assert owner == collections.Counter(id(n) for n in topo_nodes(opt))
        want = execute(opt, fused=False)
    assert out.names == want.names
    for x, y in zip(out.columns, want.columns):
        assert x.to_pylist() == y.to_pylist()


def test_the_census_catches_a_tail_that_did_not_run(plans):
    opt = _optimized(plans, "q5lite")
    with _flags(fuse=True):
        physical = lower(opt, **{**lowering_flags(), "ndev": 1})
        stats = new_stats()
        with metrics.query("physical-plan") as qm:
            execute(opt, stats)     # eight devices: today's forms ran
    assert stage_census(physical, stats, qm) is not None


def test_the_census_catches_a_form_that_did_not_run(plans):
    opt = _optimized(plans, "q5lite")
    with _flags(fuse=True):
        physical = _lowered(opt)
    stats = new_stats()
    with metrics.query("physical-plan") as qm:
        execute(opt, stats, fused=False)
    assert "ran interpreted" in stage_census(physical, stats, qm)
    # and one that is missing from the static side altogether
    dist = _optimized(plans, "dist-broadcast")
    with _flags(fuse=True, **plans["dist-broadcast"][1]):
        stats = new_stats()
        execute(dist, stats)
    assert "exchanges" in stage_census(physical, stats)


def test_string_keys_are_vetoed_statically_and_demoted_at_run_time(plans):
    for family, kind in (("string-agg", "agg"),
                         ("string-agg-chunked", "stream-agg")):
        opt = optimize(plans[family][0])
        with _flags(fuse=True):
            st = _lowered(opt).stage_at(opt)
            assert (st.kind, st.vetoed) == (kind, True)
            # without footer schemas the run decides: same kind, no verdict
            blind = lower(opt, **lowering_flags()).stage_at(opt)
            assert (blind.kind, blind.vetoed) == (kind, False)
            stats, qm, labels = _run(opt)
        assert stats["fused_segments"] == 0 and labels == []


def test_every_node_is_in_exactly_one_stage_parents_first(plans):
    for family in FAMILIES:
        opt = _optimized(plans, family)
        with _flags(fuse=True, **plans[family][1]):
            physical = _lowered(opt)
        nodes = topo_nodes(opt)
        owner = collections.Counter(id(n) for st in physical.stages
                                    for n in st.nodes)
        assert owner == collections.Counter(id(n) for n in nodes), family
        order = {id(n): i for i, n in enumerate(reversed(nodes))}
        roots = [order[id(st.node)] for st in physical.stages]
        assert roots == sorted(roots), family
        for st in physical.stages:
            assert st.nodes[-1] is st.node or st.kind == "fused-stage"
            assert physical.stage_at(st.node) is st
        for n in nodes:  # total: a demoted stage hands its nodes back
            assert physical.stage_at(n).node is n


def test_a_shared_interior_node_roots_its_own_stage(plans):
    opt = _optimized(plans, "shared-interior")
    with _flags(fuse=True):
        physical = _lowered(opt)
    parents = collections.Counter(id(c) for n in topo_nodes(opt)
                                  for c in n.children())
    shared = [n for n in topo_nodes(opt) if parents[id(n)] > 1]
    assert shared
    roots = {id(st.node) for st in physical.stages}
    assert all(id(n) in roots for n in shared)


def test_executing_a_physical_plan_is_executing_its_plan(plans):
    opt = optimize(plans["string-agg"][0])
    with _flags(fuse=True):
        physical = lower(opt, **lowering_flags())
        a, b = execute(physical), execute(opt)
    assert a.names == b.names
    for x, y in zip(a.columns, b.columns):
        assert x.to_pylist() == y.to_pylist()


def test_compiled_plan_lowers_once_per_flag_tuple(plans):
    compiled = PlanCache(maxsize=4).get(plans["string-agg-chunked"][0])
    with _flags(fuse=True):
        first = compiled.physical()
        assert compiled.physical() is first
        assert first.stage_at(compiled.optimized).kind == "stream-agg"
    with _flags(fuse=False):
        other = compiled.physical()
        assert other is not first and compiled.physical() is other
        assert other.stage_at(compiled.optimized).kind == "stream-agg-interp"
        stats = new_stats()
        compiled.execute(stats)
        assert stats["streamed"] and stats["fused_segments"] == 0
    with _flags(fuse=True):
        assert compiled.physical() is first


# -- structure ---------------------------------------------------------------

def _executor_imports(path):
    """(names imported from engine/executor.py, line) pairs of one file."""
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    return [(a.name, node.lineno) for node in ast.walk(tree)
            if isinstance(node, ast.ImportFrom)
            and (node.module or "").split(".")[-1] == "executor"
            for a in node.names]


def _py_files(*roots):
    for root in roots:
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            yield from (os.path.join(dirpath, f) for f in sorted(filenames)
                        if f.endswith(".py"))


def test_nothing_below_the_executor_imports_it():
    for name in ("verify.py", "physical.py"):
        assert _executor_imports(os.path.join(PKG, "engine", name)) == []
    private = [(os.path.relpath(p, ROOT), name, line)
               for p in _py_files(PKG, os.path.join(ROOT, "tools"),
                                  os.path.join(ROOT, "tests"), BENCH)
               for name, line in _executor_imports(p)
               if name.startswith("_")]
    assert private == []


def test_the_executor_derives_no_stage_form():
    with open(os.path.join(PKG, "engine", "executor.py")) as f:
        tree = ast.parse(f.read())
    names = {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)} | \
        {n.attr for n in ast.walk(tree) if isinstance(n, ast.Attribute)}
    assert not names & {"build_segment", "build_stream_segment",
                        "fused_sandwich", "parent_counts", "worthwhile",
                        "fused_static_eligible"}


def test_the_repo_lint_holds_the_import_rule():
    spec = importlib.util.spec_from_file_location(
        "srjt_lint_physical", os.path.join(ROOT, "tools", "srjt_lint.py"))
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)

    def run(src, relpath):
        fl = lint._FileLint(relpath, tuple(SYNC_WHITELIST))
        fl.visit(ast.parse(src))
        return [v["code"] for v in fl.out]

    engine = "spark_rapids_jni_tpu/engine/"
    assert run("from .executor import _stream_scan_of\n",
               engine + "segment.py") == ["executor-import"]
    assert run("from .executor import execute\n",
               engine + "verify.py") == ["executor-import"]
    assert run("from .executor import execute\n",
               engine + "physical.py") == ["executor-import"]
    assert run("from .executor import execute, new_stats\n",
               engine + "explain.py") == []
