"""The ``tail`` stage (engine/segment.py ``Tail``, engine/physical.py): the
operators above a streamed aggregate as one compiled program.

The suite's process has eight virtual devices and a tail is a one-device
form, so every plan here is lowered for ONE device
(``lower(..., ndev=1)``) and the ``PhysicalPlan`` executed; lowered for
the process's own mesh the same plan keeps the forms it had before the
tail, which is the reference the compiled form is compared with.

- (a) the benchmark's q5-lite and q55-lite at their ``rehearsal_rows``:
  compiled tail against ``execute(fused=False)`` and against the query
  module's pandas ``reference``, bit for bit, for 1, 11 and 17 chunks (a
  folded stream) and for an empty date window;
- (b) plan shapes of the region's node types over a warehouse with nulls
  in join keys, group keys and sort keys and ties under ``Limit``:
  compiled against interpreted, buffers and validity alike — a float sum
  whose order matters to its last bits among them;
- (c) what a warm query counts: one ``engine.tail.compiled``, no
  ``engine.tail.interp``, two host syncs (``combine-sizing``,
  ``tail-compaction``), nothing compiled on the second run or under a
  second seed;
- (d) every veto — a build matched twice, a string column, an Exchange in
  the region, four devices, a stream that ran interpreted, an empty
  stream — lowers or demotes to the forms the plan had, with equal results
  and ``engine.tail.interp`` + 1;
- EXPLAIN ANALYZE and the artifact lint render and lint the stage.

(e), the tail programs compiled for a described v5e, is in
``tests/test_chip_compile.py`` (one process may load the TPU library).
"""

import importlib.util
import json
import os
import sys

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_jni_tpu.engine import (Aggregate, Filter, Join, Limit,
                                         Project, Scan, Sort, col, execute,
                                         lit, lower, new_stats, optimize)
from spark_rapids_jni_tpu.engine import executor as ex
from spark_rapids_jni_tpu.engine import segment as sg
from spark_rapids_jni_tpu.engine.fuzz import _flags, stage_census
from spark_rapids_jni_tpu.engine.plan import Exchange, TopK
from spark_rapids_jni_tpu.engine.verify import (SchemaResolver,
                                                lint_plan_artifacts,
                                                sync_budget, verify)
from spark_rapids_jni_tpu.utils import blackbox, metrics
from spark_rapids_jni_tpu.utils.config import config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")


def one_device(opt, blind=False):
    """``opt`` lowered for one device; footer schemas unless ``blind``."""
    resolver = SchemaResolver()
    return lower(opt, **{**ex.lowering_flags(), "ndev": 1},
                 resolver=None if blind else lambda n: verify(n, resolver))


def run(plan):
    """(result, stats, the run's QueryMetrics, its host-sync labels)."""
    seq0 = max((e["seq"] for e in blackbox.tail()), default=0)
    stats = new_stats()
    with metrics.query("tail-program") as qm:
        out = execute(plan, stats)
    labels = sorted(e["label"] for e in blackbox.tail()
                    if e["ev"] == "host_sync" and e["seq"] > seq0)
    return out, stats, qm, labels


def assert_same(got, want):
    """Names, dtypes, data buffers and validity — its presence too."""
    assert got.names == want.names
    assert got.num_rows == want.num_rows
    for nm, a, b in zip(got.names, got.columns, want.columns):
        assert a.dtype == b.dtype, nm
        assert np.array_equal(np.asarray(a.data), np.asarray(b.data)), nm
        assert (a.validity is None) == (b.validity is None), nm
        if a.validity is not None:
            assert np.array_equal(np.asarray(a.validity),
                                  np.asarray(b.validity)), nm


def tail_counts(qm) -> tuple:
    return (qm.counters.get("engine.tail.compiled", 0),
            qm.counters.get("engine.tail.interp", 0))


# -- (a) the benchmark's two queries -----------------------------------------

def _bench(config_name):
    with open(os.path.join(BENCH, "configs", config_name + ".json")) as f:
        cfg = json.load(f)
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "tailtest_" + cfg["query"],
        os.path.join(BENCH, "queries", cfg["query"] + ".py"))
    query = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(query)
    return cfg, query


def _warehouse(root, config_name, seed, fact_groups):
    """The configuration's tables at its ``rehearsal_rows`` on ``seed``,
    the fact in ``fact_groups`` row groups (a chunk each)."""
    cfg, query = _bench(config_name)
    rows = {t: s["rows"] for t, s in cfg["tables"].items()}
    rows.update(cfg["rehearsal_rows"])
    frames = query.tables(seed, rows)
    paths = {}
    for name, df in frames.items():
        groups = fact_groups if name == query.FACT \
            else cfg["tables"][name]["row_groups"]
        paths[name] = os.path.join(
            root, f"{config_name}.{name}.{seed}.{fact_groups}.parquet")
        pq.write_table(pa.Table.from_pandas(df, preserve_index=False),
                       paths[name], compression="snappy",
                       row_group_size=-(-len(df) // groups))
    return query, frames, paths


#: traffic parameters under which every row group of the fact streams
OPEN = {
    "nds_q5lite_sf1": {"window_lo": 2451545, "window_hi": 2451910,
                       "fact_lo": 2450816},
    "nds_q55lite_sf1": {"d_year": 1999, "d_moy": 11, "i_manager_id": 28,
                        "limit": 100},
}
#: ... and under which the date window holds no date: every chunk streams
#: and no row survives
NO_DATES = {
    "nds_q5lite_sf1": {"window_lo": 2451545, "window_hi": 2451544,
                       "fact_lo": 2450816},
    "nds_q55lite_sf1": {"d_year": 1899, "d_moy": 11, "i_manager_id": 28,
                        "limit": 100},
}


def _against_reference(out, want: pd.DataFrame):
    assert list(out.names) == list(want.columns)
    for nm, c in zip(out.names, out.columns):
        data = np.asarray(c.data)
        if want[nm].dtype == np.float64:
            data = data.view(np.float64)    # FLOAT64 is stored as its bits
            assert np.array_equal(data.view(np.int64),
                                  want[nm].to_numpy().view(np.int64)), nm
        else:
            assert np.array_equal(data, want[nm].to_numpy()), nm
        assert c.validity is None or np.asarray(c.validity).all(), nm


@pytest.mark.parametrize("chunks", (1, 11, 17))
@pytest.mark.parametrize("config_name", sorted(OPEN))
def test_benchmark_query_compiled_equals_interpreted_and_reference(
        tmp_path, config_name, chunks):
    query, frames, paths = _warehouse(str(tmp_path), config_name,
                                      2147483901, chunks)
    params = OPEN[config_name]
    opt = optimize(query.plan(paths, params, 64 << 20))
    physical = one_device(opt)
    assert physical.stages[0].kind == "tail"
    out, stats, qm, labels = run(physical)
    assert stats["chunks"] == chunks and tail_counts(qm) == (1, 0)
    folds = qm.counters.get("engine.combine.folds", 0)
    assert folds == (1 if chunks > sg.COMBINE_ARITY else 0)
    assert labels == ["combine-fold-sizing"] * folds + \
        ["combine-sizing", "tail-compaction"]
    assert out.num_rows > 0
    assert_same(out, execute(opt, fused=False))
    _against_reference(out, query.reference(frames, params))


@pytest.mark.parametrize("config_name", sorted(NO_DATES))
def test_benchmark_query_over_an_empty_date_window(tmp_path, config_name):
    """No date in the window: every chunk streams, no row survives, and
    the compiled tail compacts to no row of the right columns."""
    query, frames, paths = _warehouse(str(tmp_path), config_name, 7, 3)
    params = NO_DATES[config_name]
    opt = optimize(query.plan(paths, params, 64 << 20))
    out, stats, qm, _ = run(one_device(opt))
    assert stats["chunks"] == 3 and out.num_rows == 0
    want = execute(opt, fused=False)
    assert out.names == want.names
    assert [c.dtype for c in out.columns] == [c.dtype for c in want.columns]
    assert len(query.reference(frames, params)) == 0


# -- (b) plan shapes of the region's node types ------------------------------

N_FACT = 6_000


@pytest.fixture(scope="module")
def wh(tmp_path_factory):
    """A fact with a nullable key and a measure whose sums depend on their
    order, and dimensions: ``dim`` (unique keys, one of them null, a
    nullable payload), ``dup`` (a key twice), ``named`` (a string
    payload), ``dates`` / ``dates_dup`` (a semi-join build under the
    stream, unique / with a key twice)."""
    root = tmp_path_factory.mktemp("tail")
    rng = np.random.default_rng(39)
    k = rng.integers(0, 24, N_FACT)
    knull = rng.random(N_FACT) < 0.05
    paths = {}

    def write(name, table, **kw):
        paths[name] = str(root / f"{name}.parquet")
        pq.write_table(table, paths[name], **kw)

    write("fact", pa.table({
        "k": pa.array(k, pa.int64(), mask=knull),
        "g": pa.array(rng.integers(0, 5, N_FACT), pa.int64()),
        "d": pa.array(rng.integers(0, 40, N_FACT), pa.int64()),
        # quarters: every sum exact, whatever the order
        "v": pa.array(rng.integers(-400, 400, N_FACT) / 4.0, pa.float64()),
        # thirds and large magnitudes: a sum's last bits depend on order
        "x": pa.array(rng.standard_normal(N_FACT) * 10.0 ** rng.integers(
            -3, 9, N_FACT) / 3.0, pa.float64()),
        "w": pa.array(rng.integers(-50, 50, N_FACT), pa.int32())}),
        row_group_size=2_000)
    dk = np.arange(24, dtype=np.int64)
    write("dim", pa.table({
        "dk": pa.array(np.append(dk, 0), pa.int64(),
                       mask=np.append(np.zeros(24, bool), True)),
        "grp": pa.array(np.append(dk % 4, 9), pa.int64(),
                        mask=np.append(dk % 7 == 3, False)),
        "dv": pa.array(np.append(dk * 0.25, 1.0), pa.float64())}))
    write("dup", pa.table({
        "dk": pa.array(np.append(dk, 5), pa.int64()),
        "grp": pa.array(np.append(dk % 4, 2), pa.int64())}))
    write("named", pa.table({
        "dk": pa.array(dk, pa.int64()),
        "name": pa.array([f"n{i % 3}" for i in dk])}))
    write("some", pa.table({"dk": pa.array(dk[::3], pa.int64())}))
    # builds above PROBE_COMPARE_MAX_BUILD slots: the merge-rank probe
    big = np.arange(9_000, dtype=np.int64) - 10
    write("big", pa.table({"dk": pa.array(big), "grp": pa.array(big % 5)}))
    write("big_dup", pa.table({
        "dk": pa.array(np.append(big, 7)),
        "grp": pa.array(np.append(big % 5, 1))}))
    write("dates", pa.table({"dd": pa.array(np.arange(0, 40, 2), pa.int64())}))
    write("dates_dup", pa.table({
        "dd": pa.array(np.append(np.arange(0, 40, 2), 4), pa.int64())}))
    return paths


def _totals(wh, measure="v", dates=None, chunk_bytes=48_000):
    """The streamed aggregate every shape sits on: by (k, g)."""
    src = Scan(wh["fact"], chunk_bytes=chunk_bytes)
    if dates is not None:
        src = Join(src, Scan(wh[dates]), ("d",), ("dd",), "semi")
    return Aggregate(Filter(src, (">=", col("w"), lit(-45))), ("k", "g"),
                     ((measure, "sum"), (measure, "count"), ("w", "min")),
                     ("total", "n", "low"))


def _joined(wh, dim="dim", how="inner", measure="v"):
    return Join(_totals(wh, measure), Scan(wh[dim]), ("k",), ("dk",), how)


SHAPES = {
    # nulls in the join key (the fact's null k group matches nothing, the
    # build's null key neither), in the group key (grp) and the sort keys
    "join-agg-sort": lambda wh: Sort(
        Aggregate(_joined(wh), ("grp",),
                  (("total", "sum"), ("n", "sum"), ("low", "min"),
                   ("dv", "max"), ("total", "mean")),
                  ("total", "n", "low", "dv", "avg")),
        (("grp", False), ("total", True))),
    "sort-nulls-last-key": lambda wh: Sort(
        _totals(wh), (("k", False), ("g", True))),
    # ties: n and g repeat, and the limit cuts inside a run of equal keys
    "limit-sort-ties": lambda wh: Limit(
        Sort(_totals(wh), (("g", True),)), 17),
    "topk-ties": lambda wh: TopK(_totals(wh), (("g", False), ("n", True)),
                                 23),
    "limit-larger-than-the-rows": lambda wh: Limit(
        Sort(_totals(wh), (("total", False), ("k", True), ("g", True))),
        10_000),
    "limit-no-sort": lambda wh: Limit(
        Filter(_totals(wh), (">", col("total"), lit(0.0))), 9),
    "filter-project": lambda wh: Project(
        Filter(_totals(wh), ("|", ("<", col("k"), lit(6)),
                             (">=", col("n"), lit(12)))),
        ("g", "total", "k")),
    "semi-join": lambda wh: Sort(
        Join(_totals(wh), Scan(wh["some"]), ("k",), ("dk",), "semi"),
        (("k", True), ("g", True))),
    "rank-probe-join": lambda wh: _agg_sort(_joined(wh, dim="big")),
    "rank-probe-semi": lambda wh: Sort(
        Join(_totals(wh), Scan(wh["big"]), ("k",), ("dk",), "semi"),
        (("k", True), ("g", True))),
    "join-filter-topk": lambda wh: TopK(
        Filter(_joined(wh), ("!=", col("grp"), lit(1))),
        (("dv", False), ("total", True), ("g", True)), 31),
    "two-aggregates": lambda wh: Sort(
        Aggregate(Aggregate(_joined(wh), ("grp", "g"),
                            (("total", "sum"), (None, "count_all")),
                            ("total", "rows")),
                  ("g",), (("total", "max"), ("rows", "sum")),
                  ("top", "rows")), (("g", True),)),
    "filter-to-nothing": lambda wh: Sort(
        Aggregate(Filter(_totals(wh), (">", col("n"), lit(10 ** 6))),
                  ("g",), (("total", "sum"),), ("total",)),
        (("g", True),)),
    "semi-under-the-stream": lambda wh: Sort(
        Aggregate(_totals(wh, dates="dates"), ("g",),
                  (("total", "sum"), ("n", "sum")), ("total", "n")),
        (("g", True),)),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_shape_compiled_equals_interpreted(wh, shape):
    opt = optimize(SHAPES[shape](wh))
    physical = one_device(opt)
    assert physical.stages[0].kind == "tail" and not physical.stages[0].vetoed
    out, stats, qm, labels = run(physical)
    assert tail_counts(qm) == (1, 0)
    assert labels == ["combine-sizing", "tail-compaction"]
    assert stage_census(physical, stats, qm) is None
    assert_same(out, execute(opt, fused=False))
    # the same plan on the process's own mesh keeps the forms it had
    assert_same(out, execute(opt))


def test_a_float_sum_whose_order_matters_keeps_the_interpreted_bits(wh):
    """``x`` sums to other last bits in another order.  The compiled tail
    adds the streamed partials in the order ``groupby_padded`` gives the
    interpreted operators, so against the same stream under the
    interpreted tail (this process's mesh: no tail) it is bit for bit."""
    def shape(measure):
        return Sort(Aggregate(_joined(wh, measure=measure), ("grp",),
                              (("total", "sum"), ("total", "mean")),
                              ("total", "avg")), (("grp", True),))

    opt = optimize(shape("x"))
    out, _, qm, _ = run(one_device(opt))
    assert tail_counts(qm) == (1, 0)
    want = execute(opt)
    assert_same(out, want)
    # the case is one: summed in the reverse order the bits differ
    t = execute(optimize(_joined(wh, measure="x")), fused=False)
    x = np.asarray(t.column("total").data).view(np.float64)
    ok = np.asarray(t.column("total").valid_mask()) \
        & np.asarray(t.column("grp").valid_mask())
    x = x[ok & (np.asarray(t.column("grp").data) == 0)]
    assert np.sum(x) != np.sum(x[::-1]) or np.cumsum(x)[-1] != np.sum(x)


# -- (c) what a warm query counts ---------------------------------------------

def test_a_warm_query_counts_one_tail_two_syncs_and_compiles_nothing(
        tmp_path):
    plans = {}
    for seed in (7, 2147483777):
        query, _, paths = _warehouse(str(tmp_path), "nds_q5lite_sf1", seed, 4)
        plans[seed] = one_device(optimize(
            query.plan(paths, OPEN["nds_q5lite_sf1"], 1 << 20)))
    run(plans[7])                                   # compiles
    for seed in (7, 2147483777):                    # warm; another seed
        _, stats, qm, labels = run(plans[seed])
        assert tail_counts(qm) == (1, 0)
        assert qm.counters.get("engine.tail.replay", 0) == 1
        assert qm.counters["engine.host_sync"] == 2
        assert labels == ["combine-sizing", "tail-compaction"]
        assert qm.counters.get("engine.segment.compile", 0) == 0
        assert qm.counters.get("engine.segment_cache.miss", 0) == 0
        assert qm.counters.get("engine.segment.replay", 0) == stats["chunks"]
        assert stage_census(plans[seed], stats, qm) is None


# -- (d) the vetoes -----------------------------------------------------------

def _agg_sort(child):
    return Sort(Aggregate(child, ("grp",), (("total", "sum"), ("n", "sum")),
                          ("total", "n")), (("grp", True),))


@pytest.mark.parametrize("dim", ("dup", "big_dup"))
def test_a_build_matched_twice_demotes_after_the_fetch(wh, dim):
    """Both probe methods count what the probe-row shape cannot hold."""
    from spark_rapids_jni_tpu.ops.join import PROBE_COMPARE_MAX_BUILD
    assert (pq.read_metadata(wh[dim]).num_rows > PROBE_COMPARE_MAX_BUILD) \
        == (dim == "big_dup")
    opt = optimize(_agg_sort(_joined(wh, dim=dim)))
    physical = one_device(opt)
    assert physical.stages[0].kind == "tail" and not physical.stages[0].vetoed
    out, stats, qm, labels = run(physical)
    assert tail_counts(qm) == (0, 1)
    # the launch and its fetch were paid before the spill was known; the
    # demoted group-by above the join is the interpreted one (no counted
    # sync), the stream's partial is compacted as it was before the tail
    assert labels == ["combine-sizing", "groupby-compaction",
                      "tail-compaction"]
    assert "ran interpreted" in stage_census(physical, stats, qm)
    assert_same(out, execute(opt, fused=False))


def test_a_string_column_is_vetoed_statically_and_at_run_time(wh):
    opt = optimize(Sort(_joined(wh, dim="named"),
                        (("k", True), ("g", True))))
    for blind in (False, True):
        physical = one_device(opt, blind=blind)
        top = physical.stages[0]
        assert (top.kind, top.vetoed) == ("tail", not blind)
        out, stats, qm, labels = run(physical)
        assert tail_counts(qm) == (0, 1)
        assert labels == ["combine-sizing", "groupby-compaction"]
        if not blind:   # the static side names what ran
            assert stage_census(physical, stats, qm) is None
            assert [e["site"] for e in sync_budget(opt, cfg=config, ndev=1)
                    ][:1] == ["interpreted-fallback"]
        assert_same(out, execute(opt, fused=False))


def test_an_exchange_in_the_region_keeps_todays_forms(wh):
    opt = optimize(_agg_sort(
        Exchange(_joined(wh), ("grp",), "hash")))
    physical = one_device(opt)
    assert "tail" not in {st.kind for st in physical.stages}
    out, _, qm, _ = run(physical)
    assert tail_counts(qm) == (0, 0)
    assert_same(out, execute(opt, fused=False))


@pytest.mark.parametrize("shape", ("left-join", "fed-from-the-right",
                                   "first-last"))
def test_a_node_outside_the_region_rule_keeps_todays_forms(wh, shape):
    plan = {
        "left-join": lambda: Sort(_joined(wh, how="left"),
                                  (("k", True), ("g", True))),
        "fed-from-the-right": lambda: Sort(
            Join(Scan(wh["dim"]), _totals(wh), ("dk",), ("k",), "inner"),
            (("dk", True), ("g", True))),
        "first-last": lambda: Sort(
            Aggregate(Sort(_totals(wh), (("k", True), ("g", True))), ("g",),
                      (("total", "first"),), ("total",)), (("g", True),)),
    }[shape]()
    opt = optimize(plan)
    physical = one_device(opt)
    assert "tail" not in {st.kind for st in physical.stages}
    assert_same(run(physical)[0], execute(opt, fused=False))


def test_four_devices_keep_todays_forms(tmp_path):
    """``tests/test_mesh4_cell.py``'s set-up: the cell's plan optimized
    under ``SRJT_DIST=1`` and lowered for four devices has Exchanges in
    the region and no tail; without them, four devices alone keep it out."""
    cfg, query = _bench("nds_q5lite_sf1_mesh4")
    _, _, paths = _warehouse(str(tmp_path), "nds_q5lite_sf1_mesh4", 7, 4)
    plan = query.plan(paths, OPEN["nds_q5lite_sf1"], 1 << 20)
    assert cfg["server_env"]["SRJT_DIST"] == "1"
    flags = ex.lowering_flags()
    with _flags(distribute=True):
        dist = optimize(plan, distribute=True)
        for ndev in (4, 1):
            kinds = [st.kind for st in lower(
                dist, **{**flags, "ndev": ndev}).stages]
            assert "tail" not in kinds
            assert any(k.startswith("exchange-") for k in kinds)
    opt = optimize(plan)
    four = lower(opt, **{**flags, "ndev": 4})
    one = lower(opt, **{**flags, "ndev": 1})
    assert one.stages[0].kind == "tail"
    assert "tail" not in {st.kind for st in four.stages}
    # stage for stage, four devices lower to what the tail demotes to
    assert [(st.kind, st.path) for st in four.stages] == \
        [(st.kind, st.path)
         for st in one.demotion(one.stages[0]) + list(one.stages[1:])]


def test_a_stream_that_ran_interpreted_hands_the_tail_a_table(wh):
    """The stream's own unique-build veto (a date twice in its semi-join
    build) interprets every chunk: the partial arrives compacted."""
    opt = optimize(_agg_sort(Join(
        _totals(wh, dates="dates_dup", chunk_bytes=1 << 20), Scan(wh["dim"]),
        ("k",), ("dk",), "inner")))
    physical = one_device(opt)
    assert physical.stages[0].kind == "tail"
    out, stats, qm, labels = run(physical)
    assert stats["chunks"] and stats["fused_segments"] == 0
    assert tail_counts(qm) == (0, 1)
    assert "tail-compaction" not in labels
    assert_same(out, execute(opt, fused=False))


def test_an_empty_stream_demotes_and_the_census_knows(wh):
    opt = optimize(_agg_sort(Join(
        Aggregate(Filter(Scan(wh["fact"], chunk_bytes=20_000),
                         (">", col("d"), lit(1_000))), ("k", "g"),
                  (("v", "sum"), ("v", "count")), ("total", "n")),
        Scan(wh["dim"]), ("k",), ("dk",), "inner")))
    physical = one_device(opt)
    assert physical.stages[0].kind == "tail"
    out, stats, qm, labels = run(physical)
    assert stats["chunks"] == 0 and out.num_rows == 0
    assert tail_counts(qm) == (0, 1)
    assert stage_census(physical, stats, qm) is None
    want = execute(opt, fused=False)
    assert out.names == want.names
    assert [c.dtype for c in out.columns] == [c.dtype for c in want.columns]


def test_exactly_one_tail_counter_ticks_per_streamed_query(wh):
    """``compiled + interp == streamed queries`` over a mix of the above."""
    plans = [SHAPES["join-agg-sort"](wh), SHAPES["topk-ties"](wh),
             _agg_sort(_joined(wh, dim="dup")),
             Sort(_joined(wh, dim="named"), (("k", True), ("g", True)))]
    compiled = interp = 0
    for plan in plans:
        _, stats, qm, _ = run(one_device(optimize(plan)))
        assert stats["streamed"]
        c, i = tail_counts(qm)
        assert c + i == 1
        compiled, interp = compiled + c, interp + i
    assert (compiled, interp) == (2, 2)


# -- EXPLAIN ANALYZE and the artifact lint ----------------------------------------

def test_explain_analyze_renders_the_tail(wh, monkeypatch):
    from spark_rapids_jni_tpu.engine import explain
    flags = ex.lowering_flags
    monkeypatch.setattr(ex, "lowering_flags",
                        lambda fused=None: {**flags(fused), "ndev": 1})
    report = explain.explain_analyze(SHAPES["join-agg-sort"](wh))
    by_label = {n["label"]: n["metrics"] for n in report.nodes}
    for label in ("sort", "join"):
        assert by_label[label]["in_program"] is True
    root = report.nodes[-1]["metrics"]
    assert root["tail_nodes"] == 3 and root["tail_cap"] >= 64
    first = report.text.splitlines()[0]
    assert first.startswith("Sort(") and "in_program=yes" in first \
        and "tail_nodes=3" in first and f"tail_cap={root['tail_cap']}" in first
    # the streamed Aggregate under it reports its groups, not its slots
    streamed = [n for n in report.nodes if n["label"] == "aggregate"
                and n["metrics"] and n["metrics"]["chunks"]]
    assert 0 < streamed[0]["metrics"]["rows_out"] <= 24 * 5 + 5


def test_the_artifact_lint_traces_the_tail(wh, monkeypatch):
    import jax
    opt = optimize(SHAPES["join-agg-sort"](wh))
    monkeypatch.setattr(jax, "devices", lambda *a: [object()])
    report = lint_plan_artifacts(opt)
    assert report["violations"] == []
    (tail,) = [r for r in report["segments"] if r["kind"] == "tail"]
    assert tail["ok"] and tail["primitives"] > 0
    assert tail["nodes"] == ["join", "aggregate", "sort"]
    assert sorted(e["site"] for e in report["syncs"] if e["count"]) == \
        ["combine-sizing", "tail-compaction"]
    # a vetoed tail is skipped, as a vetoed segment is
    named = optimize(Sort(_joined(wh, dim="named"),
                          (("k", True), ("g", True))))
    (skipped,) = [r for r in lint_plan_artifacts(named)["segments"]
                  if r["kind"] == "tail"]
    assert "skipped" in skipped
