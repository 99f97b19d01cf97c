"""TPC-H Q6 through the engine (PR 40): decimal and date expressions, the
arithmetic, and the aggregate with no group keys — on the CPU, against the
cell's own plain reference (``benchmarks/queries/tpch_q6.py``).

- the cell's plan at its ``rehearsal_rows`` equals the reference exactly,
  for three seeds, fused (one chunk program per chunk) and interpreted;
- a keyless aggregate is one row (an empty input's: ``sum`` NULL, ``count``
  0), a masked reduction: its chunk program holds no sort;
- a literal meets a decimal column at the column's scale: ``l_quantity <
  24`` keeps 23.99 and drops 24.00;
- a value past int64's checked bound fails the query (``decimal-overflow``,
  ``engine.decimal.overflow`` + 1), in the multiply and in the sum, fused
  and interpreted, and never comes back wrapped;
- a keyless stream of 1, 16, 17, 24 or 33 chunks pays ONE host sync;
- the counters' invariants per query; the FLBA / DATE file through the
  decode pool; Spark's result types and the new verifier codes.
"""

from __future__ import annotations

import importlib.util
import json
import os
from decimal import Decimal

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_jni_tpu.engine import (Aggregate, Filter, Project, Scan,
                                         col, deserialize, execute, lit,
                                         lit_date, lit_decimal, optimize,
                                         verify)
from spark_rapids_jni_tpu.engine import segment as sg
from spark_rapids_jni_tpu.engine.executor import lowering_flags, new_stats
from spark_rapids_jni_tpu.engine.physical import lower
from spark_rapids_jni_tpu.engine.verify import PlanVerificationError
from spark_rapids_jni_tpu.io import read_parquet
from spark_rapids_jni_tpu.utils import metrics, tracing
from spark_rapids_jni_tpu.utils.errors import DecimalOverflowError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CELL = "tpch_q6_sf1_1994"
SEEDS = (1, 2, 3_000_000_019)
PLAN_EXPRS = 10         # 5 comparisons + 4 ANDs + the multiply


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


Q6 = _load(os.path.join(BENCH, "queries", "tpch_q6.py"), "q6test_query")
RUN = _load(os.path.join(BENCH, "run.py"), "q6test_run")
with open(os.path.join(BENCH, "configs", "tpch_q6_sf1.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(BENCH, "traffic", "q6_1994.json")) as f:
    PARAMS = json.load(f)["params"]
CHUNK_BYTES = CONFIG["storage"]["chunk_bytes"]


def _counter(name):
    return tracing.counter_value(name)


def _revenue(table):
    (c,) = table.columns
    assert list(table.names) == ["revenue"] and table.num_rows == 1
    return np.asarray(c.data)[0], np.asarray(c.valid_mask())[0]


@pytest.fixture(scope="module")
def warehouses(tmp_path_factory):
    """The cell's warehouse at ``rehearsal_rows`` per seed, as run.py
    writes it (24 row groups)."""
    rows = {"lineitem": CONFIG["rehearsal_rows"]["lineitem"]}
    out = {}
    for seed in SEEDS:
        frames = Q6.tables(seed, rows)
        paths = RUN.write_tables(frames, CONFIG,
                                 str(tmp_path_factory.mktemp(f"q6_{seed}")))
        out[seed] = (frames, paths)
    return out


# -- the cell's plan against its reference -----------------------------------

@pytest.mark.parametrize("fused", [True, False], ids=["fused", "interpreted"])
@pytest.mark.parametrize("seed", SEEDS)
def test_q6_equals_the_reference(warehouses, seed, fused):
    frames, paths = warehouses[seed]
    want = int(Q6.reference(frames, PARAMS).revenue[0])
    opt = optimize(Q6.plan(paths, PARAMS, CHUNK_BYTES))
    stats = new_stats()
    with metrics.query("q6") as qm:
        got, valid = _revenue(execute(opt, stats, fused=fused))
    assert valid and int(got) == want
    assert stats["streamed"] and stats["chunks"] == 24
    assert stats["row_groups_pruned"] == 0
    c = qm.counters
    assert c.get("engine.agg.keyless", 0) == 1
    if fused:
        # one chunk program per chunk, every expression node in it, and
        # the result's fetch the only host sync
        assert stats["fused_segments"] == 1
        assert c.get("engine.segment.compile", 0) \
            + c.get("engine.segment.replay", 0) == 24
        assert c.get("engine.expr.fused", 0) == PLAN_EXPRS * 24
        assert c.get("engine.expr.eager", 0) == 0
        assert c.get("engine.host_sync", 0) == 1
    else:
        assert c.get("engine.expr.fused", 0) == 0
        assert c.get("engine.expr.eager", 0) == PLAN_EXPRS * 24


def test_the_control_differs_and_the_types_are_sparks(warehouses):
    frames, paths = warehouses[SEEDS[0]]
    want = Q6.reference(frames, PARAMS)
    low = Q6.reference(frames, PARAMS, float_dtype=np.float32)
    assert want.revenue.dtype == np.int64 and len(want) == 1
    assert int(low.revenue[0]) != int(want.revenue[0])
    schema = verify(Q6.plan(paths, PARAMS, CHUNK_BYTES))
    (dt,) = schema.values()
    assert (dt.id.name, dt.scale, dt.precision) == ("DECIMAL64", -4, 38)


def test_the_comparison_sees_one_unit(warehouses):
    """The harness's comparison (``benchmarks/compare.py``) on this cell's
    answer: exact passes, one unit of 10**-4 off fails, the float32
    control fails."""
    cmp = _load(os.path.join(BENCH, "compare.py"), "q6test_compare")
    frames, _ = warehouses[SEEDS[2]]
    want = Q6.reference(frames, PARAMS)

    def served(frame):
        return [(None, frame[c].to_numpy(), None) for c in frame.columns]

    off = want.assign(revenue=want.revenue + 1)
    low = Q6.reference(frames, PARAMS, float_dtype=np.float32)
    verdicts = [cmp.verdict(cmp.compare([served(f)], want))
                for f in (want, off, low)]
    assert verdicts == [True, False, False]


def test_the_plan_serializes_losslessly(warehouses):
    _, paths = warehouses[SEEDS[0]]
    p = Q6.plan(paths, PARAMS, CHUNK_BYTES)
    back = deserialize(p.serialize())
    assert back.serialize() == p.serialize()
    assert back.fingerprint() == p.fingerprint()
    assert back.child.columns == (("rev", ("*", col("l_extendedprice"),
                                           col("l_discount"))),)
    # a Project of plain names serializes as it always did
    plain = Project(Scan("x.parquet"), ["a", "b"])
    assert json.loads(plain.serialize())["nodes"][1]["columns"] == ["a", "b"]
    assert lit_decimal("0.05") == ("lit_decimal", 5, 2, 2)
    assert lit_decimal("-12.300") == ("lit_decimal", -12300, 5, 3)
    assert lit_date("1994-01-01") == ("lit_date", 8766)


def test_the_chunk_program_holds_no_sort(warehouses):
    import jax

    from spark_rapids_jni_tpu.engine.verify import (_collect_primitives,
                                                    _TraceProbe, _zero_table)
    _, paths = warehouses[SEEDS[0]]
    opt = optimize(Q6.plan(paths, PARAMS, CHUNK_BYTES))
    st = lower(opt, **lowering_flags()).stages[0]
    assert st.kind == "stream-agg" and st.node.keys == ()
    seg = st.segment
    table = _zero_table(verify(seg.input), 64)
    closed = jax.make_jaxpr(sg._build_fn(seg, _TraceProbe()))(
        table, np.int32(64), ())
    prims = set(_collect_primitives(closed.jaxpr))
    assert not {p for p in prims if "sort" in p}, prims
    assert "mul" in prims


# -- the keyless aggregate ---------------------------------------------------

@pytest.fixture(scope="module")
def small(tmp_path_factory):
    """Four rows of the cell's types, one row group."""
    units = np.array([2399, 2400, 2401, 100])
    table = pa.table({
        "l_quantity": Q6._decimal_array(units, 15, 2),
        "l_extendedprice": Q6._decimal_array(units * 7, 15, 2),
        "l_discount": Q6._decimal_array(np.array([5, 6, 7, 6]), 15, 2),
        "l_shipdate": pa.array(np.array([8766, 8800, 9000, 9130], np.int32),
                               pa.int32()).cast(pa.date32()),
    })
    path = str(tmp_path_factory.mktemp("q6small") / "li.parquet")
    pq.write_table(table, path)
    return path


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "interpreted"])
def test_literal_meets_the_decimal_at_its_scale(small, fused):
    """``l_quantity < 24`` keeps 23.99 (2399 units) and 1.00, drops 24.00
    and 24.01 — the literal is 2400 units, not 24."""
    for chunk in (None, 1 << 20):
        plan = Aggregate(Filter(Scan(small, chunk_bytes=chunk),
                                ("<", col("l_quantity"), lit(24))), [],
                         [("l_quantity", "sum"), ("l_quantity", "count")],
                         names=["s", "n"])
        out = execute(optimize(plan), fused=fused)
        assert [c.to_pylist() for c in out.columns] == [
            [Decimal("24.99")], [2]]
    # the optimizer made the scale visible, and kept it from the pruning
    # hint (a raw 24 against 2400-unit statistics would prune every group)
    opt = optimize(Aggregate(Filter(Scan(small, chunk_bytes=1 << 20),
                                    ("<", col("l_quantity"), lit(24))), [],
                             [(None, "count_all")], names=["n"]))
    assert opt.child.predicate[2] == ("lit_decimal", 2400, 4, 2)
    assert opt.child.child.predicate is None


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "interpreted"])
@pytest.mark.parametrize("chunk", [None, 1 << 20], ids=["whole", "streamed"])
def test_keyless_aggregate_is_one_row(small, fused, chunk):
    aggs = [("l_discount", "sum"), ("l_discount", "count"),
            (None, "count_all"), ("l_discount", "min"),
            ("l_discount", "max")]
    names = ["s", "n", "rows", "lo", "hi"]
    every = Aggregate(Scan(small, chunk_bytes=chunk), [], aggs, names=names)
    none = Aggregate(Filter(Scan(small, chunk_bytes=chunk),
                            (">", col("l_discount"), lit_decimal("0.50"))),
                     [], aggs, names=names)
    before = _counter("engine.agg.keyless")
    got = execute(optimize(every), fused=fused)
    empty = execute(optimize(none), fused=fused)
    assert _counter("engine.agg.keyless") - before == 2
    assert [c.to_pylist() for c in got.columns] == [
        [Decimal("0.24")], [4], [4], [Decimal("0.05")], [Decimal("0.07")]]
    # Spark: one row over nothing — sum/min/max NULL, counts 0
    assert empty.num_rows == 1
    assert [c.to_pylist() for c in empty.columns] == [
        [None], [0], [0], [None], [None]]


def test_keyless_groupby_over_other_ops():
    """``ops.aggregate``'s keyless reduction beside an eager pandas-free
    reference: mean, var/std (two passes), first/last, float sums."""
    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.ops.aggregate import groupby
    v = np.array([3.5, -1.25, 8.0, 0.5])
    i = np.array([7, -2, 11, 4], np.int64)
    t = Table([Column.from_numpy(v), Column.from_numpy(i)], ["v", "i"])
    out = groupby(t, [], [("v", "sum"), ("v", "mean"), ("i", "var"),
                          ("i", "std"), ("i", "first"), ("v", "last"),
                          ("i", "min"), ("v", "max")])
    got = [c.to_pylist()[0] for c in out.columns]
    assert got[0] == v.sum() and got[1] == v.mean()
    assert got[2] == pytest.approx(np.var(i, ddof=1))
    assert got[3] == pytest.approx(np.std(i, ddof=1))
    assert got[4:] == [7, 0.5, -2, 8.0]


# -- overflow: raised, never wrapped ------------------------------------------

def _overflow_file(tmp, groups, price, disc, at=None):
    """``groups`` row groups of 8 rows of decimal(18,2); row ``at`` (default
    every row) carries ``price`` x ``disc`` units, the others 1 x 1."""
    n = groups * 8
    p = np.ones(n, np.int64)
    d = np.ones(n, np.int64)
    sel = slice(None) if at is None else slice(at, at + 1)
    p[sel], d[sel] = price, disc
    path = str(tmp / f"ovf_{groups}_{price}_{disc}_{at}.parquet")
    pq.write_table(pa.table({"p": Q6._decimal_array(p, 18, 2),
                             "d": Q6._decimal_array(d, 18, 2)}), path,
                   row_group_size=8)
    return path


def _revenue_plan(path):
    return Aggregate(Project(Scan(path, chunk_bytes=1 << 10),
                             [("r", ("*", col("p"), col("d")))]), [],
                     [("r", "sum")], names=["revenue"])


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "interpreted"])
@pytest.mark.parametrize("case", ["multiply", "sum", "late-chunk"])
def test_overflow_fails_the_query(tmp_path, fused, case):
    if case == "multiply":      # one product of ~10**34 units
        path = _overflow_file(tmp_path, 2, 10 ** 17, 10 ** 17)
    elif case == "sum":         # products of 3*10**18 fit; 16 of them don't
        path = _overflow_file(tmp_path, 2, 3 * 10 ** 9, 10 ** 9)
    else:                       # one bad product in chunk 20 of 24: folded
        path = _overflow_file(tmp_path, 24, 10 ** 17, 10 ** 17, at=8 * 19)
    before = _counter("engine.decimal.overflow")
    with pytest.raises(DecimalOverflowError, match="decimal-overflow"):
        execute(optimize(_revenue_plan(path)), fused=fused)
    assert _counter("engine.decimal.overflow") - before == 1
    # and a sum that fits is exact
    ok = _overflow_file(tmp_path, 2, 10 ** 8, 10 ** 8)
    got, valid = _revenue(execute(optimize(_revenue_plan(ok)), fused=fused))
    assert valid and int(got) == 16 * 10 ** 16


# -- a keyless stream pays one sync, however long ------------------------------

@pytest.mark.parametrize("chunks", [1, 16, 17, 24, 33])
def test_keyless_stream_pays_one_sync(tmp_path, chunks):
    rows = 8 * chunks
    frames = Q6.tables(chunks, {"lineitem": rows})
    cfg = {**CONFIG, "tables": {"lineitem": {"row_groups": chunks}}}
    paths = RUN.write_tables(frames, cfg, str(tmp_path))
    params = {**PARAMS, "ship_lo": "1992-01-01", "ship_hi_excl": "1999-01-01",
              "disc_lo": "0.00", "qty_lt": 51}
    want = int(Q6.reference(frames, params).revenue[0])
    stats = new_stats()
    with metrics.query("q6-long") as qm:
        got, valid = _revenue(execute(optimize(
            Q6.plan(paths, params, 1 << 10)), stats))
    assert valid and int(got) == want
    c = qm.counters
    assert stats["chunks"] == chunks
    assert c.get("engine.host_sync", 0) == 1
    folds = 0 if chunks <= sg.COMBINE_ARITY \
        else 1 + (chunks - sg.COMBINE_ARITY - 1) // (sg.COMBINE_ARITY - 1)
    assert c.get("engine.combine.folds", 0) == folds


# -- the FLBA decimals and the DATE through the decode pool --------------------

def test_the_cells_file_decodes_in_the_pool(warehouses, tmp_path):
    """The cell's columns and codec in 12 groups of 20,000 rows (the cut's
    24 groups of 10,000 fall under ``OFFLOAD_MIN_BYTES`` and decode in
    place, where the cell's 250,051-row groups are 25 times over it)."""
    from spark_rapids_jni_tpu.io import decode_pool
    from spark_rapids_jni_tpu.io.parquet import (OFFLOAD_MIN_BYTES,
                                                 ParquetChunkedReader,
                                                 ParquetFile)
    frames = warehouses[SEEDS[1]][0]
    cfg = {**CONFIG, "tables": {"lineitem": {"row_groups": 12}}}
    paths = RUN.write_tables(frames, cfg, str(tmp_path))
    groups = ParquetFile(paths["lineitem"]).row_groups
    assert min(g.total_byte_size for g in groups) >= OFFLOAD_MIN_BYTES
    made = decode_pool.DecodePool(workers=2, slabs=8)
    old = decode_pool.install(made)
    made.start()
    try:
        assert made.wait_ready(), "the decode workers did not come up"
        names = ("io.scan.decode.offloaded", "io.scan.decode.inline")
        before = {k: _counter(k) for k in names}
        with ParquetChunkedReader(paths["lineitem"],
                                  pass_read_limit=CHUNK_BYTES,
                                  prefetch=1) as reader:
            parts = [(t, n) for t, n in reader.iter_staged()]
        grew = [_counter(k) - before[k] for k in names]
        assert grew == [12, 0]
        assert len(parts) == 12
        li = frames["lineitem"]
        for name in li.columns:
            got = np.concatenate([np.asarray(t[name].data)[:n]
                                  for t, n in parts])
            assert np.array_equal(got, Q6._column_units(li, name)), name
        dts = {nm: parts[0][0][nm].dtype for nm in li.columns}
        assert dts["l_shipdate"].id.name == "TIMESTAMP_DAYS"
        assert (dts["l_discount"].id.name, dts["l_discount"].scale,
                dts["l_discount"].precision) == ("DECIMAL64", -2, 15)
    finally:
        decode_pool.install(old)
        made.shutdown()


# -- the verifier: Spark's result types, the new codes --------------------------

@pytest.fixture(scope="module")
def typed(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("typed") / "t.parquet")
    pq.write_table(pa.table({
        "a": Q6._decimal_array(np.array([150, -25]), 15, 2),
        "b": Q6._decimal_array(np.array([3, 4]), 7, 3),
        "i": pa.array([2, 3], pa.int64()),
        "d": pa.array(np.array([8766, 8767], np.int32),
                      pa.int32()).cast(pa.date32()),
        "s": pa.array(["x", "y"]),
        "f": pa.array([1.5, 2.5]),
    }), path)
    return path


def _type_of(path, expr):
    (dt,) = verify(Project(Scan(path), [("x", expr)])).values()
    return dt


@pytest.mark.parametrize("expr, want", [
    (("*", col("a"), col("a")), ("DECIMAL64", 4, 31)),
    (("+", col("a"), col("a")), ("DECIMAL64", 2, 16)),
    (("-", col("a"), col("b")), ("DECIMAL64", 3, 17)),
    (("*", col("a"), col("b")), ("DECIMAL64", 5, 23)),
    (("+", col("a"), col("i")), ("DECIMAL64", 2, 23)),
    (("*", col("a"), lit(3)), ("DECIMAL64", 2, 17)),
    (("*", col("i"), col("i")), ("INT64", 0, 0)),
    (("*", col("a"), col("f")), ("FLOAT64", 0, 0)),
    (("+", col("d"), lit(30)), ("TIMESTAMP_DAYS", 0, 0)),
    (("*", ("*", col("a"), col("a")), ("*", col("a"), col("a"))),
     ("DECIMAL64", 8, 38)),
])
def test_spark_result_types(typed, expr, want):
    dt = _type_of(typed, expr)
    assert (dt.id.name, -dt.scale, dt.precision) == want


def test_sum_of_a_decimal_is_p_plus_10(typed):
    schema = verify(Aggregate(Scan(typed), [], [("a", "sum"), ("b", "sum")],
                              names=["sa", "sb"]))
    assert [(dt.precision, -dt.scale) for dt in schema.values()] == [
        (25, 2), (17, 3)]


@pytest.mark.parametrize("expr, code", [
    (("*", col("s"), lit(2)), "arithmetic-over-string"),
    (("+", col("a"), col("s")), "arithmetic-over-string"),
    (("+", col("d"), col("a")), "date-decimal-mix"),
    ((">=", col("d"), lit_decimal("1.5")), "date-decimal-mix"),
    (("*", col("d"), lit(2)), "invalid-arithmetic"),
    (("-", lit(3), col("d")), "invalid-arithmetic"),
    (("<", col("a"), lit(2 ** 62)), "overflow-unsafe-cast"),
])
def test_verifier_codes(typed, expr, code):
    plan = Filter(Scan(typed), expr) if expr[0] in ("<", ">=") \
        else Project(Scan(typed), [("x", expr)])
    with pytest.raises(PlanVerificationError) as e:
        verify(plan)
    assert e.value.code == code


def test_a_string_in_arithmetic_demotes_the_segment(typed):
    """Unverified, a STRING reaching the multiply vetoes the fused program
    (the interpreter then meets it and refuses), and no crash in between."""
    plan = Aggregate(Project(Scan(typed, chunk_bytes=1 << 10),
                             [("x", ("*", col("s"), lit(2)))]), [],
                     [("x", "sum")], names=["x"])
    st = lower(plan, **lowering_flags(), resolver=verify).stages[0]
    assert st.kind == "stream-agg" and st.vetoed
    assert not sg.runtime_eligible(st.segment, read_parquet(typed))
