"""The dense form of the chunk program's keyed aggregate
(``ops/aggregate.py::groupby_dense``) and what chooses it
(``engine/segment.py::agg_domain``).

- ops level: over nullable keys and values, dead and single rows, keys at
  both ends of the range and outside it (the in-program guard takes the sort
  form), float64 ``-0.0`` / NaN / ±inf, int64 and decimal sums near int64's
  ends, ``count`` / ``count_all`` / ``mean`` and narrow key types, the
  dense form's live groups equal the sort form's (``groupby_padded``) bit
  for bit — with the guard, and alone (the guard's branch forced), where
  the keys lie in the range;
- the choice: one integer key of the streamed file with footer statistics
  over a range of at most ``DENSE_MAX_GROUPS`` slots, and ``DENSE_OPS``;
  everything else keeps the sort form;
- the benchmark's two queries at their ``rehearsal_rows`` and the cells'
  chunking: per keyed chunk launch exactly one of ``engine.agg.dense`` /
  ``engine.agg.sorted`` grows (q5-lite: dense 11 a query, q55-lite: sorted
  12), results equal pandas, and a second seed compiles nothing;
- a decimal sum past the checked bound still fails the query in the dense
  form; the benchmark's ``agg_dense_pct`` reader on known inputs.
"""

import importlib.util
import json
import os
import sys
import zlib
from decimal import Decimal

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_jni_tpu import dtypes as dt
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.engine import (Aggregate, Filter, Project, Scan,
                                         col, execute, lit, new_stats,
                                         optimize)
from spark_rapids_jni_tpu.engine import segment as sg
from spark_rapids_jni_tpu.ops import aggregate as A
from spark_rapids_jni_tpu.utils import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
I64 = np.iinfo(np.int64)
NAN = np.float64(np.nan)


# -- ops level: the dense form equals the sort form ---------------------------

def _f64(values):
    return np.asarray(values, np.float64)


def _case(name):
    """(key Column, value Columns by name, aggs, live, lo, slots)."""
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    n = 96
    live = np.ones(n, bool)
    keys = rng.integers(100, 116, n).astype(np.int64)
    kvalid = None
    v = rng.integers(-4096, 4096, n) / 64.0          # dyadic: sums exact
    vvalid = None
    w = rng.integers(-10**6, 10**6, n).astype(np.int64)
    lo, slots = 100, 16
    aggs = [("v", "sum"), ("v", "count"), (None, "count_all"), ("v", "mean"),
            ("w", "sum"), ("w", "mean")]
    if name == "null_keys_and_values":
        kvalid = rng.random(n) < 0.8
        keys[~kvalid] = rng.integers(-50, 50, (~kvalid).sum())  # any bytes
        vvalid = rng.random(n) < 0.85
        live = rng.random(n) < 0.9
    elif name == "all_dead":
        kvalid = rng.random(n) < 0.8
        live[:] = False
    elif name == "one_live_row":
        live[:] = False
        live[37] = True
    elif name == "keys_at_both_ends":
        keys = rng.choice([100, 101, 114, 115], n).astype(np.int64)
    elif name == "key_above_range":
        keys[11] = 116
    elif name == "key_below_range":
        keys[5] = 99
        keys[6] = I64.min           # k - lo wraps: must not pass for a slot
    elif name == "dead_rows_outside_range":
        live = rng.random(n) < 0.7
        keys[~live] = I64.max       # only live keys are held to the range
    elif name == "float_specials":
        keys = np.repeat(np.arange(100, 112), 8)
        v = np.tile(_f64([1.5, -2.25, 0.5, 4.0, -0.125, 8.0, 0.0, 3.0]), 12)
        v[0:8] = -0.0                           # -0.0 survives
        v[8:16] = -0.0
        vvalid = np.ones(n, bool)
        vvalid[9] = False                       # a null adds +0.0
        v[16] = NAN
        v[24] = np.inf
        v[32] = -np.inf
        v[40], v[41] = np.inf, -np.inf          # NaN
        v[48:56] = 5e-324                       # subnormals
        v[56] = 1e308
        v[57] = 1e308                           # overflows to inf
        w = np.arange(n, dtype=np.int64)
    elif name == "int64_near_the_ends":
        w = rng.choice([I64.max - 3, I64.min + 5, 1 << 62, -(1 << 62)],
                       n).astype(np.int64)      # sums wrap alike
    elif name == "int32_key":
        keys = (keys - 100 + (1 << 30)).astype(np.int32)
        lo = 1 << 30
    elif name == "uint8_key_null_first":
        keys = rng.integers(0, 8, n).astype(np.uint8)
        kvalid = rng.random(n) < 0.5
        lo, slots = 0, 8
    elif name == "int16_negative_keys":
        keys = rng.integers(-40, -8, n).astype(np.int16)
        lo, slots = -40, 32
    elif name != "no_nulls":
        raise KeyError(name)
    key = Column.from_numpy(keys, validity=kvalid)
    values = {"v": Column.from_numpy(v, validity=vvalid),
              "w": Column.from_numpy(w)}
    return key, values, aggs, live, lo, slots


def _decimal_case():
    """A DECIMAL64 column: its sum keeps the type, its mean is a double."""
    rng = np.random.default_rng(5)
    n = 64
    keys = rng.integers(7, 11, n).astype(np.int64)
    units = rng.choice([I64.max // 8, -(I64.max // 8), 12345], n)
    dec = Column(dt.decimal64(-2, 18), data=jnp.asarray(units, jnp.int64))
    return (Column.from_numpy(keys), {"d": dec},
            [("d", "sum"), ("d", "mean"), ("d", "count")],
            np.ones(n, bool), 7, 4)


CASES = ["no_nulls", "null_keys_and_values", "all_dead", "one_live_row",
         "keys_at_both_ends", "key_above_range", "key_below_range",
         "dead_rows_outside_range", "float_specials", "int64_near_the_ends",
         "int32_key", "uint8_key_null_first", "int16_negative_keys",
         "decimal64"]
OUTSIDE = {"key_above_range", "key_below_range"}


def _groupby(form, key, values, aggs, live, lo, slots):
    """The live groups of one form as bytes: (ngroups, [arrays])."""
    table = Table([key] + list(values.values()), ["k"] + list(values))

    def run(t, live, lo):
        if form == "sorted":
            keys, out, ng = A.groupby_padded(t, ["k"], aggs, row_mask=live)
        else:
            keys, out, ng = A.groupby_dense(t, ["k"], aggs, lo, slots,
                                            row_mask=live)
        return keys[0][2:], out, ng

    (kdat, kval), out, ng = jax.jit(run)(table, live,
                                         np.asarray(lo, np.int64))
    ng = int(ng)
    arrays = [kdat, kval] + [a for c in out
                             for a in (c.data, c.validity) if a is not None]
    return ng, [np.asarray(a)[:ng].tobytes() for a in arrays], \
        [(c.dtype, c.validity is None) for c in out]


@pytest.mark.parametrize("case", CASES)
def test_dense_equals_the_sort_form_bit_for_bit(case, monkeypatch):
    args = _decimal_case() if case == "decimal64" else _case(case)
    want = _groupby("sorted", *args)
    assert _groupby("dense", *args) == want
    # the dense form alone (the guard's branch forced): the same answer
    # where every live key lies in the range, another one where not
    monkeypatch.setattr(jax.lax, "cond", lambda pred, dense, sort: dense())
    alone = _groupby("dense", *args)
    assert (alone == want) == (case not in OUTSIDE)


def test_the_float_specials_are_what_they_should_be():
    """The sort form's answers that the dense form equals: -0.0 only where
    every row of the group is -0.0; NaN, ±inf as IEEE sums them."""
    key, values, aggs, live, lo, slots = _case("float_specials")
    table = Table([key, values["v"]], ["k", "v"])
    _, out, ng = A.groupby_dense(table, ["k"], [("v", "sum")], np.int64(lo),
                                 slots, row_mask=live)
    s = np.asarray(out[0].data)[:int(ng)].view(np.float64)
    assert np.signbit(s[0]) and s[0] == 0.0          # all -0.0
    assert not np.signbit(s[1]) and s[1] == 0.0      # -0.0 and a null
    assert np.isnan(s[2]) and np.isposinf(s[3]) and np.isneginf(s[4])
    assert np.isnan(s[5]) and np.isposinf(s[7])
    assert s[6] in (0.0, 8 * 5e-324)    # subnormals, where not flushed


# -- the choice: what the host compiles ----------------------------------------

@pytest.mark.parametrize("lo,hi,want", [
    (1, 12, 16), (1, 102, 128), (5, 5, 1), (0, A.DENSE_MAX_GROUPS - 1,
                                            A.DENSE_MAX_GROUPS),
    (0, A.DENSE_MAX_GROUPS, None), (1, 1000, None), (3, 2, None)])
def test_dense_slots(lo, hi, want):
    assert A.dense_slots(lo, hi) == want


def _file(tmp_path, name, **columns):
    path = str(tmp_path / f"{name}.parquet")
    pq.write_table(pa.table(columns), path, row_group_size=64)
    return path


def _domain(plan, path, columns=None):
    from spark_rapids_jni_tpu.io.parquet import ParquetFile
    agg = plan
    scan = agg
    while not isinstance(scan, Scan):
        scan = scan.child
    seg = sg.build_stream_segment(agg, scan, sg.parent_counts(agg))
    f = ParquetFile(path)
    return sg.agg_domain(seg, f, list(range(f.num_row_groups)), columns)


def test_what_chooses_the_dense_form(tmp_path):
    rng = np.random.default_rng(3)
    n = 256
    k = rng.integers(40, 52, n)
    cols = dict(k=pa.array(k, pa.int64()), k2=pa.array(k * 97, pa.int64()),
                kf=pa.array(k * 0.5, pa.float64()),
                ks=pa.array([str(x) for x in k]),
                v=pa.array(rng.random(n), pa.float64()))
    path = _file(tmp_path, "choice", **cols)
    nostats = str(tmp_path / "nostats.parquet")
    pq.write_table(pa.table(cols), nostats, row_group_size=64,
                   write_statistics=False)
    sums = [("v", "sum"), ("v", "count"), (None, "count_all"), ("v", "mean")]

    def agg(keys, aggs=sums, child=None):
        return Aggregate(child or Scan(path, chunk_bytes=1 << 12), keys,
                         aggs, names=[f"a{i}" for i in range(len(aggs))])

    assert _domain(agg(["k"]), path) == (40, 16)
    filtered = agg(["k"], child=Filter(Scan(path, chunk_bytes=1 << 12),
                                       (">", col("v"), lit(0.5))))
    assert _domain(filtered, path) == (40, 16)
    passed = agg(["k"], child=Project(Scan(path, chunk_bytes=1 << 12),
                                      ["k", "v"]))
    assert _domain(passed, path) == (40, 16)
    # the sort form stays: a wide range, two keys, a float or string key,
    # another aggregation, a key computed in the chain, no statistics, a
    # key the scan does not read
    assert _domain(agg(["k2"]), path) is None
    assert _domain(agg(["k", "k2"]), path) is None
    assert _domain(agg(["kf"]), path) is None
    assert _domain(agg(["ks"], [(None, "count_all")]), path) is None
    assert _domain(agg(["k"], [("v", "max")]), path) is None
    computed = agg(["k"], child=Project(Scan(path, chunk_bytes=1 << 12),
                                        [("k", ("+", col("k"), lit(1))),
                                         "v"]))
    assert _domain(computed, path) is None
    assert _domain(agg(["k"]), nostats) is None
    assert _domain(agg(["k"]), path, columns=["v"]) is None


# -- the benchmark's queries at their rehearsal rows -----------------------------

def _load(path, name):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


#: cell -> (configuration, traffic, chunk launches a query, the form)
CELLS = {"q5lite_sf1_year": ("nds_q5lite_sf1", "year", 11, "dense"),
         "q55lite_sf1_nov1999": ("nds_q55lite_sf1", "nov1999", 12, "sorted")}


def _query(cell, seed, root):
    """(result, the reference, stats, the query's counters) of one
    execution of the cell's plan at its ``rehearsal_rows``, chunked as the
    cell chunks it (one row group a chunk)."""
    config_name, traffic, _, _ = CELLS[cell]
    config = _json("configs", config_name + ".json")
    params = _json("traffic", traffic + ".json")["params"]
    query = _load(os.path.join(BENCH, "queries", config["query"] + ".py"),
                  "densetest_" + config["query"])
    rows = {t: spec["rows"] for t, spec in config["tables"].items()}
    rows[query.FACT] = config["rehearsal_rows"][query.FACT]
    frames = query.tables(seed, rows)
    paths = {}
    for name, df in frames.items():
        paths[name] = os.path.join(root, f"{name}.{seed}.parquet")
        pq.write_table(
            pa.Table.from_pandas(df, preserve_index=False), paths[name],
            compression=config["storage"]["compression"],
            row_group_size=-(-len(df) // config["tables"][name]["row_groups"]))
    plan = query.plan(paths, params, config["storage"]["chunk_bytes"])
    stats = new_stats()
    with metrics.query(f"dense-{cell}-{seed}") as qm:
        out = execute(optimize(plan), stats=stats)
    return out, query.reference(frames, params), stats, dict(qm.counters)


@pytest.fixture(scope="module", params=sorted(CELLS))
def rehearsal(request, tmp_path_factory):
    sg.SEGMENT_CACHE.clear()
    root = str(tmp_path_factory.mktemp(request.param))
    return request.param, [_query(request.param, seed, root)
                           for seed in (2147483911, 3000000007)]


def test_rehearsal_counts_one_form_per_keyed_chunk_launch(rehearsal):
    cell, runs = rehearsal
    _, _, launches, form = CELLS[cell]
    other = "sorted" if form == "dense" else "dense"
    for out, want, stats, c in runs:
        assert stats["chunks"] == launches
        assert c.get(f"engine.agg.{form}", 0) == launches
        assert c.get(f"engine.agg.{other}", 0) == 0
        assert c["engine.segment.replay"] \
            + c.get("engine.segment.compile", 0) == launches
        assert out.num_rows == len(want) > 0
        for name, column in zip(want.columns, out.columns):
            got = np.asarray(column.data)
            if column.dtype.id == dt.TypeId.FLOAT64:
                got = got.view(np.float64)
            assert got.tobytes() == want[name].to_numpy().tobytes(), name


def test_rehearsal_second_seed_compiles_nothing(rehearsal):
    """The key's range is a runtime scalar: another seed (other keys and
    prices, the same domain) finds every program compiled."""
    _, runs = rehearsal
    c = runs[1][3]
    assert c.get("engine.segment.compile", 0) == 0
    assert c.get("engine.segment_cache.miss", 0) == 0


# -- a decimal sum past the checked bound still fails ------------------------------

def test_dense_decimal_sum_still_checks_the_bound(tmp_path):
    from spark_rapids_jni_tpu.utils.errors import DecimalOverflowError
    n = 64
    keys = pa.array(np.arange(n) % 4 + 1, pa.int64())

    def path(units, name):
        return _file(tmp_path, name, k=keys, p=pa.array(
            [Decimal(int(u)).scaleb(-2) for u in units], pa.decimal128(18, 2)))

    def plan(p):
        return Aggregate(Scan(p, chunk_bytes=1 << 10), ["k"],
                         [("p", "sum")], names=["s"])

    big = path(np.full(n, 10 ** 18 // 2), "big")
    assert _domain(plan(big), big) == (1, 4)
    with metrics.query("dense-ovf") as qm:
        with pytest.raises(DecimalOverflowError, match="decimal-overflow"):
            execute(optimize(plan(big)))
    assert qm.counters.get("engine.agg.dense", 0) > 0
    ok = path(np.full(n, 10 ** 12), "ok")
    out = execute(optimize(plan(ok)))
    assert [int(x) for x in np.asarray(out.columns[1].data)] \
        == [16 * 10 ** 12] * 4


# -- the benchmark's reader ---------------------------------------------------------

def _reader():
    spec = importlib.util.spec_from_file_location(
        "bench_agg_dense_pct",
        os.path.join(BENCH, "layer_metrics", "agg_dense_pct.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("start,end,want", [
    ({}, {"engine.agg.dense": 11}, 100.0),
    ({"engine.agg.sorted": 4}, {"engine.agg.sorted": 16}, 0.0),
    ({"engine.agg.dense": 2}, {"engine.agg.dense": 5,
                               "engine.agg.sorted": 1}, 75.0),
    ({}, {"engine.expr.fused": 240}, None),     # keyless: nothing to read
    ({"engine.agg.dense": 3}, {"engine.agg.dense": 3}, None)])
def test_reader_agg_dense_pct(start, end, want):
    ctx = {"snap_start": {"counters": start}, "snap_end": {"counters": end}}
    assert _reader().read(ctx) == want


def test_reader_lists_the_keyed_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == "agg_dense_pct"]
    # Q6 has no group key; Q3's keys come from its build, so its chunk
    # program always takes the sorted form and the reader does not list it
    keyed = [w["name"] for w in bench["workloads"]
             if w["config"] not in ("tpch_q6_sf1", "tpch_q3_sf1")]
    assert entry["workloads"] == keyed
