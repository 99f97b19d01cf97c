"""The fused probe join's two methods (``ops/join.py::probe_method``).

A prepared build of at most ``PROBE_COMPARE_MAX_BUILD`` rows is probed by a
broadcast compare of the keys themselves (``_probe_compare``: no hash, no
sort, no gather); a larger one by the hash merge-rank.  One meaning, two
methods, chosen by the build's row count alone.  These tests hold:

- ops level: the compare path equals the rank path and a plain numpy
  reference — ``(ri where matched, matched)`` — over key dtypes, one and
  two key columns, nulls on either side under both null semantics, dead
  build and probe rows, and both sides of the switch; the payload select
  equals the gather bit for bit;
- the benchmark's two queries at their ``rehearsal_rows``: every join of
  every chunk launch took the compare path (``engine.probe.compare ==
  joins x chunks``, ``engine.probe.rank == 0``), the result equals pandas
  and the rank path's result byte for byte, and the traced chunk program
  holds no ``gather`` and no ``sort`` outside ``groupby_padded``.
"""

import importlib.util
import json
import os
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from spark_rapids_jni_tpu import dtypes as dt
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.ops import join as J
from spark_rapids_jni_tpu.ops.selection import gather_column
from spark_rapids_jni_tpu.utils import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
NAN2 = np.frombuffer(np.uint64(0x7FF8000000000123).tobytes(), np.float64)[0]


# -- a plain reference: every pair, by the SQL rules ---------------------------

def _norm(a):
    """Join-key normalization of a numpy key array: -0.0 = 0.0, all NaNs
    one value (floats); the value itself otherwise."""
    if a.dtype.kind == "f":
        nan = np.isnan(a)
        return np.where(nan | (a == 0.0), 0.0, a), nan
    return a.astype(np.int64), np.zeros(a.shape, bool)


def reference_probe(lcols, rcols, left_live, right_live, null_equal):
    """(first matching build row or -1, matched) for every probe row.
    ``lcols`` / ``rcols``: [(values, valid or None)] per key column."""
    nl, nr = len(lcols[0][0]), len(rcols[0][0])
    eq = np.ones((nl, nr), bool)
    for (lv, lval), (rv, rval) in zip(lcols, rcols):
        (ln, lnan), (rn, rnan) = _norm(lv), _norm(rv)
        e = (ln[:, None] == rn[None, :]) & ~lnan[:, None] & ~rnan[None, :]
        e |= lnan[:, None] & rnan[None, :]
        lval = np.ones(nl, bool) if lval is None else lval
        rval = np.ones(nr, bool) if rval is None else rval
        both = lval[:, None] & rval[None, :]
        if null_equal:
            e = np.where(both, e, lval[:, None] == rval[None, :])
        else:
            e &= both
        eq &= e
    if left_live is not None:
        eq &= left_live[:, None]
    if right_live is not None:
        eq &= right_live[None, :]
    matched = eq.any(axis=1)
    first = eq.argmax(axis=1) if nr else np.zeros(nl, np.int64)
    return np.where(matched, first, -1), matched


def _column(values, valid=None):
    """A key Column of the values' dtype (float64 as stored bits)."""
    return Column.from_numpy(values, validity=valid)


def _probe(lcols, rcols, left_live, right_live, null_equal):
    names = [f"k{i}" for i in range(len(lcols))]
    build = Table([_column(v, m) for v, m in rcols], names)
    pb = J.prepare_build(build, names, right_live=None if right_live is None
                         else jnp.asarray(right_live))
    keys = Table([_column(v, m) for v, m in lcols], names)
    ri, matched = J.probe_join_prepared(
        keys, pb, left_live=None if left_live is None
        else jnp.asarray(left_live), null_equal=null_equal)
    ri, matched = np.asarray(ri), np.asarray(matched)
    return pb, np.where(matched, ri, -1), matched


def _seed(*parts) -> int:
    return zlib.crc32(repr(parts).encode())     # the same in every worker


def _force_rank(monkeypatch, table=True):
    """Every build to the rank probe; ``table=False`` also keeps an exact
    build from its direct-address table (the ``searchsorted`` form)."""
    monkeypatch.setattr(J, "PROBE_COMPARE_MAX_BUILD", -1)
    if not table:
        monkeypatch.setattr(J, "DIRECT_MAX_SLOTS", 0)


def _keys(kind, rng, nl=96, nr=24):
    """(probe values, build values): distinct build keys, probes in and
    out of them; floats carry -0.0, 0.0 and two NaN patterns."""
    if kind == "float64":
        bk = np.concatenate([[0.0, np.nan],
                             rng.permutation(nr - 2) * 0.25 + 1.0])
        lk = np.concatenate([[-0.0, 0.0, np.nan, NAN2, 1e300],
                             rng.choice(bk[2:], nl - 5) + rng.choice(
                                 [0.0, 0.125], nl - 5)])
        return lk, bk
    t = np.dtype(kind)
    bk = (rng.permutation(3 * nr)[:nr] - nr).astype(t)
    lk = rng.integers(-nr, 2 * nr, nl).astype(t)
    if kind == "int64":     # values that differ in the high word only
        bk[:2] = [1 << 40, (1 << 40) + 1]
        lk[:3] = [1 << 40, (1 << 41) + 1, 1]
    return lk, bk


def _masks(nulls, rng, nl, nr):
    lval = rng.random(nl) < 0.8 if nulls in ("left", "both") else None
    rval = None
    if nulls in ("right", "both"):      # ONE null build key: still unique
        rval = np.ones(nr, bool)
        rval[rng.integers(nr)] = False
    return lval, rval


def _one_key_case(kind, nulls, null_equal, table, monkeypatch):
    rng = np.random.default_rng(_seed(kind, nulls))
    lk, bk = _keys(kind, rng)
    lval, rval = _masks(nulls, rng, len(lk), len(bk))
    args = ([(lk, lval)], [(bk, rval)], None, None, null_equal)
    want_ri, want = reference_probe(*args)
    pb, ri, matched = _probe(*args)
    assert pb.unique and J.probe_method(pb.nr, pb.rk.columns) == "compare"
    np.testing.assert_array_equal(matched, want)
    np.testing.assert_array_equal(ri, want_ri)
    _force_rank(monkeypatch, table)
    assert J.probe_method(pb.nr, pb.rk.columns) == "rank"
    pb, ri_rank, matched_rank = _probe(*args)
    # an int32 build spans few keys; the int64 one holds 2**40
    assert (pb.direct is not None) == (table and kind == "int32")
    np.testing.assert_array_equal(matched_rank, matched)
    np.testing.assert_array_equal(ri_rank, ri)


@pytest.mark.parametrize("null_equal", [False, True], ids=["sql", "nullsafe"])
@pytest.mark.parametrize("nulls", ["none", "left", "right", "both"])
@pytest.mark.parametrize("kind", ["int32", "int64", "float64"])
def test_one_key_compare_equals_rank_and_reference(kind, nulls, null_equal,
                                                   monkeypatch):
    _one_key_case(kind, nulls, null_equal, True, monkeypatch)


@pytest.mark.parametrize("null_equal", [False, True], ids=["sql", "nullsafe"])
@pytest.mark.parametrize("nulls", ["none", "left", "right", "both"])
@pytest.mark.parametrize("kind", ["int32", "int64", "float64"])
def test_one_key_compare_equals_searchsorted(kind, nulls, null_equal,
                                             monkeypatch):
    """The same, the direct-address table forced off."""
    _one_key_case(kind, nulls, null_equal, False, monkeypatch)


@pytest.mark.parametrize("null_equal", [False, True], ids=["sql", "nullsafe"])
@pytest.mark.parametrize("live", ["all", "dead_build", "dead_probe", "both"])
@pytest.mark.parametrize("kinds", [("int64", "int32"), ("float64", "int64")],
                         ids=["i64_i32", "f64_i64"])
def test_two_keys_and_dead_rows(kinds, live, null_equal, monkeypatch):
    rng = np.random.default_rng(_seed(kinds, live))
    nl, nr = 128, 20
    l0, b0 = _keys(kinds[0], rng, nl, nr)
    # second key column: few values, so a pair matches only on BOTH columns
    b1 = (np.arange(nr) % 3).astype(kinds[1])
    l1 = rng.integers(0, 4, nl).astype(kinds[1])
    lval, rval = _masks("both", rng, nl, nr)
    left_live = rng.random(nl) < 0.7 if live in ("dead_probe", "both") \
        else None
    right_live = rng.random(nr) < 0.6 if live in ("dead_build", "both") \
        else None
    args = ([(l0, None), (l1, lval)], [(b0, None), (b1, rval)],
            left_live, right_live, null_equal)
    want_ri, want = reference_probe(*args)
    assert want.any() and not want.all()
    pb, ri, matched = _probe(*args)
    assert pb.unique
    np.testing.assert_array_equal(matched, want)
    np.testing.assert_array_equal(ri, want_ri)
    _force_rank(monkeypatch)
    _, ri_rank, matched_rank = _probe(*args)
    np.testing.assert_array_equal(matched_rank, matched)
    np.testing.assert_array_equal(ri_rank, ri)


_SWITCH = pytest.mark.parametrize(
    "nr", [0, 1, J.PROBE_COMPARE_MAX_BUILD, J.PROBE_COMPARE_MAX_BUILD + 1],
    ids=["empty", "one", "at_constant", "above_constant"])


@_SWITCH
def test_both_sides_of_the_switch(nr):
    """The choice reads the build's row count: at the constant the compare
    path runs, one row above it the rank probe by its direct-address
    table — same answer."""
    _switch_case(nr, True)


@_SWITCH
def test_both_sides_of_the_switch_searchsorted(nr, monkeypatch):
    """The same, the table forced off: one row above the constant the rank
    probe is ``searchsorted``."""
    monkeypatch.setattr(J, "DIRECT_MAX_SLOTS", 0)
    _switch_case(nr, False)


def _switch_case(nr, table):
    rng = np.random.default_rng(nr)
    bk = rng.permutation(2 * nr + 2)[:nr].astype(np.int64)
    lk = rng.integers(0, 2 * nr + 2, 64).astype(np.int64)
    args = ([(lk, None)], [(bk, None)], None, None, False)
    pb, ri, matched = _probe(*args)
    want = "compare" if nr <= J.PROBE_COMPARE_MAX_BUILD else "rank"
    assert J.probe_method(pb.nr, pb.rk.columns) == want
    assert (pb.direct is not None) == (want == "rank" and table)
    text = str(jax.make_jaxpr(J.probe_join_prepared)(
        Table([_column(lk)], ["k0"]), pb))
    # the rank probe gathers; only ``searchsorted`` loops
    assert ("gather" in text) == (want == "rank" and nr > 0)
    assert ("searchsorted" in text) == (want == "rank" and not table
                                        and nr > 0)
    assert pb.unique    # else the engine's veto, not the probe, answers
    want_ri, want_matched = reference_probe(*args)
    np.testing.assert_array_equal(matched, want_matched)
    np.testing.assert_array_equal(ri, want_ri)


# -- the direct-address table of an exact build ----------------------------------

def _pandas_pairs(lk, lval, bk, right_live) -> set:
    """(probe row, build row) of every SQL match, by ``DataFrame.merge``."""
    left = pd.DataFrame({"k": lk, "l": np.arange(len(lk))})
    right = pd.DataFrame({"k": bk, "r": np.arange(len(bk))})
    if lval is not None:
        left = left[lval]
    if right_live is not None:
        right = right[right_live]
    j = left.merge(right, on="k")
    return set(zip(j.l.tolist(), j.r.tolist()))


@pytest.mark.parametrize("table", [True, False], ids=["table", "no_table"])
@pytest.mark.parametrize("nulls", ["none", "left", "right", "both"])
@pytest.mark.parametrize("dead", [False, True], ids=["live", "dead_build"])
@pytest.mark.parametrize("kind", ["int32", "int64"])
def test_direct_table_equals_searchsorted_and_pandas(kind, dead, nulls,
                                                     table, monkeypatch):
    """The rank probe of an exact build, the table forced on and off:
    negative keys (int64 ones far from zero, so the offset from ``kmin``
    is 64-bit arithmetic), probe keys below the smallest build key and
    above the largest (the dtype's extremes among them), nulls on either
    side, and dead build rows — one of them holding a live row's key — give
    pandas' pairs and the reference's rows either way."""
    _force_rank(monkeypatch, table)
    rng = np.random.default_rng(_seed("direct", kind, dead, nulls))
    nl, nr = 2_000, 300
    base = -(1 << 36) if kind == "int64" else 0
    bk = (rng.permutation(1_000)[:nr] - 500 + base).astype(kind)
    lk = (rng.integers(-700, 700, nl) + base).astype(kind)
    info = np.iinfo(kind)
    lk[:2] = [info.min, info.max]
    lval, rval = _masks(nulls, rng, nl, nr)
    right_live = None
    if dead:
        right_live = rng.random(nr) < 0.8
        bk[-1], right_live[-1], right_live[0] = bk[0], False, True
    args = ([(lk, lval)], [(bk, rval)], None, right_live, False)
    pb, ri, matched = _probe(*args)
    assert pb.unique and (pb.direct is not None) == table
    want_ri, want = reference_probe(*args)
    np.testing.assert_array_equal(matched, want)
    np.testing.assert_array_equal(ri, want_ri)
    keep = right_live if rval is None else \
        rval if right_live is None else rval & right_live
    assert want.any() and set(zip(np.flatnonzero(matched).tolist(),
                                  ri[matched].tolist())) \
        == _pandas_pairs(lk, lval, bk, keep)


@pytest.mark.parametrize("span, table", [(64, True), (65, False)],
                         ids=["at_cap", "above_cap"])
def test_a_span_at_the_cap_takes_the_table(span, table, monkeypatch):
    """A build whose live keys span ``DIRECT_MAX_SLOTS`` gets a table of
    that many slots; one key further, ``searchsorted`` — same answer."""
    _force_rank(monkeypatch)
    monkeypatch.setattr(J, "DIRECT_MAX_SLOTS", 64)
    bk = np.concatenate([[7, 7 + span - 1], np.arange(8, 40)])
    lk = np.arange(0, 80, dtype=np.int64)
    args = ([(lk, None)], [(bk.astype(np.int64), None)], None, None, False)
    pb, ri, matched = _probe(*args)
    assert pb.unique
    assert (None if pb.direct is None else pb.direct.shape) \
        == ((64,) if table else None)
    want_ri, want = reference_probe(*args)
    np.testing.assert_array_equal(matched, want)
    np.testing.assert_array_equal(ri, want_ri)


@pytest.mark.parametrize("twin_live", [True, False],
                         ids=["duplicate", "duplicate_dead"])
def test_a_duplicated_live_key_builds_no_table(twin_live, monkeypatch):
    """Two live rows of one key: not unique, so no table (the engine's veto
    answers); the same key on a dead row leaves the build unique and the
    table the live row's."""
    _force_rank(monkeypatch)
    bk = np.arange(50, dtype=np.int64)
    bk[-1] = bk[3]
    live = np.ones(50, bool)
    live[-1] = twin_live
    pb = J.prepare_build(Table([_column(bk)], ["k0"]), ["k0"],
                         right_live=jnp.asarray(live))
    assert pb.unique != twin_live
    assert (pb.direct is None) == twin_live
    if not twin_live:
        assert int(np.asarray(pb.direct)[3]) == 3


def test_only_fixed_width_keys_take_the_compare_path():
    assert J.probe_method(2, [Column.from_pylist(["a", "b"])]) == "rank"
    assert J.probe_method(2, [_column(np.arange(2))]) == "compare"


# -- the payload select ----------------------------------------------------------

I64 = np.iinfo(np.int64)
PAYLOADS = {
    "int64_extremes": (np.array([I64.min, I64.max, 0, -1, 1 << 32, 7],
                                np.int64), None),
    "float64_bits": (np.array([-0.0, 0.0, np.nan, NAN2, np.inf, 5e-324]),
                     None),
    "nullable_int64": (np.array([5, I64.min, 6, I64.max, 8, 9], np.int64),
                       np.array([1, 0, 1, 1, 0, 1], bool)),
    "int32": (np.array([-2**31, 2**31 - 1, 0, -1, 3, 4], np.int32), None),
    "int16": (np.array([-2**15, 2**15 - 1, 0, -1, 3, 4], np.int16), None),
    "int8": (np.array([-128, 127, 0, -1, 3, 4], np.int8), None),
    "float32": (np.array([-0.0, np.nan, 1.5, -np.inf, 3e-40, 4], np.float32),
                None),
    "bool": (np.array([1, 0, 1, 1, 0, 0], bool),
             np.array([1, 1, 0, 1, 1, 0], bool)),
}


@pytest.mark.parametrize("name", sorted(PAYLOADS))
def test_payload_select_equals_gather_bit_for_bit(name):
    values, valid = PAYLOADS[name]
    col = _column(values, valid)
    ri = jnp.asarray([5, 0, 1, 1, 4, 3, 2, 0, 6, -1], jnp.int32)
    got = jax.jit(J.select_build_rows)(col, ri)
    want = gather_column(col, ri)
    assert got.dtype == want.dtype and got.data.dtype == want.data.dtype
    gv, wv = np.asarray(got.validity), np.asarray(want.validity)
    np.testing.assert_array_equal(gv, wv)
    assert not gv[-2:].any()            # out of the column: null, as gathered
    assert np.asarray(got.data)[wv].tobytes() \
        == np.asarray(want.data)[wv].tobytes()
    inside = np.asarray(ri)[:8]         # every inside row, null ones too
    assert np.asarray(got.data)[:8].tobytes() \
        == np.asarray(col.data)[inside].tobytes()


def test_inner_probe_with_payload_equals_pandas(monkeypatch):
    """What ``engine/segment.py::_probe_join_node`` does for an inner join:
    probe, then carry a payload column — against ``DataFrame.merge``."""
    rng = np.random.default_rng(11)
    bk = rng.permutation(400)[:180].astype(np.int64)
    pay = rng.integers(I64.min, I64.max, 180).astype(np.int64)
    lk = rng.integers(0, 400, 1_000).astype(np.int64)
    merged = pd.DataFrame({"k": lk, "row": np.arange(lk.size)}).merge(
        pd.DataFrame({"k": bk, "p": pay}), on="k").sort_values("row")
    build = Table([_column(bk), _column(pay)], ["k", "p"])
    outs = []
    for method in ("compare", "rank"):
        if method == "rank":
            _force_rank(monkeypatch)
        pb = J.prepare_build(build, ["k"])
        ri, matched = J.probe_join_prepared(Table([_column(lk)], ["k"]), pb)
        pcol = J.select_build_rows(build.column("p"), ri) \
            if method == "compare" else gather_column(build.column("p"), ri)
        matched = np.asarray(matched)
        np.testing.assert_array_equal(np.flatnonzero(matched),
                                      merged.row.to_numpy())
        got = np.asarray(pcol.data)[matched]
        assert got.tobytes() == merged.p.to_numpy().tobytes()
        assert np.asarray(pcol.validity)[matched].all()
        outs.append(got)
    assert outs[0].tobytes() == outs[1].tobytes()


# -- the benchmark's two queries at their rehearsal size --------------------------

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


CELLS = {"q5lite": ("nds_q5lite_sf1", "year", 1, "dense/16"),
         "q55lite": ("nds_q55lite_sf1", "nov1999", 2, "sorted")}
CHUNK_BYTES = 1 << 20       # two 20,000-row groups a chunk: several chunks


def _run_plan(plan):
    """(result Table, stats, growth of the two probe counters, recorded
    chunk-program calls, the chunk program's gathers and sorts outside the
    group-by) of one cold execution of ``plan``."""
    from spark_rapids_jni_tpu.engine import (BUILD_CACHE, execute, new_stats,
                                             optimize)
    from spark_rapids_jni_tpu.engine import segment as sg
    sg.SEGMENT_CACHE.clear()
    BUILD_CACHE.clear()
    calls = []
    launch = sg.CompiledSegment._launch

    def recording(self, *args):
        if self.probes:
            calls.append((self, args))
        return launch(self, *args)

    before = tracing.counters_snapshot("engine.probe.")
    sg.CompiledSegment._launch = recording
    try:
        stats = new_stats()
        out = execute(optimize(plan), stats=stats, fused=True)
    finally:
        sg.CompiledSegment._launch = launch
    after = tracing.counters_snapshot("engine.probe.")
    grew = {k: after.get(f"engine.probe.{k}", 0)
            - before.get(f"engine.probe.{k}", 0) for k in ("compare", "rank")}
    # the first chunk program, traced again as it was launched
    compiled, args = calls[0]
    jaxpr = jax.make_jaxpr(sg._build_fn(compiled.segment, compiled))(
        *args).jaxpr
    assert jaxpr.eqns, "nothing traced"
    return out, stats, grew, calls, _outside_groupby(jaxpr,
                                                     {"gather", "sort"})


@pytest.fixture(scope="module", params=sorted(CELLS))
def rehearsal(request, tmp_path_factory):
    import pyarrow as pa
    import pyarrow.parquet as pq
    config_name, traffic, joins, agg = CELLS[request.param]
    config = _json("configs", config_name + ".json")
    params = _json("traffic", traffic + ".json")["params"]
    query = _load(os.path.join(BENCH, "queries", config["query"] + ".py"),
                  "probetest_" + config["query"])
    root = str(tmp_path_factory.mktemp(request.param))
    rows = {t: spec["rows"] for t, spec in config["tables"].items()}
    rows[query.FACT] = config["rehearsal_rows"][query.FACT]
    frames = query.tables(2147483901, rows)
    paths = {}
    for name, df in frames.items():
        paths[name] = os.path.join(root, f"{name}.parquet")
        pq.write_table(
            pa.Table.from_pandas(df, preserve_index=False), paths[name],
            compression=config["storage"]["compression"],
            row_group_size=-(-len(df) // config["tables"][name]["row_groups"]))
    plan = query.plan(paths, params, CHUNK_BYTES)
    compare = _run_plan(plan)
    mp = pytest.MonkeyPatch()
    try:
        _force_rank(mp)
        rank = _run_plan(plan)
    finally:
        mp.undo()
    return {"want": query.reference(frames, params), "joins": joins,
            "agg": agg, "compare": compare, "rank": rank}


def _columns_bytes(t: Table) -> list:
    return [(np.asarray(c.data).tobytes(),
             None if c.validity is None else np.asarray(c.validity).tobytes())
            for c in t.columns]


def test_rehearsal_every_join_of_every_chunk_took_the_compare_path(rehearsal):
    out, stats, grew, calls, _ = rehearsal["compare"]
    assert stats["streamed"] and stats["fused_segments"] == 1
    assert stats["chunks"] > 1
    assert grew == {"compare": rehearsal["joins"] * stats["chunks"],
                    "rank": 0}
    assert len(calls) == stats["chunks"]
    compiled = calls[0][0]
    assert compiled.probes == ("compare",) * rehearsal["joins"]
    assert compiled.span_stats() == {
        "probe": f"{rehearsal['joins']}/0/{rehearsal['joins']}/0",
        "exprs": compiled.segment.exprs(),     # the stats beside it
        "agg": rehearsal["agg"]}
    # the forced merge-rank run counts the other way: compare + rank is
    # joins x chunks either way
    _, rstats, rgrew, _, _ = rehearsal["rank"]
    assert rgrew == {"compare": 0,
                     "rank": rehearsal["joins"] * rstats["chunks"]}


def test_rehearsal_result_equals_pandas_and_the_rank_path(rehearsal):
    out, want = rehearsal["compare"][0], rehearsal["want"]
    assert out.num_rows == len(want) > 0
    for name, c in zip(want.columns, out.columns):
        assert c.validity is None or np.asarray(c.validity).all()
        got = np.asarray(c.data)
        if c.dtype.id == dt.TypeId.FLOAT64:
            got = got.view(np.float64)
        assert got.tobytes() == want[name].to_numpy().tobytes(), name
    assert _columns_bytes(out) == _columns_bytes(rehearsal["rank"][0])


def _outside_groupby(jaxpr, prims, stack=""):
    """Names of ``prims`` equations outside a ``groupby_padded`` scope."""
    found = []
    for eqn in jaxpr.eqns:
        here = f"{stack}/{eqn.source_info.name_stack}"
        if eqn.primitive.name in prims and "groupby_padded" not in here:
            found.append(f"{eqn.primitive.name} @ {here}")
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _outside_groupby(sub, prims, here)
    return found


def test_rehearsal_chunk_program_has_no_gather_or_sort_outside_groupby(
        rehearsal):
    """Structure, no chip: on the compare path the chunk program's only
    gathers and sorts are the group-by's; on the rank path the probe's own
    gathers are there (so the walker sees what it is asked to see), and no
    sort: each build is keyed by one integer column, whose keys the probe
    looks up in its direct-address table."""
    assert rehearsal["compare"][4] == []
    outside = rehearsal["rank"][4]
    assert any(o.startswith("gather") for o in outside)
    assert not any(o.startswith("sort") for o in outside)
