"""The merge of the streamed partial aggregates as ONE compiled program.

``engine/segment.py::combine_partials`` used to cut, concatenate and
group the padded per-chunk partials op by op (some 450 eager launches per
query on the chip); now everything between its two host syncs is one
cached program (``CompiledCombine`` in ``SEGMENT_CACHE``).  These tests
hold it to the eager merge it replaced — kept below as the reference —
bit for bit, and pin the counts the benchmark's metrics rest on: one
trace per bucket of the partial count, ``engine.combine.*`` beside an
untouched ``engine.segment.*``, two syncs with their labels, and nothing
compiled by a second execution.
"""

import ast
import os

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.engine import (Aggregate, Filter, Scan, col,
                                         execute, lit, new_stats, optimize)
from spark_rapids_jni_tpu.engine import segment as sg
from spark_rapids_jni_tpu.engine.plan import STREAM_COMBINE
from spark_rapids_jni_tpu.ops import aggregate as agg_ops
from spark_rapids_jni_tpu.utils import config as cfg
from spark_rapids_jni_tpu.utils import metrics, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ALL_AGGS = [("f", "sum"), ("i", "sum"), ("f", "count"), (None, "count_all"),
            ("i", "min"), ("f", "max")]


def eager_merge(partials, compiled):
    """One merge as ``combine_partials`` made it before the merge became a
    program: the reference.  (The syncs' bookkeeping is left out; the
    arithmetic is that commit's, line for line — but for a merged
    partial's live slots, which come from its group count.)"""
    agg = compiled.segment.agg
    nk = len(agg.keys)
    maxng = int(jnp.max(jnp.stack([jnp.asarray(p[4]) for p in partials])))
    cap = 64
    while cap < maxng:
        cap *= 2

    def cut(a):
        return a[:cap] if a.shape[0] > cap else a

    key_cols = [
        Column(compiled.key_dtypes[i],
               data=jnp.concatenate([cut(p[0][i]) for p in partials]),
               validity=jnp.concatenate([cut(p[1][i]) for p in partials]))
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
    live = jnp.concatenate([
        cut(p[3]) if p[3].ndim
        else jnp.arange(min(cap, p[2][0].data.shape[0])) < p[3]
        for p in partials])
    knames = [f"k{i}" for i in range(nk)]
    anames = [f"a{j}" for j in range(len(agg.aggs))]
    merged = Table(key_cols + agg_cols, knames + anames)
    combine = [(anames[j], STREAM_COMBINE[op])
               for j, (_, op) in enumerate(agg.aggs)]
    out_keys, out_aggs, ngroups = agg_ops.groupby_padded(
        merged, knames, combine, row_mask=live)
    kdat = tuple(spec[2] for spec in out_keys)
    kval = tuple(spec[3] for spec in out_keys)
    return kdat, kval, tuple(out_aggs), ngroups


def eager_combine(partials, compiled):
    """The eager reference of ``combine_partials``: one merge of up to 16
    partials; a longer list folds as ``StreamedPartials`` folds a stream
    (16 pending and one more to come: merged into one, which goes first)."""
    agg = compiled.segment.agg
    pending = []
    for p in partials:
        if len(pending) == sg.COMBINE_ARITY:
            kdat, kval, out_aggs, ng = eager_merge(pending, compiled)
            pending = [(kdat, kval, out_aggs, ng, ng)]
        pending.append(p)
    kdat, kval, out_aggs, ngroups = eager_merge(pending, compiled)
    return sg._compact_padded(compiled.key_dtypes, kdat, kval, out_aggs,
                              ngroups, list(agg.keys) + list(agg.names))


def segment_for(keys, aggs):
    root = Aggregate(Filter(Scan("mem"), (">=", col("i"), lit(-50))),
                     list(keys), list(aggs),
                     names=[f"o{j}" for j in range(len(aggs))])
    return sg.build_segment(root, sg.parent_counts(root))


def chunk(rng, rows, null_keys=False, null_vals=False, ngroups=9):
    """One chunk's input table: two int64 keys, a float64 and an int64
    value.  Floats are arbitrary doubles (no grid), so a sum taken in
    another order would differ in its last bits."""
    def maybe_null(data, on):
        valid = rng.random(rows) > 0.2 if on else None
        return Column.from_numpy(data, validity=valid)

    return Table([
        maybe_null(rng.integers(0, ngroups, rows).astype(np.int64),
                   null_keys),
        maybe_null(rng.integers(0, 3, rows).astype(np.int64), null_keys),
        maybe_null(rng.normal(0.0, 1e3, rows), null_vals),
        maybe_null(rng.integers(-100, 100, rows).astype(np.int64),
                   null_vals),
    ], ["k", "k2", "f", "i"])


def make_partials(seg, count, seed, buckets=(128, 256, 32), dead=(),
                  **chunk_kw):
    """``count`` padded partials off the fused chunk program, from chunks
    of the given row buckets in turn (32 is below the combine's smallest
    capacity, so that partial is concatenated uncut).  ``dead`` names the
    chunks without a single live row (``ngroups`` 0)."""
    rng = np.random.default_rng(seed)
    out, compiled = [], None
    for n in range(count):
        rows = buckets[n % len(buckets)]
        table = chunk(rng, rows, **chunk_kw)
        compiled = sg.SEGMENT_CACHE.get(seg, table)
        nvalid = 0 if n in dead else rows - int(rng.integers(0, rows // 4))
        out.append(compiled(table, nvalid))
    return out, compiled


def same_bits(got: Table, want: Table):
    assert got.names == want.names and got.num_rows == want.num_rows
    for name, g, w in zip(got.names, got.columns, want.columns):
        assert g.dtype == w.dtype, name
        gd, wd = np.asarray(g.data), np.asarray(w.data)
        assert gd.dtype == wd.dtype and gd.tobytes() == wd.tobytes(), name
        assert (g.validity is None) == (w.validity is None), name
        if g.validity is not None:
            assert np.array_equal(np.asarray(g.validity),
                                  np.asarray(w.validity)), name


def merge_entries():
    return [c for c in sg.SEGMENT_CACHE._entries.values()
            if isinstance(c, sg.CompiledCombine)]


# -- the compiled merge equals the eager merge, bit for bit ------------------

@pytest.mark.parametrize("nkeys", [1, 2])
@pytest.mark.parametrize("count", [1, 2, 11, 12, 17])
def test_equals_eager_merge(count, nkeys):
    """Every bucket of the partial count (1, 2, 16, 16 partials in the
    program; 17 fold: 16, then the merged one and the 17th), chunks of
    three row buckets, null keys and null values, every combine op at
    once."""
    seg = segment_for(["k", "k2"][:nkeys], ALL_AGGS)
    partials, compiled = make_partials(seg, count, seed=count * 10 + nkeys,
                                       null_keys=True, null_vals=True)
    got = sg.combine_partials(partials, compiled)
    assert got.num_rows > 0
    same_bits(got, eager_combine(partials, compiled))


@pytest.mark.parametrize("agg", ALL_AGGS, ids=lambda a: f"{a[1]}-{a[0]}")
@pytest.mark.parametrize("null_vals", [False, True],
                         ids=["dense", "nullable"])
def test_each_combine_op(agg, null_vals):
    """One op at a time — ``sum`` of float64 and of int64, ``count``,
    ``count_all``, ``min``, ``max`` — over value columns with and without
    nulls: ``count``/``count_all`` partials carry no validity, the others
    do, and a group whose values are all null stays null after the merge."""
    assert agg[1] in STREAM_COMBINE
    seg = segment_for(["k"], [agg])
    partials, compiled = make_partials(seg, 5, seed=3, null_vals=null_vals,
                                       ngroups=40)
    assert (partials[0][2][0].validity is None) \
        == (agg[1] in ("count", "count_all"))
    same_bits(sg.combine_partials(partials, compiled),
              eager_combine(partials, compiled))


@pytest.mark.parametrize("dead", [(0,), (2,), (4,), (0, 1, 2, 3, 4)],
                         ids=["first", "middle", "last", "all"])
def test_all_dead_partial(dead):
    """A chunk the filter emptied hands in ``ngroups`` 0 and no live slot.
    As the LAST partial it is also what the bucket's filler repeats."""
    seg = segment_for(["k", "k2"], ALL_AGGS)
    partials, compiled = make_partials(seg, 5, seed=8, dead=dead,
                                       null_keys=True)
    assert all(int(partials[n][4]) == 0 for n in dead)
    got = sg.combine_partials(partials, compiled)
    assert (got.num_rows == 0) == (len(dead) == 5)
    same_bits(got, eager_combine(partials, compiled))


def test_capacity_follows_the_largest_partial():
    """More than 64 groups in one chunk: the sizing fetch picks the next
    power of two and every partial is cut to it."""
    seg = segment_for(["k"], [("f", "sum"), (None, "count_all")])
    partials, compiled = make_partials(seg, 3, seed=5, buckets=(256, 128),
                                       ngroups=100)
    assert max(int(p[4]) for p in partials) > 64
    sg.SEGMENT_CACHE.clear()
    got = sg.combine_partials(partials, compiled)
    same_bits(got, eager_combine(partials, compiled))
    (merge,) = merge_entries()
    assert merge.key[1][0] == 128


# -- the counts ---------------------------------------------------------------

@pytest.fixture
def counted(metrics_isolation):
    for prefix in ("engine.combine", "engine.segment", "engine.host_sync",
                   "engine.segment_cache", "engine.build_cache",
                   "engine.plan_cache", "engine.fused_stage_cache"):
        metrics_isolation(prefix)
    assert metrics.enabled()
    return tracing.counter_value


def test_one_program_serves_11_and_12_partials(counted):
    seg = segment_for(["k"], ALL_AGGS)
    partials, compiled = make_partials(seg, 12, seed=1, buckets=(128,))
    sg.SEGMENT_CACHE.clear()
    for prefix in ("engine.segment", "engine.segment_cache"):
        tracing.reset_counters(prefix)      # the chunk program's own
    a = sg.combine_partials(partials[:11], compiled)
    b = sg.combine_partials(partials, compiled)
    (merge,) = merge_entries()
    assert (merge.traces, merge.calls) == (1, 2)
    assert counted("engine.combine.compile") == 1
    assert counted("engine.combine.replay") == 1
    assert counted("engine.segment_cache.miss") == 1
    assert counted("engine.segment_cache.hit") == 1
    # the merge's launches are not the chunk program's
    assert counted("engine.segment.compile") == 0
    assert counted("engine.segment.replay") == 0
    h = metrics.histograms_snapshot("engine.combine")
    assert h["engine.combine.trace_s"]["count"] == 1
    assert h["engine.combine.replay_dispatch_s"]["count"] == 1
    same_bits(a, eager_combine(partials[:11], compiled))
    same_bits(b, eager_combine(partials, compiled))
    # another bucket of the count, or of the capacity, is another program
    sg.combine_partials(partials[:8], compiled)
    assert len(merge_entries()) == 2


def test_groupby_padded_runs_only_inside_a_trace(monkeypatch):
    """No eager ``groupby_padded`` is left in the merge: it is entered once,
    with tracers, when the program is built — and not at all on a replay."""
    seg = segment_for(["k"], ALL_AGGS)
    partials, compiled = make_partials(seg, 3, seed=2)
    entered = {"traced": 0, "eager": 0}
    inner = agg_ops.groupby_padded

    def spy(table, *args, **kwargs):
        traced = all(isinstance(leaf, jax.core.Tracer)
                     for leaf in jax.tree_util.tree_leaves(table))
        entered["traced" if traced else "eager"] += 1
        return inner(table, *args, **kwargs)

    monkeypatch.setattr(agg_ops, "groupby_padded", spy)
    sg.SEGMENT_CACHE.clear()
    sg.combine_partials(partials, compiled)
    assert entered == {"traced": 1, "eager": 0}
    sg.combine_partials(partials, compiled)
    assert entered == {"traced": 1, "eager": 0}


def benchmark_compile_counters() -> tuple:
    """``benchmarks/run.py::COMPILE_COUNTERS``, read without importing the
    benchmark (its module starts a clock and edits ``sys.path``)."""
    with open(os.path.join(ROOT, "benchmarks", "run.py")) as f:
        for node in ast.parse(f.read()).body:
            if isinstance(node, ast.Assign) \
                    and node.targets[0].id == "COMPILE_COUNTERS":
                return ast.literal_eval(node.value)
    raise AssertionError("benchmarks/run.py has no COMPILE_COUNTERS")


class _Annotation:
    """Stands in for ``jax.profiler.TraceAnnotation`` (as in
    test_span_tree.py): records the spans in the order they close."""

    log: list = []

    def __init__(self, name, **stats):
        self.rec = {"name": name, "stats": stats}

    def set_metadata(self, **stats):
        self.rec["stats"].update(stats)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        _Annotation.log.append(self.rec)
        return False


def test_streamed_query_counts(tmp_path, counted, monkeypatch):
    """Through ``execute``: per streamed query the merge replays once, the
    chunk program's compile + replay still equals the chunks (the divisor
    of ``segment_roofline``), the syncs are the same two, and a second
    execution grows none of the benchmark's compile counters."""
    rows, group_rows = 6_000, 1_024
    path = str(tmp_path / "fact.parquet")
    rng = np.random.default_rng(4)
    pq.write_table(pa.table({
        "k": pa.array(rng.integers(0, 13, rows).astype(np.int64)),
        "f": pa.array(rng.normal(0.0, 1e3, rows)),
        "i": pa.array(rng.integers(-100, 100, rows).astype(np.int64)),
    }), path, row_group_size=group_rows)
    plan = optimize(Aggregate(
        Filter(Scan(path, chunk_bytes=24 * group_rows),
               (">=", col("i"), lit(-50))),
        ["k"], [("f", "sum"), ("i", "max"), (None, "count_all")],
        names=["s", "m", "n"]))
    monkeypatch.setenv("SRJT_TRACE", "1")
    monkeypatch.setenv("SRJT_RESULT_CACHE", "0")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    cfg.refresh()
    compile_counters = benchmark_compile_counters()
    assert "engine.segment_cache.miss" in compile_counters

    def compiles():
        return sum(counted(k) for k in compile_counters)

    try:
        sg.SEGMENT_CACHE.clear()
        stats = new_stats()
        first = execute(plan, stats, fused=True)
        chunks = stats["chunks"]
        assert stats["streamed"] and chunks > 2
        assert counted("engine.combine.compile") == 1
        assert counted("engine.combine.replay") == 0
        assert counted("engine.segment.compile") \
            + counted("engine.segment.replay") == chunks
        warm = compiles()
        assert warm >= 2        # the chunk program's entry and the merge's

        for n in (1, 2):
            _Annotation.log = []
            again = execute(plan, new_stats(), fused=True)
            same_bits(again, first)
            assert counted("engine.combine.replay") == n
            assert counted("engine.combine.compile") == 1
            assert counted("engine.segment.compile") \
                + counted("engine.segment.replay") == chunks * (n + 1)
            assert counted("engine.host_sync") == 2 * (n + 1)
            assert compiles() == warm
            log = _Annotation.log
            assert [r["stats"]["label"] for r in log
                    if r["name"] == "engine.sync_wait"] \
                == ["combine-sizing", "groupby-compaction"]
            (span,) = [r for r in log if r["name"] == "engine.combine"]
            assert span["stats"]["partials"] == chunks
            assert span["stats"]["cap"] == 64
            # the merge is launched beside the chunk program's span, not
            # under it: ``segment_device_ms`` sums what lies under
            # ``engine.fused_segment``
            names = [r["name"] for r in log]
            assert names.count("engine.fused_segment") == chunks
            assert names.index("engine.combine") \
                > max(i for i, nm in enumerate(names)
                      if nm == "engine.fused_segment")
    finally:
        monkeypatch.undo()
        cfg.refresh()
