"""Streamed probe joins: fused chunk programs + cached build-side prep.

The engine's streaming loop no longer breaks at a Join whose build side is
scan-independent: the build is hashed + stable-sorted ONCE per execution
(``ops.join.prepare_build``, cached in ``engine.BUILD_CACHE``) and each
probe chunk runs filter -> probe-join -> partial-agg as one jitted program.
These tests pin the contracts: fused == interpreted == whole-table on every
chunk geometry, the probe's two methods (broadcast compare for a small
build, hash merge-rank above ``PROBE_COMPARE_MAX_BUILD``) give one result
and count themselves per chunk launch, the build cache shows exactly
``hits == chunks - 1`` on a cold stream, non-unique build hashes fall back
(correct, just interpreted), and the chunked reader's prefetch thread dies
when the consumer abandons the stream.
"""

import os
import threading

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.engine import (
    BUILD_CACHE, Aggregate, Filter, Join, Scan, col, execute, lit,
    new_stats, optimize,
)
from spark_rapids_jni_tpu.io import ParquetChunkedReader
from spark_rapids_jni_tpu.ops.join import prepare_build, probe_join_prepared
from spark_rapids_jni_tpu.utils import config, tracing

N_FACT = 3_000


@pytest.fixture(scope="module")
def warehouse(tmp_path_factory):
    root = tmp_path_factory.mktemp("join_stream_wh")
    rng = np.random.default_rng(23)

    def fact_cols(n, kmax=40):
        return {
            "k": pa.array(rng.integers(0, kmax, n).astype(np.int64)),
            "v": pa.array(np.round(rng.uniform(-5.0, 50.0, n), 3)),
            "w": pa.array(rng.integers(-100, 100, n).astype(np.int64)),
        }

    pq.write_table(pa.table(fact_cols(N_FACT)), root / "fact.parquet",
                   row_group_size=500)
    pq.write_table(pa.table(fact_cols(300, kmax=35)),
                   root / "small.parquet", row_group_size=100)
    pq.write_table(pa.table(fact_cols(400)), root / "whole.parquet",
                   row_group_size=400)
    # first row group entirely filtered out by v > 0 (a probe chunk whose
    # every row dies before the join)
    dead = fact_cols(1_000)
    v = np.asarray(dead["v"].to_numpy(zero_copy_only=False)).copy()
    v[:500] = -1.0
    dead["v"] = pa.array(v)
    pq.write_table(pa.table(dead), root / "deadfirst.parquet",
                   row_group_size=500)
    # unique build keys (the prepared-probe fast path)...
    pq.write_table(pa.table({
        "dk": pa.array(np.arange(0, 30, dtype=np.int64)),
        "dv": pa.array((np.arange(0, 30) % 5).astype(np.int64)),
    }), root / "dim.parquet")
    # ...and duplicated ones (forces the interpreted fallback)
    pq.write_table(pa.table({
        "dk": pa.array(np.concatenate([np.arange(0, 30),
                                       np.arange(0, 10)]).astype(np.int64)),
        "dv": pa.array((np.arange(0, 40) % 5).astype(np.int64)),
    }), root / "dupdim.parquet")
    return root


def join_agg_plan(fact, dim, chunk_bytes=None, how="inner"):
    """filter(fact) |> join(dim) |> group by the dim payload."""
    keys = ["dv"] if how == "inner" else ["k"]
    return Aggregate(
        Join(Filter(Scan(str(fact), chunk_bytes=chunk_bytes),
                    (">", col("v"), lit(0.0))),
             Scan(str(dim)), ["k"], ["dk"], how=how),
        keys,
        [("v", "sum"), ("w", "min"), (None, "count_all")],
        names=["s", "lo", "n"])


def as_rows(t: Table):
    cols = [np.asarray(c.data, np.float64) for c in t.columns]
    valids = [np.ones(t.num_rows, bool) if c.validity is None
              else np.asarray(c.validity) for c in t.columns]
    return sorted(zip(*[c.tolist() for c in cols],
                      *[v.tolist() for v in valids]))


GEOMETRIES = [
    ("small.parquet", 24),        # ~1-row chunks
    ("fact.parquet", 1_000),      # chunks cut row groups unevenly
    ("fact.parquet", 24 * 1_024), # chunk ~ row group
    ("whole.parquet", 1 << 30),   # whole table, one chunk
]


@pytest.mark.parametrize("fname,chunk_bytes", GEOMETRIES)
@pytest.mark.parametrize("how", ["inner", "semi"])
def test_streamed_join_matches_interpreter(warehouse, fname, chunk_bytes,
                                           how):
    fact = warehouse / fname
    dim = warehouse / "dim.parquet"
    stats = new_stats()
    fused = execute(optimize(join_agg_plan(fact, dim, chunk_bytes,
                                           how=how)),
                    stats=stats, fused=True)
    assert stats["streamed"] and stats["chunks"] >= 1
    assert stats["fused_segments"] == 1
    interp = execute(optimize(join_agg_plan(fact, dim, chunk_bytes,
                                            how=how)), fused=False)
    whole = execute(optimize(join_agg_plan(fact, dim, how=how)),
                    fused=False)
    assert as_rows(fused) == as_rows(interp) == as_rows(whole)


@pytest.mark.parametrize("how", ["inner", "semi"])
def test_probe_methods_agree_and_count_per_chunk(warehouse, how,
                                                 metrics_isolation,
                                                 monkeypatch):
    """The 30-row build takes the compare path; with the constant moved
    under it the same plan takes the rank probe: same rows, and
    ``compare + rank == joins x chunks`` either way.  The build is prepared
    anew for each: a compare build carries no sorted keys."""
    from spark_rapids_jni_tpu.engine import segment as sg
    from spark_rapids_jni_tpu.ops import join as J
    metrics_isolation("engine.probe")
    plan = join_agg_plan(warehouse / "fact.parquet",
                         warehouse / "dim.parquet", 24 * 1_024, how=how)
    results = {}
    for method, other in (("compare", "rank"), ("rank", "compare")):
        if method == "rank":
            monkeypatch.setattr(J, "PROBE_COMPARE_MAX_BUILD", 29)
        sg.SEGMENT_CACHE.clear()    # one plan shape, two programs
        BUILD_CACHE.clear()         # and two prepares
        tracing.reset_counters("engine.probe")
        stats = new_stats()
        results[method] = as_rows(execute(optimize(plan), stats=stats,
                                          fused=True))
        assert stats["fused_segments"] == 1 and stats["chunks"] > 1
        assert tracing.counter_value(f"engine.probe.{method}") \
            == stats["chunks"]
        assert tracing.counter_value(f"engine.probe.{other}") == 0
    sg.SEGMENT_CACHE.clear()
    assert results["compare"] == results["rank"]
    whole = execute(optimize(join_agg_plan(warehouse / "fact.parquet",
                                           warehouse / "dim.parquet",
                                           how=how)), fused=False)
    assert results["compare"] == as_rows(whole)


def test_build_cache_cold_stream_hits_chunks_minus_one(warehouse,
                                                       metrics_isolation):
    BUILD_CACHE.clear()
    metrics_isolation("engine.build_cache")
    h0, m0 = BUILD_CACHE.hits, BUILD_CACHE.misses
    stats = new_stats()
    execute(optimize(join_agg_plan(warehouse / "fact.parquet",
                                   warehouse / "dim.parquet", 24 * 1_024)),
            stats=stats, fused=True)
    assert stats["chunks"] > 1 and stats["fused_segments"] == 1
    # exactly one get per chunk: the first misses and pays the build
    # hash + sort, every later chunk reuses it
    assert BUILD_CACHE.misses - m0 == 1
    assert BUILD_CACHE.hits - h0 == stats["chunks"] - 1
    assert tracing.counter_value("engine.build_cache.miss") == 1
    assert tracing.counter_value("engine.build_cache.hit") == \
        stats["chunks"] - 1
    # a repeat execution hits on every chunk (the build shape is cached)
    stats2 = new_stats()
    execute(optimize(join_agg_plan(warehouse / "fact.parquet",
                                   warehouse / "dim.parquet", 24 * 1_024)),
            stats=stats2, fused=True)
    assert BUILD_CACHE.misses - m0 == 1
    assert BUILD_CACHE.hits - h0 == stats["chunks"] - 1 + stats2["chunks"]


def test_build_cache_env_capacity_and_eviction(warehouse):
    os.environ["SRJT_BUILD_CACHE"] = "1"
    config.refresh()
    try:
        BUILD_CACHE.clear()
        e0 = BUILD_CACHE.evictions
        assert BUILD_CACHE.maxsize == 1
        for dim in ("dim.parquet", "dupdim.parquet"):
            execute(optimize(join_agg_plan(warehouse / "fact.parquet",
                                           warehouse / dim, 24 * 1_024,
                                           how="semi")), fused=True)
        assert len(BUILD_CACHE) <= 1
        assert BUILD_CACHE.evictions > e0
    finally:
        del os.environ["SRJT_BUILD_CACHE"]
        config.refresh()


def test_empty_build_side(warehouse, tmp_path):
    pq.write_table(pa.table({
        "dk": pa.array(np.zeros(0, np.int64)),
        "dv": pa.array(np.zeros(0, np.int64)),
    }), tmp_path / "empty_dim.parquet")
    for how in ("inner", "semi"):
        stats = new_stats()
        fused = execute(optimize(join_agg_plan(
            warehouse / "fact.parquet", tmp_path / "empty_dim.parquet",
            24 * 1_024, how=how)), stats=stats, fused=True)
        interp = execute(optimize(join_agg_plan(
            warehouse / "fact.parquet", tmp_path / "empty_dim.parquet",
            how=how)), fused=False)
        assert stats["streamed"]
        assert fused.num_rows == 0 == interp.num_rows
        assert fused.names == interp.names


def test_fully_filtered_probe_chunk(warehouse):
    fact = warehouse / "deadfirst.parquet"
    dim = warehouse / "dim.parquet"
    stats = new_stats()
    fused = execute(optimize(join_agg_plan(fact, dim, 4_000)),
                    stats=stats, fused=True)
    assert stats["chunks"] >= 2  # the dead chunk still flowed through
    interp = execute(optimize(join_agg_plan(fact, dim, 4_000)),
                     fused=False)
    whole = execute(optimize(join_agg_plan(fact, dim)), fused=False)
    assert as_rows(fused) == as_rows(interp) == as_rows(whole)


def test_duplicate_build_hashes_fall_back(warehouse):
    # dupdim repeats dk 0..9: the <=1-candidate probe shape doesn't hold,
    # so the fused path must veto itself — and still be right
    stats = new_stats()
    fused = execute(optimize(join_agg_plan(warehouse / "fact.parquet",
                                           warehouse / "dupdim.parquet",
                                           24 * 1_024)),
                    stats=stats, fused=True)
    assert stats["streamed"] and stats["fused_segments"] == 0
    whole = execute(optimize(join_agg_plan(warehouse / "fact.parquet",
                                           warehouse / "dupdim.parquet")),
                    fused=False)
    assert as_rows(fused) == as_rows(whole)


# -- prepared-build ops-level edge cases ------------------------------------

def _null_key_table(n):
    return Table([Column.from_numpy(np.zeros(n, np.int64),
                                    validity=np.zeros(n, bool))], ["k"])


def test_prepared_probe_all_null_keys_both_null_semantics():
    build = _null_key_table(1)
    probe = _null_key_table(4)
    pb = prepare_build(build, ["k"])
    assert pb.unique
    # SQL '=' never matches null keys...
    _, matched = probe_join_prepared(probe, pb, null_equal=False)
    assert not np.asarray(matched).any()
    # ...while null-safe '<=>' matches them all
    ri, matched = probe_join_prepared(probe, pb, null_equal=True)
    assert np.asarray(matched).all()
    assert (np.asarray(ri) == 0).all()


def test_prepared_build_all_null_multirow_not_unique():
    # every null key hashes identically: a multi-row all-null build is
    # non-unique, which is exactly what makes the engine fall back
    pb = prepare_build(_null_key_table(3), ["k"])
    assert not pb.unique


def test_prepared_probe_matches_reference_join():
    rng = np.random.default_rng(5)
    bk = rng.permutation(np.arange(0, 64, dtype=np.int64))[:40]
    lk = rng.integers(0, 80, 256).astype(np.int64)
    pb = prepare_build(Table([Column.from_numpy(bk)], ["k"]), ["k"])
    assert pb.unique
    ri, matched = probe_join_prepared(
        Table([Column.from_numpy(lk)], ["k"]), pb)
    ri, matched = np.asarray(ri), np.asarray(matched)
    want = np.isin(lk, bk)
    np.testing.assert_array_equal(matched, want)
    np.testing.assert_array_equal(bk[ri[matched]], lk[matched])


# -- reader close / prefetch-thread reaping ---------------------------------

def test_reader_close_reaps_abandoned_prefetch_thread(warehouse):
    before = set(threading.enumerate())
    reader = ParquetChunkedReader(str(warehouse / "fact.parquet"),
                                  pass_read_limit=24 * 1_024, prefetch=2)
    it = reader.iter_staged()
    next(it)
    spawned = [t for t in threading.enumerate() if t not in before]
    assert spawned  # the producer is running
    # a consumer that raises mid-stream never exhausts/closes `it`;
    # close() must still reap the producer
    reader.close()
    for t in spawned:
        t.join(timeout=5)
    assert not any(t.is_alive() for t in spawned)
    reader.close()  # idempotent


def test_reader_context_manager_closes(warehouse):
    before = set(threading.enumerate())
    with ParquetChunkedReader(str(warehouse / "fact.parquet"),
                              pass_read_limit=24 * 1_024,
                              prefetch=2) as reader:
        it = reader.iter_staged()  # hold the ref: a bare next() temporary
        next(it)                   # would be GC-closed before we can look
        spawned = [t for t in threading.enumerate() if t not in before]
        assert spawned
    for t in spawned:
        t.join(timeout=5)
    assert not any(t.is_alive() for t in spawned)
