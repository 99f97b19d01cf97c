"""Streaming top-k: ORDER BY ... LIMIT without materializing the table.

The optimizer rewrites ``Limit(Sort(x), n)`` into a ``TopK`` node; when the
child streams over one chunked scan, the executor keeps a capacity-k device
buffer merged per chunk on the order-preserving u64 key words (ops/order.py)
plus a global arrival-index tiebreak word.  The contracts pinned here: the
streamed result equals the full sort + slice bit-for-bit INCLUDING tie
order, on every chunk geometry (1-row chunks, unaligned, row-group-aligned,
whole-table), with nulls, descending keys, and degenerate k.
"""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_jni_tpu.engine import (
    Filter, Limit, Scan, Sort, TopK, col, execute, lit, new_stats, optimize,
)

N = 3_000


@pytest.fixture(scope="module")
def warehouse(tmp_path_factory):
    root = tmp_path_factory.mktemp("topk_wh")
    rng = np.random.default_rng(41)

    def cols(n):
        nv = rng.uniform(0.0, 9.0, n)
        return {
            # g: 8 distinct values over thousands of rows — ties everywhere
            "g": pa.array(rng.integers(0, 8, n).astype(np.int64)),
            "v": pa.array(np.round(rng.uniform(-5.0, 50.0, n), 3)),
            "w": pa.array(rng.integers(-100, 100, n).astype(np.int64)),
            "nv": pa.array([None if x < 1.0 else float(np.round(x, 3))
                            for x in nv], pa.float64()),
        }

    pq.write_table(pa.table(cols(N)), root / "fact.parquet",
                   row_group_size=500)
    pq.write_table(pa.table(cols(300)), root / "small.parquet",
                   row_group_size=100)
    pq.write_table(pa.table(cols(400)), root / "whole.parquet",
                   row_group_size=400)
    return root


def topk_plan(path, keys, n, chunk_bytes=None):
    return Limit(Sort(Filter(Scan(str(path), chunk_bytes=chunk_bytes),
                             (">", col("v"), lit(0.0))),
                      keys), n)


def ordered_rows(t):
    """Exact ordered row tuples, validity included (no sorting: order IS
    the contract under test)."""
    datas = [np.asarray(c.data) for c in t.columns]
    valids = [np.ones(t.num_rows, bool) if c.validity is None
              else np.asarray(c.validity) for c in t.columns]
    return [tuple((bool(vl[i]), d[i].item() if vl[i] else None)
                  for d, vl in zip(datas, valids))
            for i in range(t.num_rows)]


GEOMETRIES = [
    ("small.parquet", 24),        # ~1-row chunks
    ("fact.parquet", 1_000),      # chunks cut row groups unevenly
    ("fact.parquet", 24 * 1_024), # chunk ~ row group
    ("whole.parquet", 1 << 30),   # whole table, one chunk
]


def test_optimizer_fuses_limit_sort():
    plan = topk_plan("x.parquet", [("w", True)], 9, chunk_bytes=1_000)
    opt = optimize(plan)
    assert isinstance(opt, TopK)
    assert opt.n == 9 and opt.keys == (("w", True),)


@pytest.mark.parametrize("fname,chunk_bytes", GEOMETRIES)
def test_streamed_topk_matches_full_sort(warehouse, fname, chunk_bytes):
    # oversize k is pinned separately by test_topk_k_zero_and_oversize
    keys = [("w", True), ("v", False)]
    for k in (1, 17):
        stats = new_stats()
        streamed = execute(optimize(topk_plan(warehouse / fname, keys, k,
                                              chunk_bytes)), stats=stats)
        assert stats["topk"] and stats["streamed"]
        full = execute(optimize(topk_plan(warehouse / fname, keys, k)))
        assert ordered_rows(streamed) == ordered_rows(full)


@pytest.mark.parametrize("fname,chunk_bytes", GEOMETRIES)
def test_topk_tie_order_deterministic(warehouse, fname, chunk_bytes):
    # a single 8-valued key: nearly every row ties; the buffer must keep
    # exactly the first arrivals in post-filter row order, whatever the
    # chunk geometry — i.e. match the full STABLE sort's head
    keys = [("g", True)]
    streamed = execute(optimize(topk_plan(warehouse / fname, keys, 25,
                                          chunk_bytes)))
    full = execute(optimize(topk_plan(warehouse / fname, keys, 25)))
    assert ordered_rows(streamed) == ordered_rows(full)


def test_topk_geometry_invariant_result(warehouse):
    # same file at different chunkings must agree row-for-row
    keys = [("g", False), ("w", True)]
    results = [ordered_rows(execute(optimize(
        topk_plan(warehouse / "fact.parquet", keys, 40, cb))))
        for cb in (1_000, 24 * 1_024, None)]
    assert results[0] == results[1] == results[2]


def test_topk_with_nulls(warehouse):
    keys = [("nv", True)]  # nullable sort key
    streamed = execute(optimize(topk_plan(warehouse / "fact.parquet", keys,
                                          30, 24 * 1_024)))
    full = execute(optimize(topk_plan(warehouse / "fact.parquet", keys,
                                      30)))
    assert ordered_rows(streamed) == ordered_rows(full)


def test_topk_k_zero_and_oversize(warehouse):
    z = execute(optimize(topk_plan(warehouse / "small.parquet",
                                   [("w", True)], 0, 1_000)))
    assert z.num_rows == 0
    big = execute(optimize(topk_plan(warehouse / "small.parquet",
                                     [("w", True)], 10 ** 6, 1_000)))
    full = execute(optimize(topk_plan(warehouse / "small.parquet",
                                      [("w", True)], 10 ** 6)))
    assert ordered_rows(big) == ordered_rows(full)


def test_topk_flag_disables_streaming(warehouse):
    """Whether a TopK streams is the plan's call alone: over a chunked scan
    it streams, over the same scan unchunked it sorts the whole table, and
    both give the same rows in the same order."""
    stats = new_stats()
    whole = execute(optimize(topk_plan(warehouse / "fact.parquet",
                                       [("w", True)], 12)), stats=stats)
    assert not stats["topk"]
    stats = new_stats()
    streamed = execute(optimize(topk_plan(warehouse / "fact.parquet",
                                          [("w", True)], 12, 24 * 1_024)),
                       stats=stats)
    assert stats["topk"]
    assert ordered_rows(whole) == ordered_rows(streamed)
