"""The build-row form of the chunk program's keyed aggregate
(``ops/aggregate.py::groupby_build_rows``) scatters a chunk's live rows
only: where at most ``BUILD_SPARSE_MAX_ROWS`` rows are live they are
compacted into that many entries first, else every row is scattered.

- at 0, 1, K - 1, K, K + 1 and all live rows, dead rows carrying arbitrary
  slots and values, the guarded form's totals equal every row's scatter
  (``_build_row_totals``) and numpy's bit for bit, and its flag says which
  branch it took;
- decimal sums near and past the checked bound give the same overflow flag
  (``expr.sum_check`` over the totals, as the stream's merge checks them);
- a sum whose slot holds only null rows is null, its count 0;
- the benchmark's ``build_sparse_pct`` reader on known inputs.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu import dtypes as dt
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.engine.expr import OVERFLOW_UNITS, sum_check
from spark_rapids_jni_tpu.ops import aggregate as A

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
K = A.BUILD_SPARSE_MAX_ROWS
N = 4 * K                       # a chunk bucket the compaction shrinks
NSLOTS = 3_001
DEC = dt.decimal64(-4, 38)      # Q3's revenue sum: decimal(38,4)
AGGS = [("v", "sum"), (None, "count_all"), ("w", "sum"), ("w", "count"),
        ("w", "mean")]
LIVE = {"none": 0, "one": 1, "k_less_one": K - 1, "k": K, "k_plus_one": K + 1,
        "all": N}


def _chunk(nlive: int, scale: str, seed: int):
    """(table, live, slot): ``nlive`` live rows at random positions, each
    with a slot of ``[0, NSLOTS)``; a dead row with any int32 slot and any
    value.  ``w`` is null on a fifth of the rows and on every row of slots
    ``< 40``.  ``scale``: ``near`` puts the sum of the live rows' values
    (all positive) just under the checked bound, ``past`` just over it."""
    rng = np.random.default_rng(seed)
    live = np.zeros(N, bool)
    live[rng.choice(N, nlive, replace=False)] = True
    slot = rng.integers(0, NSLOTS, N).astype(np.int32)
    slot[~live] = rng.integers(-2**31, 2**31 - 1, int((~live).sum()))
    top = int(OVERFLOW_UNITS / max(nlive, 1) * (0.98 if scale == "near"
                                                 else 1.02))
    v = top - rng.integers(0, 1_000, N)
    v[~live] = rng.integers(-2**62, 2**62, int((~live).sum()))
    wvalid = (rng.random(N) < 0.8) & ~((slot >= 0) & (slot < 40))
    w = rng.integers(-10**9, 10**9, N)
    table = Table([Column(DEC, data=jnp.asarray(v.astype(np.int64))),
                   Column(dt.INT64, data=jnp.asarray(w.astype(np.int64)),
                          validity=jnp.asarray(wvalid))], ["v", "w"])
    return table, jnp.asarray(live), jnp.asarray(slot)


_guarded = jax.jit(lambda t, live, slot: A.groupby_build_rows(
    t, AGGS, live, slot, NSLOTS))
_full = jax.jit(lambda t, live, slot: A._build_row_totals(
    [(None if c is None else t.column(c), op) for c, op in AGGS], live, slot,
    NSLOTS))


def _arrays(rows, out) -> list:
    got = [np.asarray(rows)]
    for c in out:
        got += [np.asarray(c.data), None if c.validity is None
                else np.asarray(c.validity)]
    return got


def _flag(rows, out):
    ovf: list = []
    sum_check(out[0], rows > 0, ovf)
    return bool(ovf[0])


@pytest.mark.parametrize("scale", ["near", "past"])
@pytest.mark.parametrize("nlive", list(LIVE.values()), ids=list(LIVE))
def test_the_compacted_and_full_forms_are_bit_equal(nlive, scale):
    table, live, slot = _chunk(nlive, scale, seed=nlive + len(scale))
    rows, out, sparse = _guarded(table, live, slot)
    frows, fout = _full(table, live, slot)
    assert int(sparse) == int(nlive <= K)
    got, want = _arrays(rows, out), _arrays(frows, fout)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert (g is None) == (w is None)
        assert g is None or (g.dtype == w.dtype and np.array_equal(g, w))
    assert [c.dtype for c in out] == [c.dtype for c in fout]
    # numpy's totals of the live rows
    lv, sl = np.asarray(live), np.asarray(slot)
    v = np.asarray(table.column("v").data)
    w, wv = (np.asarray(a) for a in (table.column("w").data,
                                     table.column("w").validity))
    want_rows = np.bincount(sl[lv], minlength=NSLOTS)
    want_v = np.zeros(NSLOTS, np.int64)
    np.add.at(want_v, sl[lv], v[lv])
    want_w = np.zeros(NSLOTS, np.int64)
    np.add.at(want_w, sl[lv & wv], w[lv & wv])
    want_wn = np.bincount(sl[lv & wv], minlength=NSLOTS)
    assert np.array_equal(got[0], want_rows)
    assert np.array_equal(got[1], want_v)
    assert np.array_equal(got[2], want_rows > 0)
    assert np.array_equal(got[5], want_w) and np.array_equal(got[7], want_wn)
    # a slot whose rows are all null has a null sum and a count of 0
    assert np.array_equal(got[6], want_wn > 0)
    assert not got[6][:40].any() and not got[7][:40].any()
    # the merge's overflow check over the totals: the same flag either way,
    # set only where the live rows' magnitudes pass the bound
    assert _flag(rows, out) == _flag(frows, fout) == \
        (scale == "past" and nlive > 0)


def _reader(name):
    path = os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"lm_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("start, end, want", [
    ({}, {"engine.agg.build_sparse": 48, "engine.agg.build_full": 0}, 100.0),
    ({"engine.agg.build_sparse": 24, "engine.agg.build_full": 24},
     {"engine.agg.build_sparse": 42, "engine.agg.build_full": 30}, 75.0),
    ({}, {"engine.agg.build_full": 24}, 0.0),
    ({}, {"engine.agg.dense": 11}, None),       # no build-row chunk
    ({"engine.agg.build_sparse": 24}, {"engine.agg.build_sparse": 24},
     None)])
def test_the_build_sparse_pct_reader(start, end, want):
    got = _reader("build_sparse_pct").read(
        {"snap_start": {"counters": start}, "snap_end": {"counters": end}})
    assert got == want
