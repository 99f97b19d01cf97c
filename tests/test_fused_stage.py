"""Whole-stage fusion across the exchange (SRJT_FUSE_EXCHANGE).

The ``partial-agg -> hash Exchange -> final-agg`` sandwich executes as ONE
``jax.jit(shard_map(...))`` program: partial groupby, murmur3 placement,
bucket scatter, ``all_to_all``, and the final combine with zero host
round-trips between the three plan nodes.  These tests pin the PR's
acceptance criteria:

* bit-exact parity against the host-orchestrated path (positional, not
  just multiset — the fused output restores global groupby order);
* the static ``verify.sync_budget`` EQUALS the runtime ``engine.host_sync``
  counter — one boundary sync per fused stage, including for EMPTY inputs
  (the PR 8 review's empty-input upper-bound discrepancy, closed);
* in-program exchange attribution: wire/rows matrices derived from the
  device-side counts with matrix-sum == counter invariants, and EXPLAIN
  ANALYZE rendering ``in_program=yes``;
* the AQE escape hatch: a placement-hot stage routes to the host path
  where the skew split still fires (ledgered), a balanced stage dispatches
  the fused program — parity holds either way;
* overflow of the static capacity falls back to the host path (a runtime
  re-plan, never an error).
"""

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_jni_tpu.engine import (
    Aggregate, Scan, execute, new_stats, optimize,
)
from spark_rapids_jni_tpu.engine import segment as sg
from spark_rapids_jni_tpu.engine.adaptive import runtime_entries
from spark_rapids_jni_tpu.engine.fuzz import _flags
from spark_rapids_jni_tpu.engine.physical import lower
from spark_rapids_jni_tpu.engine.verify import (
    SYNC_WHITELIST, lint_fused_stage, plan_exchanges, sync_budget,
)
from spark_rapids_jni_tpu.utils import metrics, tracing
from spark_rapids_jni_tpu.utils.config import config

NDEV = 8
N_ROWS = 20_000
N_KEYS = 500


@pytest.fixture(scope="module")
def warehouse(tmp_path_factory):
    root = tmp_path_factory.mktemp("fused")
    rng = np.random.default_rng(42)
    k = rng.integers(0, N_KEYS, N_ROWS)
    # quarter-grid values: partial-then-combine float sums are exactly
    # representable, so parity is bit-exact despite reduction-order
    # differences between the fused and host paths
    v = (rng.integers(0, 400, N_ROWS) * 0.25).astype(np.float64)
    pq.write_table(pa.table({"k": pa.array(k, pa.int64()),
                             "v": pa.array(v, pa.float64())}),
                   root / "fact.parquet", row_group_size=4_000)
    pq.write_table(pa.table({"k": pa.array([], pa.int64()),
                             "v": pa.array([], pa.float64())}),
                   root / "empty.parquet")
    return root


def _sandwich(root, name="fact.parquet"):
    return Aggregate(Scan(root / name), ("k",),
                     (("v", "sum"), ("v", "count")), ("total", "n"))


def _df(table):
    return pd.DataFrame({
        n: (np.array(c.to_pylist(), dtype=object) if c.dtype.is_string
            else np.asarray(c.to_numpy()))
        for n, c in zip(table.names, table.columns)})


def _host_syncs():
    return tracing.counters_snapshot("engine.host_sync") \
        .get("engine.host_sync", 0)


def _counter(name):
    return tracing.counters_snapshot(name).get(name, 0)


# -- the tentpole: one program, exact budget, bit-exact parity -------------


def test_fused_stage_bit_exact_parity(warehouse):
    with _flags(fuse_exchange=True):
        opt = optimize(_sandwich(warehouse), distribute=True)
        stats = new_stats()
        before = _counter("engine.fused_stage.dispatches")
        out = execute(opt, stats)
        assert _counter("engine.fused_stage.dispatches") == before + 1
        # the lowered exchange still ticks the executed-exchange census
        assert stats["exchanges"] == len(plan_exchanges(opt)) == 1
    with _flags(fuse_exchange=False):
        ref = execute(optimize(_sandwich(warehouse), distribute=True),
                      new_stats())
    # positional parity, not just multiset: the fused output restores the
    # global-groupby order the host path produces
    pd.testing.assert_frame_equal(_df(out), _df(ref), check_exact=True)


def test_static_budget_equals_runtime_sync_counter(warehouse):
    """Satellite 1: ``sync_budget`` is EXACT for the fused path — the
    static charge equals the runtime ``engine.host_sync`` counter."""
    with _flags(fuse_exchange=True):
        opt = optimize(_sandwich(warehouse), distribute=True)
        budget = sync_budget(opt, cfg=config, ndev=NDEV)
        assert [e["site"] for e in budget] == ["groupby-compaction"]
        assert all(e["site"] in SYNC_WHITELIST for e in budget)
        before = _host_syncs()
        execute(opt, new_stats())
        assert _host_syncs() - before == sum(e["count"] for e in budget) == 1


def test_chunked_sandwich_pays_its_budget(warehouse):
    """Over a chunked scan too: the fused stage dispatches once and pays
    its one-sync budget where the host path streams the chunks and pays
    its own; both tick the exchange census and agree bit for bit."""
    plan = Aggregate(Scan(warehouse / "fact.parquet", chunk_bytes=64_000),
                     ("k",), (("v", "sum"), ("v", "count")), ("total", "n"))
    out, paid = {}, {}
    for fuse_x in (True, False):
        with _flags(fuse_exchange=fuse_x):
            opt = optimize(plan, distribute=True)
            budget = sum(e["count"]
                         for e in sync_budget(opt, cfg=config, ndev=NDEV))
            stats = new_stats()
            syncs = _host_syncs()
            before = _counter("engine.fused_stage.dispatches")
            out[fuse_x] = execute(opt, stats)
            paid[fuse_x] = _host_syncs() - syncs
            assert paid[fuse_x] == budget
            assert stats["exchanges"] == len(plan_exchanges(opt)) == 1
            assert _counter("engine.fused_stage.dispatches") - before \
                == int(fuse_x)
            assert fuse_x or stats["chunks"] > 1
    assert paid[True] == 1 < paid[False]
    pd.testing.assert_frame_equal(_df(out[True]), _df(out[False]),
                                  check_exact=True)


def test_empty_input_budget_still_exact(warehouse):
    """The PR 8 review discrepancy, closed: an EMPTY input pays exactly
    the statically-charged syncs on both the fused path (dead-row
    synthesis keeps the one-sync program running) and the host exchange
    (whose empty-input early-out is gone)."""
    for fuse_x in (True, False):
        with _flags(fuse_exchange=fuse_x):
            opt = optimize(_sandwich(warehouse, "empty.parquet"),
                           distribute=True)
            budget = sum(e["count"]
                         for e in sync_budget(opt, cfg=config, ndev=NDEV)
                         if e["site"] in ("groupby-compaction",
                                          "exchange-counts-sizing",
                                          "exchange-compaction"))
            before = _host_syncs()
            out = execute(opt, new_stats())
            paid = _host_syncs() - before
            assert out.num_rows == 0
            if fuse_x:
                assert paid == budget == 1
            else:
                # the host path's interpreted-agg fallback on 0 rows pays
                # no groupby sync; the EXCHANGE charge (the discrepancy
                # PR 8 flagged) is now exact
                assert paid >= 2  # both exchange syncs actually paid


def test_lowering_reports_fused_stage(warehouse):
    with _flags(fuse_exchange=True):
        opt = optimize(_sandwich(warehouse), distribute=True)
        flags = dict(fuse=True, fuse_exchange=True)
        stages = lower(opt, ndev=NDEV, **flags).stages
        assert "fused-stage" in [s.kind for s in stages]
        st = next(s for s in stages if s.kind == "fused-stage")
        assert isinstance(st.stage, sg.FusedStage)
        assert st.nodes == (opt, st.stage.exchange, st.stage.partial)
        # on one device the fusion is moot and the stage disappears: the
        # sandwich's exchange is the identity there
        kinds = [s.kind for s in lower(opt, ndev=1, **flags).stages]
        assert "fused-stage" not in kinds
        assert "exchange-identity" in kinds


def test_compiled_once_then_replayed(warehouse):
    sg.FUSED_STAGE_CACHE.clear()
    with _flags(fuse_exchange=True):
        opt = optimize(_sandwich(warehouse), distribute=True)
        execute(opt, new_stats())
        hits = sg.FUSED_STAGE_CACHE.stats()["hits"]
        before = _counter("engine.fused_stage.compile")
        execute(opt, new_stats())
        assert sg.FUSED_STAGE_CACHE.stats()["hits"] == hits + 1
        assert _counter("engine.fused_stage.compile") == before  # replay


# -- satellite 2: in-program attribution -----------------------------------


def test_wire_and_rows_matrices_sum_to_counters(warehouse):
    from spark_rapids_jni_tpu.parallel.mesh import ROW_AXIS, make_mesh
    with _flags(fuse_exchange=True):
        opt = optimize(_sandwich(warehouse), distribute=True)
        stage = sg.fused_sandwich(opt)
        assert stage is not None
        inp = execute(stage.partial.child, new_stats())
        mesh = make_mesh(NDEV)
        res = sg.run_fused_stage(stage, inp, mesh, ROW_AXIS)
        assert res is not None
        out, info = res
        # matrix-sum == counter invariant: every padded slot crosses the
        # wire, so the wire matrix tiles to exactly the counted bytes
        assert int(info["wire_matrix"].sum()) == info["wire_bytes"] \
            == NDEV * NDEV * info["capacity"] * info["row_size"]
        # the rows matrix is device-derived send counts: its sum is the
        # total live partial rows, >= one row per live group
        assert info["rows_matrix"].shape == (NDEV, NDEV)
        assert int(info["rows_matrix"].sum()) >= N_KEYS
        assert out.num_rows == N_KEYS

        # and the executor increments engine.exchange.wire_bytes by the
        # same figure when it dispatches the same (cached) program
        before = _counter("engine.exchange.wire_bytes")
        execute(opt, new_stats())
        assert _counter("engine.exchange.wire_bytes") - before \
            == info["wire_bytes"]


def test_explain_analyze_marks_in_program(warehouse):
    from spark_rapids_jni_tpu.engine.explain import explain_analyze
    with _flags(fuse_exchange=True):
        rep = explain_analyze(_sandwich(warehouse), distribute=True)
    if not rep.summary:
        pytest.skip("SRJT_METRICS off")
    assert "in_program=yes" in rep.text
    assert "Exchange(hash" in rep.text


# -- the AQE escape hatch ---------------------------------------------------


def _placement_hot_keys(n_keys=64):
    """int64 keys that all murmur3-place on device 0 of an 8-way mesh —
    partial aggregation cannot dissolve PLACEMENT skew (distinct keys,
    one destination), so both the probe and the host exchange see it."""
    import jax.numpy as jnp

    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.dtypes import INT64
    from spark_rapids_jni_tpu.parallel import shuffle as sh
    cand = np.arange(4096, dtype=np.int64)
    t = Table([Column(INT64, data=jnp.asarray(cand))], ["k"])
    dest = np.asarray(sh.partition_ids(t, NDEV))
    hot = cand[dest == 0][:n_keys]
    assert len(hot) == n_keys
    return hot


@pytest.fixture(scope="module")
def skewed_warehouse(tmp_path_factory):
    root = tmp_path_factory.mktemp("fused_skew")
    rng = np.random.default_rng(7)
    hot = _placement_hot_keys()
    k = hot[rng.integers(0, len(hot), N_ROWS)]
    v = (rng.integers(0, 400, N_ROWS) * 0.25).astype(np.float64)
    pq.write_table(pa.table({"k": pa.array(k, pa.int64()),
                             "v": pa.array(v, pa.float64())}),
                   root / "fact.parquet", row_group_size=4_000)
    return root


def test_aqe_probe_routes_hot_stage_to_host_and_split_fires(
        skewed_warehouse):
    """AQE composition: the skew split fires AT the boundary the fusion
    erases, so the counts probe must route the hot stage to the host path
    where ``try_skew_split``'s full machinery still runs — and parity vs
    the AQE-off paths must hold."""
    with _flags(fuse_exchange=True, aqe=True):
        opt = optimize(_sandwich(skewed_warehouse), distribute=True)
        stats = new_stats()
        before = _counter("engine.fused_stage.aqe_fallbacks")
        out = execute(opt, stats)
        assert _counter("engine.fused_stage.aqe_fallbacks") == before + 1
        rt = runtime_entries(opt)
        probes = [d for d in rt if d["kind"] == "fused_stage"]
        assert probes and probes[0]["dispatch"] == "host"
        assert probes[0]["measured_skew"] > probes[0]["threshold"]
        splits = [d for d in rt if d["kind"] == "adaptive:skew_split"
                  and d.get("triggered")]
        assert splits, "skew split did not fire on the routed-to-host stage"
        assert stats["aqe_splits"] == len(splits)
    with _flags(fuse_exchange=False, aqe=False):
        ref = execute(optimize(_sandwich(skewed_warehouse),
                               distribute=True), new_stats())
    pd.testing.assert_frame_equal(_df(out), _df(ref), check_exact=True)


def test_aqe_probe_dispatches_balanced_stage_fused(warehouse):
    """The balanced side of the hatch: probe skew under the threshold
    dispatches the fused program, and the probe's counts fetch is itself
    a budgeted sync — static budget == runtime counter, AQE included."""
    with _flags(fuse_exchange=True, aqe=True):
        opt = optimize(_sandwich(warehouse), distribute=True)
        budget = sync_budget(opt, cfg=config, ndev=NDEV)
        assert sorted(e["site"] for e in budget) == \
            ["exchange-counts-sizing", "groupby-compaction"]
        stats = new_stats()
        before = _host_syncs()
        out = execute(opt, stats)
        assert _host_syncs() - before == sum(e["count"] for e in budget) == 2
        rt = runtime_entries(opt)
        probes = [d for d in rt if d["kind"] == "fused_stage"]
        assert probes and probes[0]["dispatch"] == "fused"
        assert stats["aqe_splits"] == 0
    with _flags(fuse_exchange=False, aqe=False):
        ref = execute(optimize(_sandwich(warehouse), distribute=True),
                      new_stats())
    pd.testing.assert_frame_equal(_df(out), _df(ref), check_exact=True)


# -- fallback rules ---------------------------------------------------------


def test_capacity_overflow_falls_back_to_host_path(warehouse, monkeypatch):
    """An adversarial input overflowing the static capacity is a runtime
    re-plan: the overflow counter (read at the one boundary sync) routes
    the stage to the host-orchestrated path, never an error."""
    sg.FUSED_STAGE_CACHE.clear()
    monkeypatch.setattr(sg, "fused_capacity", lambda n_local, ndev: 2)
    try:
        with _flags(fuse_exchange=True):
            opt = optimize(_sandwich(warehouse), distribute=True)
            before = _counter("engine.fused_stage.overflow_fallbacks")
            out = execute(opt, new_stats())
            assert _counter("engine.fused_stage.overflow_fallbacks") \
                == before + 1
        with _flags(fuse_exchange=False):
            ref = execute(optimize(_sandwich(warehouse), distribute=True),
                          new_stats())
        pd.testing.assert_frame_equal(
            _df(out).sort_values("k").reset_index(drop=True),
            _df(ref).sort_values("k").reset_index(drop=True),
            check_exact=True)
    finally:
        sg.FUSED_STAGE_CACHE.clear()


def test_string_keys_fall_back_to_host_path(tmp_path):
    """Variable-width columns can't cross the dense word-plane exchange:
    the runtime eligibility veto falls back, result still correct."""
    n = 800
    rng = np.random.default_rng(3)
    words = np.array(["ab", "cd", "ef", "gh"], dtype=object)
    pq.write_table(pa.table({"k": pa.array(words[rng.integers(0, 4, n)]),
                             "v": pa.array(rng.integers(0, 100, n) * 0.5)}),
                   tmp_path / "s.parquet")
    plan = Aggregate(Scan(tmp_path / "s.parquet"), ("k",),
                     (("v", "sum"),), ("total",))
    with _flags(fuse_exchange=True):
        opt = optimize(plan, distribute=True)
        before = _counter("engine.fused_stage.dispatches")
        out = execute(opt, new_stats())
        assert _counter("engine.fused_stage.dispatches") == before
    with _flags(fuse_exchange=False):
        ref = execute(optimize(plan, distribute=True), new_stats())
    a = _df(out).sort_values("k").reset_index(drop=True)
    b = _df(ref).sort_values("k").reset_index(drop=True)
    pd.testing.assert_frame_equal(a, b, check_exact=True)


# -- the jaxpr lint ---------------------------------------------------------


def test_lint_fused_stage_artifact(warehouse):
    with _flags(fuse_exchange=True):
        opt = optimize(_sandwich(warehouse), distribute=True)
        stage = sg.fused_sandwich(opt)
        inp = execute(stage.partial.child, new_stats())
        rep = lint_fused_stage(stage, inp)
    assert "skipped" not in rep
    assert rep["ok"], rep["violations"]
    assert rep["primitives"] > 0
