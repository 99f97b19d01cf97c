"""Partitioning-aware distributed planning: Exchange placement, shuffle
elimination, broadcast-vs-shuffle join selection, and executor parity.

The planner half pins the rewrite contracts (where exchanges land, when
they're eliminated, how the broadcast threshold decides); the executor half
pins that both exchange kinds produce exactly the single-device result on
the 8-device virtual mesh, and that the static exchange census
(``verify.plan_exchanges``) always matches the executed count — the same
invariant ci/premerge.sh asserts on the bench smoke artifact.
"""

import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_jni_tpu.engine import (
    Aggregate, Filter, Join, Scan, col, execute, lit, new_stats, optimize,
)
from spark_rapids_jni_tpu.engine.plan import (
    Exchange, Partitioning, co_partitioned, deserialize, partitioning,
    topo_nodes,
)
from spark_rapids_jni_tpu.engine.verify import (
    PlanVerificationError, check_partitioning, plan_exchanges, verify,
)
from spark_rapids_jni_tpu.utils import config as cfg

N_FACT = 20_000
N_DIM = 500


@pytest.fixture(scope="module")
def warehouse(tmp_path_factory):
    root = tmp_path_factory.mktemp("dist")
    rng = np.random.default_rng(42)
    k = rng.integers(0, N_DIM, N_FACT)
    fact = pa.table({
        "k": pa.array(k, pa.int64()),
        "v": pa.array(np.round(rng.uniform(0, 100, N_FACT), 3),
                      pa.float64()),
    })
    pq.write_table(fact, root / "fact.parquet", row_group_size=4_000)
    dk = np.arange(N_DIM, dtype=np.int64)
    dim = pa.table({"dk": pa.array(dk), "grp": pa.array(dk % 7)})
    pq.write_table(dim, root / "dim.parquet")
    return root, fact.to_pandas(), dim.to_pandas()


def _join_agg(root, group="grp"):
    j = Join(Scan(root / "fact.parquet", chunk_bytes=100_000),
             Scan(root / "dim.parquet"), ("k",), ("dk",), "inner")
    return Aggregate(j, (group,), (("v", "sum"), ("v", "count")),
                     ("total", "n"))


def _exchanges(plan):
    return [n for n in topo_nodes(plan) if isinstance(n, Exchange)]


def _as_df(table):
    # to_numpy decodes FLOAT64 bit-pattern storage (dtypes.device_storage)
    out = pd.DataFrame({n: c.to_numpy()
                        for n, c in zip(table.names, table.columns)})
    return out.sort_values(table.names[0]).reset_index(drop=True)


# -- plan node -------------------------------------------------------------

def test_exchange_serialize_round_trip():
    e = Exchange(Scan("/tmp/x.parquet"), ("k", "j"), "hash")
    r = deserialize(e.serialize())
    assert isinstance(r, Exchange)
    assert r.keys == ("k", "j") and r.kind == "hash"
    assert r.fingerprint() == e.fingerprint()
    b = deserialize(Exchange(Scan("/tmp/x.parquet"),
                             kind="broadcast").serialize())
    assert b.kind == "broadcast" and b.keys == ()


def test_exchange_validates_kind_and_keys():
    with pytest.raises(ValueError):
        Exchange(Scan("/t"), ("k",), "range")
    with pytest.raises(ValueError):
        Exchange(Scan("/t"), (), "hash")
    with pytest.raises(ValueError):
        Exchange(Scan("/t"), ("k",), "broadcast")


def test_scan_serialization_backward_compatible():
    """Default scans serialize without the new field, so fingerprints of
    plans from earlier engine versions are unchanged."""
    import json
    blob = json.loads(Scan("/tmp/x.parquet").serialize())
    assert all("partitioned_by" not in n for n in blob["nodes"])
    s = deserialize(Scan("/tmp/x.parquet",
                         partitioned_by=("k",)).serialize())
    assert s.partitioned_by == ("k",)


def test_partitioning_propagation():
    base = Scan("/tmp/x.parquet")
    assert partitioning(base) == Partitioning("none", ())
    h = Exchange(base, ("k",), "hash")
    assert partitioning(h) == Partitioning("hash", ("k",))
    # filter preserves placement; a project keeping the key preserves,
    # one dropping it does not
    from spark_rapids_jni_tpu.engine.plan import Filter as F, Project
    assert partitioning(F(h, (">", col("k"), lit(0)))).kind == "hash"
    assert partitioning(Project(h, ("k", "v"))).keys == ("k",)
    assert partitioning(Project(h, ("v",))).kind == "none"
    # aggregate grouping on the placement key preserves it
    agg = Aggregate(h, ("k",), (("v", "sum"),), ("t",))
    assert partitioning(agg) == Partitioning("hash", ("k",))
    # declared scan partitioning
    s = Scan("/tmp/x.parquet", partitioned_by=("k",))
    assert partitioning(s) == Partitioning("hash", ("k",))


def test_co_partitioned_is_positional():
    lp = Partitioning("hash", ("k",))
    rp = Partitioning("hash", ("dk",))
    assert co_partitioned(lp, rp, ("k",), ("dk",))
    assert not co_partitioned(lp, rp, ("dk",), ("k",))
    assert not co_partitioned(Partitioning("none", ()), rp, ("k",), ("dk",))


# -- optimizer rules -------------------------------------------------------

def test_broadcast_threshold_picks_join_strategy(warehouse, monkeypatch):
    root, _, _ = warehouse
    # dim (500 rows) under the default 100k threshold: broadcast build +
    # one hash exchange on the aggregate partials
    opt = optimize(_join_agg(root), distribute=True)
    kinds = sorted(e.kind for e in _exchanges(opt))
    assert kinds == ["broadcast", "hash"]
    join = [n for n in topo_nodes(opt) if isinstance(n, Join)][0]
    assert isinstance(join.right, Exchange)
    assert join.right.kind == "broadcast"

    # threshold 0 forces the shuffle join: both sides hash-exchange on the
    # join keys, plus the partial-agg exchange
    monkeypatch.setenv("SRJT_BROADCAST_ROWS", "0")
    cfg.refresh()
    try:
        opt = optimize(_join_agg(root), distribute=True)
        assert sorted(e.kind for e in _exchanges(opt)) == 3 * ["hash"]
        join = [n for n in topo_nodes(opt) if isinstance(n, Join)][0]
        assert isinstance(join.left, Exchange)
        assert join.left.keys == ("k",)
        assert isinstance(join.right, Exchange)
        assert join.right.keys == ("dk",)
    finally:
        monkeypatch.delenv("SRJT_BROADCAST_ROWS")
        cfg.refresh()


def test_partial_aggregation_pushed_below_exchange(warehouse):
    """Decomposable aggs split: partial below the hash exchange, combine
    above — only per-device partial rows cross the wire."""
    root, _, _ = warehouse
    opt = optimize(_join_agg(root), distribute=True)
    combine = opt
    assert isinstance(combine, Aggregate)
    assert isinstance(combine.child, Exchange)
    partial = combine.child.child
    assert isinstance(partial, Aggregate)
    assert partial.keys == combine.keys == ("grp",)
    assert partial.aggs == (("v", "sum"), ("v", "count"))
    # count partials combine by sum
    assert combine.aggs == (("total", "sum"), ("n", "sum"))


def test_non_decomposable_agg_exchanges_full_input(warehouse):
    root, _, _ = warehouse
    j = Join(Scan(root / "fact.parquet"), Scan(root / "dim.parquet"),
             ("k",), ("dk",), "inner")
    plan = Aggregate(j, ("grp",), (("v", "mean"),), ("avg_v",))
    opt = optimize(plan, distribute=True)
    assert isinstance(opt, Aggregate)
    assert isinstance(opt.child, Exchange)
    assert opt.child.kind == "hash"
    # no partial: the exchange feeds the join output straight in
    assert not isinstance(opt.child.child, Aggregate)


def test_shuffle_elimination_on_co_partitioned_input(warehouse):
    """The acceptance criterion: scans declared co-partitioned on the join
    keys plan with ZERO exchanges when the aggregate groups on the
    partition key — verified and counted statically."""
    root, _, _ = warehouse
    j = Join(Scan(root / "fact.parquet", partitioned_by=("k",)),
             Scan(root / "dim.parquet", partitioned_by=("dk",)),
             ("k",), ("dk",), "inner")
    plan = Aggregate(j, ("k",), (("v", "sum"),), ("total",))
    opt = optimize(plan, distribute=True)
    assert len(_exchanges(opt)) == 0
    assert plan_exchanges(opt) == []
    verify(opt)
    check_partitioning(opt)


def test_co_partitioned_plan_executes_no_exchange(warehouse):
    """What the static census says of the co-partitioned plan is what
    runs: zero exchanges executed, and the single-device answer."""
    root, _, _ = warehouse

    def plan(**part):
        j = Join(Scan(root / "fact.parquet", **part.get("f", {})),
                 Scan(root / "dim.parquet", **part.get("d", {})),
                 ("k",), ("dk",), "inner")
        return Aggregate(j, ("k",), (("v", "sum"),), ("total",))

    opt = optimize(plan(f={"partitioned_by": ("k",)},
                        d={"partitioned_by": ("dk",)}), distribute=True)
    stats = new_stats()
    out = _as_df(execute(opt, stats))
    assert stats["exchanges"] == len(plan_exchanges(opt)) == 0
    base = _as_df(execute(optimize(plan()), new_stats()))
    pd.testing.assert_frame_equal(out, base, atol=1e-6)


def test_order_sensitive_agg_stays_single_stream(warehouse):
    """first/last/collect_list results depend on input row order, which
    the hash exchange does not preserve — the planner must leave their
    whole subtree as the original single stream (no Exchange anywhere),
    so distributed results stay identical to single-device execution."""
    root, _, _ = warehouse
    j = Join(Scan(root / "fact.parquet"), Scan(root / "dim.parquet"),
             ("k",), ("dk",), "inner")
    for op in ("first", "last", "collect_list"):
        plan = Aggregate(j, ("grp",), (("v", op),), ("x",))
        opt = optimize(plan, distribute=True)
        assert _exchanges(opt) == [], op
    # mixed with decomposable ops: still order-sensitive, still no split
    mixed = Aggregate(j, ("grp",), (("v", "sum"), ("v", "first")),
                      ("total", "f"))
    opt = optimize(mixed, distribute=True)
    assert _exchanges(opt) == []
    assert isinstance(opt, Aggregate) and opt.aggs == mixed.aggs
    # ungrouped order-sensitive aggs must not see exchanges below either
    ungrouped = Aggregate(j, (), (("v", "first"),), ("f",))
    assert _exchanges(optimize(ungrouped, distribute=True)) == []
    # parity: the distributed plan IS the single-stream plan
    plan = Aggregate(j, ("grp",), (("v", "first"),), ("f",))
    base = _as_df(execute(optimize(plan), new_stats()))
    out = _as_df(execute(optimize(plan, distribute=True), new_stats()))
    pd.testing.assert_frame_equal(out, base)


def test_redundant_exchange_eliminated(warehouse):
    """A hand-placed exchange over an identically-placed child folds away;
    back-to-back exchanges collapse to the outer placement."""
    root, _, _ = warehouse
    s = Scan(root / "fact.parquet", partitioned_by=("k",))
    opt = optimize(Exchange(s, ("k",), "hash"))
    assert len(_exchanges(opt)) == 0
    stacked = Exchange(Exchange(Scan(root / "fact.parquet"), ("v",),
                                "hash"),
                       ("k",), "hash")
    opt = optimize(stacked)
    ex = _exchanges(opt)
    assert len(ex) == 1 and ex[0].keys == ("k",)


# -- verify ----------------------------------------------------------------

def test_infer_exchange_checks_keys(warehouse):
    root, _, _ = warehouse
    verify(Exchange(Scan(root / "fact.parquet"), ("k",), "hash"))
    with pytest.raises(PlanVerificationError, match="unknown-column"):
        verify(Exchange(Scan(root / "fact.parquet"), ("nope",), "hash"))


def test_check_partitioning_flags_mismatched_join(warehouse):
    root, _, _ = warehouse
    bad = Join(Exchange(Scan(root / "fact.parquet"), ("v",), "hash"),
               Exchange(Scan(root / "dim.parquet"), ("dk",), "hash"),
               ("k",), ("dk",), "inner")
    with pytest.raises(PlanVerificationError, match="partitioning-mismatch"):
        check_partitioning(bad)


def test_check_partitioning_flags_split_groups(warehouse):
    root, _, _ = warehouse
    bad = Aggregate(Exchange(Scan(root / "fact.parquet"), ("v",), "hash"),
                    ("k",), (("v", "sum"),), ("t",))
    with pytest.raises(PlanVerificationError, match="partitioning-mismatch"):
        check_partitioning(bad)


def test_check_partitioning_accepts_partial_aggregate(warehouse):
    """An aggregate feeding an exchange is a partial by construction: its
    per-device split groups must NOT be flagged."""
    root, _, _ = warehouse
    opt = optimize(_join_agg(root), distribute=True)
    check_partitioning(opt)  # must not raise


def test_sync_budget_covers_exchanges(warehouse):
    from spark_rapids_jni_tpu.engine.verify import sync_budget
    root, _, _ = warehouse
    plan = Aggregate(Exchange(Scan(root / "fact.parquet"), ("k",), "hash"),
                     ("k",), (("v", "sum"),), ("t",))
    sites = [e["site"] for e in sync_budget(plan)]
    assert "exchange-counts-sizing" in sites
    assert "exchange-compaction" in sites


# -- executor parity -------------------------------------------------------

def test_distributed_results_match_single_device(warehouse, monkeypatch):
    root, fact_df, dim_df = warehouse
    oracle = (fact_df.merge(dim_df, left_on="k", right_on="dk")
              .groupby("grp")
              .agg(total=("v", "sum"), n=("v", "count"))
              .reset_index().sort_values("grp").reset_index(drop=True))
    oracle["n"] = oracle["n"].astype(np.int64)

    base = _as_df(execute(optimize(_join_agg(root)), new_stats()))
    pd.testing.assert_frame_equal(base, oracle, check_dtype=False,
                                  atol=1e-6)

    # broadcast plan
    opt = optimize(_join_agg(root), distribute=True)
    stats = new_stats()
    out = _as_df(execute(opt, stats))
    pd.testing.assert_frame_equal(out, base, atol=1e-6)
    assert stats["exchanges"] == len(plan_exchanges(opt)) == 2

    # hash-exchange plan
    monkeypatch.setenv("SRJT_BROADCAST_ROWS", "0")
    cfg.refresh()
    try:
        opt = optimize(_join_agg(root), distribute=True)
        stats = new_stats()
        out = _as_df(execute(opt, stats))
        pd.testing.assert_frame_equal(out, base, atol=1e-6)
        assert stats["exchanges"] == len(plan_exchanges(opt)) == 3
    finally:
        monkeypatch.delenv("SRJT_BROADCAST_ROWS")
        cfg.refresh()


def test_multi_chunk_exchange_survives_boundary_skew(tmp_path, monkeypatch):
    """A chunk's contiguous shard can straddle a whole-table shard
    boundary, so its per-(src, dest) count can reach the SUM of two global
    pair counts: 128 same-destination rows centered on the first table
    shard boundary split 64/64 across the global (src, dest) pairs but all
    land in one chunk shard — a capacity sized from the global max alone
    overflows on this valid input."""
    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.engine import executor as ex
    from spark_rapids_jni_tpu.parallel.shuffle import partition_ids

    n, chunk_rows = 1536, 1024        # 2 chunks; table shard = 192 rows
    pool = np.arange(4096, dtype=np.int64)
    dests = np.asarray(partition_ids(
        Table([Column.from_numpy(pool)], ["k"]), 8))
    hot = pool[dests == dests[0]]     # keys all placed on one destination
    cold = pool[dests != dests[0]]
    k = cold[np.arange(n) % len(cold)]
    # hot rows at [128, 256): inside chunk 0's shard 1 ([128, 256) at
    # chunk-shard size 128) but split 64/64 by the table boundary at 192
    k[128:256] = hot[np.arange(128) % len(hot)]
    v = np.arange(n, dtype=np.int64)
    pq.write_table(pa.table({"k": pa.array(k), "v": pa.array(v)}),
                   tmp_path / "skew.parquet")
    monkeypatch.setattr(ex, "_EXCHANGE_CHUNK_ROWS", chunk_rows)
    plan = Aggregate(Exchange(Scan(tmp_path / "skew.parquet"), ("k",),
                              "hash"),
                     ("k",), (("v", "sum"),), ("t",))
    stats = new_stats()
    out = _as_df(execute(optimize(plan), stats))
    assert stats["exchanges"] == 1
    oracle = (pd.DataFrame({"k": k, "v": v}).groupby("k")
              .agg(t=("v", "sum")).reset_index()
              .sort_values("k").reset_index(drop=True))
    pd.testing.assert_frame_equal(out, oracle, check_dtype=False)


def test_string_key_exchange_places_spark_exact(tmp_path):
    """String keys hash their ORIGINAL UTF-8 bytes (Spark UTF8String
    murmur3) — invariant to the width the exchange explodes at, so
    placement matches Scan.partitioned_by's documented contract and
    co-partitioning claims over string keys stay meaningful."""
    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.parallel import shuffle as sh
    from spark_rapids_jni_tpu.parallel.stringplane import explode_strings

    vals = ["a", "bb", "ccc", "", "delta", "echo-echo",
            "a-much-longer-string-key"] * 3
    t = Table([Column.from_pylist(vals)], ["s"])
    ids = []
    for overrides in (None, {"s": 64}):
        exploded, plan = explode_strings(t, width_overrides=overrides)
        specs = sh.key_specs_for(exploded, ["s"], plan)
        assert specs[0][0] == "string"
        ids.append(np.asarray(
            sh.partition_ids_specs(exploded.columns, specs, 8)))
    np.testing.assert_array_equal(ids[0], ids[1])

    # end-to-end: a string-keyed hash exchange executes and reassembles
    words = np.array(["alpha", "bravo", "charlie", "delta", "echo"])
    s = words[np.arange(400) % 5]
    v = np.arange(400, dtype=np.int64)
    pq.write_table(pa.table({"s": pa.array(s), "v": pa.array(v)}),
                   tmp_path / "s.parquet")
    plan = Aggregate(Exchange(Scan(tmp_path / "s.parquet"), ("s",), "hash"),
                     ("s",), (("v", "sum"),), ("t",))
    stats = new_stats()
    out = execute(optimize(plan), stats)
    assert stats["exchanges"] == 1
    got = (pd.DataFrame({"s": out.columns[0].to_pylist(),
                         "t": out.columns[1].to_numpy()})
           .sort_values("s").reset_index(drop=True))
    oracle = (pd.DataFrame({"s": s, "v": v}).groupby("s")
              .agg(t=("v", "sum")).reset_index()
              .sort_values("s").reset_index(drop=True))
    pd.testing.assert_frame_equal(got, oracle, check_dtype=False)


# -- per-device exchange attribution (docs/OBSERVABILITY.md) ----------------

def test_device_load_stats_balanced_skewed_empty():
    """The shared skew/straggler helper both the shuffle counts pass and
    the executor report through: 1.0 balanced, ndev on one-destination,
    and a zero-row exchange is balanced by definition (no 0/0)."""
    from spark_rapids_jni_tpu.parallel.shuffle import device_load_stats
    st = device_load_stats(np.full(8, 25, np.int64))
    assert st["skew"] == 1.0 and st["straggler_share"] == 0.0
    assert st["max_dev_rows"] == 25 and st["total_rows"] == 200
    hot = np.zeros(8, np.int64)
    hot[3] = 160
    st = device_load_stats(hot)
    assert st["skew"] == 8.0
    assert st["straggler_share"] == pytest.approx(7 / 8)
    assert st["dev_rows"][3] == st["max_dev_rows"] == 160
    st = device_load_stats(np.zeros(8, np.int64))
    assert st["skew"] == 1.0 and st["straggler_share"] == 0.0


def test_exchange_wire_matrix_sums_to_counter(warehouse, monkeypatch):
    """The acceptance invariant ci/premerge.sh asserts on the smoke
    artifact: summing every exchange's per-(src, dest) wire matrix
    reproduces the query's engine.exchange.wire_bytes counter exactly,
    and the derived per-device columns are internally consistent."""
    from spark_rapids_jni_tpu.utils import metrics
    if not metrics.enabled():
        pytest.skip("SRJT_METRICS off")
    root, _, _ = warehouse
    monkeypatch.setenv("SRJT_BROADCAST_ROWS", "0")
    cfg.refresh()
    try:
        with metrics.query("dist-attrib") as qm:
            execute(optimize(_join_agg(root), distribute=True), new_stats())
    finally:
        monkeypatch.delenv("SRJT_BROADCAST_ROWS")
        cfg.refresh()
    summ = qm.summary()
    ex = [n for n in summ["nodes"] if n.get("wire_matrix")]
    assert len(ex) == 3      # both join sides + the partial-agg exchange
    total = sum(sum(map(sum, n["wire_matrix"])) for n in ex)
    assert total == summ["counters"]["engine.exchange.wire_bytes"]
    for n in ex:
        rows = np.asarray(n["rows_matrix"])
        assert rows.shape == (8, 8)
        # dev_rows IS the matrix's per-destination column sum
        np.testing.assert_array_equal(rows.sum(axis=0), n["dev_rows"])
        assert n["max_dev_rows"] == max(n["dev_rows"])
        assert n["skew"] >= 1.0
        assert 0.0 <= n["straggler_share"] < 1.0


def test_exchange_skew_balanced_vs_skewed(tmp_path, metrics_isolation):
    """skew == 1.0 when every destination receives the same row count;
    == ndev (and straggler_share (ndev-1)/ndev) when a seeded hot key
    routes every row to one device.  Gauges mirror the span fields."""
    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.parallel.shuffle import partition_ids
    from spark_rapids_jni_tpu.utils import metrics
    if not metrics.enabled():
        pytest.skip("SRJT_METRICS off")
    metrics_isolation("engine.exchange")

    pool = np.arange(4096, dtype=np.int64)
    dests = np.asarray(partition_ids(
        Table([Column.from_numpy(pool)], ["k"]), 8))
    # one representative key per destination device
    reps = np.array([pool[dests == d][0] for d in range(8)])

    def run(keys, name):
        v = np.arange(len(keys), dtype=np.int64)
        p = tmp_path / f"{name}.parquet"
        pq.write_table(pa.table({"k": pa.array(keys), "v": pa.array(v)}), p)
        plan = Aggregate(Exchange(Scan(p), ("k",), "hash"),
                         ("k",), (("v", "sum"),), ("t",))
        with metrics.query(name) as qm:
            execute(optimize(plan), new_stats())
        spans = [n for n in qm.summary()["nodes"] if n.get("rows_matrix")]
        assert len(spans) == 1
        return spans[0]

    bal = run(np.tile(reps, 200), "balanced")     # 200 rows per device
    assert bal["skew"] == 1.0
    assert bal["straggler_share"] == 0.0
    assert bal["dev_rows"] == [200] * 8

    hot = run(np.repeat(reps[2], 1600), "skewed")  # one destination
    assert hot["skew"] == 8.0
    assert hot["straggler_share"] == pytest.approx(7 / 8)
    assert hot["max_dev_rows"] == 1600
    assert hot["dev_rows"][int(dests[reps[2]])] == 1600
    from spark_rapids_jni_tpu.utils import metrics as m
    g = m.gauges_snapshot("engine.exchange")
    assert g["engine.exchange.skew"] == 8.0
    assert g["engine.exchange.max_dev_rows"] == 1600.0


def test_broadcast_exchange_attributed_balanced(warehouse):
    """A broadcast replicates the build to every device — structurally
    balanced, so its span reports skew 1.0 / dev_rows == num_rows on all
    lanes without any matrix (nothing is partitioned)."""
    from spark_rapids_jni_tpu.utils import metrics
    if not metrics.enabled():
        pytest.skip("SRJT_METRICS off")
    root, _, _ = warehouse
    with metrics.query("bcast-attrib") as qm:
        execute(optimize(_join_agg(root), distribute=True), new_stats())
    spans = [n for n in qm.summary()["nodes"]
             if n.get("skew") is not None and not n.get("rows_matrix")]
    assert spans, "broadcast exchange did not report device balance"
    b = spans[0]
    assert b["skew"] == 1.0 and b["straggler_share"] == 0.0
    assert b["max_dev_rows"] == N_DIM
    assert b["dev_rows"] == [N_DIM] * 8


def test_explain_analyze_renders_device_columns(warehouse):
    """EXPLAIN ANALYZE on the dist plan carries the per-device columns:
    skew, straggler share, max_dev_rows, and the dev_rows breakdown."""
    from spark_rapids_jni_tpu.engine.explain import explain_analyze
    root, _, _ = warehouse
    os.environ["SRJT_DIST"] = "1"
    cfg.refresh()
    try:
        rep = explain_analyze(_join_agg(root))
    finally:
        del os.environ["SRJT_DIST"]
        cfg.refresh()
    if not rep.summary:
        pytest.skip("SRJT_METRICS off")
    assert "skew=" in rep.text
    assert "straggler=" in rep.text
    assert "max_dev_rows=" in rep.text
    assert "dev_rows=[" in rep.text


def test_explain_analyze_renders_exchanges(warehouse):
    from spark_rapids_jni_tpu.engine.explain import explain_analyze
    root, _, _ = warehouse
    rep = explain_analyze(_join_agg(root))
    assert "Exchange" not in rep.text  # distribution off by default
    os.environ["SRJT_DIST"] = "1"
    cfg.refresh()
    try:
        rep = explain_analyze(_join_agg(root))
    finally:
        del os.environ["SRJT_DIST"]
        cfg.refresh()
    assert "Exchange(broadcast)" in rep.text
    assert "Exchange(hash, keys=['grp'])" in rep.text
    if rep.summary:  # metrics enabled in this session
        assert "wire_bytes=" in rep.text
        assert "exchanges=2" in rep.text
