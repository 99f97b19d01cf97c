"""Adaptive query execution (SRJT_AQE=1, engine/adaptive.py).

Pins the three runtime rules and their shared discipline:

- **broadcast flip**: a planned hash exchange on a join build side runs as
  a broadcast when the MEASURED build row count lands under the runtime
  threshold — recorded (triggered or not) as ``adaptive:broadcast_flip``;
- **skew split**: hot destinations measured by the exchange counts pass
  are re-dealt round-robin with a provable per-(src, dest) capacity bound
  (an adversarial single hot key cannot overflow or lose rows), the
  post-delivery skew is folded back into the ledger entry, and a verified
  self-composable consumer gets a post-exchange partial-combine;
- **profile-warmed planning**: run 2 of a source fingerprint plans its
  broadcast-vs-shuffle choices from run 1's measured actuals
  (``adaptive:history_warmed``).

Every rule re-verifies through RewriteChecker before changing anything,
results stay bit-identical to the AQE-off single-device plan, and
``adaptive.reset`` keeps runtime entries from accumulating across
executions of a cached plan.
"""

import importlib.util
import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_jni_tpu.engine import (
    Aggregate, Filter, Join, Scan, adaptive, col, execute, lit, new_stats,
    optimize,
)
from spark_rapids_jni_tpu.engine.plan import Exchange, topo_nodes
from spark_rapids_jni_tpu.utils import config as cfg
from spark_rapids_jni_tpu.utils import metrics, profile

N_FACT = 8_000
N_DIM = 400


@pytest.fixture(scope="module")
def warehouse(tmp_path_factory):
    """Star schema with a HOT fact key: half the fact sits on one key, so
    hash placement concentrates half the wire onto one device."""
    root = tmp_path_factory.mktemp("aqe")
    rng = np.random.default_rng(7)
    k = rng.integers(0, N_DIM, N_FACT)
    k[: N_FACT // 2] = 3
    # int64 payload: sums are exact, so parity checks are == not approx
    fact = pa.table({
        "k": pa.array(k, pa.int64()),
        "v": pa.array(np.arange(N_FACT, dtype=np.int64)),
    })
    pq.write_table(fact, root / "fact.parquet", row_group_size=2_000)
    dk = np.arange(N_DIM, dtype=np.int64)
    dim = pa.table({"dk": pa.array(dk), "grp": pa.array(dk % 7)})
    pq.write_table(dim, root / "dim.parquet")
    return root


def _join_agg(root):
    j = Join(Scan(root / "fact.parquet", chunk_bytes=100_000),
             Scan(root / "dim.parquet"), ("k",), ("dk",), "inner")
    return Aggregate(j, ("grp",), (("v", "sum"),), ("total",))


def _as_df(table):
    out = pd.DataFrame({n: c.to_numpy()
                        for n, c in zip(table.names, table.columns)})
    return out.sort_values(table.names[0]).reset_index(drop=True)


def _aqe_env(monkeypatch, **flags):
    for k, v in flags.items():
        monkeypatch.setenv(k, str(v))
    cfg.refresh()


# -- config / eligibility ---------------------------------------------------

def test_flip_threshold_follows_broadcast_rows(monkeypatch):
    try:
        _aqe_env(monkeypatch, SRJT_BROADCAST_ROWS=123)
        assert adaptive.flip_threshold() == 123     # default -1: follow
        _aqe_env(monkeypatch, SRJT_AQE_BROADCAST_ROWS=7)
        assert adaptive.flip_threshold() == 7       # explicit knob wins
    finally:
        monkeypatch.delenv("SRJT_BROADCAST_ROWS")
        monkeypatch.delenv("SRJT_AQE_BROADCAST_ROWS")
        cfg.refresh()


def test_stamp_eligibility_marks_exchanges():
    build = Exchange(Scan("/tmp/d.parquet"), ("dk",), "hash")
    j = Join(Scan("/tmp/f.parquet"), build, ("k",), ("dk",), "inner")
    aggx = Exchange(j, ("grp",), "hash")
    plan = Aggregate(aggx, ("grp",), (("v", "sum"),), ("total",))
    adaptive.stamp_eligibility(plan)
    assert getattr(build, "_aqe_flip", False)        # join build side
    assert getattr(aggx, "_aqe_split", False)        # aggregate child
    assert getattr(aggx, "_aqe_combine") == \
        (("grp",), (("v", "sum"),), ("v",))
    assert not getattr(j.left, "_aqe_flip", False)   # probe side: never


def test_combine_spec_rules():
    ex = Exchange(Scan("/t"), ("g",), "hash")
    ok = Aggregate(ex, ("g",), (("a", "sum"), ("b", "min")), ("x", "y"))
    assert adaptive._combine_spec(ok) == \
        (("g",), (("a", "sum"), ("b", "min")), ("a", "b"))
    # mean does not self-compose; duplicate source cols would collide on
    # rename; a col shadowing a group key would corrupt the keys
    for bad in (
        Aggregate(ex, ("g",), (("a", "mean"),), ("x",)),
        Aggregate(ex, ("g",), (("a", "sum"), ("a", "max")), ("x", "y")),
        Aggregate(ex, ("g",), (("g", "sum"),), ("x",)),
        Aggregate(ex, (), (("a", "sum"),), ("x",)),
    ):
        assert adaptive._combine_spec(bad) is None


# -- skew-split planning (pure host math) -----------------------------------

def test_plan_skew_split_balanced_declines():
    node = Exchange(Scan("/t"), ("k",), "hash")
    counts = np.full((8, 8), 100, dtype=np.int64)
    split, cap, st = adaptive.plan_skew_split(node, counts, 8)
    assert split is None and cap is None
    assert st["skew"] == 1.0


def test_plan_skew_split_hot_dest_capacity_bound(monkeypatch):
    node = Exchange(Scan("/t"), ("k",), "hash")
    counts = np.full((8, 8), 10, dtype=np.int64)
    counts[:, 2] = 500                       # one hot destination
    split, cap, st = adaptive.plan_skew_split(node, counts, 8)
    assert split is not None and split[0] == (2,)
    assert 0 <= split[1] < 8                 # salt is a device index
    # the round-robin deal bounds every (src, dest) cell at base +
    # ceil(hot_per_src / ndev) — the capacity the executor projects
    assert cap == 10 + -(-500 // 8)
    assert st["skew"] > float(cfg.config.aqe_skew)


# -- shuffle-level split: adversarial single hot key ------------------------

def test_skew_split_single_key_no_row_loss(monkeypatch):
    """Every row of the shuffle carries ONE key: without the split all
    1600 rows land on one device; with it they re-deal evenly, nothing
    overflows the PROJECTED capacity, and no row is lost or duplicated."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.parallel import shuffle as sh
    from spark_rapids_jni_tpu.parallel.mesh import (
        make_mesh, pad_to_multiple, shard_table,
    )
    try:
        _aqe_env(monkeypatch, SRJT_AQE_SKEW=1.5)
        ndev = 8
        mesh = make_mesh(ndev)
        pool = np.arange(4096, dtype=np.int64)
        dests = np.asarray(sh.partition_ids(
            Table([Column.from_numpy(pool)], ["k"]), ndev))
        hotkey = pool[dests == 2][0]
        n = 1600
        t = Table([Column.from_numpy(np.full(n, hotkey, np.int64)),
                   Column.from_numpy(np.arange(n, dtype=np.int64))],
                  ["k", "v"])
        padded, nlive = pad_to_multiple(t, ndev)
        live = jax.device_put(jnp.arange(padded.num_rows) < nlive)
        stt = shard_table(padded, mesh)
        counts = sh.partition_counts(stt, mesh, ["k"], n_valid_rows=n)
        node = Exchange(Scan("/tmp/x.parquet"), ("k",), "hash")
        split, cap_need, st = adaptive.plan_skew_split(node, counts, ndev)
        assert split is not None and st["skew"] == pytest.approx(8.0)
        out, ok, ovf = sh.shuffle_table_padded(
            stt, mesh, ["k"], capacity=sh.cap_bucket(cap_need),
            live=live, split=split)
        assert int(np.asarray(ovf)) == 0
        keep = np.asarray(ok)
        per_dest = keep.reshape(ndev, ndev, -1).sum(axis=(1, 2))
        assert per_dest.sum() == n
        # the staggered deal spreads the single key across ALL devices
        assert per_dest.max() <= -(-n // ndev) + ndev
        vv = np.asarray(out.columns[1].data)[keep]
        assert sorted(vv.tolist()) == list(range(n))   # no loss, no dup
    finally:
        monkeypatch.delenv("SRJT_AQE_SKEW")
        cfg.refresh()


def test_shuffle_split_requires_projected_capacity():
    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.parallel import shuffle as sh
    from spark_rapids_jni_tpu.parallel.mesh import (
        make_mesh, pad_to_multiple, shard_table,
    )
    mesh = make_mesh(8)
    t = Table([Column.from_numpy(np.arange(64, dtype=np.int64))], ["k"])
    padded, _ = pad_to_multiple(t, 8)
    stt = shard_table(padded, mesh)
    with pytest.raises(ValueError, match="projected capacity"):
        sh.shuffle_table_padded(stt, mesh, ["k"], split=((2,), 0))


# -- end-to-end: flip + split + combine, with parity ------------------------

def test_aqe_rules_fire_with_parity(warehouse, monkeypatch):
    """Hash-planned join over the hot-key fact: the flip rule replaces the
    build exchange at runtime, the split rule re-deals the partial-agg
    exchange's hot destination, the combine collapses it back, and the
    result is exactly the single-device answer."""
    base = execute(optimize(_join_agg(warehouse)), new_stats())
    try:
        _aqe_env(monkeypatch, SRJT_AQE=1, SRJT_AQE_SKEW=1.5,
                 SRJT_BROADCAST_ROWS=0,            # plan every join hash
                 SRJT_AQE_BROADCAST_ROWS=1_000_000)  # ...flip at runtime
        opt = optimize(_join_agg(warehouse), distribute=True)
        stats = new_stats()
        out = execute(opt, stats)
        assert stats["aqe_flips"] >= 1
        assert stats["aqe_splits"] >= 1
        rt = adaptive.runtime_entries(opt)
        (flip,) = [d for d in rt if d["kind"] == "adaptive:broadcast_flip"
                   and d["triggered"]]
        assert flip["measured_rows"] == N_DIM
        assert (flip["before"], flip["after"]) == ("hash", "broadcast")
        assert flip["path"]
        (split,) = [d for d in rt if d["kind"] == "adaptive:skew_split"
                    and d["triggered"]]
        assert split["measured_skew"] > 1.5
        assert split["hot_devices"]
        # post-delivery proof folded back in: the re-deal flattened the
        # hot destination, and the partial-combine collapsed the
        # scattered groups (7 grp values) back to one row each
        assert split["post_skew"] is not None
        assert split["post_skew"] < split["measured_skew"]
        assert split["combine"] is True and split["combined_rows"] == 7
        pd.testing.assert_frame_equal(_as_df(out), _as_df(base))
    finally:
        for k in ("SRJT_AQE", "SRJT_AQE_SKEW", "SRJT_BROADCAST_ROWS",
                  "SRJT_AQE_BROADCAST_ROWS"):
            monkeypatch.delenv(k)
        cfg.refresh()


def test_skew_split_brings_skew_gauge_under_threshold(warehouse,
                                                     monkeypatch):
    """A non-decomposable mean sends the whole hot-key fact through the
    exchange: the split is applied, the exchange's post-placement skew
    gauge lands under the trigger threshold, and the answer is the
    AQE-off single-device one."""
    if not metrics.enabled():
        pytest.skip("SRJT_METRICS off")

    def meanplan():
        return Aggregate(Scan(warehouse / "fact.parquet"), ("k",),
                         (("v", "mean"),), ("m",))

    base = execute(optimize(meanplan()), new_stats())
    try:
        _aqe_env(monkeypatch, SRJT_AQE=1, SRJT_AQE_SKEW=2.0)
        opt = optimize(meanplan(), distribute=True)
        stats = new_stats()
        out = execute(opt, stats)
        gauge = metrics.gauges_snapshot("engine.exchange.skew")[
            "engine.exchange.skew"]
        splits = [d for d in adaptive.runtime_entries(opt)
                  if d["kind"] == "adaptive:skew_split" and d["triggered"]]
        assert splits and stats["aqe_splits"] == len(splits)
        assert splits[0]["measured_skew"] > 2.0
        assert gauge < 2.0
        pd.testing.assert_frame_equal(_as_df(out), _as_df(base))
    finally:
        for k in ("SRJT_AQE", "SRJT_AQE_SKEW"):
            monkeypatch.delenv(k)
        cfg.refresh()


def test_aqe_declines_are_recorded_not_applied(warehouse, monkeypatch):
    """Thresholds that nothing crosses: the rules are consulted and
    recorded (triggered=no) but the planned strategies execute."""
    try:
        _aqe_env(monkeypatch, SRJT_AQE=1, SRJT_BROADCAST_ROWS=0,
                 SRJT_AQE_BROADCAST_ROWS=10)   # dim (400 rows) stays hash
        opt = optimize(_join_agg(warehouse), distribute=True)
        stats = new_stats()
        execute(opt, stats)
        assert stats.get("aqe_flips", 0) == 0
        assert stats.get("aqe_splits", 0) == 0   # default skew 4.0 holds
        rt = adaptive.runtime_entries(opt)
        assert rt and all(not d["triggered"] for d in rt)
    finally:
        for k in ("SRJT_AQE", "SRJT_BROADCAST_ROWS",
                  "SRJT_AQE_BROADCAST_ROWS"):
            monkeypatch.delenv(k)
        cfg.refresh()


def test_aqe_off_leaves_no_runtime_entries(warehouse, monkeypatch):
    try:
        _aqe_env(monkeypatch, SRJT_BROADCAST_ROWS=0)
        opt = optimize(_join_agg(warehouse), distribute=True)
        stats = new_stats()
        execute(opt, stats)
        assert stats.get("aqe_flips", 0) == 0
        assert adaptive.runtime_entries(opt) == []
    finally:
        monkeypatch.delenv("SRJT_BROADCAST_ROWS")
        cfg.refresh()


def test_reset_strips_runtime_entries_across_executions(warehouse,
                                                        monkeypatch):
    """PlanCache re-executes the same optimized plan object: runtime
    entries must not accumulate run over run."""
    try:
        _aqe_env(monkeypatch, SRJT_AQE=1, SRJT_AQE_SKEW=1.5,
                 SRJT_BROADCAST_ROWS=0, SRJT_AQE_BROADCAST_ROWS=1_000_000)
        opt = optimize(_join_agg(warehouse), distribute=True)
        execute(opt, new_stats())
        first = adaptive.runtime_entries(opt)
        execute(opt, new_stats())
        assert len(adaptive.runtime_entries(opt)) == len(first)
    finally:
        for k in ("SRJT_AQE", "SRJT_AQE_SKEW", "SRJT_BROADCAST_ROWS",
                  "SRJT_AQE_BROADCAST_ROWS"):
            monkeypatch.delenv(k)
        cfg.refresh()


# -- profile-warmed planning ------------------------------------------------

def test_history_overrides_queue(monkeypatch):
    fake = {"runs": 2, "decisions": [
        {"kind": "shuffle", "side": "left", "actual_rows": 999},
        {"kind": "broadcast", "actual_rows": 40, "est_rows": 40},
        {"kind": "partial_agg"},
        {"kind": "shuffle", "side": "right", "actual_rows": 50,
         "est_rows": 500},
    ]}
    monkeypatch.setattr(profile, "history", lambda fp, **kw: dict(fake))
    warm = adaptive.history_overrides("f" * 64)
    assert warm["runs"] == 2
    # only build-side placements queue: broadcast + shuffle(side=right)
    assert [b["prior_kind"] for b in warm["builds"]] == \
        ["broadcast", "shuffle"]
    assert adaptive.next_build_actual(warm)["actual_rows"] == 40
    assert adaptive.next_build_actual(warm)["actual_rows"] == 50
    assert adaptive.next_build_actual(warm) is None      # exhausted
    assert adaptive.next_build_actual(None) is None
    monkeypatch.setattr(profile, "history", lambda fp, **kw: None)
    assert adaptive.history_overrides("f" * 64) is None


def test_history_warms_rerun_to_broadcast(warehouse, tmp_path,
                                          monkeypatch):
    """Run 1 plans a shuffle join from the footer estimate (400 dim rows >
    threshold 100); its profile records the MEASURED build (50 rows after
    the filter).  Run 2 of the same source plan reads that actual and
    plans the broadcast join outright, with identical results."""
    try:
        _aqe_env(monkeypatch, SRJT_AQE=1, SRJT_METRICS=1,
                 SRJT_PROFILE_DIR=str(tmp_path), SRJT_BROADCAST_ROWS=100)

        def mkplan():
            dim = Filter(Scan(warehouse / "dim.parquet"),
                         ("<", col("dk"), lit(50)))
            j = Join(Scan(warehouse / "fact.parquet", chunk_bytes=100_000),
                     dim, ("k",), ("dk",), "inner")
            return Aggregate(j, ("grp",), (("v", "sum"),), ("total",))

        def run(name):
            opt = optimize(mkplan(), distribute=True)
            with metrics.query(name):
                out = execute(opt, new_stats())
            kinds = sorted(e.kind for e in topo_nodes(opt)
                           if isinstance(e, Exchange))
            return opt, out, kinds

        opt1, out1, kinds1 = run("aqe-warm-1")
        opt2, out2, kinds2 = run("aqe-warm-2")
        assert "broadcast" not in kinds1
        assert "broadcast" in kinds2
        assert getattr(opt1, "_source_fingerprint") == \
            getattr(opt2, "_source_fingerprint")
        (warm,) = [d for d in getattr(opt2, "_decisions", ())
                   if d.get("kind") == "adaptive:history_warmed"]
        assert warm["choice"] == "broadcast"
        assert warm["est_before"] == N_DIM       # the footer estimate
        assert warm["est_rows"] == 50            # run 1's measured actual
        assert warm["prior_kind"] == "shuffle"
        assert warm["threshold"] == 100
        # run 1's ledger carries no warmed entry — nothing to warm from
        assert not [d for d in getattr(opt1, "_decisions", ())
                    if d.get("kind") == "adaptive:history_warmed"]
        pd.testing.assert_frame_equal(_as_df(out1), _as_df(out2))
    finally:
        for k in ("SRJT_AQE", "SRJT_METRICS", "SRJT_PROFILE_DIR",
                  "SRJT_BROADCAST_ROWS"):
            monkeypatch.delenv(k)
        cfg.refresh()


# -- rendering --------------------------------------------------------------

def test_explain_decision_line_renders_adaptive_fields():
    from spark_rapids_jni_tpu.engine.explain import _decision_line
    flip = _decision_line({
        "kind": "adaptive:broadcast_flip", "path": "root.child.right",
        "runtime": True, "triggered": True, "before": "hash",
        "after": "broadcast", "measured_rows": 42, "threshold": 100,
    }, {})
    assert "adaptive:broadcast_flip" in flip
    assert "triggered=yes" in flip and "hash->broadcast" in flip
    assert "measured_rows=42" in flip
    split = _decision_line({
        "kind": "adaptive:skew_split", "path": "root.child",
        "runtime": True, "triggered": True, "measured_skew": 5.5,
        "post_skew": 1.12, "hot_devices": [2, 5], "combine": True,
        "combined_rows": 7, "threshold": 4.0,
    }, {})
    assert "measured_skew=5.50" in split and "post_skew=1.12" in split
    assert "hot_devices=2,5" in split and "combined_rows=7" in split
    declined = _decision_line({
        "kind": "adaptive:skew_split", "path": "root.child",
        "runtime": True, "triggered": False, "measured_skew": 1.2,
        "threshold": 4.0, "verify_rejected": True,
    }, {})
    assert "triggered=no" in declined
    warm = _decision_line({
        "kind": "adaptive:history_warmed", "est_before": 400,
        "est_rows": 50, "choice": "broadcast", "prior_kind": "shuffle",
        "runs": 1, "threshold": 100,
    }, {})
    assert "est_before=400" in warm and "est_rows=50" in warm
    assert "choice=broadcast" in warm and "prior_kind=shuffle" in warm


def test_profile_cli_decisions_renders_adaptive(tmp_path, monkeypatch,
                                                capsys):
    try:
        _aqe_env(monkeypatch, SRJT_METRICS=1)
        with metrics.query("aqe-cli") as qm:
            qm.fingerprint = "ab" * 32
            qm.set_decisions([
                {"kind": "adaptive:skew_split", "path": "root.child",
                 "runtime": True, "triggered": True, "measured_skew": 6.1,
                 "post_skew": 1.14, "hot_devices": [3], "combine": True,
                 "combined_rows": 7, "threshold": 4.0},
                {"kind": "adaptive:broadcast_flip", "path": "root.right",
                 "runtime": True, "triggered": False, "measured_rows": 900,
                 "threshold": 100, "before": "hash", "after": "hash",
                 "verify_rejected": True},
            ])
        profile.write(metrics.recent_summaries()[-1],
                      dir_path=str(tmp_path))
    finally:
        monkeypatch.delenv("SRJT_METRICS")
        cfg.refresh()
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "srjt_profile.py")
    spec = importlib.util.spec_from_file_location("srjt_profile_cli", path)
    cli = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli)
    assert cli.main(["--dir", str(tmp_path), "decisions", "-1"]) == 0
    out = capsys.readouterr().out
    assert "adaptive:skew_split" in out and "triggered=yes" in out
    assert "measured_skew=6.10" in out and "post_skew=1.14" in out
    assert "hot_devices=3" in out and "combined_rows=7" in out
    assert "adaptive:broadcast_flip" in out and "triggered=no" in out
    assert "! VERIFY_REJECTED" in out
