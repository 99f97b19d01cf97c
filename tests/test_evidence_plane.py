"""The AQE evidence plane: decision ledger, est-vs-actual cardinality
tracking, and live query progress (ISSUE 12).

Three claims under test:

- the optimizer records WHY it shaped the plan (broadcast-vs-shuffle with
  the threshold and estimate it saw, partial-agg splits, TopK rewrites)
  and the ledger's structural entries match a static census of the final
  plan — the count can't drift from the plan shape;
- estimates meet actuals after the run: ``est_rows``/``q_error`` flow
  through EXPLAIN ANALYZE and the profile store, and ``profile.diff``
  flags a misestimate the base run didn't have;
- a second bridge connection can watch a running PLAN_EXECUTE's chunk
  progress (OP_QUERY_STATUS) without adding a single device sync to the
  execution hot path.
"""

import os
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_jni_tpu.engine import (Aggregate, Filter, Join, Scan,
                                         col, execute, lit, optimize)
from spark_rapids_jni_tpu.engine.explain import explain_analyze
from spark_rapids_jni_tpu.engine.plan import node_paths
from spark_rapids_jni_tpu.engine.verify import decision_census
from spark_rapids_jni_tpu.utils import config as cfg
from spark_rapids_jni_tpu.utils import faults, metrics, profile, tracing


@pytest.fixture(scope="module")
def warehouse(tmp_path_factory):
    root = tmp_path_factory.mktemp("evidence_wh")
    rng = np.random.default_rng(17)
    n = 4_000
    pq.write_table(pa.table({
        "k": pa.array(rng.integers(0, 40, n).astype(np.int64)),
        "v": pa.array(np.round(rng.uniform(-5.0, 50.0, n), 3)),
    }), root / "fact.parquet", row_group_size=500)
    pq.write_table(pa.table({
        "dk": pa.array(np.arange(0, 40, dtype=np.int64)),
        "dv": pa.array((np.arange(0, 40) % 5).astype(np.int64)),
    }), root / "dim.parquet")
    return root


def _join_agg(root, chunk_bytes=12_000):
    return Aggregate(
        Join(Filter(Scan(str(root / "fact.parquet"),
                         chunk_bytes=chunk_bytes),
                    (">", col("v"), lit(0.0))),
             Scan(str(root / "dim.parquet")), ["k"], ["dk"]),
        ["dv"], [("v", "sum"), (None, "count_all")], names=["s", "n"])


# -- decision ledger ---------------------------------------------------------


def test_decision_ledger_matches_census(warehouse):
    opt = optimize(_join_agg(warehouse), distribute=True)
    dec = getattr(opt, "_decisions", None)
    assert dec, "distributed optimize must record its decisions"
    kinds = {d["kind"] for d in dec}
    assert "broadcast" in kinds     # small dim side under the threshold
    assert "partial_agg" in kinds   # the agg split below its exchange
    # every structural decision carries a path that resolves to a real
    # node of the final plan, and the counts equal the static census
    paths = set(node_paths(opt).values())
    pathed = [d for d in dec if "path" in d]
    assert all(d["path"] in paths for d in pathed)
    census = decision_census(opt, dist=True)
    assert len(pathed) == len(census)
    assert sorted((d["kind"], d["path"]) for d in pathed) == \
        sorted((c["kind"], c["path"]) for c in census)
    # the broadcast entry explains itself: estimate vs threshold
    bd = next(d for d in dec if d["kind"] == "broadcast")
    assert bd["est_rows"] <= bd["threshold"]


def test_decision_ledger_topk_and_forced_shuffle(warehouse, monkeypatch):
    # the TopK rewrite (Limit-over-Sort fusion) is a recorded decision too
    from spark_rapids_jni_tpu.engine import Limit, Sort
    plan = Limit(Sort(_join_agg(warehouse), (("s", False),)), 3)
    opt = optimize(plan, distribute=True)
    dec = getattr(opt, "_decisions", ())
    assert any(d["kind"] == "topk" for d in dec)
    # forcing the broadcast threshold to zero flips the join decision to
    # shuffle, and the ledger says so (with the estimate that drove it)
    monkeypatch.setenv("SRJT_BROADCAST_ROWS", "0")
    cfg.refresh()
    try:
        opt2 = optimize(_join_agg(warehouse), distribute=True)
        dec2 = getattr(opt2, "_decisions", ())
        sides = {d.get("side") for d in dec2 if d["kind"] == "shuffle"}
        assert {"left", "right"} <= sides
        assert len([d for d in dec2 if "path" in d]) == \
            len(decision_census(opt2, dist=True))
    finally:
        monkeypatch.delenv("SRJT_BROADCAST_ROWS")
        cfg.refresh()


def test_single_device_plan_has_empty_ledger(warehouse):
    opt = optimize(_join_agg(warehouse), distribute=False)
    assert getattr(opt, "_decisions", []) == []
    assert decision_census(opt, dist=False) == []


# -- cardinality: est_rows stamps, q_error, unknown counter ------------------


def test_est_rows_stamped_on_every_node(warehouse, metrics_isolation):
    from spark_rapids_jni_tpu.engine.plan import topo_nodes
    metrics_isolation("engine.estimate")
    opt = optimize(_join_agg(warehouse), distribute=True)
    seen_known = seen_unknown = 0
    for n in topo_nodes(opt):
        assert hasattr(n, "_est_rows")
        if n._est_rows is None:
            seen_unknown += 1
        else:
            seen_known += 1
    assert seen_known > 0  # scans estimate from footer metadata
    # the planner admits what it can't estimate, and the counter agrees
    assert tracing.counter_value("engine.estimate.unknown") >= seen_unknown > 0


def test_q_error_definition():
    assert metrics.q_error(100, 400) == 4.0
    assert metrics.q_error(400, 100) == 4.0   # symmetric: max(e/a, a/e)
    assert metrics.q_error(40, 40) == 1.0
    assert metrics.q_error(None, 7) is None   # unknown estimate: no score
    assert metrics.q_error(0, 0) == 1.0       # zero clamps to one row
    assert metrics.q_error(10, 0) == 10.0


def test_explain_analyze_renders_evidence(warehouse):
    rep = explain_analyze(_join_agg(warehouse), fused=True, distribute=True)
    node_lines = [ln for ln in rep.text.splitlines()
                  if ln.strip() and not ln.lstrip().startswith("--")]
    assert node_lines
    for ln in node_lines:
        assert "est_rows=" in ln and "q_error=" in ln, ln
    # the footer renders every ledger entry, scored against actuals
    assert rep.decisions
    assert f"-- decisions ({len(rep.decisions)}):" in rep.text
    assert rep.text.count("\n--   ") == len(rep.decisions)
    bd = next(d for d in rep.decisions if d["kind"] == "broadcast")
    assert f"est_rows={bd['est_rows']}" in rep.text
    # the dim-side scan's estimate is exact (40 unique keys, no filter):
    # its node line must carry q_error=1.00
    dim_line = next(ln for ln in node_lines if "dim.parquet" in ln)
    assert "q_error=1.00" in dim_line
    # structured nodes carry the estimate for programmatic consumers
    assert any(n.get("est_rows") is not None for n in rep.nodes)


# -- profile store: persisted decisions, scoring, diff flag ------------------


def test_profile_persists_and_scores_decisions(warehouse):
    opt = optimize(_join_agg(warehouse), distribute=True)
    with metrics.query("evidence") as qm:
        execute(opt)
    prof = profile.compact(qm.summary())
    assert any(n.get("q_error") is not None for n in prof["nodes"])
    dec = prof.get("decisions")
    assert dec and len(dec) == len(getattr(opt, "_decisions"))
    bd = next(d for d in dec if d["kind"] == "broadcast")
    # the dim broadcast's estimate was exact: scored, not flagged
    assert bd["actual_rows"] == 40
    assert bd["q_error"] == 1.0
    assert bd["misestimate"] is False


def test_profile_store_keeps_dist_run_evidence(warehouse, tmp_path,
                                               monkeypatch):
    """A distributed run with hash exchanges, persisted by the store: the
    store's summary carries the exchanges' skew, and the stored profile
    its decision ledger and per-node q_error."""
    monkeypatch.setenv("SRJT_PROFILE_DIR", str(tmp_path))
    monkeypatch.setenv("SRJT_BROADCAST_ROWS", "0")
    cfg.refresh()
    try:
        opt = optimize(_join_agg(warehouse), distribute=True)
        with metrics.query("evidence-store") as qm:
            execute(opt)
        if qm is None:
            pytest.skip("SRJT_METRICS off")
    finally:
        monkeypatch.delenv("SRJT_PROFILE_DIR")
        monkeypatch.delenv("SRJT_BROADCAST_ROWS")
        cfg.refresh()
    summ = profile.store_summary(str(tmp_path))
    assert summ["profiles"] == 1
    assert summ["top_exchange_skew"] is not None
    (path,) = profile.list_profiles(str(tmp_path))
    stored = profile.read(path)
    assert stored["decisions"]
    assert any(d["kind"] == "shuffle" for d in stored["decisions"])
    assert any(n.get("q_error") is not None for n in stored["nodes"])


def _mk_summary(est_rows, actual_rows):
    """Minimal summary: one broadcast decision over one join-side node."""
    return {"qid": 1, "name": "seed", "wall_s": 0.01,
            "fingerprint": "f" * 16, "stats": {}, "counters": {},
            "histograms": {},
            "nodes": [{"label": "scan", "path": "root.child.right",
                       "wall_s": 0.001, "rows_out": actual_rows,
                       "est_rows": est_rows}],
            "decisions": [{"kind": "broadcast", "how": "inner",
                           "est_rows": est_rows, "threshold": 100_000,
                           "path": "root.child.right"}]}


def test_profile_diff_flags_seeded_misestimate():
    # base run: the estimate was right; cand run: same plan, same decision,
    # but the data moved under the stats — est 50 rows, actual 5_000
    base = profile.compact(_mk_summary(50, 50))
    cand = profile.compact(_mk_summary(50, 5_000))
    assert base["decisions"][0]["misestimate"] is False
    assert cand["decisions"][0]["misestimate"] is True
    assert cand["decisions"][0]["q_error"] == 100.0
    d = profile.diff(base, cand)
    mis = [f for f in d["flags"] if f.startswith("misestimate:")]
    assert len(mis) == 1
    assert "broadcast" in mis[0] and "q_error=100.0" in mis[0]
    # same misestimate in BOTH runs is not a regression — no flag
    d2 = profile.diff(cand, cand)
    assert not [f for f in d2["flags"] if f.startswith("misestimate:")]
    # per-node q_error rides the node delta rows
    row = next(r for r in d["nodes"] if r["label"] == "scan")
    assert row["q_error_base"] is None and row["q_error_cand"] is None


def test_srjt_profile_decisions_cli(tmp_path, warehouse, capsys):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import srjt_profile
    d = str(tmp_path / "store")
    profile.write(_mk_summary(50, 5_000), d)
    assert srjt_profile.main(["--dir", d, "decisions", "-1"]) == 0
    out = capsys.readouterr().out
    assert "broadcast" in out and "MISESTIMATE" in out
    assert "est=50" in out and "actual=5000" in out


# -- live progress -----------------------------------------------------------


def test_footer_chunk_estimate_is_footer_only(tmp_path):
    from spark_rapids_jni_tpu.io import ParquetChunkedReader
    n = 8_000
    p = tmp_path / "est.parquet"
    pq.write_table(pa.table({"a": pa.array(np.arange(n, dtype=np.int64))}),
                   p, row_group_size=1_000)
    r = ParquetChunkedReader(p, pass_read_limit=4 << 10)
    est = r.footer_chunk_estimate()
    assert est >= 8  # at least one chunk per row group
    # the estimate is sane against the real chunk count (same ballpark;
    # footer byte sizes include encoding overhead, so it may overshoot)
    actual = sum(1 for _ in ParquetChunkedReader(p, pass_read_limit=4 << 10))
    assert est >= actual // 2


def test_progress_isolation_two_bound_queries():
    """Two concurrent QueryMetrics on worker threads: each thread's
    progress lands only on its own query, and the registry drops each on
    finish()."""
    qa, qb = metrics.QueryMetrics("qa"), metrics.QueryMetrics("qb")
    try:
        qa.progress_total(10)
        qb.progress_total(20)

        def work(qm, chunks, rows):
            with metrics.bind(qm):
                for _ in range(chunks):
                    metrics.current().progress_step(chunks=1, rows=rows,
                                                    nbytes=rows * 8)

        ta = threading.Thread(target=work, args=(qa, 4, 100))
        tb = threading.Thread(target=work, args=(qb, 7, 10))
        ta.start(), tb.start()
        ta.join(), tb.join()
        snap = {e["name"]: e for e in metrics.progress_snapshot()}
        assert snap["qa"]["chunks_done"] == 4
        assert snap["qa"]["rows"] == 400
        assert snap["qa"]["chunks_total"] == 10
        assert snap["qb"]["chunks_done"] == 7
        assert snap["qb"]["rows"] == 70
        assert snap["qb"]["bytes"] == 7 * 80
    finally:
        qa.finish(), qb.finish()
    names = {e["name"] for e in metrics.progress_snapshot()}
    assert "qa" not in names and "qb" not in names


def test_executor_publishes_progress(warehouse):
    with metrics.query("prog") as qm:
        execute(optimize(_join_agg(warehouse)))
        p = dict(qm.progress)
    assert p["chunks_done"] > 1          # the fact scan streamed
    assert p["chunks_total"] >= p["chunks_done"] // 2  # footer estimate
    assert p["rows"] > 0 and p["bytes"] > 0


@pytest.fixture
def arm_faults(monkeypatch):
    def _arm(spec):
        monkeypatch.setenv("SRJT_FAULTS", spec)
        cfg.refresh()
        faults.reset()
    yield _arm
    monkeypatch.delenv("SRJT_FAULTS", raising=False)
    cfg.refresh()
    faults.reset()


def test_query_status_polls_running_plan_execute(tmp_path, arm_faults):
    """OP_QUERY_STATUS from a second connection observes monotonically
    increasing chunk progress on a PLAN_EXECUTE that is holding the
    dispatch lock (the OP_CANCEL second-connection pattern)."""
    from spark_rapids_jni_tpu.bridge import BridgeClient
    from spark_rapids_jni_tpu.bridge.server import BridgeServer
    n = 40_000
    path = str(tmp_path / "slow.parquet")
    pq.write_table(pa.table({
        "k": pa.array((np.arange(n) % 13).astype(np.int64)),
        "v": pa.array(np.arange(n, dtype=np.int64)),
    }), path, row_group_size=2_048)  # ~20 groups x HANG_S = a slow stream
    arm_faults("parquet.chunk:*:timeout")
    sock = str(tmp_path / "status.sock")
    server = BridgeServer(sock)
    st = threading.Thread(target=server.serve_forever, daemon=True)
    st.start()
    for _ in range(100):
        if os.path.exists(sock):
            break
        time.sleep(0.01)
    c1 = BridgeClient(sock)
    result: list = []

    def submit():
        plan = Aggregate(Scan(path, chunk_bytes=1 << 16), ["k"],
                         [("v", "sum")], names=["s"])
        result.append(c1.execute_plan(plan))

    worker = threading.Thread(target=submit, daemon=True)
    worker.start()
    c2 = BridgeClient(sock)
    samples = []
    try:
        while worker.is_alive() and len(samples) < 400:
            for q in c2.query_status():
                if q["name"].startswith("plan:"):
                    samples.append(q)
            time.sleep(0.02)
        worker.join(timeout=60)
        assert result and len(result[0]) == 1
        assert len(samples) >= 2, "poller never saw the query in flight"
        done = [s["chunks_done"] for s in samples]
        assert done == sorted(done)          # monotone
        assert done[-1] > done[0]            # ... and actually increasing
        assert samples[-1]["chunks_total"] > 0
        assert samples[-1]["rows"] > 0
        # the finished query leaves the registry
        assert all(not q["name"].startswith("plan:")
                   for q in c2.query_status())
    finally:
        c2.shutdown_server()
        c1.close()
        st.join(timeout=10)


# -- OP_METRICS prefix filter + Prometheus exposition ------------------------


def test_op_metrics_prefix_filter(tmp_path):
    from spark_rapids_jni_tpu.bridge import BridgeClient, spawn_server
    sock = str(tmp_path / "pref.sock")
    proc = spawn_server(sock)
    try:
        c = BridgeClient(sock)
        full = c.metrics()
        filt = c.metrics(prefix="bridge.")
        assert set(filt["counters"]) <= set(full["counters"])
        assert all(k.startswith("bridge.") for k in filt["counters"])
        assert all(k.startswith("bridge.") for k in filt["histograms"])
        assert all(k.startswith("bridge.") for k in filt["gauges"])
        # an unmatched prefix empties the blocks but not the envelope
        none = c.metrics(prefix="nosuch.")
        assert none["counters"] == {} and none["histograms"] == {}
        assert "ops" in none  # server-op block rides along regardless
        c.shutdown_server()
    finally:
        proc.wait(timeout=30)


def test_prometheus_text_format(metrics_isolation):
    metrics_isolation("test.prom")
    metrics.count("test.prom.ticks", 3)
    with metrics.query("promq"):
        metrics.gauge_set("test.prom.level", 2.5)
        for v in (0.001, 0.002, 0.004, 0.5):
            metrics.observe("test.prom.lat", v)
    text = metrics.prometheus_text(prefix="test.prom")
    lines = text.splitlines()
    assert text.endswith("\n")
    assert "# TYPE srjt_test_prom_ticks counter" in lines
    assert "srjt_test_prom_ticks 3" in lines
    assert "# TYPE srjt_test_prom_level gauge" in lines
    assert "srjt_test_prom_level 2.5" in lines
    assert "# TYPE srjt_test_prom_lat histogram" in lines
    buckets = [ln for ln in lines if ln.startswith(
        "srjt_test_prom_lat_bucket")]
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in buckets]
    assert counts == sorted(counts)              # cumulative
    assert buckets[-1].startswith('srjt_test_prom_lat_bucket{le="+Inf"}')
    assert counts[-1] == 4
    assert "srjt_test_prom_lat_count 4" in lines
    assert "srjt_queries_in_flight 0" in lines
    # remote form: an OP_METRICS-shaped snapshot renders the same families
    snap = {"counters": {"test.prom.ticks": 3},
            "histograms": metrics.histograms_snapshot("test.prom"),
            "gauges": metrics.gauges_snapshot("test.prom")}
    rtext = metrics.prometheus_text(snap=snap)
    assert "srjt_test_prom_ticks 3" in rtext
    assert "srjt_test_prom_lat_count 4" in rtext
    assert "srjt_queries_in_flight" not in rtext  # no live progress block


def test_srjt_export_cli_warm(capsys):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import srjt_export
    assert srjt_export.main(["--warm", "--prefix", "engine.stream"]) == 0
    out = capsys.readouterr().out
    assert "# TYPE srjt_engine_stream_chunk_latency_s histogram" in out
    for ln in out.splitlines():
        assert ln.startswith(("#", "srjt_")), ln


def test_srjt_export_cli_warm_full_scrape(capsys):
    """The whole local scrape, no prefix: every line is a ``# TYPE``
    declaration of a known type or an ``srjt_`` sample whose value parses,
    and histograms expose their buckets."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import srjt_export
    assert srjt_export.main(["--warm"]) == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    samples = 0
    for ln in lines:
        if ln.startswith("# TYPE "):
            parts = ln.split()
            assert len(parts) == 4, ln
            assert parts[3] in ("counter", "gauge", "histogram"), ln
            continue
        assert ln.startswith("srjt_"), ln
        float(ln.rsplit(" ", 1)[1])
        samples += 1
    assert samples > 0
    assert any("_bucket{le=" in ln for ln in lines)
