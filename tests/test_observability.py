"""Config flags, profiler scopes, bridge metrics (SURVEY §5 aux subsystems).

Reference analogs: nvtx ranges toggled by ``ai.rapids.cudf.nvtx.enabled``
(pom.xml:84,407), ``RMM_LOGGING_LEVEL`` (pom.xml:81), the refcount.debug
leak tracking sysprop (pom.xml:85,406), slf4j logging.
"""

import json
import os

import numpy as np
import pytest

from spark_rapids_jni_tpu.utils import config as cfg
from spark_rapids_jni_tpu.utils import tracing


def test_config_defaults():
    c = cfg.Config.from_env() if "SRJT_TRACE" not in os.environ else None
    assert cfg.config.log_format in ("text", "json")


def test_config_refresh_reads_env(monkeypatch):
    monkeypatch.setenv("SRJT_TRACE", "1")
    monkeypatch.setenv("SRJT_LOG_LEVEL", "debug")
    c = cfg.refresh()
    assert c.trace is True
    assert c.log_level == "DEBUG"
    monkeypatch.delenv("SRJT_TRACE")
    monkeypatch.setenv("SRJT_LOG_LEVEL", "WARNING")
    c = cfg.refresh()
    assert c.trace is False


def test_op_scope_wraps_computation(monkeypatch):
    import jax.numpy as jnp
    monkeypatch.setenv("SRJT_TRACE", "1")
    cfg.refresh()
    with tracing.op_scope("test_op"):
        out = jnp.arange(8).sum()
    assert int(out) == 28
    monkeypatch.delenv("SRJT_TRACE")
    cfg.refresh()


def test_named_scope_lands_in_hlo():
    """The named_scope must attribute HLO to the op (NVTX-range analog)."""
    import jax
    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.ops.hash import murmur3_hash

    t = Table([Column.from_numpy(np.arange(16, dtype=np.int64))])
    def f():
        return murmur3_hash(t).data
    # Lowered.as_text() lost its debug_info kwarg; scope names survive in
    # the compiled module's HLO metadata instead
    text = jax.jit(f).lower().compile().as_text()
    assert "murmur3_hash" in text


def test_bridge_metrics(tmp_path):
    from spark_rapids_jni_tpu.bridge import BridgeClient, spawn_server
    from spark_rapids_jni_tpu.columnar import Column, Table

    sock = str(tmp_path / "bridge.sock")
    proc = spawn_server(sock)
    try:
        c = BridgeClient(sock)
        t = Table([Column.from_numpy(np.arange(10, dtype=np.int64))])
        h = c.import_table(t)
        m = c.metrics()
        assert m["live_handles"] == 1
        assert m["errors"] == 0
        assert sum(m["ops"].values()) >= 2  # ping + import at least
        assert m["busy_s"] >= 0
        # the OP_METRICS body now carries the engine-wide observability
        # layer too (flat counters + SRJT_METRICS histograms/queries)
        assert isinstance(m["counters"], dict)
        assert isinstance(m["histograms"], dict)
        assert isinstance(m["queries"], list)
        with pytest.raises(RuntimeError):
            c.table_meta(999999)  # bad handle -> server-side error
        m2 = c.metrics()
        assert m2["errors"] == 1
        c.release(h)
        assert c.metrics()["live_handles"] == 0
        c.shutdown_server()
    finally:
        proc.wait(timeout=10)


# ---------------------------------------------------------------------------
# memory observability (the RMM role, VERDICT r3 missing #7)


def test_device_memory_census_sees_new_buffers():
    from spark_rapids_jni_tpu.utils import memory
    import jax.numpy as jnp
    before = memory.device_memory_stats()["live_bytes"]
    keep = jnp.ones((1 << 18,), jnp.float32)  # 1 MB
    float(keep[0])
    after = memory.device_memory_stats()["live_bytes"]
    assert after - before >= 1 << 20
    del keep


def test_memory_scope_high_water_and_budget():
    from spark_rapids_jni_tpu.utils import memory
    import jax.numpy as jnp
    with memory.track("alloc") as scope:
        x = jnp.ones((1 << 18,), jnp.float32)
        float(x[0])
        scope.checkpoint()
        del x
    assert scope.stats.high_water_bytes >= scope.stats.start_bytes + (1 << 20)
    import pytest as _pytest
    with _pytest.raises(memory.BudgetExceeded):
        with memory.track("tight", budget_bytes=1) as scope:
            y = jnp.ones((1024,), jnp.float32)
            float(y[0])
            scope.checkpoint()


def test_chunked_reader_mem_debug_path(tmp_path, monkeypatch):
    """SRJT_MEM_DEBUG=1 routes the chunked reader through MemoryScope
    checkpoints (the RMM-role observability hook) without changing rows."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    import numpy as np
    from spark_rapids_jni_tpu.io import ParquetChunkedReader
    n = 5_000
    t = pa.table({"a": pa.array(np.arange(n, dtype=np.int64))})
    p = tmp_path / "m.parquet"
    pq.write_table(t, p, row_group_size=1_000)
    monkeypatch.setenv("SRJT_MEM_DEBUG", "1")
    cfg.refresh()
    try:
        total = sum(tb.num_rows for tb in
                    ParquetChunkedReader(p, pass_read_limit=8 << 10))
    finally:
        monkeypatch.delenv("SRJT_MEM_DEBUG")
        cfg.refresh()
    assert total == n


# ---------------------------------------------------------------------------
# SRJT_METRICS: query-scoped spans/histograms/gauges (utils/metrics.py) and
# EXPLAIN ANALYZE (engine/explain.py)


@pytest.fixture(scope="module")
def metrics_warehouse(tmp_path_factory):
    """A chunked fact table + unique-key dim for streamed agg/join plans."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    root = tmp_path_factory.mktemp("metrics_wh")
    rng = np.random.default_rng(7)
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


def _agg_plan(root, chunk_bytes=12_000):
    from spark_rapids_jni_tpu.engine import (Aggregate, Filter, Scan, col,
                                             lit)
    return Aggregate(
        Filter(Scan(str(root / "fact.parquet"), chunk_bytes=chunk_bytes),
               (">", col("v"), lit(0.0))),
        ["k"], [("v", "sum"), (None, "count_all")], names=["s", "n"])


def _join_plan(root, chunk_bytes=12_000):
    from spark_rapids_jni_tpu.engine import (Aggregate, Filter, Join, Scan,
                                             col, lit)
    return Aggregate(
        Join(Filter(Scan(str(root / "fact.parquet"),
                         chunk_bytes=chunk_bytes),
                    (">", col("v"), lit(0.0))),
             Scan(str(root / "dim.parquet")), ["k"], ["dk"]),
        ["dv"], [("v", "sum"), (None, "count_all")], names=["s", "n"])


def test_metrics_concurrent_writes_no_lost_updates(metrics_isolation):
    """count()/observe() from worker threads racing counters_snapshot()
    reads on the main thread: totals exact, reads monotone, no tearing."""
    import threading
    from spark_rapids_jni_tpu.utils import metrics
    metrics_isolation("test.conc")
    n, workers = 2_000, 2

    def body():
        for _ in range(n):
            metrics.count("test.conc.ticks")
            metrics.observe("test.conc.vals", 1.0)

    threads = [threading.Thread(target=body) for _ in range(workers)]
    for t in threads:
        t.start()
    last = 0
    while any(t.is_alive() for t in threads):
        v = tracing.counters_snapshot("test.conc").get("test.conc.ticks", 0)
        assert v >= last  # snapshot under the writers: monotone, no tears
        last = v
        metrics.histograms_snapshot("test.conc")
    for t in threads:
        t.join()
    assert tracing.counter_value("test.conc.ticks") == n * workers
    h = metrics.histograms_snapshot("test.conc")["test.conc.vals"]
    assert h["count"] == n * workers
    assert h["sum"] == float(n * workers)


def test_explain_analyze_totals_match_interpreter(metrics_warehouse):
    """Per-node rows/chunks in the report agree with the flat stats AND
    with the node-by-node interpreter's result, fused on and off."""
    from spark_rapids_jni_tpu.engine import (execute, explain_analyze,
                                             optimize)

    def as_rows(t):
        return sorted(zip(*[np.asarray(c.data, np.float64).tolist()
                            for c in t.columns]))

    want = execute(optimize(_agg_plan(metrics_warehouse)), fused=False)
    for fused in (True, False):
        rep = explain_analyze(_agg_plan(metrics_warehouse), fused=fused)
        assert as_rows(rep.result) == as_rows(want)
        root_span = rep.nodes[-1]["metrics"]  # topo order: root last
        assert root_span is not None
        assert root_span["rows_out"] == rep.result.num_rows
        assert rep.summary["stats"]["chunks"] > 1
        assert root_span["chunks"] == rep.summary["stats"]["chunks"]
        # every scanned row enters the streaming aggregate exactly once
        assert root_span["rows_in"] == 4_000
        assert root_span["wall_s"] > 0
        assert f"chunks={root_span['chunks']}" in rep.text


def test_snapshot_carries_query_summaries(metrics_warehouse):
    """The export body (``metrics.snapshot()``, what OP_METRICS embeds)
    holds the summary of a query just run, and serializes as JSON."""
    from spark_rapids_jni_tpu.engine import execute, optimize
    from spark_rapids_jni_tpu.utils import metrics
    if not metrics.enabled():
        pytest.skip("SRJT_METRICS off")
    with metrics.query("snapshot-summary"):
        execute(optimize(_agg_plan(metrics_warehouse)))
    snap = json.loads(json.dumps(metrics.snapshot()))
    mine = [q for q in snap["queries"] if q["name"] == "snapshot-summary"]
    assert mine and mine[-1]["nodes"] and mine[-1]["wall_s"] > 0


def test_build_cache_hit_attributed_to_owning_query(metrics_warehouse,
                                                    metrics_isolation):
    """Two queries over the same streamed join: the first owns the one
    miss, the second owns only hits — per-query counters sum to the flat
    registry's totals."""
    from spark_rapids_jni_tpu.engine import (BUILD_CACHE, execute, new_stats,
                                             optimize)
    from spark_rapids_jni_tpu.utils import metrics
    metrics_isolation("engine.build_cache")
    BUILD_CACHE.clear()
    s1, s2 = new_stats(), new_stats()
    with metrics.query("q1") as q1:
        execute(optimize(_join_plan(metrics_warehouse)), stats=s1,
                fused=True)
    with metrics.query("q2") as q2:
        execute(optimize(_join_plan(metrics_warehouse)), stats=s2,
                fused=True)
    assert s1["streamed"] and s1["chunks"] > 1 and s1["fused_segments"] == 1
    assert q1.counters["engine.build_cache.miss"] == 1
    assert q1.counters["engine.build_cache.hit"] == s1["chunks"] - 1
    # the second query never misses: the prepared build it reuses was paid
    # for (and is attributed to) q1
    assert "engine.build_cache.miss" not in q2.counters
    assert q2.counters["engine.build_cache.hit"] == s2["chunks"]
    flat = tracing.counters_snapshot("engine.build_cache")
    assert flat["engine.build_cache.miss"] == 1
    assert flat["engine.build_cache.hit"] == \
        q1.counters["engine.build_cache.hit"] + \
        q2.counters["engine.build_cache.hit"]
    # the completed queries surfaced through the export path too
    names = [q["name"] for q in metrics.recent_summaries()]
    assert "q1" in names and "q2" in names


def test_metrics_disabled_restores_fast_path(monkeypatch,
                                             metrics_isolation):
    """SRJT_METRICS=0: no query contexts, no histogram/gauge writes — but
    the flat tracing counters stay on (they predate the metrics layer)."""
    from spark_rapids_jni_tpu.utils import metrics
    metrics_isolation("test.off")
    monkeypatch.setenv("SRJT_METRICS", "0")
    cfg.refresh()
    try:
        assert not metrics.enabled()
        with metrics.query("off") as qm:
            assert qm is None
            metrics.observe("test.off.h", 1.0)
            metrics.gauge_set("test.off.g", 2.0)
            metrics.time_add("test.off.t", 0.5)
            metrics.count("test.off.c")
        assert metrics.histograms_snapshot("test.off") == {}
        assert metrics.gauges_snapshot("test.off") == {}
        assert tracing.counter_value("test.off.c") == 1
    finally:
        monkeypatch.delenv("SRJT_METRICS")
        cfg.refresh()
    assert metrics.enabled()


def test_config_refresh_covers_every_field(monkeypatch):
    """refresh() iterates dataclasses.fields — a newly declared flag can't
    be silently dropped from the hand-maintained assignment list again."""
    import dataclasses
    monkeypatch.setenv("SRJT_METRICS", "0")
    c = cfg.refresh()
    assert c.metrics is False
    monkeypatch.delenv("SRJT_METRICS")
    c = cfg.refresh()
    assert c.metrics is True
    fresh = cfg.Config.from_env()
    for f in dataclasses.fields(cfg.Config):
        assert getattr(cfg.config, f.name) == getattr(fresh, f.name)


def test_logger_null_handler_and_live_level(monkeypatch):
    """logger() installs exactly one NullHandler (library etiquette) and
    re-applies SRJT_LOG_LEVEL on every call."""
    import logging
    log = cfg.logger()
    assert any(isinstance(h, logging.NullHandler) for h in log.handlers)
    n0 = len(log.handlers)
    monkeypatch.setenv("SRJT_LOG_LEVEL", "debug")
    cfg.refresh()
    log2 = cfg.logger()
    assert log2 is log
    assert log2.level == logging.DEBUG
    assert len(log2.handlers) == n0  # no duplicate handlers on re-call
    monkeypatch.delenv("SRJT_LOG_LEVEL")
    cfg.refresh()
    assert cfg.logger().level == logging.WARNING


# ---------------------------------------------------------------------------
# PR 6: timeline-era observability — histogram export completeness, device
# telemetry, throughput attribution, JSON logging, profile() hardening, and
# the bench regression gate


def test_histogram_snapshot_exports_sum_count_mean(metrics_isolation):
    """Snapshots must carry sum/count (and the derived mean) alongside the
    buckets — without them a scraper can't compute averages."""
    from spark_rapids_jni_tpu.utils import metrics
    metrics_isolation("test.hist")
    for v in (1.0, 2.0, 6.0):
        metrics.observe("test.hist.lat", v)
    h = metrics.histograms_snapshot("test.hist")["test.hist.lat"]
    assert h["count"] == 3
    assert h["sum"] == 9.0
    assert h["mean"] == pytest.approx(3.0)
    assert h["min"] == 1.0 and h["max"] == 6.0
    assert h["buckets"]  # the [le, count] pairs are still there


def test_explain_analyze_throughput_columns(metrics_warehouse):
    """Per-node cost attribution: bytes_moved / GB/s in both the
    structured nodes and the rendered tree."""
    from spark_rapids_jni_tpu.engine import explain_analyze
    rep = explain_analyze(_agg_plan(metrics_warehouse), fused=True)
    root = rep.nodes[-1]["metrics"]
    assert root["bytes_moved"] > 0
    assert root["GBps"] is not None and root["GBps"] > 0
    assert "bytes_moved=" in rep.text
    assert "GB/s=" in rep.text
    # conservation: the scan's bytes_out feed downstream bytes_in, so the
    # plan's total moved bytes must exceed the raw decoded column bytes
    total = sum(n["metrics"]["bytes_moved"] for n in rep.nodes
                if n["metrics"] is not None)
    assert total >= root["bytes_moved"]


def test_memory_telemetry_in_summary_and_gauges(metrics_warehouse,
                                                metrics_isolation):
    """mem_checkpoint() during a streamed query lands device-memory gauges
    in the flat registry AND a memory block in the query summary (and the
    EXPLAIN ANALYZE footer)."""
    from spark_rapids_jni_tpu.engine import explain_analyze
    from spark_rapids_jni_tpu.utils import metrics
    metrics_isolation("memory.device")
    rep = explain_analyze(_agg_plan(metrics_warehouse), fused=True)
    mem = rep.summary.get("memory")
    assert mem, "streamed query recorded no memory telemetry"
    assert mem["source"] in ("runtime", "census")
    assert mem["samples"] >= 1
    assert mem["high_water_bytes"] >= mem["live_bytes"] >= 0
    assert mem["high_water_bytes"] > 0
    g = metrics.gauges_snapshot("memory.device")
    assert g["memory.device.live_bytes"] >= 0
    assert g["memory.device.high_water_bytes"] > 0
    assert "-- memory" in rep.text


def test_telemetry_snapshot_and_nbytes():
    """telemetry_snapshot() always answers (census fallback on CPU), and
    table_nbytes sums exactly the buffers a Table holds — metadata reads
    only, no device sync."""
    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.utils import memory
    snap = memory.telemetry_snapshot()
    assert snap["source"] in ("runtime", "census")
    assert snap["live_bytes"] >= 0
    t = Table([Column.from_numpy(np.arange(100, dtype=np.int64)),
               Column.from_numpy(np.arange(100, dtype=np.float64))],
              ["a", "b"])
    nb = memory.table_nbytes(t)
    assert nb == sum(memory.column_nbytes(c) for c in t.columns)
    assert nb >= 2 * 100 * 8


def test_json_log_format(monkeypatch, capsys):
    """SRJT_LOG_FORMAT=json: one JSON object per line on stderr carrying
    ts/level/logger/msg and the bound query name; switching back to text
    detaches the handler and restores propagation."""
    import logging
    from spark_rapids_jni_tpu.utils import metrics
    monkeypatch.setenv("SRJT_LOG_FORMAT", "json")
    cfg.refresh()
    try:
        log = cfg.logger()
        assert log.propagate is False
        jh = [h for h in log.handlers if getattr(h, "_srjt_json", False)]
        assert len(jh) == 1
        rec = logging.LogRecord("spark_rapids_jni_tpu", logging.WARNING,
                                __file__, 1, "hello %s", ("world",), None)
        doc = json.loads(jh[0].format(rec))
        assert doc["level"] == "WARNING"
        assert doc["logger"] == "spark_rapids_jni_tpu"
        assert doc["msg"] == "hello world"
        assert isinstance(doc["ts"], float)
        assert "query" not in doc
        with metrics.query("jq"):
            doc = json.loads(jh[0].format(rec))
            assert doc["query"] == "jq"
        log.warning("through the handler")
        assert '"msg": "through the handler"' in capsys.readouterr().err
    finally:
        monkeypatch.delenv("SRJT_LOG_FORMAT")
        cfg.refresh()
    log = cfg.logger()
    assert log.propagate is True
    assert not [h for h in log.handlers if getattr(h, "_srjt_json", False)]


def test_profile_noop_without_jax_profiler(monkeypatch, tmp_path):
    """profile() must create the logdir and degrade to a warned no-op when
    jax.profiler can't start (headless shells, unsupported backends)."""
    import jax

    def boom(logdir):
        raise RuntimeError("no profiler here")

    monkeypatch.setattr(jax.profiler, "trace", boom)
    logdir = tmp_path / "prof" / "run1"
    ran = False
    with tracing.profile(str(logdir)):
        ran = True
    assert ran
    assert logdir.is_dir()  # created even though tracing never started


def test_profile_enters_and_exits_jax_trace(monkeypatch, tmp_path):
    import jax
    calls = []

    class FakeTrace:
        def __init__(self, logdir):
            calls.append(("init", logdir))

        def __enter__(self):
            calls.append(("enter",))

        def __exit__(self, *exc):
            calls.append(("exit",))

    monkeypatch.setattr(jax.profiler, "trace", FakeTrace)
    with tracing.profile(str(tmp_path / "d")):
        calls.append(("body",))
    assert [c[0] for c in calls] == ["init", "enter", "body", "exit"]


def test_histogram_percentiles_in_snapshot(metrics_isolation):
    """Power-of-two-bucket percentiles: ordered, clamped to [min, max],
    within the documented 2x error bound, and a single observation
    collapses every percentile to its (clamped) value."""
    from spark_rapids_jni_tpu.utils import metrics
    metrics_isolation("test.pct")
    for v in range(1, 101):
        metrics.observe("test.pct.lat", float(v))
    h = metrics.histograms_snapshot("test.pct")["test.pct.lat"]
    assert h["min"] <= h["p50"] <= h["p90"] <= h["p99"] <= h["max"]
    for q, exact in (("p50", 50.0), ("p90", 90.0), ("p99", 99.0)):
        assert exact / 2 <= h[q] <= exact * 2, q
    metrics.observe("test.pct.one", 3.0)
    h1 = metrics.histograms_snapshot("test.pct")["test.pct.one"]
    assert h1["p50"] == h1["p90"] == h1["p99"] == 3.0
    # the same fields ride the per-query summary (the profile-store path)
    with metrics.query("pctq") as qm:
        if qm is None:
            return                     # SRJT_METRICS off: nothing to pin
        metrics.observe("test.pct.q", 7.0)
    hq = metrics.recent_summaries()[-1]["histograms"]["test.pct.q"]
    assert hq["p50"] == hq["p99"] == 7.0
