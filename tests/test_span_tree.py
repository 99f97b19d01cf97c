"""One span tree per query (utils/tracing.op_scope ``timed=`` / ``**stats``).

What is pinned here, all on the CPU and with no profiler:

- a streamed fused aggregate served through ``BridgeServer`` leaves one
  histogram per span of the tree in the query's summary, with the counts
  the tree promises (one decode per row group read, one stage per chunk,
  one wait per ``engine.host_sync``), nested in time, every sync after
  the stream;
- under ``SRJT_TRACE=1`` each span reaches ``jax.profiler.TraceAnnotation``
  with the client's trace id and its stats, on the producer thread too;
- with metrics and tracing off a ``timed`` scope reads no clock; a timed
  span records the thread's CPU seconds inside its wall seconds, in one
  observation; ``sp.stat()`` reaches whichever sink is on;
- the request's own path (PR 38): decode, verify, prepare, precompute,
  stream open and close each once per request, the six stretches the
  coverage guard adds up, one ``bridge.conn.idle`` per turnaround;
- the benchmark's readers of these spans (``benchmarks/layer_metrics``)
  and the launch-counting helper (``benchmarks/span_reduce.py``) give
  known values on known inputs and None where there is nothing to read.
"""

import importlib.util
import os
import sys
import threading
import time
import types

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_jni_tpu.engine import (Aggregate, Scan, execute, new_stats,
                                         optimize)
from spark_rapids_jni_tpu.utils import config as cfg
from spark_rapids_jni_tpu.utils import metrics, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
ROWS, GROUP_ROWS = 40_000, 8_192        # 5 row groups
CHUNK_BYTES = 1 << 17                   # 2 chunks per full group: 9 in all


class _Annotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: records what each
    span was given, on which thread, and when it opened and closed."""

    log: list = []

    def __init__(self, name, **stats):
        self.rec = {"name": name, "stats": stats,
                    "thread": threading.get_ident()}

    def set_metadata(self, **stats):
        self.rec["stats"].update(stats)

    def __enter__(self):
        self.rec["t0"] = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.rec["t1"] = time.perf_counter()
        _Annotation.log.append(self.rec)
        return False


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One PLAN_EXECUTE + export of a streamed fused aggregate through an
    in-process ``BridgeServer``, traced by `_Annotation`.  The plan runs
    twice: the second run compiles nothing, as in a benchmark window."""
    import jax

    from spark_rapids_jni_tpu.bridge import BridgeClient
    from spark_rapids_jni_tpu.bridge.server import BridgeServer
    root = tmp_path_factory.mktemp("spans")
    path = str(root / "fact.parquet")
    pq.write_table(pa.table({
        "k": pa.array((np.arange(ROWS) % 13).astype(np.int64)),
        "v": pa.array(np.arange(ROWS, dtype=np.int64)),
    }), path, row_group_size=GROUP_ROWS)
    plan = Aggregate(Scan(path, chunk_bytes=CHUNK_BYTES), ["k"],
                     [("v", "sum")], names=["s"])
    mp = pytest.MonkeyPatch()
    mp.setenv("SRJT_TRACE", "1")
    mp.setenv("SRJT_RESULT_CACHE", "0")
    mp.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    cfg.refresh()
    sock = str(root / "b.sock")
    server = BridgeServer(sock)
    st = threading.Thread(target=server.serve_forever, daemon=True)
    st.start()
    deadline = time.monotonic() + 10
    while not os.path.exists(sock) and time.monotonic() < deadline:
        time.sleep(0.01)
    client = BridgeClient(sock)
    try:
        (h,) = client.execute_plan(plan)
        client.release(h)
        _Annotation.log = []
        before = client.metrics()
        (h,) = client.execute_plan(plan)
        cols = client.export_host(h)
        client.release(h)
        after = client.metrics()
        # the server closes a request's span after it wrote the reply: on a
        # loaded host the second poll's may still be open when the reply is
        # read here
        deadline = time.monotonic() + 5
        while sum(r["name"] == "bridge.op.metrics"
                  for r in list(_Annotation.log)) < 2 \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        out = {"before": before, "after": after, "cols": cols,
               "trace_id": client.trace_id, "log": list(_Annotation.log),
               "query": [q for q in after["queries"]
                         if q.get("trace_id") == client.trace_id][-1]}
    finally:
        client.shutdown_server()
        client.close()
        st.join(timeout=10)
        mp.undo()
        cfg.refresh()
    assert not st.is_alive()
    return out


def _hist(query, name):
    h = query["histograms"].get(name)
    return (h["sum"], h["count"]) if h else (0.0, 0)


# -- (a) the tree's counts and its nesting ---------------------------------------

def test_result_is_right(served):
    (_, keys, _), (_, sums, _) = served["cols"]
    got = dict(zip(keys.tolist(), sums.tolist()))
    v = np.arange(ROWS, dtype=np.int64)
    want = {int(k): int(v[v % 13 == k].sum()) for k in range(13)}
    assert got == want


def test_one_span_per_unit_of_work(served):
    q = served["query"]
    stats = q["stats"]
    assert stats["streamed"] and stats["fused_segments"] == 1
    assert stats["row_groups_read"] == 5 and stats["chunks"] == 9
    assert _hist(q, "io.scan.decode_s")[1] == stats["row_groups_read"]
    assert _hist(q, "io.scan.stage_s")[1] == stats["chunks"]
    # each staged chunk took one transfer buffer, from the list or new
    assert _hist(q, "io.scan.stage.pack_s")[1] == stats["chunks"]
    assert q["counters"].get("io.scan.stage.reused", 0) \
        + q["counters"].get("io.scan.stage.fresh", 0) == stats["chunks"]
    assert _hist(q, "engine.stream_s")[1] == 1
    assert _hist(q, "engine.stream.first_wait_s")[1] == 1
    assert _hist(q, "engine.post_stream_s")[1] == 1
    assert _hist(q, "engine.execute_s")[1] == 1


def test_one_span_per_stretch_of_the_request(served):
    """PR 38: the request's own path, once each — in the query's summary
    where the query context is open, process-wide for the two that lie
    before it."""
    q = served["query"]
    for name in ("engine.plan.prepare_s", "engine.precompute_s",
                 "engine.stream.open_s", "engine.stream.close_s",
                 "engine.post_stream.sync_wait_s"):
        assert _hist(q, name)[1] == 1, name
    for name in ("bridge.plan.decode_s", "bridge.plan.verify_s",
                 "engine.plan.prepare_s", "engine.precompute_s",
                 "engine.stream.open_s", "engine.stream.close_s"):
        assert _grew(served, name)[1] == 1, name
    assert "bridge.plan.decode_s" not in q["histograms"]    # before `wall_s`
    assert _grew(served, "bridge.plan.verify_s")[0] \
        <= _grew(served, "bridge.plan.decode_s")[0]


def test_stretches_of_the_execution_are_disjoint(served):
    q = served["query"]
    pre, stream, tail, run = (_hist(q, n)[0] for n in (
        "engine.precompute_s", "engine.stream_s", "engine.post_stream_s",
        "engine.execute_s"))
    assert pre + stream + tail <= run
    assert _hist(q, "engine.stream.open_s")[0] \
        + _hist(q, "engine.stream.first_wait_s")[0] \
        + _hist(q, "engine.stream.close_s")[0] <= stream
    # the tail's waits are among the query's, and here (no fold) all of them
    tail_wait = _hist(q, "engine.post_stream.sync_wait_s")[0]
    assert 0 <= tail_wait <= tail
    assert tail_wait == pytest.approx(_hist(q, "engine.sync_wait_s")[0],
                                      abs=1e-9)


def test_children_of_the_request_add_up(served):
    """The coverage guard's six stretches hold >= 90 % of the request's
    server time here (a chip run wants 95): nothing long is unnamed."""
    request = _grew(served, "bridge.op.plan_execute_s")[0]
    parts = sum(_grew_or_zero(served, name) for name in (
        "bridge.plan.decode_s", "engine.plan.prepare_s",
        "engine.sched.queue_wait_s", "engine.precompute_s",
        "engine.stream_s", "engine.post_stream_s"))
    assert 0.90 * request <= parts <= request
    reader = _bench_module("layer_metrics", "request_span_coverage_pct")
    ctx = {"snap_start": served["before"], "snap_end": served["after"]}
    assert reader.read(ctx) == pytest.approx(parts / request * 100)


def test_one_idle_span_per_turnaround(served):
    """Between the two snapshots the connection served execute, export,
    free and release, and then asked for the second snapshot: four waits
    for the client.  The wait after a metrics poll is none."""
    assert _grew(served, "bridge.conn.idle_s")[1] == 4
    ops = sorted((r for r in served["log"]
                  if r["name"].startswith("bridge.op.")),
                 key=lambda r: r["t0"])
    # (a loaded server may close the warm-up's last span after the log
    # was emptied: the window starts with the first snapshot)
    names = [r["name"][len("bridge.op."):] for r in ops]
    ops = ops[names.index("metrics"):]
    assert [r["name"][len("bridge.op."):] for r in ops] \
        == ["metrics", "plan_execute", "export_table", "free_shm", "release",
            "metrics"]
    # (the log also holds the wait that ended with the first snapshot)
    idles = sorted((r for r in served["log"]
                    if r["name"] == "bridge.conn.idle"
                    and r["t0"] >= ops[0]["t0"]), key=lambda r: r["t0"])
    assert len(idles) == 4
    assert all(r["stats"]["trace_id"] == served["trace_id"] for r in idles)
    # each wait lies between one reply and the next request; none follows
    # the first snapshot's reply
    for done, idle, nxt in zip(ops[1:], idles, ops[2:]):
        assert done["t1"] <= idle["t0"] <= idle["t1"] <= nxt["t0"]


def test_cpu_seconds_lie_inside_wall_seconds(served):
    """Under ``SRJT_TRACE=1`` every timed span's histogram holds `cpu_sum`
    beside `sum`, process wide and in the query's summary: 0 <= cpu_sum
    <= sum."""
    timed = ("bridge.op.plan_execute_s", "bridge.plan.decode_s",
             "bridge.plan.verify_s", "bridge.conn.idle_s",
             "engine.plan.prepare_s", "engine.execute_s",
             "engine.precompute_s", "engine.stream_s",
             "engine.stream.open_s", "engine.stream.first_wait_s",
             "engine.stream.close_s", "engine.sync_wait_s",
             "io.scan.decode_s", "io.scan.stage_s", "io.scan.stage.pack_s")
    for where in (served["after"]["histograms"],
                  served["query"]["histograms"]):
        for name in timed:
            if name in where:
                h = where[name]
                assert 0 <= h["cpu_sum"] <= h["sum"] + 1e-6, (name, h)
    assert all(n in served["after"]["histograms"] for n in timed)
    # what is observed by hand carries none
    assert "cpu_sum" not in served["after"]["histograms"][
        "engine.post_stream_s"]
    # the summary's share of the process-wide CPU seconds is its own
    for name in ("io.scan.stage.pack_s", "engine.stream_s",
                 "io.scan.decode_s"):
        h0 = served["before"]["histograms"][name]
        h1 = served["after"]["histograms"][name]
        assert served["query"]["histograms"][name]["cpu_sum"] \
            == pytest.approx(h1["cpu_sum"] - h0["cpu_sum"], rel=1e-6)
    # a wait for the client is no work of this thread
    idle = served["after"]["histograms"]["bridge.conn.idle_s"]
    assert idle["cpu_sum"] < 0.5 * idle["sum"] + 1e-3


def test_one_wait_per_host_sync(served):
    q = served["query"]
    assert q["counters"]["engine.host_sync"] == 2
    assert _hist(q, "engine.sync_wait_s")[1] \
        == q["counters"]["engine.host_sync"]
    labels = [r["stats"]["label"] for r in served["log"]
              if r["name"] == "engine.sync_wait"]
    assert labels == ["combine-sizing", "groupby-compaction"]


def test_spans_nest_in_time(served):
    q = served["query"]
    stream, tail, run = (_hist(q, n)[0] for n in (
        "engine.stream_s", "engine.post_stream_s", "engine.execute_s"))
    assert _hist(q, "engine.stream.first_wait_s")[0] <= stream
    assert stream + tail <= run <= q["wall_s"]
    # the tail holds every sync of a fused plan, so its self time
    # (benchmarks' post_stream_ms) is a plain difference
    assert _hist(q, "engine.sync_wait_s")[0] <= tail


def test_every_sync_opens_after_the_stream_closed(served):
    log = served["log"]
    (stream,) = [r for r in log if r["name"] == "engine.stream"]
    (run,) = [r for r in log if r["name"] == "engine.execute"]
    syncs = [r for r in log if r["name"] == "engine.sync_wait"]
    assert len(syncs) == 2
    assert all(stream["t1"] <= r["t0"] and r["t1"] <= run["t1"]
               for r in syncs)
    assert run["t0"] <= stream["t0"]


def test_the_tail_span_lies_after_the_stream_inside_the_execution(
        tmp_path, monkeypatch):
    """``engine.tail`` (one per streamed query whose plan has a ``tail``
    stage: lowered for one device) opens after ``engine.stream`` closed
    and closes inside ``engine.execute``, with its stats; the tail's one
    wait nests in it, and the stretch after the stream holds both."""
    import jax

    from spark_rapids_jni_tpu.engine import Sort, lower
    from spark_rapids_jni_tpu.engine.executor import lowering_flags
    path = str(tmp_path / "fact.parquet")
    pq.write_table(pa.table({
        "k": pa.array((np.arange(ROWS) % 13).astype(np.int64)),
        "v": pa.array(np.arange(ROWS, dtype=np.int64)),
    }), path, row_group_size=GROUP_ROWS)
    plan = optimize(Sort(Aggregate(Scan(path, chunk_bytes=CHUNK_BYTES),
                                   ["k"], [("v", "sum")], names=["s"]),
                         (("s", False),)))
    physical = lower(plan, **{**lowering_flags(), "ndev": 1})
    assert physical.stages[0].kind == "tail"
    execute(physical)                       # compiles
    monkeypatch.setenv("SRJT_TRACE", "1")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    cfg.refresh()
    try:
        _Annotation.log = []
        with metrics.query("span-tree-tail") as qm:
            with tracing.op_scope("engine.execute", timed=True):
                out = execute(physical)
        log = list(_Annotation.log)
    finally:
        monkeypatch.undo()
        cfg.refresh()
    assert out.column("s").to_pylist() == sorted(
        out.column("s").to_pylist(), reverse=True) and out.num_rows == 13
    (run,) = [r for r in log if r["name"] == "engine.execute"]
    (stream,) = [r for r in log if r["name"] == "engine.stream"]
    (tail,) = [r for r in log if r["name"] == "engine.tail"]
    assert run["t0"] <= stream["t0"] <= stream["t1"] <= tail["t0"] \
        <= tail["t1"] <= run["t1"]
    assert tail["stats"]["nodes"] == 1 and tail["stats"]["cap"] >= 13
    assert "veto" not in tail["stats"]
    waits = {r["stats"]["label"]: r for r in log
             if r["name"] == "engine.sync_wait"}
    assert sorted(waits) == ["combine-sizing", "tail-compaction"]
    assert tail["t0"] <= waits["tail-compaction"]["t0"] \
        <= waits["tail-compaction"]["t1"] <= tail["t1"]
    assert waits["combine-sizing"]["t1"] <= tail["t0"]
    h = qm.summary()["histograms"]
    assert h["engine.tail_s"]["count"] == 1
    assert h["engine.tail_s"]["sum"] <= h["engine.post_stream_s"]["sum"]
    assert qm.counters["engine.tail.compiled"] == 1


# -- (b) the bridge's own timer ----------------------------------------------------

def _grew(served, name):
    h0 = served["before"]["histograms"].get(name, {"sum": 0.0, "count": 0})
    h1 = served["after"]["histograms"][name]
    return h1["sum"] - h0["sum"], h1["count"] - h0["count"]


def _grew_or_zero(served, name):
    return _grew(served, name)[0] \
        if name in served["after"]["histograms"] else 0.0


def test_bridge_op_timer_encloses_the_query(served):
    seconds, count = _grew(served, "bridge.op.plan_execute_s")
    assert count == 1
    assert seconds >= served["query"]["wall_s"]
    assert _grew(served, "bridge.op.export_table_s")[1] == 1
    wall, queries = _grew(served, "engine.query.wall_s")
    assert queries == 1 and abs(wall - served["query"]["wall_s"]) < 1e-5


# -- (d) what the annotation is given ---------------------------------------------

SERVE_THREAD_SPANS = ("bridge.op.plan_execute", "bridge.plan.decode",
                      "engine.execute", "engine.stream",
                      "engine.stream.first_wait", "engine.stream.wait_reader",
                      "engine.sync_wait", "bridge.op.export_table",
                      "bridge.export", "bridge.conn.idle",
                      "bridge.plan.verify", "engine.plan.prepare",
                      "engine.precompute", "engine.stream.open",
                      "engine.stream.close")


@pytest.mark.parametrize("name", SERVE_THREAD_SPANS)
def test_serve_thread_span_carries_the_trace_id(served, name):
    spans = [r for r in served["log"] if r["name"] == name]
    assert spans, name
    (serve_thread,) = {r["thread"] for r in served["log"]
                       if r["name"] == "bridge.op.plan_execute"}
    assert {r["thread"] for r in spans} == {serve_thread}
    assert all(r["stats"].get("trace_id") == served["trace_id"]
               for r in spans)


def test_late_stats_reach_their_open_spans(served):
    """What is known only at a span's end is set through its handle."""
    by_name = {}
    for r in served["log"]:
        by_name.setdefault(r["name"], []).append(r["stats"])
    (decode,) = by_name["bridge.plan.decode"]
    assert decode["bytes"] > 0 and decode["nodes"] == 2     # scan, aggregate
    assert by_name["engine.plan.prepare"][0]["hit"] == 1    # the second run
    assert by_name["engine.precompute"][0]["nodes"] == 0    # no dimension
    (opened,) = by_name["engine.stream.open"]
    assert (opened["groups"], opened["pruned"]) == (5, 0)
    for stats in by_name["io.scan.decode"]:
        assert stats["pages"] >= 2 and stats["runs"] >= 0
        assert stats["dense"] == "2/2" and "worker_ms" not in stats
    assert "io.scan.decode.walked" not in by_name


def test_producer_thread_spans_carry_trace_id_and_stats(served):
    log = served["log"]
    (serve_thread,) = {r["thread"] for r in log
                       if r["name"] == "engine.stream"}
    decodes = [r for r in log if r["name"] == "io.scan.decode"]
    stages = [r for r in log if r["name"] == "io.scan.stage"]
    assert len(decodes) == 5 and len(stages) == 9
    (producer,) = {r["thread"] for r in decodes + stages}
    assert producer != serve_thread
    assert all(r["stats"]["trace_id"] == served["trace_id"]
               for r in decodes + stages)
    assert [r["stats"]["group"] for r in decodes] == [0, 1, 2, 3, 4]
    assert all(r["stats"]["bytes"] > 0 for r in decodes + stages)
    assert sum(r["stats"]["reused"] for r in stages) \
        == served["query"]["counters"].get("io.scan.stage.reused", 0)
    assert {r["stats"]["reused"] for r in stages} <= {0, 1}
    # the blob: two nullable 8-byte columns padded to the 8192-row bucket
    assert stages[0]["stats"]["bytes"] == 8192 * (8 + 1) * 2
    waits = [r["stats"]["chunk"] for r in log
             if r["name"] == "engine.stream.wait_reader"]
    assert waits == list(range(10))     # 9 chunks and the end mark
    (export,) = [r for r in log if r["name"] == "bridge.export"]
    assert export["stats"]["bytes"] == 13 * (8 + 8 + 1)   # sum is nullable


# -- (c) off means off ---------------------------------------------------------------

def test_timed_scope_reads_no_clock_when_metrics_and_trace_are_off(
        monkeypatch):
    monkeypatch.setenv("SRJT_METRICS", "0")
    monkeypatch.setenv("SRJT_TRACE", "0")
    monkeypatch.setenv("SRJT_TIMELINE", "0")
    cfg.refresh()
    calls = []
    monkeypatch.setattr(tracing, "time", types.SimpleNamespace(
        perf_counter=lambda: calls.append("wall") or 0.0,
        thread_time=lambda: calls.append("cpu") or 0.0))
    try:
        with tracing.op_scope("engine.sync_wait", timed=True, label="x"):
            pass
        assert calls == []
        monkeypatch.setenv("SRJT_METRICS", "1")
        cfg.refresh()
        with tracing.op_scope("engine.sync_wait", timed=True, label="x"):
            pass
        # no trace kept: the wall clock alone (the thread's CPU clock is a
        # system call)
        assert calls == ["wall", "wall"]
        monkeypatch.setenv("SRJT_TRACE", "1")
        cfg.refresh()
        del calls[:]
        with tracing.op_scope("engine.sync_wait", timed=True, label="x"):
            pass
        # the CPU stretch inside the wall stretch, each clock read twice
        assert calls == ["wall", "cpu", "cpu", "wall"]
    finally:
        monkeypatch.undo()
        cfg.refresh()


def test_stat_reaches_whichever_sink_is_on(monkeypatch, metrics_isolation):
    import jax

    from spark_rapids_jni_tpu.utils import timeline
    metrics_isolation("test.span")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    try:
        for trace, tl in ((0, 0), (1, 0), (0, 1), (1, 1)):
            monkeypatch.setenv("SRJT_TRACE", str(trace))
            monkeypatch.setenv("SRJT_TIMELINE", str(tl))
            cfg.refresh()
            timeline.reset()
            _Annotation.log = []
            with tracing.op_scope("test.span.late", timed=True,
                                  early=1) as sp:
                sp.stat(late=2, dense="3/3")
            anns = [r["stats"] for r in _Annotation.log]
            assert anns == ([{"early": 1, "late": 2, "dense": "3/3"}]
                            if trace else [])
            events = [e for e in timeline.events_snapshot()
                      if e["name"] == "test.span.late"]
            assert [{k: e["args"][k] for k in ("early", "late", "dense")}
                    for e in events] \
                == ([{"early": 1, "late": 2, "dense": "3/3"}] if tl else [])
        assert metrics.histograms_snapshot("test.span")[
            "test.span.late_s"]["count"] == 4
    finally:
        monkeypatch.undo()
        cfg.refresh()
        timeline.reset()


def test_a_timed_span_is_one_observation(monkeypatch, metrics_isolation):
    """Wall and CPU seconds arrive together: one `metrics.observe` call
    per timed span, none for an untimed one."""
    metrics_isolation("test.span")
    calls = []
    real = metrics.observe
    monkeypatch.setattr(tracing, "_observe",
                        lambda *a: calls.append(a) or real(*a))
    try:
        with tracing.op_scope("test.span.one", timed=True):
            sum(range(2_000))
        with tracing.op_scope("test.span.none"):
            pass
        ((name, wall, cpu),) = calls
        # no trace kept: no CPU seconds, and no `cpu_sum` in the record
        assert name == "test.span.one_s" and wall > 0 and cpu is None
        h = metrics.histograms_snapshot("test.span")["test.span.one_s"]
        assert (h["count"], h["sum"]) == (1, wall) and "cpu_sum" not in h
        monkeypatch.setenv("SRJT_TRACE", "1")
        cfg.refresh()
        del calls[:]
        with tracing.op_scope("test.span.cpu", timed=True):
            sum(range(2_000))
        ((name, wall, cpu),) = calls
        assert name == "test.span.cpu_s" and 0 <= cpu <= wall
        h = metrics.histograms_snapshot("test.span")["test.span.cpu_s"]
        assert (h["count"], h["sum"], h["cpu_sum"]) == (1, wall, cpu)
    finally:
        monkeypatch.undo()
        cfg.refresh()


def test_timed_scope_feeds_query_and_process_histograms(metrics_isolation):
    metrics_isolation("test.span")
    with metrics.query("q") as qm:
        with tracing.op_scope("test.span.a", timed=True):
            pass
        with tracing.op_scope("test.span.a", timed=True):
            pass
        with tracing.op_scope("test.span.b"):       # not timed
            pass
    assert qm.summary()["histograms"]["test.span.a_s"]["count"] == 2
    assert "test.span.b_s" not in qm.summary()["histograms"]
    assert metrics.histograms_snapshot("test.span")["test.span.a_s"][
        "count"] == 2


# -- every sync site of the exchange paths is timed too ----------------------------

@pytest.mark.parametrize("fuse_exchange", [True, False])
def test_exchange_syncs_are_all_timed(tmp_path, fuse_exchange):
    from spark_rapids_jni_tpu.engine.fuzz import _flags
    rng = np.random.default_rng(7)
    path = tmp_path / "fact.parquet"
    pq.write_table(pa.table({
        "k": pa.array(rng.integers(0, 300, 6_000), pa.int64()),
        "v": pa.array(rng.integers(0, 100, 6_000), pa.int64())}), path)
    plan = Aggregate(Scan(path), ("k",), (("v", "sum"),), ("total",))
    with _flags(fuse_exchange=fuse_exchange):
        with metrics.query("dist") as qm:
            execute(optimize(plan, distribute=True), new_stats())
    q = qm.summary()
    assert q["counters"]["engine.host_sync"] >= 1
    assert q["histograms"]["engine.sync_wait_s"]["count"] \
        == q["counters"]["engine.host_sync"]


# -- (e) the benchmark's readers, on a synthetic ctx --------------------------------

def _bench_module(kind, name):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    path = os.path.join(BENCH, kind, name + ".py") if kind \
        else os.path.join(BENCH, name + ".py")
    spec = importlib.util.spec_from_file_location(f"spantest_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _h(total, count):
    return {"sum": total, "count": count}


def _ctx(queries, hist_start=None, hist_end=None):
    loop = types.SimpleNamespace(
        clients=[types.SimpleNamespace(trace_id="t1")],
        samples=[(0, 0.0, 0.5)] * len(queries))
    return {"loop": loop, "trace": None, "trace_doc": None,
            "snap_start": {"histograms": hist_start or {}},
            "snap_end": {"histograms": hist_end or {}, "queries": queries}}


def _query(trace_id="t1", **hists):
    return {"trace_id": trace_id, "wall_s": 0.5,
            "histograms": {k.replace("__", "."): v
                           for k, v in hists.items()}}


QUERIES = [
    _query(io__scan__decode_s=_h(0.040, 2), io__scan__stage_s=_h(0.030, 3),
           engine__stream__first_wait_s=_h(0.024, 1),
           engine__sync_wait_s=_h(0.100, 2),
           engine__post_stream_s=_h(0.350, 1)),
    _query(io__scan__decode_s=_h(0.060, 2), io__scan__stage_s=_h(0.030, 3),
           engine__stream__first_wait_s=_h(0.057, 1),
           engine__sync_wait_s=_h(0.020, 2),
           engine__post_stream_s=_h(0.250, 1)),
    _query(io__scan__decode_s=_h(0.020, 2), io__scan__stage_s=_h(0.030, 3),
           engine__stream__first_wait_s=_h(0.025, 1),
           engine__sync_wait_s=_h(0.030, 2),
           engine__post_stream_s=_h(0.260, 1)),
    # another client's query (the warm-up's): never read
    _query(trace_id="warm", io__scan__decode_s=_h(9.0, 1),
           io__scan__stage_s=_h(9.0, 1),
           engine__stream__first_wait_s=_h(9.0, 1),
           engine__sync_wait_s=_h(9.0, 1), engine__post_stream_s=_h(9.0, 1)),
]

KNOWN = {
    "scan_decode_ms": 20.0,         # 0.120 s over 6 row groups
    "scan_stage_ms": 10.0,          # 0.090 s over 9 chunks
    "scan_first_wait_ms": 25.0,     # the median of 24, 57, 25
    "sync_wait_ms": 50.0,           # (100 + 20 + 30) / 3
    "post_stream_ms": 236.66666666666666,   # (250 + 230 + 230) / 3
}


@pytest.mark.parametrize("name", sorted(KNOWN))
def test_program_span_reader(name):
    reader = _bench_module("layer_metrics", name)
    assert reader.read(_ctx(QUERIES)) == pytest.approx(KNOWN[name])
    # a program without the spans, or a window without queries: nothing
    assert reader.read(_ctx([_query()])) is None
    assert reader.read(_ctx([])) is None


def test_bridge_server_reader_takes_process_wide_growth():
    reader = _bench_module("layer_metrics", "bridge_server_ms")
    start = {"bridge.op.plan_execute_s": _h(10.0, 20),
             "bridge.op.metrics_s": _h(1.0, 5),
             "engine.query.wall_s": _h(9.0, 20)}
    end = {"bridge.op.plan_execute_s": _h(15.2, 30),
           "bridge.op.export_table_s": _h(0.03, 10),    # new in the window
           "bridge.op.release_s": _h(0.01, 10),
           "bridge.op.metrics_s": _h(7.0, 6),           # the snapshots' own
           "engine.query.wall_s": _h(14.16, 30),
           "engine.stream_s": _h(99.0, 30)}
    # (5.2 + 0.03 + 0.01 - 5.16) s over 10 queries
    assert reader.read(_ctx([], start, end)) == pytest.approx(8.0)
    assert reader.read(_ctx([], start, start)) is None      # no query ran
    assert reader.read(_ctx([], {}, {"engine.query.wall_s": _h(1.0, 2)})) \
        is None                                              # no bridge timer
    assert reader.read(_ctx([])) is None


# PR 38's six: process-wide growth between the window's two snapshots
def _hc(total, count, cpu):
    return {"sum": total, "count": count, "cpu_sum": cpu}


REQUEST_START = {
    "bridge.op.plan_execute_s": _hc(10.0, 20, 2.0),
    "bridge.plan.decode_s": _hc(0.020, 20, 0.020),
    "bridge.conn.idle_s": _hc(40.0, 79, 0.01),
    "engine.plan.prepare_s": _hc(0.004, 20, 0.004),
    "engine.precompute_s": _hc(0.100, 20, 0.080),
    "engine.stream_s": _hc(5.0, 20, 1.0),
    "engine.post_stream_s": _h(4.0, 20),
    "engine.post_stream.sync_wait_s": _h(1.0, 20),
    "io.scan.stage.pack_s": _hc(0.220, 220, 0.110),
}
REQUEST_END = {
    "bridge.op.plan_execute_s": _hc(11.0, 30, 2.5),         # + 1.0 s
    "bridge.plan.decode_s": _hc(0.035, 30, 0.035),          # + 15 ms
    "bridge.conn.idle_s": _hc(40.1, 119, 0.02),             # + 100 ms
    "engine.plan.prepare_s": _hc(0.009, 30, 0.009),         # + 5 ms
    "engine.precompute_s": _hc(0.160, 30, 0.120),           # + 60 ms
    "engine.stream_s": _hc(5.6, 30, 1.2),                   # + 600 ms
    "engine.post_stream_s": _h(4.3, 30),                    # + 300 ms
    "engine.post_stream.sync_wait_s": _h(1.08, 30),         # + 80 ms
    "io.scan.stage.pack_s": _hc(0.330, 330, 0.150),   # + 110 ms, 40 on CPU
}
REQUEST_KNOWN = {
    "plan_decode_ms": 1.5,
    "client_turnaround_ms": 10.0,
    "precompute_ms": 6.0,
    "tail_host_ms": 22.0,                   # (300 - 80) / 10
    "stage_pack_wait_ms": 70.0 / 110,       # (110 - 40) ms over 110 chunks
    "request_span_coverage_pct": 98.0,      # 15 + 5 + 0 + 60 + 600 + 300
}


@pytest.mark.parametrize("name", sorted(REQUEST_KNOWN))
def test_request_path_reader(name):
    reader = _bench_module("layer_metrics", name)
    ctx = _ctx([_query()] * 10, REQUEST_START, REQUEST_END)
    assert reader.read(ctx) == pytest.approx(REQUEST_KNOWN[name])
    # a window in which nothing grew, a program without the histograms
    # (the parent's) and an empty snapshot: nothing, and no exception
    assert reader.read(_ctx([_query()] * 10, REQUEST_END, REQUEST_END)) \
        is None
    parent = {k: {"sum": v["sum"], "count": v["count"]}
              for k, v in REQUEST_END.items()
              if k in ("bridge.op.plan_execute_s", "engine.stream_s",
                       "engine.post_stream_s", "io.scan.stage.pack_s")}
    assert reader.read(_ctx([_query()] * 10, {}, parent)) is None
    assert reader.read(_ctx([])) is None


def test_request_path_readers_are_in_the_benchmark():
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    mine = [m for m in bench["per_layer"] if m["name"] in REQUEST_KNOWN]
    assert [m["name"] for m in mine] == [
        "plan_decode_ms", "client_turnaround_ms", "precompute_ms",
        "tail_host_ms", "stage_pack_wait_ms", "request_span_coverage_pct"]
    at = bench["per_layer"].index(mine[0])
    assert mine == bench["per_layer"][at:at + 6]    # appended together
    # each lists the six cells it was accepted with (PR 38): a later cell
    # is not appended to an accepted entry, which would edit the benchmark
    for m in mine:
        assert m["workloads"] == ACCEPTED_WITH \
            and m["moves"] == "fact_rows_per_s"
        assert m["source"] == "program_span"
    assert set(ACCEPTED_WITH) <= set(cells)


#: the cells PR 38's request-path readers were accepted with
ACCEPTED_WITH = ["q5lite_sf1_year", "q55lite_sf1_nov1999", "q5lite_sf1_14day",
                 "q5lite_sf1_mesh4", "q5lite_sf1_c4", "q5lite_sf10_year"]


# -- (f) launches inside a derived interval ------------------------------------------

def test_launch_counting_on_a_hand_built_trace():
    sr = _bench_module("", "span_reduce")
    serve, other = 3, 4
    streams = [(serve, 100, 200), (serve, 220, 300),    # two in one execute
               (serve, 1100, 1300), (other, 1100, 1350),
               (serve, 2100, 2200)]
    executes = [(serve, 50, 500), (serve, 1000, 1600), (other, 1050, 1400),
                (serve, 2050, 2900), (serve, 3000, 3100)]   # last: no stream
    intervals = sr.intervals_after(streams, executes)
    assert intervals == [(300, 500), (1300, 1600), (1350, 1400),
                         (2200, 2900)]
    launches = [150, 300, 310, 499, 500, 501, 1299, 1301, 1599, 2500]
    serve_only = [intervals[0], intervals[1], intervals[3]]
    # 300, 310, 499, 500 | 1301, 1599 | 2500
    assert sr.launches_per_interval(serve_only, launches) == 7 / 3
    # a traced window that starts at 400 cuts the first interval off
    assert sr.launches_per_interval(serve_only, launches,
                                    (400, 2950)) == 3 / 2
    # ... and one that no interval lies whole inside leaves nothing
    assert sr.launches_per_interval(serve_only, launches,
                                    (400, 1500)) is None
    assert sr.launches_per_interval([], launches) is None
    assert sr.intervals_after([], executes) == []


def test_launch_counting_finds_nothing_in_a_trace_without_the_spans():
    """The recorded v5e probe (PR 27's fixture) has launches but neither
    `engine.stream` nor `engine.execute`: the reader says nothing, as it
    does on a commit from before the spans."""
    sr = _bench_module("", "span_reduce")
    fixture = os.path.join(BENCH, "fixtures", "tpu_probe.xplane.pb")
    assert sr.post_stream_launches(fixture) is None
    assert sr.xplane_of({"trace": None, "trace_doc": None}) is None
