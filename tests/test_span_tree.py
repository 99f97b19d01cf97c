"""One span tree per query (utils/tracing.op_scope ``timed=`` / ``**stats``).

What is pinned here, all on the CPU and with no profiler:

- a streamed fused aggregate served through ``BridgeServer`` leaves one
  histogram per span of the tree in the query's summary, with the counts
  the tree promises (one decode per row group read, one stage per chunk,
  one wait per ``engine.host_sync``), nested in time, every sync after
  the stream;
- under ``SRJT_TRACE=1`` each span reaches ``jax.profiler.TraceAnnotation``
  with the client's trace id and its stats, on the producer thread too;
- with metrics and tracing off a ``timed`` scope reads no clock;
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


# -- (b) the bridge's own timer ----------------------------------------------------

def _grew(served, name):
    h0 = served["before"]["histograms"].get(name, {"sum": 0.0, "count": 0})
    h1 = served["after"]["histograms"][name]
    return h1["sum"] - h0["sum"], h1["count"] - h0["count"]


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
                      "bridge.export")


@pytest.mark.parametrize("name", SERVE_THREAD_SPANS)
def test_serve_thread_span_carries_the_trace_id(served, name):
    spans = [r for r in served["log"] if r["name"] == name]
    assert spans, name
    (serve_thread,) = {r["thread"] for r in served["log"]
                       if r["name"] == "bridge.op.plan_execute"}
    assert {r["thread"] for r in spans} == {serve_thread}
    assert all(r["stats"].get("trace_id") == served["trace_id"]
               for r in spans)


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
        perf_counter=lambda: calls.append(1) or 0.0))
    try:
        with tracing.op_scope("engine.sync_wait", timed=True, label="x"):
            pass
        assert calls == []
        monkeypatch.setenv("SRJT_METRICS", "1")
        cfg.refresh()
        with tracing.op_scope("engine.sync_wait", timed=True, label="x"):
            pass
        assert len(calls) == 2
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
