"""The deployment of the benchmark's cell ``q5lite_sf1_c4`` (configuration
``nds_q5lite_sf1_c4``: NDS q5-lite sent by four Spark task threads at once
to ONE bridge server on one chip, ``SRJT_MAX_SESSIONS=4``), on the CPU.

- (a) the cell's plan at the configuration's ``rehearsal_rows``, served by
  a child with the configuration's ``server_env`` to 4 concurrent clients
  x 3 queries, for three seeds: every result equals the plain pandas
  reference and the one-client result byte for byte;
- (b) what the scheduler counts: 12 admissions, nothing queued or shed,
  no session left live, at most 4 live at once;
- (c) every query's own summary holds the one-client run's counts, and the
  summaries add up to the process-wide growth: the per-query metrics
  context does not leak between queries through the producer threads — nor
  through the pool of decode worker processes (io/decode_pool.py) the four
  producers share: every fact row group is decoded by a worker, 4 x 3 x 11
  of them, and counted in the query that asked for it;
- (d) a session that blocks at the gate or in the admission queue leaves a
  span (``TraceAnnotation`` under ``SRJT_TRACE=1``, the histogram
  ``<name>_s``) with its own trace id; a single session leaves none;
- (e) a 5th client against ``SRJT_MAX_SESSIONS=4`` queues and is answered
  exactly;
- the benchmark's four new readers give known values on known inputs and
  None where the program has nothing to read.
"""

import importlib.util
import json
import os
import sys
import threading
import time
import types

import numpy as np
import pytest

from spark_rapids_jni_tpu.bridge import BridgeClient
from spark_rapids_jni_tpu.bridge.client import spawn_server
from spark_rapids_jni_tpu.engine.scheduler import SCHEDULER, Scheduler
from spark_rapids_jni_tpu.utils import config as cfg
from spark_rapids_jni_tpu.utils import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
SEEDS = (7, 20, 2147483777)
CLIENTS, ROUNDS = 4, 3
CELL = "q5lite_sf1_c4"


def _load(path, name):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CONFIG = _json("configs", "nds_q5lite_sf1_c4.json")
TRAFFIC = _json("traffic", "year_c4.json")
PARAMS = TRAFFIC["params"]
QUERY = _load(os.path.join(BENCH, "queries", CONFIG["query"] + ".py"),
              "c4test_query")


def _warehouse(root, seed, fact_rows):
    """(frames, serialized plan) of the cell on ``seed``, the fact cut to
    ``fact_rows``; written as `benchmarks/run.py::write_tables` writes."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    rows = {t: spec["rows"] for t, spec in CONFIG["tables"].items()}
    rows[QUERY.FACT] = fact_rows
    frames = QUERY.tables(seed, rows)
    paths = {}
    for name, df in frames.items():
        paths[name] = os.path.join(root, f"{name}.{seed}.parquet")
        pq.write_table(
            pa.Table.from_pandas(df, preserve_index=False), paths[name],
            compression=CONFIG["storage"]["compression"],
            row_group_size=-(-len(df) // CONFIG["tables"][name]["row_groups"]))
    plan = QUERY.plan(paths, PARAMS, CONFIG["storage"]["chunk_bytes"])
    return frames, plan.serialize()


def _together(sock, blob, clients, rounds):
    """``clients`` connections, each sending ``rounds`` queries when the
    last reply has come, all started at once (`run.py::ClosedLoop`)."""
    conns = [BridgeClient(sock, timeout=900) for _ in range(clients)]
    results = [[] for _ in conns]
    errors = []
    start = threading.Barrier(clients)

    def client(i):
        try:
            start.wait(timeout=60)
            for _ in range(rounds):
                (h,) = conns[i].execute_plan(blob)
                results[i].append(conns[i].export_host(h))
                conns[i].release(h)
        except Exception as e:  # noqa: BLE001 — the test shows it
            errors.append((i, repr(e)))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    trace_ids = [c.trace_id for c in conns]
    for c in conns:
        c.close()
    assert not errors, errors
    return results, trace_ids


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One child with the configuration's ``server_env``; per seed the
    one-client run (warm) and then 4 clients x 3 queries at once."""
    root = str(tmp_path_factory.mktemp("c4"))
    sock = os.path.join(root, "c4.sock")
    proc = spawn_server(sock, env=dict(CONFIG["server_env"]), timeout=180)
    client = BridgeClient(sock, timeout=900)
    out = []
    try:
        for seed in SEEDS:
            frames, blob = _warehouse(
                root, seed, CONFIG["rehearsal_rows"][QUERY.FACT])
            (h,) = client.execute_plan(blob)
            client.release(h)
            # warm: compiles nothing, and the server's decode workers, which
            # start behind the first streamed scan, are up
            for _ in range(200):
                (h,) = client.execute_plan(blob)
                alone = client.export_host(h)
                client.release(h)
                before = client.metrics()
                mine = [q for q in before["queries"]
                        if q.get("trace_id") == client.trace_id][-1]
                if mine["counters"].get("io.scan.decode.inline") == 1:
                    break
                time.sleep(0.25)
            results, trace_ids = _together(sock, blob, CLIENTS, ROUNDS)
            after = client.metrics()
            out.append({
                "frames": frames, "alone": alone, "results": results,
                "alone_query": [q for q in before["queries"]
                                if q.get("trace_id") == client.trace_id][-1],
                "queries": [q for q in after["queries"]
                            if q.get("trace_id") in trace_ids],
                "trace_ids": trace_ids, "before": before, "after": after,
                "live_handles": client.live_count()})
        client.shutdown_server()
        proc.wait(timeout=60)
    finally:
        client.close()
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    return out


def _grew(run, name):
    return run["after"]["counters"].get(name, 0) \
        - run["before"]["counters"].get(name, 0)


def _hist_grew(run, name):
    h0 = run["before"]["histograms"].get(name) or {"sum": 0.0, "count": 0}
    h1 = run["after"]["histograms"].get(name) or {"sum": 0.0, "count": 0}
    return h1["sum"] - h0["sum"], h1["count"] - h0["count"]


by_seed = pytest.mark.parametrize("i", range(len(SEEDS)),
                                  ids=[str(s) for s in SEEDS])


# -- (a) four clients at once == one client == pandas, byte for byte ------------

@by_seed
def test_every_result_equals_reference_and_one_client(served, i):
    run = served[i]
    want = QUERY.reference(run["frames"], PARAMS)
    assert [len(r) for r in run["results"]] == [ROUNDS] * CLIENTS
    for cols in (c for per_client in run["results"] for c in per_client):
        assert len(cols) == len(run["alone"]) == len(want.columns)
        for name, (_, got, valid), (_, alone, _) in zip(
                want.columns, cols, run["alone"]):
            assert valid is None or np.asarray(valid).all()
            ref = want[name].to_numpy()
            assert got.dtype == alone.dtype == ref.dtype
            assert got.tobytes() == alone.tobytes() == ref.tobytes(), name
    assert run["live_handles"] == 0


# -- (b) what the scheduler counts -----------------------------------------------

@by_seed
def test_all_admitted_none_queued_none_shed(served, i):
    run = served[i]
    assert _grew(run, "engine.sched.admitted") == CLIENTS * ROUNDS
    assert _grew(run, "engine.sched.queued") == 0
    assert _grew(run, "engine.sched.shed") == 0
    assert run["after"]["gauges"]["engine.sched.live"] == 0
    sched = run["after"]["scheduler"]
    assert sched["live"] == 0 and sched["sessions"] == []
    assert sched["max_sessions"] == int(
        CONFIG["server_env"]["SRJT_MAX_SESSIONS"]) == CLIENTS
    assert not [k for k in run["after"]["counters"]
                if k.startswith("engine.degraded")]
    # one observation per admission; the plans really ran side by side
    total, count = _hist_grew(run, "engine.sched.live_sessions")
    assert count == CLIENTS * ROUNDS
    assert run["after"]["histograms"]["engine.sched.live_sessions"]["max"] \
        <= CLIENTS
    assert total / count > 1.5
    assert run["alone_query"]["histograms"][
        "engine.sched.live_sessions"]["max"] == 1


# -- (c) the per-query context does not leak between concurrent queries ----------

PER_QUERY = ("engine.host_sync", "engine.segment.replay",
             "engine.combine.replay", "engine.probe.compare",
             "io.parquet.chunks", "io.parquet.decode.pages",
             "io.parquet.decode.runs", "io.parquet.decode.dense_chunks",
             "io.parquet.bytes_decoded", "io.scan.decode.offloaded",
             "io.scan.decode.inline", "engine.build_cache.hit",
             "engine.segment_cache.hit", "engine.plan_cache.hit",
             "engine.sched.admitted")


@by_seed
def test_each_summary_holds_the_one_client_counts(served, i):
    run = served[i]
    alone = run["alone_query"]
    assert alone["counters"]["engine.host_sync"] == 2
    # the 11 fact row groups by a decode worker, `date_dim`'s one here
    assert alone["counters"]["io.scan.decode.offloaded"] == 11
    assert alone["counters"]["io.scan.decode.inline"] == 1
    assert _grew(run, "io.scan.decode.offloaded") == CLIENTS * ROUNDS * 11
    assert len(run["queries"]) == CLIENTS * ROUNDS
    for q in run["queries"]:
        assert q["outcome"]["status"] == "ok"
        assert q["stats"]["chunks"] == alone["stats"]["chunks"] == 11
        assert {k: q["counters"].get(k) for k in PER_QUERY} \
            == {k: alone["counters"].get(k) for k in PER_QUERY}
        for name in ("io.scan.decode_s", "io.scan.decode.worker_s",
                     "io.scan.stage_s", "io.scan.stage.pack_s",
                     "engine.stream.chunk_latency_s", "engine.sync_wait_s",
                     "engine.stream_s", "engine.execute_s"):
            assert q["histograms"][name]["count"] \
                == alone["histograms"][name]["count"], name
        # which of the two grows depends on what the other three producers
        # hold at that moment; their sum is the query's own staged blobs
        assert q["counters"].get("io.scan.stage.reused", 0) \
            + q["counters"].get("io.scan.stage.fresh", 0) \
            == q["histograms"]["io.scan.stage_s"]["count"] >= 11
    assert _grew(run, "io.scan.stage.reused") \
        + _grew(run, "io.scan.stage.fresh") \
        == _hist_grew(run, "io.scan.stage_s")[1]


@by_seed
def test_summaries_add_up_to_the_process_wide_growth(served, i):
    run = served[i]
    for name in PER_QUERY:
        assert sum(q["counters"].get(name, 0) for q in run["queries"]) \
            == _grew(run, name), name
    for name in ("io.scan.stage.reused", "io.scan.stage.fresh"):
        assert sum(q["counters"].get(name, 0) for q in run["queries"]) \
            == _grew(run, name), name
    for name in ("io.scan.decode_s", "io.scan.decode.worker_s",
                 "io.scan.stage_s", "io.scan.stage.pack_s",
                 "engine.sync_wait_s", "engine.sched.gate_wait_s"):
        total, count = _hist_grew(run, name)
        mine = [q["histograms"].get(name) or {"sum": 0.0, "count": 0}
                for q in run["queries"]]
        assert sum(h["count"] for h in mine) == count, name
        assert sum(h["sum"] for h in mine) == pytest.approx(total, rel=1e-6)


REQUEST_SPANS = ("engine.plan.prepare_s", "engine.precompute_s",
                 "engine.stream.open_s", "engine.stream.close_s",
                 "engine.post_stream.sync_wait_s")


@by_seed
def test_each_request_keeps_its_own_path_counts(served, i):
    """PR 38's spans under four clients: once per request in its own
    summary, 12 process-wide, and a wait for the client per turnaround of
    each connection."""
    run = served[i]
    for q in [run["alone_query"]] + run["queries"]:
        for name in REQUEST_SPANS:
            assert q["histograms"][name]["count"] == 1, name
        tail_wait = q["histograms"]["engine.post_stream.sync_wait_s"]["sum"]
        assert tail_wait == pytest.approx(
            q["histograms"]["engine.sync_wait_s"]["sum"], abs=1e-9)
        for name, h in q["histograms"].items():
            if "cpu_sum" in h:
                assert 0 <= h["cpu_sum"] <= h["sum"] + 1e-6, name
        pre, stream, tail, whole = (q["histograms"][n]["sum"] for n in (
            "engine.precompute_s", "engine.stream_s", "engine.post_stream_s",
            "engine.execute_s"))
        assert pre + stream + tail <= whole <= q["wall_s"]
    for name in ("bridge.plan.decode_s", "bridge.plan.verify_s",
                 "engine.plan.prepare_s", "engine.precompute_s",
                 "bridge.op.plan_execute_s"):
        assert _hist_grew(run, name)[1] == CLIENTS * ROUNDS, name
    # execute, export, free, release per query; a connection's first
    # request follows no reply, and the polls' own waits are none
    assert _hist_grew(run, "bridge.conn.idle_s")[1] \
        == CLIENTS * (4 * ROUNDS - 1)
    # no trace is kept in this child: no span read the thread's CPU clock
    assert not [name for name, h in run["after"]["histograms"].items()
                if "cpu_sum" in h]


# -- (d) the scheduler's waits as spans ------------------------------------------

class _Annotation:
    """Stands in for ``jax.profiler.TraceAnnotation`` (as in
    `test_mesh4_cell.py`): what each span was given, and when it was open."""

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


@pytest.fixture
def traced_env(monkeypatch):
    """``SRJT_TRACE=1`` with `_Annotation` in the profiler's place and the
    configuration's ``server_env``; the defaults come back afterwards."""
    import jax
    for k, v in {**CONFIG["server_env"], "SRJT_TRACE": "1"}.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    cfg.refresh()
    _Annotation.log = []
    yield
    monkeypatch.undo()
    cfg.refresh()


def _sched_spans(name):
    return [r for r in _Annotation.log if r["name"] == name]


def _hist_count(name):
    return (metrics.histograms_snapshot(name).get(name) or {"count": 0})[
        "count"]


def test_a_single_session_opens_no_gate_span(traced_env):
    sched = Scheduler()
    before = _hist_count("engine.sched.gate_wait_s")
    s = sched.admit(fingerprint="a" * 16, trace_id="t-solo")
    for _ in range(50):
        s.gate()
    s.release()
    assert _sched_spans("engine.sched.gate_wait") == []
    assert _sched_spans("engine.sched.queue_wait") == []
    assert _hist_count("engine.sched.gate_wait_s") == before
    assert sched.stats()["rounds"] == 0


def test_a_blocked_gate_is_a_span_with_the_waiting_trace_id(traced_env):
    """Two sessions: ``a`` spends its round and blocks; ``b`` spends its
    own, the round turns, ``a`` goes on.  ``b`` never blocked."""
    sched = Scheduler()
    before = _hist_count("engine.sched.gate_wait_s")
    a = sched.admit(fingerprint="a" * 16, trace_id="t-a")
    b = sched.admit(fingerprint="b" * 16, trace_id="t-b")
    for _ in range(a.credits):
        a.gate()
    assert _sched_spans("engine.sched.gate_wait") == []   # credits: no span
    blocked = threading.Thread(target=a.gate)
    blocked.start()
    time.sleep(0.1)
    assert blocked.is_alive()                   # parked: b holds credits
    for _ in range(b.credits):
        b.gate()
    b.gate()        # b's round is spent too: the round turns, nobody waits
    blocked.join(timeout=10)
    assert not blocked.is_alive()
    (span,) = _sched_spans("engine.sched.gate_wait")
    assert span["stats"] == {"sid": a.sid, "live": 2, "trace_id": "t-a"}
    assert span["thread"] == blocked.ident
    assert span["t1"] - span["t0"] >= 0.09
    assert _hist_count("engine.sched.gate_wait_s") == before + 1
    # the span closed outside the lock: the scheduler answers at once
    assert sched.stats()["rounds"] == 1
    a.release()
    b.release()


def test_a_queued_admission_is_a_span(traced_env, monkeypatch):
    monkeypatch.setenv("SRJT_MAX_SESSIONS", "1")
    cfg.refresh()
    sched = Scheduler()
    before = _hist_count("engine.sched.queue_wait_s")
    hold = sched.admit(fingerprint="a" * 16, trace_id="t-hold")
    assert _sched_spans("engine.sched.queue_wait") == []
    got = []
    waiter = threading.Thread(target=lambda: got.append(
        sched.admit(fingerprint="b" * 16, trace_id="t-wait")))
    waiter.start()
    time.sleep(0.1)
    assert not got
    hold.release()
    waiter.join(timeout=10)
    (span,) = _sched_spans("engine.sched.queue_wait")
    assert span["stats"] == {"live": 1, "trace_id": "t-wait"}
    assert span["t1"] - span["t0"] >= 0.09
    assert got[0].queued_s >= 0.09
    assert _hist_count("engine.sched.queue_wait_s") == before + 1
    got[0].release()
    assert sched.stats()["queued"] == 1 and sched.live_count() == 0


def test_gate_under_more_threads_than_cores(traced_env, monkeypatch):
    """The gate drops ``_cv`` between its first look and its wait: 16
    sessions of uneven length hammer it with a short switch interval.  No
    deadlock, no credit spent twice, every wait closed as a span."""
    monkeypatch.setenv("SRJT_MAX_SESSIONS", "16")
    cfg.refresh()
    sched = Scheduler()
    before = _hist_count("engine.sched.gate_wait_s")
    sessions = [sched.admit(fingerprint=f"{i:x}" * 16, trace_id=f"t{i}")
                for i in range(16)]
    passed, overdrawn = [], []

    def spin(s, n):
        for _ in range(n):
            s.gate()
            if s.credits < 0:
                overdrawn.append(s.sid)
        passed.append(n)
        s.release()

    threads = [threading.Thread(target=spin, args=(s, 20 + 15 * i))
               for i, s in enumerate(sessions)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert sorted(passed) == [20 + 15 * i for i in range(16)]
    assert not overdrawn and sched.live_count() == 0
    waits = _sched_spans("engine.sched.gate_wait")
    assert waits and all(2 <= w["stats"]["live"] <= 16 for w in waits)
    assert _hist_count("engine.sched.gate_wait_s") == before + len(waits)


@pytest.fixture
def in_process(traced_env, tmp_path):
    """An in-process ``BridgeServer`` under `traced_env`, and the cell's
    plan on a small fact (12 row groups, 11 chunks per query)."""
    from spark_rapids_jni_tpu.bridge.server import BridgeServer
    frames, blob = _warehouse(str(tmp_path), 11, 24_000)
    sock = os.path.join(str(tmp_path), "b.sock")
    server = BridgeServer(sock)
    st = threading.Thread(target=server.serve_forever, daemon=True)
    st.start()
    deadline = time.monotonic() + 10
    while not os.path.exists(sock) and time.monotonic() < deadline:
        time.sleep(0.01)
    client = BridgeClient(sock, timeout=900)
    (h,) = client.execute_plan(blob)       # compile before the clients meet
    client.release(h)
    _Annotation.log = []
    yield types.SimpleNamespace(server=server, sock=sock, blob=blob,
                                frames=frames, client=client)
    client.shutdown_server()
    client.close()
    st.join(timeout=10)
    assert not st.is_alive()


def _equals_reference(cols, frames):
    want = QUERY.reference(frames, PARAMS)
    return all(got.tobytes() == want[name].to_numpy().tobytes()
               for name, (_, got, _) in zip(want.columns, cols))


def test_served_gate_waits_carry_their_own_query(in_process):
    """Four clients through the bridge: every `engine.sched.gate_wait`
    annotation lies on a serve thread, inside that thread's
    `engine.execute` span, with that request's trace id."""
    results, trace_ids = _together(in_process.sock, in_process.blob,
                                   CLIENTS, 2)
    assert all(_equals_reference(cols, in_process.frames)
               for per_client in results for cols in per_client)
    waits = _sched_spans("engine.sched.gate_wait")
    executes = _sched_spans("engine.execute")
    assert len(executes) == CLIENTS * 2
    assert waits, "four concurrent streams of 11 chunks never blocked"
    for w in waits:
        (around,) = [e for e in executes if e["thread"] == w["thread"]
                     and e["t0"] <= w["t0"] and w["t1"] <= e["t1"]]
        assert w["stats"]["trace_id"] == around["stats"]["trace_id"]
        assert w["stats"]["trace_id"] in trace_ids
        assert 2 <= w["stats"]["live"] <= CLIENTS
    snap = in_process.client.metrics()
    mine = [q for q in snap["queries"] if q.get("trace_id") in trace_ids]
    assert sum((q["histograms"].get("engine.sched.gate_wait_s")
                or {"count": 0})["count"] for q in mine) == len(waits)
    assert SCHEDULER.live_count() == 0


# -- (e) a fifth client queues and is answered exactly -----------------------------

def test_a_fifth_client_queues_and_is_answered_exactly(in_process):
    before = in_process.client.metrics()["counters"]
    held = [SCHEDULER.admit(fingerprint=f"{i}" * 16, trace_id=f"held-{i}")
            for i in range(CLIENTS)]            # the four slots are taken
    fifth = BridgeClient(in_process.sock, timeout=900)
    got = []
    sender = threading.Thread(target=lambda: got.append(
        fifth.execute_plan(in_process.blob)))
    sender.start()
    deadline = time.monotonic() + 30
    while SCHEDULER.stats()["queued"] == 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    time.sleep(0.05)
    assert not got and SCHEDULER.live_count() == CLIENTS
    held[0].release()
    sender.join(timeout=120)
    ((h,),) = got
    assert _equals_reference(fifth.export_host(h), in_process.frames)
    fifth.release(h)
    for s in held[1:]:
        s.release()
    after = in_process.client.metrics()["counters"]
    assert after["engine.sched.queued"] \
        - before.get("engine.sched.queued", 0) == 1
    assert after.get("engine.sched.shed", 0) \
        == before.get("engine.sched.shed", 0)
    (span,) = _sched_spans("engine.sched.queue_wait")
    assert span["stats"] == {"live": CLIENTS, "trace_id": fifth.trace_id}
    assert SCHEDULER.live_count() == 0
    fifth.close()


# -- the bridge's last-plan pair ---------------------------------------------------

def test_last_plan_pair_is_written_under_the_metrics_lock(in_process):
    """N connection threads end plans at once: stats and summary of the
    last plan are one plan's only if both are assigned under the lock
    `_op_metrics` reads them under."""
    server = in_process.server
    seen = []

    class Watched(type(server)):
        def __setattr__(self, name, value):
            if name in ("_last_plan_stats", "_last_plan_summary"):
                seen.append((name, self._metrics_lock.locked()))
            super().__setattr__(name, value)

    server.__class__ = Watched
    try:
        (h,) = in_process.client.execute_plan(in_process.blob)
        in_process.client.release(h)
    finally:
        server.__class__ = Watched.__mro__[1]
    assert seen == [("_last_plan_stats", True), ("_last_plan_summary", True)]
    snap = in_process.client.metrics()
    assert snap["last_plan"]["chunks"] == 11
    assert snap["last_plan_summary"]["trace_id"] == in_process.client.trace_id


# -- the benchmark's cell and its readers, on synthetic contexts -------------------

def _reader(name):
    return _load(os.path.join(BENCH, "layer_metrics", name + ".py"),
                 f"c4test_{name}")


def _h(total, count):
    return {"sum": total, "count": count}


def _ctx(h0=None, h1=None, samples=None, clients=CLIENTS, admitted=(0, 0)):
    loop = types.SimpleNamespace(
        clients=[types.SimpleNamespace(trace_id=f"t{i}")
                 for i in range(clients)],
        samples=samples if samples is not None
        else [(i % clients, 0.0, 0.5) for i in range(8)],
        t_start=0.0, t_end=2.0)
    return {"loop": loop, "trace": None, "trace_doc": None,
            "snap_start": {"counters": {"engine.sched.admitted": admitted[0]},
                           "histograms": h0 or {}},
            "snap_end": {"counters": {"engine.sched.admitted": admitted[1]},
                         "histograms": h1 or {}, "queries": []}}


def test_the_cell_is_the_year_cell_sent_by_four_clients():
    run = _load(os.path.join(BENCH, "run.py"), "c4test_run")
    cell = run.Cell(CELL)
    base = _json("configs", "nds_q5lite_sf1.json")
    assert (cell.chips, cell.entry["traffic"]) == (1, "year_c4")
    assert TRAFFIC["clients"] == CLIENTS and TRAFFIC["loop"] == "closed"
    assert PARAMS == _json("traffic", "year.json")["params"]
    for key in ("query", "scale_factor", "tables", "storage",
                "rehearsal_rows", "reduced"):
        assert CONFIG[key] == base[key], key
    assert CONFIG["guarantees"][:3] == base["guarantees"]
    assert CONFIG["server_env"] == {"SRJT_RESULT_CACHE": "0",
                                    "SRJT_MAX_SESSIONS": "4"}
    assert CONFIG["deployment"]["concurrent_tasks"] == CLIENTS
    assert [m["name"] for m in cell.metrics("end_to_end")] \
        == ["fact_rows_per_s", "setup_s"]
    mine = {m["name"] for m in cell.metrics("per_layer")}
    new = {"sched_gate_wait_ms", "sched_sessions_live", "stream_overlap_pct",
           "client_share_min_pct"}
    # two accepted readers find nothing to read with four clients and list
    # the four accepted cells instead (PERF.md section 3)
    silent = {"bridge_overhead_ms", "post_stream_launches"}
    assert new <= mine and not silent & mine
    # the request path's six readers and the chunk aggregate's form
    assert "bridge_server_ms" in mine and len(mine) == 17 + 6 + 1
    for other in ("q5lite_sf1_year", "q55lite_sf1_nov1999",
                  "q5lite_sf1_14day", "q5lite_sf1_mesh4"):
        theirs = {m["name"] for m in run.Cell(other).metrics("per_layer")}
        assert silent <= theirs and not new & theirs


@pytest.mark.parametrize("name,want", [("sched_gate_wait_ms", 100.0),
                                       ("sched_sessions_live", 3.75)])
def test_reader_reads_the_histograms_growth(name, want):
    ctx = _ctx({"engine.sched.gate_wait_s": _h(0.2, 4),
                "engine.sched.live_sessions": _h(4.0, 4)},
               {"engine.sched.gate_wait_s": _h(1.0, 20),
                "engine.sched.live_sessions": _h(34.0, 12)}, admitted=(2, 10))
    assert _reader(name).read(ctx) == pytest.approx(want)


def test_a_window_that_never_blocked_waited_zero():
    """Sessions were admitted and none blocked: 0 ms, not nothing."""
    reader = _reader("sched_gate_wait_ms")
    assert reader.read(_ctx(admitted=(2, 10))) == 0.0
    assert reader.read(_ctx(admitted=(2, 2))) is None
    assert reader.read(_ctx(admitted=(2, 10), samples=[])) is None


@pytest.mark.parametrize("name", ["sched_gate_wait_ms", "sched_sessions_live",
                                  "stream_overlap_pct"])
def test_reader_finds_nothing_where_the_program_has_nothing(name):
    assert _reader(name).read(_ctx()) is None


def test_client_share_is_the_least_served_clients_share():
    reader = _reader("client_share_min_pct")
    samples = [(c, 0.0, 0.5) for c, n in enumerate((5, 5, 5, 3))
               for _ in range(n)] + [(3, 0.0, None)]    # a failure: not done
    assert reader.read(_ctx(samples=samples)) \
        == pytest.approx(3 / (18 / 4) * 100.0)
    assert reader.read(_ctx()) == pytest.approx(100.0)
    assert reader.read(_ctx(clients=1)) is None
    assert reader.read(_ctx(samples=[])) is None


def test_stream_overlap_on_a_hand_made_span_list():
    share = _reader("stream_overlap_pct").overlap_share
    window = (100, 200)
    assert share([[(100, 200)], [(100, 200)]], window) == 1.0
    assert share([[(100, 200)]], window) == 0.0
    # one thread's spans nest and overlap: it still counts once
    assert share([[(100, 200), (100, 200), (120, 130)]], window) == 0.0
    assert share([[(0, 150)], [(120, 300)]], window) == pytest.approx(0.3)
    # three threads: [110,150] x [130,170] x [140,190] -> two open 130..170
    three = [[(110, 150)], [(130, 170)], [(140, 190)]]
    assert share(three, window) == pytest.approx(0.4)
    assert share(three, window, depth=3) == pytest.approx(0.1)
    # back to back on one thread is that thread streaming throughout;
    # spans outside the window count nothing
    assert share([[(100, 150), (150, 200)], [(0, 90), (210, 300)]],
                 window) == 0.0
    assert share([[(100, 150), (150, 200)], [(100, 200)]], window) == 1.0
    # a whole-query span missing from the stretch: its chunk-level
    # children stand for it — (100..160) against (105..115) + (150..190)
    assert share([[(100, 120), (110, 130), (125, 160)],
                  [(105, 115), (150, 190)]], window) == pytest.approx(0.2)


def test_stream_overlap_on_the_recorded_trace(monkeypatch):
    """`fixtures/tpu_probe.xplane.pb`: the spans of one thread never
    overlap with another's; given to two threads they cover what they
    cover."""
    reader = _reader("stream_overlap_pct")
    fixture = os.path.join(BENCH, "fixtures", "tpu_probe.xplane.pb")
    monkeypatch.setattr(reader.span_reduce, "xplane_of", lambda ctx: fixture)
    assert reader.read({}) == 0.0       # `engine.fused_segment`, one thread
    monkeypatch.setattr(reader, "SPANS", {"engine.stream"})
    assert reader.read({}) is None      # no such span there
    planes = [p for p in reader.trace_reduce.read_planes(fixture)
              if p.name == "/host:CPU"]
    window = reader.trace_reduce._host_spans(planes[0])[2]
    spans = [(s, e) for _, s, e in reader.span_reduce.named_spans(
        planes[0], {"engine.fused_segment"})["engine.fused_segment"]]
    alone = reader.overlap_share([spans], window, depth=1)
    assert 0.0 < alone < 1.0
    assert reader.overlap_share([spans, spans], window) == alone
