"""Whole-plan bridge dispatch: one PLAN_EXECUTE round trip vs per-op calls.

The Flare-style win (PAPERS.md) the engine exists for: on an RTT-dominated
link, shipping the serialized plan in ONE message beats a round trip per
relational op.  The same multi-op query (scan x2 -> join -> groupby -> sort)
runs both ways against one server; results must agree and the plan path must
cost strictly fewer round trips.  The server's plan cache must report a hit
on the second submission of the same plan.
"""

import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_jni_tpu.bridge import BridgeClient, spawn_server
from spark_rapids_jni_tpu.bridge import protocol as P
from spark_rapids_jni_tpu.engine import (Aggregate, Filter, Join,
                                         PlanVerificationError, Scan, Sort,
                                         col, lit)


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    sock = str(tmp_path_factory.mktemp("bridge") / "tpub.sock")
    proc = spawn_server(sock)
    yield sock
    try:
        c = BridgeClient(sock)
        c.shutdown_server()
    except Exception:
        proc.kill()
    proc.wait(timeout=30)


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    root = tmp_path_factory.mktemp("planio")
    rng = np.random.default_rng(3)
    k = rng.integers(0, 20, 400).astype(np.int64)
    pq.write_table(pa.table({
        "k": pa.array(k),
        "v": pa.array(rng.integers(-50, 50, 400).astype(np.int64)),
    }), root / "fact.parquet")
    dk = np.arange(20, dtype=np.int64)
    pq.write_table(pa.table({
        "k": pa.array(dk),
        "w": pa.array(dk * 10),
    }), root / "dim.parquet")
    return root


def multi_op_plan(root):
    j = Join(Scan(root / "fact.parquet"), Scan(root / "dim.parquet"),
             ["k"], ["k"], how="inner")
    agg = Aggregate(j, ["k"], [("v", "sum"), ("w", "sum")],
                    names=["sv", "sw"])
    return Sort(agg, (("k", True),))


def run_per_op(c, root):
    """The same query, one bridge round trip per relational op."""
    th1 = c.read_parquet(str(root / "fact.parquet"))
    th2 = c.read_parquet(str(root / "dim.parquet"))
    jh = c.join(th1, th2, [0], [0], "inner")       # -> k, v, w
    gh = c.groupby(jh, [0], [(1, P.AGG_SUM), (2, P.AGG_SUM)])
    sh = c.sort(gh, [(0, True, None)])
    return sh, [th1, th2, jh, gh]


def test_plan_execute_one_round_trip(server, files):
    c = BridgeClient(server)

    before = c.round_trips
    handles = c.execute_plan(multi_op_plan(files))
    plan_trips = c.round_trips - before
    assert plan_trips == 1          # the whole multi-op plan in ONE message
    assert len(handles) == 1

    before = c.round_trips
    sh, temps = run_per_op(c, files)
    per_op_trips = c.round_trips - before
    assert plan_trips < per_op_trips  # 1 vs scan+scan+join+groupby+sort

    got = c.export_table(handles[0])
    want = c.export_table(sh)
    assert got.num_rows == want.num_rows == 20
    assert got.num_columns == want.num_columns == 3
    for i in range(3):
        np.testing.assert_array_equal(np.asarray(got.columns[i].data),
                                      np.asarray(want.columns[i].data),
                                      err_msg=f"col {i}")

    for h in handles + [sh] + temps:
        c.release(h)
    assert c.live_count() == 0
    c.close()


def test_plan_cache_hit_on_resubmission(server, files):
    c = BridgeClient(server)
    plan = multi_op_plan(files)

    h1 = c.execute_plan(plan)
    m1 = c.metrics()
    assert m1["plan_cache"]["size"] >= 1
    assert m1["last_plan"]["nodes"] >= 4

    # the identical plan serialized again -> same fingerprint -> cache hit
    h2 = c.execute_plan(plan.serialize())
    m2 = c.metrics()
    assert m2["plan_cache"]["hits"] == m1["plan_cache"]["hits"] + 1
    assert m2["plan_cache"]["misses"] == m1["plan_cache"]["misses"]

    t1, t2 = c.export_table(h1[0]), c.export_table(h2[0])
    for i in range(t1.num_columns):
        np.testing.assert_array_equal(np.asarray(t1.columns[i].data),
                                      np.asarray(t2.columns[i].data))
    for h in h1 + h2:
        c.release(h)
    c.close()


def test_plan_execute_error_discipline(server):
    """A malformed plan errors back; the server survives (CATCH_STD role)."""
    c = BridgeClient(server)
    with pytest.raises(RuntimeError):
        c.execute_plan(b'{"version":1,"root":0,"nodes":[{"op":"Nope"}]}')
    c.ping()
    with pytest.raises(RuntimeError):  # scan of a missing file
        c.execute_plan(Scan("/nonexistent/q.parquet"))
    c.ping()
    c.close()


def test_plan_execute_structured_verification_error(server, files):
    """A plan failing build-time verification comes back as a
    PlanVerificationError with the check code and node path intact — the
    server verifies BEFORE executing, so the reply is a structured error
    document, not a traceback string from deep inside a chunk loop."""
    c = BridgeClient(server)
    bad = Sort(Filter(Scan(files / "fact.parquet"),
                      (">", col("nope"), lit(1))), (("k", True),))
    with pytest.raises(PlanVerificationError) as ei:
        c.execute_plan(bad)
    assert ei.value.code == "unknown-column"
    assert ei.value.node_path == "root.child"
    assert "nope" in ei.value.message
    c.ping()  # server survived

    # dtype-family mismatch on join keys: also structured
    pq.write_table(pa.table({"w": pa.array(np.zeros(4))}),
                   files / "floatdim.parquet")
    mismatch = Join(Scan(files / "fact.parquet"),
                    Scan(files / "floatdim.parquet"), ["k"], ["w"],
                    how="inner")
    with pytest.raises(PlanVerificationError) as ei:
        c.execute_plan(mismatch)
    assert ei.value.code == "join-key-dtype-mismatch"
    assert ei.value.node_path == "root"
    c.ping()
    c.close()


def test_second_connection_after_release_and_close(server, files):
    """A server must take a second client: the first runs a plan (a snappy
    scan), a per-op read and an export, releases everything and closes;
    only after its server thread is gone does a second connection send
    PLAN_EXECUTE.  The server used to die with SIGSEGV here — pyarrow,
    first loaded inside the first connection's thread, lost its allocator
    state with that thread (bridge/server.py loads it on the main thread)."""
    import time
    c = BridgeClient(server)
    handles = c.execute_plan(multi_op_plan(files))
    th = c.read_parquet(str(files / "fact.parquet"))
    want = c.export_host(handles[0])
    for h in handles + [th]:
        c.release(h)
    assert c.live_count() == 0
    c.close()
    time.sleep(1.0)  # let the first connection's server thread exit

    c2 = BridgeClient(server)
    h2 = c2.execute_plan(multi_op_plan(files))
    got = c2.export_host(h2[0])
    for (_, a, _), (_, b, _) in zip(got, want):
        np.testing.assert_array_equal(a, b)
    c2.release(h2[0])
    assert c2.metrics()["errors"] >= 0  # the server is alive and answering
    c2.close()


def test_children_inherit_the_platform_and_report_their_device(server,
                                                                monkeypatch):
    """``child_environ`` injects no platform — a child takes what it
    inherits, or jax's own choice — and the server says over OP_METRICS
    which device it computes on."""
    from spark_rapids_jni_tpu.utils.config import child_environ
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert "JAX_PLATFORMS" not in child_environ()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert child_environ()["JAX_PLATFORMS"] == "cpu"
    assert "spark_rapids_jni_tpu" in "".join(
        os.listdir(child_environ()["PYTHONPATH"].split(os.pathsep)[0]))

    c = BridgeClient(server)
    device = c.metrics()["device"]
    c.close()
    # the suite's servers inherit JAX_PLATFORMS=cpu from tests/conftest.py
    assert device["platform"] == "cpu"
    assert device["kind"] and device["count"] >= 1
    assert "memory" in device  # allocator stats; None where unreported
