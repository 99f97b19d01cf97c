"""The four-chip deployment of the benchmark's cell ``q5lite_sf1_mesh4``
(configuration ``nds_q5lite_sf1_mesh4``: NDS q5-lite under ``SRJT_DIST=1``
on a 4-device mesh), on the CPU.

- (a) the cell's plan at the configuration's ``rehearsal_rows``, served
  over the bridge by a 4-device child with the configuration's
  ``server_env``, equals the plain pandas reference exactly, and equals a
  1-device child's result without ``SRJT_DIST`` bit for bit, for three
  seeds: the shares add up to the whole;
- (b) what a warm distributed query counts: 4 exchanges, the syncs
  ``verify.sync_budget`` charges, 2 shuffles, 2 broadcasts, no exchange
  program rebuilt, and one timed span per exchange in its summary;
- (c) under ``SRJT_TRACE=1`` the exchange spans reach
  ``jax.profiler.TraceAnnotation`` with the client's trace id and their
  stats, and a hash exchange's two waits nest inside it;
- (d) the static census of the plan equals the executed one;
- the benchmark's readers of these spans and counters give known values
  on known inputs and None where the program has nothing to read.
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
from spark_rapids_jni_tpu.utils import config as cfg

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
SEEDS = (7, 20, 2147483777)
DEVICES = "--xla_force_host_platform_device_count="


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


CONFIG = _json("configs", "nds_q5lite_sf1_mesh4.json")
PARAMS = _json("traffic", "year.json")["params"]
QUERY = _load(os.path.join(BENCH, "queries", CONFIG["query"] + ".py"),
              "mesh4test_query")


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
    return frames, plan


def _query_of(snapshot, trace_id):
    return [q for q in snapshot["queries"]
            if q.get("trace_id") == trace_id][-1]


def _serve(sock, env, plans):
    """Each plan twice through one server child started with ``env``;
    per plan the second (warm) run's columns, its summary and the
    process-wide counters before and after it."""
    proc = spawn_server(sock, env=env, timeout=180)
    client = BridgeClient(sock, timeout=900)
    out = []
    try:
        device = client.metrics()["device"]
        for plan in plans:
            (h,) = client.execute_plan(plan)
            client.release(h)
            before = client.metrics()
            (h,) = client.execute_plan(plan)
            cols = client.export_host(h)
            client.release(h)
            after = client.metrics()
            out.append({"cols": cols, "before": before["counters"],
                        "after": after["counters"],
                        "query": _query_of(after, client.trace_id)})
        client.shutdown_server()
        proc.wait(timeout=60)
    finally:
        client.close()
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    return device, out


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("mesh4"))
    houses = [_warehouse(root, seed, CONFIG["rehearsal_rows"][QUERY.FACT])
              for seed in SEEDS]
    blobs = [plan.serialize() for _, plan in houses]
    mesh_env = {**CONFIG["server_env"], "XLA_FLAGS": DEVICES + "4"}
    one_env = {"SRJT_RESULT_CACHE": "0", "SRJT_DIST": "0",
               "XLA_FLAGS": DEVICES + "1"}
    mesh_device, mesh = _serve(os.path.join(root, "m.sock"), mesh_env, blobs)
    one_device, one = _serve(os.path.join(root, "o.sock"), one_env, blobs)
    assert mesh_device["count"] == 4 and one_device["count"] == 1
    return {"frames": [f for f, _ in houses], "plans": [p for _, p in houses],
            "mesh": mesh, "one": one}


def _grew(run, name):
    return run["after"].get(name, 0) - run["before"].get(name, 0)


def _hist(query, name):
    h = query["histograms"].get(name)
    return (h["sum"], h["count"]) if h else (0.0, 0)


# -- (a) four devices == one device == pandas, bit for bit ----------------------

@pytest.mark.parametrize("i", range(len(SEEDS)), ids=[str(s) for s in SEEDS])
def test_mesh_result_equals_reference_and_one_device(served, i):
    want = QUERY.reference(served["frames"][i], PARAMS)
    mesh_cols = served["mesh"][i]["cols"]
    one_cols = served["one"][i]["cols"]
    assert len(mesh_cols) == len(one_cols) == len(want.columns)
    for name, (_, got, valid), (_, alone, valid1) in zip(
            want.columns, mesh_cols, one_cols):
        assert valid is None or np.asarray(valid).all()
        assert valid1 is None or np.asarray(valid1).all()
        ref = want[name].to_numpy()
        assert got.dtype == alone.dtype == ref.dtype
        assert got.tobytes() == alone.tobytes() == ref.tobytes(), name


# -- (b) what a warm distributed query counts -----------------------------------

@pytest.mark.parametrize("i", range(len(SEEDS)), ids=[str(s) for s in SEEDS])
def test_warm_query_counts(served, i):
    from spark_rapids_jni_tpu.engine import optimize
    from spark_rapids_jni_tpu.engine.verify import sync_budget
    run = served["mesh"][i]
    q = run["query"]
    budget = len(sync_budget(optimize(served["plans"][i], distribute=True),
                             ndev=4))
    assert q["stats"]["exchanges"] == 4
    assert _grew(run, "engine.host_sync") == budget == 6
    assert q["counters"]["engine.host_sync"] == budget
    assert _grew(run, "engine.exchange.shuffles") == 2
    assert _grew(run, "engine.exchange.broadcasts") == 2
    assert _grew(run, "engine.exchange.program_build") == 0
    assert run["after"]["engine.exchange.program_build"] >= 2
    assert not [k for k in run["after"] if k.startswith("engine.degraded")]
    assert _hist(q, "engine.exchange.hash_s")[1] == 2
    assert _hist(q, "engine.exchange.broadcast_s")[1] == 2
    assert _hist(q, "engine.sync_wait_s")[1] == budget
    # the one-device child plans no exchange and times none
    alone = served["one"][i]
    assert alone["query"]["stats"]["exchanges"] == 0
    assert _hist(alone["query"], "engine.exchange.hash_s")[1] == 0
    assert _grew(alone, "engine.host_sync") == 2


# -- (d) the static census equals the executed one -------------------------------

def test_static_census_equals_executed(served):
    from spark_rapids_jni_tpu.engine import optimize
    from spark_rapids_jni_tpu.engine.verify import plan_exchanges
    census = plan_exchanges(optimize(served["plans"][0], distribute=True))
    assert sorted(e["kind"] for e in census) \
        == ["broadcast", "broadcast", "hash", "hash"]
    assert all(run["query"]["stats"]["exchanges"] == len(census)
               for run in served["mesh"])
    labels = [n["label"] for n in served["mesh"][0]["query"]["nodes"]]
    assert labels.count("exchange") == len(census)


# -- (c) the spans under SRJT_TRACE=1 --------------------------------------------

class _Annotation:
    """Stands in for ``jax.profiler.TraceAnnotation`` (as in
    `test_span_tree.py`): what each span was given, and when it was open."""

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
def traced(tmp_path_factory):
    """The cell's plan on a small fact, twice through an in-process
    ``BridgeServer`` over this process's devices, `_Annotation` in the
    profiler's place."""
    import jax

    from spark_rapids_jni_tpu.bridge.server import BridgeServer
    root = str(tmp_path_factory.mktemp("mesh4spans"))
    _, plan = _warehouse(root, 11, 24_000)
    mp = pytest.MonkeyPatch()
    for k, v in {**CONFIG["server_env"], "SRJT_TRACE": "1"}.items():
        mp.setenv(k, v)
    mp.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    cfg.refresh()
    sock = os.path.join(root, "b.sock")
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
        (h,) = client.execute_plan(plan)
        client.release(h)
        out = {"log": list(_Annotation.log), "trace_id": client.trace_id,
               "devices": len(jax.devices()),
               "query": _query_of(client.metrics(), client.trace_id)}
    finally:
        client.shutdown_server()
        client.close()
        st.join(timeout=10)
        mp.undo()
        cfg.refresh()
    assert not st.is_alive()
    return out


@pytest.mark.parametrize("name,stat", [("engine.exchange.hash", "chunks"),
                                       ("engine.exchange.broadcast",
                                        "wire_bytes")])
def test_exchange_span_is_an_annotation_with_the_trace_id(traced, name, stat):
    spans = [r for r in traced["log"] if r["name"] == name]
    assert len(spans) == 2
    (serve_thread,) = {r["thread"] for r in traced["log"]
                       if r["name"] == "engine.execute"}
    for r in spans:
        assert r["thread"] == serve_thread
        assert r["stats"]["trace_id"] == traced["trace_id"]
        assert r["stats"]["rows"] >= 1 and r["stats"][stat] >= 1


def test_a_hash_exchange_holds_its_two_waits(traced):
    log = traced["log"]
    hashes = [r for r in log if r["name"] == "engine.exchange.hash"]
    waits = [r for r in log if r["name"] == "engine.sync_wait"
             and r["stats"]["label"].startswith("exchange-")]
    assert len(waits) == 4
    for h in hashes:
        inside = [w for w in waits if h["t0"] <= w["t0"] and w["t1"] <= h["t1"]]
        assert [w["stats"]["label"] for w in inside] \
            == ["exchange-counts-sizing", "exchange-compaction"]
        assert h["t1"] - h["t0"] >= sum(w["t1"] - w["t0"] for w in inside)
    # a broadcast waits for nothing; no exchange span holds another
    spans = hashes + [r for r in log
                      if r["name"] == "engine.exchange.broadcast"]
    spans.sort(key=lambda r: r["t0"])
    assert all(a["t1"] <= b["t0"] for a, b in zip(spans, spans[1:]))
    q = traced["query"]
    assert _hist(q, "engine.exchange.hash_s")[1] == 2
    assert _hist(q, "engine.exchange.broadcast_s")[1] == 2
    assert _hist(q, "engine.exchange.hash_s")[0] \
        >= sum(w["t1"] - w["t0"] for w in waits) * 0.999


# -- the benchmark's readers, on a synthetic ctx ----------------------------------

def _reader(name):
    return _load(os.path.join(BENCH, "layer_metrics", name + ".py"),
                 f"mesh4test_{name}")


def _ctx(c0, c1, h0=None, h1=None, queries=4):
    loop = types.SimpleNamespace(
        clients=[types.SimpleNamespace(trace_id="t1")],
        samples=[(0, 0.0, 0.5)] * queries, t_start=0.0, t_end=2.0)
    return {"loop": loop, "trace": None, "trace_doc": None,
            "snap_start": {"counters": c0, "histograms": h0 or {}},
            "snap_end": {"counters": c1, "histograms": h1 or {},
                         "queries": []}}


def _h(total, count):
    return {"sum": total, "count": count}


@pytest.mark.parametrize("name,want", [
    ("exchanges_per_query", 4.0), ("exchange_wire_bytes", 2560.0),
    ("exchange_ms", 30.0)])
def test_reader_reads_growth_per_query(name, want):
    ctx = _ctx({"engine.exchange.shuffles": 4, "engine.exchange.broadcasts": 4,
                "engine.exchange.wire_bytes": 5120},
               {"engine.exchange.shuffles": 12,
                "engine.exchange.broadcasts": 12,
                "engine.exchange.wire_bytes": 15360},
               {"engine.exchange.hash_s": _h(0.2, 4),
                "engine.exchange.broadcast_s": _h(0.02, 4)},
               {"engine.exchange.hash_s": _h(0.3, 12),
                "engine.exchange.broadcast_s": _h(0.04, 12)})
    assert _reader(name).read(ctx) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "exchanges_per_query", "exchange_wire_bytes", "exchange_ms",
    "exchange_device_ms", "chip_busy_max_pct", "chip_busy_min_pct"])
def test_reader_finds_nothing_in_a_program_without_exchanges(name):
    assert _reader(name).read(_ctx({}, {})) is None


def test_chip_busy_and_exchange_device_time_on_the_recorded_trace(
        monkeypatch):
    """`fixtures/tpu_probe.xplane.pb` (one v5e chip; `selfcheck.py` has its
    numbers): the one plane's busy share, and the device time of the
    executions launched inside a named host span at any depth."""
    fixture = os.path.join(BENCH, "fixtures", "tpu_probe.xplane.pb")
    busy = _reader("chip_busy_max_pct")
    monkeypatch.setattr(busy.span_reduce, "xplane_of", lambda ctx: fixture)
    share = 0.000837507422 / 0.600205138 * 100.0
    assert busy.chip_busy_pcts({}) == [pytest.approx(share, rel=1e-9)]
    assert busy.read({}) == _reader("chip_busy_min_pct").read({}) \
        == pytest.approx(share, rel=1e-9)
    dev = _reader("exchange_device_ms")
    assert dev.exchange_device_s(fixture) is None    # no exchange span there
    monkeypatch.setattr(dev, "SPANS", {"engine.fused_segment"})
    seconds, window = dev.exchange_device_s(fixture)
    assert seconds == pytest.approx(0.000810988828, rel=1e-9)
    assert window == pytest.approx(0.600205138, rel=1e-9)
    ctx = _ctx({}, {}, queries=4)
    ctx["trace"], ctx["trace_doc"] = {"busy_s": 1.0}, {"log_dir": ""}
    assert dev.read(ctx) == pytest.approx(
        seconds / window * 2.0 / 4 * 1e3, rel=1e-9)
