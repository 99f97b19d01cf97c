"""The pool of decode worker processes (io/decode_pool.py, io/decode_worker.py)
under the streamed scan (io/parquet.py `_host_slices`), on the CPU with a
pool of 2.

- the streamed scan through the pool hands out the very bytes the decode in
  this process hands out (values, validity, which fields are None), for both
  benchmark queries' fact files at ``rehearsal_rows`` and for a file whose
  chunks carry nulls; row groups come in file order with a window of them in
  flight and a pruned group in the middle;
- what engages: ``io.scan.decode.offloaded`` + ``.inline`` = row groups read;
  a 12-row group, a string column and the unstaged iteration decode here; the
  decoder's own counters (``io.parquet.decode.*``) and the span stats are
  what they are without the pool, plus ``worker_ms``;
- what goes wrong: a worker killed mid-stream is replaced and the stream's
  result is complete and equal (one retry at ``parquet.chunk``); an injected
  ``parquet.chunk`` fault is retried; a group a worker cannot decode is decoded
  here; no ``memfd_create`` means no pool and no failure;
- lifetime: `close()` and cancellation with groups in flight return every
  slab and leave no producer thread; `shutdown` leaves no process and no
  thread; a process that exits — or is killed — leaves no worker behind; a
  worker holds the CPU backend only and no profiler;
- many streams over few workers with a short switch interval lose nothing.
"""

import importlib.util
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_jni_tpu.io import decode_pool
from spark_rapids_jni_tpu.io import parquet as pqt
from spark_rapids_jni_tpu.io.parquet import ParquetChunkedReader
from spark_rapids_jni_tpu.utils import config as cfg
from spark_rapids_jni_tpu.utils import metrics, tracing
from spark_rapids_jni_tpu.utils.errors import CancelToken, QueryCancelledError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNTERS = ("io.scan.decode.offloaded", "io.scan.decode.inline",
            "io.parquet.decode.pages", "io.parquet.decode.runs",
            "io.parquet.decode.dense_chunks", "io.parquet.bytes_decoded",
            "engine.retries.parquet.chunk")
CHUNK_BYTES = 8 << 20
WORKERS = 2


def _counters() -> dict:
    return {c: tracing.counter_value(c) for c in COUNTERS}


def _grew(before: dict) -> dict:
    return {k.rsplit(".", 1)[1] if k.startswith("io.") else k: v - before[k]
            for k, v in _counters().items()}


def _alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    # a zombie is gone for every purpose but its parent's wait()
    with open(f"/proc/{pid}/stat") as f:
        return f.read().rsplit(")", 1)[1].split()[0] != "Z"


def _until(cond, timeout=10.0) -> bool:
    deadline = time.monotonic() + timeout
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.01)
    return cond()


@pytest.fixture(scope="module")
def pool():
    """A pool of 2 in the shared pool's place, up before the first test."""
    made = decode_pool.DecodePool(workers=WORKERS, slabs=8)
    old = decode_pool.install(made)
    made.start()
    assert made.wait_ready(), "the decode workers did not come up"
    yield made
    decode_pool.install(old)
    made.shutdown()


def _bench():
    spec = importlib.util.spec_from_file_location(
        "bench_run_for_workers", os.path.join(ROOT, "benchmarks", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def warehouses(tmp_path_factory):
    """Both queries' warehouses at ``rehearsal_rows``, as `run.py` writes."""
    bench = _bench()
    out = {}
    for name in ("q5lite_sf1_year", "q55lite_sf1_nov1999"):
        cell = bench.Cell(name)
        frames = cell.query.tables(2147483777, cell.rows(rehearsal=True))
        root = tmp_path_factory.mktemp(name)
        out[name] = (cell, frames,
                     bench.write_tables(frames, cell.config, str(root)))
    return out


@pytest.fixture(scope="module")
def nulls_file(tmp_path_factory):
    """12 row groups of 20,000 rows: a sorted key (so a range prunes), a
    column with 5 % nulls, one with none, one all null in group 3."""
    n, groups = 240_000, 12
    rng = np.random.default_rng(35)
    k = np.sort(rng.integers(0, 1200, n)).astype(np.int64)
    hole = np.zeros(n, bool)
    hole[3 * n // groups:4 * n // groups] = True
    table = pa.table({
        "k": pa.array(k),
        "v": pa.array(rng.integers(0, 1 << 40, n) / 4096, pa.float64(),
                      mask=rng.random(n) < 0.05),
        "full": pa.array(rng.integers(0, 300, n).astype(np.int32)),
        "gap": pa.array(rng.integers(0, 9, n), pa.int64(), mask=hole),
    })
    path = str(tmp_path_factory.mktemp("nulls") / "nulls.parquet")
    pq.write_table(table, path, compression="snappy",
                   row_group_size=n // groups)
    return path, k


def _slices(path, offload: bool, **kw):
    """Every host slice of the streamed scan, copied out as the staged
    pack copies it: [(name, dtype, values bytes, validity bytes | None)]."""
    reader = ParquetChunkedReader(path, pass_read_limit=CHUNK_BYTES, **kw)
    out = []
    for sl in reader._host_slices(offload=offload):
        out.append([(h.schema.name, h.values.dtype.str, h.values.tobytes(),
                     None if h.validity is None else h.validity.tobytes())
                    for h in sl])
    return out, reader


# -- the same bytes ----------------------------------------------------------------

@pytest.mark.parametrize("which", ["q5lite_sf1_year", "q55lite_sf1_nov1999",
                                   "nulls"])
def test_pool_hands_out_the_bytes_of_the_inline_decode(pool, warehouses,
                                                       nulls_file, which):
    path = nulls_file[0] if which == "nulls" \
        else warehouses[which][2]["store_sales"]
    before = _counters()
    want, _ = _slices(path, offload=False)
    inline = _grew(before)
    before = _counters()
    got, reader = _slices(path, offload=True)
    pooled = _grew(before)
    assert len(got) == len(want) == 12
    assert got == want
    if which == "nulls":
        assert any(v is not None and not all(v) for _, _, _, v in got[0])
        assert not any(got[3][3][3])                # "gap", all null there
    assert (inline["offloaded"], inline["inline"]) == (0, 12)
    assert (pooled["offloaded"], pooled["inline"]) == (12, 0)
    assert reader.groups_read == 12 and reader.groups_pruned == 0
    # the decoder's counters, published here from the workers' tallies
    for name in ("pages", "runs", "dense_chunks", "bytes_decoded"):
        assert pooled[name] == inline[name] > 0, name
    assert pool.slabs_free() == pool.slabs_total()


@pytest.mark.parametrize("workload", ["q5lite_sf1_year",
                                      "q55lite_sf1_nov1999"])
def test_staged_scan_through_the_pool_keeps_the_decode_invariants(
        pool, warehouses, workload):
    """`iter_staged` (the cells' path) with the pool on: 3 dense chunks a
    group (`test_parquet_decode_identity.py`'s invariant), every group
    offloaded, the device tables equal to the frames."""
    cell, frames, paths = warehouses[workload]
    fact = frames["store_sales"]
    before = _counters()
    h0 = metrics.histograms_snapshot("io.scan.decode").copy()
    with ParquetChunkedReader(paths["store_sales"],
                              pass_read_limit=CHUNK_BYTES,
                              prefetch=1) as reader:
        parts = [(t, n) for t, n in reader.iter_staged()]
        groups = reader.groups_read
    grew = _grew(before)
    assert groups == 12 and sum(n for _, n in parts) == len(fact)
    assert (grew["offloaded"], grew["inline"]) == (12, 0)
    assert grew["dense_chunks"] == 3 * groups
    assert grew["pages"] >= 3 * groups and grew["runs"] >= grew["pages"]
    for name in fact.columns:
        got = np.concatenate([np.asarray(t[name].data)[:n]
                              for t, n in parts])
        assert got.tobytes() == fact[name].to_numpy().tobytes(), name
    h1 = metrics.histograms_snapshot("io.scan.decode")

    def count(h, name):
        return (h.get(name) or {"count": 0})["count"]
    assert count(h1, "io.scan.decode_s") - count(h0, "io.scan.decode_s") == 12
    assert count(h1, "io.scan.decode.worker_s") \
        - count(h0, "io.scan.decode.worker_s") == 12
    assert pool.slabs_free() == pool.slabs_total()


def test_file_order_with_a_window_in_flight_and_a_pruned_group(pool,
                                                              nulls_file):
    """``k`` in [400, 500] or above 700 is not a range: prune with two
    readers' worth of predicates — a middle group and both ends."""
    path, k = nulls_file
    per = len(k) // 12
    lo, hi = int(k[per * 4]), int(k[per * 8 - 1])
    keep = [g for g in range(12)
            if not (k[g * per:(g + 1) * per].max() < lo
                    or k[g * per:(g + 1) * per].min() > hi)]
    assert 0 not in keep and 11 not in keep and len(keep) >= 4
    before = _counters()
    got, reader = _slices(path, offload=True, predicate=("k", lo, hi))
    want, plain = _slices(path, offload=False, predicate=("k", lo, hi))
    assert got == want and len(got) == len(keep)
    assert reader.groups_read == plain.groups_read == len(keep)
    assert reader.groups_pruned == plain.groups_pruned == 12 - len(keep)
    # in file order: the first key of every slice grows
    firsts = [np.frombuffer(sl[0][2], np.int64)[0] for sl in got]
    assert firsts == sorted(firsts)
    assert firsts[0] == k[keep[0] * per]
    grew = _grew(before)
    assert grew["offloaded"] == len(keep)
    # a hole in the middle: groups 0-2 and 6-11 of what is left
    full, _ = _slices(path, offload=True)
    assert [sl[0][2] for sl in full][keep[0]:keep[-1] + 1] \
        == [sl[0][2] for sl in got]


# -- what engages ------------------------------------------------------------------

def test_small_groups_strings_and_the_unstaged_iteration_decode_here(
        pool, warehouses, tmp_path):
    cell, frames, paths = warehouses["q5lite_sf1_year"]
    before = _counters()
    got, reader = _slices(paths["store"], offload=True)     # 12 rows
    assert reader.groups_read == 1 and len(got[0][0][2]) == 12 * 8
    grew = _grew(before)
    assert (grew["offloaded"], grew["inline"]) == (0, 1)
    foot = pqt.ParquetFile(paths["store"]).row_groups[0].total_byte_size
    assert foot < pqt.OFFLOAD_MIN_BYTES \
        < pqt.ParquetFile(paths["store_sales"]).row_groups[0].total_byte_size

    # a string column: the slab carries fixed-width columns only
    n = 60_000
    rng = np.random.default_rng(3)
    path = str(tmp_path / "s.parquet")
    pq.write_table(pa.table({
        "k": pa.array(rng.integers(0, 1 << 40, n)),
        "s": pa.array([f"name-{v}" for v in rng.integers(0, 99, n)])}), path,
        row_group_size=n // 2)
    assert pqt.ParquetFile(path).row_groups[0].total_byte_size \
        > pqt.OFFLOAD_MIN_BYTES
    before = _counters()
    reader = ParquetChunkedReader(path, pass_read_limit=CHUNK_BYTES)
    assert sum(sl[0].num_rows for sl in reader._host_slices(offload=True)) == n
    grew = _grew(before)
    assert (grew["offloaded"], grew["inline"]) == (0, 2)
    # ... and the same file's fixed-width column alone is carried
    before = _counters()
    reader = ParquetChunkedReader(path, pass_read_limit=CHUNK_BYTES,
                                  columns=["k"])
    assert sum(sl[0].num_rows for sl in reader._host_slices(offload=True)) == n
    assert _grew(before)["offloaded"] == 2

    # iteration as Tables (`_exec_scan`, top-k): `to_column` may alias the
    # host buffer, so nothing there reads from a slab
    before = _counters()
    rows = sum(t.num_rows for t in ParquetChunkedReader(
        paths["store_sales"], pass_read_limit=CHUNK_BYTES))
    assert rows == len(frames["store_sales"])
    grew = _grew(before)
    assert (grew["offloaded"], grew["inline"]) == (0, 12)


def test_decode_span_says_who_decoded(pool, warehouses, monkeypatch):
    """``io.scan.decode`` itself carries what its decode walked and who
    did it (``worker_ms``: a worker), set through the open span's handle."""
    import jax
    log = []

    class Annotation:
        def __init__(self, name, **stats):
            self.stats = stats
            log.append((name, stats))

        def set_metadata(self, **stats):
            self.stats.update(stats)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setenv("SRJT_TRACE", "1")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    cfg.refresh()
    try:
        _, _, paths = warehouses["q5lite_sf1_year"]
        _slices(paths["store_sales"], offload=True)
        _slices(paths["store"], offload=True)
    finally:
        monkeypatch.undo()
        cfg.refresh()
    assert not [s for n, s in log if n == "io.scan.decode.walked"]
    spans = [s for n, s in log if n == "io.scan.decode"]
    assert len(spans) == 13
    assert [s["group"] for s in spans] == list(range(12)) + [0]
    for s in spans[:12]:
        assert s["dense"] == "3/3" and s["pages"] >= 3
        assert 0 < s["worker_ms"] < 10_000
    assert "worker_ms" not in spans[12] and spans[12]["dense"] == "2/2"


# -- what goes wrong ---------------------------------------------------------------

def test_a_killed_worker_is_replaced_and_the_stream_is_whole(pool,
                                                             nulls_file,
                                                             monkeypatch):
    path, _ = nulls_file
    monkeypatch.setenv("SRJT_RETRY_BACKOFF_S", "0.001")
    cfg.refresh()
    want, _ = _slices(path, offload=False)
    pids = [h["hello"] for h in pool.hellos()]
    assert len(pids) == WORKERS
    before = _counters()
    reader = ParquetChunkedReader(path, pass_read_limit=CHUNK_BYTES)
    got = []
    try:
        for i, sl in enumerate(reader._host_slices(offload=True)):
            got.append([(h.schema.name, h.values.dtype.str,
                         h.values.tobytes(), None if h.validity is None
                         else h.validity.tobytes()) for h in sl])
            if i == 2:          # mid-stream: a window of groups is in flight
                for pid in pids:
                    os.kill(pid, signal.SIGKILL)
    finally:
        monkeypatch.undo()
        cfg.refresh()
    assert got == want                      # complete, in order, equal
    grew = _grew(before)
    assert grew["offloaded"] + grew["inline"] == 12
    assert grew["dense_chunks"] == sum(
        v is not None and all(v) for sl in want for _, _, _, v in sl)
    assert _until(lambda: not any(_alive(p) for p in pids))
    # both are replaced, and the pool serves again
    assert pool.wait_ready(60)
    new = [h["hello"] for h in pool.hellos()]
    assert len(new) == WORKERS and not set(new) & set(pids)
    assert _until(lambda: pool.slabs_free() == pool.slabs_total())
    before = _counters()
    assert _slices(path, offload=True)[0] == want
    assert _grew(before)["offloaded"] == 12


def test_a_worker_lost_with_a_group_is_one_retry(pool, nulls_file,
                                                 monkeypatch):
    """The group in a dying worker's hands reads `WorkerLost`, a transient
    error: `retry_call` submits it again (`engine.retries.parquet.chunk`)."""
    path, _ = nulls_file
    monkeypatch.setenv("SRJT_RETRY_BACKOFF_S", "0.001")
    cfg.refresh()
    pf = pqt.ParquetFile(path)
    try:
        # stop both workers, so that what is submitted stays in their hands
        pids = [h["hello"] for h in pool.hellos()]
        for pid in pids:
            os.kill(pid, signal.SIGSTOP)
        reader = ParquetChunkedReader(path, pass_read_limit=CHUNK_BYTES)
        lease = reader._offload(5)
        assert lease is not None and lease.ticket is not None
        assert _until(lambda: lease.ticket.running)
        for pid in pids:
            os.kill(pid, signal.SIGKILL)
        before = _counters()
        got = list(reader._host_slices_group(5, lease))
        grew = _grew(before)
    finally:
        monkeypatch.undo()
        cfg.refresh()
    assert grew["engine.retries.parquet.chunk"] >= 1
    assert grew["offloaded"] + grew["inline"] == 1
    want = pf._decode_group(5)
    assert [h.values.tobytes() for h in got[0]] \
        == [h.values.tobytes() for h in want]
    assert pool.wait_ready(60)
    assert _until(lambda: pool.slabs_free() == pool.slabs_total())


def test_an_injected_chunk_fault_is_retried_with_the_pool_on(pool,
                                                             nulls_file,
                                                             monkeypatch):
    from spark_rapids_jni_tpu.utils import faults
    path, _ = nulls_file
    want, _ = _slices(path, offload=False)
    monkeypatch.setenv("SRJT_FAULTS", "parquet.chunk:3:io_error")
    monkeypatch.setenv("SRJT_RETRY_BACKOFF_S", "0.001")
    cfg.refresh()
    faults.reset()
    try:
        before = _counters()
        got, _ = _slices(path, offload=True)
        grew = _grew(before)
    finally:
        monkeypatch.undo()
        cfg.refresh()
        faults.reset()
    assert got == want
    assert grew["engine.retries.parquet.chunk"] == 1
    assert (grew["offloaded"], grew["inline"]) == (12, 0)
    assert pool.slabs_free() == pool.slabs_total()


def test_a_group_a_worker_cannot_decode_is_decoded_here(pool, nulls_file,
                                                        tmp_path):
    """The worker answers with an error (here: the file is gone for it);
    this process decodes the group and raises — or not — what it finds."""
    path, _ = nulls_file
    reader = ParquetChunkedReader(path, pass_read_limit=CHUNK_BYTES)
    real = reader.file.path
    reader.file.path = str(tmp_path / "nowhere.parquet")    # what is sent
    try:
        before = _counters()
        got = [[h.values.tobytes() for h in sl]
               for sl in reader._host_slices(offload=True)]
        grew = _grew(before)
    finally:
        reader.file.path = real
    want, _ = _slices(path, offload=False)
    assert got == [[v for _, _, v, _ in sl] for sl in want]
    assert (grew["offloaded"], grew["inline"]) == (0, 12)
    assert pool.slabs_free() == pool.slabs_total()


def test_without_memfd_there_is_no_pool_and_no_failure(nulls_file,
                                                      monkeypatch, caplog):
    path, _ = nulls_file
    old = decode_pool.install(None)
    monkeypatch.delattr(os, "memfd_create")
    try:
        with caplog.at_level("WARNING"):
            assert decode_pool.shared() is None
            assert decode_pool.shared() is None
        before = _counters()
        got, _ = _slices(path, offload=True)
        grew = _grew(before)
    finally:
        monkeypatch.undo()
        decode_pool.install(old)
    assert len(got) == 12
    assert (grew["offloaded"], grew["inline"]) == (0, 12)
    assert sum("decode pool: cannot be made" in r.getMessage()
               for r in caplog.records) == 1            # logged once


# -- lifetime ----------------------------------------------------------------------

def test_close_with_groups_in_flight_returns_every_slab(pool, warehouses):
    _, _, paths = warehouses["q5lite_sf1_year"]
    threads = threading.active_count()
    reader = ParquetChunkedReader(paths["store_sales"],
                                  pass_read_limit=CHUNK_BYTES, prefetch=1)
    it = reader.iter_staged()
    next(it)                                  # LIMIT 1: abandon the rest
    assert pool.slabs_free() < pool.slabs_total()
    reader.close()
    assert _until(lambda: pool.slabs_free() == pool.slabs_total())
    assert _until(lambda: threading.active_count() == threads)
    assert pool.live() == WORKERS             # the pool stays
    # ... and an iterator that is dropped, never closed
    it = ParquetChunkedReader(paths["store_sales"],
                              pass_read_limit=CHUNK_BYTES)._host_slices(
                                  offload=True)
    next(it)
    assert pool.slabs_free() == pool.slabs_total() - (decode_pool.READ_AHEAD + 1)
    del it
    assert _until(lambda: pool.slabs_free() == pool.slabs_total())


def test_cancellation_with_groups_in_flight_returns_every_slab(pool,
                                                               warehouses):
    _, _, paths = warehouses["q5lite_sf1_year"]
    threads = threading.active_count()
    token = CancelToken()
    reader = ParquetChunkedReader(paths["store_sales"],
                                  pass_read_limit=CHUNK_BYTES, prefetch=1,
                                  cancel=token)
    seen = 0
    with pytest.raises(QueryCancelledError):
        for _ in reader.iter_staged():
            seen += 1
            if seen == 2:
                token.cancel("test")
    reader.close()
    assert 2 <= seen < 12
    assert _until(lambda: pool.slabs_free() == pool.slabs_total())
    assert _until(lambda: threading.active_count() == threads)
    # a stopped worker holds a cancelled stream's wait no longer than a poll
    pids = [h["hello"] for h in pool.hellos()]
    for pid in pids:
        os.kill(pid, signal.SIGSTOP)
    try:
        token = CancelToken(timeout_s=0.3)
        reader = ParquetChunkedReader(paths["store_sales"],
                                      pass_read_limit=CHUNK_BYTES,
                                      cancel=token)
        t0 = time.monotonic()
        with pytest.raises(Exception) as e:
            list(reader._host_slices(offload=True))
        assert type(e.value).__name__ in ("QueryTimeoutError",
                                          "QueryCancelledError")
        assert time.monotonic() - t0 < 5
    finally:
        for pid in pids:
            os.kill(pid, signal.SIGCONT)
    assert _until(lambda: pool.slabs_free() == pool.slabs_total())


def test_workers_hold_the_cpu_backend_only_and_no_profiler(pool,
                                                           monkeypatch):
    """Whatever the parent's environment says: a worker started now, with
    the parent set for an accelerator and a traced run, is held to the CPU."""
    for hello in pool.hellos():
        assert hello["backends"] in ([], ["cpu"])
        assert hello["jax_platforms"] == "cpu" and hello["trace"] is False
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setenv("SRJT_TRACE", "1")
    one = decode_pool.DecodePool(workers=1)
    try:
        one.start()
        assert one.wait_ready()
        (hello,) = one.hellos()
        assert hello["backends"] in ([], ["cpu"])
        assert hello["jax_platforms"] == "cpu" and hello["trace"] is False
        with open(f"/proc/{hello['hello']}/environ", "rb") as f:
            env = dict(kv.split(b"=", 1) for kv in f.read().split(b"\0")
                       if b"=" in kv)
        assert env[b"JAX_PLATFORMS"] == b"cpu" and b"SRJT_TRACE" not in env
    finally:
        one.shutdown()


def test_shutdown_leaves_no_process_and_no_thread(nulls_file):
    path, _ = nulls_file
    threads = threading.active_count()
    mine = decode_pool.DecodePool(workers=2, slabs=3)
    old = decode_pool.install(mine)
    try:
        mine.start()
        assert mine.wait_ready()
        pids = [h["hello"] for h in mine.hellos()]
        # 3 slabs under a window of 4: the fourth group decodes here
        before = _counters()
        got, _ = _slices(path, offload=True)
        grew = _grew(before)
        assert len(got) == 12 and grew["offloaded"] + grew["inline"] == 12
        assert grew["inline"] >= 1 and grew["offloaded"] >= 3
        it = ParquetChunkedReader(path, pass_read_limit=CHUNK_BYTES) \
            ._host_slices(offload=True)
        next(it)                                # groups in flight at shutdown
    finally:
        decode_pool.install(old)
        mine.shutdown()
    mine.shutdown()                             # idempotent
    assert not any(_alive(p) for p in pids)
    assert _until(lambda: threading.active_count() == threads)
    assert mine.submit(path, 0, None, 1 << 20) is None
    # what was in flight reads as lost, and the stream decodes on: here
    rest = list(it)
    assert len(rest) == 11


OWNER = """
import os, sys, time
sys.path.insert(0, {root!r})
from spark_rapids_jni_tpu.io import decode_pool
decode_pool.install(decode_pool.DecodePool(workers=2))
pool = decode_pool.shared()         # starts it; the exit handler owns it
assert pool is not None and pool.wait_ready()
print(" ".join(str(h["hello"]) for h in pool.hellos()), flush=True)
if sys.argv[1] == "hang":
    time.sleep(600)
"""


@pytest.mark.parametrize("how", ["exit", "sigkill"])
def test_no_worker_outlives_the_process_that_owns_the_pool(tmp_path, how):
    env = cfg.child_environ()
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-c", OWNER.format(root=ROOT),
         "hang" if how == "sigkill" else "leave"],
        stdout=subprocess.PIPE, env=env, text=True)
    try:
        pids = [int(p) for p in proc.stdout.readline().split()]
        assert len(pids) == 2
        if how == "sigkill":
            assert all(_alive(p) for p in pids)
            proc.kill()
        assert proc.wait(timeout=60) == (-signal.SIGKILL if how == "sigkill"
                                         else 0)
        assert _until(lambda: not any(_alive(p) for p in pids), 30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


# -- many streams, few workers -----------------------------------------------------

def test_sixteen_streams_over_two_workers_lose_nothing(pool, nulls_file):
    path, _ = nulls_file
    want, _ = _slices(path, offload=False)
    want = json.dumps([[(n, d) for n, d, _, _ in sl] for sl in want]), \
        [[(v, m) for _, _, v, m in sl] for sl in want]
    results, errors = {}, []
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)

    def stream(i):
        try:
            got, reader = _slices(path, offload=True)
            results[i] = ([[(v, m) for _, _, v, m in sl] for sl in got],
                          reader.groups_read)
        except Exception as e:  # noqa: BLE001 — the test shows it
            errors.append((i, repr(e)))

    before = _counters()
    try:
        threads = [threading.Thread(target=stream, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    grew = _grew(before)
    assert not errors, errors
    assert len(results) == 16
    for got, groups in results.values():
        assert groups == 12 and got == want[1]
    # every group was decoded exactly once, by a worker or — with all 8
    # slabs out — here
    assert grew["offloaded"] + grew["inline"] == 16 * 12
    assert grew["offloaded"] >= 12
    assert _until(lambda: pool.slabs_free() == pool.slabs_total())
