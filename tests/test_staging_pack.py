"""The staged pack (io/staging.py): the blob `stage_fixed_table` ships is byte
for byte the old construction's, and a transfer buffer is reused only when
the device is done with it.

The old construction — pad with `np.concatenate`, copy with `tobytes`, join —
is kept here as the plain reference."""

import threading

import jax
import numpy as np
import pytest

from spark_rapids_jni_tpu import dtypes as dt
from spark_rapids_jni_tpu.io import staging, write_parquet
from spark_rapids_jni_tpu.io.parquet import ParquetChunkedReader
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.utils import metrics, tracing

BUCKET = 4096
KINDS = {"w8": (dt.INT64, np.int64), "w4": (dt.INT32, np.int32),
         "w2": (dt.INT16, np.int16), "w1": (dt.INT8, np.int8)}


def reference_blob(specs) -> bytes:
    """What `stage_fixed_table` packed before it wrote columns in place."""
    bucket = staging._bucket(len(specs[0][2]))
    parts = []

    def push(arr):
        arr = np.ascontiguousarray(arr)
        arr = np.concatenate([arr, np.zeros(bucket - len(arr), arr.dtype)])
        b = arr.tobytes()
        parts.append(b + b"\0" * (-len(b) % 4))

    for _, _, values, validity in specs:
        push(values)
        if validity is not None:
            push(np.asarray(validity, np.uint8))
    return b"".join(parts)


def packed_blob(specs) -> bytes:
    plan, total = staging._plan_for(specs)
    blob = np.full(total, 0xA5A5A5A5, np.uint32)    # a dirty, reused buffer
    staging._pack_into(blob, specs, plan)
    return blob.tobytes()


def values_of(np_type, n, seed=0, strided=False):
    rng = np.random.default_rng(seed)
    info = np.iinfo(np_type)
    v = rng.integers(info.min, info.max, n * (2 if strided else 1),
                     dtype=np_type)
    return v[::2] if strided else v


# -- (a) the blob is the old construction's -------------------------------------

@pytest.mark.parametrize("n", [1, 1023, 1024, BUCKET - 1, BUCKET])
@pytest.mark.parametrize("nullable", [False, True], ids=["dense", "nullable"])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_blob_equals_the_old_construction(kind, nullable, n):
    dtype, np_type = KINDS[kind]
    validity = (np.random.default_rng(n).random(n) > 0.3) if nullable else None
    specs = [("c", dtype, values_of(np_type, n, seed=n), validity)]
    assert staging._bucket(n) == (1024 if n <= 1024 else BUCKET)
    assert [e[0] for e in staging._plan_for(specs)[0]] \
        == [kind] + ["w1"] * nullable
    assert packed_blob(specs) == reference_blob(specs)


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_strided_input_packs_like_its_copy(kind):
    dtype, np_type = KINDS[kind]
    n = BUCKET - 7
    v = values_of(np_type, n, strided=True)
    valid = np.ones(2 * n, np.uint8)[::2]
    assert not v.flags.c_contiguous and not valid.flags.c_contiguous
    specs = [("c", dtype, v, valid)]
    assert packed_blob(specs) == reference_blob(specs)


def test_a_byte_swapped_input_packs_its_values():
    n = 1500
    v = values_of(np.int64, n)
    swapped = v.astype(">i8")
    assert swapped.tobytes() != v.tobytes()
    assert packed_blob([("c", dt.INT64, swapped, None)]) \
        == reference_blob([("c", dt.INT64, v, None)])


def test_every_width_in_one_blob():
    n = 1025
    rng = np.random.default_rng(5)
    specs = [
        ("a", dt.INT64, values_of(np.int64, n), rng.random(n) > 0.5),
        ("b", dt.FLOAT64, rng.standard_normal(n), None),
        ("c", dt.INT16, values_of(np.int16, n), rng.random(n) > 0.5),
        ("d", dt.BOOL8, rng.random(n) > 0.5, None),
        ("e", dt.FLOAT32, rng.random(n).astype(np.float32), None),
        ("f", dt.INT8, values_of(np.int8, n), (rng.random(n) > 0.5)
         .astype(np.int64)),
    ]
    assert packed_blob(specs) == reference_blob(specs)


def test_an_unpadded_read_is_the_padded_one_cut_in_one_launch(monkeypatch):
    """``stage_fixed_table(specs)`` cuts the unpacked arrays back to the
    true row count in ONE program (``_trim``) — every column in its device
    storage, a validity as bool — where it sliced column by column."""
    n = 1500
    rng = np.random.default_rng(6)
    specs = [
        ("a", dt.INT64, values_of(np.int64, n), rng.random(n) > 0.5),
        ("b", dt.FLOAT64, rng.standard_normal(n), None),
        ("c", dt.INT16, values_of(np.int16, n), rng.random(n) > 0.5),
        ("d", dt.FLOAT32, rng.random(n).astype(np.float32), None),
    ]
    launches = []
    trim = staging._trim
    monkeypatch.setattr(staging, "_trim", lambda *a: (
        launches.append(a[1:]), trim(*a))[1])
    cut = staging.stage_fixed_table(specs)
    assert len(launches) == 1 and launches[0][0] == n
    padded, rows = staging.stage_fixed_table(specs, padded=True)
    assert len(launches) == 1 and rows == n
    assert cut.names == padded.names and cut.num_rows == n
    for c, p, (_, dtype, values, validity) in zip(
            cut.columns, padded.columns, specs):
        assert c.data.dtype == p.data.dtype == np.dtype(dtype.device_storage)
        assert np.array_equal(np.asarray(c.data), np.asarray(p.data)[:n])
        assert np.asarray(c.data).tobytes() == np.asarray(values).tobytes()
        assert (c.validity is None) == (validity is None)
        if validity is not None:
            assert c.validity.dtype == p.validity.dtype == np.bool_
            assert np.array_equal(np.asarray(c.validity), validity)
            assert not np.asarray(p.validity)[n:].any()


def test_values_of_another_width_are_refused():
    with pytest.raises(TypeError, match="4-byte column"):
        packed_blob([("c", dt.INT32, np.arange(10, dtype=np.int64), None)])


# -- (b) a buffer is reused only when the device is done with it ----------------

class _Pending:
    """Stands in for a device array whose transfer has not finished."""

    def __init__(self):
        self.ready = self.deleted = False

    def is_ready(self):
        assert not self.deleted, "is_ready of a deleted array crashes"
        return self.ready

    def is_deleted(self):
        return self.deleted


def test_a_buffer_stays_out_until_its_arrays_are_ready():
    pool = staging._BlobPool()
    first, reused = pool.take(256)
    assert not reused
    a, b = _Pending(), _Pending()
    pool.give(first, (a, b))
    assert pool.held_bytes() == 1024
    second, reused = pool.take(256)
    assert not reused and second is not first
    a.ready = True                       # one of two: still the device's
    third, reused = pool.take(256)
    assert not reused and third is not first
    b.ready = True
    again, reused = pool.take(256)
    assert reused and again is first
    assert pool.held_bytes() == 0        # out again: the caller's alone


def test_a_buffer_whose_array_was_deleted_is_forgotten():
    pool = staging._BlobPool()
    buf, _ = pool.take(256)
    gone = _Pending()
    gone.deleted = True
    pool.give(buf, (gone, _Pending()))
    other, reused = pool.take(256)
    assert not reused and other is not buf
    assert pool.held_bytes() == 0


def test_a_buffer_goes_only_to_a_blob_of_its_size():
    pool = staging._BlobPool()
    buf, _ = pool.take(256)
    pool.give(buf, ())
    other, reused = pool.take(512)
    assert not reused and other.size == 512
    same, reused = pool.take(256)
    assert reused and same is buf


def chunk_specs(i, n, nullable=False):
    rng = np.random.default_rng(1000 + i)
    valid = (rng.random(n) > 0.2) if nullable else None
    return [("k", dt.INT64, rng.integers(-2**60, 2**60, n), valid),
            ("q", dt.INT32, rng.integers(-2**30, 2**30, n, dtype=np.int32),
             None),
            ("p", dt.FLOAT64, rng.standard_normal(n), valid)]


def assert_table_is(table, specs, n_rows=None):
    for name, _, values, validity in specs:
        col = table.column(name)
        got = col.to_numpy()
        if n_rows is not None:                        # the padded form
            assert len(got) == staging._bucket(len(values))
            assert not got[n_rows:].any(), name
            got = got[:n_rows]
        np.testing.assert_array_equal(got, values, err_msg=name)
        if validity is not None:
            mask = np.asarray(col.valid_mask())
            assert not mask[len(values):].any(), name
            np.testing.assert_array_equal(mask[:len(values)], validity)


@pytest.mark.parametrize("padded", [True, False], ids=["padded", "sliced"])
@pytest.mark.parametrize("n", [1500, 200_000], ids=["small", "chunk"])
def test_back_to_back_chunks_keep_their_own_rows(monkeypatch, n, padded):
    """More chunks than the list ever held, staged without a wait between
    them: a buffer rewritten under a pending transfer, or under a CPU array
    that aliases it, shows as another chunk's rows."""
    monkeypatch.setattr(staging, "_pool", staging._BlobPool())
    warm = staging.stage_fixed_table(chunk_specs(99, n, True), padded=padded)
    jax.block_until_ready([c.data for c in (warm[0] if padded else warm)
                           .columns])
    before = tracing.counters_snapshot("io.scan.stage")
    inputs = [chunk_specs(i, n, nullable=True) for i in range(12)]
    tables = [staging.stage_fixed_table(s, padded=padded) for s in inputs]
    after = tracing.counters_snapshot("io.scan.stage")
    for specs, out in zip(inputs, tables):
        if padded:
            assert out[1] == n
            assert_table_is(out[0], specs, n_rows=n)
        else:
            assert_table_is(out, specs)
    grew = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    assert grew["io.scan.stage.reused"] >= 1         # the warmed buffer
    assert grew["io.scan.stage.reused"] \
        + grew.get("io.scan.stage.fresh", 0) == len(inputs)
    # nothing is pending any more: the next chunk takes a buffer back
    jax.block_until_ready([c.data for t in tables
                           for c in (t[0] if padded else t).columns])
    assert staging._pool.take(staging._plan_for(inputs[0])[1])[1]


# -- (c) the counters, a second pass, the cap -----------------------------------

@pytest.fixture
def fact_file(tmp_path):
    n = 9 * 4096
    rng = np.random.default_rng(8)
    t = Table([Column.from_numpy(np.arange(n, dtype=np.int64)),
               Column.from_numpy(rng.integers(0, 50, n)),
               Column.from_numpy(rng.standard_normal(n))], ["k", "s", "p"])
    path = tmp_path / "fact.parquet"
    write_parquet(t, path, row_group_size=4096)
    return path, t


def one_pass(path, prefetch):
    """(per-chunk host copies, the query's summary) of one streamed scan;
    each chunk is waited for, as a consumer that uses it would."""
    with metrics.query("pass") as qm:
        reader = ParquetChunkedReader(path, prefetch=prefetch)
        got = []
        for table, n_rows in reader.iter_staged():
            got.append({nm: table.column(nm).to_numpy()[:n_rows]
                        for nm in table.names})
    return got, qm.summary()


@pytest.mark.parametrize("prefetch", [0, 1], ids=["serial", "producer"])
def test_one_counter_grows_per_staged_chunk(monkeypatch, fact_file, prefetch):
    monkeypatch.setattr(staging, "_pool", staging._BlobPool())
    path, t = fact_file
    for _ in range(2):
        got, summary = one_pass(path, prefetch)
        c = summary["counters"]
        assert len(got) == 9 == summary["histograms"]["io.scan.stage_s"][
            "count"]
        assert c.get("io.scan.stage.reused", 0) \
            + c.get("io.scan.stage.fresh", 0) == 9
        assert summary["histograms"]["io.scan.stage.pack_s"]["count"] == 9
        assert summary["histograms"]["io.scan.stage.pack_s"]["sum"] \
            <= summary["histograms"]["io.scan.stage_s"]["sum"]
        for nm in t.names:
            np.testing.assert_array_equal(
                np.concatenate([g[nm] for g in got]),
                t.column(nm).to_numpy())


def test_a_second_pass_allocates_nothing(monkeypatch, fact_file):
    monkeypatch.setattr(staging, "_pool", staging._BlobPool())
    path, _ = fact_file
    _, first = one_pass(path, prefetch=0)
    assert first["counters"]["io.scan.stage.fresh"] >= 1
    held = staging._pool.held_bytes()
    _, second = one_pass(path, prefetch=0)
    assert second["counters"].get("io.scan.stage.fresh", 0) == 0
    assert second["counters"]["io.scan.stage.reused"] == 9
    assert staging._pool.held_bytes() == held


@pytest.mark.parametrize("cap", [40_000, 1 << 20])
def test_many_sizes_never_hold_more_than_the_cap(monkeypatch, cap):
    monkeypatch.setattr(staging, "POOL_MAX_BYTES", cap)
    monkeypatch.setattr(staging, "_pool", staging._BlobPool())
    most = 0
    for i in range(30):
        n = 1024 << (i % 5)                       # 5 buckets x 2 schemas
        specs = chunk_specs(i, n)[:1 + i % 2]
        out = staging.stage_fixed_table(specs)
        jax.block_until_ready([c.data for c in out.columns])
        assert_table_is(out, specs)
        most = max(most, staging._pool.held_bytes())
        assert staging._pool.held_bytes() <= cap
    assert most > 0 or cap < 8192                  # it did keep some


def test_a_blob_larger_than_the_cap_is_never_kept(monkeypatch):
    monkeypatch.setattr(staging, "POOL_MAX_BYTES", 4096)
    pool = staging._BlobPool()
    small, _ = pool.take(512)
    pool.give(small, ())
    big, _ = pool.take(2048)
    pool.give(big, ())                    # evicts the idle one, and still
    assert pool.held_bytes() == 0         # does not fit
    assert not pool.take(2048)[1] and not pool.take(512)[1]


# -- (d) four producers at once ---------------------------------------------------

def test_four_threads_get_four_buffers(monkeypatch):
    monkeypatch.setattr(staging, "_pool", staging._BlobPool())
    n = 5000
    for i in range(4):                    # four idle buffers of one size
        staging._pool.give(np.empty(staging._plan_for(chunk_specs(0, n))[1],
                                    np.uint32), ())
    inside = threading.Barrier(4, timeout=30)
    seen, real = [], staging._pack_into

    def pack(blob, specs, plan):
        seen.append(blob.ctypes.data)
        inside.wait()                     # all four hold a buffer now
        real(blob, specs, plan)

    monkeypatch.setattr(staging, "_pack_into", pack)
    inputs = [chunk_specs(i, n, nullable=True) for i in range(4)]
    out = [None] * 4

    def work(i):
        out[i] = staging.stage_fixed_table(inputs[i], padded=True)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=60)
    assert len(set(seen)) == 4
    for specs, (table, n_rows) in zip(inputs, out):
        assert n_rows == n
        assert_table_is(table, specs, n_rows=n)


def test_the_free_list_under_more_threads_than_cores(monkeypatch):
    """12 threads take and give buffers of two sizes for a second, with the
    interpreter switching threads every few bytecodes: no buffer is ever in
    two hands, and the list's byte count is what it holds."""
    import os
    import sys
    import time
    monkeypatch.setattr(staging, "POOL_MAX_BYTES", 6 * 4096)
    pool = staging._BlobPool()
    out, out_lock, faults_seen = set(), threading.Lock(), []
    deadline = time.monotonic() + 1.0

    def work(k):
        rng = np.random.default_rng(k)
        while time.monotonic() < deadline:
            buf, _ = pool.take(512 if rng.random() < 0.5 else 1024)
            with out_lock:
                if id(buf) in out:
                    faults_seen.append("one buffer in two hands")
                out.add(id(buf))
            buf[:] = k                    # ours alone: nobody overwrites it
            arrays = (_Pending(), _Pending())
            if (buf != k).any():
                faults_seen.append("a buffer was written by two threads")
            with out_lock:
                out.discard(id(buf))
            pool.give(buf, arrays)
            for a in arrays:              # the device finishes a little later
                a.ready = True
            if not 0 <= pool.held_bytes() <= 6 * 4096:
                faults_seen.append(f"held {pool.held_bytes()}")

    threads = [threading.Thread(target=work, args=(k,))
               for k in range(max(12, (os.cpu_count() or 4) + 4))]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=30)
    finally:
        sys.setswitchinterval(switch)
    assert not any(th.is_alive() for th in threads)
    assert not faults_seen, faults_seen[:3]
    pool.take(1)                          # a poll: everything lent is ready
    assert pool.held_bytes() == sum(b.nbytes for b in pool._idle)
    assert not pool._lent
