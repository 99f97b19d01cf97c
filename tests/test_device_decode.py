"""Device-side Parquet decode (SRJT_DEVICE_DECODE, ops/parquet_decode).

Golden parity against pyarrow's own decode across the supported matrix
(codec x encoding x dtype x nulls), the typed truncation error, the
ledgered host fallback for unsupported shapes, the parquet.device_decode
fault seam (transient retry + persistent transfer-error fallback), the
footer-parse-once cache, the word assembly, and the engine
end-to-end path (bit-exact vs the host decoder, decode=device in EXPLAIN
ANALYZE, census == ledger, "pages" partitioning).
"""

import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import spark_rapids_jni_tpu  # noqa: F401  (enables x64)
import spark_rapids_jni_tpu.utils.config as cfgmod
from spark_rapids_jni_tpu.io import parquet as pqio
from spark_rapids_jni_tpu.ops import parquet_decode as pqd
from spark_rapids_jni_tpu.utils import faults


@pytest.fixture
def device_decode_env(monkeypatch):
    """SRJT_DEVICE_DECODE=1 for the test body, restored on teardown."""
    monkeypatch.setenv("SRJT_DEVICE_DECODE", "1")
    cfgmod.refresh()
    yield
    monkeypatch.delenv("SRJT_DEVICE_DECODE")
    cfgmod.refresh()


def _decode_file(path, columns=None):
    """Every row group through plan_device_group + decode_table; returns
    [(DevicePageChunk, decoded Table)] — asserts no host fallback."""
    pf = pqio.ParquetFile(path)
    out = []
    for gi in range(pf.num_row_groups):
        chunk, reason = pqio.plan_device_group(pf, gi, columns, 1 << 30)
        assert chunk is not None, f"group {gi} fell back: {reason}"
        out.append((chunk, pqd.decode_table(chunk.to_device(), chunk.geom)))
    return out


def _assert_group_parity(chunk, table, ref):
    """Decoded device table == the pyarrow row group, values and nulls.

    Bit-exact on the valid slots: floats compare as bit patterns (the
    decoder may store FLOAT64 as int64 words), and expected values come
    from ``drop_null()`` so pyarrow never round-trips a nullable int
    column through float64.
    """
    n = chunk.nrows
    assert n == ref.num_rows
    for name, col in zip(table.names, table.columns):
        arr = ref[name].combine_chunks()
        want_valid = ~np.asarray(arr.is_null())
        got = np.asarray(col.data)[:n]
        if col.validity is not None:
            got_valid = np.asarray(col.validity)[:n]
            assert np.array_equal(got_valid, want_valid), name
            # padded rows past nrows must be invalid, not garbage
            assert not np.asarray(col.validity)[n:].any(), name
        else:
            assert want_valid.all(), name
            got_valid = want_valid
        gotv = got[got_valid]
        want = arr.drop_null().to_numpy(zero_copy_only=False)
        if np.issubdtype(want.dtype, np.floating):
            width = gotv.dtype.itemsize * 8
            iw = np.dtype(f"int{width}")
            wb = want.astype(np.dtype(f"float{width}")).view(iw)
            assert np.array_equal(gotv.view(iw), wb), name
        else:
            assert np.array_equal(gotv.astype(np.int64),
                                  want.astype(np.int64)), name


def _column(rng, dtype, n, nulls):
    if dtype == "bool":
        vals = rng.integers(0, 2, n).astype(bool)
        typ = pa.bool_()
    elif dtype.startswith("float"):
        vals = (rng.integers(-1000, 1000, n) * 0.25).astype(dtype)
        typ = pa.float32() if dtype == "float32" else pa.float64()
    else:
        lo, hi = (-(1 << 30), 1 << 30) if dtype == "int32" else \
            (-(1 << 60), 1 << 60)
        vals = rng.integers(lo, hi, n).astype(dtype)
        typ = pa.int32() if dtype == "int32" else pa.int64()
    if nulls == "none":
        mask = None
    elif nulls == "all":
        mask = np.ones(n, bool)
    else:
        mask = rng.random(n) < 0.25
    return pa.array(vals, type=typ, mask=mask)


class TestGoldenParity:
    """Kernel-level decode vs pyarrow across the supported matrix."""

    @pytest.mark.parametrize("nulls", ["none", "sparse", "all"])
    @pytest.mark.parametrize(
        "dtype", ["int32", "int64", "float32", "float64", "bool"])
    def test_snappy_plain(self, tmp_path, dtype, nulls):
        rng = np.random.default_rng(11)
        n = 1200
        path = str(tmp_path / "t.parquet")
        pq.write_table(pa.table({"x": _column(rng, dtype, n, nulls)}),
                       path, row_group_size=n // 2, compression="snappy",
                       use_dictionary=False)
        ref = pq.ParquetFile(path)
        for gi, (chunk, table) in enumerate(_decode_file(path)):
            _assert_group_parity(chunk, table, ref.read_row_group(gi))

    @pytest.mark.parametrize("nulls", ["none", "sparse"])
    def test_uncompressed_plain(self, tmp_path, nulls):
        rng = np.random.default_rng(12)
        n = 1200
        path = str(tmp_path / "t.parquet")
        pq.write_table(pa.table({
            "a": _column(rng, "int64", n, nulls),
            "b": _column(rng, "float64", n, nulls),
        }), path, row_group_size=n // 2, compression="none",
            use_dictionary=False)
        ref = pq.ParquetFile(path)
        for gi, (chunk, table) in enumerate(_decode_file(path)):
            _assert_group_parity(chunk, table, ref.read_row_group(gi))

    @pytest.mark.parametrize("nulls", ["none", "sparse", "all"])
    @pytest.mark.parametrize("codec", ["snappy", "none"])
    def test_dictionary_encoding(self, tmp_path, codec, nulls):
        # low cardinality keeps pyarrow on RLE_DICTIONARY pages
        rng = np.random.default_rng(13)
        n = 1200
        vals = rng.integers(0, 17, n).astype(np.int64) * 1001
        mask = None if nulls == "none" else \
            (np.ones(n, bool) if nulls == "all" else rng.random(n) < 0.25)
        path = str(tmp_path / "t.parquet")
        pq.write_table(
            pa.table({"x": pa.array(vals, type=pa.int64(), mask=mask)}),
            path, row_group_size=n // 2, compression=codec)
        pf = pqio.ParquetFile(path)
        chunk, reason = pqio.plan_device_group(pf, 0, None, 1 << 30)
        assert chunk is not None, reason
        assert chunk.geom.column("x").encoding == "dict"
        ref = pq.ParquetFile(path)
        for gi, (chunk, table) in enumerate(_decode_file(path)):
            _assert_group_parity(chunk, table, ref.read_row_group(gi))

    def test_snappy_back_references_ship_fewer_bytes(self, tmp_path):
        # repeating values make snappy emit copies, not only literals: the
        # copy resolution decodes bit-exact, and the compressed pages the
        # device path ships are fewer bytes than the decoded columns
        import jax
        n = 2_400
        path = str(tmp_path / "t.parquet")
        pq.write_table(pa.table({
            "a": pa.array((np.arange(n) % 97).astype(np.int64)),
            "b": pa.array(np.repeat(np.arange(n // 100), 100)
                          .astype(np.int64)),
        }), path, compression="snappy", use_dictionary=False)
        chunk, reason = pqio.plan_device_group(pqio.ParquetFile(path), 0,
                                               None, 1 << 30)
        assert chunk is not None, reason
        table = jax.jit(pqd.decode_table, static_argnums=1)(
            chunk.to_device(), chunk.geom)
        _assert_group_parity(chunk, table, pq.read_table(path))
        assert chunk.comp_bytes < chunk.unc_bytes

    def test_multi_column_multi_page(self, tmp_path):
        # small data_page_size forces several pages per column chunk, so
        # the on-device row -> (page, slot) derivation sees npages > 1
        rng = np.random.default_rng(14)
        n = 4000
        path = str(tmp_path / "t.parquet")
        pq.write_table(pa.table({
            "i": _column(rng, "int64", n, "sparse"),
            "f": _column(rng, "float64", n, "none"),
            "b": _column(rng, "bool", n, "sparse"),
        }), path, row_group_size=n, compression="snappy",
            use_dictionary=False, data_page_size=4096)
        (chunk, table), = _decode_file(path)
        assert chunk.geom.column("i").npages > 1
        _assert_group_parity(chunk, table, pq.read_table(path))


class TestEdges:
    def test_empty_file_scan(self, tmp_path, device_decode_env):
        from spark_rapids_jni_tpu.engine import Scan, execute, new_stats
        path = str(tmp_path / "empty.parquet")
        pq.write_table(pa.table({"x": pa.array([], type=pa.int64())}), path)
        out = execute(Scan(path), new_stats())
        assert out.num_rows == 0 and list(out.names) == ["x"]

    def test_truncated_page_raises_typed_error(self, tmp_path):
        path = str(tmp_path / "t.parquet")
        pq.write_table(pa.table({"x": pa.array(range(500), pa.int64())}),
                       path, compression="snappy", use_dictionary=False)
        pf = pqio.ParquetFile(path)
        # shrink the chunk bound so the first page body overruns it —
        # byte-identical to a truncated/torn object-store read
        pf.row_groups[0].chunks[0].total_compressed = 5
        with pytest.raises(pqio.TruncatedPageError):
            pqio.plan_device_group(pf, 0, None, 1 << 30)
        from spark_rapids_jni_tpu.utils.errors import TransientError
        assert issubclass(pqio.TruncatedPageError, TransientError)
        assert issubclass(pqio.TruncatedPageError, OSError)

    def test_unsupported_shapes_report_reason(self, tmp_path):
        cases = {
            "strings": (pa.table({"s": pa.array(["a", "bb", None])}),
                        "physical_type"),
            "nested": (pa.table({"l": pa.array([[1], [2, 3], None])}),
                       "nested"),
        }
        for name, (table, want) in cases.items():
            path = str(tmp_path / f"{name}.parquet")
            pq.write_table(table, path)
            chunk, reason = pqio.plan_device_group(
                pqio.ParquetFile(path), 0, None, 1 << 30)
            assert chunk is None and reason == want, (name, reason)

    def test_unsupported_codec_falls_back(self, tmp_path):
        path = str(tmp_path / "t.parquet")
        pq.write_table(pa.table({"x": pa.array(range(500), pa.int64())}),
                       path, compression="zstd", use_dictionary=False)
        chunk, reason = pqio.plan_device_group(
            pqio.ParquetFile(path), 0, None, 1 << 30)
        assert chunk is None and reason == "codec"

    def test_footer_parsed_once(self, tmp_path, metrics_isolation):
        from spark_rapids_jni_tpu.utils import metrics
        metrics_isolation("io.footer_parses")
        path = str(tmp_path / "t.parquet")
        pq.write_table(pa.table({"x": pa.array(range(500), pa.int64())}),
                       path, compression="snappy", use_dictionary=False)
        for _ in range(3):
            pf = pqio.ParquetFile(path)
            pqio.plan_device_group(pf, 0, None, 1 << 30)
        snap = metrics.snapshot()["counters"]
        if metrics.enabled():
            assert snap.get("io.footer_parses") == 1

    def test_word_assembly_matches_numpy(self):
        # the shift assembly vs numpy's little-endian view of the same bytes
        rng = np.random.default_rng(15)
        b = rng.integers(0, 256, (2, 512, 4), dtype=np.uint8)
        import jax.numpy as jnp
        got = pqd.assemble_u32(jnp.asarray(b))
        want = b.view("<u4").reshape(2, 512)
        assert np.array_equal(np.asarray(got), want)


class TestFaultSeam:
    def test_transient_fault_is_retried(self, tmp_path, monkeypatch,
                                        device_decode_env,
                                        metrics_isolation):
        from spark_rapids_jni_tpu.engine import (Aggregate, Scan, execute,
                                                 new_stats)
        from spark_rapids_jni_tpu.utils import metrics
        metrics_isolation("io.device_decode")
        path = str(tmp_path / "t.parquet")
        rng = np.random.default_rng(16)
        pq.write_table(pa.table({
            "k": pa.array(rng.integers(0, 7, 2000), pa.int64()),
            "x": pa.array(rng.integers(0, 99, 2000), pa.int64()),
        }), path, row_group_size=500, compression="snappy",
            use_dictionary=False)
        plan = Aggregate(Scan(path, chunk_bytes=1 << 20), ["k"],
                         [("x", "sum")], names=["s"])
        base = execute(plan, new_stats())
        monkeypatch.setenv("SRJT_RETRY_BACKOFF_S", "0.001")
        monkeypatch.setenv("SRJT_FAULTS", "parquet.device_decode:1:io_error")
        cfgmod.refresh()
        faults.reset()
        try:
            out = execute(plan, new_stats())
        finally:
            monkeypatch.delenv("SRJT_FAULTS")
            cfgmod.refresh()
            faults.reset()

        def norm(t):
            cols = {n: np.asarray(c.data) for n, c in zip(t.names,
                                                          t.columns)}
            order = np.argsort(cols["k"])
            return [(n, cols[n][order].tolist()) for n in sorted(cols)]

        assert norm(out) == norm(base)
        if metrics.enabled():
            snap = metrics.snapshot()["counters"]
            # the one-shot fault was retried, not fallen back
            assert snap.get("io.device_decode.fallbacks", 0) == 0
            assert snap.get("io.device_decode.chunks", 0) >= 1

    def test_persistent_fault_falls_back_to_host(self, tmp_path,
                                                 monkeypatch,
                                                 device_decode_env):
        from spark_rapids_jni_tpu.engine.explain import explain_analyze
        from spark_rapids_jni_tpu.engine import Aggregate, Scan, execute, \
            new_stats
        path = str(tmp_path / "t.parquet")
        rng = np.random.default_rng(17)
        pq.write_table(pa.table({
            "k": pa.array(rng.integers(0, 7, 2000), pa.int64()),
            "v": pa.array(rng.integers(0, 99, 2000), pa.int64()),
        }), path, row_group_size=500, compression="snappy",
            use_dictionary=False)
        plan = Aggregate(Scan(path, chunk_bytes=1 << 20), ["k"],
                         [("v", "sum")], names=["s"])
        base = execute(plan, new_stats())
        monkeypatch.setenv("SRJT_RETRY_BACKOFF_S", "0.001")
        monkeypatch.setenv("SRJT_FAULTS", "parquet.device_decode:*:io_error")
        cfgmod.refresh()
        faults.reset()
        try:
            rep = explain_analyze(plan, distribute=False)
        finally:
            monkeypatch.delenv("SRJT_FAULTS")
            cfgmod.refresh()
            faults.reset()

        def norm(t):
            cols = {n: np.asarray(c.data) for n, c in zip(t.names,
                                                          t.columns)}
            order = np.argsort(cols["k"])
            return [(n, cols[n][order].tolist()) for n in sorted(cols)]

        assert norm(rep.result) == norm(base)
        dd = next(d for d in rep.decisions
                  if d["kind"] == "scan:device_decode" and d.get("runtime"))
        assert dd["choice"] == "host"
        assert dd["device_chunks"] == 0 and dd["host_chunks"] >= 1
        assert "transfer_error" in dd["reasons"]


class TestEngineE2E:
    def _warehouse(self, tmp_path, n=6000):
        rng = np.random.default_rng(21)
        path = str(tmp_path / "fact.parquet")
        pq.write_table(pa.table({
            "k": pa.array(rng.integers(0, 9, n), pa.int64()),
            "v": pa.array(rng.integers(-999, 999, n), pa.int64()),
            "f": pa.array(rng.random(n), pa.float64()),
        }), path, row_group_size=n // 4, compression="snappy",
            use_dictionary=False)
        return path

    def _plan(self, path):
        from spark_rapids_jni_tpu.engine import (Aggregate, Filter, Scan,
                                                 col, lit)
        return Aggregate(
            Filter(Scan(path, chunk_bytes=1 << 20),
                   (">", col("f"), lit(0.25))),
            ["k"], [("v", "sum"), ("v", "max"), (None, "count_all")],
            names=["s", "m", "n"])

    @staticmethod
    def _norm(t):
        cols = {n: np.asarray(c.data) for n, c in zip(t.names, t.columns)}
        order = np.argsort(cols["k"])
        return [(n, cols[n][order].tolist()) for n in sorted(cols)]

    def test_device_matches_host_bit_exact(self, tmp_path, monkeypatch):
        from spark_rapids_jni_tpu.engine import execute, new_stats
        path = self._warehouse(tmp_path)
        plan = self._plan(path)
        host = execute(plan, new_stats())
        monkeypatch.setenv("SRJT_DEVICE_DECODE", "1")
        cfgmod.refresh()
        try:
            st = new_stats()
            dev = execute(plan, st)
        finally:
            monkeypatch.delenv("SRJT_DEVICE_DECODE")
            cfgmod.refresh()
        assert self._norm(dev) == self._norm(host)
        assert st["chunks"] == 4 and st["fused_segments"] >= 1

    def test_explain_renders_device_decode(self, tmp_path,
                                           device_decode_env):
        from spark_rapids_jni_tpu.engine.explain import explain_analyze
        rep = explain_analyze(self._plan(self._warehouse(tmp_path)),
                              distribute=False)
        assert "decode=device" in rep.text
        assert "link_bytes=" in rep.text
        dd = next(d for d in rep.decisions
                  if d["kind"] == "scan:device_decode" and d.get("runtime"))
        assert dd["choice"] == "device"
        assert dd["device_chunks"] == 4 and dd["host_chunks"] == 0

    def test_mixed_schema_routes_strings_to_host(self, tmp_path,
                                                 device_decode_env):
        # a string column in the scanned schema vetoes the device plan for
        # the whole group — the ledger must say why, results stay right
        from spark_rapids_jni_tpu.engine import Scan
        from spark_rapids_jni_tpu.engine.explain import explain_analyze
        rng = np.random.default_rng(22)
        n = 2000
        path = str(tmp_path / "mixed.parquet")
        pq.write_table(pa.table({
            "k": pa.array(rng.integers(0, 9, n), pa.int64()),
            "s": pa.array([f"r{i % 13}" for i in range(n)]),
        }), path, row_group_size=n // 2, compression="snappy")
        rep = explain_analyze(Scan(path, chunk_bytes=1 << 20),
                              distribute=False)
        assert rep.result.num_rows == n
        got = sorted(np.asarray(
            rep.result.columns[rep.result.names.index("k")].data).tolist())
        assert got == sorted(pq.read_table(path)["k"].to_numpy().tolist())
        dd = [d for d in rep.decisions
              if d["kind"] == "scan:device_decode" and d.get("runtime")]
        if dd:  # veto may route before the ledger opens; if present, host
            assert dd[0]["choice"] == "host"

    def test_pages_partitioning_and_census(self, tmp_path,
                                           device_decode_env):
        from spark_rapids_jni_tpu.engine import optimize
        from spark_rapids_jni_tpu.engine.plan import (NO_PARTITIONING,
                                                      Scan as PScan,
                                                      partitioning,
                                                      topo_nodes)
        from spark_rapids_jni_tpu.engine.verify import decision_census
        plan = self._plan(self._warehouse(tmp_path))
        opt = optimize(plan, distribute=True)
        led = [d for d in getattr(opt, "_decisions", [])
               if d["kind"] == "scan:device_decode"]
        cen = [c for c in decision_census(opt, dist=True)
               if c["kind"] == "scan:device_decode"]
        assert led and cen and led[0]["path"] == cen[0]["path"]
        assert led[0]["choice"] == "page_routed"
        sn = next(n for n in topo_nodes(opt) if isinstance(n, PScan))
        assert partitioning(sn).kind == "pages"
        # aggregating over page-partitioned input needs a real exchange:
        # the planner must not pretend pages align with hash keys
        assert partitioning(opt).kind in ("hash",) or \
            partitioning(opt) is NO_PARTITIONING

    def test_decode_segment_lints_clean(self, tmp_path):
        from spark_rapids_jni_tpu.engine import lower, optimize
        from spark_rapids_jni_tpu.engine.verify import lint_decode_segment
        path = self._warehouse(tmp_path)
        opt = optimize(self._plan(path), distribute=False)
        st = lower(opt, fuse=True, fuse_exchange=False, ndev=1).stage_at(opt)
        assert st.kind == "stream-agg"
        seg = st.segment
        chunk, reason = pqio.plan_device_group(
            pqio.ParquetFile(path), 0, None, 1 << 30)
        assert chunk is not None, reason
        rep = lint_decode_segment(seg, chunk.geom)
        assert rep["ok"], rep["violations"]
        assert rep["decode"] and rep["primitives"] > 0
