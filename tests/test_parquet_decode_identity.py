"""The host decoder's `_HostColumn`s, field by field, against pyarrow's own
reader — on the benchmark's files and on the page shapes the stream decoder
and the null-free path have to get right.

The decoder (io/parquet.py `_ChunkDecoder`) unpacks level and dictionary-
index streams a page at a time and hands a null-free chunk's value stream
out as the dense array.  Neither may change what comes out: values, null
positions, dtypes, and WHICH fields are None (an all-true validity stays an
array: the staged plan and the segments' fingerprints are keyed on it).
What is held here, for every file:

- ``values`` equal pyarrow's at the valid slots and are 0 at the null ones;
  ``chars`` / ``offsets`` rebuild pyarrow's strings;
- ``validity`` is None exactly for a required (non-nullable) column — the
  parent's rule — and equals pyarrow's null positions otherwise;
- the decoder's counters: ``io.parquet.decode.dense_chunks`` grows by one
  per null-free optional fixed-width chunk and by none for a chunk with a
  null; ``pages`` and ``runs`` count what the file holds.
"""

import importlib.util
import os
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_jni_tpu.io.parquet import (ParquetChunkedReader,
                                             ParquetFile, _DecodeTally,
                                             _walk_pages)
from spark_rapids_jni_tpu.utils import config as cfg
from spark_rapids_jni_tpu.utils import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNTERS = ("io.parquet.decode.pages", "io.parquet.decode.runs",
            "io.parquet.decode.dense_chunks")


def _counters() -> dict:
    return {c.rsplit(".", 1)[1]: tracing.counter_value(c) for c in COUNTERS}


def _grew(before: dict) -> dict:
    return {k: v - before[k] for k, v in _counters().items()}


# -- the comparison ---------------------------------------------------------------

def assert_host_column_equals_arrow(host, arr: pa.ChunkedArray, field):
    arr = arr.combine_chunks()
    n = len(arr)
    want_valid = np.asarray(arr.is_valid())
    assert host.num_rows == n, field.name
    if field.nullable:
        assert host.validity is not None, field.name
        assert host.validity.dtype == np.bool_ and host.validity.shape == (n,)
        assert np.array_equal(host.validity, want_valid), field.name
    else:
        assert host.validity is None, field.name
    assert host.child is None and host.children is None
    assert host.loffsets is None
    if pa.types.is_string(field.type):
        assert host.values is None
        assert host.chars.dtype == np.uint8
        assert host.offsets.dtype == np.int32 and len(host.offsets) == n + 1
        raw = host.chars.tobytes()
        got = [raw[a:b].decode() if ok else None for a, b, ok in
               zip(host.offsets[:-1], host.offsets[1:], want_valid)]
        assert got == arr.to_pylist(), field.name
        # a null row contributes no characters
        lens = np.diff(host.offsets)
        assert not lens[~want_valid].any(), field.name
        return
    assert host.chars is None and host.offsets is None
    assert host.values.shape == (n,), field.name
    assert host.values.flags.writeable, field.name     # never a file view
    if pa.types.is_boolean(field.type):
        want = arr.fill_null(False).to_numpy(zero_copy_only=False) \
            .astype(host.values.dtype)
    else:
        want = arr.fill_null(0).to_numpy(zero_copy_only=False)
        assert host.values.dtype == want.dtype, field.name
    # bit for bit: floats too (the reference's sums depend on it), and a
    # null slot holds 0
    assert host.values.tobytes() == want.tobytes(), field.name


def assert_file_equals_arrow(path, columns=None) -> _DecodeTally:
    """Every row group, decoded one at a time as the producer thread
    does; returns what the decode walked."""
    pf = ParquetFile(path)
    meta = pq.ParquetFile(path)
    names = columns or pf.names
    tally = _DecodeTally()
    for gi in range(pf.num_row_groups):
        hosts = pf._decode_group(gi, columns, tally)
        want = meta.read_row_group(gi, columns=names)
        assert [h.schema.name for h in hosts] == names
        for host, name in zip(hosts, names):
            assert_host_column_equals_arrow(
                host, want.column(name), meta.schema_arrow.field(name))
    return tally


def nullable_fixed_chunks(path) -> int:
    """Column chunks of the file that are optional and fixed-width: those
    the null-free path may take."""
    meta = pq.ParquetFile(path)
    per_group = sum(f.nullable and not pa.types.is_string(f.type)
                    for f in meta.schema_arrow)
    return per_group * meta.num_row_groups


def page_null_shares(path, column: str, mask: np.ndarray) -> list:
    """The share of null rows in each data page (v1) of row group 0."""
    pf = ParquetFile(path)
    chunk = pf.row_groups[0].chunks[pf.names.index(column)]
    pages, _, _ = _walk_pages(pf._buf, chunk)
    ends = np.cumsum([nv for _, _, _, nv in pages])
    assert ends[-1] == len(mask)
    return [float(mask[a:b].mean()) for a, b in zip(ends - [p[3] for p in
                                                            pages], ends)]


def write(tmp_path, table: pa.Table, **kwargs) -> str:
    path = str(tmp_path / "t.parquet")
    pq.write_table(table, path, **kwargs)
    return path


def with_nulls(rng, values: np.ndarray, share: float, kind) -> pa.Array:
    return pa.array(values, kind, mask=rng.random(len(values)) < share)


# -- the benchmark's files ----------------------------------------------------------

def _bench():
    spec = importlib.util.spec_from_file_location(
        "bench_run_for_identity", os.path.join(ROOT, "benchmarks", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


BENCH_TABLES = [("q5lite_sf1_year", "store_sales"),
                ("q5lite_sf1_year", "date_dim"),
                ("q5lite_sf1_year", "store"),
                ("q55lite_sf1_nov1999", "store_sales"),
                ("q55lite_sf1_nov1999", "date_dim"),
                ("q55lite_sf1_nov1999", "item")]


@pytest.fixture(scope="module")
def warehouses(tmp_path_factory):
    """Both queries' warehouses at ``rehearsal_rows``, written exactly as
    ``benchmarks/run.py::write_tables`` writes them."""
    bench = _bench()
    out = {}
    for name in sorted({w for w, _ in BENCH_TABLES}):
        cell = bench.Cell(name)
        frames = cell.query.tables(20261003, cell.rows(rehearsal=True))
        root = tmp_path_factory.mktemp(name)
        out[name] = (cell, bench.write_tables(frames, cell.config, str(root)))
    return out


@pytest.mark.parametrize("workload,table", BENCH_TABLES)
def test_benchmark_file_decodes_to_arrows_values(warehouses, workload, table):
    cell, paths = warehouses[workload]
    before = _counters()
    tally = assert_file_equals_arrow(paths[table])
    groups = cell.config["tables"][table]["row_groups"]
    columns = len(cell.config["tables"][table]["columns"])
    assert tally.chunks == columns * groups
    # pandas frames have no nulls and arrow writes every column optional:
    # every chunk takes the null-free path
    assert tally.dense_chunks == columns * groups
    assert tally.pages >= tally.chunks and tally.runs >= tally.pages
    assert _grew(before) == {"pages": tally.pages, "runs": tally.runs,
                             "dense_chunks": tally.dense_chunks}


@pytest.mark.parametrize("workload", ["q5lite_sf1_year",
                                      "q55lite_sf1_nov1999"])
def test_streamed_fact_scan_counts_three_dense_chunks_a_group(warehouses,
                                                              workload):
    """The invariant the cells' traces are read by: the streamed scan of
    the fact table takes the null-free path for its 3 columns in every
    row group it decodes."""
    cell, paths = warehouses[workload]
    before = _counters()
    with ParquetChunkedReader(paths["store_sales"], pass_read_limit=8 << 20,
                              prefetch=1) as reader:
        rows = sum(n for _, n in reader.iter_staged())
        groups = reader.groups_read
    assert rows == cell.rows(rehearsal=True)["store_sales"]
    assert groups == cell.config["tables"]["store_sales"]["row_groups"]
    grew = _grew(before)
    assert grew["dense_chunks"] == 3 * groups
    assert grew["pages"] >= 3 * groups and grew["runs"] >= grew["pages"]


def test_decode_span_carries_what_the_decode_walked(warehouses, monkeypatch):
    """Under ``SRJT_TRACE=1`` every ``io.scan.decode`` span is given, when
    the decode ends, what engaged: ``runs``, ``pages`` and
    ``dense=<n>/<chunks>`` of that row group — through the open span's
    handle (``set_metadata``), not on a child span of its own."""
    import jax
    log = []

    class Annotation:
        def __init__(self, name, **stats):
            self.rec = {"name": name, "stats": stats, "late": {}}

        def set_metadata(self, **stats):
            assert "t0" in self.rec and "t1" not in self.rec    # while open
            self.rec["late"].update(stats)

        def __enter__(self):
            self.rec["t0"] = time.perf_counter()

        def __exit__(self, *exc):
            self.rec["t1"] = time.perf_counter()
            log.append(self.rec)

    _, paths = warehouses["q55lite_sf1_nov1999"]
    monkeypatch.setenv("SRJT_TRACE", "1")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    cfg.refresh()
    try:
        before = _counters()
        with ParquetChunkedReader(paths["store_sales"],
                                  pass_read_limit=8 << 20) as reader:
            slices = list(reader._host_slices())
    finally:
        monkeypatch.undo()
        cfg.refresh()
    grew = _grew(before)
    decodes = [r for r in log if r["name"] == "io.scan.decode"]
    assert not [r for r in log if r["name"] == "io.scan.decode.walked"]
    assert len(slices) == len(decodes) == 12
    for gi, r in enumerate(decodes):
        assert r["stats"]["group"] == gi and r["stats"]["bytes"] > 0
        assert sorted(r["late"]) == ["dense", "pages", "runs"]
        assert r["late"]["dense"] == "3/3"
    assert sum(r["late"]["runs"] for r in decodes) == grew["runs"]
    assert sum(r["late"]["pages"] for r in decodes) == grew["pages"]


# -- page shapes ---------------------------------------------------------------------

N = 30_000
SMALL_PAGES = dict(data_page_size=1024, write_batch_size=256)


def mixed_table(rng, share: float) -> pa.Table:
    return pa.table({
        "i64": with_nulls(rng, rng.integers(-2**62, 2**62, N), share,
                          pa.int64()),
        "i32": with_nulls(rng, rng.integers(0, 1000, N).astype(np.int32),
                          share, pa.int32()),
        "f64": with_nulls(rng, rng.integers(0, 50, N) / 4096, share,
                          pa.float64()),
        "f32": with_nulls(rng, rng.standard_normal(N).astype(np.float32),
                          share, pa.float32()),
        "b": with_nulls(rng, rng.random(N) < 0.3, share, pa.bool_()),
        "s": with_nulls(rng, np.array([f"brand#{v}" for v in
                                       rng.integers(0, 40, N)], object),
                        share, pa.string()),
    })


@pytest.mark.parametrize("codec", ["none", "snappy", "gzip", "zstd"])
@pytest.mark.parametrize("share", [0.0, 0.05])
def test_codecs_with_and_without_nulls(tmp_path, codec, share):
    rng = np.random.default_rng(5)
    path = write(tmp_path, mixed_table(rng, share), compression=codec,
                 row_group_size=N // 3, **SMALL_PAGES)
    tally = assert_file_equals_arrow(path)
    assert tally.chunks == 6 * 3
    assert tally.dense_chunks == (0 if share else nullable_fixed_chunks(path))


@pytest.mark.parametrize("use_dictionary", [True, False])
def test_required_columns_keep_validity_none(tmp_path, use_dictionary):
    rng = np.random.default_rng(6)
    schema = pa.schema([pa.field("k", pa.int64(), nullable=False),
                        pa.field("v", pa.float64(), nullable=False),
                        pa.field("s", pa.string(), nullable=False),
                        pa.field("o", pa.int64(), nullable=True)])
    table = pa.table({"k": rng.integers(0, 9, N), "v": rng.random(N),
                      "s": [f"s{v}" for v in rng.integers(0, 5, N)],
                      "o": rng.integers(0, 9, N)}, schema=schema)
    path = write(tmp_path, table, use_dictionary=use_dictionary,
                 **SMALL_PAGES)
    tally = assert_file_equals_arrow(path)
    # a required chunk has no levels to observe: only "o" is counted
    assert tally.dense_chunks == 1


@pytest.mark.parametrize("version", ["1.0", "2.0"])
@pytest.mark.parametrize("layout", ["all_null_pages", "null_free_page_between",
                                    "one_null_in_the_last_page",
                                    "all_null_chunk"])
def test_null_layouts_across_pages(tmp_path, layout, version):
    """Pages of ~512 rows; the null-free test is made over the chunk, so
    one null anywhere keeps the whole chunk on the masked path."""
    rng = np.random.default_rng(8)
    mask = np.zeros(N, bool)
    if layout == "all_null_pages":
        # nulls take no room in a page, so only the writer's cap of 20,000
        # rows a page ends one: 25,000 nulls in a row hold a whole page
        mask[2000:27_000] = True
        mask[28_000:] = rng.random(N - 28_000) < 0.5
    elif layout == "null_free_page_between":
        mask[:8192] = rng.random(8192) < 0.3
        mask[16_384:] = rng.random(N - 16_384) < 0.3
    elif layout == "one_null_in_the_last_page":
        mask[N - 2] = True
    else:
        mask[:] = True
    table = pa.table({
        "k": pa.array(rng.integers(0, 300, N), pa.int64(), mask=mask),
        "v": pa.array(rng.integers(0, 1 << 40, N) / 4096, pa.float64(),
                      mask=mask),
        "s": pa.array(np.array([f"s{v}" for v in rng.integers(0, 9, N)],
                               object), pa.string(), mask=mask),
        "full": pa.array(rng.integers(0, 300, N), pa.int64()),
    })
    path = write(tmp_path, table, data_page_version=version, **SMALL_PAGES)
    if version == "1.0":                        # the layout is what it says
        nulls = page_null_shares(path, "k", mask)
        if layout == "all_null_pages":
            assert 1.0 in nulls and 0.0 in nulls
        if layout == "all_null_chunk":
            assert set(nulls) == {1.0}
        if layout == "null_free_page_between":
            inner = nulls.index(0.0)
            assert 0 < min(nulls[:inner]) and 0 < max(nulls[inner + 1:]) < 1
        if layout == "one_null_in_the_last_page":
            assert not any(nulls[:-1]) and 0 < nulls[-1] < 1
    tally = assert_file_equals_arrow(path)
    assert tally.dense_chunks == 1              # "full", and it alone


@pytest.mark.parametrize("version", ["1.0", "2.0"])
def test_dictionary_falls_back_to_plain_mid_chunk(tmp_path, version):
    """The benchmark's price column: the dictionary page fills up and the
    writer goes on in PLAIN, inside one column chunk."""
    rng = np.random.default_rng(9)
    table = pa.table({"price": pa.array(rng.integers(2, 1 << 40, N) / 4096),
                      "nulls": with_nulls(rng, rng.integers(2, 1 << 40, N)
                                          / 4096, 0.05, pa.float64())})
    path = write(tmp_path, table, dictionary_pagesize_limit=32 << 10,
                 data_page_version=version, **SMALL_PAGES)
    encodings = pq.ParquetFile(path).metadata.row_group(0).column(0).encodings
    assert "PLAIN" in encodings and "RLE_DICTIONARY" in encodings
    tally = assert_file_equals_arrow(path)
    assert tally.dense_chunks == 1


@pytest.mark.parametrize("bits", [1, 4, 15, 17, 24])
def test_dictionary_index_width(tmp_path, bits):
    """A dictionary of just over 2**(bits-1) entries: the chunk's last
    pages carry ``bits``-bit indices (the earlier ones narrower, as the
    dictionary grows)."""
    rng = np.random.default_rng(bits)
    distinct = (1 << (bits - 1)) + 1
    n = max(N, distinct + 4096)
    keys = np.concatenate([rng.permutation(distinct),
                           rng.integers(0, distinct, n - distinct)])
    table = pa.table({"k": pa.array(keys.astype(np.int32) * 3 - 7)})
    path = write(tmp_path, table, dictionary_pagesize_limit=1 << 30,
                 compression="snappy", row_group_size=n)
    pf = ParquetFile(path)
    assert pf.num_row_groups == 1
    _, dict_page, encoding = _walk_pages(pf._buf, pf.row_groups[0].chunks[0])
    assert encoding == "dict" and dict_page[3] == distinct  # no PLAIN pages
    tally = assert_file_equals_arrow(path)
    assert tally.dense_chunks == 1


def test_counters_grow_by_what_the_tally_holds(tmp_path):
    rng = np.random.default_rng(11)
    path = write(tmp_path, mixed_table(rng, 0.0), row_group_size=N // 2)
    before = _counters()
    tally = assert_file_equals_arrow(path, columns=["i64", "f64", "s"])
    assert (tally.chunks, tally.dense_chunks) == (6, 4)
    assert _grew(before) == {"pages": tally.pages, "runs": tally.runs,
                             "dense_chunks": 4}
