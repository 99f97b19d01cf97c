"""RowConversion tests.

Ports the reference's round-trip property (RowConversionTest.java:29-59:
8-column table incl. decimals, trailing nulls, to-rows -> from-rows equals the
original) and adds what the reference lacks (SURVEY.md §4 gap): golden
wire-format bytes, layout unit tests, batching tests, randomized all-dtype
round-trips — all hardware-free on the CPU harness.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from spark_rapids_jni_tpu import dtypes as dt
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.ops.row_conversion import (
    fixed_width_layout, convert_to_rows, convert_from_rows,
)


def roundtrip(table, **kw):
    blobs = convert_to_rows(table, **kw)
    parts = [convert_from_rows(b, table.dtypes()) for b in blobs]
    return blobs, parts


def assert_tables_equal(a: Table, b: Table):
    """Value+null equality, the analog of AssertUtils.assertTablesAreEqual."""
    assert a.num_columns == b.num_columns
    for ca, cb in zip(a.columns, b.columns):
        assert ca.dtype == cb.dtype
        va, vb = ca.validity_numpy(), cb.validity_numpy()
        np.testing.assert_array_equal(va, vb)
        da, db = ca.to_numpy(), cb.to_numpy()
        np.testing.assert_array_equal(da[va], db[vb])


# -- layout planner ---------------------------------------------------------

def test_layout_natural_alignment():
    # int8 then int64 must pad to 8; validity byte after data; row pads to 8
    lay = fixed_width_layout([dt.INT8, dt.INT64, dt.INT16])
    assert lay.offsets == (0, 8, 16)
    assert lay.validity_offset == 18
    assert lay.row_size == 24  # 18 data+2 used -> 19 bytes -> pad 24

def test_layout_packed_descending():
    # the Java doc's advice (RowConversion.java:74-89): 64->32->16->8 packs tight
    lay = fixed_width_layout([dt.INT64, dt.INT32, dt.INT16, dt.INT8])
    assert lay.offsets == (0, 8, 12, 14)
    assert lay.validity_offset == 15
    assert lay.row_size == 16

def test_layout_rejects_strings():
    with pytest.raises(TypeError):
        fixed_width_layout([dt.STRING])


# -- golden wire format -----------------------------------------------------

def test_wire_format_golden():
    """Hand-computed bytes: layout must match the reference wire format."""
    t = Table([
        Column.from_numpy(np.array([0x11223344, -1], np.int32)),
        Column.fixed(dt.INT8, np.array([0x7F, 2], np.int8),
                     validity=np.array([True, False])),
        Column.from_numpy(np.array([0x0102030405060708, 0], np.int64)),
    ])
    lay = fixed_width_layout(t.dtypes())
    assert lay.offsets == (0, 4, 8) and lay.validity_offset == 16
    assert lay.row_size == 24
    [blob] = convert_to_rows(t)
    raw = np.asarray(blob.children[0].data).view(np.uint8)
    row0 = raw[:24]
    np.testing.assert_array_equal(row0[0:4], [0x44, 0x33, 0x22, 0x11])  # LE int32
    assert row0[4] == 0x7F
    np.testing.assert_array_equal(
        row0[8:16], [0x08, 0x07, 0x06, 0x05, 0x04, 0x03, 0x02, 0x01])
    assert row0[16] == 0b111  # all three columns valid
    row1 = raw[24:48]
    assert row1[16] == 0b101  # middle column null


def test_offsets_are_row_size_stride():
    t = Table([Column.from_numpy(np.arange(5, dtype=np.int64))])
    [blob] = convert_to_rows(t)
    lay = fixed_width_layout(t.dtypes())
    np.testing.assert_array_equal(
        np.asarray(blob.offsets), np.arange(6, dtype=np.int32) * lay.row_size)


def numpy_pack(cols, layout):
    """Host oracle for the fixed-width wire (independent of the device
    kernel): each column's bytes at its offset, one validity bit per
    column after them."""
    n = len(cols[0][0])
    out = np.zeros((n, layout.row_size), np.uint8)
    for (data, valid), off in zip(cols, layout.offsets):
        if data.dtype == np.bool_:
            data = data.astype(np.uint8)
        out[:, off:off + data.dtype.itemsize] = \
            data.view(np.uint8).reshape(n, data.dtype.itemsize)
    for i, (data, valid) in enumerate(cols):
        bit = np.uint8(1 << (i % 8))
        out[:, layout.validity_offset + i // 8] |= \
            bit if valid is None else np.where(valid, bit, np.uint8(0))
    return out


def test_fixed_wire_bytes_match_oracle():
    """Eight fixed-width types with nulls in two columns, on random rows:
    the device's row bytes equal the host oracle's, byte for byte."""
    rng = np.random.default_rng(0)
    n = 1_000
    cols = [
        (rng.integers(-2**62, 2**62, n).astype(np.int64), None),
        (rng.standard_normal(n), rng.random(n) > 0.1),
        (rng.integers(-2**31, 2**31 - 1, n).astype(np.int32), None),
        (rng.standard_normal(n).astype(np.float32), None),
        (rng.integers(-2**15, 2**15 - 1, n).astype(np.int16),
         rng.random(n) > 0.5),
        (rng.integers(-128, 128, n).astype(np.int8), None),
        (rng.random(n) > 0.5, None),
        (rng.integers(-10**15, 10**15, n).astype(np.int64), None),
    ]
    schema = [dt.INT64, dt.FLOAT64, dt.INT32, dt.FLOAT32, dt.INT16, dt.INT8,
              dt.BOOL8, dt.decimal64(-4)]
    table = Table([Column.from_numpy(d, validity=v, dtype=t)
                   for (d, v), t in zip(cols, schema)])
    [blob] = convert_to_rows(table)
    got = np.asarray(blob.children[0].data).view(np.uint8)
    want = numpy_pack(cols, fixed_width_layout(schema)).reshape(-1)
    np.testing.assert_array_equal(got, want)


# -- round trips ------------------------------------------------------------

def test_reference_roundtrip():
    """Port of RowConversionTest.fixedWidthRowsRoundTrip (reference
    src/test/java/..../RowConversionTest.java:29-59)."""
    t = Table([
        Column.from_pylist([5, 1, 0, 2, 7, None], dt.INT64),
        Column.from_pylist([5.0, 9.5, 0.9, 7.23, 2.8, None], dt.FLOAT64),
        Column.from_pylist([5, 1, 0, 2, 7, None], dt.INT32),
        Column.from_pylist([true := True, False, False, True, False, None]),
        Column.from_pylist([5.0, 9.5, 0.9, 7.23, 2.8, None], dt.FLOAT32),
        Column.from_pylist([1, 3, 5, 7, 9, None], dt.INT8),
        Column.fixed(dt.decimal32(-3), np.array([175, 459, 375, 987, 401, 0], np.int32),
                     validity=np.array([1, 1, 1, 1, 1, 0], bool)),
        Column.fixed(dt.decimal64(-8), np.array([123456789, 286, 22, 9, 56, 0], np.int64),
                     validity=np.array([1, 1, 1, 1, 1, 0], bool)),
    ])
    blobs, parts = roundtrip(t)
    assert len(blobs) == 1               # no batch overflow (test asserts 1 batch)
    assert blobs[0].size == t.num_rows   # row count preserved
    assert_tables_equal(t, parts[0])
    # decimal scale survives the schema round trip
    assert parts[0].columns[6].dtype == dt.decimal32(-3)
    assert parts[0].columns[7].dtype == dt.decimal64(-8)


@pytest.mark.parametrize("d", [
    dt.INT8, dt.INT16, dt.INT32, dt.INT64, dt.UINT8, dt.UINT16, dt.UINT32,
    dt.UINT64, dt.FLOAT32, dt.FLOAT64, dt.BOOL8, dt.TIMESTAMP_DAYS,
    dt.TIMESTAMP_MICROSECONDS, dt.decimal32(-2), dt.decimal64(3),
])
def test_single_dtype_roundtrip(d):
    rng = np.random.default_rng(hash(d) % 2**32)
    n = 77
    store = d.storage
    if store.kind == 'f':
        vals = rng.standard_normal(n).astype(store)
    else:
        info = np.iinfo(store)
        vals = rng.integers(info.min, info.max, size=n,
                            dtype=store if store != np.dtype(np.uint64) else np.uint64)
    if d == dt.BOOL8:
        vals = (vals & 1).astype(np.uint8)
    validity = rng.random(n) > 0.3
    t = Table([Column.fixed(d, vals, validity=validity)])
    _, parts = roundtrip(t)
    assert_tables_equal(t, parts[0])


def test_all_valid_column_has_set_bits():
    t = Table([Column.from_numpy(np.arange(3, dtype=np.int32))])
    _, parts = roundtrip(t)
    np.testing.assert_array_equal(parts[0].columns[0].validity_numpy(),
                                  [True] * 3)


def test_batching_splits_and_aligns():
    n = 100
    t = Table([Column.from_numpy(np.arange(n, dtype=np.int64))])
    lay = fixed_width_layout(t.dtypes())
    # force ~3 batches: cap at 40 rows worth of bytes -> 32-row aligned batches
    blobs, parts = roundtrip(t, max_batch_bytes=40 * lay.row_size)
    assert [b.size for b in blobs] == [32, 32, 32, 4]
    got = np.concatenate([p.columns[0].to_numpy() for p in parts])
    np.testing.assert_array_equal(got, np.arange(n))


def test_from_rows_rejects_bad_width():
    t = Table([Column.from_numpy(np.arange(4, dtype=np.int64))])
    [blob] = convert_to_rows(t)
    with pytest.raises(ValueError):
        convert_from_rows(blob, [dt.INT8])  # wrong schema -> wrong row width


def test_from_rows_rejects_non_list():
    c = Column.from_numpy(np.arange(4, dtype=np.int64))
    with pytest.raises(TypeError):
        convert_from_rows(c, [dt.INT64])


def test_batch_align_cannot_exceed_cap():
    """ADVICE r1: forcing 32-row alignment must not silently exceed the cap."""
    t = Table([Column.from_numpy(np.arange(64, dtype=np.int64))])
    lay = fixed_width_layout(t.dtypes())
    with pytest.raises(ValueError):
        convert_to_rows(t, max_batch_bytes=16 * lay.row_size)  # < 32 rows/batch


def test_from_padded_bytes_rejects_int32_offset_overflow():
    from spark_rapids_jni_tpu.ops.strings_common import from_padded_bytes
    mat = np.zeros((3, 4), np.uint8)
    lengths = np.array([2**30, 2**30, 2**30], np.int64)  # sums past 2^31
    with pytest.raises(OverflowError):
        from_padded_bytes(mat, lengths)


def test_jit_to_rows_traceable():
    """The kernel path stays inside one jit (no host sync per column)."""
    lay = fixed_width_layout([dt.INT64, dt.FLOAT64])
    from spark_rapids_jni_tpu.ops.row_conversion import _to_rows_bytes
    fcol = Column.from_numpy(np.arange(8, dtype=np.float64))  # bits storage
    datas = (jnp.arange(8, dtype=jnp.int64), fcol.data)
    out = _to_rows_bytes(lay, datas, (None, None))
    assert out.shape == (8 * lay.row_size,)


def test_blob_child_list_invariant():
    """offsets[-1] == child.size (bytes) even with the packed-u32 backing."""
    import numpy as np
    from spark_rapids_jni_tpu.columnar import PackedByteColumn
    t = Table([Column.from_numpy(np.arange(100, dtype=np.int64))])
    blob = convert_to_rows(t)[0]
    child = blob.children[0]
    assert isinstance(child, PackedByteColumn)
    assert int(np.asarray(blob.offsets)[-1]) == child.size
    assert child.bytes_numpy().size == child.size


def test_decimal128_round_trip():
    """DECIMAL128 (two int64 limbs, 16-byte aligned) through the wire."""
    import decimal
    vals = [12345678901234567890123456789,
            -98765432109876543210987654321,
            (1 << 126) - 1, -(1 << 126), 0, None]
    d128 = dt.decimal128(-6)
    t = Table([Column.from_pylist(vals, dtype=d128),
               Column.from_numpy(np.arange(6, dtype=np.int64))])
    layout = fixed_width_layout(t.dtypes())
    assert layout.offsets[0] == 0 and layout.row_size % 8 == 0
    blobs = convert_to_rows(t)
    back = convert_from_rows(blobs[0], t.dtypes())
    got = back.columns[0].to_pylist()
    ctx = decimal.Context(prec=50)
    for v, g in zip(vals, got):
        if v is None:
            assert g is None
        else:
            assert g == decimal.Decimal(v).scaleb(-6, ctx), v
    assert back.columns[1].to_pylist() == list(range(6))


# -- variable-width (STRING) rows -------------------------------------------

def numpy_pack_var(cols_np, schema):
    """Host oracle for the variable-width contract (independent of the
    device kernel): fixed region with 8-byte (offset, length) string slots,
    validity tail, align8 variable region, per-field align8 padding."""
    from spark_rapids_jni_tpu.ops.row_conversion import variable_width_layout
    vlay = variable_width_layout(schema)
    base = vlay.base
    n = len(cols_np[0][0])
    rows = []
    for i in range(n):
        fixed = bytearray(base.row_size)
        var = bytearray()
        for ci, ((data, valid), dtp, off) in enumerate(
                zip(cols_np, schema, base.offsets)):
            if dtp.is_string:
                s = data[i] if (valid is None or valid[i]) else b""
                if isinstance(s, str):
                    s = s.encode("utf-8")
                foff = base.row_size + len(var)
                fixed[off:off + 4] = np.uint32(foff).tobytes()
                fixed[off + 4:off + 8] = np.uint32(len(s)).tobytes()
                var += s + b"\0" * (-len(s) % 8)
            else:
                b = np.asarray(data[i]).tobytes()
                fixed[off:off + len(b)] = b
        for ci, (data, valid) in enumerate(cols_np):
            if valid is None or valid[i]:
                fixed[base.validity_offset + ci // 8] |= 1 << (ci % 8)
        rows.append(bytes(fixed) + bytes(var))
    return rows


def make_var_table(n, seed=0):
    rng = np.random.default_rng(seed)
    words = ["", "a", "béta", "cherry-pie", "δelta-δelta", "x" * 37,
             "\U0001F600smile", "tail"]
    s1 = [words[k] for k in rng.integers(0, len(words), n)]
    v1 = rng.random(n) > 0.2
    s2 = [words[k] for k in rng.integers(0, len(words), n)]
    i64 = rng.integers(-2**62, 2**62, n).astype(np.int64)
    i32 = rng.integers(-2**31, 2**31 - 1, n).astype(np.int32)
    vi = rng.random(n) > 0.5
    schema = [dt.INT64, dt.STRING, dt.INT32, dt.STRING]
    table = Table([
        Column.from_numpy(i64),
        Column.from_pylist([s if ok else None for s, ok in zip(s1, v1)],
                           dtype=dt.STRING),
        Column.from_numpy(i32, validity=vi),
        Column.from_pylist(list(s2), dtype=dt.STRING),
    ])
    cols_np = [(i64, None), (s1, v1), (i32, vi), (s2, None)]
    return table, cols_np, schema


def test_var_layout_slots():
    from spark_rapids_jni_tpu.ops.row_conversion import variable_width_layout
    vlay = variable_width_layout([dt.INT32, dt.STRING, dt.INT8])
    # int32 at 0, string slot 8-aligned at 8, int8 at 16, validity 17,
    # var region starts align8(18) = 24
    assert vlay.base.offsets == (0, 8, 16)
    assert vlay.base.validity_offset == 17
    assert vlay.base.row_size == 24
    assert vlay.string_idx == (1,)


def test_var_wire_bytes_match_oracle():
    table, cols_np, schema = make_var_table(257, seed=3)
    blobs = convert_to_rows(table)
    assert len(blobs) == 1
    rows = numpy_pack_var(cols_np, schema)
    got = blobs[0]
    offs = np.asarray(got.offsets)
    child = np.asarray(got.children[0].data).view(np.uint8)
    exp_offs = np.cumsum([0] + [len(r) for r in rows])
    np.testing.assert_array_equal(offs, exp_offs)
    np.testing.assert_array_equal(child, np.frombuffer(
        b"".join(rows), np.uint8))


def test_var_roundtrip():
    table, _, schema = make_var_table(500, seed=4)
    blobs, parts = roundtrip(table)
    assert sum(p.num_rows for p in parts) == table.num_rows
    got = parts[0]
    for ci in range(table.num_columns):
        a, b = table.columns[ci], got.columns[ci]
        np.testing.assert_array_equal(a.validity_numpy(), b.validity_numpy())
        if a.dtype.is_string:
            la, lb = a.to_pylist(), b.to_pylist()
            va = a.validity_numpy()
            assert [x for x, ok in zip(la, va) if ok] == \
                [x for x, ok in zip(lb, va) if ok]
        else:
            va = a.validity_numpy()
            np.testing.assert_array_equal(a.to_numpy()[va], b.to_numpy()[va])


def test_var_all_null_and_empty_strings():
    table = Table([
        Column.from_pylist(["", None, "", None], dtype=dt.STRING),
        Column.from_numpy(np.arange(4, dtype=np.int64)),
    ])
    blobs, parts = roundtrip(table)
    got = parts[0]
    np.testing.assert_array_equal(got.columns[0].validity_numpy(),
                                  [True, False, True, False])
    assert got.columns[0].to_pylist()[0] == ""
    np.testing.assert_array_equal(got.columns[1].to_numpy(),
                                  np.arange(4))


def test_var_batching_by_bytes():
    table, cols_np, schema = make_var_table(600, seed=5)
    blobs = convert_to_rows(table, max_batch_bytes=8192)
    assert len(blobs) > 1
    rows = numpy_pack_var(cols_np, schema)
    rejoined = b"".join(
        np.asarray(b.children[0].data).view(np.uint8).tobytes()
        for b in blobs)
    assert rejoined == b"".join(rows)
    for b in blobs[:-1]:
        assert (np.asarray(b.offsets)[-1]) <= 8192
    parts = [convert_from_rows(b, schema) for b in blobs]
    assert sum(p.num_rows for p in parts) == 600


def test_var_all_string_schema():
    """A table whose columns are ALL strings (no fixed-width buffer to
    derive the row count from) must still convert (reviewer regression)."""
    t = Table([Column.from_pylist(["abc", "", "longer-string", None]),
               Column.from_pylist(["x", "yy", None, "zzzz"])])
    blobs = convert_to_rows(t)
    back = convert_from_rows(blobs[0], t.dtypes())
    assert back.columns[0].to_pylist() == ["abc", "", "longer-string", None]
    assert back.columns[1].to_pylist() == ["x", "yy", None, "zzzz"]


def test_var_middle_batches_keep_32_alignment():
    """The HARD alignment contract on the variable-width path: whenever at
    least one whole 32-row group fits max_batch_bytes, the middle-batch cut
    is aligned down to a 32-row boundary (convert_to_rows docstring)."""
    table, cols_np, schema = make_var_table(600, seed=5)
    blobs = convert_to_rows(table, max_batch_bytes=8192)
    assert len(blobs) > 2
    for b in blobs[:-1]:
        assert b.size % 32 == 0, "middle batch not 32-row aligned"
        assert int(np.asarray(b.offsets)[-1]) <= 8192
    parts = [convert_from_rows(b, schema) for b in blobs]
    assert sum(p.num_rows for p in parts) == 600


def test_var_oversized_group_is_the_only_unaligned_exemption():
    """The one legal unaligned middle cut: a single 32-row group whose bytes
    exceed max_batch_bytes (here every row is ~1KB, so any 32 consecutive
    rows blow a 4KB budget).  Batches go out unaligned, nothing is lost."""
    n, cap = 40, 4096
    strs = ["x" * 1000 for _ in range(n)]
    table = Table([
        Column.from_pylist(strs, dtype=dt.STRING),
        Column.from_numpy(np.arange(n, dtype=np.int64)),
    ])
    blobs = convert_to_rows(table, max_batch_bytes=cap)
    assert len(blobs) > 1
    sizes = [b.size for b in blobs]
    assert any(s % 32 for s in sizes[:-1])  # unaligned middle cuts happened
    # the exemption's precondition really holds: rows are so wide that no
    # aligned group could have fit the budget
    per_row = int(np.asarray(blobs[0].offsets)[1])
    assert 32 * per_row > cap
    parts = [convert_from_rows(b, table.dtypes()) for b in blobs]
    assert sum(p.num_rows for p in parts) == n
    got = sum((p.columns[0].to_pylist() for p in parts), [])
    assert got == strs
