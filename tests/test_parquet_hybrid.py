"""The RLE / bit-packed hybrid stream decoder (io/parquet.py) against a
per-value reference.

``_rle_bitpacked_hybrid`` decodes a stream as a whole: one walk over the run
headers, every bit-packed payload unpacked together, every RLE run expanded
by one ``np.repeat``.  The reference below reads the same bytes one value at
a time through a bit cursor, so the two share nothing but the format.  The
streams are built here, run by run, the way writers build them: pyarrow's
63-group cap, parquet-mr's longer runs with multi-byte headers, a last group
whose padding the writer dropped.
"""

import numpy as np
import pytest

from spark_rapids_jni_tpu.io.parquet import (_DecodeTally,
                                             _rle_bitpacked_hybrid,
                                             _unpack_groups)


# -- building streams ------------------------------------------------------------

def _varint(v: int) -> bytes:
    out = bytearray()
    while v >= 0x80:
        out.append((v & 0x7F) | 0x80)
        v >>= 7
    out.append(v)
    return bytes(out)


def rle_run(value: int, count: int, bit_width: int) -> bytes:
    return _varint(count << 1) + value.to_bytes((bit_width + 7) // 8,
                                                "little")


def packed_run(values, bit_width: int, truncate: bool = False) -> bytes:
    """A bit-packed run of ceil(len/8) groups, LSB first.  ``truncate``
    drops the bytes of the last group that hold only padding, as some
    writers do."""
    groups = -(-len(values) // 8)
    bits = 0
    for i, v in enumerate(values):
        bits |= int(v) << (i * bit_width)
    body = bits.to_bytes(groups * bit_width, "little")
    if truncate:
        body = body[:-(-len(values) * bit_width // 8)]
    return _varint((groups << 1) | 1) + body


# -- the per-value reference: a bit cursor ---------------------------------------

def reference(buf: bytes, bit_width: int, num_values: int) -> list:
    out, pos = [], 0
    byte_width = (bit_width + 7) // 8
    while len(out) < num_values and pos < len(buf):
        header = shift = 0
        while True:
            b = buf[pos]
            pos += 1
            header |= (b & 0x7F) << shift
            shift += 7
            if not b & 0x80:
                break
        if header & 1:
            cursor = pos * 8                    # a bit address in buf
            for _ in range((header >> 1) * 8):
                v = 0
                for k in range(bit_width):
                    byte, bit = divmod(cursor + k, 8)
                    if byte < len(buf):         # dropped padding reads 0
                        v |= ((buf[byte] >> bit) & 1) << k
                out.append(v)
                cursor += bit_width
            pos += (header >> 1) * bit_width
        else:
            v = int.from_bytes(buf[pos:pos + byte_width], "little")
            out.extend([v] * (header >> 1))
            pos += byte_width
    return out[:num_values]


def check(buf: bytes, bit_width: int, num_values: int, runs: int) -> None:
    want = reference(buf, bit_width, num_values)
    assert len(want) == num_values
    tally = _DecodeTally()
    for form in (buf, memoryview(buf)):         # the decoder is handed views
        got = _rle_bitpacked_hybrid(form, bit_width, num_values, tally)
        assert got.dtype == np.int32 and got.shape == (num_values,)
        assert got.view(np.uint32).tolist() == want
    assert tally.runs == 2 * runs


def _values(rng, n: int, bit_width: int) -> list:
    return rng.integers(0, 1 << bit_width, n, dtype=np.uint64).tolist()


# -- every bit width ---------------------------------------------------------------

@pytest.mark.parametrize("bit_width", range(1, 33))
def test_packed_stream_of_every_bit_width(bit_width):
    """pyarrow's shape: bit-packed runs of at most 63 groups, one after
    the other; the last run's last group holds padding."""
    rng = np.random.default_rng(bit_width)
    vals = _values(rng, 63 * 8 * 2 + 13, bit_width)
    vals[0], vals[1] = (1 << bit_width) - 1, 0      # all ones, all zeros
    buf = b"".join(packed_run(vals[a:a + 504], bit_width)
                   for a in range(0, len(vals), 504))
    check(buf, bit_width, len(vals), runs=3)


@pytest.mark.parametrize("bit_width", range(1, 33))
def test_mixed_stream_of_every_bit_width(bit_width):
    """RLE and bit-packed runs interleaved, the stream starting and
    ending with either kind."""
    rng = np.random.default_rng(100 + bit_width)
    top = (1 << bit_width) - 1
    a, b, c = (_values(rng, n, bit_width) for n in (40, 8, 24))
    buf = (rle_run(top, 9, bit_width) + packed_run(a, bit_width)
           + rle_run(0, 1, bit_width) + rle_run(top // 2, 300, bit_width)
           + packed_run(b, bit_width) + packed_run(c, bit_width)
           + rle_run(1, 77, bit_width))
    check(buf, bit_width, 9 + 40 + 1 + 300 + 8 + 24 + 77, runs=7)


# -- the shapes of a stream ---------------------------------------------------------

@pytest.mark.parametrize("count,num_values", [(1, 1), (20000, 20000),
                                              (20000, 7)])
def test_one_rle_run(count, num_values):
    """The definition levels of a null-free page: one run, which may
    promise more values than the page asks for."""
    check(rle_run(1, count, 1), 1, num_values, runs=1)


@pytest.mark.parametrize("bit_width", [1, 4, 15, 17, 24])
def test_one_packed_run(bit_width):
    rng = np.random.default_rng(bit_width)
    vals = _values(rng, 504, bit_width)
    check(packed_run(vals, bit_width), bit_width, 504, runs=1)


@pytest.mark.parametrize("num_values", [1, 5, 9, 63, 505, 1001])
def test_num_values_not_a_multiple_of_8(num_values):
    """The last group is padded to 8 values; the caller's count cuts it."""
    rng = np.random.default_rng(num_values)
    vals = _values(rng, num_values, 11)
    buf = b"".join(packed_run(vals[a:a + 504], 11)
                   for a in range(0, num_values, 504))
    check(buf, 11, num_values, runs=-(-num_values // 504))


@pytest.mark.parametrize("bit_width,n", [(3, 5), (7, 9), (17, 3), (32, 1),
                                         (2, 3)])
def test_truncated_last_group(bit_width, n):
    """A writer may drop the padding bytes of the stream's last group."""
    rng = np.random.default_rng(n)
    head = _values(rng, 16, bit_width)
    tail = _values(rng, n, bit_width)
    buf = packed_run(head, bit_width) + packed_run(tail, bit_width,
                                                   truncate=True)
    assert len(buf) < len(packed_run(head, bit_width)
                          + packed_run(tail, bit_width))
    check(buf, bit_width, 16 + n, runs=2)


@pytest.mark.parametrize("groups", [64, 200, 2500, 16384])
def test_packed_run_longer_than_63_groups(groups):
    """parquet-mr writes runs of any length: the header takes two or
    three bytes."""
    rng = np.random.default_rng(groups)
    vals = _values(rng, groups * 8, 9)
    buf = packed_run(vals, 9)
    assert buf[0] & 0x80                        # a multi-byte header
    check(buf, 9, groups * 8, runs=1)


@pytest.mark.parametrize("count", [64, 128, 16384, 3_000_000])
def test_rle_run_with_a_multi_byte_header(count):
    buf = rle_run(5, 3, 3) + rle_run(6, count, 3) + rle_run(2, 4, 3)
    assert buf[2] & 0x80
    check(buf, 3, count + 7, runs=3)


def test_multi_byte_headers_between_packed_runs():
    rng = np.random.default_rng(7)
    a, b = _values(rng, 8 * 100, 5), _values(rng, 8 * 3, 5)
    buf = (packed_run(a, 5) + rle_run(31, 1000, 5) + packed_run(b, 5)
           + rle_run(0, 129, 5))
    check(buf, 5, 800 + 1000 + 24 + 129, runs=4)


@pytest.mark.parametrize("num_values", [0, 1, 4096])
def test_bit_width_zero(num_values):
    """A dictionary of one entry, or levels of a required column: no
    bytes are read at all."""
    got = _rle_bitpacked_hybrid(b"", 0, num_values)
    assert got.dtype == np.int32 and got.tolist() == [0] * num_values
    got = _rle_bitpacked_hybrid(b"\xff\xff", 0, num_values)
    assert got.tolist() == [0] * num_values


def test_no_values_asked_for():
    """An all-null page has a value stream with nothing in it."""
    assert _rle_bitpacked_hybrid(b"", 7, 0).tolist() == []
    assert _rle_bitpacked_hybrid(packed_run([1] * 8, 7), 7, 0).tolist() == []


def test_bytes_after_the_last_run_are_not_read():
    buf = rle_run(3, 10, 2) + packed_run([1, 2, 3, 0, 1, 2, 3, 0], 2)
    check(buf + b"\xff" * 9, 2, 18, runs=2)
    check(buf + b"\xff" * 9, 2, 10, runs=1)


@pytest.mark.parametrize("buf,bit_width,num_values", [
    (rle_run(1, 10, 1), 1, 11),
    (packed_run([1] * 16, 4), 4, 17),
    (rle_run(1, 10, 1) + packed_run([1] * 8, 1), 1, 19),
])
def test_stream_shorter_than_asked_raises(buf, bit_width, num_values):
    with pytest.raises(ValueError, match="truncated"):
        _rle_bitpacked_hybrid(buf, bit_width, num_values)


def test_values_above_31_bits_wrap_into_int32():
    """32-bit values come back as their int32 bit pattern, from both
    kinds of run (the parent's decoder wrapped the bit-packed ones)."""
    buf = rle_run(0xFFFFFFFF, 3, 32) + packed_run([0x80000000] * 8, 32)
    got = _rle_bitpacked_hybrid(buf, 32, 11)
    assert got.tolist() == [-1] * 3 + [-2**31] * 8


# -- the unpack alone ----------------------------------------------------------------

@pytest.mark.parametrize("bit_width", range(1, 33))
def test_unpack_groups(bit_width):
    rng = np.random.default_rng(bit_width)
    vals = _values(rng, 8 * 37, bit_width)
    payload = np.frombuffer(packed_run(vals, bit_width)[1:], np.uint8)
    got = _unpack_groups(payload, bit_width)
    assert got.dtype == np.int32
    assert got.view(np.uint32).tolist() == vals
