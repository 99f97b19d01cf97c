"""Device-side Parquet page decode (`SRJT_DEVICE_DECODE`).

The host scan path (io/parquet.py `_ChunkDecoder`) decompresses and decodes
pages in pure Python/numpy, then ships *uncompressed* bytes over the link;
staging (io/staging.py) hides the transfer but not the decode.  This module
moves the inner loops into jitted kernels so the link carries the
*compressed* page bytes and decode runs on-device, overlapped with compute
by the existing double-buffered prefetch pipeline:

- **snappy** raw-block decompression as a two-pass token scan: pass 1 is a
  (vmapped) sequential walk over the token *headers* only — a few dozen
  iterations per page, each O(1) — scattering per-token (dest, literal-src,
  copy-offset) marks; pass 2 is fully parallel over output bytes: a
  ``cummax`` recovers each byte's owning token and a pointer-doubling chase
  resolves back-reference chains (literal bytes are fixed points).  Pages
  whose token scan found no back-references (``has_copies=False``, the
  common case for high-entropy and dict-encoded data) skip the chase
  entirely — the gather is one ``take_along_axis``.
- **RLE/bit-packed hybrid** decode (def levels, dictionary indices) with the
  same shape: sequential run-header walk, then parallel per-slot extraction
  from a ``cummax`` over run marks.
- **PLAIN** fixed-width decode as a byte gather + word assembly (the
  two-stage u8 -> u32 -> int64 rebuild staging already proves on TPU, where
  only <=32-bit bitcasts exist), and **dictionary gather** through the
  decoded dictionary page.

Word assembly is XLA shifts and ors (``assemble_u32``) on every platform.

Wire format: each column chunk ships as padded ``uint8`` *page planes* —
``comp[P+1, CB]`` (row 0 = dictionary page or zeros, rows 1..P = data
pages) plus the tiny ``clen/ulen/nv[P+1]`` per-page byte/value counts.
That is ALL that crosses the link: the global row -> (page, slot) map is
derived in-kernel from a ``cumsum`` over ``nv`` (shipping it as i32
tables would cost 8 B/row/col — more than compressed int64 data).  All
dimensions are power-of-two buckets recorded in the static
:class:`ChunkGeom`, so one jitted program serves every chunk of the same
(schema, geometry) class.  Everything here is pure traced code: zero host
syncs, zero callbacks — `verify.py` lints the jaxpr.

Unsupported shapes (nesting, v2 pages, non-RLE levels, codecs beyond
snappy/uncompressed, strings) never reach this module: io/parquet.py's
`plan_device_group` routes them to the host decoder with a ledgered
fallback reason.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import Column, Table
from ..dtypes import DType, TypeId

#: floor for the per-page byte/value buckets (lane-width aligned)
MIN_BUCKET = 128


def bucket(n: int, floor: int = MIN_BUCKET) -> int:
    """Next power of two >= max(n, floor) — the geometry-class quantizer."""
    b = int(floor)
    while b < n:
        b *= 2
    return b


# -- static geometry (the jit cache key) ------------------------------------

@dataclass(frozen=True)
class ColumnGeom:
    """Static decode geometry for one column chunk.

    ``encoding`` is the *data-page* value encoding class: ``"plain"`` or
    ``"dict"`` (PLAIN_DICTIONARY / RLE_DICTIONARY).  ``has_copies`` is the
    host token-scan's verdict on the snappy streams: False means every page
    is literal-only and the device decompressor skips the pointer chase.
    Buckets: ``cb``/``ub`` compressed/uncompressed page bytes, ``vb`` values
    per page, ``db`` dictionary entries, ``tb`` snappy tokens per page (the
    pass-1 walk's compact carry size); ``npages`` is the (pow2) data-page
    count.
    """

    name: str
    dtype: DType
    physical: int
    codec: int
    encoding: str
    max_def: int
    has_copies: bool
    npages: int
    cb: int
    ub: int
    vb: int
    db: int
    tb: int = 64


@dataclass(frozen=True)
class ChunkGeom:
    """Static geometry for a whole row-group chunk: per-column geometry
    plus the shared row-table bucket ``rb``."""

    columns: tuple
    rb: int

    def column(self, name: str) -> ColumnGeom:
        for c in self.columns:
            if c.name == name:
                return c
        raise KeyError(name)


_I32 = jnp.int32
_U32 = jnp.uint32


def _i32(x):
    return x.astype(_I32)


# -- snappy: two-pass token-scan decompression ------------------------------

def _snappy_pass1(comp, clen, ulen, ub: int, tb: int):
    """Sequential token-header walk for ONE page (vmapped by the caller).

    Walks the token *headers* only, carrying a COMPACT per-token table of
    static bucket ``tb`` (the host token scan's count), never the
    output-sized planes: under vmap every loop iteration pays a masked
    select over the carried state, so the carry must stay tokens-sized —
    with byte-sized carries the walk's memory traffic dwarfs the actual
    decompression.  The table then scatters ONCE into the per-output-byte
    planes the parallel pass consumes: ``mark[ub]`` (token start position,
    -1 elsewhere), ``lsrc[ub]`` (literal source byte offset in ``comp``),
    ``coff[ub]`` (back-reference offset; 0 marks a literal).
    """
    cb = comp.shape[0]

    def rd(pos):
        return _i32(comp[jnp.clip(pos, 0, cb - 1)])

    # uvarint preamble (uncompressed length): skip 1-5 bytes
    b = [rd(jnp.int32(k)) for k in range(5)]
    c = [bk >> 7 for bk in b]
    hdr = 1 + c[0] + c[0] * c[1] + c[0] * c[1] * c[2] \
        + c[0] * c[1] * c[2] * c[3]

    def cond(st):
        s, d, k = st[0], st[1], st[2]
        # k < tb is a safety bound only: the host scan sized tb to the
        # real token count, so a correct stream never trips it
        return (s < clen) & (d < ulen) & (k < tb)

    def body(st):
        s, d, k, dk, ls, co = st
        tag = rd(s)
        kind = tag & 3
        lcode = tag >> 2
        # literal: 1-4 extra LE length bytes when lcode >= 60
        nlb = jnp.clip(lcode - 59, 0, 4)
        e = [rd(s + 1 + k) for k in range(4)]
        extra = e[0] | e[1] << 8 | e[2] << 16 | e[3] << 24
        emask = jnp.where(nlb >= 4, jnp.int32(-1),
                          (jnp.int32(1) << (8 * jnp.minimum(nlb, 3))) - 1)
        lit_len = jnp.where(lcode < 60, lcode + 1, (extra & emask) + 1)
        lit_start = s + 1 + nlb
        # copies
        n1, n2, n3, n4 = e  # bytes after the tag
        len1 = ((tag >> 2) & 7) + 4
        off1 = ((tag & 0xE0) << 3) | n1
        off2 = n1 | n2 << 8
        off3 = n1 | n2 << 8 | n3 << 16 | n4 << 24
        cp_len = jnp.where(kind == 1, len1, lcode + 1)
        cp_off = jnp.where(kind == 1, off1,
                           jnp.where(kind == 2, off2, off3))
        cp_off = jnp.maximum(cp_off, 1)  # 0 is the literal marker
        cp_adv = jnp.where(kind == 1, 2, jnp.where(kind == 2, 3, 5))

        is_lit = kind == 0
        tok_len = jnp.where(is_lit, lit_len, cp_len)
        dk = dk.at[k].set(d)
        ls = ls.at[k].set(jnp.where(is_lit, lit_start, 0))
        co = co.at[k].set(jnp.where(is_lit, 0, cp_off))
        s = s + jnp.where(is_lit, 1 + nlb + lit_len, cp_adv)
        return s, d + tok_len, k + 1, dk, ls, co

    # unused slots keep destination ub: out of bounds -> scatter-dropped
    init = (hdr, jnp.int32(0), jnp.int32(0),
            jnp.full((tb,), ub, _I32), jnp.zeros((tb,), _I32),
            jnp.zeros((tb,), _I32))
    _, _, _, dk, ls, co = jax.lax.while_loop(cond, body, init)
    mark = jnp.full((ub,), -1, _I32).at[dk].set(dk, mode="drop")
    lsrc = jnp.zeros((ub,), _I32).at[dk].set(ls, mode="drop")
    coff = jnp.zeros((ub,), _I32).at[dk].set(co, mode="drop")
    return mark, lsrc, coff


def _snappy_decompress(comp, clen, ulen, ub: int, has_copies: bool,
                       tb: int):
    """``comp[R, CB]`` snappy pages -> ``u8[R, UB]`` uncompressed planes."""
    r, cb = comp.shape
    mark, lsrc, coff = jax.vmap(_snappy_pass1,
                                in_axes=(0, 0, 0, None, None))(
        comp, clen, ulen, ub, tb)
    iota = jnp.arange(ub, dtype=_I32)[None, :]
    tid = jax.lax.cummax(mark, axis=1)
    tidc = jnp.clip(tid, 0, ub - 1)
    lit = jnp.take_along_axis(lsrc, tidc, axis=1)
    off = jnp.take_along_axis(coff, tidc, axis=1)
    if has_copies:
        # pointer-doubling chase: literal positions are fixed points, copy
        # positions point strictly backwards, so log2(ub) rounds resolve
        # every chain (incl. overlapping RLE-style copies)
        ptr = jnp.where(off == 0, iota, jnp.clip(iota - off, 0, ub - 1))
        ptr = jnp.broadcast_to(ptr, (r, ub))
        for _ in range(int(ub).bit_length()):
            ptr = jnp.take_along_axis(ptr, ptr, axis=1)
        src = jnp.take_along_axis(lit, ptr, axis=1) + \
            (ptr - jnp.take_along_axis(tidc, ptr, axis=1))
    else:
        src = lit + (iota - tidc)
    out = jnp.take_along_axis(comp, jnp.clip(src, 0, cb - 1), axis=1)
    return jnp.where(iota < ulen[:, None], out, jnp.uint8(0))


def _decompress(comp, clen, ulen, g: ColumnGeom):
    """Codec dispatch (static): ``u8[R, CB]`` pages -> ``u8[R, UB]``."""
    from ..io.parquet import CODEC_SNAPPY, CODEC_UNCOMPRESSED
    if g.codec == CODEC_SNAPPY:
        return _snappy_decompress(comp, clen, ulen, g.ub, g.has_copies,
                                  g.tb)
    if g.codec == CODEC_UNCOMPRESSED:
        if g.cb >= g.ub:
            return comp[:, :g.ub]
        return jnp.pad(comp, ((0, 0), (0, g.ub - g.cb)))
    raise ValueError(f"device decode: unsupported codec {g.codec}")


# -- RLE / bit-packed hybrid ------------------------------------------------

def _hybrid_pass1(data, start, end, bw, n, vb: int):
    """Sequential run-header walk for ONE hybrid stream (vmapped).

    Returns scatter planes over value slots: ``mark[vb]`` (run start slot),
    ``pk[vb]`` (bit-packed run?), ``bb[vb]`` (bit offset of the run's packed
    payload), ``rv[vb]`` (the RLE run value).
    """
    ub = data.shape[0]

    def rd(pos):
        return _i32(data[jnp.clip(pos, 0, ub - 1)])

    def cond(st):
        s, v = st[0], st[1]
        return (s < end) & (v < n)

    def body(st):
        s, v, mark, pk, bb, rv = st
        b = [rd(s + k) for k in range(5)]
        c = [bk >> 7 for bk in b]
        seg = [bk & 0x7F for bk in b]
        h = seg[0] \
            + c[0] * (seg[1] << 7) \
            + c[0] * c[1] * (seg[2] << 14) \
            + c[0] * c[1] * c[2] * (seg[3] << 21) \
            + c[0] * c[1] * c[2] * c[3] * (seg[4] << 28)
        hlen = 1 + c[0] + c[0] * c[1] + c[0] * c[1] * c[2] \
            + c[0] * c[1] * c[2] * c[3]
        dp = s + hlen
        packed = (h & 1) == 1
        groups = h >> 1
        bwb = (bw + 7) >> 3  # RLE value byte width
        d = [rd(dp + k) for k in range(4)]
        raw = (d[0] | d[1] << 8 | d[2] << 16 | d[3] << 24).astype(_U32)
        vmask = jnp.where(bwb >= 4, _U32(0xFFFFFFFF),
                          (_U32(1) << _U32(8 * jnp.minimum(bwb, 3))) - 1)
        cnt = jnp.where(packed, groups * 8, groups)
        cnt = jnp.maximum(cnt, 1)  # corrupt zero-count header: still advance
        adv = jnp.where(packed, groups * bw, bwb)
        vc = jnp.clip(v, 0, vb - 1)
        mark = mark.at[vc].set(v)
        pk = pk.at[vc].set(packed)
        bb = bb.at[vc].set(dp * 8)
        rv = rv.at[vc].set(raw & vmask)
        return dp + adv, v + cnt, mark, pk, bb, rv

    init = (start, jnp.int32(0),
            jnp.full((vb,), -1, _I32), jnp.zeros((vb,), jnp.bool_),
            jnp.zeros((vb,), _I32), jnp.zeros((vb,), _U32))
    _, _, mark, pk, bb, rv = jax.lax.while_loop(cond, body, init)
    return mark, pk, bb, rv


def _rle_hybrid(data, start, end, bw, n, vb: int):
    """RLE/bit-packed hybrid streams -> ``u32[R, vb]`` values.

    ``data[R, UB]`` uncompressed page planes; ``start``/``end`` byte ranges
    and ``bw`` bit widths are per-row (dynamic — for dictionary indices the
    width byte itself lives in the page payload); ``n`` values per row.
    """
    r, ub = data.shape
    mark, pk, bb, rv = jax.vmap(_hybrid_pass1,
                                in_axes=(0, 0, 0, 0, 0, None))(
        data, start, end, bw, n, vb)
    rid = jax.lax.cummax(mark, axis=1)
    ridc = jnp.clip(rid, 0, vb - 1)
    pk2 = jnp.take_along_axis(pk, ridc, axis=1)
    bb2 = jnp.take_along_axis(bb, ridc, axis=1)
    rv2 = jnp.take_along_axis(rv, ridc, axis=1)
    iota = jnp.arange(vb, dtype=_I32)[None, :]
    bit = bb2 + (iota - ridc) * bw[:, None]
    byte0 = bit >> 3
    sh = (bit & 7).astype(_U32)
    by = [jnp.take_along_axis(
        data, jnp.clip(byte0 + k, 0, ub - 1), axis=1).astype(_U32)
        for k in range(5)]
    lo = by[0] | by[1] << 8 | by[2] << 16 | by[3] << 24
    # straddle byte: (hi << (32 - sh)) is undefined at sh == 0, so compute
    # the shift mod 32 and select it away
    hi = jnp.where(sh == 0, _U32(0), by[4] << ((_U32(32) - sh) & _U32(31)))
    bwm = jnp.where(bw >= 32, _U32(0xFFFFFFFF),
                    (_U32(1) << jnp.minimum(bw, 31).astype(_U32)) - 1)
    val = ((lo >> sh) | hi) & bwm[:, None]
    val = jnp.where(pk2, val, rv2)
    return jnp.where(iota < n[:, None], val, _U32(0))


# -- PLAIN fixed-width gather + word assembly -------------------------------

def assemble_u32(b):
    """``u8[..., 4]`` little-endian byte groups -> ``u32[...]``."""
    x = b.astype(_U32)
    return x[..., 0] | x[..., 1] << 8 | x[..., 2] << 16 | x[..., 3] << 24


def _plain_gather(unc, voff, nn, dtype: DType):
    """PLAIN-encoded values: byte gather at per-slot offsets + assembly.

    ``unc[R, UB]`` page planes, ``voff[R]`` value-section starts, ``nn[R,V]``
    per-slot value ordinals (-1 on null slots — clipped, caller masks).
    Returns ``[R, V]`` in the dtype's device storage.
    """
    r, ub = unc.shape
    nnc = jnp.clip(nn, 0, None)
    if dtype.id == TypeId.BOOL8:
        byte = jnp.take_along_axis(
            unc, jnp.clip(voff[:, None] + (nnc >> 3), 0, ub - 1), axis=1)
        return ((byte.astype(_U32) >> (nnc & 7).astype(_U32))
                & _U32(1)).astype(jnp.uint8)
    size = np.dtype(dtype.storage).itemsize
    base = voff[:, None] + nnc * size
    offs = base[:, :, None] + jnp.arange(size, dtype=_I32)
    flat = jnp.clip(offs.reshape(r, -1), 0, ub - 1)
    b = jnp.take_along_axis(unc, flat, axis=1).reshape(r, -1, size)
    if size == 4:
        w = assemble_u32(b)
        if dtype.id == TypeId.FLOAT32:
            return jax.lax.bitcast_convert_type(w, jnp.float32)
        return jax.lax.bitcast_convert_type(w, jnp.dtype(dtype.storage))
    # size == 8: rebuild from u32 pairs (staging's TPU-proven idiom —
    # only <= 32-bit bitcasts exist there).  FLOAT64 device storage IS the
    # int64 bit pattern (dtypes.device_storage), so this is the final form.
    lo = assemble_u32(b[..., :4])
    hi = assemble_u32(b[..., 4:])
    pairs = jnp.stack([lo, hi], axis=-1)
    return jax.lax.bitcast_convert_type(pairs, jnp.int64)


# -- column decode ----------------------------------------------------------

def _le32(unc, at: int):
    """u32 little-endian read at static byte offset ``at`` of each row."""
    return (_i32(unc[:, at]) | _i32(unc[:, at + 1]) << 8
            | _i32(unc[:, at + 2]) << 16 | _i32(unc[:, at + 3]) << 24)


def _decode_column(p: dict, g: ColumnGeom, rb: int):
    """One column chunk's planes -> (data[rb], validity[rb] | None)."""
    if g.encoding == "plain":
        # PLAIN never reads the dict row -- skip decompressing plane 0
        unc = None
        dunc = _decompress(p["comp"][1:], p["clen"][1:], p["ulen"][1:], g)
    else:
        unc = _decompress(p["comp"], p["clen"], p["ulen"], g)  # [P+1, UB]
        dunc = unc[1:]
    ulen_d = p["ulen"][1:]
    nv_d = p["nv"][1:]
    npages, vb = g.npages, g.vb
    iota_v = jnp.arange(vb, dtype=_I32)[None, :]

    if g.max_def > 0:
        # v1 page layout: [u32 def-len][def RLE hybrid][values] — the
        # length prefix lives INSIDE the (de)compressed body, so the value
        # offset is dynamic per page
        dlen = _le32(dunc, 0)
        voff = 4 + dlen
        lv = _rle_hybrid(dunc, jnp.full((npages,), 4, _I32), voff,
                         jnp.ones((npages,), _I32), nv_d, vb)
        valid = (lv == _U32(g.max_def)) & (iota_v < nv_d[:, None])
        nn = jnp.cumsum(valid, axis=1, dtype=_I32) - 1
        nnon = nn[:, -1] + 1
    else:
        voff = jnp.zeros((npages,), _I32)
        valid = iota_v < nv_d[:, None]
        nn = jnp.broadcast_to(iota_v, (npages, vb))
        nnon = nv_d

    if g.encoding == "plain":
        dense = _plain_gather(dunc, voff, nn, g.dtype)
    else:  # dictionary: decode the dict page, then gather through indices
        dvals = _plain_gather(
            unc[:1], jnp.zeros((1,), _I32),
            jnp.arange(g.db, dtype=_I32)[None, :], g.dtype)[0]
        nd = p["nv"][0]
        dvals = jnp.where(jnp.arange(g.db, dtype=_I32) < nd, dvals,
                          jnp.zeros((), dvals.dtype))
        bw = _i32(jnp.take_along_axis(
            dunc, jnp.clip(voff, 0, g.ub - 1)[:, None], axis=1)[:, 0])
        idx = _rle_hybrid(dunc, voff + 1, ulen_d, bw, nnon, vb)
        slot = jnp.take_along_axis(idx, jnp.clip(nn, 0, vb - 1),
                                   axis=1).astype(_I32)
        dense = dvals[jnp.clip(slot, 0, g.db - 1)]

    zero = jnp.zeros((), dense.dtype)
    dense = jnp.where(valid, dense, zero)

    # global row -> (page, slot) map, derived on-device from the per-page
    # value counts: shipping it as i32 tables would cost 8 B/row/col —
    # more than the int64 data itself once compressed
    nvc = jnp.cumsum(nv_d, dtype=_I32)  # rows at/under each page
    start = nvc - nv_d                  # first global row of each page
    iota_r = jnp.arange(rb, dtype=_I32)
    rp = jnp.sum(iota_r[None, :] >= nvc[:, None], axis=0, dtype=_I32)
    inrow = iota_r < nvc[-1]  # rows past the chunk are bucket pad
    rpc = jnp.clip(rp, 0, npages - 1)
    ric = jnp.clip(iota_r - start[rpc], 0, vb - 1)
    data = jnp.where(inrow, dense[rpc, ric], zero)
    if g.max_def > 0:
        return data, valid[rpc, ric] & inrow
    return data, None


def decode_table(planes: dict, geom: ChunkGeom) -> Table:
    """Page planes -> bucket-padded device Table (pure traced code).

    Mirrors the staged host chunk contract (io/staging.py padded=True):
    rows are padded to the ``rb`` bucket with zeroed values and False
    validity; a column carries validity iff its schema has a def level.
    """
    cols, names = [], []
    for g in geom.columns:
        data, validity = _decode_column(planes[g.name], g, geom.rb)
        storage = jnp.dtype(g.dtype.device_storage)
        if data.dtype != storage:  # e.g. unsigned storage: same-width bits
            data = jax.lax.bitcast_convert_type(data, storage)
        cols.append(Column(g.dtype, data=data, validity=validity))
        names.append(g.name)
    return Table(cols, names)


def probe_table(geom: ChunkGeom) -> Table:
    """A 1-row host-materialized Table with the decode output's schema —
    the executor's segment-eligibility probe (stream_runtime_eligible
    inspects dtypes/validity, and one row dodges the empty-agg veto)."""
    cols, names = [], []
    for g in geom.columns:
        data = jnp.zeros((1,), jnp.dtype(g.dtype.device_storage))
        validity = jnp.ones((1,), jnp.bool_) if g.max_def > 0 else None
        cols.append(Column(g.dtype, data=data, validity=validity))
        names.append(g.name)
    return Table(cols, names)


def zero_planes(geom: ChunkGeom) -> dict:
    """All-zero planes matching ``geom`` — abstract inputs for jaxpr lint
    and shape probing (a zero page decodes to zero rows: the token walk's
    loop condition fails immediately)."""
    out = {}
    for g in geom.columns:
        out[g.name] = {
            "comp": jnp.zeros((g.npages + 1, g.cb), jnp.uint8),
            "clen": jnp.zeros((g.npages + 1,), _I32),
            "ulen": jnp.zeros((g.npages + 1,), _I32),
            "nv": jnp.zeros((g.npages + 1,), _I32),
        }
    return out
