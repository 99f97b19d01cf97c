"""RowConversion: columnar Table <-> packed row-major blobs (LIST<INT8>).

TPU-native re-design of the reference op (reference
src/main/cpp/src/row_conversion.cu, Java API RowConversion.java):

- Wire format is IDENTICAL to the reference so blobs interoperate with
  UnsafeRow-style CPU consumers: C-struct natural alignment per column in
  schema order, one validity bit per column in bytes appended at the row tail,
  row padded to a 64-bit multiple (reference row_conversion.cu:432-456
  ``compute_fixed_width_layout``; layout documented in RowConversion.java:50-99).
- Output is split into batches so no batch exceeds 2^31-1 bytes, with batch row
  counts a multiple of 32 (reference row_conversion.cu:476-511 keeps int32 list
  offsets valid and validity words batch-local).
- Fixed-width types only, like the reference at this snapshot
  (row_conversion.cu:515,573 CUDF_FAIL on non-fixed-width).

The kernel design is TPU-first rather than a translation of the CUDA kernels:
where the reference stages per-block shared-memory tiles and does warp-ballot
validity packing (row_conversion.cu:75-108,158-165,255-272), we express the
whole conversion as a dense uint32 *row-word matrix* ``u32[rows, row_size/4]``
built from per-column bitcasts/shifts — XLA fuses the whole thing into one
elementwise pass over HBM, and every operation is 32-bit (the VPU lane width;
64-bit float bitcasts do not exist on TPU — see utils/floatbits.py).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import Column, PackedByteColumn, Table
from ..dtypes import DType, TypeId, INT8, UINT8
from ..utils.tracing import traced

# Reference parity: per-batch byte ceiling from cudf's int32 list offsets
# (row_conversion.cu:384-386) and 32-row batch alignment (:477-479).
MAX_BATCH_BYTES = (1 << 31) - 1
BATCH_ROW_ALIGN = 32


@dataclass(frozen=True)
class RowLayout:
    """Host-side packed-row layout plan (one per schema).

    Mirrors the reference's ``compute_fixed_width_layout``
    (row_conversion.cu:432-456): natural alignment per column, validity bytes
    at the tail, 64-bit row padding.
    """

    schema: tuple[DType, ...]
    offsets: tuple[int, ...]  # byte offset of each column's value in the row
    validity_offset: int      # first validity byte
    row_size: int             # padded total bytes per row

    @property
    def num_validity_bytes(self) -> int:
        return (len(self.schema) + 7) // 8


def fixed_width_layout(schema: Sequence[DType]) -> RowLayout:
    schema = tuple(schema)
    for dt in schema:
        if not dt.is_fixed_width:
            # parity with CUDF_FAIL "only fixed-width types" (row_conversion.cu:515)
            raise TypeError(f"row conversion requires fixed-width types, got {dt!r}")
    off = 0
    offsets = []
    for dt in schema:
        size = dt.itemsize
        off = (off + size - 1) // size * size  # natural C alignment
        offsets.append(off)
        off += size
    validity_offset = off
    off += (len(schema) + 7) // 8
    row_size = (off + 7) // 8 * 8  # 64-bit row padding (row_conversion.cu:86)
    return RowLayout(schema, tuple(offsets), validity_offset, row_size)


# ---------------------------------------------------------------------------
# kernels (jitted per (layout, n) via trace caching)
# ---------------------------------------------------------------------------

def _col_to_u32_parts(dtype: DType, data: jnp.ndarray) -> list[tuple[int, jnp.ndarray]]:
    """Decompose one column into (byte_width, uint32-extended value) parts.

    8-byte types yield two parts (lo, hi); smaller types one part whose value
    occupies the low ``byte_width`` bytes of the uint32.
    """
    size = dtype.itemsize
    if size == 16:
        # DECIMAL128: int64[n, 2] limb pairs -> four LE words
        quad = jax.lax.bitcast_convert_type(data, jnp.uint32)  # (n, 2, 2)
        return [(4, quad[..., 0, 0]), (4, quad[..., 0, 1]),
                (4, quad[..., 1, 0]), (4, quad[..., 1, 1])]
    if size == 8:
        # FLOAT64 included: its device buffer already holds IEEE bit patterns
        # as int64 (dtypes.device_storage), so every 8-byte type is an integer
        # bitcast — exact on TPU, where 64-bit float bitcasts don't exist
        pair = jax.lax.bitcast_convert_type(data, jnp.uint32)  # (n, 2) LE
        return [(4, pair[..., 0]), (4, pair[..., 1])]
    if size == 4:
        return [(4, jax.lax.bitcast_convert_type(data, jnp.uint32))]
    if size == 2:
        u16 = jax.lax.bitcast_convert_type(data, jnp.uint16)
        return [(2, u16.astype(jnp.uint32))]
    u8 = jax.lax.bitcast_convert_type(data, jnp.uint8)
    return [(1, u8.astype(jnp.uint32))]


def _build_planes(layout: RowLayout, datas: Sequence[jnp.ndarray],
                  masks: Sequence[Optional[jnp.ndarray]],
                  extra_parts=None, n: Optional[int] = None
                  ) -> list[jnp.ndarray]:
    """One dense ``u32[n]`` *plane* per row word (word-major decomposition).

    Planes stay in the TPU's natural dense 1-D layout — the key to the fast
    wire path (see ``_to_rows_wire``): all per-column shifts/ors fuse into one
    elementwise pass, and no intermediate ever has a sub-128 minor dimension
    that XLA would pad to full lane width.

    ``extra_parts``: optional {column index: [(byte_width, u32 part), ...]}
    overriding the value decomposition for columns whose device buffer is
    not the wire value (the variable-width path injects (offset, length)
    slot words for STRING columns here).
    """
    nwords = layout.row_size // 4
    if n is None:
        # derive the row count from any present buffer — an all-string
        # schema has None at every datas position, so check extra_parts too
        for d in datas:
            if d is not None:
                n = d.shape[0]
                break
        else:
            for parts in (extra_parts or {}).values():
                if parts:
                    n = parts[0][1].shape[0]
                    break
            else:
                n = 0
    # word index -> list of uint32 contributions (pre-shifted into place)
    contribs: dict[int, list[jnp.ndarray]] = {}

    def place(byte_off: int, width: int, value_u32: jnp.ndarray):
        w, b = divmod(byte_off, 4)
        assert b + width <= 4, "parts never straddle words (natural alignment)"
        v = value_u32 if b == 0 else value_u32 << jnp.uint32(8 * b)
        contribs.setdefault(w, []).append(v)

    for ci, (dt, off, data) in enumerate(zip(layout.schema, layout.offsets,
                                             datas)):
        parts = (extra_parts[ci] if extra_parts and ci in extra_parts
                 else _col_to_u32_parts(dt, data))
        for i, (width, part) in enumerate(parts):
            place(off + 4 * i, width, part)

    # validity bytes: bit i%8 of byte i//8 set when column i's row is valid
    # (wire layout per RowConversion.java:90-97; reference packs these bits with
    # atomics/ballots — here each byte is a sum of shifted bool lanes)
    for byte_idx in range(layout.num_validity_bytes):
        byte = jnp.zeros((n,), jnp.uint32)
        for bit in range(8):
            i = byte_idx * 8 + bit
            if i >= len(layout.schema):
                break
            m = masks[i]
            lane = (jnp.ones((n,), jnp.uint32) if m is None
                    else m.astype(jnp.uint32))
            byte = byte | (lane << jnp.uint32(bit))
        place(layout.validity_offset + byte_idx, 1, byte)

    zero = jnp.zeros((n,), jnp.uint32)
    return [functools.reduce(jnp.bitwise_or, contribs[w])
            if w in contribs else zero for w in range(nwords)]


def _to_row_words(layout: RowLayout, datas: Sequence[jnp.ndarray],
                  masks: Sequence[Optional[jnp.ndarray]]) -> jnp.ndarray:
    """Pack columns into the row-word matrix ``u32[n, row_size // 4]``.

    The (n, nwords) matrix is the *shuffle* representation (row-granular
    gathers); for bulk wire output prefer ``_to_rows_wire`` which avoids this
    shape's lane padding entirely.
    """
    return jnp.stack(_build_planes(layout, datas, masks), axis=1)


# Row-group width of the wire formulation: 32 rows of nwords words become one
# (32*nwords)-lane output row, keeping every minor dimension >= 128 lanes for
# typical row sizes so nothing is lane-padded.  This is the TPU analog of the
# reference's staged shared-memory coalescing (row_conversion.cu:75-108,
# 278-300): instead of staging tiles in shared memory for int64-coalesced
# writes, group rows so XLA's natural (8,128) tiling IS the coalesced layout.
WIRE_GROUP = 32


@functools.lru_cache(maxsize=None)
def _wire_perm(nwords: int):
    """Lane permutation taking w-major concat order to row-major wire order.

    After concatenating the 32-row reshapes of each plane, lane w*32+i holds
    word w of group-row i; the wire wants lane i*nwords+w.
    """
    perm = np.empty(WIRE_GROUP * nwords, np.int32)
    for w in range(nwords):
        for i in range(WIRE_GROUP):
            perm[i * nwords + w] = w * WIRE_GROUP + i
    inv = np.empty_like(perm)
    inv[perm] = np.arange(perm.size, dtype=np.int32)
    return perm, inv


def _to_rows_wire(layout: RowLayout, datas, masks) -> jnp.ndarray:
    """Fast path: packed wire image as dense ``u32[n * row_size // 4]``.

    The bytes of this array (little-endian) are exactly the packed rows.  The
    pipeline is planes -> 32-row-group concat -> constant lane permutation;
    measured ~2x the naive (n, nwords) stack on TPU because no step touches a
    lane-padded layout (the (n, nwords) matrix pads nwords -> 128 lanes, a
    ~10x write amplification for typical row sizes).
    """
    nwords = layout.row_size // 4
    planes = _build_planes(layout, datas, masks)
    n = datas[0].shape[0] if datas else 0
    ngroups = -(-n // WIRE_GROUP) if n else 0
    padded = ngroups * WIRE_GROUP
    if padded != n:
        planes = [jnp.concatenate(
            [p, jnp.zeros((padded - n,), jnp.uint32)]) for p in planes]
    if ngroups == 0:
        return jnp.zeros((0,), jnp.uint32)
    perm, _ = _wire_perm(nwords)
    grouped = jnp.concatenate(
        [p.reshape(ngroups, WIRE_GROUP) for p in planes], axis=1)
    wire = grouped[:, jnp.asarray(perm)].reshape(-1)
    return wire if padded == n else wire[:n * nwords]


def _from_wire(layout: RowLayout, wire: jnp.ndarray, n: int):
    """Inverse of ``_to_rows_wire``: dense u32 wire image -> planes list."""
    nwords = layout.row_size // 4
    ngroups = -(-n // WIRE_GROUP) if n else 0
    padded = ngroups * WIRE_GROUP
    if padded != n:
        wire = jnp.concatenate(
            [wire, jnp.zeros((padded - n) * nwords, jnp.uint32)])
    if ngroups == 0:
        zero = jnp.zeros((0,), jnp.uint32)
        return [zero for _ in range(nwords)]
    _, inv = _wire_perm(nwords)
    grouped = wire.reshape(ngroups, WIRE_GROUP * nwords)[:, jnp.asarray(inv)]
    return [grouped[:, w * WIRE_GROUP:(w + 1) * WIRE_GROUP].reshape(-1)[:n]
            for w in range(nwords)]


def _from_planes(layout: RowLayout, planes: list):
    """Unpack per-word planes (``u32[n]`` each) into (datas, masks)."""
    datas, masks = [], []

    def word_at(byte_off: int) -> jnp.ndarray:
        return planes[byte_off // 4]

    def subword(byte_off: int, width: int) -> jnp.ndarray:
        w, b = divmod(byte_off, 4)
        v = planes[w]
        if b:
            v = v >> jnp.uint32(8 * b)
        if width < 4:
            v = v & jnp.uint32((1 << (8 * width)) - 1)
        return v

    for dt, off in zip(layout.schema, layout.offsets):
        size = dt.itemsize
        if size == 16:  # DECIMAL128 -> int64[n, 2] limb pairs
            quad = jnp.stack([jnp.stack([word_at(off), word_at(off + 4)], -1),
                              jnp.stack([word_at(off + 8), word_at(off + 12)],
                                        -1)], axis=-2)
            data = jax.lax.bitcast_convert_type(quad, jnp.int64)
        elif size == 8:
            pair = jnp.stack([word_at(off), word_at(off + 4)], axis=-1)
            data = jax.lax.bitcast_convert_type(pair, jnp.int64)
            if dt.id != TypeId.FLOAT64:  # FLOAT64 keeps its bit-pattern buffer
                data = data.astype(dt.jnp_dtype)
        elif size == 4:
            data = jax.lax.bitcast_convert_type(word_at(off), dt.jnp_dtype)
        elif size == 2:
            u16 = subword(off, 2).astype(jnp.uint16)
            data = jax.lax.bitcast_convert_type(u16, dt.jnp_dtype)
        else:
            u8 = subword(off, 1).astype(jnp.uint8)
            data = u8 if dt.jnp_dtype == jnp.uint8 else \
                jax.lax.bitcast_convert_type(u8, dt.jnp_dtype)
        datas.append(data)

    for i in range(len(layout.schema)):
        byte = subword(layout.validity_offset + i // 8, 1)
        masks.append(((byte >> jnp.uint32(i % 8)) & jnp.uint32(1)).astype(jnp.bool_))
    return datas, masks


def _from_row_words(layout: RowLayout, words: jnp.ndarray):
    """Unpack ``u32[n, nwords]`` (shuffle representation) into (datas, masks)."""
    return _from_planes(layout, [words[:, w]
                                 for w in range(layout.row_size // 4)])


@functools.partial(jax.jit, static_argnums=0)
def _to_rows_wire_jit(layout: RowLayout, datas, masks) -> jnp.ndarray:
    return _to_rows_wire(layout, datas, masks)


@functools.partial(jax.jit, static_argnums=0)
def _to_rows_bytes(layout: RowLayout, datas, masks) -> jnp.ndarray:
    """u8[n * row_size] packed rows for one batch (jitted per layout/shape)."""
    wire = _to_rows_wire(layout, datas, masks)
    return jax.lax.bitcast_convert_type(wire, jnp.uint8).reshape(-1)  # LE


@functools.partial(jax.jit, static_argnums=0)
def _from_rows_bytes(layout: RowLayout, data_u8: jnp.ndarray):
    n = data_u8.shape[0] // layout.row_size
    grouped = data_u8.reshape(-1, 4)
    wire = jax.lax.bitcast_convert_type(grouped, jnp.uint32)
    return _from_planes(layout, _from_wire(layout, wire, n))


@functools.partial(jax.jit, static_argnums=(0, 2))
def _from_rows_wire_jit(layout: RowLayout, wire_u32: jnp.ndarray, n: int):
    return _from_planes(layout, _from_wire(layout, wire_u32, n))


# ---------------------------------------------------------------------------
# variable-width (STRING) rows
# ---------------------------------------------------------------------------
#
# The reference snapshot punts on variable width (row_conversion.cu:515,573
# CUDF_FAIL "only fixed-width types"), but its build machinery exists to feed
# Spark's UnsafeRow consumers, so the variable-width contract here follows
# UnsafeRow conventions grafted onto the documented fixed-width layout
# (RowConversion.java:50-99):
#
#   | fixed region | validity bytes | pad to 8 | variable region | (8-aligned)
#
# - STRING columns occupy an 8-byte naturally-aligned slot in the fixed
#   region: u32 LE byte offset FROM ROW START to the field's bytes, then
#   u32 LE byte length.
# - validity bytes exactly as the fixed-width contract (bit i%8 of byte
#   i//8 per column i).
# - the variable region starts at align8(validity end); fields appear in
#   column order, each padded to an 8-byte multiple with zero bytes
#   (UnsafeRow's roundUpTo8 convention), so every row size is 8-aligned.
# - NULL strings write length 0 at the offset the field would occupy and
#   contribute no variable bytes.


@dataclass(frozen=True)
class VarRowLayout:
    """Layout plan for rows with STRING columns.

    ``base`` plans the fixed region (slots + validity + pad); its
    ``row_size`` is the variable region's start offset.
    """

    base: RowLayout
    string_idx: tuple[int, ...]


def variable_width_layout(schema: Sequence[DType]) -> VarRowLayout:
    schema = tuple(schema)
    off = 0
    offsets = []
    for dt in schema:
        size = 8 if dt.is_string else dt.itemsize
        if not (dt.is_string or dt.is_fixed_width):
            raise TypeError(
                f"row conversion supports fixed-width and STRING, got {dt!r}")
        off = (off + size - 1) // size * size
        offsets.append(off)
        off += size
    validity_offset = off
    off += (len(schema) + 7) // 8
    var_start = (off + 7) // 8 * 8
    base = RowLayout(schema, tuple(offsets), validity_offset, var_start)
    return VarRowLayout(base, tuple(i for i, dt in enumerate(schema)
                                    if dt.is_string))


# (An owner-fill merge formulation — two sorts + flat gathers, the pattern
# in ops/join.py:_expand_pairs — was measured ~3x slower than the single
# (slot, value) wire sort below and removed; see docs/PERF.md r5 notes.)


def _string_words(col: Column, width: int):
    """(u32[n * width//4] flat LE word matrix, int32[n] effective lengths).

    ``width`` must be an 8-byte multiple; nulls get length 0 (they write no
    variable bytes — see the contract above).
    """
    from .strings_common import to_padded_bytes
    mat, lengths = to_padded_bytes(col, width=width)
    if col.validity is not None:
        lengths = jnp.where(col.validity, lengths, 0)
        mat = jnp.where(col.validity[:, None], mat, jnp.uint8(0))
    words = jax.lax.bitcast_convert_type(
        mat.reshape(mat.shape[0], width // 4, 4), jnp.uint32)
    return words.reshape(-1), lengths


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _to_rows_wire_var(vlayout: VarRowLayout, swidths: tuple, total_words: int,
                      datas, masks, smat_words, slens, row_off4):
    """Variable-width wire image as dense ``u32[total_words]``.

    ``datas`` has None at string positions; ``smat_words``/``slens`` are the
    flat padded word matrices + effective lengths per string column (order
    of ``vlayout.string_idx``); ``row_off4`` the per-row word offsets.

    TPU formulation: every candidate output word lives in a dense
    (n, base_words + sum(swidths)/4) lane grid built ELEMENTWISE (fixed
    planes + per-column padded string words), each lane's destination wire
    slot is also elementwise, and ONE stable 2-operand (slot, value) sort
    delivers the wire image as its first ``total_words`` entries.  Ragged
    interleave is inherently data-dependent movement — on TPU that costs
    one sort; this shape does it with no gathers, no scatter, no unsort
    pass (compare _run_owner_fill, which needs two sorts plus flat
    gathers and measures ~3x slower here).
    """
    base = vlayout.base
    base_words = base.row_size // 4
    n = row_off4.shape[0]
    # per-field padded word counts and per-row exclusive cumsum across cols
    pw = [((l + 7) // 8 * 2).astype(jnp.int32) for l in slens]
    cumb = []
    acc = jnp.zeros((n,), jnp.int32)
    for w in pw:
        cumb.append(acc)
        acc = acc + w
    # slot words for each string column: byte offset from row start + length
    extra = {}
    for k, idx in enumerate(vlayout.string_idx):
        off_bytes = (base.row_size + 4 * cumb[k]).astype(jnp.uint32)
        extra[idx] = [(4, off_bytes), (4, slens[k].astype(jnp.uint32))]
    planes = _build_planes(base, datas, masks, extra_parts=extra, n=n)

    dead = jnp.int32(total_words)
    keys = [row_off4 + w for w in range(base_words)]
    vals = list(planes)
    var_base = row_off4 + base_words
    for k, (words, wbytes) in enumerate(zip(smat_words, swidths)):
        w4 = wbytes // 4
        mat = words.reshape(n, w4)
        col_base = var_base + cumb[k]
        for w in range(w4):
            live = w < pw[k]
            keys.append(jnp.where(live, col_base + w, dead))
            vals.append(mat[:, w])
    key = jnp.concatenate(keys)
    val = jnp.concatenate(vals)
    _, sval = jax.lax.sort((key, val), num_keys=1, is_stable=False)
    return sval[:total_words]


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _from_rows_var(vlayout: VarRowLayout, swidths: tuple, n: int,
                   wire_u32, row_off4):
    """Inverse: wire words + row offsets -> (fixed datas, masks, string
    (byte-matrix, length) pairs).  Pure flat gathers (row starts are known,
    so no owner-fill is needed on this side)."""
    base = vlayout.base
    base_words = base.row_size // 4
    W = wire_u32.shape[0]
    idx = row_off4[:, None] + jnp.arange(base_words, dtype=jnp.int32)[None, :]
    mat = jnp.take(wire_u32, jnp.clip(idx, 0, max(W - 1, 0)).reshape(-1))
    planes = [mat.reshape(n, base_words)[:, w] for w in range(base_words)]

    def subword(byte_off, width):
        w, b = divmod(byte_off, 4)
        v = planes[w]
        if b:
            v = v >> jnp.uint32(8 * b)
        if width < 4:
            v = v & jnp.uint32((1 << (8 * width)) - 1)
        return v

    wire_u8 = jax.lax.bitcast_convert_type(wire_u32, jnp.uint8).reshape(-1)
    datas = []
    strings = []
    sk = 0
    for ci, (dt, off) in enumerate(zip(base.schema, base.offsets)):
        if dt.is_string:
            foff = planes[off // 4]
            flen = planes[off // 4 + 1].astype(jnp.int32)
            width = swidths[sk]
            sk += 1
            byte0 = (row_off4 * 4 + foff.astype(jnp.int32))
            bidx = byte0[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
            smat = jnp.take(wire_u8,
                            jnp.clip(bidx, 0, max(W * 4 - 1, 0)).reshape(-1)
                            ).reshape(n, width)
            keep = jnp.arange(width, dtype=jnp.int32)[None, :] < flen[:, None]
            strings.append((jnp.where(keep, smat, jnp.uint8(0)), flen))
            datas.append(None)
            continue
        size = dt.itemsize
        if size == 16:
            quad = jnp.stack(
                [jnp.stack([planes[off // 4], planes[off // 4 + 1]], -1),
                 jnp.stack([planes[off // 4 + 2], planes[off // 4 + 3]], -1)],
                axis=-2)
            data = jax.lax.bitcast_convert_type(quad, jnp.int64)
        elif size == 8:
            pair = jnp.stack([planes[off // 4], planes[off // 4 + 1]], -1)
            data = jax.lax.bitcast_convert_type(pair, jnp.int64)
            if dt.id != TypeId.FLOAT64:
                data = data.astype(dt.jnp_dtype)
        elif size == 4:
            data = jax.lax.bitcast_convert_type(planes[off // 4],
                                                dt.jnp_dtype)
        elif size == 2:
            u16 = subword(off, 2).astype(jnp.uint16)
            data = jax.lax.bitcast_convert_type(u16, dt.jnp_dtype)
        else:
            u8 = subword(off, 1).astype(jnp.uint8)
            data = u8 if dt.jnp_dtype == jnp.uint8 else \
                jax.lax.bitcast_convert_type(u8, dt.jnp_dtype)
        datas.append(data)

    masks = []
    for i in range(len(base.schema)):
        byte = subword(base.validity_offset + i // 8, 1)
        masks.append(((byte >> jnp.uint32(i % 8)) & jnp.uint32(1))
                     .astype(jnp.bool_))
    return datas, masks, strings


@functools.partial(jax.jit, static_argnums=0)
def _var_probe(vlayout: VarRowLayout, soffs, svalids):
    """ONE device program -> [max_len per string col ..., total bytes].

    The only data-dependent statics of the variable-width conversion, so
    the host pays a single scalar-vector fetch before launching the fused
    kernel (each fetch is a host sync; its cost is not measured on today's
    machine)."""
    outs = []
    total = jnp.int64(0)
    for offs, valid in zip(soffs, svalids):
        ln = (offs[1:] - offs[:-1]).astype(jnp.int32)
        if valid is not None:
            ln = jnp.where(valid, ln, 0)
        outs.append(jnp.max(ln) if ln.shape[0] else jnp.int32(0))
        total = total + jnp.sum((ln.astype(jnp.int64) + 7) // 8 * 8)
    n = soffs[0].shape[0] - 1 if soffs else 0
    total = total + vlayout.base.row_size * n
    return jnp.stack([o.astype(jnp.int64) for o in outs] + [total])


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _to_rows_var_fused(vlayout: VarRowLayout, swidths: tuple,
                       total_words: int, datas, masks, soffs, schars):
    """Single-batch fused program: string padded matrices, row offsets and
    the wire sort in ONE compilation — no eager dispatch chatter."""
    smat_words = []
    slens = []
    n = soffs[0].shape[0] - 1 if soffs else (
        datas[0].shape[0] if datas and datas[0] is not None else 0)
    row_sizes = jnp.full((n,), vlayout.base.row_size, jnp.int64)
    for k, (offs, chars) in enumerate(zip(soffs, schars)):
        w = swidths[k]
        starts = offs[:-1]
        lengths = (offs[1:] - starts).astype(jnp.int32)
        valid = masks[vlayout.string_idx[k]]
        if valid is not None:
            lengths = jnp.where(valid, lengths, 0)
        idx = starts[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
        mat = jnp.take(chars, idx, mode="clip")
        keep = jnp.arange(w, dtype=jnp.int32)[None, :] < lengths[:, None]
        mat = jnp.where(keep, mat, jnp.uint8(0))
        words = jax.lax.bitcast_convert_type(
            mat.reshape(n, w // 4, 4), jnp.uint32)
        smat_words.append(words.reshape(-1))
        slens.append(lengths)
        row_sizes = row_sizes + ((lengths.astype(jnp.int64) + 7) // 8 * 8)
    row_ends = jnp.cumsum(row_sizes)
    row_off4 = ((row_ends - row_sizes) // 4).astype(jnp.int32)
    wire = _to_rows_wire_var(vlayout, swidths, total_words, datas, masks,
                             tuple(smat_words), tuple(slens), row_off4)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               row_ends.astype(jnp.int32)])
    return wire, offsets


def _convert_to_rows_var(table: Table, max_batch_bytes: int) -> list[Column]:
    """Host wrapper for the variable-width path.

    All per-row math (lengths, row sizes, offsets) stays ON DEVICE — host
    syncs are scalars only (total bytes, max string length): fewer host
    syncs, and no n-sized array crosses to the host.
    """
    vlayout = variable_width_layout(table.dtypes())
    base = vlayout.base
    n = table.num_rows
    scols = [table.columns[i] for i in vlayout.string_idx]
    soffs = tuple(jnp.asarray(c.offsets, jnp.int32) for c in scols)
    svalids = tuple(c.validity for c in scols)
    schars = tuple(jnp.asarray(c.data, jnp.uint8)
                   if c.data is not None and c.data.shape[0]
                   else jnp.zeros((1,), jnp.uint8) for c in scols)
    probe = np.asarray(_var_probe(vlayout, soffs, svalids))  # one fetch
    # align8 widths (not pow2 buckets): every lane of the padded matrix
    # rides the wire sort, so slack lanes are real sort work
    swidths = tuple(max(8, (int(mx) + 7) // 8 * 8) for mx in probe[:-1])
    total_bytes = int(probe[-1]) if n else 0

    datas = tuple(None if dt.is_string else c.data
                  for dt, c in zip(base.schema, table.columns))
    masks = tuple(c.validity for c in table.columns)

    if total_bytes <= max_batch_bytes:  # common case: ONE fused program
        wire, offsets = _to_rows_var_fused(vlayout, swidths,
                                           total_bytes // 4, datas, masks,
                                           soffs, schars)
        return [Column.list_(PackedByteColumn(INT8, data=wire), offsets)]

    smat_words = []
    slens = []
    row_sizes = jnp.full((n,), base.row_size, jnp.int64)
    for c, w in zip(scols, swidths):
        words, lengths = _string_words(c, w)
        smat_words.append(words)
        slens.append(lengths)
        row_sizes = row_sizes + ((lengths.astype(jnp.int64) + 7) // 8 * 8)
    row_ends = jnp.cumsum(row_sizes)

    def emit(start, stop, total_words, row_off4, ends):
        bdatas = tuple(None if d is None else d[start:stop] for d in datas)
        bmasks = tuple(None if m is None else m[start:stop] for m in masks)
        bwords = tuple(words.reshape(-1, w // 4)[start:stop].reshape(-1)
                       for w, words in zip(swidths, smat_words))
        blens = tuple(l[start:stop] for l in slens)
        wire = _to_rows_wire_var(vlayout, tuple(swidths), total_words,
                                 bdatas, bmasks, bwords, blens, row_off4)
        offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   ends.astype(jnp.int32)])
        return Column.list_(PackedByteColumn(INT8, data=wire), offsets)

    # multi-batch: row boundary planning needs the size vector on the host
    ends_np = np.asarray(row_ends)
    sizes_np = np.diff(np.concatenate([[0], ends_np]))
    if int(sizes_np.max()) > max_batch_bytes:
        raise ValueError(
            f"a single row packs to {int(sizes_np.max())} bytes, above "
            f"max_batch_bytes={max_batch_bytes}")
    out = []
    start = 0
    while start < n:
        # batch greedily by bytes, 32-row aligned when at least one whole
        # group fits (reference row_conversion.cu:476-511); searchsorted
        # gives >= start+1 because every single row fits max_batch_bytes
        base_off = int(ends_np[start - 1]) if start else 0
        stop = int(np.searchsorted(ends_np, base_off + max_batch_bytes,
                                   side="right"))
        if stop < n:
            fit = stop - start
            if fit >= BATCH_ROW_ALIGN:
                # at least one whole aligned group fits the byte budget:
                # align the cut down — the HARD contract middle batches keep
                stop = start + fit // BATCH_ROW_ALIGN * BATCH_ROW_ALIGN
            else:
                # fewer than 32 rows fit: greedy maximality of searchsorted
                # means one aligned group genuinely exceeds max_batch_bytes,
                # the single case the contract exempts — enforce that this
                # is why the cut is unaligned
                group_end = min(start + BATCH_ROW_ALIGN, n)
                assert int(ends_np[group_end - 1]) - base_off \
                    > max_batch_bytes, "unaligned middle batch despite a " \
                    "fitting aligned group"
        total_words = int(ends_np[stop - 1] - base_off) // 4
        row_off4 = ((row_ends[start:stop] - row_sizes[start:stop]
                     - base_off) // 4).astype(jnp.int32)
        out.append(emit(start, stop, total_words, row_off4,
                        row_ends[start:stop] - base_off))
        start = stop
    return out


@functools.partial(jax.jit, static_argnums=0)
def _from_rows_probe(vlayout: VarRowLayout, wire, row_off4):
    """Max string length per string column, stacked — one fetch."""
    base = vlayout.base
    outs = []
    for idx in vlayout.string_idx:
        slot_word = base.offsets[idx] // 4 + 1
        lens = jnp.take(wire, jnp.clip(row_off4 + slot_word, 0,
                                       max(wire.shape[0] - 1, 0)))
        outs.append(jnp.max(lens).astype(jnp.int64))
    return jnp.stack(outs)


def _convert_from_rows_var(rows: Column, schema: Sequence[DType]) -> Table:
    from .strings_common import from_padded_bytes
    vlayout = variable_width_layout(schema)
    base = vlayout.base
    child = rows.children[0]
    offs = jnp.asarray(rows.offsets, jnp.int64)
    n = offs.shape[0] - 1
    sizes = offs[1:] - offs[:-1]
    if n and int(jnp.sum(((sizes < base.row_size) |
                          (sizes % 8 != 0)).astype(jnp.int32))):
        raise ValueError(
            f"variable-width row blobs must be 8-byte aligned and at least "
            f"the fixed region ({base.row_size} B)")
    if child.data.dtype == jnp.uint32:
        wire = child.data
    else:
        wire = jax.lax.bitcast_convert_type(
            jnp.asarray(child.data, jnp.uint8).reshape(-1, 4), jnp.uint32)
    row_off4 = (offs[:-1] // 4).astype(jnp.int32)

    # ONE host sync sizes every padded string matrix (trace-stable align8
    # buckets) — the mirror of _var_probe on the to-rows side; per-column
    # fetches would pay one host sync each
    if n and vlayout.string_idx:
        maxes = np.asarray(_from_rows_probe(vlayout, wire, row_off4))
        swidths = [max(8, (int(mx) + 7) // 8 * 8) for mx in maxes]
    else:
        swidths = [8] * len(vlayout.string_idx)

    datas, masks, strings = _from_rows_var(vlayout, tuple(swidths), n,
                                           wire, row_off4)
    cols = []
    sk = 0
    for dt, d, m in zip(base.schema, datas, masks):
        if dt.is_string:
            smat, slen = strings[sk]
            sk += 1
            cols.append(from_padded_bytes(smat, slen, validity=m))
        else:
            cols.append(Column(dt, data=d, validity=m))
    return Table(cols)


# ---------------------------------------------------------------------------
# public API (mirrors RowConversion.java:101-121)
# ---------------------------------------------------------------------------

@traced("convert_to_rows")
def convert_to_rows(table: Table, max_batch_bytes: int = MAX_BATCH_BYTES) -> list[Column]:
    """Columnar table -> list of LIST<INT8> row-blob columns.

    Analog of ``RowConversion.convertToRows`` (RowConversion.java:101-108).
    Returns multiple columns when the packed output would exceed
    ``max_batch_bytes`` (reference row_conversion.cu:476-511).  Batch row
    counts are a multiple of 32 except possibly the last — a hard contract
    on both paths.  The fixed-width path raises when even one 32-row group
    exceeds ``max_batch_bytes``; the variable-width (STRING) path cuts a
    middle batch unaligned ONLY in that same oversized-group case (whenever
    at least one aligned group fits the budget, the cut is aligned).

    STRING columns produce variable-width rows under the UnsafeRow-style
    contract documented above ``VarRowLayout`` (the reference snapshot
    CUDF_FAILs here, row_conversion.cu:515).
    """
    if any(dt.is_string for dt in table.dtypes()):
        return _convert_to_rows_var(table, max_batch_bytes)
    layout = fixed_width_layout(table.dtypes())
    n = table.num_rows
    rows_per_batch = max(1, max_batch_bytes // layout.row_size)
    if rows_per_batch < n:
        if layout.row_size * BATCH_ROW_ALIGN > max_batch_bytes:
            # a 32-row-aligned batch would exceed the cap (and for the default
            # cap, overflow the int32 LIST offsets the format protects)
            raise ValueError(
                f"row size {layout.row_size} too large: a {BATCH_ROW_ALIGN}"
                f"-row aligned batch exceeds max_batch_bytes={max_batch_bytes}")
        rows_per_batch = rows_per_batch // BATCH_ROW_ALIGN * BATCH_ROW_ALIGN
    out = []
    start = 0
    while start < n or (n == 0 and not out):
        stop = min(n, start + rows_per_batch)
        datas = tuple(c.data[start:stop] for c in table.columns)
        masks = tuple(None if c.validity is None else c.validity[start:stop]
                      for c in table.columns)
        wire = _to_rows_wire_jit(layout, datas, masks)
        nb = stop - start
        offsets = jnp.arange(nb + 1, dtype=jnp.int32) * layout.row_size
        out.append(Column.list_(PackedByteColumn(INT8, data=wire), offsets))
        start = stop
        if n == 0:
            break
    return out


@traced("convert_from_rows")
def convert_from_rows(rows: Column, schema: Sequence[DType]) -> Table:
    """LIST<INT8> row blobs -> columnar table.

    Analog of ``RowConversion.convertFromRows`` (RowConversion.java:110-121);
    ``schema`` plays the role of the flattened (type-id, scale) pairs the Java
    layer marshals (RowConversion.java:113-118).
    """
    if rows.dtype.id != TypeId.LIST or not rows.children:
        raise TypeError("expected a LIST<INT8> row-blob column")
    child = rows.children[0]
    if child.dtype not in (INT8, UINT8):
        # parity with the INT8/UINT8 child guard (row_conversion.cu:525-528)
        raise TypeError(f"row blobs must be LIST<INT8>, child is {child.dtype!r}")
    if any(dt.is_string for dt in schema):
        return _convert_from_rows_var(rows, schema)
    layout = fixed_width_layout(schema)
    offs = np.asarray(rows.offsets)
    n = offs.shape[0] - 1
    widths = np.diff(offs)
    if n and not (widths == layout.row_size).all():
        # parity with the size cross-check (row_conversion.cu:537-542)
        raise ValueError(
            f"row width mismatch: blobs have {set(widths.tolist())} bytes/row, "
            f"schema packs to {layout.row_size}")
    if child.data.dtype == jnp.uint32:  # packed-word blob (convert_to_rows)
        datas, masks = _from_rows_wire_jit(layout, child.data, n)
    else:
        datas, masks = _from_rows_bytes(layout, jnp.asarray(child.data,
                                                            jnp.uint8))
    cols = [Column(dt, data=d, validity=m)
            for dt, d, m in zip(layout.schema, datas, masks)]
    return Table(cols)
