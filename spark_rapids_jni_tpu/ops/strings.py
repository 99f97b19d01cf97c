"""String ops over Arrow-layout STRING columns.

The compute form is the padded byte matrix (strings_common.py); results are
BOOL8/INT32 columns (predicates) or new STRING columns.  Character semantics
follow Spark: ``length``/``substring`` count UTF-8 characters, not bytes.

These are the building blocks the reference's RegexRewrite component lowers
regexes onto (startsWith/endsWith/contains — see regex_rewrite.py) plus the
string functions NDS queries need.  Predicates are fully jit-able; ops that
produce new STRING columns compact through the host at the API boundary
(XLA needs static shapes; inside fused pipelines keep the matrix form).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import Column
from ..dtypes import INT32, BOOL8
from .strings_common import to_padded_bytes, from_padded_bytes

_I32 = jnp.int32


def _prop_valid(col: Column, extra=None):
    v = col.validity
    if extra is not None:
        v = extra if v is None else (v & extra)
    return v


def byte_length(col: Column) -> Column:
    """Byte length per row (jit-able straight off the offsets)."""
    offsets = jnp.asarray(col.offsets, _I32)
    return Column(INT32, data=offsets[1:] - offsets[:-1],
                  validity=_prop_valid(col))


def char_length(col: Column) -> Column:
    """Spark ``length()``: UTF-8 character count (continuation bytes excluded)."""
    mat, lengths = to_padded_bytes(col)
    in_str = jnp.arange(mat.shape[1], dtype=_I32)[None, :] < lengths[:, None]
    starts = ((mat & jnp.uint8(0xC0)) != jnp.uint8(0x80)) & in_str
    return Column(INT32, data=starts.sum(axis=1, dtype=_I32),
                  validity=_prop_valid(col))


def upper(col: Column) -> Column:
    """ASCII uppercase (multi-byte code points pass through unchanged)."""
    mat, lengths = to_padded_bytes(col)
    out = jnp.where((mat >= 97) & (mat <= 122), mat - 32, mat)
    return from_padded_bytes(out, lengths, _prop_valid(col))


def lower(col: Column) -> Column:
    """ASCII lowercase (multi-byte code points pass through unchanged)."""
    mat, lengths = to_padded_bytes(col)
    out = jnp.where((mat >= 65) & (mat <= 90), mat + 32, mat)
    return from_padded_bytes(out, lengths, _prop_valid(col))


# ---------------------------------------------------------------------------
# literal search predicates (the RegexRewrite lowering targets)
# ---------------------------------------------------------------------------

def _literal(pat) -> bytes:
    return pat.encode() if isinstance(pat, str) else bytes(pat)


@functools.partial(jax.jit, static_argnums=2)
def _match_positions(mat, lengths, pat: bytes):
    """bool[n, W]: window at shift s equals ``pat`` and fits in the row."""
    n, w = mat.shape
    if len(pat) == 0:
        fits = jnp.arange(w, dtype=_I32)[None, :] <= lengths[:, None]
        return fits
    padded = jnp.pad(mat, ((0, 0), (0, len(pat))))
    eq = jnp.ones((n, w), jnp.bool_)
    for i, b in enumerate(pat):
        eq = eq & (padded[:, i:i + w] == jnp.uint8(b))
    fits = (jnp.arange(w, dtype=_I32)[None, :]
            <= (lengths[:, None] - len(pat)))
    return eq & fits


def starts_with(col: Column, pat) -> Column:
    pat = _literal(pat)
    mat, lengths = to_padded_bytes(col)
    hit = _match_positions(mat, lengths, pat)[:, 0] if mat.shape[1] else \
        jnp.zeros((len(col),), jnp.bool_)
    if len(pat) == 0:
        hit = jnp.ones((len(col),), jnp.bool_)
    return Column(BOOL8, data=hit.astype(jnp.uint8), validity=_prop_valid(col))


def ends_with(col: Column, pat) -> Column:
    pat = _literal(pat)
    mat, lengths = to_padded_bytes(col)
    if len(pat) == 0:
        hit = jnp.ones((len(col),), jnp.bool_)
    else:
        pos = _match_positions(mat, lengths, pat)
        tailpos = jnp.clip(lengths - len(pat), 0, mat.shape[1] - 1)
        hit = jnp.take_along_axis(pos, tailpos[:, None], axis=1)[:, 0]
        hit = hit & (lengths >= len(pat))
    return Column(BOOL8, data=hit.astype(jnp.uint8), validity=_prop_valid(col))


def contains(col: Column, pat) -> Column:
    pat = _literal(pat)
    mat, lengths = to_padded_bytes(col)
    if len(pat) == 0:
        hit = jnp.ones((len(col),), jnp.bool_)
    else:
        hit = _match_positions(mat, lengths, pat).any(axis=1)
    return Column(BOOL8, data=hit.astype(jnp.uint8), validity=_prop_valid(col))


def equal(col: Column, other) -> Column:
    """Elementwise ``==`` against a python string or another STRING column.

    The kernel the interpreted Filter path lowers ``==``/``!=`` predicates
    over STRING columns onto (engine/expr.py::evaluate) — raw ``col.data`` is a
    chars buffer, so the generic jnp comparison is meaningless for strings.
    """
    mat, lengths = to_padded_bytes(col)
    if isinstance(other, Column):
        omat, olengths = to_padded_bytes(other)
        w = max(mat.shape[1], omat.shape[1])
        if mat.shape[1] < w:
            mat = jnp.pad(mat, ((0, 0), (0, w - mat.shape[1])))
        if omat.shape[1] < w:
            omat = jnp.pad(omat, ((0, 0), (0, w - omat.shape[1])))
        in_str = jnp.arange(w, dtype=_I32)[None, :] < lengths[:, None]
        hit = (lengths == olengths) & \
            jnp.where(in_str, mat == omat, True).all(axis=1)
        return Column(BOOL8, data=hit.astype(jnp.uint8),
                      validity=_prop_valid(col, other.validity))
    pat = _literal(other)
    if len(pat) == 0:
        hit = lengths == 0
    elif len(pat) > mat.shape[1]:
        hit = jnp.zeros((len(col),), jnp.bool_)
    else:
        target = jnp.asarray(np.frombuffer(pat, np.uint8))
        hit = (lengths == len(pat)) & \
            (mat[:, :len(pat)] == target).all(axis=1)
    return Column(BOOL8, data=hit.astype(jnp.uint8), validity=_prop_valid(col))


def find(col: Column, pat) -> Column:
    """First byte index of ``pat`` per row, -1 when absent (cudf find())."""
    pat = _literal(pat)
    mat, lengths = to_padded_bytes(col)
    pos = _match_positions(mat, lengths, pat)
    first = jnp.argmax(pos, axis=1).astype(_I32)
    found = pos.any(axis=1)
    idx = jnp.where(found, first, _I32(-1))
    if len(pat) == 0:
        idx = jnp.zeros((len(col),), _I32)
    return Column(INT32, data=idx, validity=_prop_valid(col))


# ---------------------------------------------------------------------------
# substring (character-based, Spark semantics)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(2, 3))
def _substring_matrix(mat, lengths, start: int, length: int | None):
    n, w = mat.shape
    in_str = jnp.arange(w, dtype=_I32)[None, :] < lengths[:, None]
    is_start = ((mat & jnp.uint8(0xC0)) != jnp.uint8(0x80)) & in_str
    nchars = is_start.sum(axis=1, dtype=_I32)
    # byte offset of each character: scatter byte positions into char slots
    char_no = jnp.cumsum(is_start, axis=1, dtype=_I32) - 1
    char_no = jnp.where(is_start, char_no, w)  # park non-starts in a spare slot
    bytepos = jnp.broadcast_to(jnp.arange(w, dtype=_I32)[None, :], (n, w))
    rows = jnp.broadcast_to(jnp.arange(n, dtype=_I32)[:, None], (n, w))
    char_byte = jnp.full((n, w + 1), 0, _I32)
    char_byte = char_byte.at[rows, char_no].set(bytepos, mode="drop")
    # char index c >= nchars maps to the row's byte length
    cidx = jnp.arange(w + 1, dtype=_I32)[None, :]
    char_byte = jnp.where(cidx >= nchars[:, None], lengths[:, None], char_byte)

    # Spark substring: 1-based, 0 treated as 1, negative counts from the end
    if start > 0:
        first_char = jnp.full((n,), start - 1, _I32)
    elif start == 0:
        first_char = jnp.zeros((n,), _I32)
    else:
        first_char = jnp.maximum(nchars + start, 0)
    first_char = jnp.minimum(first_char, nchars)
    if length is None:
        last_char = nchars
    else:
        last_char = jnp.minimum(first_char + max(length, 0), nchars)

    sb = jnp.take_along_axis(char_byte, first_char[:, None], axis=1)[:, 0]
    eb = jnp.take_along_axis(char_byte, last_char[:, None], axis=1)[:, 0]
    out_len = eb - sb
    idx = sb[:, None] + jnp.arange(w, dtype=_I32)[None, :]
    gathered = jnp.take_along_axis(
        jnp.pad(mat, ((0, 0), (0, 1))), jnp.clip(idx, 0, w), axis=1)
    keep = jnp.arange(w, dtype=_I32)[None, :] < out_len[:, None]
    return jnp.where(keep, gathered, jnp.uint8(0)), out_len


def substring(col: Column, start: int, length: int | None = None) -> Column:
    """Spark ``substring(str, pos[, len])`` — character-based."""
    mat, lengths = to_padded_bytes(col)
    out, out_len = _substring_matrix(mat, lengths, int(start),
                                     None if length is None else int(length))
    return from_padded_bytes(out, out_len, _prop_valid(col))


def concat_padded(mats, lens, valids=None):
    """Jit-able Spark ``concat`` over padded byte matrices.

    Each input row scatters at its running start offset into an output of
    static width sum(w_k); dead lanes route to an out-of-bounds column and
    drop.  Returns (u8[n, W] matrix, lengths, valid) — null if any input
    row is null.
    """
    n = mats[0].shape[0]
    W = int(sum(m.shape[1] for m in mats))
    out = jnp.zeros((n, W), jnp.uint8)
    pos = jnp.zeros((n,), _I32)
    rows = jnp.arange(n, dtype=_I32)[:, None]
    for m, l in zip(mats, lens):
        w = m.shape[1]
        lane = jnp.arange(w, dtype=_I32)
        tgt = pos[:, None] + lane[None, :]
        tgt = jnp.where(lane[None, :] < l[:, None], tgt, W)  # dead -> drop
        out = out.at[jnp.broadcast_to(rows, (n, w)), tgt].set(m, mode="drop")
        pos = pos + l.astype(_I32)
    valid = None
    if valids is not None:
        for v in valids:
            if v is not None:
                valid = v if valid is None else (valid & v)
    return out, pos, valid


def concat(*cols: Column) -> Column:
    """Spark ``concat``: null if any input is null.  The scatter runs on
    device (concat_padded); only the Arrow materialization is host-side."""
    mats, lens, valids = [], [], []
    for c in cols:
        m, l = to_padded_bytes(c)
        mats.append(m)
        lens.append(l)
        valids.append(c.validity)
    out, out_len, valid = concat_padded(mats, lens, valids)
    if valid is not None and bool(valid.all()):
        valid = None
    return from_padded_bytes(out, out_len, valid)


# ---------------------------------------------------------------------------
# replace / split: greedy non-overlapping literal matches, vectorized
# ---------------------------------------------------------------------------


def _greedy_matches(pos, L: int):
    """Left-to-right non-overlapping selection of candidate starts.

    ``pos`` is bool[n, w] candidate match starts; a start is active iff no
    active start began within the previous L-1 bytes (Spark/cudf replace
    semantics).  One lax.scan over the width, vectorized across rows."""
    if L <= 1:
        return pos
    n, w = pos.shape

    def step(cool, x):
        can = (cool == 0) & x
        cool = jnp.where(can, _I32(L - 1),
                         jnp.maximum(cool - 1, 0))
        return cool, can

    _, act = jax.lax.scan(step, jnp.zeros((n,), _I32), pos.T)
    return act.T


@functools.partial(jax.jit, static_argnums=(2, 3))
def _replace_matrix(mat, lengths, pat: bytes, rep: bytes):
    """(out matrix, out lengths) for literal replace-all."""
    n, w = mat.shape
    L, R = len(pat), len(rep)
    act = _greedy_matches(_match_positions(mat, lengths, pat), L)
    c = jnp.cumsum(act, axis=1, dtype=_I32)          # inclusive active count
    count = c[:, -1] if w else jnp.zeros((n,), _I32)
    # covered[j]: byte j belongs to a match  (an active start in (j-L, j])
    cpad = jnp.pad(c, ((0, 0), (L, 0)))
    covered = (c - cpad[:, :w]) > 0
    # prior_ended[j]: matches fully before byte j  (starts at p <= j - L)
    prior = cpad[:, :w]
    W = w + (w // max(L, 1)) * max(R - L, 0)
    out = jnp.zeros((n, W), jnp.uint8)
    rows = jnp.arange(n, dtype=_I32)[:, None]
    j = jnp.arange(w, dtype=_I32)[None, :]
    in_str = j < lengths[:, None]
    # pass 1: keep bytes outside matches, shifted by earlier size deltas
    tgt = j + prior * (R - L)
    tgt = jnp.where(in_str & ~covered, tgt, W)       # dead lanes drop
    out = out.at[jnp.broadcast_to(rows, (n, w)),
                 jnp.clip(tgt, 0, W)].set(mat, mode="drop")
    # pass 2: write the replacement at each active start's shifted position
    start_out = j + (c - 1) * (R - L)
    for r, b in enumerate(rep):
        tr = jnp.where(act, start_out + r, W)
        out = out.at[jnp.broadcast_to(rows, (n, w)),
                     jnp.clip(tr, 0, W)].set(jnp.uint8(b), mode="drop")
    out_len = lengths + count * (R - L)
    return out, out_len


def replace(col: Column, search, replacement) -> Column:
    """Spark ``replace(str, search, replace)``: all non-overlapping literal
    occurrences, left to right.  Empty search returns the input unchanged
    (Spark semantics)."""
    pat = _literal(search)
    rep = _literal(replacement)
    if len(pat) == 0:
        return col
    mat, lengths = to_padded_bytes(col)
    out, out_len = _replace_matrix(mat, lengths, pat, rep)
    return from_padded_bytes(out, out_len, _prop_valid(col))


@functools.partial(jax.jit, static_argnums=2)
def _delim_layout(mat, lengths, delim: bytes):
    """(active starts, inclusive count cumsum, total count) for a delimiter."""
    act = _greedy_matches(_match_positions(mat, lengths, delim), len(delim))
    c = jnp.cumsum(act, axis=1, dtype=_I32)
    total = c[:, -1] if mat.shape[1] else jnp.zeros(mat.shape[:1], _I32)
    return act, c, total


def split_part(col: Column, delim, index: int) -> Column:
    """Spark ``split_part(str, delim, partNum)``: 1-based; negative counts
    from the end; 0 is an error.  Out-of-range parts are empty strings;
    the delimiter is a literal."""
    d = _literal(delim)
    if len(d) == 0 or index == 0:
        raise ValueError("split_part needs a non-empty delimiter and a "
                         "non-zero part number (negative counts from "
                         "the end)")
    mat, lengths = to_padded_bytes(col)
    n, w = mat.shape
    act, c, total = _delim_layout(mat, lengths, d)
    # 0-based part number per row; rows have total+1 parts
    if index > 0:
        k = jnp.full((n,), index - 1, _I32)
    else:
        k = total + 1 + index  # may go negative -> out of range
    j = jnp.arange(w, dtype=_I32)[None, :]
    # start byte of part k: 0, or end of the k-th delimiter; end byte:
    # start of the (k+1)-th delimiter or row length
    def nth_start(m):
        """Byte position of the (m+1)-th active delimiter per row."""
        hit = act & (c == m[:, None] + 1)
        anyhit = hit.any(axis=1)
        p = jnp.argmax(hit, axis=1).astype(_I32)
        return jnp.where(anyhit, p, lengths), anyhit
    p, prev_ok = nth_start(k - 1)
    sb = jnp.where(k > 0, jnp.where(prev_ok, p + len(d), lengths),
                   jnp.int32(0))
    ok = (k == 0) | (prev_ok & (k > 0))
    e, e_ok = nth_start(k)
    eb = jnp.where(e_ok, e, lengths)
    have = ok & (k >= 0) & (sb <= lengths)
    out_len = jnp.where(have, jnp.maximum(eb - sb, 0), 0)
    idx = sb[:, None] + j
    gathered = jnp.take_along_axis(
        jnp.pad(mat, ((0, 0), (0, 1))), jnp.clip(idx, 0, w), axis=1)
    keep = j < out_len[:, None]
    return from_padded_bytes(jnp.where(keep, gathered, jnp.uint8(0)),
                             out_len, _prop_valid(col))


def split(col: Column, delim) -> Column:
    """Spark ``split(str, delim)`` with a literal delimiter -> LIST<STRING>.

    Match positions and part boundaries are computed on device; the ragged
    LIST<STRING> materialization happens at the host boundary like every
    other ragged producer in the engine."""
    d = _literal(delim)
    if len(d) == 0:
        raise ValueError("split needs a non-empty delimiter")
    mat, lengths = to_padded_bytes(col)
    n, w = mat.shape
    act, c, total = _delim_layout(mat, lengths, d)
    act_np = np.asarray(act)
    len_np = np.asarray(lengths).astype(np.int64)
    mat_np = np.asarray(mat)
    total_np = np.asarray(total).astype(np.int64)
    nparts_row = total_np + 1
    if col.validity is not None:
        # null rows get EMPTY list ranges (the engine-wide Arrow
        # convention), not a phantom one-part list; their delimiter hits
        # and lengths are zeroed so starts_d stays aligned with the
        # non-first parts below
        vnp = np.asarray(col.validity)
        nparts_row[~vnp] = 0
        act_np = act_np.copy()
        act_np[~vnp] = False
        len_np = len_np.copy()
        len_np[~vnp] = 0
    loffsets = np.zeros(n + 1, np.int64)
    np.cumsum(nparts_row, out=loffsets[1:])
    # vectorized part boundaries: delimiter starts (row-major order) split
    # each row into parts; a part's bytes are [prev_end, start), the last
    # part ends at the row length.  No per-part Python loop.
    rows_d, starts_d = np.nonzero(act_np)        # in row-major order
    nparts = int(loffsets[-1])
    part_row = np.repeat(np.arange(n), nparts_row)
    nonempty = nparts_row > 0                    # null rows have no parts
    first = np.zeros(nparts, np.bool_)
    first[loffsets[:-1][nonempty]] = True
    part_start = np.zeros(nparts, np.int64)
    part_end = np.empty(nparts, np.int64)
    # parts after a delimiter start at delim_pos + len(d); each row's
    # non-first parts align with its delimiters in order
    part_start[~first] = starts_d + len(d)
    part_end[:] = len_np[part_row]
    # non-last parts end at their delimiter's position
    last = np.zeros(nparts, np.bool_)
    last[loffsets[1:][nonempty] - 1] = True
    part_end[~last] = starts_d
    plens = np.maximum(part_end - part_start, 0)
    offsets = np.zeros(nparts + 1, np.int64)
    np.cumsum(plens, out=offsets[1:])
    if offsets[-1] > np.iinfo(np.int32).max:
        raise OverflowError("split output exceeds int32 char offsets")
    # one fancy-indexed gather for all part bytes
    byte_row = np.repeat(part_row, plens)
    byte_col = np.repeat(part_start, plens) + \
        np.arange(int(offsets[-1])) - np.repeat(offsets[:-1], plens)
    chars = mat_np[byte_row, byte_col] if byte_row.size else \
        np.zeros(0, np.uint8)
    child = Column.string(jnp.asarray(chars), offsets.astype(np.int32))
    return Column.list_(child, loffsets.astype(np.int32),
                        validity=_prop_valid(col))


# ---------------------------------------------------------------------------
# trim / pad
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _trim_matrix(mat, lengths, trimset: bytes, left: bool, right: bool):
    n, w = mat.shape
    j = jnp.arange(w, dtype=_I32)[None, :]
    in_str = j < lengths[:, None]
    is_t = jnp.zeros((n, w), jnp.bool_)
    for b in trimset:
        is_t = is_t | (mat == jnp.uint8(b))
    is_t = is_t & in_str
    if left:
        lead = jnp.cumprod(is_t, axis=1, dtype=jnp.int32).sum(
            axis=1, dtype=_I32)
    else:
        lead = jnp.zeros((n,), _I32)
    if right:
        tail_t = is_t | ~in_str  # padding counts as trimmable from the right
        trail = jnp.cumprod(tail_t[:, ::-1], axis=1, dtype=jnp.int32).sum(
            axis=1, dtype=_I32) - (w - lengths)
        trail = jnp.maximum(trail, 0)
    else:
        trail = jnp.zeros((n,), _I32)
    out_len = jnp.maximum(lengths - lead - trail, 0)
    idx = lead[:, None] + j
    gathered = jnp.take_along_axis(
        jnp.pad(mat, ((0, 0), (0, 1))), jnp.clip(idx, 0, w), axis=1)
    keep = j < out_len[:, None]
    return jnp.where(keep, gathered, jnp.uint8(0)), out_len


def _trim(col: Column, chars, left: bool, right: bool) -> Column:
    if chars == "" or (isinstance(chars, (bytes, bytearray))
                       and len(chars) == 0):
        return col  # Spark: TRIM('' FROM s) is a no-op
    trimset = chars.encode() if isinstance(chars, str) else \
        b" " if chars is None else bytes(chars)
    if any(b >= 0x80 for b in trimset):
        # the match is byte-wise; a multi-byte trim character would strip
        # individual UTF-8 bytes and corrupt the row
        raise ValueError("only ASCII trim characters are supported")
    mat, lengths = to_padded_bytes(col)
    out, out_len = _trim_matrix(mat, lengths, trimset, left, right)
    return from_padded_bytes(out, out_len, _prop_valid(col))


def trim(col: Column, chars: str | None = None) -> Column:
    """Spark ``trim``: strip leading+trailing characters (default space).

    The trim set must be ASCII (byte-wise matching); an empty trim set is a
    no-op as in Spark."""
    return _trim(col, chars, True, True)


def ltrim(col: Column, chars: str | None = None) -> Column:
    return _trim(col, chars, True, False)


def rtrim(col: Column, chars: str | None = None) -> Column:
    return _trim(col, chars, False, True)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _pad_matrix(mat, lengths, width: int, pad: bytes, left: bool):
    n, w = mat.shape
    j = jnp.arange(w, dtype=_I32)[None, :]
    in_str = j < lengths[:, None]
    starts = ((mat & jnp.uint8(0xC0)) != jnp.uint8(0x80)) & in_str
    nchars = starts.sum(axis=1, dtype=_I32)
    pad_count = jnp.clip(width - nchars, 0, width)
    lane = jnp.arange(width, dtype=_I32)
    cyc = np.frombuffer(bytes(pad[i % len(pad)] for i in range(width)),
                        np.uint8) if width else np.zeros(0, np.uint8)
    padmat = jnp.where(lane[None, :] < pad_count[:, None],
                       jnp.asarray(cyc)[None, :], jnp.uint8(0))
    tmat, tlen = _substring_matrix(mat, lengths, 1, width)  # <= width chars
    if left:
        out, out_len, _ = concat_padded([padmat, tmat], [pad_count, tlen])
    else:
        out, out_len, _ = concat_padded([tmat, padmat], [tlen, pad_count])
    return out, out_len


def _pad(col: Column, width: int, pad: str, left: bool) -> Column:
    pb = pad.encode()
    if not pb:
        raise ValueError("pad string must be non-empty")
    if any(b >= 0x80 for b in pb):
        raise ValueError("only ASCII pad strings are supported")
    mat, lengths = to_padded_bytes(col)
    out, out_len = _pad_matrix(mat, lengths, int(width), pb, left)
    return from_padded_bytes(out, out_len, _prop_valid(col))


def lpad(col: Column, width: int, pad: str = " ") -> Column:
    """Spark ``lpad``: left-pad (cycling ``pad``) to ``width`` characters;
    longer strings truncate to the first ``width`` characters."""
    return _pad(col, width, pad, True)


def rpad(col: Column, width: int, pad: str = " ") -> Column:
    return _pad(col, width, pad, False)


# ---------------------------------------------------------------------------
# SQL LIKE (%, _) — dynamic-programming match over the byte matrix
# ---------------------------------------------------------------------------

def _parse_like(pattern: str, escape: str = "\\"):
    toks = []
    i = 0
    while i < len(pattern):
        ch = pattern[i]
        if ch == escape and i + 1 < len(pattern):
            toks.append(("lit", pattern[i + 1].encode()))
            i += 2
        elif ch == "%":
            toks.append(("any", None))
            i += 1
        elif ch == "_":
            toks.append(("one", None))
            i += 1
        else:
            toks.append(("lit", ch.encode()))
            i += 1
    return tuple(toks)


def like(col: Column, pattern: str, escape: str = "\\") -> Column:
    """SQL LIKE — NFA over byte positions, one vectorized step per token.

    Note: ``_`` matches one *byte* here; multi-byte UTF-8 characters under
    ``_`` are a known divergence (cudf's like is byte-based too).
    """
    toks = _parse_like(pattern, escape)
    mat, lengths = to_padded_bytes(col)
    n, w = mat.shape
    # reach[i, j] — pattern prefix consumed exactly j bytes of row i
    reach = (jnp.arange(w + 1, dtype=_I32)[None, :] == 0)
    reach = jnp.broadcast_to(reach, (n, w + 1))
    inb = jnp.arange(w, dtype=_I32)[None, :] < lengths[:, None]
    for kind, lit in toks:
        if kind == "lit":
            for b in lit:  # multi-byte UTF-8 pattern chars consume per byte
                step = reach[:, :-1] & (mat == jnp.uint8(b)) & inb
                reach = jnp.pad(step, ((0, 0), (1, 0)))
        elif kind == "one":
            step = reach[:, :-1] & inb
            reach = jnp.pad(step, ((0, 0), (1, 0)))
        else:  # '%' — consume any number of bytes: prefix-or to the right
            reach = jax.lax.associative_scan(jnp.logical_or, reach, axis=1)
    hit = jnp.take_along_axis(reach, lengths[:, None], axis=1)[:, 0]
    return Column(BOOL8, data=hit.astype(jnp.uint8), validity=_prop_valid(col))
