"""Equi-joins: inner/left/right/full-outer/cross/left-semi/left-anti.

TPU-native replacement for cudf's hash joins (the SortMergeJoin/ShuffledHashJoin
targets in BASELINE.json configs[3]).  Open-addressing hash tables don't
vectorize on TPU; instead:

    1. key each side with xxhash64 over the join columns (ops/hash.py)
    2. sort the build side by hash (radix sort)
    3. merge-rank (sort + cumsum) -> candidate range [lo, hi) per probe row
    4. expand ranges to pairs via marker/filler sort + cummax forward fill
       (searchsorted binary search serializes on TPU — docs/PERF.md)
    5. verify true key equality per pair (hash collisions filtered exactly)

The expansion size is data-dependent (it IS the join cardinality), so pair
materialization host-syncs one scalar — the same place cudf returns its
gather-map size.  All heavy work is device-side sort/scan/gather.

Null join keys never match (SQL equi-join semantics), enforced by the
verification pass; null-safe equality (<=>) is ``null_equal=True``.

A prepared build (``PreparedBuild``, the fused chunk segment's join) is
probed by one of two methods, chosen by ``probe_method`` from the build's
row count (a static shape) and the key dtypes: small builds by a broadcast
compare of the keys themselves (``_probe_compare``: no hash, no sort, no
gather), larger ones by rank (``_probe_rank``) — where the build is keyed by
one integer column (no hash: its uniqueness is exact) one gather from a
direct-address table of its key span (``DIRECT_MAX_SLOTS``), else a
``searchsorted`` of the int64 keys themselves; any other build by the hash
merge-rank of steps 1-3 and 5.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import Column, Table
from ..dtypes import TypeId
from .hash import xxhash64
from .order import normalize_f64_bits, normalize_f32_bits
from .selection import gather_table
from .strings_common import to_padded_bytes
from ..utils.tracing import traced

_I32 = jnp.int32


def _key_table(table: Table, on) -> Table:
    return Table([table.column(k) for k in on])


def _pair_equal(lcol: Column, rcol: Column, li, ri, null_equal: bool):
    """Per-pair true equality of key values at rows (li, ri); ``li`` None:
    every left row, in order (row i pairs with ``ri[i]``)."""
    def at_l(a):
        return a if li is None else jnp.take(a, li, axis=0)

    lv = at_l(lcol.valid_mask())
    rv = jnp.take(rcol.valid_mask(), ri)
    if lcol.dtype.is_string:
        lmat, llen = to_padded_bytes(lcol)
        rmat, rlen = to_padded_bytes(rcol)
        w = max(lmat.shape[1], rmat.shape[1])
        lmat = jnp.pad(lmat, ((0, 0), (0, w - lmat.shape[1])))
        rmat = jnp.pad(rmat, ((0, 0), (0, w - rmat.shape[1])))
        eq = at_l(llen) == jnp.take(rlen, ri)
        eq = eq & (at_l(lmat) == jnp.take(rmat, ri, axis=0)).all(axis=1)
    elif lcol.dtype.id == TypeId.FLOAT64:
        # compare normalized bit patterns: -0.0 = 0.0, NaN matches NaN
        # (Spark join-key float normalization)
        ln = normalize_f64_bits(lcol.data.astype(jnp.uint64))
        rn = normalize_f64_bits(rcol.data.astype(jnp.uint64))
        eq = at_l(ln) == jnp.take(rn, ri)
    elif lcol.dtype.id == TypeId.FLOAT32:
        ln = normalize_f32_bits(jax.lax.bitcast_convert_type(
            jnp.asarray(lcol.data, jnp.float32), jnp.uint32))
        rn = normalize_f32_bits(jax.lax.bitcast_convert_type(
            jnp.asarray(rcol.data, jnp.float32), jnp.uint32))
        eq = at_l(ln) == jnp.take(rn, ri)
    else:
        eq = at_l(lcol.data) == jnp.take(rcol.data, ri)
    if null_equal:
        eq = jnp.where(lv & rv, eq, lv == rv)
    else:
        eq = eq & lv & rv
    return eq


_TAG = np.int64(1) << 32  # packs (tie tag, unsort index) into ONE operand


def _rank_bounds(ref, queries, ref_sorted=None) \
        -> tuple[jnp.ndarray, jnp.ndarray]:
    """(lo, hi) ranks: count of ``ref`` elements < / <= each query.

    The searchsorted replacement: TPU binary search serializes into ~20
    rounds of slow gathers (docs/PERF.md); a merge-rank is one sort of
    [queries, refs] + cumsum + one unsort.  Queries are NOT duplicated and
    both sorts carry exactly two operands: the tie tag and the unsort index
    share one packed int64 (tag in bit 32 — a query sorts before equal
    refs, so the ref prefix-count at a query position is its strict rank
    ``lo``).  ``hi`` then comes from equal-run lengths of the sorted refs
    (reverse-cummin run ends + two gathers), not a second merged sort.
    ``ref`` need not be sorted; pass ``ref_sorted`` if the caller already
    sorted it (``_probe_ranges`` shares its build-side sort).
    """
    nq, nr = queries.shape[0], ref.shape[0]
    vals = jnp.concatenate([queries, ref])
    c = jnp.concatenate([jnp.arange(nq, dtype=jnp.int64),
                         _TAG + jnp.arange(nr, dtype=jnp.int64)])
    _, sc = jax.lax.sort((vals, c), num_keys=2, is_stable=False)
    isref = sc >= _TAG
    crs = jnp.cumsum(isref.astype(jnp.int32))
    _, rank_q = jax.lax.sort((sc, crs), num_keys=1, is_stable=False)
    lo = rank_q[:nq]

    srt = jnp.sort(ref) if ref_sorted is None else ref_sorted
    idx = jnp.arange(nr, dtype=jnp.int32)
    if nr:
        is_last = jnp.concatenate([srt[1:] != srt[:-1],
                                   jnp.ones((1,), jnp.bool_)])
        run_end = jnp.flip(jax.lax.cummin(
            jnp.flip(jnp.where(is_last, idx, jnp.int32(nr)))))
        p = jnp.clip(lo, 0, nr - 1)
        match = (lo < nr) & (jnp.take(srt, p) == queries)
        hi = lo + jnp.where(match, jnp.take(run_end, p) - p + 1, 0)
    else:
        hi = lo
    return lo, hi


def _build_sort(rh):
    """The build-side half of the sorted-probe prelude: cast to the 32-bit
    rank domain and stable-sort once.  Factored out so ``PreparedBuild``
    can compute it once per execution and share it across probe chunks."""
    rh = rh.astype(_I32)
    rh_sorted, r_order = jax.lax.sort(
        (rh, jnp.arange(rh.shape[0], dtype=_I32)), num_keys=1,
        is_stable=True)
    return rh, rh_sorted, r_order


def _probe_ranges(lh, rh):
    """Sorted-probe prelude: one sort of the build side, per-probe ranges.

    Returns (r_order, lo, offsets, starts, expansion) where probe row i's
    candidates occupy sorted positions [lo, hi) recoverable from
    starts/offsets, and ``expansion`` is the total candidate-pair count.

    Ranking runs on the LOW 32 BITS of the hashes: int32 sort keys are
    markedly cheaper than int64, and a 32-bit collision between distinct
    64-bit hashes only widens a candidate range — the exact per-pair key
    verification downstream filters it, same as a full hash collision.
    """
    lh = lh.astype(_I32)
    rh, rh_sorted, r_order = _build_sort(rh)
    lo, hi = _rank_bounds(rh, lh, ref_sorted=rh_sorted)
    lo, hi = lo.astype(_I32), hi.astype(_I32)
    counts = (hi - lo).astype(jnp.int64)
    offsets = jnp.cumsum(counts)
    starts = offsets - counts
    expansion = offsets[-1] if counts.shape[0] else jnp.int64(0)
    return r_order, lo, offsets, starts, expansion


@jax.tree_util.register_pytree_node_class
class PreparedBuild:
    """Join build-side state reusable across probe chunks.

    Captures the build side's sorted rank domain (``rh_sorted``) and the
    build row at each of its positions (``r_order``) — plus the key and
    payload Tables the per-pair verify and output assembly gather from.
    The rank domain is one of two (``exact``):

    - the keys themselves, as int64 (``exact_keys``: ONE integer key
      column; a null key ranks as ``_NULL_KEY``): a probe key's rank finds
      the one build row that can hold it, and ``unique`` is exact.  A build
      the compare probe takes (``probe_method``) reads its keys as they
      are, and has no sorted domain (``rh_sorted`` / ``r_order`` None);
    - any other key (several columns, strings, floats): xxhash64 of the
      key columns cast to 32 bits (``rh``; dead rows replaced by even
      sentinels) — ``_probe_ranges``' domain, where two keys may share a
      hash and ``unique`` says the HASHES are distinct.

    Computed ONCE per join (cached in ``engine.cache.BUILD_CACHE`` across
    chunks/executions) where the naive streamed loop re-hashed and
    re-sorted the build side on every chunk.

    ``unique`` (host bool, the one sync ``prepare_build`` pays): every
    probe row has at most one candidate, which is what lets
    ``probe_join_prepared`` stay at probe-row shape with no expansion and
    no per-chunk sync.  Registered as a jax pytree so a prepared build
    crosses the jit boundary of a fused chunk program as ordinary traced
    inputs.

    ``direct`` (a unique exact build whose live keys span at most
    ``DIRECT_MAX_SLOTS``, else None): the int32 build row of each key
    ``kmin + i`` at slot ``i``, ``_NO_ROW`` where no live row holds it —
    ``kmin`` the smallest live key, a device scalar, so another build of
    the same slot count compiles nothing.
    """

    __slots__ = ("rk", "payload", "rh", "rh_sorted", "r_order",
                 "right_live", "unique", "nr", "exact", "direct", "kmin")

    def __init__(self, rk, payload, rh, rh_sorted, r_order, right_live,
                 unique, nr, exact=False, direct=None, kmin=None):
        self.rk = rk                  # build key Table
        self.payload = payload        # build Table for output gathers
        self.rh = rh                  # int32 hashes; None where exact
        self.rh_sorted = rh_sorted    # the sorted rank domain (or None)
        self.r_order = r_order        # build row at each sorted position
        self.right_live = right_live  # optional build row mask
        self.unique = unique          # host bool: <= 1 candidate a probe
        self.nr = nr
        self.exact = exact            # ranked on the keys themselves
        self.direct = direct          # direct-address table (or None)
        self.kmin = kmin              # int64 key at its slot 0

    def tree_flatten(self):
        return ((self.rk, self.payload, self.rh, self.rh_sorted,
                 self.r_order, self.right_live, self.direct, self.kmin),
                (self.unique, self.nr, self.exact))

    @classmethod
    def tree_unflatten(cls, aux, children):
        *arrays, direct, kmin = children
        return cls(*arrays, *aux, direct=direct, kmin=kmin)


#: a null key's place in the exact rank domain.  A live key of this value
#: beside a null one counts as a duplicate (``_exact_build_sort``,
#: ``_exact_unique``), so a
#: unique build holds at most one row here and the verify tells them apart.
_NULL_KEY = np.int64(np.iinfo(np.int64).min)


def exact_keys(key_cols) -> bool:
    """Is a build keyed by ``key_cols`` ranked on its keys themselves?  ONE
    integer column whose values an int64 holds (``DENSE_KEY_TYPES``); every
    other key is ranked by its 32-bit hash (``PreparedBuild``)."""
    from .aggregate import DENSE_KEY_TYPES
    return len(key_cols) == 1 and key_cols[0].dtype.id in DENSE_KEY_TYPES \
        and key_cols[0].data is not None and key_cols[0].data.ndim == 1


def _key_rank_domain(col: Column):
    """One key column as the exact rank domain: int64 values, a null as
    ``_NULL_KEY``."""
    k = col.data.astype(jnp.int64)
    return k if col.validity is None else jnp.where(col.validity, k,
                                                    _NULL_KEY)


def _live_keys(key: Column, right_live):
    """``(int64 keys, mask)``: the build rows a probe may match — live,
    with a non-null key."""
    ok = key.valid_mask()
    return key.data.astype(jnp.int64), \
        ok if right_live is None else ok & right_live


@jax.jit
def _exact_build_sort(key: Column, right_live):
    """A large exact build's one program: ``(sorted keys, build row at each
    position, unique, kmin, kmax)``.  Among equal keys live rows sort
    before dead ones, so a key's first position holds a live row wherever
    one has it; ``unique``: no key is held by two live rows; ``kmin`` /
    ``kmax``: the smallest and largest live non-null key (``kmin > kmax``
    where there is none)."""
    k, ok = _live_keys(key, right_live)
    kmin = jnp.min(jnp.where(ok, k, np.iinfo(np.int64).max))
    kmax = jnp.max(jnp.where(ok, k, np.iinfo(np.int64).min))
    ks = _key_rank_domain(key)
    row = jnp.arange(ks.shape[0], dtype=_I32)
    if right_live is None:
        ks, order = jax.lax.sort((ks, row), num_keys=1, is_stable=False)
        return ks, order, ~jnp.any(ks[1:] == ks[:-1]), kmin, kmax
    dead = (~right_live).astype(_I32)
    ks, dead, order = jax.lax.sort((ks, dead, row), num_keys=2,
                                   is_stable=False)
    twice = (ks[1:] == ks[:-1]) & (dead[1:] == 0) & (dead[:-1] == 0)
    return ks, order, ~jnp.any(twice), kmin, kmax


#: The most slots a direct-address table (``PreparedBuild.direct``) may
#: hold: 2^26 int32 slots are 256 MiB of HBM.  A unique exact build whose
#: live keys span more is probed by ``searchsorted``.
DIRECT_MAX_SLOTS = 1 << 26


def _direct_slots(kmin: int, kmax: int):
    """The slots of the direct-address table for live keys ``kmin`` ..
    ``kmax``: their span rounded up to a power of two (so builds of one
    size class share a program), or None where there is no key or the
    span passes ``DIRECT_MAX_SLOTS``."""
    span = kmax - kmin + 1
    if span < 1:
        return None
    slots = 1 << (span - 1).bit_length()
    return slots if slots <= DIRECT_MAX_SLOTS else None


@functools.partial(jax.jit, static_argnums=(2,))
def _direct_table(key: Column, right_live, slots: int, kmin):
    """The direct-address table of a unique exact build: slot ``key -
    kmin`` holds the key's live row, every other slot ``_NO_ROW``."""
    k, ok = _live_keys(key, right_live)
    at = jnp.where(ok, k - kmin, slots).astype(_I32)   # dropped where not ok
    return jnp.full((slots,), _NO_ROW, _I32).at[at].set(
        jnp.arange(k.shape[0], dtype=_I32), mode="drop")


@jax.jit
def _exact_unique(key: Column, right_live):
    """A small exact build's ``unique``, from every pair of its rows: no
    two live rows hold one key.  The compare probe reads the keys
    themselves, so such a build needs no sorted domain."""
    ks = _key_rank_domain(key)
    row = jnp.arange(ks.shape[0], dtype=_I32)
    pair = (ks[:, None] == ks[None, :]) & (row[:, None] < row[None, :])
    if right_live is not None:
        pair = pair & right_live[:, None] & right_live[None, :]
    return ~jnp.any(pair)


def prepare_build(right: Table, on_right, right_live=None,
                  payload: Table | None = None) -> PreparedBuild:
    """Rank and sort the join build side once; see ``PreparedBuild``.

    ``payload`` defaults to ``right`` itself (inner-join output columns);
    pass a pruned Table to bound what fused programs carry.  One host sync
    (the ``unique`` scalar, with the key range of an exact build) per call
    — never per probe chunk.
    """
    rk = _key_table(right, on_right)
    payload = right if payload is None else payload
    if exact_keys(rk.columns):
        key = rk.columns[0]
        nr = int(key.data.shape[0])
        if probe_method(nr, rk.columns) == "compare":
            return PreparedBuild(rk, payload, None, None, None, right_live,
                                 bool(_exact_unique(key, right_live)), nr,
                                 exact=True)
        ks, r_order, unique, kmin, kmax = _exact_build_sort(key, right_live)
        unique, lo, hi = (x.item() for x in jax.device_get((unique, kmin,
                                                            kmax)))
        slots = _direct_slots(lo, hi) if unique else None
        direct = None if slots is None else \
            _direct_table(key, right_live, slots, kmin)
        return PreparedBuild(rk, payload, None, ks, r_order, right_live,
                             bool(unique), nr, exact=True, direct=direct,
                             kmin=None if direct is None else kmin)
    rh = xxhash64(rk).data
    if right_live is not None:
        iota = jnp.arange(rh.shape[0], dtype=rh.dtype)
        rh = jnp.where(right_live, rh, iota * 2)  # even sentinels
    rh32, rh_sorted, r_order = _build_sort(rh)
    nr = int(rh32.shape[0])
    unique = True if nr <= 1 else \
        bool(jnp.all(rh_sorted[1:] != rh_sorted[:-1]))
    return PreparedBuild(rk, payload, rh32, rh_sorted, r_order, right_live,
                         unique, nr)


#: A prepared build of at most this many rows is probed by comparing the
#: keys themselves (``_probe_compare``); above it the rank probe runs.
#: The compare's work grows with ``nl * nr``, the rank's does not grow with
#: ``nr``; the chip sweep that places the crossover is in PERF.md section 6
#: (PR 31).
PROBE_COMPARE_MAX_BUILD = 8192

_NO_ROW = np.int32(np.iinfo(np.int32).max)  # "no build row": above any nr


def _compare_ok(col: Column) -> bool:
    """A column the compare path can read: 1-D fixed-width storage (what
    ``engine.segment.stream_runtime_eligible`` demands of a fused join)."""
    return not col.dtype.is_string and col.data is not None \
        and col.data.ndim == 1


def probe_method(nr: int, key_cols) -> str:
    """``"compare"`` or ``"rank"``: how ``probe_join_prepared`` looks a
    probe row up in a prepared build of ``nr`` rows.  Reads the build's row
    count (a static shape) and the key columns' storage, nothing else: one
    algorithm that wants a different method by size."""
    if nr <= PROBE_COMPARE_MAX_BUILD and all(_compare_ok(c) for c in key_cols):
        return "compare"
    return "rank"


def _words32(data) -> list:
    """A 1-D fixed-width buffer as uint32 word arrays (most significant
    first) that hold its bit pattern: the chip emulates 64-bit lanes, so
    every per-pair compare and reduce below runs on 32-bit words.  (BOOL8
    storage is uint8: no column buffer is ``bool``.)"""
    size = data.dtype.itemsize
    if size == 8:
        u = data.astype(jnp.uint64)   # int64 -> uint64 wraps: bits kept
        return [(u >> np.uint64(32)).astype(jnp.uint32),
                u.astype(jnp.uint32)]
    u = jax.lax.bitcast_convert_type(
        data, {4: jnp.uint32, 2: jnp.uint16, 1: jnp.uint8}[size])
    return [u.astype(jnp.uint32)]


def _from_words32(words: list, dtype):
    """Inverse of ``_words32`` for a buffer of ``dtype``."""
    dtype = jnp.dtype(dtype)
    if dtype.itemsize == 8:
        u = (words[0].astype(jnp.uint64) << np.uint64(32)) \
            | words[1].astype(jnp.uint64)
        return u.astype(dtype)
    u = words[0].astype({4: jnp.uint32, 2: jnp.uint16,
                         1: jnp.uint8}[dtype.itemsize])
    return jax.lax.bitcast_convert_type(u, dtype)


def _key_words(lcol: Column, rcol: Column) -> tuple:
    """(left words, right words) whose word-wise equality is
    ``_pair_equal``'s value equality: normalized bits for floats (-0.0 =
    0.0, NaN = NaN), the promoted integer value otherwise."""
    if lcol.dtype.id == TypeId.FLOAT64:
        ld = normalize_f64_bits(lcol.data.astype(jnp.uint64))
        rd = normalize_f64_bits(rcol.data.astype(jnp.uint64))
    elif lcol.dtype.id == TypeId.FLOAT32:
        ld = normalize_f32_bits(jax.lax.bitcast_convert_type(
            jnp.asarray(lcol.data, jnp.float32), jnp.uint32))
        rd = normalize_f32_bits(jax.lax.bitcast_convert_type(
            jnp.asarray(rcol.data, jnp.float32), jnp.uint32))
    else:
        wide = jnp.promote_types(lcol.data.dtype, rcol.data.dtype)
        ld, rd = lcol.data.astype(wide), rcol.data.astype(wide)
    return _words32(ld), _words32(rd)


def _compare_matrix(left_keys: Table, rk: Table, left_live, right_live,
                    null_equal: bool):
    """``(eq, llive, rlive)``: the ``[nr, nl]`` key equality of every build
    row with every probe row — ``_pair_equal``'s null and float rules, in
    full width — and the row masks with the null keys folded in (a null key
    matches nothing unless ``null_equal``).  A broadcast compare the
    reduces below fuse with: it is never materialized."""
    eq = None                    # [nr, nl]
    llive, rlive = left_live, right_live
    for lc, rc in zip(left_keys.columns, rk.columns):
        lw, rw = _key_words(lc, rc)
        e = None
        for a, b in zip(lw, rw):
            w = b[:, None] == a[None, :]
            e = w if e is None else e & w
        lv, rv = lc.validity, rc.validity
        if null_equal:
            if lv is not None or rv is not None:
                lv = lc.valid_mask()[None, :]
                rv = rc.valid_mask()[:, None]
                e = (e & lv & rv) | ~(lv | rv)
        else:       # a null key matches nothing: fold it into the row masks
            if lv is not None:
                llive = lv if llive is None else llive & lv
            if rv is not None:
                rlive = rv if rlive is None else rlive & rv
        eq = e if eq is None else eq & e
    return eq, llive, rlive


@traced("probe_compare")
def _probe_compare(left_keys: Table, pb: "PreparedBuild", left_live,
                   null_equal: bool):
    """``probe_join_prepared`` for a small build: compare every probe key
    with every build key and reduce, ``ri[i] = min{j : key_l[i] == key_r[j],
    build row j live}``.  The ``[nr, nl]`` match is one fused
    broadcast-compare-reduce — probe rows along the lanes, build rows along
    the reduced axis — that is never materialized.  Exact by construction:
    the keys themselves are tested, in full width, with ``_pair_equal``'s
    null and float rules; no hash, no sort, no gather."""
    nr = pb.nr
    eq, llive, rlive = _compare_matrix(left_keys, pb.rk, left_live,
                                       pb.right_live, null_equal)
    cand = jnp.arange(nr, dtype=_I32)
    if rlive is not None:
        cand = jnp.where(rlive, cand, _NO_ROW)
    ri = jnp.min(jnp.where(eq, cand[:, None], _NO_ROW), axis=0)
    matched = ri < nr
    if llive is not None:
        matched = matched & llive
    return jnp.where(matched, ri, 0), matched


def probe_padded(left_keys: Table, right_keys: Table, left_live,
                 right_live):
    """Probe a padded build that nobody prepared, at probe-row shape and
    inside one program: ``(ri, matched, spill)`` — the int32 build row of
    every live probe row that has one, the match mask, and how many
    candidates that shape could not hold (``spill`` > 0: some probe row has
    more than one, so an inner join's output would outgrow the probe side
    and the caller must take another path).  Both sides are padded Tables of
    key columns under live masks; a null key matches nothing.

    ``probe_method``'s two methods, chosen by the build's slot count: the
    broadcast compare counts the live build rows that equal each probe key;
    above ``PROBE_COMPARE_MAX_BUILD`` it is ``inner_join_padded`` at a
    capacity of one pair per probe row, whose overflow is the spill (a
    second candidate by the 32-bit hash counts too: it is an upper bound)."""
    nl, nr = left_keys.num_rows, right_keys.num_rows
    if probe_method(nr, list(left_keys.columns)
                    + list(right_keys.columns)) == "compare":
        with jax.named_scope("probe_compare"):
            eq, llive, rlive = _compare_matrix(left_keys, right_keys,
                                               left_live, right_live, False)
            cand = jnp.arange(nr, dtype=_I32)
            if rlive is not None:
                eq = eq & rlive[:, None]
            hits = jnp.sum(eq, axis=0, dtype=_I32)
            ri = jnp.min(jnp.where(eq, cand[:, None], _NO_ROW), axis=0)
            matched = hits > 0
            if llive is not None:
                matched = matched & llive
            spill = jnp.sum(jnp.where(matched, hits - 1, 0), dtype=jnp.int64)
            return jnp.where(matched, ri, 0), matched, spill
    names = [f"k{i}" for i in range(left_keys.num_columns)]
    _li, ri, eq, _npairs, overflow = inner_join_padded(
        Table(left_keys.columns, names), Table(right_keys.columns, names),
        names, names, capacity=nl, left_live=left_live,
        right_live=right_live, pack=False)
    return ri, eq, overflow.astype(jnp.int64)


@traced("probe_compare")
def select_build_rows(col: Column, ri) -> Column:
    """``gather_column(col, ri)`` for a small build column without the
    gather: each output row is the masked sum of the column's bit pattern
    over the one-hot ``ri[i] == j`` — one term at most, so int64 extremes,
    float64 ``-0.0`` and NaN payloads come out bit for bit.  An ``ri``
    outside the column gives a null row, as the gather does."""
    onehot = jnp.arange(col.data.shape[0], dtype=_I32)[:, None] \
        == ri[None, :]

    def pick(words):
        return [jnp.sum(jnp.where(onehot, w[:, None], np.uint32(0)), axis=0,
                        dtype=jnp.uint32) for w in words]

    data = _from_words32(pick(_words32(col.data)), col.data.dtype)
    valid = pick([col.valid_mask().astype(jnp.uint32)])[0] != 0
    return Column(col.dtype, data=data, validity=valid)


def probe_join_prepared(left_keys: Table, pb: PreparedBuild,
                        left_live=None, null_equal: bool = False):
    """Probe a ``PreparedBuild``: masked gather map + match mask per row.

    Requires ``pb.unique`` (no two live build rows share a place in the
    rank domain), so each probe row has at most ONE candidate and the
    result stays at probe-row shape — no expansion sort, fully jit-able,
    zero host syncs.  Returns ``(ri, matched)``: the int32 build row per
    probe row (arbitrary where unmatched — mask before trusting it) and the
    bool match mask.  ``null_equal=True`` is null-safe equality (``<=>``);
    default SQL semantics never match null keys.

    Two methods, one meaning (``probe_method``): a small build is probed by
    ``_probe_compare``, a larger one by rank (``_probe_rank``).
    """
    nl = left_keys.num_rows
    if pb.nr == 0:
        return jnp.zeros((nl,), _I32), jnp.zeros((nl,), jnp.bool_)
    if probe_method(pb.nr, list(left_keys.columns)
                    + list(pb.rk.columns)) == "compare":
        return _probe_compare(left_keys, pb, left_live, null_equal)
    return _probe_rank(left_keys, pb, left_live, null_equal)


@traced("probe_rank")
def _probe_rank(left_keys: Table, pb: PreparedBuild, left_live,
                null_equal: bool):
    """``probe_join_prepared`` for a larger build: each probe key names the
    one build row that can hold it, and that row's key is verified against
    the probe's.  An exact build with a direct-address table
    (``pb.direct``) reads the row at the key's offset from ``pb.kmin``:
    one gather (a null key, under ``null_equal``, ranks instead).  Another
    exact build (``pb.exact``) looks the int64 key itself up in its sorted
    keys (``jnp.searchsorted``: no sort in the program): the row at the
    rank is the first of the key's run, live where one is.  A hashed build
    merge-ranks the 32-bit hashes (``_rank_bounds``; dead probe rows given
    odd sentinels) and asks first that the run is not empty."""
    nl = left_keys.num_rows
    direct = pb.direct is not None and not null_equal
    if direct:
        lc = left_keys.columns[0]
        slots = pb.direct.shape[0]
        off = lc.data.astype(jnp.int64) - pb.kmin
        eq = lc.valid_mask() & (off >= 0) & (off < slots)
        ri = jnp.take(pb.direct, jnp.clip(off, 0, slots - 1).astype(_I32))
        eq = eq & (ri != _NO_ROW)
        ri = jnp.where(eq, ri, 0)
    elif pb.exact:
        lo = jnp.searchsorted(pb.rh_sorted,
                              _key_rank_domain(left_keys.columns[0]),
                              side="left")
        eq = True
    else:
        lh = xxhash64(left_keys).data
        if left_live is not None:
            iota = jnp.arange(nl, dtype=lh.dtype)
            lh = jnp.where(left_live, lh, iota * 2 + 1)  # odd sentinels
        lo, hi = _rank_bounds(pb.rh, lh.astype(_I32),
                              ref_sorted=pb.rh_sorted)
        eq = hi > lo
    if not direct:
        ri = jnp.take(pb.r_order,
                      jnp.clip(lo, 0, pb.nr - 1).astype(_I32)).astype(_I32)
    for lc, rc in zip(left_keys.columns, pb.rk.columns):
        eq = eq & _pair_equal(lc, rc, None, ri, null_equal=null_equal)
    if pb.right_live is not None:
        eq = eq & jnp.take(pb.right_live, ri)
    if left_live is not None:
        eq = eq & left_live
    return ri, eq


def _expand_pairs(r_order, lo, offsets, starts, nl, nr, total):
    """Enumerate candidate pairs 0..total over precomputed probe ranges.

    ``total`` may be a host int (exact size) or a static capacity; pairs
    beyond the true expansion get in_range=False.

    Gather-free run inversion: probe rows with candidates become markers at
    their run-start slot (unique), materialized against one filler per slot
    by a keyed first-occurrence sort (the same trick as the shuffle's bucket
    pack), then ``cummax`` forward-fills the run owner — both the probe-row
    index and the run start are monotone in the slot index.
    """
    if nl == 0:
        z = jnp.zeros((total,), _I32)
        return z, z, jnp.zeros((total,), jnp.bool_)
    assert total < 2**31 - 2, "pair capacity exceeds int32 slot ids"
    # slot ids fit int32 (capacities are way under 2^31); run starts at or
    # beyond the capacity can't own a slot, so clamping them to the filler
    # key keeps the int32 range safe even when the true expansion overflows
    j = jnp.arange(total, dtype=_I32)
    counts = offsets - starts
    # merge run-start markers (probe rows with candidates, at their start
    # slot) against the slot ids; a run starting at j owns slot j, so
    # markers tag-sort BEFORE equal slots.  The carried probe-row index is
    # monotone along sorted markers (starts is strictly increasing over
    # counts>0 rows), so one cummax forward-fills each slot's owner; the
    # run start is then a gather of ``starts`` — no second marker sort, no
    # third operand.
    mark_key = jnp.where((counts > 0) & (starts <= total), starts,
                         jnp.int64(total + 1)).astype(_I32)
    vals = jnp.concatenate([mark_key, j])
    c = jnp.concatenate([jnp.arange(nl, dtype=jnp.int64),
                         _TAG + j.astype(jnp.int64)])
    _, sc = jax.lax.sort((vals, c), num_keys=2, is_stable=False)
    owner = jax.lax.cummax(jnp.where(sc < _TAG, sc.astype(_I32),
                                     jnp.int32(-1)))
    _, own_q = jax.lax.sort((sc, owner), num_keys=1, is_stable=False)
    li = own_q[nl:]
    in_range = (li >= 0) & (j < offsets[-1])
    li = jnp.clip(li, 0, max(nl - 1, 0))
    within = (j - jnp.take(starts, li)).astype(_I32)
    ri_sorted_pos = jnp.clip(jnp.take(lo, li) + within, 0, max(nr - 1, 0))
    ri = jnp.take(r_order, ri_sorted_pos).astype(_I32)
    return li, ri, in_range


@jax.jit
def _probe_stage(lk: Table, rk: Table):
    """Stage 1 as ONE compiled program (eager per-op dispatch costs a
    network round trip per op on remotely-attached devices)."""
    lh = xxhash64(lk).data
    rh = xxhash64(rk).data
    return (lh, rh) + _probe_ranges(lh, rh)


@functools.partial(jax.jit, static_argnums=(0,))
def _expand_verify_stage(capacity: int, probe, lk: Table, rk: Table):
    """Stage 2: enumerate candidate pairs + verify key equality.

    ``capacity`` is the static pair bound — callers round the true
    expansion up to a power of two so join cardinality (data-dependent)
    costs at most log2 distinct XLA compilations, not one per size."""
    lh, rh, r_order, lo, offsets, starts, _ = probe
    li, ri, in_range = _expand_pairs(r_order, lo, offsets, starts,
                                     lh.shape[0], rh.shape[0], capacity)
    eq = in_range
    for lc, rc in zip(lk.columns, rk.columns):
        eq = eq & _pair_equal(lc, rc, li, ri, null_equal=False)
    return li, ri, eq, jnp.sum(eq.astype(jnp.int64))


def _candidates(left: Table, right: Table, on_left, on_right):
    """Device candidate pairs + host pair count; returns (li, ri, eq, lk, rk).

    The expansion size is the hash-collision join cardinality — one host
    scalar sync, the same place cudf returns its gather-map size.
    """
    lk = _key_table(left, on_left)
    rk = _key_table(right, on_right)
    # string keys size their padded matrices on the host (to_padded_bytes),
    # so the string path runs its stages eagerly (either side may be the
    # string one, e.g. joining an empty untyped partition against strings)
    has_string = any(c.dtype.is_string
                     for c in list(lk.columns) + list(rk.columns))
    if has_string:
        lh = xxhash64(lk).data
        rh = xxhash64(rk).data
        probe = (lh, rh) + _probe_ranges(lh, rh)
    else:
        probe = _probe_stage(lk, rk)
    total = int(probe[-1]) if left.num_rows else 0

    if total == 0:
        z = jnp.zeros((0,), _I32)
        return z, z, jnp.zeros((0,), jnp.bool_), lk, rk

    if has_string:
        lh, rh, r_order, lo, offsets, starts, _ = probe
        li, ri, _ = _expand_pairs(r_order, lo, offsets, starts,
                                  lh.shape[0], rh.shape[0], total)
        eq = jnp.ones((total,), jnp.bool_)
        for lc, rc in zip(lk.columns, rk.columns):
            eq = eq & _pair_equal(lc, rc, li, ri, null_equal=False)
        return li, ri, eq, lk, rk

    cap = 1 << max(4, (total - 1).bit_length())
    li, ri, eq, _ = _expand_verify_stage(cap, probe, lk, rk)
    return li, ri, eq, lk, rk


def _compact_pairs(li, ri, eq):
    """Keep true-equal pairs; device compaction, one scalar host sync."""
    from .selection import nonzero_indices
    sel = nonzero_indices(eq)
    return jnp.take(li, sel), jnp.take(ri, sel)


@traced("inner_join")
def inner_join(left: Table, right: Table, on_left, on_right=None,
               suffixes=("", "_r")) -> Table:
    """Inner equi-join; returns left columns then right non-key columns."""
    on_right = on_right or on_left
    li, ri, eq, _, _ = _candidates(left, right, on_left, on_right)
    li, ri = _compact_pairs(li, ri, eq)
    return _assemble(left, right, li, ri, on_left, on_right, suffixes,
                     right_valid=None)


def inner_join_padded(left: Table, right: Table, on_left, on_right,
                      capacity: int, left_live=None, right_live=None,
                      pack: bool = True):
    """Fully jit-able inner join at a static pair capacity.

    Returns (li, ri, live, npairs, overflow): int32 pair indices padded to
    ``capacity`` with a live mask, the live pair count, and the count of
    candidate pairs that didn't fit (an upper bound on lost true pairs).
    ``pack=False`` skips the front-packing compaction sort and returns the
    pairs in candidate order with ``live`` as an arbitrary-position mask —
    for callers that filter by mask anyway (the distributed join's host
    compaction), the pack is a pure capacity-sized sort wasted.
    The building block for shard-local joins inside pjit/shard_map
    (distributed SortMergeJoin) where XLA needs static shapes — the
    role the 2^31-byte batch split plays in the reference
    (row_conversion.cu:476-511): a tunable static bound with overflow
    *counted*, never silently dropped.

    ``left_live``/``right_live``: row masks for padded inputs (e.g. rows
    out of a shuffle exchange).  Dead rows never produce pairs, and their
    hashes are replaced with per-row sentinels so a block of dead rows
    can't explode the candidate expansion against itself.
    """
    on_right = on_right or on_left
    lk = _key_table(left, on_left)
    rk = _key_table(right, on_right)
    lh = xxhash64(lk).data
    rh = xxhash64(rk).data
    if left_live is not None:
        iota = jnp.arange(lh.shape[0], dtype=lh.dtype)
        lh = jnp.where(left_live, lh, iota * 2 + 1)  # odd sentinels
    if right_live is not None:
        iota = jnp.arange(rh.shape[0], dtype=rh.dtype)
        rh = jnp.where(right_live, rh, iota * 2)     # even sentinels
    r_order, lo, offsets, starts, expansion = _probe_ranges(lh, rh)
    nl, nr = lh.shape[0], rh.shape[0]
    if capacity >= nl:
        # FK fast path: each probe row's FIRST candidate is a direct pair
        # (slot i = probe row i — no enumeration sorts), and only the
        # surplus candidates from duplicate-key runs ride the expansion
        # machinery, at the leftover capacity.  For unique build keys (the
        # dominant join shape) the expansion side is structurally empty.
        counts = offsets - starts
        iota = jnp.arange(nl, dtype=_I32)
        ri_d = jnp.take(r_order,
                        jnp.clip(lo, 0, max(nr - 1, 0)).astype(_I32))
        dir_ok = counts > 0
        xcounts = jnp.maximum(counts - 1, 0)
        xoffsets = jnp.cumsum(xcounts)
        xstarts = xoffsets - xcounts
        xcap = capacity - nl
        if xcap > 0:
            li_x, ri_x, ok_x = _expand_pairs(
                r_order, (lo + 1).astype(lo.dtype), xoffsets, xstarts,
                nl, nr, xcap)
            li = jnp.concatenate([iota, li_x])
            ri = jnp.concatenate([ri_d, ri_x])
            in_range = jnp.concatenate([dir_ok, ok_x])
        else:
            li, ri, in_range = iota, ri_d, dir_ok
        # surplus candidates that didn't fit the extra slots are lost even
        # when nl-side direct slots sit dead, so overflow counts extras
        xtotal = xoffsets[-1] if nl else jnp.int64(0)
        overflow = jnp.maximum(xtotal - xcap, 0)
    else:
        li, ri, in_range = _expand_pairs(r_order, lo, offsets, starts,
                                         nl, nr, capacity)
        overflow = jnp.maximum(expansion - capacity, 0)
    eq = in_range
    if left_live is not None:
        eq = eq & jnp.take(left_live, li)
    if right_live is not None:
        eq = eq & jnp.take(right_live, ri)
    for lc, rc in zip(lk.columns, rk.columns):
        eq = eq & _pair_equal(lc, rc, li, ri, null_equal=False)
    # candidate pairs beyond capacity can't be equality-checked at static
    # shape; ``overflow`` (set per path above) is their count — a superset
    # bound on lost true pairs
    npairs = jnp.sum(eq.astype(jnp.int32))
    if not pack:
        return li, ri, eq, npairs, overflow
    from .selection import nonzero_indices
    order = nonzero_indices(eq, count=capacity)
    live = jnp.arange(capacity, dtype=jnp.int32) < npairs
    return (jnp.take(li, order), jnp.take(ri, order), live, npairs, overflow)


@traced("left_join")
def left_join(left: Table, right: Table, on_left, on_right=None,
              suffixes=("", "_r")) -> Table:
    on_right = on_right or on_left
    li, ri, eq, _, _ = _candidates(left, right, on_left, on_right)
    from .selection import nonzero_indices
    matched_rows = jnp.zeros((left.num_rows,), jnp.bool_)
    if li.shape[0]:
        matched_rows = matched_rows.at[li].max(eq)
    li_m, ri_m = _compact_pairs(li, ri, eq)
    un = nonzero_indices(~matched_rows)
    li_all = jnp.concatenate([li_m, un]).astype(_I32)
    ri_all = jnp.concatenate([ri_m, jnp.full(un.shape, -1, _I32)])
    return _assemble(left, right, li_all, ri_all, on_left, on_right, suffixes,
                     right_valid=ri_all >= 0)


@traced("right_join")
def right_join(left: Table, right: Table, on_left, on_right=None,
               suffixes=("", "_r")) -> Table:
    """Right outer equi-join (cudf::right_join role, SURVEY §2.2).

    Output shape follows the engine convention (left columns then right
    non-key columns); key columns are coalesced so unmatched right rows
    carry the right side's key values, matching the pandas/Spark oracle."""
    from .selection import nonzero_indices
    on_right = on_right or on_left
    li, ri, eq, _, _ = _candidates(left, right, on_left, on_right)
    matched_r = jnp.zeros((right.num_rows,), jnp.bool_)
    if ri.shape[0]:
        matched_r = matched_r.at[ri].max(eq)
    li_m, ri_m = _compact_pairs(li, ri, eq)
    un = nonzero_indices(~matched_r)
    li_all = jnp.concatenate([li_m, jnp.full(un.shape, -1, _I32)])
    ri_all = jnp.concatenate([ri_m, un]).astype(_I32)
    return _assemble_outer(left, right, li_all, ri_all, on_left, on_right,
                           suffixes, left_valid=li_all >= 0, right_valid=None)


@traced("full_join")
def full_join(left: Table, right: Table, on_left, on_right=None,
              suffixes=("", "_r")) -> Table:
    """Full outer equi-join (cudf::full_join role, SURVEY §2.2): matched
    pairs, then unmatched left rows (right side null), then unmatched right
    rows (left side null, keys coalesced from the right)."""
    from .selection import nonzero_indices
    on_right = on_right or on_left
    li, ri, eq, _, _ = _candidates(left, right, on_left, on_right)
    matched_l = jnp.zeros((left.num_rows,), jnp.bool_)
    matched_r = jnp.zeros((right.num_rows,), jnp.bool_)
    if li.shape[0]:
        matched_l = matched_l.at[li].max(eq)
        matched_r = matched_r.at[ri].max(eq)
    li_m, ri_m = _compact_pairs(li, ri, eq)
    ul = nonzero_indices(~matched_l)
    ur = nonzero_indices(~matched_r)
    li_all = jnp.concatenate(
        [li_m, ul, jnp.full(ur.shape, -1, _I32)]).astype(_I32)
    ri_all = jnp.concatenate(
        [ri_m, jnp.full(ul.shape, -1, _I32), ur]).astype(_I32)
    return _assemble_outer(left, right, li_all, ri_all, on_left, on_right,
                           suffixes, left_valid=li_all >= 0,
                           right_valid=ri_all >= 0)


@traced("cross_join")
def cross_join(left: Table, right: Table, suffixes=("", "_r")) -> Table:
    """Cartesian product (cudf::cross_join role): every left row paired with
    every right row, left-major order; all columns of both sides kept."""
    nl, nr = left.num_rows, right.num_rows
    li = jnp.repeat(jnp.arange(nl, dtype=_I32), nr)
    ri = jnp.tile(jnp.arange(nr, dtype=_I32), nl)
    return _assemble(left, right, li, ri, (), (), suffixes, right_valid=None)


def _distinct_reps(table: Table, on):
    """(representative-row index array, group id per row) for the key columns.

    Bounds semi/anti work by |distinct keys| instead of join cardinality —
    with a hot key, the candidate expansion over raw rows would be quadratic.
    Device-side throughout; one host sync for the distinct-key count.
    """
    from .order import SortKey, encode_keys, rows_differ_from_prev
    from .selection import nonzero_indices
    keys = [SortKey(table.column(k)) for k in on]
    words = encode_keys(keys)
    order = jnp.lexsort(tuple(reversed(words)))
    bounds = rows_differ_from_prev(words, order)
    seg = jnp.cumsum(bounds.astype(_I32)) - 1
    n = order.shape[0]
    seg_of_row = jnp.zeros((n,), _I32).at[order].set(seg)
    reps = jnp.take(order, nonzero_indices(bounds)).astype(_I32)
    return reps, seg_of_row


def _matched_left_rows(left: Table, right: Table, on_left, on_right):
    lreps, lseg_of_row = _distinct_reps(left, on_left)
    rreps, _ = _distinct_reps(right, on_right)
    knames = [f"k{i}" for i in range(len(on_left))]
    lrep_t = gather_table(Table([left.column(k) for k in on_left], knames),
                          lreps)
    rrep_t = gather_table(Table([right.column(k) for k in on_right], knames),
                          rreps)
    li, ri, eq, _, _ = _candidates(lrep_t, rrep_t, knames, knames)
    matched_unique = jnp.zeros((lreps.shape[0],), jnp.bool_)
    if li.shape[0]:
        matched_unique = matched_unique.at[li].max(eq)
    return jnp.take(matched_unique, lseg_of_row)


@traced("left_semi_join")
def left_semi_join(left: Table, right: Table, on_left, on_right=None) -> Table:
    from .selection import nonzero_indices
    on_right = on_right or on_left
    matched = _matched_left_rows(left, right, on_left, on_right)
    return gather_table(left, nonzero_indices(matched))


@traced("left_anti_join")
def left_anti_join(left: Table, right: Table, on_left, on_right=None) -> Table:
    from .selection import nonzero_indices
    on_right = on_right or on_left
    matched = _matched_left_rows(left, right, on_left, on_right)
    return gather_table(left, nonzero_indices(~matched))


def _assemble(left, right, li, ri, on_left, on_right, suffixes, right_valid):
    on_r = tuple(on_right) if isinstance(on_right, (list, tuple)) else on_right
    if any(c.dtype.is_string or c.dtype.is_nested for c in
           list(left.columns) + list(right.columns)):
        # string/nested gathers size ragged output on the host -> eager
        return _assemble_body(left, right, li, ri, on_r, tuple(suffixes),
                              right_valid)
    return _assemble_jit(left, right, li, ri, on_r, tuple(suffixes),
                         right_valid)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _assemble_jit(left, right, li, ri, on_right, suffixes, right_valid):
    return _assemble_body(left, right, li, ri, on_right, suffixes,
                          right_valid)


def _assemble_body(left, right, li, ri, on_right, suffixes, right_valid):
    lcols = gather_table(left, li)
    rnames = right.names or [f"c{i}" for i in range(right.num_columns)]
    keep_r = [i for i, nm in enumerate(rnames)
              if not (isinstance(on_right, tuple) and nm in on_right)]
    rsub = Table([right.columns[i] for i in keep_r],
                 [rnames[i] for i in keep_r])
    rcols = gather_table(rsub, ri, indices_valid=right_valid)
    lnames = lcols.names or [f"l{i}" for i in range(lcols.num_columns)]
    names = list(lnames) + [
        nm + (suffixes[1] if nm in lnames else "") for nm in rsub.names]
    return Table(list(lcols.columns) + list(rcols.columns), names)


def _assemble_outer(left, right, li, ri, on_left, on_right, suffixes,
                    left_valid, right_valid):
    """Assemble an outer join where either side's row index may be -1.

    Key columns are coalesced — a row missing on the left takes the right
    side's key value (concat + single gather so STRING/nested keys work the
    same as fixed-width)."""
    from .selection import gather_column, _concat_columns
    on_left = list(on_left)
    on_right = list(on_right if on_right is not None else on_left)
    lnames = list(left.names or [f"l{i}" for i in range(left.num_columns)])
    rnames = list(right.names or [f"c{i}" for i in range(right.num_columns)])
    nl = left.num_rows
    out_cols, out_names = [], []
    for nm, col in zip(lnames, left.columns):
        if nm in on_left and left_valid is not None:
            rk = right.column(on_right[on_left.index(nm)])
            both = _concat_columns([col, rk])
            idx = jnp.where(left_valid, jnp.clip(li, 0, max(nl - 1, 0)),
                            nl + jnp.clip(ri, 0, max(right.num_rows - 1, 0)))
            out_cols.append(gather_column(both, idx))
        else:
            out_cols.append(gather_column(col, jnp.clip(li, 0, max(nl - 1, 0)),
                                          indices_valid=left_valid))
        out_names.append(nm)
    for nm, col in zip(rnames, right.columns):
        if nm in on_right:
            continue
        out_cols.append(gather_column(
            col, jnp.clip(ri, 0, max(right.num_rows - 1, 0)),
            indices_valid=right_valid))
        out_names.append(nm + (suffixes[1] if nm in lnames else ""))
    return Table(out_cols, out_names)


@traced("sort_merge_join")
def sort_merge_join(left: Table, right: Table, on_left, on_right=None,
                    how: str = "inner") -> Table:
    """SortMergeJoin surface: the exchange plans in BASELINE.json configs[3]
    name this; physically the same sorted-probe expansion as inner_join."""
    on_right = on_right or on_left
    if how == "inner":
        return inner_join(left, right, on_left, on_right)
    if how == "left":
        return left_join(left, right, on_left, on_right)
    if how == "right":
        return right_join(left, right, on_left, on_right)
    if how in ("full", "outer", "full_outer"):
        return full_join(left, right, on_left, on_right)
    if how == "cross":
        return cross_join(left, right)
    if how == "semi":
        return left_semi_join(left, right, on_left, on_right)
    if how == "anti":
        return left_anti_join(left, right, on_left, on_right)
    raise ValueError(f"unsupported join type {how!r}")
