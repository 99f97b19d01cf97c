"""GroupBy aggregation: sort-based segmented reduction.

The TPU-native answer to cudf's hash_groupby (what the reference's Spark plans
call HashAggregate — BASELINE.json configs[2]).  A hash table with open
addressing is a pointer-chasing structure XLA can't vectorize; sorting by the
group keys and running segmented reductions is the same O(n log n) work
expressed as radix sort + scans, which map perfectly onto the VPU:

    1. order  = lexsort(key encodings)          (ops/order.py)
    2. bounds = sorted row != previous row      (rows_differ_from_prev)
    3. seg_id = cumsum(bounds) - 1
    4. each aggregation = jax.ops.segment_<op>(values[order], seg_id)

``groupby_padded`` is the fully jit-able core: output padded to n rows with a
group-count scalar (static shapes for pjit pipelines — the distributed
partial-aggregation path).  ``groupby`` compacts at the host boundary.
``groupby_dense`` is its sort-free form for one integer key of a small,
known domain: a masked reduction per key slot, guarded in the program.

Null semantics match Spark: null keys form their own group (nulls equal in
GROUP BY); null values are excluded from sum/min/max/mean/count(col), while
count(*) counts rows.  sum/mean over FLOAT64 use the hardware float
approximation (float_values); min/max over FLOAT64 run on the total-order bit
encoding and are exact.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import Column, Table
from ..dtypes import DType, TypeId, INT64, FLOAT64
from .order import SortKey, encode_keys, rows_differ_from_prev
from .selection import gather_table
from . import order as _order
from ..utils.tracing import traced

AGGS = ("sum", "min", "max", "mean", "count", "count_all", "var", "std",
        "sumsq", "fsum", "first", "last", "collect_list")

# ops the sort-carried fast path implements; first/last need positional
# selection and collect_list is ragged (host-compacted in ``groupby``)
_FAST_OPS = frozenset(AGGS) - {"first", "last", "collect_list"}

#: the most key slots (a power of two) ``groupby_dense`` reduces over.  On a
#: TPU v5e, over a 262,144-row chunk (``tools/agg_sweep.py``): 0.61-0.73 ms
#: up to 256 slots, 1.18 ms (float64 sum) at 512, where the one-hot starts
#: to be materialized, and no better than the sort form's 2.18 ms at 1,024
DENSE_MAX_GROUPS = 512

#: the most live rows of a chunk ``groupby_build_rows`` compacts before its
#: scatter-add; a chunk with more scatters every row.  On a TPU v5e, 262,144
#: rows into 145,761 slots (``tools/agg_sweep.py --build-row``): every row
#: scattered 36-64 ms, the compacted form 0.8 / 1.4 / 2.6 / 5.0 ms at 2,048 /
#: 4,096 / 8,192 / 16,384 entries; 4,096 holds three times TPC-H Q3's
#: ~1,300 live rows a chunk
BUILD_SPARSE_MAX_ROWS = 4096

#: the aggregations the dense form computes: sums and counts of a slot
DENSE_OPS = frozenset({"sum", "count", "count_all", "mean"})

#: key types whose value order is their slots' order: the integers an int64
#: holds
DENSE_KEY_TYPES = frozenset({TypeId.INT8, TypeId.INT16, TypeId.INT32,
                             TypeId.INT64, TypeId.UINT8, TypeId.UINT16,
                             TypeId.UINT32})


# ---------------------------------------------------------------------------
# fast path: sort-carried aggregation (no gathers, no scatters)
#
# Profiling on TPU (docs/PERF.md methodology): XLA's segment_sum lowers to a
# serialized scatter (~165 ms for 2M rows) and a random 2M-row gather costs
# ~28 ms, while a multi-operand lax.sort is ~5 ms and a cumsum ~2.5 ms.  So
# the fast path never gathers or scatters: value columns ride the key sort
# as payload operands, sums come from prefix-sum differences at segment
# starts, min/max from a doubling segmented scan, and group compaction is a
# second payload-carrying sort keyed by segment id.  This is the TPU shape
# of the reference's hash aggregation (BASELINE configs[2]): measured ~19.6x
# the scatter-based formulation on a 2M-row 100k-group aggregation.
# ---------------------------------------------------------------------------

def _shift_down(arr, shift: int, fill):
    """arr shifted so row i sees row i-shift (front-filled), gather-free."""
    pad = jnp.full((shift,) + arr.shape[1:], fill, arr.dtype)
    return jnp.concatenate([pad, arr[:-shift]], axis=0)


def _seg_scan(vals, seg, op, identity):
    """Running ``op`` from each segment's start, via log2(n) doubling passes."""
    n = vals.shape[0]
    shift = 1
    while shift < n:
        pv = _shift_down(vals, shift, identity)
        ps = _shift_down(seg, shift, jnp.int32(-1))
        vals = jnp.where(ps == seg, op(vals, pv), vals)
        shift *= 2
    return vals


def _seg_first_valid(vals, has, seg):
    """Forward-fill each segment's first VALID value (doubling passes).

    Rows before any valid value keep their own payload; callers mask those
    rows out anyway.  Gather-free, like _seg_scan."""
    n = vals.shape[0]
    shift = 1
    while shift < n:
        pv = _shift_down(vals, shift, jnp.zeros((), vals.dtype))
        ph = _shift_down(has, shift, jnp.zeros((), jnp.bool_))
        ps = _shift_down(seg, shift, jnp.int32(-1))
        same = ps == seg
        take_prev = same & ph  # an earlier valid value wins
        vals = jnp.where(take_prev, pv, vals)
        has = jnp.where(same, has | ph, has)
        shift *= 2
    return vals


def _seg_last_valid(vals, has, seg):
    """Forward-fill the NEAREST preceding VALID value per row (doubling).

    Unlike _seg_first_valid (earliest valid wins — the whole segment sees
    one value), this keeps the latest: a row only adopts an earlier value
    while it has none yet.  Rows before any valid keep their payload."""
    n = vals.shape[0]
    shift = 1
    while shift < n:
        pv = _shift_down(vals, shift, jnp.zeros((), vals.dtype))
        ph = _shift_down(has, shift, jnp.zeros((), jnp.bool_))
        ps = _shift_down(seg, shift, jnp.int32(-1))
        same = ps == seg
        take_prev = same & ph & jnp.logical_not(has)
        vals = jnp.where(take_prev, pv, vals)
        has = jnp.where(same, has | ph, has)
        shift *= 2
    return vals


def _fast_eligible(key_cols, agg_cols) -> bool:
    for c in key_cols + agg_cols:
        if c.data is None or c.dtype.is_string or c.data.ndim != 1:
            return False
    return True


def _sum_dtype_and_vals(col: Column, sval, svalid):
    """Widened contribution vector + (output dtype, is_float) per Spark."""
    tid = col.dtype.id
    if tid == TypeId.FLOAT64:
        vals = Column(col.dtype, data=sval).float_values()
        return vals, FLOAT64, True
    if tid == TypeId.FLOAT32:
        return jnp.asarray(sval, jnp.float64), FLOAT64, True
    out = col.dtype if col.dtype.is_decimal else INT64
    return sval.astype(jnp.int64), out, False


def _float64_vals(col: Column, sval) -> jnp.ndarray:
    """float64 value vector (Spark casts var/std inputs to double)."""
    tid = col.dtype.id
    if tid == TypeId.FLOAT64:
        return Column(col.dtype, data=sval).float_values()
    if col.dtype.is_decimal:
        return sval.astype(jnp.float64) * (10.0 ** col.dtype.scale)
    return jnp.asarray(sval, jnp.float64)


def _fast_groupby_padded(key_cols, agg_specs, row_mask):
    """(out_keys specs, out_aggs Columns, ngroups) — see groupby_padded."""
    n = key_cols[0].data.shape[0]
    words = encode_keys([SortKey(c) for c in key_cols])
    if row_mask is not None:
        words = [(~row_mask).astype(jnp.uint64)] + words
    nw = len(words)

    # distinct agg-input columns ride the sort once each
    distinct: list[Column] = []
    col_slot: dict[int, int] = {}
    for col, op in agg_specs:
        if col is not None and id(col) not in col_slot:
            col_slot[id(col)] = len(distinct)
            distinct.append(col)

    # non-nullable columns skip the validity payload (no point carrying a
    # constant all-ones byte vector through the sort)
    payloads = []
    for c in key_cols + distinct:
        payloads.append(c.data)
        if c.validity is not None:
            payloads.append(c.validity.astype(jnp.uint8))
    sorted_ops = jax.lax.sort(tuple(words) + tuple(payloads), num_keys=nw,
                              is_stable=True)
    swords = sorted_ops[:nw]
    sp = sorted_ops[nw:]
    ones = jnp.ones((n,), jnp.bool_)
    sdata, svalid_list = [], []
    pi = 0
    for c in key_cols + distinct:
        sdata.append(sp[pi])
        pi += 1
        if c.validity is not None:
            svalid_list.append(sp[pi].astype(jnp.bool_))
            pi += 1
        else:
            svalid_list.append(ones)
    skey_data = sdata[:len(key_cols)]
    skey_valid = svalid_list[:len(key_cols)]
    sval_of = sdata[len(key_cols):]
    svalid_of = svalid_list[len(key_cols):]

    first = jnp.zeros((n,), jnp.bool_).at[0].set(True)
    bounds = first
    for w in swords:
        bounds = bounds | jnp.concatenate([first[:1], w[1:] != w[:-1]])
    seg = jnp.cumsum(bounds.astype(jnp.int32)) - 1
    live_sorted = None if row_mask is None else (swords[0] == 0)
    if row_mask is None:
        ngroups = seg[-1] + 1
    else:
        ngroups = jnp.sum((bounds & live_sorted).astype(jnp.int32))

    live_b = bounds if live_sorted is None else (bounds & live_sorted)
    start_key = jnp.where(live_b, seg, jnp.int32(n))
    is_end = jnp.concatenate([bounds[1:], jnp.ones((1,), jnp.bool_)])
    live_e = is_end if live_sorted is None else (is_end & live_sorted)
    end_key = jnp.where(live_e, seg, jnp.int32(n))

    # prefix-before vectors (psb trick) for every sum-like aggregation; the
    # compacted psb of group g+1 minus group g's IS the segment total —
    # exact for integers; floats use the scan path below instead
    start_payloads: list = list(skey_data) + [m.astype(jnp.uint8)
                                              for m in skey_valid]
    end_payloads: list = []
    plans = []  # (op, col_slot, start_slots/end_slots info ...)

    idx = jnp.arange(n, dtype=jnp.int32)
    count_cache: dict = {}

    def add_start_payload(arr):
        start_payloads.append(arr)
        return len(start_payloads) - 1

    def add_end_payload(arr):
        end_payloads.append(arr)
        return len(end_payloads) - 1

    for col, op in agg_specs:
        if op == "count_all":
            m = jnp.ones((n,), jnp.int64) if live_sorted is None else \
                live_sorted.astype(jnp.int64)
            ps = jnp.cumsum(m)
            grand = ps[-1]
            plans.append(("psb", None, add_start_payload(ps - m), grand,
                          INT64, None))
            continue
        slot = col_slot[id(col)]
        sval, svalid = sval_of[slot], svalid_of[slot]
        if live_sorted is not None:
            svalid = svalid & live_sorted
        if slot in count_cache:
            count_slot, cgrand = count_cache[slot]
        else:
            cm = svalid.astype(jnp.int64)
            cps = jnp.cumsum(cm)
            count_slot = add_start_payload(cps - cm)
            cgrand = cps[-1]
            count_cache[slot] = (count_slot, cgrand)
        if op == "count":
            plans.append(("psb", None, count_slot, cgrand, INT64, None))
            continue
        if op in ("sum", "mean"):
            vals, out_dtype, is_float = _sum_dtype_and_vals(col, sval, svalid)
            if is_float:
                zero = jnp.zeros((), vals.dtype)
                m = jnp.where(svalid, vals, zero)
                scanned = _seg_scan(m, seg, jnp.add, zero)
                plans.append((op + "_scan", col, add_end_payload(scanned),
                              (count_slot, cgrand), out_dtype, None))
            else:
                zero = jnp.zeros((), vals.dtype)
                m = jnp.where(svalid, vals, zero)
                ps = jnp.cumsum(m)
                plans.append((op + "_psb", col, add_start_payload(ps - m),
                              (count_slot, cgrand, ps[-1]), out_dtype, None))
            continue
        if op in ("var", "std", "sumsq", "fsum"):
            vf = _float64_vals(col, sval)
            zero = jnp.zeros((), jnp.float64)
            if op in ("var", "std"):
                # shift by each segment's first VALID value before
                # accumulating moments (variance is shift-invariant; the
                # naive two-moment formula cancels catastrophically when
                # |mean| >> std).  Null-slot payloads are arbitrary (NaN,
                # garbage), so the pivot must come from a valid row.
                pivot = _seg_first_valid(jnp.where(svalid, vf, zero),
                                         svalid, seg)
                vf = jnp.where(svalid, vf - pivot, zero)
            m = jnp.where(svalid, vf, zero)
            s_slot = add_end_payload(_seg_scan(m, seg, jnp.add, zero))
            q_slot = add_end_payload(_seg_scan(m * m, seg, jnp.add, zero))
            plans.append(("var_scan", col, (s_slot, q_slot),
                          (count_slot, cgrand, op), FLOAT64, None))
            continue
        if op in ("min", "max"):
            tid = col.dtype.id
            if tid in (TypeId.FLOAT32, TypeId.FLOAT64):
                enc = _order._fixed_to_u64(Column(col.dtype, data=sval))
                ident = jnp.uint64(2**64 - 1) if op == "min" else jnp.uint64(0)
                enc = jnp.where(svalid, enc, ident)
                combine = jnp.minimum if op == "min" else jnp.maximum
                scanned = _seg_scan(enc, seg, combine, ident)
                plans.append(("minmax_enc", col, add_end_payload(scanned),
                              (count_slot, cgrand, op), col.dtype, None))
            else:
                if jnp.issubdtype(sval.dtype, jnp.integer):
                    info = jnp.iinfo(sval.dtype)
                    ident = jnp.asarray(info.max if op == "min" else info.min,
                                        sval.dtype)
                else:
                    ident = jnp.asarray(jnp.inf if op == "min" else -jnp.inf,
                                        sval.dtype)
                m = jnp.where(svalid, sval, ident)
                combine = jnp.minimum if op == "min" else jnp.maximum
                scanned = _seg_scan(m, seg, combine, ident)
                plans.append(("minmax", col, add_end_payload(scanned),
                              (count_slot, cgrand, op), col.dtype, None))
            continue
        raise ValueError(f"unknown aggregation {op!r}; expected one of {AGGS}")

    comp_s = jax.lax.sort((start_key,) + tuple(start_payloads), num_keys=1,
                          is_stable=True)[1:]
    if end_payloads:
        comp_e = jax.lax.sort((end_key,) + tuple(end_payloads), num_keys=1,
                              is_stable=True)[1:]

    nkeys = len(key_cols)
    out_keys = []
    for i, c in enumerate(key_cols):
        out_keys.append(("fixed", c.dtype, comp_s[i],
                         comp_s[nkeys + i].astype(jnp.bool_)))

    def psb_total(slot, grand):
        psb = comp_s[slot]
        nxt = jnp.concatenate([psb[1:], psb[-1:]])
        return jnp.where(idx == ngroups - 1, grand, nxt) - psb

    out_aggs = []
    for kind, col, slot, extra, out_dtype, _ in plans:
        if kind == "psb":
            out_aggs.append(Column(INT64, data=psb_total(slot, extra)))
            continue
        if kind in ("sum_psb", "mean_psb"):
            count_slot, cgrand, grand = extra
            out_aggs.append(_sum_result(
                kind[:-4], col, psb_total(slot, grand),
                psb_total(count_slot, cgrand), out_dtype, False))
            continue
        if kind in ("sum_scan", "mean_scan"):
            count_slot, cgrand = extra
            out_aggs.append(_sum_result(
                kind[:-5], col, comp_e[slot], psb_total(count_slot, cgrand),
                out_dtype, True))
            continue
        if kind == "var_scan":
            s_slot, q_slot = slot
            count_slot, cgrand, op = extra
            counts = psb_total(count_slot, cgrand)
            s = comp_e[s_slot]
            q = comp_e[q_slot]
            if op in ("sumsq", "fsum"):
                out_aggs.append(Column.fixed(
                    FLOAT64, q if op == "sumsq" else s,
                    validity=counts > 0))
                continue
            nf = counts.astype(jnp.float64)
            var = (q - s * s / jnp.maximum(nf, 1.0)) / \
                jnp.maximum(nf - 1.0, 1.0)
            var = jnp.maximum(var, 0.0)  # clamp catastrophic cancellation
            data = jnp.sqrt(var) if op == "std" else var
            out_aggs.append(Column.fixed(FLOAT64, data, validity=counts > 1))
            continue
        if kind == "minmax":
            count_slot, cgrand, op = extra
            counts = psb_total(count_slot, cgrand)
            out_aggs.append(Column(out_dtype, data=comp_e[slot],
                                   validity=counts > 0))
            continue
        if kind == "minmax_enc":
            count_slot, cgrand, op = extra
            counts = psb_total(count_slot, cgrand)
            data = _order.decode_minmax_bits(comp_e[slot], out_dtype)
            out_aggs.append(Column(out_dtype, data=data,
                                   validity=counts > 0))
            continue
    return out_keys, out_aggs, ngroups


def _keyless_padded(agg_specs, row_mask, n: int):
    """An aggregate with no group keys: ONE group, a masked reduction over
    the rows — no sort, no segment ids.  Every output column has one row;
    ``ngroups`` is 1 (Spark returns one row for an ungrouped aggregate, an
    empty input's included: ``sum`` NULL there, ``count`` 0)."""
    live = jnp.ones((n,), jnp.bool_) if row_mask is None else row_mask
    out = []
    for col, op in agg_specs:
        if op == "count_all":
            out.append(Column(INT64, data=jnp.sum(live, dtype=jnp.int64)[None]))
            continue
        valid = live & col.valid_mask()
        count = jnp.sum(valid, dtype=jnp.int64)
        has = (count > 0)[None]
        if op == "count":
            out.append(Column(INT64, data=count[None]))
        elif op in ("sum", "mean"):
            vals, out_dtype, is_float = _sum_dtype_and_vals(col, col.data,
                                                            valid)
            s = jnp.sum(jnp.where(valid, vals, jnp.zeros((), vals.dtype)))
            out.append(_sum_result(op, col, s[None], count[None], out_dtype,
                                   is_float))
        elif op in ("var", "std", "sumsq", "fsum"):
            vf = jnp.where(valid, _float64_vals(col, col.data), 0.0)
            if op in ("var", "std"):
                # two passes: moments about the mean (no cancellation)
                mean = jnp.sum(vf) / jnp.maximum(count, 1).astype(
                    jnp.float64)
                vf = jnp.where(valid, vf - mean, 0.0)
            s, q = jnp.sum(vf), jnp.sum(vf * vf)
            if op in ("sumsq", "fsum"):
                out.append(Column.fixed(FLOAT64, (q if op == "sumsq" else s)
                                        [None], validity=has))
                continue
            nf = count.astype(jnp.float64)
            var = jnp.maximum(q / jnp.maximum(nf - 1.0, 1.0), 0.0)
            out.append(Column.fixed(
                FLOAT64, (jnp.sqrt(var) if op == "std" else var)[None],
                validity=(count > 1)[None]))
        elif op in ("min", "max"):
            if col.dtype.id in (TypeId.FLOAT32, TypeId.FLOAT64):
                enc = _order._fixed_to_u64(col)
                ident = jnp.uint64(2**64 - 1) if op == "min" \
                    else jnp.uint64(0)
                enc = jnp.where(valid, enc, ident)
                red = jnp.min(enc, initial=ident) if op == "min" \
                    else jnp.max(enc, initial=ident)
                data = _order.decode_minmax_bits(red[None], col.dtype)
            else:
                info = jnp.iinfo(col.data.dtype)
                ident = jnp.asarray(info.max if op == "min" else info.min,
                                    col.data.dtype)
                m = jnp.where(valid, col.data, ident)
                red = (jnp.min(m, initial=ident) if op == "min"
                       else jnp.max(m, initial=ident))
                data = red[None]
            out.append(Column(col.dtype, data=data, validity=has))
        elif op in ("first", "last"):
            # Spark first/last (ignoreNulls=False): the value at the first /
            # last live row
            idx = jnp.arange(n, dtype=jnp.int32)
            pos = jnp.min(jnp.where(live, idx, n), initial=n) \
                if op == "first" else jnp.max(jnp.where(live, idx, -1),
                                              initial=-1)
            any_row = (pos >= 0) & (pos < n)
            at = jnp.clip(pos, 0, max(n - 1, 0))
            data = col.data[at][None] if n else \
                jnp.zeros((1,) + col.data.shape[1:], col.data.dtype)
            ok = col.valid_mask()[at] & any_row if n else jnp.bool_(False)
            out.append(Column(col.dtype, data=data, validity=ok[None]))
        else:
            raise ValueError(f"aggregation {op!r} over no group keys is "
                             "not supported")
    return [], out, jnp.int32(1)


def _sum_result(op: str, col: Column, s, count, out_dtype,
                is_float: bool) -> Column:
    """A ``sum`` or ``mean`` Column from each group's total ``s`` and its
    count of valid rows: null where the group had none (Spark)."""
    has = count > 0
    if op == "mean":
        m = s.astype(jnp.float64) / jnp.maximum(count, 1).astype(jnp.float64)
        if col.dtype.is_decimal:
            m = m * (10.0 ** col.dtype.scale)
        return Column.fixed(FLOAT64, m, validity=has)
    if is_float:
        return Column.fixed(FLOAT64, s, validity=has)
    return Column(out_dtype, data=s, validity=has)


# ---------------------------------------------------------------------------
# dense form: one integer key of a small, known domain — no sort
#
# When every live key is known to lie in [lo, lo + slots), a group is a
# slot, ``key - lo``, and each aggregation a masked reduction over the
# one-hot ``slot == j`` (the shape of ``ops/join.py::select_build_rows``):
# no sort, no scan, no gather or scatter.  The present slots are then
# compacted to the front in key order, so the result is ``groupby_padded``'s
# — same rows, dtypes, validity and group order.  Who knows the domain is
# the caller (a streamed file's footer statistics: ``engine/segment.py``);
# the answer never rests on it: ``groupby_dense`` checks inside the program
# that every live key does lie in it, and a chunk where one does not takes
# the sort form (``lax.cond``: no host sync).
# ---------------------------------------------------------------------------

def dense_slots(lo: int, hi: int):
    """The dense form's slot count for keys known to lie in ``[lo, hi]``:
    the power of two >= ``hi - lo + 1``, or None above
    ``DENSE_MAX_GROUPS`` (a null key takes one slot more, in the program)."""
    span = hi - lo + 1
    if span < 1:
        return None
    slots = 1 << (span - 1).bit_length()
    return slots if slots <= DENSE_MAX_GROUPS else None


def groupby_dense(table: Table, key_names: list, aggs: list[tuple], lo,
                  slots: int, row_mask=None):
    """``groupby_padded(table, key_names, aggs, row_mask=row_mask)`` for
    ONE key of a ``DENSE_KEY_TYPES`` type whose live values are expected in
    ``[lo, lo + slots)`` — ``lo`` a traced int64 scalar, ``slots`` static —
    and ``DENSE_OPS`` aggregations.  A null key has a slot of its own,
    first (nulls sort first).  Where a live non-null key lies outside the
    range, the sort form's result is taken instead."""
    key = table.column(key_names[0])
    resolved = [(None if op == "count_all" else
                 c if isinstance(c, Column) else table.column(c), op)
                for c, op in aggs]
    n = key.data.shape[0]
    live = jnp.ones((n,), jnp.bool_) if row_mask is None else row_mask
    nullable = key.validity is not None
    with jax.named_scope("groupby_dense"):
        k64 = key.data.astype(jnp.int64)
        d = k64 - lo                    # wraps only where k64 - lo >= 2**63
        has_key = live & key.validity if nullable else live
        inside = (k64 >= lo) & (d >= 0) & (d < slots)
        fits = jnp.all(inside | ~has_key)
        slot = jnp.where(has_key, d.astype(jnp.int32) + np.int32(nullable),
                         jnp.where(live, np.int32(0), np.int32(-1)))
        dense = _dense_groupby(key, resolved, live, slot, lo,
                               slots + nullable)

    def sort_form():
        out_keys, out_aggs, ngroups = groupby_padded(table, key_names, aggs,
                                                     row_mask=row_mask)
        return out_keys[0][2], out_keys[0][3], out_aggs, ngroups

    with jax.named_scope("groupby_dense"):
        # the branch that keeps the dense result returns zeros, and a select
        # takes it: a branch that returns its operands makes the TPU
        # compiler abort (HloReplicationAnalysis, jax 0.9's libtpu)
        sort = jax.lax.cond(fits,
                            lambda: jax.tree.map(jnp.zeros_like, dense),
                            sort_form)
        kdat, kval, out_aggs, ngroups = jax.tree.map(
            lambda a, b: jnp.where(fits, a, b), dense, sort)
    return [("fixed", key.dtype, kdat, kval)], out_aggs, ngroups


# ---------------------------------------------------------------------------
# build-row form: a group that is one row of a unique join build — no sort
#
# Where every group key is a column of one build row (the join's key among
# them: ``engine/segment.py::build_row_join``), a group is that row, and its
# totals add into the row's slot: a scatter-add of each live row into
# ``slot`` (the build row it joined), no sort.  The chunk's partial is one
# slot per build row; partials merge by adding slot to slot.
#
# On a TPU a scatter is a serialized loop over its updates, whether they
# add anything or not (70-120 ns an int64 update on a v5e), so a chunk whose
# joins keep few rows first compacts its live rows into a bucket of
# ``BUILD_SPARSE_MAX_ROWS`` — by a binary search of the live mask's prefix
# count, no scatter and no sort — and scatters that bucket.  A chunk with
# more live rows scatters every row (``lax.cond``: no host sync).  Both add
# the same int64 units into the same slots: the answer is the same.
# ---------------------------------------------------------------------------

def groupby_build_rows(table: Table, aggs: list[tuple], live, slot,
                       nslots: int) -> tuple:
    """``(rows, Columns, sparse)``: each of ``nslots`` slots' live row
    count and its ``sum`` / ``count`` / ``count_all`` totals, row i adding
    into ``slot[i]`` where ``live[i]``.  A sum's input is integral or
    decimal (exact in any order); it is null where no valid row added to
    it.  ``sparse``: an int32 scalar, 1 where the chunk's live rows were
    compacted before the scatter, 0 where every row was scattered."""
    resolved = [(c if op == "count_all" or isinstance(c, Column)
                 else table.column(c), op) for c, op in aggs]
    n, k = live.shape[0], BUILD_SPARSE_MAX_ROWS
    with jax.named_scope("groupby_build_row"):
        if n <= k:                  # a compaction would not shrink it
            return (*_build_row_totals(resolved, live, slot, nslots),
                    jnp.int32(0))
        counted = jnp.cumsum(live, dtype=jnp.int32)
        nlive = counted[n - 1]
        at = jnp.minimum(_first_reaching(counted, k), np.int32(n - 1))
        packed = [(c if op == "count_all" else Column(
                       c.dtype, data=c.data[at],
                       validity=None if c.validity is None
                       else c.validity[at]), op) for c, op in resolved]
        sparse = _build_row_totals(
            packed, jnp.arange(k, dtype=jnp.int32) < nlive, slot[at],
            nslots)
        fits = nlive <= k

        def full_form():
            return _build_row_totals(resolved, live, slot, nslots)

        # the branch that keeps the compacted totals returns zeros, and a
        # select takes them: a branch that returns its operands makes the
        # TPU compiler abort (as in ``groupby_dense``)
        full = jax.lax.cond(
            fits, lambda: jax.tree.map(jnp.zeros_like, sparse), full_form)
        rows, out = jax.tree.map(lambda a, b: jnp.where(fits, a, b),
                                 sparse, full)
    return rows, out, fits.astype(jnp.int32)


def _first_reaching(counted, k: int):
    """For j < ``k``, the first row whose prefix count ``counted`` reaches
    j + 1 — the j-th live row's position, ``n`` where there is none: a
    binary search of each j at once, unrolled (one ``k``-element gather a
    round, log2(n) + 1 rounds; no loop, no scatter, no sort)."""
    n = counted.shape[0]
    want = jnp.arange(1, k + 1, dtype=jnp.int32)
    pos = jnp.zeros((k,), jnp.int32)    # rows known to count short of want
    step = 1 << (n.bit_length() - 1)
    while step:
        nxt = pos + np.int32(step)
        short = counted[jnp.minimum(nxt, np.int32(n)) - 1] < want
        pos = jnp.where((nxt <= n) & short, nxt, pos)
        step >>= 1
    return pos


def _build_row_totals(resolved: list, live, slot, nslots: int) -> tuple:
    """``(rows, Columns)`` of ``groupby_build_rows`` by one scatter-add of
    every row a total takes: row i into ``slot[i]`` where ``live[i]``, a
    dead row into a spare slot.  ``resolved``: ``(Column or None, op)``."""
    dest = jnp.where(live, slot, np.int32(nslots))

    def total(v):
        return jax.ops.segment_sum(v, dest, nslots + 1)[:nslots]

    rows = total(live.astype(jnp.int64))
    out = []
    for col, op in resolved:
        if op == "count_all":
            out.append(Column(INT64, data=rows))
            continue
        valid = live & col.valid_mask()
        cnt = rows if col.validity is None else \
            total(valid.astype(jnp.int64))
        if op == "count":
            out.append(Column(INT64, data=cnt))
            continue
        vals, out_dtype, is_float = _sum_dtype_and_vals(col, col.data, valid)
        if is_float:
            raise TypeError("the build-row form sums no float")
        s = total(jnp.where(valid, vals, jnp.zeros((), vals.dtype)))
        out.append(_sum_result(op, col, s, cnt, out_dtype, False))
    return rows, out


def _signed_zeros(s, col: Column, valid, slot, rows, count):
    """``s``, each slot's float sum from a reduction that starts at +0.0,
    with -0.0 where every row of the slot holds -0.0 — what IEEE addition,
    and so the sort form's scan, gives them.  Only a chunk that holds a
    -0.0 pays for the count of the slots' other rows (``lax.cond``)."""
    if col.dtype.id == TypeId.FLOAT64:      # the stored bit pattern
        negz = col.data.astype(jnp.uint64) == np.uint64(1 << 63)
    else:
        negz = jax.lax.bitcast_convert_type(
            jnp.asarray(col.data, jnp.float32), jnp.uint32) \
            == np.uint32(1 << 31)
    negz = negz & valid
    others = jax.lax.cond(jnp.any(negz & (slot >= 0)),
                          lambda: count(~negz),
                          lambda: jnp.ones(rows.shape, rows.dtype))
    return jnp.where((others == 0) & (rows > 0), np.float64(-0.0), s)


def _dense_groupby(key: Column, resolved: list, live, slot, lo, ks: int):
    """The dense form proper: ``(key data, key validity, aggregate
    Columns, ngroups)`` padded to the input's rows.  ``slot`` is each row's
    slot in ``[0, ks)``, -1 for a dead row; slot 0 is the null key's when
    ``key`` has validity."""
    n = slot.shape[0]
    nullable = key.validity is not None
    j = jnp.arange(ks, dtype=jnp.int32)
    onehot = slot[None, :] == j[:, None]    # fused into each reduction

    def count(mask=None):
        m = onehot if mask is None else onehot & mask[None, :]
        return jnp.sum(m, axis=1, dtype=jnp.int32).astype(jnp.int64)

    rows = count()                          # live rows of each slot
    counts: dict = {}
    per_slot = []                           # one Column of ks rows per agg
    for col, op in resolved:
        if op == "count_all":
            per_slot.append(Column(INT64, data=rows))
            continue
        if id(col) not in counts:
            counts[id(col)] = rows if col.validity is None \
                else count(col.validity)
        cnt = counts[id(col)]
        if op == "count":
            per_slot.append(Column(INT64, data=cnt))
            continue
        valid = col.valid_mask()
        vals, out_dtype, is_float = _sum_dtype_and_vals(col, col.data, valid)
        zero = jnp.zeros((), vals.dtype)
        if is_float:
            # a null row adds +0.0, as in the sort form's scan
            contrib = jnp.where(valid, vals, zero)[None, :]
            s = jnp.sum(jnp.where(onehot, contrib, zero), axis=1)
            s = _signed_zeros(s, col, valid, slot, rows, count)
        else:
            s = jnp.sum(jnp.where(onehot & valid[None, :], vals[None, :],
                                  zero), axis=1, dtype=vals.dtype)
        per_slot.append(_sum_result(op, col, s, cnt, out_dtype, is_float))

    kval = j >= np.int32(nullable)
    kdat = (lo + (j - np.int32(nullable)).astype(jnp.int64)).astype(
        key.data.dtype)
    if nullable:
        # the null group's key bytes: its first live row's, as the stable
        # sort leaves them
        idx = jnp.arange(n, dtype=jnp.int32)
        first = jnp.min(jnp.where(live & ~key.validity, idx, np.int32(n)))
        null_data = jnp.sum(jnp.where(idx == first, key.data,
                                      jnp.zeros((), key.data.dtype)),
                            dtype=key.data.dtype)
        kdat = jnp.where(kval, kdat, null_data)

    # compaction: the present slots, in key order, to the front
    present = rows > 0
    ngroups = jnp.sum(present.astype(jnp.int32))   # the sort form's dtype
    pos = jnp.cumsum(present.astype(jnp.int32)) - 1
    pick = present[None, :] & (pos[None, :] == j[:, None])   # (out, slot)

    def compact(a):
        if a.dtype == jnp.bool_:
            return compact(a.astype(jnp.int32)) != 0
        out = jnp.sum(jnp.where(pick, a[None, :], jnp.zeros((), a.dtype)),
                      axis=1, dtype=a.dtype)    # one term at most: exact
        return out[:n] if ks >= n else jnp.pad(out, (0, n - ks))

    out_aggs = [Column(c.dtype, data=compact(c.data),
                       validity=None if c.validity is None
                       else compact(c.validity)) for c in per_slot]
    return compact(kdat), compact(kval), out_aggs, ngroups


def _seg_ids(keys: list[SortKey], row_mask=None):
    """Sort+segment the rows; masked-out rows sort last as dead groups.

    With ``row_mask`` (padded pipelines, e.g. post-shuffle), the returned
    ``ngroups`` counts only live groups — dead rows sort after every live row
    via a primary mask word, so live groups occupy seg ids [0, ngroups).
    """
    words = encode_keys(keys)
    if row_mask is not None:
        words = [(~row_mask).astype(jnp.uint64)] + words  # live rows first
    order = jnp.lexsort(tuple(reversed(words)))
    bounds = rows_differ_from_prev(words, order)
    seg = jnp.cumsum(bounds.astype(jnp.int32)) - 1
    if order.shape[0] == 0:
        return order, seg, jnp.int32(0)
    if row_mask is None:
        ngroups = seg[-1] + 1
    else:
        live_sorted = jnp.take(row_mask, order)
        ngroups = jnp.sum((bounds & live_sorted).astype(jnp.int32))
    return order, seg, ngroups


def _segment_reduce(op: str, vals, seg, num_segments: int, valid=None):
    if valid is None:
        valid = jnp.ones(vals.shape[:1], jnp.bool_)
    if op == "sum":
        z = jnp.zeros((), vals.dtype)
        contrib = jnp.where(valid, vals, z)
        return jax.ops.segment_sum(contrib, seg, num_segments)
    if op == "min":
        big = jnp.iinfo(vals.dtype).max if jnp.issubdtype(vals.dtype, jnp.integer) \
            else jnp.inf
        contrib = jnp.where(valid, vals, jnp.asarray(big, vals.dtype))
        return jax.ops.segment_min(contrib, seg, num_segments)
    if op == "max":
        small = jnp.iinfo(vals.dtype).min if jnp.issubdtype(vals.dtype, jnp.integer) \
            else -jnp.inf
        contrib = jnp.where(valid, vals, jnp.asarray(small, vals.dtype))
        return jax.ops.segment_max(contrib, seg, num_segments)
    raise ValueError(op)


def _agg_column(col: Column, op: str, order, seg, num_segments: int,
                live_sorted=None):
    """One aggregation over sorted rows.

    ``live_sorted``: sorted-order live-row mask for padded pipelines; the
    single place dead rows are excluded from every op, count_all included.
    """
    if op == "count_all":
        live = jnp.ones(order.shape, jnp.int64) if live_sorted is None \
            else live_sorted.astype(jnp.int64)
        return Column(INT64, data=jax.ops.segment_sum(live, seg, num_segments))

    sval = None if col.data is None else jnp.take(col.data, order, axis=0)
    svalid = jnp.take(col.valid_mask(), order)
    if live_sorted is not None:
        svalid = svalid & live_sorted
    counts = jax.ops.segment_sum(svalid.astype(jnp.int64), seg, num_segments)

    if op == "count":
        return Column(INT64, data=counts)

    has_any = counts > 0
    tid = col.dtype.id
    if op in ("sum", "mean"):
        if tid == TypeId.FLOAT64:
            vals = Column(col.dtype, data=sval).float_values()
        elif tid == TypeId.FLOAT32:
            vals = jnp.asarray(sval, jnp.float64)
        elif col.dtype.is_decimal:
            vals = sval.astype(jnp.int64)  # unscaled sum keeps the scale
        else:
            vals = sval.astype(jnp.int64)  # Spark widens integral sums to long
        s = _segment_reduce("sum", vals, seg, num_segments, svalid)
        if op == "mean":
            m = s.astype(jnp.float64) / jnp.maximum(counts, 1).astype(jnp.float64)
            if col.dtype.is_decimal:
                m = m * (10.0 ** col.dtype.scale)
            return Column.fixed(FLOAT64, m, validity=has_any)
        if tid in (TypeId.FLOAT32, TypeId.FLOAT64):
            return Column.fixed(FLOAT64, s, validity=has_any)
        out_dtype = col.dtype if col.dtype.is_decimal else INT64
        return Column(out_dtype, data=s, validity=has_any)

    if op in ("first", "last"):
        # Spark first/last (ignoreNulls=False): the value at the group's
        # first/last live row in input order (the key sort is stable)
        n = sval.shape[0]
        idxv = jnp.arange(n, dtype=jnp.int32)
        live = jnp.ones((n,), jnp.bool_) if live_sorted is None \
            else live_sorted
        if op == "first":
            pos = jax.ops.segment_min(jnp.where(live, idxv, n),
                                      seg, num_segments)
        else:
            pos = jax.ops.segment_max(jnp.where(live, idxv, -1),
                                      seg, num_segments)
        has_row = (pos >= 0) & (pos < n)
        pos_c = jnp.clip(pos, 0, max(n - 1, 0))
        data = jnp.take(sval, pos_c, axis=0)
        valid = jnp.take(col.valid_mask(), jnp.take(order, pos_c)) & has_row
        return Column(col.dtype, data=data, validity=valid)

    if op in ("var", "std", "sumsq", "fsum"):
        vf = _float64_vals(col, sval)
        if op in ("var", "std"):
            # shift by the segment's first VALID value (variance is
            # shift-invariant; the naive formula cancels when |mean| >> std;
            # null-slot payloads are arbitrary and must not leak in)
            n_ = vf.shape[0]
            first_idx = jax.ops.segment_min(
                jnp.where(svalid, jnp.arange(n_, dtype=jnp.int32),
                          jnp.int32(n_)), seg, num_segments)
            pivot = jnp.take(jnp.where(svalid, vf, 0.0),
                             jnp.clip(first_idx, 0, max(n_ - 1, 0)))
            vf = jnp.where(svalid, vf - jnp.take(pivot, seg), 0.0)
        s = _segment_reduce("sum", vf, seg, num_segments, svalid)
        q = _segment_reduce("sum", vf * vf, seg, num_segments, svalid)
        if op in ("sumsq", "fsum"):
            return Column.fixed(FLOAT64, q if op == "sumsq" else s,
                                validity=has_any)
        nf = counts.astype(jnp.float64)
        var = (q - s * s / jnp.maximum(nf, 1.0)) / jnp.maximum(nf - 1.0, 1.0)
        var = jnp.maximum(var, 0.0)
        data = jnp.sqrt(var) if op == "std" else var
        return Column.fixed(FLOAT64, data, validity=counts > 1)

    if op in ("min", "max"):
        if tid in (TypeId.FLOAT32, TypeId.FLOAT64):
            # exact on the total-order encoding, decode via gather of argmin
            enc = _order._fixed_to_u64(Column(col.dtype, data=sval))
            enc = jnp.where(svalid, enc,
                            jnp.where(op == "min", jnp.uint64(2**64 - 1),
                                      jnp.uint64(0)))
            red = _segment_reduce(op, enc.astype(jnp.uint64), seg, num_segments)
            data = _order.decode_minmax_bits(red, col.dtype)
            return Column(col.dtype, data=data, validity=has_any)
        red = _segment_reduce(op, sval, seg, num_segments, svalid)
        return Column(col.dtype, data=red, validity=has_any)

    if op == "collect_list":
        raise ValueError("collect_list output is ragged; it is only "
                         "available through ops.aggregate.groupby")
    raise ValueError(f"unknown aggregation {op!r}; expected one of {AGGS}")


@traced("groupby_padded")
def groupby_padded(table: Table, key_names: list, aggs: list[tuple],
                   keys_cols: list | None = None, row_mask=None):
    """Jit-able core: (key_table_padded, agg_table_padded, ngroups).

    Outputs have n rows; rows >= ngroups are padding.  Strings in VALUE
    position are unsupported (as in cudf hash aggregations).
    """
    key_cols = keys_cols if keys_cols is not None else \
        [table.column(k) for k in key_names]

    resolved = []
    for col_ref, op in aggs:
        col = col_ref if isinstance(col_ref, Column) else \
            (None if op == "count_all" else table.column(col_ref))
        resolved.append((col, op))
    agg_inputs = [c for c, _ in resolved if c is not None]
    if not key_cols:
        rows = row_mask.shape[0] if row_mask is not None else \
            table.num_rows
        return _keyless_padded(resolved, row_mask, rows)
    if key_cols and key_cols[0].data is not None \
            and key_cols[0].data.shape[0] > 0 \
            and _fast_eligible(key_cols, agg_inputs) \
            and all(op in _FAST_OPS for _, op in resolved):
        return _fast_groupby_padded(key_cols, resolved, row_mask)

    skeys = [SortKey(c) for c in key_cols]
    order, seg, ngroups = _seg_ids(skeys, row_mask)
    n = order.shape[0]

    first_row_of_seg = jax.ops.segment_min(
        jnp.arange(n, dtype=jnp.int32), seg, n)  # n-padded
    out_keys = []
    for c in key_cols:
        if c.dtype.is_string:
            from .strings_common import to_padded_bytes
            mat, lengths = to_padded_bytes(c)
            srt = jnp.take(order, jnp.clip(first_row_of_seg, 0, n - 1))
            gm = jnp.take(mat, srt, axis=0)
            gl = jnp.take(lengths, srt)
            out_keys.append(("string", gm, gl,
                             jnp.take(c.valid_mask(), srt)))
        else:
            srt = jnp.take(order, jnp.clip(first_row_of_seg, 0, n - 1))
            data = jnp.take(c.data, srt, axis=0)
            valid = jnp.take(c.valid_mask(), srt)
            out_keys.append(("fixed", c.dtype, data, valid))

    live_sorted = None if row_mask is None else jnp.take(row_mask, order)
    out_aggs = []
    for col, op in resolved:
        if col is None:  # count_all carries no input column
            out_aggs.append(_agg_column(None, op, order, seg, n, live_sorted))
            continue
        if col.dtype.is_string and op not in ("count", "count_all"):
            raise TypeError("string value aggregation not supported")
        if col.dtype.is_string and op == "count":
            # no fixed-width buffer to gather; count validity directly
            svalid = jnp.take(col.valid_mask(), order)
            if live_sorted is not None:
                svalid = svalid & live_sorted
            out_aggs.append(Column(INT64, data=jax.ops.segment_sum(
                svalid.astype(jnp.int64), seg, n)))
        else:
            out_aggs.append(_agg_column(col, op, order, seg, n, live_sorted))
    return out_keys, out_aggs, ngroups


@functools.partial(jax.jit, static_argnums=(1, 2))
def _groupby_compiled(table: Table, key_names: tuple, aggs: tuple):
    """Fixed-width groupby_padded as ONE compiled program (key specs are
    static; Columns are pytrees, so outputs cross the jit boundary whole)."""
    out_keys, out_aggs, ngroups = groupby_padded(table, list(key_names),
                                                 list(aggs))
    key_cols = [Column(spec[1], data=spec[2], validity=spec[3])
                for spec in out_keys]  # eligibility guarantees "fixed"
    return key_cols, out_aggs, ngroups


def _host_key_segments(table: Table, key_names: list, value_col=None):
    """(order, key_bounds, pair_bounds) of the host-side key lexsort.

    The alignment contract the ragged-agg wrappers rely on: the base
    groupby's group order is ascending in the encoded key words, and so is
    this lexsort — group i of the base is segment i here.  ``key_bounds``
    marks each group's first sorted row; with ``value_col`` the sort is
    over (keys, value) and ``pair_bounds`` additionally marks each
    distinct (key, value) run (else None).  Keys encode exactly once."""
    key_cols = [table.column(k) for k in key_names]
    kwords = [np.asarray(w) for w in
              encode_keys([SortKey(c) for c in key_cols])]
    vwords = [] if value_col is None else \
        [np.asarray(w) for w in encode_keys([SortKey(value_col)])]
    order = np.lexsort(tuple(reversed(kwords + vwords)))
    n = len(order)

    def bounds_of(words):
        b = np.ones(n, np.bool_)
        if n:
            b[1:] = np.zeros(n - 1, np.bool_)
            for w in words:
                sw = w[order]
                b[1:] |= sw[1:] != sw[:-1]
        return b

    kb = bounds_of(kwords)
    pb = None if value_col is None else (kb | bounds_of(vwords))
    return order, kb, pb


def _assemble_special_aggs(base: Table, nkeys: int, aggs: list,
                           names: list | None, is_special, build) -> Table:
    """Interleave base scalar-agg columns with specially-built columns in
    the caller's agg order (shared epilogue of the ragged-agg wrappers)."""
    out_cols = list(base.columns[:nkeys])
    oi = nkeys
    for ref, op in aggs:
        if is_special(op):
            out_cols.append(build(ref))
        else:
            out_cols.append(base.columns[oi])
            oi += 1
    agg_names = names or [
        f"{op}_{ref if isinstance(ref, str) else i}"
        for i, (ref, op) in enumerate(aggs)]
    return Table(out_cols, list(base.names[:nkeys]) + list(agg_names))


def _groupby_with_collect(table: Table, key_names: list, aggs: list,
                          names: list | None) -> Table:
    """groupby with collect_list aggs: ragged output, host-compacted.

    Scalar aggs run through the normal device path; the list columns are
    built host-side over the same sorted-key segmentation
    (_host_key_segments), so group order matches.  Spark semantics: null
    elements are dropped; empty groups give [] not null.
    """
    others = [(r, op) for r, op in aggs if op != "collect_list"]
    base = groupby(table, key_names, others) if others else \
        groupby(table, key_names, [(key_names[0], "count_all")])
    nkeys = len(key_names)
    order, bounds, _ = _host_key_segments(table, key_names)
    n = len(order)
    starts = np.flatnonzero(bounds)

    def collect(ref) -> Column:
        col = ref if isinstance(ref, Column) else table.column(ref)
        valid = col.validity_numpy()[order]
        if col.dtype.is_string:
            vals = col.to_pylist()
            groups = [[vals[r] for r in order[a:b] if vals[r] is not None]
                      for a, b in zip(starts, np.append(starts[1:], n))]
            flat = [v for g in groups for v in g]
            child = Column.from_pylist(flat, dtype=col.dtype)
        else:
            vals = col.to_numpy()[order]
            groups = [vals[a:b][valid[a:b]]
                      for a, b in zip(starts, np.append(starts[1:], n))]
            child = Column.from_numpy(
                np.concatenate(groups) if groups else
                np.zeros(0, col.dtype.storage), dtype=col.dtype)
        lens = np.fromiter((len(g) for g in groups), np.int64, len(starts))
        offsets = np.zeros(len(starts) + 1, np.int64)
        np.cumsum(lens, out=offsets[1:])
        if offsets[-1] > np.iinfo(np.int32).max:
            raise ValueError("collect_list output exceeds int32 offsets")
        return Column.list_(child, offsets.astype(np.int32))

    return _assemble_special_aggs(base, nkeys, aggs, names,
                                  lambda op: op == "collect_list", collect)


def _groupby_with_nunique(table: Table, key_names: list, aggs: list,
                          names: list | None) -> Table:
    """groupby with count(DISTINCT col) aggs (Spark nunique).

    Alignment via _host_key_segments: a lexsort over (keys, value)
    segments identically to the base groupby's group order, so each
    group's distinct-valid-value count lands at its base row.  Spark
    semantics: null values are not counted; an all-null group counts 0.
    """
    others = [(r, op) for r, op in aggs
              if op not in ("nunique", "count_distinct")]
    base = groupby(table, key_names, others) if others else \
        groupby(table, key_names, [(key_names[0], "count_all")])
    nkeys = len(key_names)
    ngroups = base.num_rows

    def nunique(ref) -> Column:
        col = table.column(ref)
        order, kb, pb = _host_key_segments(table, key_names, value_col=col)
        if len(order) == 0:
            return Column.fixed(INT64, np.zeros(0, np.int64))
        gid = np.cumsum(kb) - 1
        valid = col.validity_numpy()[order]
        take = pb & valid  # first row of each distinct non-null value
        cnt = np.bincount(gid[take], minlength=ngroups).astype(np.int64)
        return Column.fixed(INT64, cnt)

    return _assemble_special_aggs(
        base, nkeys, aggs, names,
        lambda op: op in ("nunique", "count_distinct"), nunique)


@traced("groupby")
def groupby(table: Table, key_names: list, aggs: list[tuple],
            names: list | None = None) -> Table:
    """GROUP BY key_names with aggregations [(column, op), ...] -> compact Table.

    op in {sum, min, max, mean, count, count_all, var, std, sumsq, fsum,
    first, last, collect_list} (the AGGS tuple) plus nunique /
    count_distinct (Spark count(DISTINCT col): null values not counted).
    var/std are sample (ddof=1) moments; first/last follow Spark's
    ignoreNulls=False positional semantics; collect_list drops null
    elements and returns a LIST column (host-compacted — ragged output
    can't stay padded).
    """
    # One compiled program instead of eager per-op dispatch: on remote
    # devices each eager op costs a full round trip, which turned this host
    # wrapper into minutes of latency.  Jit requires hashable static specs
    # and fixed-width columns (string keys size their padded matrices on
    # the host).
    if any(op in ("nunique", "count_distinct") for _, op in aggs):
        return _groupby_with_nunique(table, key_names, aggs, names)
    if any(op == "collect_list" for _, op in aggs):
        return _groupby_with_collect(table, key_names, aggs, names)
    jitable = all(isinstance(k, str) for k in key_names) and \
        all(isinstance(r, str) for r, _ in aggs) and \
        all(op in _FAST_OPS for _, op in aggs)
    if jitable:
        try:
            key_cols = [table.column(k) for k in key_names]
            agg_cols = [table.column(r) for r, op in aggs
                        if op != "count_all"]
            jitable = table.num_rows > 0 and \
                _fast_eligible(key_cols, agg_cols)
        except (KeyError, ValueError):
            jitable = False
    if jitable:
        out_key_cols, out_aggs, ngroups = _groupby_compiled(
            table, tuple(key_names), tuple((r, op) for r, op in aggs))
        out_keys = [("fixed", c.dtype, c.data, c.valid_mask())
                    for c in out_key_cols]
    else:
        out_keys, out_aggs, ngroups = groupby_padded(table, key_names, aggs)
    ng = int(ngroups)
    cols = []
    for spec in out_keys:
        if spec[0] == "string":
            _, gm, gl, gv = spec
            gm, gl, gv = (np.asarray(gm)[:ng], np.asarray(gl)[:ng],
                          np.asarray(gv)[:ng])
            from .strings_common import from_padded_bytes
            has_null = not gv.all()
            cols.append(from_padded_bytes(gm, gl, gv if has_null else None))
        else:
            _, dtype, data, valid = spec
            v = np.asarray(valid)[:ng]
            cols.append(Column(dtype, data=jnp.asarray(np.asarray(data)[:ng]),
                               validity=jnp.asarray(v) if not v.all() else None))
    for c in out_aggs:
        data = jnp.asarray(np.asarray(c.data)[:ng])
        valid = None if c.validity is None else \
            jnp.asarray(np.asarray(c.validity)[:ng])
        cols.append(Column(c.dtype, data=data, validity=valid))
    key_names_out = [k if isinstance(k, str) else f"key{i}"
                     for i, k in enumerate(key_names)]
    agg_names = names or [
        f"{op}_{ref if isinstance(ref, str) else i}"
        for i, (ref, op) in enumerate(aggs)]
    return Table(cols, key_names_out + list(agg_names))
