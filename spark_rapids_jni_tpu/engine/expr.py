"""Expressions: Spark's typing of them, and one evaluator for the
interpreter and every compiled program.

The language (``plan.py``) is nested tuples: columns, literals — plain
Python values, exact decimals ``("lit_decimal", unscaled, precision,
scale)`` and dates ``("lit_date", days)`` — comparisons, booleans and the
arithmetic ``*``, ``+``, ``-``.

**Typing** follows Spark's ``DecimalPrecision``: a decimal(p1,s1) times a
decimal(p2,s2) is decimal(p1+p2+1, s1+s2); a sum or difference is
decimal(max(s1,s2) + max(p1-s1, p2-s2) + 1, max(s1,s2)); ``sum`` of a
decimal(p,s) is decimal(min(38, p+10), s); an integral operand is the
decimal of its type's digits (a literal: of its own digits).  Precision is
capped at 38, and the scale is kept where Spark would give up digits of it
past 38: such a value does not fit the storage below anyway.

**Storage** departs from Spark: every decimal is held as int64 units of
``10**-scale`` (``DECIMAL64``, however many digits the type allows), where
Spark keeps a decimal above 18 digits in 128 bits.  So every operation that
can grow a value — a multiply, a sum, a column brought to a larger scale —
checks it on the device: a magnitude of ``OVERFLOW_UNITS`` (2**62) or more
raises a flag (an f32 estimate, so a value of 2**63 or more, which int64
cannot hold, can never pass).  The flag travels with the program's result
and is read in the fetch that brings the result; a set flag fails the query
with ``DecimalOverflowError`` (``decimal-overflow``) and counts
``engine.decimal.overflow``.  No wrapped value is ever returned.

A comparison brings both sides to one scale: ``l_quantity < 24`` over a
decimal(15,2) column compares units with 2400.  A float side compares (and
computes) in floating point, as Spark compares a decimal with a double.
"""

from __future__ import annotations

import operator
from typing import Optional

import jax.numpy as jnp
import numpy as np

from ..dtypes import BOOL8, FLOAT64, INT64, TIMESTAMP_DAYS, DType, TypeId
from .plan import ARITH_OPS

#: the magnitude, in units, at which a decimal (or integral) value counts as
#: overflowed: int64 holds 2**63, and the check is an f32 estimate
OVERFLOW_UNITS = 2.0 ** 62

MAX_PRECISION = 38

#: comparison operators (booleans and arithmetic are the other heads)
COMPARISONS = {">=": operator.ge, "<=": operator.le, ">": operator.gt,
               "<": operator.lt, "==": operator.eq, "!=": operator.ne}

#: Spark's decimal view of the integral types: DecimalType.forType
_INT_DIGITS = {TypeId.INT8: 3, TypeId.INT16: 5, TypeId.INT32: 10,
               TypeId.INT64: 20, TypeId.UINT8: 3, TypeId.UINT16: 5,
               TypeId.UINT32: 10, TypeId.UINT64: 20}


# -- typing ------------------------------------------------------------------

def count_nodes(expr) -> int:
    """Expression nodes other than columns and literals: what the
    ``engine.expr.*`` counters count."""
    if not isinstance(expr, tuple) or expr[0] in ("col", "lit") \
            or expr[0].startswith("lit_"):
        return 0
    return 1 + sum(count_nodes(e) for e in expr[1:])


def is_arith(expr) -> bool:
    """Whether the expression holds an arithmetic node."""
    return isinstance(expr, tuple) and (
        expr[0] in ARITH_OPS or any(is_arith(e) for e in expr[1:]))


def decimal_view(dt: Optional[DType], value=None) -> Optional[tuple]:
    """``(precision, scale)`` of an operand as Spark sees it in decimal
    arithmetic, or None when it is not decimal or integral.  ``value``: a
    plain integer literal's value (its own digits count)."""
    if dt is None:
        if isinstance(value, int) and not isinstance(value, bool):
            return max(len(str(abs(value))), 1), 0
        return None
    if dt.is_decimal:
        p = dt.precision or (9 if dt.id == TypeId.DECIMAL32 else 18)
        return p, -dt.scale
    if dt.id in _INT_DIGITS:
        return _INT_DIGITS[dt.id], 0
    return None


def decimal_type(p: int, s: int) -> DType:
    """The engine's storage of a decimal(p,s): int64 units."""
    return DType(TypeId.DECIMAL64, -s, min(p, MAX_PRECISION))


def arith_result(op: str, a: tuple, b: tuple) -> tuple:
    """Spark's ``(precision, scale)`` of ``a op b`` over two decimal views."""
    (p1, s1), (p2, s2) = a, b
    if op == "*":
        return min(p1 + p2 + 1, MAX_PRECISION), s1 + s2
    s = max(s1, s2)
    return min(s + max(p1 - s1, p2 - s2) + 1, MAX_PRECISION), s


def sum_type(dt: DType) -> DType:
    """``sum`` of a decimal: decimal(min(38, p+10), s)."""
    p, s = decimal_view(dt)
    return decimal_type(p + 10, s)


def _kind(dt: Optional[DType], value=None) -> str:
    """The domain an operand computes in: dec, int, float, date, str, bool,
    other (None: unknown)."""
    if dt is None:
        if isinstance(value, bool):
            return "bool"
        if isinstance(value, int):
            return "int"
        if isinstance(value, float):
            return "float"
        if isinstance(value, str):
            return "str"
        return "other"
    if dt.is_string:
        return "str"
    if dt.is_decimal:
        return "dec"
    if dt.is_floating:
        return "float"
    if dt.id == TypeId.TIMESTAMP_DAYS:
        return "date"
    if dt.id == TypeId.BOOL8:
        return "bool"
    if dt.id in _INT_DIGITS:
        return "int"
    return "other"


class ExprTypeError(ValueError):
    """An expression the language cannot type; ``code`` names the check
    (the verifier re-raises it as a ``PlanVerificationError``)."""

    def __init__(self, code: str, message: str):
        self.code = code
        super().__init__(message)


def arith_dtype(op: str, ta: Optional[DType], tb: Optional[DType],
                va=None, vb=None) -> DType:
    """Result type of ``a op b`` (``va``/``vb``: a plain literal's value
    where the side is one); raises ``ExprTypeError`` for an operand the
    arithmetic does not take."""
    ka, kb = _kind(ta, va), _kind(tb, vb)
    if "str" in (ka, kb):
        raise ExprTypeError("arithmetic-over-string",
                            f"arithmetic {op!r} over a STRING operand")
    if "date" in (ka, kb) and "dec" in (ka, kb):
        raise ExprTypeError("date-decimal-mix",
                            f"arithmetic {op!r} mixes DATE and DECIMAL")
    if "date" in (ka, kb):
        if op == "*" or (ka, kb) not in (("date", "int"), ("int", "date")) \
                or (op == "-" and ka == "int"):
            raise ExprTypeError("invalid-arithmetic",
                                f"{op!r} over a DATE takes date +/- days")
        return TIMESTAMP_DAYS
    if not {ka, kb} <= {"dec", "int", "float"}:
        raise ExprTypeError("invalid-arithmetic",
                            f"arithmetic {op!r} over {ka} and {kb} operands")
    if "float" in (ka, kb):
        return FLOAT64
    if "dec" not in (ka, kb):
        return INT64
    return decimal_type(*arith_result(op, decimal_view(ta, va),
                                      decimal_view(tb, vb)))


def compare_check(op: str, ta: Optional[DType], tb: Optional[DType],
                  va=None, vb=None) -> None:
    """Raise ``ExprTypeError`` for a comparison of a DATE with a decimal."""
    ka, kb = _kind(ta, va), _kind(tb, vb)
    if {ka, kb} == {"date", "dec"}:
        raise ExprTypeError("date-decimal-mix",
                            f"comparison {op!r} between DATE and DECIMAL")


def literal(expr) -> tuple:
    """``(value, dtype)`` of a literal node: a plain literal's dtype is
    None (typed by what it meets)."""
    if expr[0] == "lit_decimal":
        return expr[1], decimal_type(expr[2], expr[3])
    if expr[0] == "lit_date":
        return expr[1], TIMESTAMP_DAYS
    return expr[1], None


# -- evaluation --------------------------------------------------------------
#
# Traced inside every compiled program and run eagerly by the interpreter:
# pure array code, no host value taken from an array.  ``ovf`` collects the
# overflow checks (boolean scalars) the expression raised.

def _flag(ovf: list, cond) -> None:
    ovf.append(jnp.any(cond))


def _estimate(v):
    """|v| as an f32 estimate (Python literals stay Python numbers)."""
    if isinstance(v, (int, float)):
        return abs(v) * 1.0
    return jnp.abs(v.astype(jnp.float32))


def _scale(dt: Optional[DType]) -> int:
    return -dt.scale if dt is not None and dt.is_decimal else 0


def _rescale(v, by: int, ovf: list):
    """Units ``v`` times ``10**by`` (``by`` >= 0), checked."""
    if by == 0:
        return v
    if isinstance(v, int):
        return v * 10 ** by
    _flag(ovf, _estimate(v) >= OVERFLOW_UNITS / 10 ** by)
    return v.astype(jnp.int64) * np.int64(10 ** by)


def _as_float(v, dt: Optional[DType]):
    """A numeric operand as floating-point values (FLOAT64 columns are
    values already: ``evaluate`` reads them through ``float_values``)."""
    s = _scale(dt)
    if isinstance(v, (int, float)):
        return v / 10 ** s
    if s:
        return v.astype(jnp.float64) / np.float64(10 ** s)
    return v if dt is not None and dt.is_floating else v.astype(jnp.float64)


def _align(op: str, a, ta, b, tb, ovf: list) -> tuple:
    """Both operands of comparison ``op`` in one domain: floats if a side
    is one, units of the larger scale if a side is a decimal, else as they
    are."""
    ka, kb = _kind(ta, a), _kind(tb, b)
    compare_check(op, ta, tb, a, b)
    if "float" in (ka, kb) and {ka, kb} <= {"float", "dec", "int"} \
            and "dec" in (ka, kb):
        return _as_float(a, ta), _as_float(b, tb)
    if "dec" in (ka, kb) and {ka, kb} <= {"dec", "int"}:
        sa, sb = _scale(ta), _scale(tb)
        s = max(sa, sb)
        return _rescale(a, s - sa, ovf), _rescale(b, s - sb, ovf)
    return a, b


def _arith(op: str, a, ta, b, tb, ovf: list) -> tuple:
    """``(values, dtype)`` of ``a op b``."""
    dt = arith_dtype(op, ta, tb, a, b)
    if dt.is_floating:
        a, b = _as_float(a, ta), _as_float(b, tb)
        return (a * b if op == "*" else a + b if op == "+" else a - b), dt
    if dt.id == TypeId.TIMESTAMP_DAYS:
        out = a + b if op == "+" else a - b
        return (out if isinstance(out, int)
                else out.astype(jnp.int32)), dt
    sa, sb = _scale(ta), _scale(tb)
    if op == "*":
        if not isinstance(a, int) or not isinstance(b, int):
            _flag(ovf, _estimate(a) * _estimate(b) >= OVERFLOW_UNITS)
        out = _i64(a) * _i64(b)
    else:
        s = max(sa, sb)
        a, b = _rescale(a, s - sa, ovf), _rescale(b, s - sb, ovf)
        if not isinstance(a, int) or not isinstance(b, int):
            _flag(ovf, _estimate(a) + _estimate(b) >= OVERFLOW_UNITS)
        out = _i64(a) + _i64(b) if op == "+" else _i64(a) - _i64(b)
    return out, dt


def _i64(v):
    return v if isinstance(v, int) else v.astype(jnp.int64)


def evaluate(expr, table, ovf: list) -> tuple:
    """``(values, valid_or_None, dtype)`` of ``expr`` over ``table``:
    comparisons and booleans give bool data, arithmetic its type's
    (``dtype`` None: a plain literal, typed by what it meets).  A STRING
    column comes back as its Column (compared by ``ops.strings.equal``)."""
    from ..columnar import Column
    head = expr[0]
    if head == "col":
        c = table.column(expr[1])
        if c.dtype.is_string:
            return c, c.validity, c.dtype
        vals = c.float_values() if c.dtype.is_floating else c.data
        return vals, c.validity, c.dtype
    if head in ("lit", "lit_decimal", "lit_date"):
        value, dt = literal(expr)
        return value, None, dt
    if head == "not":
        v, valid, _ = evaluate(expr[1], table, ovf)
        return jnp.logical_not(v), valid, BOOL8
    a, avalid, ta = evaluate(expr[1], table, ovf)
    b, bvalid, tb = evaluate(expr[2], table, ovf)
    valid = avalid if bvalid is None else \
        (bvalid if avalid is None else avalid & bvalid)
    if head in ARITH_OPS:
        if isinstance(a, Column) or isinstance(b, Column):
            raise ExprTypeError("arithmetic-over-string",
                                f"arithmetic {head!r} over a STRING column")
        out, dt = _arith(head, a, ta, b, tb, ovf)
        return out, valid, dt
    if isinstance(a, Column) or isinstance(b, Column):
        # STRING operand: chars/offsets need the dedicated equality kernel;
        # found by the plan-space fuzzer — ("!=", col(<str>), lit(<str>))
        # previously compared the raw chars buffer against the literal
        if head not in ("==", "!="):
            raise ValueError(
                f"string comparison {head!r} unsupported (only ==/!=; "
                f"verify() rejects ordering comparisons over strings)")
        from ..ops import strings as _strings
        scol, other = (a, b) if isinstance(a, Column) else (b, a)
        eq = jnp.asarray(_strings.equal(scol, other).data, jnp.bool_)
        return (eq if head == "==" else jnp.logical_not(eq)), valid, BOOL8
    if head == "&":
        return jnp.logical_and(a, b), valid, BOOL8
    if head == "|":
        return jnp.logical_or(a, b), valid, BOOL8
    if head not in COMPARISONS:
        raise ValueError(f"unknown expression op {head!r}")
    a, b = _align(head, a, ta, b, tb, ovf)
    return COMPARISONS[head](a, b), valid, BOOL8


def column_of(vals, valid, dt: Optional[DType], rows: int):
    """A computed output as a Column of ``rows`` rows (a literal
    broadcast)."""
    from ..columnar import Column
    if dt is None or isinstance(vals, (int, float, bool)):
        v = vals
        if dt is None:
            dt = BOOL8 if isinstance(v, bool) else \
                FLOAT64 if isinstance(v, float) else INT64
        vals = jnp.full((rows,), v, dt.device_storage if not dt.is_floating
                        else jnp.float64)
    if dt.is_floating:
        return Column.fixed(FLOAT64, jnp.asarray(vals, jnp.float64),
                            validity=valid)
    return Column(dt, data=jnp.asarray(vals, dt.device_storage),
                  validity=valid)


def project(table, items: tuple, ovf: list):
    """``Project``'s output over ``table``: each ``(name, expr)`` a child
    column (as it is) or a computed one."""
    from ..columnar import Table
    cols = []
    for name, e in items:
        if e[0] == "col":
            cols.append(table.column(e[1]))
            continue
        vals, valid, dt = evaluate(e, table, ovf)
        cols.append(column_of(vals, valid, dt, table.num_rows))
    return Table(cols, [n for n, _ in items])


def sum_check(col, mask, ovf: list) -> None:
    """The overflow check of a decimal ``sum`` over ``col``'s rows under
    ``mask``: every partial sum is bounded by the sum of magnitudes."""
    est = jnp.sum(jnp.where(mask & col.valid_mask(), _estimate(col.data),
                            np.float32(0)))
    _flag(ovf, est >= OVERFLOW_UNITS)


def any_flag(ovf: list):
    """The one flag of a program: None when nothing was checked."""
    if not ovf:
        return None
    return jnp.any(jnp.stack([jnp.asarray(f) for f in ovf]))


def raise_if_overflow(flag) -> None:
    """Fail the query when a flag — fetched by the caller, with the result
    it rides — is set."""
    if flag is None or not np.any(np.asarray(flag)):
        return
    from ..utils import metrics
    from ..utils.errors import DecimalOverflowError
    metrics.count("engine.decimal.overflow")
    raise DecimalOverflowError(
        f"a decimal value or sum reached {OVERFLOW_UNITS:.0f} units, past "
        "what the engine's int64 storage holds with its checked margin")


def decimal_sums(aggs, table) -> list:
    """The input columns of the decimal sums among ``aggs`` (``(column,
    op)`` pairs; a mean sums too): what ``sum_check`` guards."""
    return [c for c, op in aggs if op in ("sum", "mean")
            and table.column(c).dtype.is_decimal]
