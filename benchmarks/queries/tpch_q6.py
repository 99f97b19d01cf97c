"""TPC-H Query 6 ("Forecasting Revenue Change"), as published:

    select sum(l_extendedprice * l_discount) as revenue from lineitem
    where l_shipdate >= date '[DATE]'
      and l_shipdate < date '[DATE]' + interval '1' year
      and l_discount between [DISCOUNT] - 0.01 and [DISCOUNT] + 0.01
      and l_quantity < [QUANTITY]

over dbgen's types (``dss.ddl``): the three measures DECIMAL(15,2), the ship
date a DATE.  The plan computes the product in the chunk program and sums
it with no group key; the answer is one exact decimal(38,4).

A query module gives the harness four things: the tables (from a seed),
the plan (from the tables' paths and the traffic's parameters), the plain
reference of the same semantics, and the bytes one streamed chunk has to
move.  It imports nothing of the program but the plan vocabulary, and only
inside ``plan``.
"""

from __future__ import annotations

import datetime

import numpy as np
import pandas as pd

FACT = "lineitem"               # the table `fact_rows_per_s` counts
EPOCH = datetime.date(1970, 1, 1)
ORDER_LO = datetime.date(1992, 1, 1)      # STARTDATE
ORDER_HI = datetime.date(1998, 8, 2)      # ENDDATE - 151 days
PARTS_PER_SF = 200_000


def _days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - EPOCH).days


def _units(text: str, scale: int) -> int:
    """An exact decimal's text in units of ``10**-scale``."""
    from decimal import Decimal
    return int(Decimal(text).scaleb(scale))


def retail_cents(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE in cents: 90000 + ((key/10) mod 20001) + 100 (key mod
    1000)."""
    return 90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1_000)


def _decimal_array(units: np.ndarray, precision: int, scale: int):
    """An Arrow decimal128 array whose unscaled values are ``units``."""
    import pyarrow as pa
    u = units.astype(np.int64)
    limbs = np.empty((len(u), 2), np.int64)
    limbs[:, 0] = u
    limbs[:, 1] = np.where(u < 0, -1, 0)
    return pa.Array.from_buffers(pa.decimal128(precision, scale), len(u),
                                 [None, pa.py_buffer(limbs.tobytes())])


def tables(seed: int, rows: dict) -> dict:
    """``lineitem`` in ``l_orderkey`` order from the seed, with the spec's
    distributions: orders of 1-7 lines, an order date uniform over
    [1992-01-01, 1998-08-02] and each line shipped 1-121 days after it;
    quantity 1-50, discount 0.00-0.10, extended price = quantity x the
    part's retail price.  Every seed gives the same row count (and so the
    same row groups and chunks) with other values."""
    import pyarrow as pa
    rng = np.random.default_rng(seed)
    n = rows["lineitem"]
    lines = rng.integers(1, 8, n)                   # lines per order
    orders = int(np.searchsorted(np.cumsum(lines), n)) + 1
    order_of = np.repeat(np.arange(orders), lines[:orders])[:n]
    span = (ORDER_HI - ORDER_LO).days
    orderdate = (ORDER_LO - EPOCH).days + rng.integers(0, span + 1, orders)
    ship = (orderdate[order_of] + rng.integers(1, 122, n)).astype(np.int32)
    qty = rng.integers(1, 51, n).astype(np.int64)
    disc = rng.integers(0, 11, n).astype(np.int64)          # hundredths
    partkey = rng.integers(1, PARTS_PER_SF + 1, n).astype(np.int64)
    price = qty * retail_cents(partkey)                     # cents
    col = pd.arrays.ArrowExtensionArray
    return {"lineitem": pd.DataFrame({
        "l_quantity": col(_decimal_array(qty * 100, 15, 2)),
        "l_extendedprice": col(_decimal_array(price, 15, 2)),
        "l_discount": col(_decimal_array(disc, 15, 2)),
        "l_shipdate": col(pa.array(ship, pa.int32()).cast(pa.date32())),
    })}


def plan(paths: dict, params: dict, chunk_bytes: int):
    from spark_rapids_jni_tpu.engine import (Aggregate, Filter, Project, Scan,
                                             col, lit, lit_date, lit_decimal)
    ship = col("l_shipdate")
    disc = col("l_discount")
    # `between 0.06 - 0.01 and 0.06 + 0.01`, folded as Spark folds it
    pred = ("&", ("&", (">=", ship, lit_date(params["ship_lo"])),
                  ("<", ship, lit_date(params["ship_hi_excl"]))),
            ("&", ("&", (">=", disc, lit_decimal(params["disc_lo"])),
                   ("<=", disc, lit_decimal(params["disc_hi"]))),
             ("<", col("l_quantity"), lit(params["qty_lt"]))))
    rev = Project(Filter(Scan(paths["lineitem"], chunk_bytes=chunk_bytes),
                         pred),
                  [("rev", ("*", col("l_extendedprice"), disc))])
    return Aggregate(rev, [], [("rev", "sum")], names=["revenue"])


def _column_units(frame: pd.DataFrame, name: str) -> np.ndarray:
    """A decimal column's unscaled int64 values, a date column's days."""
    import pyarrow as pa
    arr = pa.chunked_array(frame[name].array.__arrow_array__()) \
        .combine_chunks()
    if pa.types.is_date32(arr.type):
        return arr.cast(pa.int32()).to_numpy().astype(np.int64)
    limbs = np.frombuffer(arr.buffers()[1], np.int64,
                          2 * len(arr), 16 * arr.offset).reshape(-1, 2)
    return limbs[:, 0].copy()


def reference(frames: dict, params: dict,
              float_dtype=np.float64) -> pd.DataFrame:
    """Plain numpy over int64 units: ``revenue`` in units of 10**-4 (the
    decimal(38,4) the plan's sum is).  ``float_dtype`` float64 is the exact
    computation the configuration states; the control passes float32 and
    then multiplies and sums in float32, rounding the sum to units."""
    li = frames["lineitem"]
    ship = _column_units(li, "l_shipdate")
    qty = _column_units(li, "l_quantity")
    disc = _column_units(li, "l_discount")
    price = _column_units(li, "l_extendedprice")
    keep = (ship >= _days(params["ship_lo"])) \
        & (ship < _days(params["ship_hi_excl"])) \
        & (disc >= _units(params["disc_lo"], 2)) \
        & (disc <= _units(params["disc_hi"], 2)) \
        & (qty < params["qty_lt"] * 100)
    if float_dtype == np.float64:
        revenue = int(np.sum(price[keep] * disc[keep]))
    else:
        prod = (price[keep].astype(float_dtype) / float_dtype(100)) \
            * (disc[keep].astype(float_dtype) / float_dtype(100))
        total = np.sum(prod, dtype=float_dtype)
        revenue = int(np.rint(np.float64(total) * 1e4))
    return pd.DataFrame({"revenue": np.array([revenue], np.int64)})


def chunk_bytes_needed(chunk_rows: float, rows: dict) -> float:
    """Bytes one streamed chunk's work has to move through HBM whatever
    implements it: the three 8-byte decimal columns and the 4-byte date in
    once, the one-row partial (its sum and its overflow flag) out."""
    return chunk_rows * (3 * 8 + 4) + 16
