"""TPC-H Query 3 ("Shipping Priority"), as published:

    select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
           o_orderdate, o_shippriority
    from customer, orders, lineitem
    where c_mktsegment = '[SEGMENT]' and c_custkey = o_custkey
      and l_orderkey = o_orderkey
      and o_orderdate < date '[DATE]' and l_shipdate > date '[DATE]'
    group by l_orderkey, o_orderdate, o_shippriority
    order by revenue desc, o_orderdate
    limit 10

over dbgen's types (``dss.ddl``).  The dimension side (``customer`` by
segment, ``orders`` before the date, their join) is one build of nearly a
tenth of the orders (145,761 rows at SF1); ``lineitem`` streams through a
probe of that build, a decimal product and a three-key group-by; the top
10 come last.

The same four functions as every query module, and ``probe_bytes_needed``:
what the streamed probe alone has to move.  Imports nothing of the program
but the plan vocabulary, only inside ``plan``, and the one name
``engine_can_run`` looks for.
"""

from __future__ import annotations

import datetime
import importlib.util
import os

import numpy as np
import pandas as pd


def _sibling(name: str):
    """``benchmarks/queries/<name>.py``, loaded from this file's directory
    whatever is on the path."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location(f"tpch_q3_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


Q6 = _sibling("tpch_q6")        # lineitem's distributions and decimal helpers

FACT = "lineitem"               # the table `fact_rows_per_s` counts
SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY")
KEYS_USED, KEYS_SPAN = 8, 32    # dbgen's sparse o_orderkey: 8 of every 32
ORDER_DAYS = (Q6.ORDER_HI - Q6.ORDER_LO).days + 1      # 2,405
BLOCK = len(SEGMENTS) * ORDER_DAYS      # (segment, order day) cells: 12,025
KEYS = ["l_orderkey", "o_orderdate", "o_shippriority"]
OUT = ["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]


def _lines_per_order(rng, orders: int, lines: int) -> np.ndarray:
    """1-7 lines per order, moved by one line on as few orders as it takes
    for the total to be exactly ``lines``."""
    per = rng.integers(1, 8, orders)
    diff = lines - int(per.sum())
    room = np.flatnonzero(per < 7 if diff > 0 else per > 1)
    assert abs(diff) <= len(room), "lines per order out of 1-7"
    per[rng.choice(room, abs(diff), replace=False)] += np.sign(diff)
    return per


def _dealt(rng, n: int) -> np.ndarray:
    """Each of ``n`` orders' place in the (segment, order day) grid of
    ``BLOCK`` cells: every whole block of ``BLOCK`` consecutive orders
    holds each cell once, in a seeded order, and the last, partial block
    holds evenly spaced cells.  So every seed puts the same number of
    orders in every cell, and every chunk of the file nearly so."""
    whole, rest = divmod(n, BLOCK)
    cells = [rng.permutation(BLOCK) for _ in range(whole)]
    cells.append(rng.permutation(np.arange(rest) * BLOCK // max(rest, 1)))
    return np.concatenate(cells)


def _segments(rng, custkeys: np.ndarray) -> np.ndarray:
    """Each customer's segment index: the customers who place orders (key
    not a multiple of 3) and the others dealt over the five segments
    apart, each in equal shares."""
    seg = np.empty(len(custkeys), np.int64)
    for part in (custkeys % 3 != 0, custkeys % 3 == 0):
        idx = np.flatnonzero(part)
        seg[idx] = rng.permutation(len(idx)) % len(SEGMENTS)
    return seg


def engine_can_run() -> None:
    """Fail at once on an engine that cannot run this cell in a run's
    time: one without the aggregate's build-row form
    (``engine.segment.build_row_join``).  Such an engine vetoes the chunk
    program wherever two of the build's 145,761 keys share a 32-bit hash
    (about 92 % of seeds) and interprets every chunk, compiling its eager
    sorts anew; elsewhere it sorts three keys of 262,144 rows a chunk, a
    program that took 2,392 s to compile on a TPU v5e."""
    from spark_rapids_jni_tpu.engine import segment
    if not hasattr(segment, "build_row_join"):
        raise RuntimeError("this engine has no build-row aggregate "
                           "(engine.segment.build_row_join): TPC-H Q3 at "
                           "SF1 would not end a query within a run")


def tables(seed: int, rows: dict) -> dict:
    """``lineitem`` in ``l_orderkey`` order, ``orders`` and ``customer``
    from the seed.  Every seed gives the same row counts at every step of
    the plan — the segment's customers, the orders before any date, the
    build — and so the same programs, with other keys and values.  Asks
    ``engine_can_run`` first."""
    import pyarrow as pa
    engine_can_run()
    rng = np.random.default_rng(seed)
    n, n_orders, n_cust = rows["lineitem"], rows["orders"], rows["customer"]
    per = _lines_per_order(rng, n_orders, n)
    i = np.arange(n_orders, dtype=np.int64)
    orderkey = i // KEYS_USED * KEYS_SPAN + i % KEYS_USED + 1
    custkeys = np.arange(1, n_cust + 1, dtype=np.int64)
    cust_seg = _segments(rng, custkeys)
    cell = _dealt(rng, n_orders)
    orderdate = (Q6.ORDER_LO - Q6.EPOCH).days + cell % ORDER_DAYS
    # a customer of the order's segment, uniform among those who order
    custkey = np.empty(n_orders, np.int64)
    for s in range(len(SEGMENTS)):
        pool = custkeys[(custkeys % 3 != 0) & (cust_seg == s)]
        mine = np.flatnonzero(cell // ORDER_DAYS == s)
        custkey[mine] = pool[rng.integers(0, len(pool), len(mine))]
    order_of = np.repeat(i, per)
    ship = orderdate[order_of] + rng.integers(1, 122, n)
    qty = rng.integers(1, 51, n).astype(np.int64)
    disc = rng.integers(0, 11, n).astype(np.int64)          # hundredths
    partkey = rng.integers(1, Q6.PARTS_PER_SF + 1, n).astype(np.int64)
    price = qty * Q6.retail_cents(partkey)                  # cents
    segment = np.asarray(SEGMENTS, dtype=object)[cust_seg]
    col = pd.arrays.ArrowExtensionArray

    def date(days):
        return col(pa.array(days.astype(np.int32), pa.int32())
                   .cast(pa.date32()))

    return {
        "lineitem": pd.DataFrame({
            "l_orderkey": orderkey[order_of],
            "l_extendedprice": col(Q6._decimal_array(price, 15, 2)),
            "l_discount": col(Q6._decimal_array(disc, 15, 2)),
            "l_shipdate": date(ship)}),
        "orders": pd.DataFrame({
            "o_orderkey": orderkey,
            "o_custkey": custkey,
            "o_orderdate": date(orderdate),
            "o_shippriority": np.zeros(n_orders, np.int32)}),
        "customer": pd.DataFrame({"c_custkey": custkeys,
                                  "c_mktsegment": segment}),
    }


def plan(paths: dict, params: dict, chunk_bytes: int):
    from spark_rapids_jni_tpu.engine import (Aggregate, Filter, Join, Limit,
                                             Project, Scan, Sort, col, lit,
                                             lit_date)
    day = lit_date(params["date"])
    customers = Filter(Scan(paths["customer"]),
                       ("==", col("c_mktsegment"), lit(params["segment"])))
    orders = Filter(Scan(paths["orders"]), ("<", col("o_orderdate"), day))
    placed = Join(orders, customers, ["o_custkey"], ["c_custkey"],
                  how="inner")
    lines = Filter(Scan(paths["lineitem"], chunk_bytes=chunk_bytes),
                   (">", col("l_shipdate"), day))
    joined = Join(lines, placed, ["l_orderkey"], ["o_orderkey"], how="inner")
    rev = Project(joined, KEYS[:1] + [
        ("rev", ("*", col("l_extendedprice"),
                 ("-", lit(1), col("l_discount"))))] + KEYS[1:])
    by_order = Aggregate(rev, KEYS, [("rev", "sum")], names=["revenue"])
    top = Limit(Sort(by_order, (("revenue", False), ("o_orderdate", True))),
                params["limit"])
    return Project(top, OUT)


def reference(frames: dict, params: dict,
              float_dtype=np.float64) -> pd.DataFrame:
    """Plain numpy and pandas over int64 units: ``revenue`` in units of
    10**-4 (the decimal(38,4) the plan's sum is), ``o_orderdate`` in days.
    ``float_dtype`` float64 is the exact computation the configuration
    states; the control passes float32 and then multiplies and sums in
    float32, rounding each sum to units."""
    day = (datetime.date.fromisoformat(params["date"]) - Q6.EPOCH).days
    cu, od, li = frames["customer"], frames["orders"], frames["lineitem"]
    in_segment = cu.c_custkey.to_numpy()[
        cu.c_mktsegment.to_numpy() == params["segment"]]
    odate = Q6._column_units(od, "o_orderdate")
    keep = (odate < day) & np.isin(od.o_custkey.to_numpy(), in_segment)
    build = pd.DataFrame({"l_orderkey": od.o_orderkey.to_numpy()[keep],
                          "o_orderdate": odate[keep].astype(np.int32),
                          "o_shippriority":
                              od.o_shippriority.to_numpy()[keep]})
    ship = Q6._column_units(li, "l_shipdate")
    late = ship > day
    price = Q6._column_units(li, "l_extendedprice")[late]
    disc = Q6._column_units(li, "l_discount")[late]
    if float_dtype == np.float64:
        rev = price * (100 - disc)                  # units of 10**-4
    else:
        rev = (price.astype(float_dtype) / float_dtype(100)) \
            * (float_dtype(1) - disc.astype(float_dtype) / float_dtype(100))
    lines = pd.DataFrame({"l_orderkey": li.l_orderkey.to_numpy()[late],
                          "revenue": rev})
    j = lines.merge(build, on="l_orderkey")
    out = j.groupby(KEYS, as_index=False, sort=False).revenue.sum()
    if float_dtype != np.float64:
        out["revenue"] = np.rint(out.revenue.to_numpy(np.float64) * 1e4)
    out["revenue"] = out.revenue.astype(np.int64)
    out = out.sort_values(["revenue", "o_orderdate"],
                          ascending=[False, True], kind="stable")
    n = params["limit"]
    edge = out.iloc[n - 1:n + 1][["revenue", "o_orderdate"]].to_numpy()
    assert float_dtype != np.float64 or len(edge) < 2 \
        or tuple(edge[0]) != tuple(edge[1]), \
        "two groups tie on (revenue, o_orderdate) across the limit"
    return out.head(n)[OUT].reset_index(drop=True)


def _build_rows(rows: dict) -> float:
    """The build's expected size: a fifth of the orders (one segment of
    five) placed before the date (1,169 of the 2,405 order days)."""
    return rows["orders"] / len(SEGMENTS) * 1_169 / ORDER_DAYS


def chunk_bytes_needed(chunk_rows: float, rows: dict) -> float:
    """Bytes one streamed chunk's work has to move through HBM whatever
    implements it: the chunk's key, two decimals and date in (28 bytes a
    row), the build's sorted keys and payload read once (8 + 4 + 4 bytes a
    build row), and the chunk's groups out, a key and a revenue sum (16
    bytes) each: the build's orders whose lines the chunk holds
    (``lineitem`` is in ``l_orderkey`` order, so the chunk's share of the
    build)."""
    groups = _build_rows(rows) * chunk_rows / rows["lineitem"]
    return chunk_rows * 28 + _build_rows(rows) * 16 + groups * 16


def probe_bytes_needed(chunk_rows: float, rows: dict) -> float:
    """Bytes the streamed probe alone has to move whatever implements it:
    the probe keys in (8 bytes a row), the build's keys and payload read
    once, the matched build row, the two payload columns and the match
    mask out (4 + 4 + 4 + 1 bytes a row)."""
    return chunk_rows * (8 + 13) + _build_rows(rows) * 16
