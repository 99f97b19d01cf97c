"""NDS q5-lite, store channel: the deployment `chip_smoke.py` proved on a
v5e, copied here so that later PRs may change the program and not the
yardstick.

    semi join store_sales against the date window -> sum/count by store
    -> join store -> sum by s_mgr -> sort

A query module gives the harness four things: the tables (from a seed),
the plan (from the tables' paths and the traffic's parameters), the plain
pandas reference of the same semantics, and the bytes one streamed chunk
has to move.  It imports nothing of the program but the plan vocabulary,
and only inside ``plan``.
"""

from __future__ import annotations

import numpy as np
import pandas as pd

FACT = "store_sales"            # the table `fact_rows_per_s` counts
D_DATE_SK0 = 2_415_022          # date_dim's first d_date_sk (1900-01-02)
SOLD_LO, SOLD_HI = 2_450_816, 2_452_642   # store_sales' sold-date domain
# prices are whole multiples of 1/4096 up to 20,000 (the spec's decimal(7,2)
# range, on a dyadic grid): up to 27 significant bits each, so every float64
# partial sum over the whole table (< 2**48 units) is exact, the result does
# not depend on the order of summation and compares bit for bit — while a
# float32 cannot hold even one such value, so a float32 path fails on a
# group of a single row
PRICE_UNIT = 4096
PRICE_MAX_UNITS = 20_000 * PRICE_UNIT


def sold_dates(n: int) -> np.ndarray:
    """``n`` sale dates in order, spread evenly over the sold-date domain:
    every day has n / 1,827 rows (to one), so a date window holds the same
    number of rows whatever the seed."""
    days = SOLD_HI - SOLD_LO + 1
    return SOLD_LO + np.arange(n, dtype=np.int64) * days // n


def tables(seed: int, rows: dict) -> dict:
    """The three frames, from the seed.  ``rows`` gives each table's row
    count (the configuration's, or a rehearsal's cut of the fact).  Every
    seed gives the same sizes — rows per day, groups, build-table rows —
    with other keys and prices: a seed must not change the work, or the
    shapes the programs were compiled for."""
    rng = np.random.default_rng(seed)
    n = rows["store_sales"]
    store_sk = rng.integers(1, rows["store"] + 1, n).astype(np.int64)
    price = rng.integers(2, PRICE_MAX_UNITS + 1, n).astype(np.float64) \
        / PRICE_UNIT
    sk = np.arange(1, rows["store"] + 1, dtype=np.int64)
    return {
        "store_sales": pd.DataFrame({"ss_sold_date_sk": sold_dates(n),
                                     "ss_store_sk": store_sk,
                                     "ss_ext_sales_price": price}),
        "date_dim": pd.DataFrame({"d_date_sk": np.arange(
            D_DATE_SK0, D_DATE_SK0 + rows["date_dim"], dtype=np.int64)}),
        "store": pd.DataFrame({"s_store_sk": sk, "s_mgr": sk % 4}),
    }


def plan(paths: dict, params: dict, chunk_bytes: int):
    from spark_rapids_jni_tpu.engine import (Aggregate, Filter, Join, Scan,
                                             Sort, col, lit)
    dates = Filter(Scan(paths["date_dim"]),
                   ("&", (">=", col("d_date_sk"), lit(params["window_lo"])),
                    ("<=", col("d_date_sk"), lit(params["window_hi"]))))
    kept = Join(Scan(paths["store_sales"], chunk_bytes=chunk_bytes), dates,
                ["ss_sold_date_sk"], ["d_date_sk"], how="semi")
    # the fact-side range: the optimizer turns it into a row-group pruning
    # hint, so row groups of the date-ordered file outside it are skipped
    pred = (">=", col("ss_sold_date_sk"), lit(params["fact_lo"]))
    if "fact_hi" in params:
        pred = ("&", pred, ("<=", col("ss_sold_date_sk"),
                            lit(params["fact_hi"])))
    totals = Aggregate(Filter(kept, pred), ["ss_store_sk"],
                       [("ss_ext_sales_price", "sum"),
                        ("ss_ext_sales_price", "count")],
                       names=["sales", "n"])
    joined = Join(totals, Scan(paths["store"]), ["ss_store_sk"],
                  ["s_store_sk"], how="inner")
    return Sort(Aggregate(joined, ["s_mgr"],
                          [("sales", "sum"), ("n", "sum")],
                          names=["sales", "n"]),
                (("s_mgr", True),))


def reference(frames: dict, params: dict,
              float_dtype=np.float64) -> pd.DataFrame:
    """Plain pandas.  ``float_dtype`` is float64, the precision the
    configuration states; the control computes in float32."""
    s, d, st = frames["store_sales"], frames["date_dim"], frames["store"]
    d = d[(d.d_date_sk >= params["window_lo"])
          & (d.d_date_sk <= params["window_hi"])]
    keep = s.ss_sold_date_sk.isin(d.d_date_sk) \
        & (s.ss_sold_date_sk >= params["fact_lo"])
    if "fact_hi" in params:
        keep &= s.ss_sold_date_sk <= params["fact_hi"]
    kept = s[keep].assign(
        ss_ext_sales_price=s.ss_ext_sales_price[keep].astype(float_dtype))
    totals = kept.groupby("ss_store_sk").agg(
        sales=("ss_ext_sales_price", "sum"),
        n=("ss_ext_sales_price", "count")).reset_index()
    joined = totals.merge(st, left_on="ss_store_sk", right_on="s_store_sk")
    out = joined.groupby("s_mgr").agg(sales=("sales", "sum"), n=("n", "sum")) \
        .reset_index().sort_values("s_mgr").reset_index(drop=True)
    return out.astype({"sales": np.float64, "n": np.int64})


def chunk_bytes_needed(chunk_rows: float, rows: dict) -> float:
    """Bytes one streamed chunk's work has to move through HBM whatever
    implements it: the three 8-byte fact columns in once, the partial
    aggregate (store, sum, count; one row per store) out once.  ``rows``
    is the configuration's table of row counts."""
    return chunk_rows * 3 * 8 + rows["store"] * 3 * 8
