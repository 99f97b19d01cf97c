"""TPC-DS query 55, nearly verbatim ("lite": ``i_brand`` is left out —
a string column knocks a plan off the fused path, `engine/segment.py`).

    select i_brand_id, sum(ss_ext_sales_price) ext_price
    from date_dim, store_sales, item
    where d_date_sk = ss_sold_date_sk and ss_item_sk = i_item_sk
      and i_manager_id = 28 and d_moy = 11 and d_year = 1999
    group by i_brand_id order by ext_price desc, i_brand_id limit 100

What it forces beside q5-lite: a streamed inner probe join that carries a
payload column (``i_brand_id``) into the group key, a mid-cardinality
group-by, and top-k.  Same four functions as every query module.
"""

from __future__ import annotations

import numpy as np
import pandas as pd

FACT = "store_sales"
D_DATE_SK0 = 2_415_022          # date_dim's first d_date_sk (1900-01-02)
SOLD_LO, SOLD_HI = 2_450_816, 2_452_642
PRICE_UNIT = 4096               # multiples of 1/4096: see nds_q5lite.py
PRICE_MAX_UNITS = 20_000 * PRICE_UNIT
BRAND_DOMAIN = 1_000            # the spec's i_brand_id domain, about
MANAGERS = 100                  # i_manager_id 1..100
BRANDS_PER_MANAGER = 150        # distinct brands among one manager's items


def tables(seed: int, rows: dict) -> dict:
    """Every seed gives the same sizes, with other keys and prices (a seed
    must not change the work, or the shapes the programs were compiled
    for): each day has the same number of sales; the sales go round a
    seeded permutation of the items, so each item sells once in any
    ``items`` consecutive rows (every item sells in every month at SF1);
    each manager has items / 100 items, of exactly 150 distinct brands."""
    rng = np.random.default_rng(seed)
    n, items = rows["store_sales"], rows["item"]
    days = SOLD_HI - SOLD_LO + 1
    sold = SOLD_LO + np.arange(n, dtype=np.int64) * days // n
    order = rng.permutation(items)
    item_sk = (order[(np.arange(n) + rng.integers(items)) % items] + 1) \
        .astype(np.int64)
    price = rng.integers(2, PRICE_MAX_UNITS + 1, n).astype(np.float64) \
        / PRICE_UNIT
    # the k-th item (in a seeded order) belongs to manager k % 100 + 1 and
    # is that manager's (k // 100)-th: its brand is one of the manager's 150
    rank = np.empty(items, np.int64)
    rank[rng.permutation(items)] = np.arange(items)
    manager = rank % MANAGERS + 1
    shift = rng.integers(0, BRAND_DOMAIN, MANAGERS + 1)
    brand = 1_001_001 + ((rank // MANAGERS) % BRANDS_PER_MANAGER * 6
                         + shift[manager]) % BRAND_DOMAIN
    d = np.arange(rows["date_dim"])
    dates = np.datetime64("1900-01-02") + d.astype("timedelta64[D]")
    months = dates.astype("datetime64[M]").astype(np.int64)
    return {
        "store_sales": pd.DataFrame({"ss_sold_date_sk": sold,
                                     "ss_item_sk": item_sk,
                                     "ss_ext_sales_price": price}),
        "date_dim": pd.DataFrame({
            "d_date_sk": (D_DATE_SK0 + d).astype(np.int64),
            "d_year": (1970 + months // 12).astype(np.int64),
            "d_moy": (months % 12 + 1).astype(np.int64)}),
        "item": pd.DataFrame({
            "i_item_sk": np.arange(1, items + 1, dtype=np.int64),
            "i_brand_id": brand.astype(np.int64),
            "i_manager_id": manager.astype(np.int64)}),
    }


def plan(paths: dict, params: dict, chunk_bytes: int):
    from spark_rapids_jni_tpu.engine import (Aggregate, Filter, Join, Limit,
                                             Scan, Sort, col, lit)
    dates = Filter(Scan(paths["date_dim"]),
                   ("&", ("==", col("d_year"), lit(params["d_year"])),
                    ("==", col("d_moy"), lit(params["d_moy"]))))
    items = Filter(Scan(paths["item"]),
                   ("==", col("i_manager_id"), lit(params["i_manager_id"])))
    sales = Scan(paths["store_sales"], chunk_bytes=chunk_bytes)
    in_month = Join(sales, dates, ["ss_sold_date_sk"], ["d_date_sk"],
                    how="inner")
    managed = Join(in_month, items, ["ss_item_sk"], ["i_item_sk"],
                   how="inner")
    by_brand = Aggregate(managed, ["i_brand_id"],
                         [("ss_ext_sales_price", "sum")], names=["ext_price"])
    return Limit(Sort(by_brand, (("ext_price", False), ("i_brand_id", True))),
                 params["limit"])


def reference(frames: dict, params: dict,
              float_dtype=np.float64) -> pd.DataFrame:
    s, d, it = frames["store_sales"], frames["date_dim"], frames["item"]
    d = d[(d.d_year == params["d_year"]) & (d.d_moy == params["d_moy"])]
    it = it[it.i_manager_id == params["i_manager_id"]]
    j = s.merge(d, left_on="ss_sold_date_sk", right_on="d_date_sk") \
         .merge(it, left_on="ss_item_sk", right_on="i_item_sk")
    j = j.assign(ss_ext_sales_price=j.ss_ext_sales_price.astype(float_dtype))
    out = j.groupby("i_brand_id").agg(
        ext_price=("ss_ext_sales_price", "sum")).reset_index()
    out = out.astype({"ext_price": np.float64, "i_brand_id": np.int64})
    return out.sort_values(["ext_price", "i_brand_id"],
                           ascending=[False, True]) \
        .head(params["limit"]).reset_index(drop=True)


def chunk_bytes_needed(chunk_rows: float, rows: dict) -> float:
    """The three 8-byte fact columns in once, the partial aggregate
    (brand, sum; at most one row per brand) out once.  The build tables
    (30 dates, some 180 items) stay on the device and are not counted:
    bytes the work needs, not bytes a particular join moves."""
    return chunk_rows * 3 * 8 + min(BRAND_DOMAIN, rows["item"]) * 2 * 8
