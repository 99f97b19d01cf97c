#!/usr/bin/env python3
"""Chip smoke: serve a TPC-DS SF1-scale query from the accelerator, through
the bridge, and check every answer.

    python chip_smoke.py             # one chip: the served path, end to end
    python chip_smoke.py --chips 4   # four chips: the exchange across a mesh

This process is a pure bridge client (numpy, pyarrow, pandas, sockets): it
initialises no jax backend.  ONE server child, started the way any client
starts one (``spawn_server``), holds the chip(s); platform, device kind and
count are what that child reports over the bridge.

Data (from ``--seed``) is the NDS q5-lite warehouse at TPC-DS SF1
cardinalities — ``store_sales`` 2,880,404 rows, ``date_dim`` 73,049,
``store`` 12 — written here as snappy Parquet with several row groups.
Every result is compared exactly with a pandas computation of the same
query; the float column is quarter-valued so sums are order-independent.

The last line of stdout is ``{"ok": true, "device": {...}}`` only when every
phase passed AND the server computed on a TPU.  The platform verdict is the
last check, so a CPU run rehearses every request and comparison, prints
``smoke: all results equal ...`` and then fails.  Timings printed here are
smoke timings, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq

from spark_rapids_jni_tpu.bridge import BridgeClient, spawn_server
from spark_rapids_jni_tpu.dtypes import FLOAT64, INT64
from spark_rapids_jni_tpu.engine import (Aggregate, Filter, Join, Scan, Sort,
                                         col, lit)

# TPC-DS SF1 (specification v3, table 3-2 row counts)
SF1_STORE_SALES = 2_880_404
SF1_DATE_DIM = 73_049
SF1_STORE = 12
MIN_ROWS = 2_000_000            # the floor every earlier record used
D_DATE_SK0 = 2_415_022          # date_dim's first d_date_sk (1900-01-02)
SOLD_LO, SOLD_HI = 2_450_816, 2_452_642   # store_sales' sold-date domain
# q5's date window, widened from 14 days to the year 2000 so a fifth of the
# fact rows survive the semi join
WIN_LO, WIN_HI = 2_451_545, 2_451_910
# fact-side range predicate: the optimizer turns it into a row-group
# pruning hint, so the first row groups of the date-ordered file are skipped
FACT_LO = 2_451_000
ROW_GROUPS = 12
CHUNK_BYTES = 8 << 20           # one row group (6.5 MB decoded) per chunk


_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    log(f"  ok: {what}")


# -- data ---------------------------------------------------------------------

def make_warehouse(root: str, rows: int, seed: int) -> dict:
    """Write the three tables; returns the pandas frames (the reference)."""
    rng = np.random.default_rng(seed)
    sold = np.sort(rng.integers(SOLD_LO, SOLD_HI + 1, rows)).astype(np.int64)
    store_sk = rng.integers(1, SF1_STORE + 1, rows).astype(np.int64)
    # quarter-valued prices: every partial sum is exact in float64, so the
    # result does not depend on summation order and compares bit-exactly
    price = rng.integers(2, 1200, rows).astype(np.float64) / 4.0
    sales = pd.DataFrame({"ss_sold_date_sk": sold, "ss_store_sk": store_sk,
                          "ss_ext_sales_price": price})
    dates = pd.DataFrame({"d_date_sk": np.arange(
        D_DATE_SK0, D_DATE_SK0 + SF1_DATE_DIM, dtype=np.int64)})
    sk = np.arange(1, SF1_STORE + 1, dtype=np.int64)
    store = pd.DataFrame({"s_store_sk": sk, "s_mgr": sk % 4})
    paths = {}
    for name, df, rg in (("store_sales", sales, -(-rows // ROW_GROUPS)),
                         ("date_dim", dates, SF1_DATE_DIM),
                         ("store", store, SF1_STORE)):
        paths[name] = os.path.join(root, f"{name}.parquet")
        pq.write_table(pa.Table.from_pandas(df, preserve_index=False),
                       paths[name], compression="snappy", row_group_size=rg)
    n_groups = pq.ParquetFile(paths["store_sales"]).metadata.num_row_groups
    check(n_groups > 1, f"store_sales has {n_groups} row groups")
    return {"paths": paths, "sales": sales, "dates": dates, "store": store}


def q5_lite(paths: dict):
    dates = Filter(Scan(paths["date_dim"]),
                   ("&", (">=", col("d_date_sk"), lit(WIN_LO)),
                    ("<=", col("d_date_sk"), lit(WIN_HI))))
    sales = Scan(paths["store_sales"], chunk_bytes=CHUNK_BYTES)
    kept = Filter(Join(sales, dates, ["ss_sold_date_sk"], ["d_date_sk"],
                       how="semi"),
                  (">=", col("ss_sold_date_sk"), lit(FACT_LO)))
    totals = Aggregate(kept, ["ss_store_sk"],
                       [("ss_ext_sales_price", "sum"),
                        ("ss_ext_sales_price", "count")],
                       names=["sales", "n"])
    joined = Join(totals, Scan(paths["store"]), ["ss_store_sk"],
                  ["s_store_sk"], how="inner")
    return Sort(Aggregate(joined, ["s_mgr"],
                          [("sales", "sum"), ("n", "sum")],
                          names=["sales", "n"]),
                (("s_mgr", True),))


def q5_lite_pandas(wh: dict) -> pd.DataFrame:
    s, d, st = wh["sales"], wh["dates"], wh["store"]
    d = d[(d.d_date_sk >= WIN_LO) & (d.d_date_sk <= WIN_HI)]
    kept = s[s.ss_sold_date_sk.isin(d.d_date_sk)
             & (s.ss_sold_date_sk >= FACT_LO)]
    totals = kept.groupby("ss_store_sk").agg(
        sales=("ss_ext_sales_price", "sum"),
        n=("ss_ext_sales_price", "count")).reset_index()
    joined = totals.merge(st, left_on="ss_store_sk", right_on="s_store_sk")
    return joined.groupby("s_mgr").agg(sales=("sales", "sum"),
                                       n=("n", "sum")) \
        .reset_index().sort_values("s_mgr").reset_index(drop=True)


def equal_to_reference(cols: list, want: pd.DataFrame, what: str) -> None:
    check(len(cols) == want.shape[1], f"{what}: {want.shape[1]} columns")
    for (dtype, data, validity), name in zip(cols, want.columns):
        ref = want[name].to_numpy()
        check(validity is None or bool(validity.all()),
              f"{what}.{name}: no nulls")
        check(data.shape == ref.shape
              and data.dtype == ref.dtype and np.array_equal(data, ref),
              f"{what}.{name}: {len(ref)} values equal the reference exactly")


# -- metrics --------------------------------------------------------------------

COUNTERS = ("engine.segment.compile", "engine.segment.replay",
            "engine.segment_cache.hit", "engine.segment_cache.miss",
            "engine.fused_stage_cache.miss", "engine.build_cache.hit",
            "engine.build_cache.miss", "engine.plan_cache.hit",
            "engine.plan_cache.miss", "engine.host_sync",
            "engine.degraded", "engine.retries",
            "io.device_decode.fallbacks", "io.parquet.chunks",
            "engine.exchange.shuffles", "engine.exchange.broadcasts")


def counters_of(m: dict) -> dict:
    return {k: int(m["counters"].get(k, 0)) for k in COUNTERS}


def no_hidden_fallback(m: dict) -> None:
    c = m["counters"]
    degraded = {k: v for k, v in c.items()
                if k.startswith("engine.degraded") and v}
    check(not degraded, f"engine.degraded is 0 ({degraded or 'no rung taken'})")
    check(int(c.get("io.device_decode.fallbacks", 0)) == 0,
          "io.device_decode.fallbacks is 0")
    check(int(m["errors"]) == 0, "server errors is 0")


# -- phases -----------------------------------------------------------------------

def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def run_one_chip(sock: str, wh: dict) -> dict:
    paths = wh["paths"]
    plan = q5_lite(paths)
    want = q5_lite_pandas(wh)
    log(f"reference (pandas): {want.to_dict('list')}")

    c = BridgeClient(sock, timeout=900)
    # (a) cold PLAN_EXECUTE, (b) the same plan again
    (h_cold,), t_cold = timed(lambda: c.execute_plan(plan))
    m_cold = c.metrics()
    (h_warm,), t_warm = timed(lambda: c.execute_plan(plan))
    m_warm = c.metrics()
    log(f"smoke timing: plan cold {t_cold:.3f} s, warm {t_warm:.3f} s")
    cc, cw = counters_of(m_cold), counters_of(m_warm)
    log(f"counters after cold: {json.dumps(cc)}")
    log(f"counters after warm: {json.dumps(cw)}")
    log(f"last_plan: {json.dumps(m_warm['last_plan'])}")
    check(m_warm["plan_cache"]["hits"] == m_cold["plan_cache"]["hits"] + 1
          and m_warm["plan_cache"]["misses"] == m_cold["plan_cache"]["misses"],
          "warm run is a plan-cache hit")
    for k in ("engine.segment.compile", "engine.segment_cache.miss",
              "engine.fused_stage_cache.miss", "engine.build_cache.miss"):
        check(cw[k] == cc[k], f"warm run adds no {k} ({cc[k]} -> {cw[k]})")
    check(cc["engine.segment.compile"] >= 1 and cc["io.parquet.chunks"] > 1,
          "cold run compiled a fused segment and streamed several chunks")
    lp = m_warm["last_plan"]
    check(lp.get("row_groups_pruned", 0) >= 1
          and lp.get("row_groups_read", 0) > 1,
          f"row-group pushdown ran (pruned {lp.get('row_groups_pruned')}, "
          f"read {lp.get('row_groups_read')})")
    # (e) exports, compared exactly
    equal_to_reference(c.export_host(h_cold), want, "cold result")
    equal_to_reference(c.export_host(h_warm), want, "warm result")
    c.release(h_cold)
    c.release(h_warm)
    check(c.live_count() == 0, "live handles back to 0 after release")
    c.close()

    # (c) a second connection, after the first released and closed
    time.sleep(1.0)   # let the first connection's server thread finish
    c = BridgeClient(sock, timeout=900)
    (h2,), t_second = timed(lambda: c.execute_plan(plan))
    log(f"smoke timing: plan from a second connection {t_second:.3f} s")
    equal_to_reference(c.export_host(h2), want, "second-connection result")
    c.release(h2)

    # (d) per-op scan, then the reference's one op both ways, full table
    sales = wh["sales"]
    th, t_read = timed(lambda: c.read_parquet(paths["store_sales"]))
    nrows, schema = c.table_meta(th)
    check(nrows == len(sales) and [d.id for d in schema]
          == [INT64.id, INT64.id, FLOAT64.id],
          f"read_parquet(store_sales): {nrows} rows, i64/i64/f64")
    blobs, t_to_cold = timed(lambda: c.convert_to_rows(th))
    check(len(blobs) == 1, "convert_to_rows gave one row blob")
    back, t_from_cold = timed(lambda: c.convert_from_rows(blobs[0], schema))
    equal_to_reference(c.export_host(back), sales, "row round trip")
    blobs2, t_to_warm = timed(lambda: c.convert_to_rows(th))
    back2, t_from_warm = timed(lambda: c.convert_from_rows(blobs2[0], schema))
    log(f"smoke timing: read_parquet {t_read:.3f} s; to_rows cold "
        f"{t_to_cold:.3f} s warm {t_to_warm:.3f} s; from_rows cold "
        f"{t_from_cold:.3f} s warm {t_from_warm:.3f} s "
        f"({nrows} rows x {len(schema)} columns)")
    log("row conversion implementation: XLA concat + constant lane "
        "permutation (ops/row_conversion.py; the one path on every platform)")
    for h in (th, back, back2, *blobs, *blobs2):
        c.release(h)
    check(c.live_count() == 0, "live handles back to 0 after release")

    m = c.metrics()
    log(f"counters at end: {json.dumps(counters_of(m))}")
    log(f"server ops: {json.dumps(m['ops'])} busy_s {m['busy_s']}")
    log(f"device memory: {json.dumps(m['device'].get('memory'))}")
    no_hidden_fallback(m)
    check(m["open_exports"] == 0, "no shm export left open")
    c.shutdown_server()
    return m["device"]


def run_four_chips(sock: str, wh: dict) -> dict:
    """Only what exists across chips: the q5-lite plan under SRJT_DIST=1,
    its exact comparison, and the evidence that the exchange ran over all
    four devices."""
    plan = q5_lite(wh["paths"])
    want = q5_lite_pandas(wh)
    log(f"reference (pandas): {want.to_dict('list')}")
    c = BridgeClient(sock, timeout=900)
    (h,), t_cold = timed(lambda: c.execute_plan(plan))
    log(f"smoke timing: distributed plan cold {t_cold:.3f} s")
    equal_to_reference(c.export_host(h), want, "distributed result")
    c.release(h)
    check(c.live_count() == 0, "live handles back to 0 after release")
    m = c.metrics()
    log(f"counters: {json.dumps(counters_of(m))}")
    log(f"last_plan: {json.dumps(m['last_plan'])}")
    log(f"devices block: {json.dumps(m.get('devices'))}")
    check(m["last_plan"].get("exchanges", 0) >= 1
          and int(m["counters"].get("engine.exchange.shuffles", 0)) >= 1,
          "a hash exchange executed")
    ndev = m["device"]["count"]
    mats = [n["rows_matrix"] for n in m["last_plan_summary"].get("nodes", ())
            if n.get("rows_matrix")]
    check(len(mats) >= 1, "an Exchange span carries a (src, dest) rows matrix")
    log(f"exchange rows matrices [src][dest]: {mats}")
    # the partial-aggregate exchange: one row per store key.  12 fixed keys
    # and a fixed hash leave nothing to chance.  Partial rows are dealt
    # contiguously, so every device must be a source; one destination
    # holding everything is what the exchange exists to prevent.
    rows = max((np.asarray(x, np.int64) for x in mats), key=lambda x: x.sum())
    check(rows.shape == (ndev, ndev), f"matrix is {ndev} x {ndev}")
    check(int(rows.sum()) == SF1_STORE,
          f"the {SF1_STORE} per-store partial rows all crossed the exchange")
    check(bool((rows.sum(axis=1) > 0).all()),
          f"every device sent rows ({rows.sum(axis=1).tolist()})")
    dest = rows.sum(axis=0)
    check(int((dest > 0).sum()) >= 2 and int(dest.max()) < int(rows.sum()),
          f"rows did not all land on one device ({dest.tolist()})")
    received = np.sum([n["dev_rows"] for n in
                       m["last_plan_summary"].get("nodes", ())
                       if n.get("dev_rows")], axis=0)
    check(len(received) == ndev and bool((received > 0).all()),
          "every device received rows over the plan's exchanges, broadcast "
          f"replicas included ({received.tolist()})")
    gauges = (m.get("devices") or {}).get("exchange_rows", {})
    check(sorted(gauges) == [str(d) for d in range(ndev)],
          f"OP_METRICS devices block covers devices 0..{ndev - 1}")
    no_hidden_fallback(m)
    c.shutdown_server()
    return m["device"]


def log_server_traceback(bundles: str) -> None:
    """The newest post-mortem bundle's traceback (utils/blackbox.py)."""
    names = sorted(os.listdir(bundles)) if os.path.isdir(bundles) else []
    if names:
        with open(os.path.join(bundles, names[-1])) as f:
            err = json.load(f).get("error", {})
        log(f"server-side traceback:\n{err.get('traceback', '(none)')}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20)
    ap.add_argument("--rows", type=int, default=SF1_STORE_SALES,
                    help="store_sales rows (a cut below SF1 is printed; "
                         f"under {MIN_ROWS} only rehearses the control flow)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()

    if args.rows != SF1_STORE_SALES:
        log(f"CUT: store_sales {args.rows} rows instead of SF1's "
            f"{SF1_STORE_SALES}" + ("" if args.rows >= MIN_ROWS else
                                    f" (under the {MIN_ROWS} floor: a "
                                    "rehearsal, never a chip result)"))
    proc = None
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        # post-mortem bundles: a failed op's server-side traceback
        bundles = os.path.join(tmp, "blackbox")
        try:
            wh, t_gen = timed(lambda: make_warehouse(tmp, args.rows,
                                                     args.seed))
            log(f"warehouse: store_sales {args.rows} rows, date_dim "
                f"{SF1_DATE_DIM}, store {SF1_STORE} (seed {args.seed}, "
                f"{t_gen:.1f} s, "
                f"{os.path.getsize(wh['paths']['store_sales']) >> 20} MiB)")
            sock = os.path.join(tmp, "tpub.sock")
            env = {"SRJT_BLACKBOX_DIR": bundles}
            if args.chips == 4:
                env["SRJT_DIST"] = "1"
            proc, t_up = timed(lambda: spawn_server(sock, env=env,
                                                    timeout=300))
            log(f"server up in {t_up:.1f} s")
            device = (run_four_chips if args.chips == 4
                      else run_one_chip)(sock, wh)
            rc = proc.wait(timeout=120)
            proc = None
            check(rc == 0, f"server child exited 0 (rc={rc})")
        except Exception as e:  # noqa: BLE001 — any failure is the verdict
            log(f"SMOKE FAILED: {type(e).__name__}: {e}")
            log_server_traceback(bundles)
            return 1
        finally:
            if proc is not None:
                proc.kill()
                proc.wait()

    # this process must have left the accelerator to the child
    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is not None and xb.backends_are_initialized():
        log("SMOKE FAILED: the client process initialised a jax backend")
        return 1
    log("  ok: the client process initialised no jax backend")
    log(f"smoke: all results equal the reference; server device "
        f"{json.dumps(device)}")
    # the platform verdict comes last, so a CPU run rehearses everything
    if device["platform"] != "tpu":
        log(f"SMOKE FAILED: server computed on platform "
            f"{device['platform']!r}, not 'tpu'")
        return 1
    if device["count"] != args.chips:
        log(f"SMOKE FAILED: server saw {device['count']} device(s), "
            f"--chips {args.chips}")
        return 1
    if args.rows < MIN_ROWS:
        log(f"SMOKE FAILED: {args.rows} rows is under the {MIN_ROWS} floor")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
