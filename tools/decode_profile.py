#!/usr/bin/env python3
"""What the host Parquet decode of one benchmark cell's fact file costs.

    python tools/decode_profile.py [--workload q5lite_sf1_year] [--seed 33]
                                   [--reps 3] [--threads 2,4] [--top 12]
                                   [--procs 1,2,4,8] [--streams 1,4]
                                   [--package-root DIR] [--out FILE]

Writes the cell's warehouse from ``--seed`` at the configuration's full row
counts, exactly as ``benchmarks/run.py::write_tables`` writes it, and decodes
the fact file with ``io/parquet.py::ParquetFile._decode_group`` — the call the
streamed scan's producer thread makes once per row group.  No cell runs this
file and it starts no server: it imports the decoder, so jax is loaded but
nothing runs on a device.  One JSON line per finding:

- ``serial``: milliseconds per row group, best and median over ``--reps``
  passes over all row groups, and the decoder's own counters per group;
- ``column``: the same for each column decoded alone;
- ``threads``: effective milliseconds per group when a pool of N threads
  decodes the groups (wall time of the pass ÷ groups): what fanning the
  groups out would buy;
- ``procs`` (with ``--procs``): the same per group when the streamed scan's
  own path (`ParquetChunkedReader._host_slices(offload=True)`: a window of
  `READ_AHEAD` + 1 groups in `io/decode_pool.py`'s worker PROCESSES) supplies
  the groups to S concurrent streams (``--streams``) that only take them: what
  N workers can supply, and the workers' own seconds per group;
- ``crossover`` (with ``--procs``): the fact table cut to a few rows per
  group — footer ``total_byte_size``, the decode in this process, and one
  group's round trip through a worker (submit, wait, release; no read-ahead)
  with the worker's copy into the slab: the round trip's cost over the decode
  is what a group must be worth, `io/parquet.py::OFFLOAD_MIN_BYTES`;
- ``dimension``: each other table of the warehouse, decoded whole;
- ``profile``: the functions of ``io/`` by cumulative time (cProfile over
  one pass; the profiler's overhead inflates functions called often).

The times are host times: compare parent and change on the same machine.
``--package-root`` names another checkout whose ``spark_rapids_jni_tpu`` is
profiled in this one's place (the parent's, unpacked beside it); the files
are written by this checkout's ``benchmarks/`` either way.
"""

from __future__ import annotations

import argparse
import cProfile
import json
import os
import pstats
import statistics
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COUNTERS = ("io.parquet.decode.pages", "io.parquet.decode.runs",
            "io.parquet.decode.dense_chunks")


def _ms(seconds: list, per: int) -> dict:
    return {"best_ms": round(min(seconds) / per * 1e3, 3),
            "median_ms": round(statistics.median(seconds) / per * 1e3, 3)}


def _passes(fn, reps: int) -> list:
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        out.append(time.perf_counter() - t0)
    return out


def _pool_lines(emit, args, cell, paths, fact, frames, root) -> None:
    """The ``procs`` and ``crossover`` lines (see the module's docstring)."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    from spark_rapids_jni_tpu.io import decode_pool
    from spark_rapids_jni_tpu.io import parquet as pqt
    from spark_rapids_jni_tpu.utils import metrics

    chunk_bytes = cell.config["storage"]["chunk_bytes"]

    def stream(path):
        rd = pqt.ParquetChunkedReader(path, pass_read_limit=chunk_bytes)
        return sum(1 for _ in rd._host_slices(offload=True))

    def streams(n, path):
        ts = [threading.Thread(target=stream, args=(path,)) for _ in range(n)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()

    groups = pqt.ParquetFile(paths[fact]).num_row_groups
    for n in (int(x) for x in args.procs.split(",") if x):
        pool = decode_pool.DecodePool(workers=n)
        old = decode_pool.install(pool)
        try:
            t0 = time.perf_counter()
            pool.start()
            if not pool.wait_ready():
                emit(what="procs", workers=n, error="workers did not come up")
                continue
            up_s = time.perf_counter() - t0
            stream(paths[fact])                 # maps, footers, page cache
            for k in (int(x) for x in args.streams.split(",") if x):
                h0 = metrics.histograms_snapshot("io.scan.decode.worker_s") \
                    .get("io.scan.decode.worker_s") or {"sum": 0.0, "count": 0}
                took = _passes(lambda: streams(k, paths[fact]), args.reps)
                h1 = metrics.histograms_snapshot("io.scan.decode.worker_s")[
                    "io.scan.decode.worker_s"]
                emit(what="procs", workers=n, streams=k, start_s=round(up_s, 2),
                     window=decode_pool.READ_AHEAD + 1, **_ms(took, groups * k),
                     worker_ms=round((h1["sum"] - h0["sum"]) * 1e3
                                     / max(1, h1["count"] - h0["count"]), 3))
            if n != 1:
                continue
            df = frames[fact]
            for rows in (12, 1000, 2500, 5000, 10000, 20000, 50000, 240034):
                path = os.path.join(root, f"cut{rows}.parquet")
                pq.write_table(pa.Table.from_pandas(df.iloc[:rows],
                                                    preserve_index=False),
                               path, compression=cell.config["storage"][
                                   "compression"])
                pf = pqt.ParquetFile(path)
                need = decode_pool.slab_bytes(
                    [c.dtype.storage.itemsize for c in pf.schema], rows)

                copies = []

                def trip():
                    t = pool.submit(path, 0, None, need)
                    pool.wait(t)
                    copies.append(t.reply["copy_s"])
                    pool.release(t)

                trip()
                emit(what="crossover", rows=rows, total_byte_size=int(
                         pf.row_groups[0].total_byte_size),
                     inline_ms=_ms(_passes(lambda: pf._decode_group(0),
                                           5 * args.reps), 1)["median_ms"],
                     round_trip_ms=_ms(_passes(trip, 5 * args.reps),
                                       1)["median_ms"],
                     slab_copy_ms=round(statistics.median(copies) * 1e3, 3))
        finally:
            decode_pool.install(old)
            pool.shutdown()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="q5lite_sf1_year")
    ap.add_argument("--seed", type=int, default=33)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--threads", default="2,4")
    ap.add_argument("--top", type=int, default=12)
    ap.add_argument("--procs", default="",
                    help="decode-pool sizes to time, e.g. 1,2,4,8")
    ap.add_argument("--streams", default="1,4")
    ap.add_argument("--package-root", default=ROOT)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.package_root))
    sys.path.insert(1, os.path.join(ROOT, "benchmarks"))

    # the decoder first: benchmarks/run.py puts its own checkout on the path
    from spark_rapids_jni_tpu.io.parquet import ParquetFile
    from spark_rapids_jni_tpu.utils import tracing

    import run as bench            # benchmarks/run.py: a pure client, no jax

    lines = []

    def emit(**rec) -> None:
        lines.append(json.dumps(rec))
        print(lines[-1], flush=True)

    cell = bench.Cell(args.workload)
    fact = cell.query.FACT
    with tempfile.TemporaryDirectory(prefix="decode_profile_") as root:
        frames = cell.query.tables(args.seed, cell.rows(rehearsal=False))
        paths = bench.write_tables(frames, cell.config, root)
        pf = ParquetFile(paths[fact])
        groups = range(pf.num_row_groups)
        emit(what="file", package_root=os.path.abspath(args.package_root),
             workload=args.workload, seed=args.seed, table=fact,
             rows=pf.num_rows, row_groups=pf.num_row_groups,
             file_bytes=os.path.getsize(paths[fact]), columns=pf.names,
             cpus=os.cpu_count())

        def one_pass(columns=None):
            for gi in groups:
                pf._decode_group(gi, columns)

        one_pass()                                  # page cache, imports
        before = {c: tracing.counter_value(c) for c in COUNTERS}
        one_pass()
        counted = {c.rsplit(".", 1)[1] + "_per_group":
                   (tracing.counter_value(c) - before[c]) / len(groups)
                   for c in COUNTERS}
        emit(what="serial", **_ms(_passes(one_pass, args.reps), len(groups)),
             **counted)
        for col in pf.names:
            emit(what="column", column=col,
                 **_ms(_passes(lambda: one_pass([col]), args.reps),
                       len(groups)))
        for n in (int(x) for x in args.threads.split(",") if x):
            def pooled():
                with ThreadPoolExecutor(max_workers=n) as ex:
                    list(ex.map(pf._decode_group, groups))
            emit(what="threads", workers=n,
                 **_ms(_passes(pooled, args.reps), len(groups)))
        if args.procs:
            _pool_lines(emit, args, cell, paths, fact, frames, root)
        for name, path in paths.items():
            if name == fact:
                continue
            dim = ParquetFile(path)
            dim._decode_all_groups()
            emit(what="dimension", table=name, rows=dim.num_rows,
                 **_ms(_passes(dim._decode_all_groups, args.reps), 1))

        prof = cProfile.Profile()
        prof.runcall(one_pass)
        stats = pstats.Stats(prof).stats        # {(file, line, fn): (.., ct, ..)}
        total = max(ct for (_, _, _, ct, _) in stats.values())
        rows = sorted(((ct, tt, nc, f"{os.path.basename(fl)}:{fn}")
                       for (fl, _, fn), (_, nc, tt, ct, _) in stats.items()
                       if os.sep + "io" + os.sep in fl), reverse=True)
        emit(what="profile", pass_ms=round(total * 1e3, 1), top=[
            {"fn": fn, "calls": nc, "cum_pct": round(100 * ct / total, 1),
             "self_pct": round(100 * tt / total, 1)}
            for ct, tt, nc, fn in rows[:args.top]])
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write("\n".join(lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
