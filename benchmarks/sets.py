#!/usr/bin/env python3
"""Sets of runs of one cell, and their spreads: how a bound is measured.

    python benchmarks/sets.py --workload <cell> --seeds 11,12,13 \\
        [--sets 2] [--seconds <run_seconds>] [--trace 0] [--out <file>]

Runs `run.py` once per seed, ``--sets`` times over the same seeds, one
process per run (each pays its own set-up, as in the driver's check),
prints every result line, and for each metric of each set the median and
the spread: (third quartile - first quartile) / median, by
``statistics.quantiles(values, n=4)``.  A bound is about five times the
widest spread over the cells, never under 1%.  ``--out`` appends every
line to a file (under `chiprun_out/` on the chip machine).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def spread(values: list) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    median = statistics.median(values)
    return (q3 - q1) / median if median else float("nan")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    sets, incorrect = [], 0
    for k in range(args.sets):
        rows = []
        for seed in seeds:
            t = time.perf_counter()
            proc = subprocess.run(
                [sys.executable, os.path.join(HERE, "run.py"),
                 "--workload", args.workload, "--seed", str(seed),
                 "--seconds", str(seconds), "--trace", str(args.trace)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
            took = time.perf_counter() - t
            lines = proc.stdout.strip().splitlines()
            line = lines[-1] if lines else ""
            record = {"set": k, "seed": seed, "rc": proc.returncode,
                      "process_s": round(took, 3),
                      "result": json.loads(line) if line else None}
            failed = proc.returncode != 0 or not record["result"] \
                or not record["result"]["correct"]
            incorrect += failed
            if failed:
                record["stderr_tail"] = proc.stderr[-6000:]
            print(json.dumps(record), flush=True)
            if args.out:       # the file also keeps the end of the run's log
                record["stderr_tail"] = proc.stderr[-6000:]
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(record) + "\n")
            if record["result"]:
                rows.append(record["result"]["metrics"])
        sets.append(rows)
    for k, rows in enumerate(sets):
        for name in sorted({n for r in rows for n in r}):
            values = [r[name]["value"] for r in rows if name in r]
            if len(values) >= 2:
                print(f"set {k} {args.workload} {name}: median "
                      f"{statistics.median(values):.6g} spread "
                      f"{spread(values) * 100:.3f}% n={len(values)} "
                      f"values {[round(v, 4) for v in values]}", flush=True)
    return 1 if incorrect else 0


if __name__ == "__main__":
    sys.exit(main())
