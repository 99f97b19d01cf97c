#!/usr/bin/env python3
"""The control of `correct`: the plain reference computed in float32 —
the nearest precision below the float64 the configurations state — put in
the program's place and compared as a served result is.  It has to come
out NOT correct.

    python benchmarks/control.py --workload <cell> --seeds 1,2,3 [--rehearsal]

Prints, per seed, each number compared beside its limit for the control
and (``served`` = the float64 reference itself, standing for a sound
program) for the exact result.  Host work only; on the chip machine it
runs at the cell's own size.  Exits 1 if a control passes the comparison.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import compare  # noqa: E402
from run import Cell  # noqa: E402


def as_served(frame) -> list:
    """A reference frame in the shape `export_host` gives a result."""
    return [(None, frame[name].to_numpy(), None) for name in frame.columns]


def readings(cell: Cell, seed: int, rehearsal: bool) -> dict:
    frames = cell.query.tables(seed, cell.rows(rehearsal))
    params = cell.traffic["params"]
    want = cell.query.reference(frames, params)
    low = cell.query.reference(frames, params, float_dtype=np.float32)
    out = {}
    for who, frame in (("served", want), ("control", low)):
        checks = compare.compare([as_served(frame)], want)
        out[who] = {"correct": compare.verdict(checks),
                    **{k: c["value"] for k, c in checks.items()}}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearsal", action="store_true",
                    help="the configuration's cut row count")
    args = ap.parse_args()
    cell = Cell(args.workload)
    passed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        r = readings(cell, seed, args.rehearsal)
        print(json.dumps({"workload": args.workload, "seed": seed, **r}),
              flush=True)
        passed += r["control"]["correct"] or not r["served"]["correct"]
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
