#!/usr/bin/env python
"""Seeded plan-space fuzzer CLI (the generative half of docs/ANALYSIS.md).

Drives ``engine/fuzz.py``: synthesize random valid plans over a seeded
parquet warehouse, sweep each through the executor flag matrix
(``SRJT_FUSE``/``SRJT_DIST``/``SRJT_BROADCAST_ROWS``/``SRJT_AQE``),
and assert the rewrite-soundness invariants (verify-after-rewrite,
ledger==census, exchange census==executed counter, sync whitelist,
bit-exact executor parity, pandas-oracle parity).  Any failure is
shrunk to a minimal plan and reported as ``seed + case + plan JSON`` —
a one-line deterministic repro.

Gate usage:

    python tools/srjt_fuzz.py --smoke            # premerge: fixed seed
    python tools/srjt_fuzz.py --seed N --count M --full \
        --out target/fuzz-repro.json             # nightly sweep

Exit status 0 = zero soundness violations; 1 = failures (repro JSON on
stdout and, with ``--out``, persisted as the CI artifact).
"""

import argparse
import json
import os
import sys
import tempfile

# must precede the first jax import: the differential matrix needs the
# 8-device virtual CPU mesh the engine's distributed tests use
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_xla_flags = os.environ.setdefault("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _xla_flags:
    os.environ["XLA_FLAGS"] = (
        _xla_flags + " --xla_force_host_platform_device_count=8").strip()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: the premerge smoke contract: fixed seed, ~50 plans, core matrix
SMOKE_SEED = 20260805
SMOKE_COUNT = 50


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help=f"fixed-seed gate corpus (seed {SMOKE_SEED}, "
                         f"{SMOKE_COUNT} plans, core variant matrix)")
    ap.add_argument("--seed", type=int, default=SMOKE_SEED)
    ap.add_argument("--count", type=int, default=SMOKE_COUNT)
    ap.add_argument("--full", action="store_true",
                    help="sweep the extended variant matrix "
                         "(adds dist-nofuse and dist-fused-aqe)")
    ap.add_argument("--out", default=None,
                    help="write the failure report (seed + shrunk "
                         "minimal plan JSON) to this path on failure")
    ap.add_argument("--no-shrink", action="store_true",
                    help="report raw failing plans without minimizing")
    args = ap.parse_args(argv)

    from pathlib import Path

    from spark_rapids_jni_tpu.engine import fuzz

    if args.smoke:
        seed, count, variants = SMOKE_SEED, SMOKE_COUNT, fuzz.VARIANTS
    else:
        seed, count = args.seed, args.count
        variants = fuzz.FULL_VARIANTS if args.full else fuzz.VARIANTS

    with tempfile.TemporaryDirectory(prefix="srjt-fuzz-") as tmp:
        report = fuzz.run_corpus(
            seed, count, Path(tmp), variants=variants,
            log=lambda m: print(f"srjt_fuzz: {m}", file=sys.stderr),
            shrink_failures=not args.no_shrink)

    report["variants"] = [v["name"] for v in variants]
    if report["failures"]:
        print(json.dumps(report, indent=2, default=str))
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(report, f, indent=2, default=str)
            print(f"srjt_fuzz: repro artifact at {args.out}",
                  file=sys.stderr)
        print(f"srjt_fuzz: {len(report['failures'])} soundness "
              f"violation(s) in {count} plans (seed {seed})",
              file=sys.stderr)
        return 1
    print(f"srjt_fuzz: OK — {count} plans x {len(variants)} variants, "
          f"0 soundness violations (seed {seed})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
