#!/usr/bin/env python3
"""Chip sweep behind ``ops/join.py::PROBE_COMPARE_MAX_BUILD`` and
``DIRECT_MAX_SLOTS``.

    python tools/probe_sweep.py [--nl 262144] [--nr 32,512,4096,18000,73049]
                                [--span 0] [--methods compare,rank,direct]
                                [--out FILE]

For each build size and key span: one probe of ``nl`` int64 keys (nullable,
under a live mask) against a prepared build of ``nr`` distinct int64 keys
drawn from ``span`` consecutive values (0: four times ``nr``), carrying one
int64 payload column — what ``engine/segment.py::_probe_join_node`` does
for an inner join — once by each method of ``probe_join_prepared``:
``compare``, ``rank`` (the ``searchsorted`` of the sorted keys) and
``direct`` (one gather from the direct-address table).  The method is
forced by moving the module constants, which ``prepare_build`` and the
probe read.  Prints one JSON line per (nr, span, method): compile seconds,
milliseconds per call (``reps`` launches queued, one ``block_until_ready``
at the end, so the device's time and not the dispatch's), and whether the
methods gave the same answer.  A time printed here means something only on
the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--nl", type=int, default=262_144)
    ap.add_argument("--nr", default="32,512,4096,18000,73049")
    ap.add_argument("--span", default="0")
    ap.add_argument("--methods", default="compare,rank")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=31)
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from spark_rapids_jni_tpu import Column, Table
    from spark_rapids_jni_tpu import dtypes as dt
    from spark_rapids_jni_tpu.ops import join as J
    from spark_rapids_jni_tpu.ops.selection import gather_column
    from spark_rapids_jni_tpu.utils.config import enable_compile_cache
    enable_compile_cache()
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.platform, "kind": dev.device_kind,
                      "nl": args.nl}), flush=True)

    def emit(rec):
        print(json.dumps(rec), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")

    rng = np.random.default_rng(args.seed)
    cap = J.DIRECT_MAX_SLOTS
    for nr in (int(x) for x in args.nr.split(",")):
        for span in (int(x) or 4 * nr for x in args.span.split(",")):
            bk = rng.choice(span, nr, replace=False).astype(np.int64) \
                + 2_415_022
            pay = rng.integers(-2**62, 2**62, nr).astype(np.int64)
            build = Table([Column(dt.INT64, data=jnp.asarray(bk)),
                           Column(dt.INT64, data=jnp.asarray(pay))],
                          ["k", "p"])
            lk = rng.integers(0, span, args.nl).astype(np.int64) + 2_415_022
            keys = Table([Column(dt.INT64, data=jnp.asarray(lk),
                                 validity=jnp.ones(args.nl, jnp.bool_))],
                         ["k"])
            live = jnp.asarray(rng.random(args.nl) < 0.9)
            answers = {}
            for method in args.methods.split(","):
                J.PROBE_COMPARE_MAX_BUILD = nr if method == "compare" else -1
                J.DIRECT_MAX_SLOTS = cap if method == "direct" else 0
                t = time.perf_counter()
                pb = J.prepare_build(build, ["k"])
                prepare_s = time.perf_counter() - t
                if method == "direct" and pb.direct is None:
                    emit({"nr": nr, "span": span, "method": method,
                          "skipped": "span above DIRECT_MAX_SLOTS"})
                    continue

                def step(keys, pb, live):
                    ri, matched = J.probe_join_prepared(keys, pb,
                                                        left_live=live)
                    pcol = pb.payload.column("p")
                    c = J.select_build_rows(pcol, ri) \
                        if method == "compare" else gather_column(pcol, ri)
                    return ri, matched, c.data, c.validity

                fn = jax.jit(step)
                t = time.perf_counter()
                out = jax.block_until_ready(fn(keys, pb, live))
                compile_s = time.perf_counter() - t
                jax.block_until_ready(fn(keys, pb, live))
                t = time.perf_counter()
                for _ in range(args.reps):
                    out = fn(keys, pb, live)
                jax.block_until_ready(out)
                ms = (time.perf_counter() - t) / args.reps * 1e3
                ri, matched, pdata, pvalid = (np.asarray(a) for a in out)
                answers[method] = (matched, np.where(matched, ri, -1),
                                   np.where(matched, pdata, 0),
                                   pvalid & matched)
                want = np.isin(lk, bk) & np.asarray(live)
                emit({"nr": nr, "span": span, "method": method,
                      "unique": bool(pb.unique),
                      "slots": None if pb.direct is None
                      else int(pb.direct.shape[0]),
                      "prepare_s": round(prepare_s, 2),
                      "compile_s": round(compile_s, 2), "ms_per_call": ms,
                      "matched": int(matched.sum()),
                      "equals_numpy": bool((matched == want).all()
                                           and (bk[ri[matched]]
                                                == lk[matched]).all())})
            if len(answers) > 1:
                first, *rest = answers.values()
                same = all((x == y).all() for other in rest
                           for x, y in zip(first, other))
                emit({"nr": nr, "span": span, "methods_agree": bool(same)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
