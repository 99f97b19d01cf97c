#!/usr/bin/env python3
"""Chip sweep behind ``ops/aggregate.py::DENSE_MAX_GROUPS``.

    python tools/agg_sweep.py [--nl 262144] [--k 16,32,...,2048]
                              [--mixes f64,i64] [--guarded 128] [--out FILE]

For each key-domain size ``K``: one masked group-by of ``nl`` int64 keys
spread over ``K`` values (a tenth of the rows dead, as a chunk's live mask
leaves them), once by the sort form (``groupby_padded``) and once by the
dense form (``groupby_dense``), for each aggregate mix — ``f64``: a float64
``sum`` and ``count`` of one column (q5's chunk aggregate), ``i64``: an
int64 ``sum``.  The dense form is timed alone: its in-program guard, whose
other branch is the sort form, is compiled only at the sizes ``--guarded``
names (every such program compiles the sort too).  Prints one JSON line per
(K, mix, form): compile seconds, milliseconds per call (``reps`` launches
queued, one ``block_until_ready`` at the end, so the device's time and not
the dispatch's), and whether the form gave the sort form's answer bit for
bit.  A time printed here means something only on the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

MIXES = {"f64": (("v", "sum"), ("v", "count")), "i64": (("w", "sum"),)}
LO = 1                          # the stores' first key


def _bytes(out) -> list:
    """The live groups of a group-by's result as bytes, to compare."""
    (kdat, kval), aggs, ngroups = out
    ng = int(ngroups)
    arrays = [kdat, kval]
    for data, valid in aggs:
        arrays += [data] + ([] if valid is None else [valid])
    return [np.asarray(a)[:ng].tobytes() for a in arrays] + [ng]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--nl", type=int, default=262_144)
    ap.add_argument("--k", default="16,32,64,128,256,512,1024,2048")
    ap.add_argument("--mixes", default="f64,i64")
    ap.add_argument("--guarded", default="",
                    help="K values at which the guarded program is timed too")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=41)
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from spark_rapids_jni_tpu import Column, Table
    from spark_rapids_jni_tpu import dtypes as dt
    from spark_rapids_jni_tpu.ops import aggregate as A
    from spark_rapids_jni_tpu.utils.config import enable_compile_cache
    enable_compile_cache()
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.platform, "kind": dev.device_kind,
                      "nl": args.nl}), flush=True)
    guarded_k = {int(x) for x in args.guarded.split(",") if x}
    real_cond = jax.lax.cond

    def emit(rec):
        print(json.dumps(rec), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")

    def timed(fn, *a):
        t = time.perf_counter()
        out = jax.block_until_ready(fn(*a))
        compile_s = time.perf_counter() - t
        jax.block_until_ready(fn(*a))
        t = time.perf_counter()
        for _ in range(args.reps):
            res = fn(*a)
        jax.block_until_ready(res)
        return out, compile_s, (time.perf_counter() - t) / args.reps * 1e3

    def flat(out):
        keys, aggs, ngroups = out
        return ((keys[0][2], keys[0][3]),
                [(c.data, c.validity) for c in aggs], ngroups)

    def unguarded(pred, true_fun, false_fun):
        # the guard's sort branch is left out; any other cond stays
        if false_fun.__name__ == "sort_form":
            return true_fun()
        return real_cond(pred, true_fun, false_fun)

    rng = np.random.default_rng(args.seed)
    live = jnp.asarray(rng.random(args.nl) < 0.9)
    price = rng.integers(2, 20_000 * 4096 + 1, args.nl) / 4096.0
    wide = rng.integers(-2**40, 2**40, args.nl).astype(np.int64)
    sort_fns = {m: jax.jit(lambda t, live, aggs=aggs: flat(A.groupby_padded(
        t, ["k"], list(aggs), row_mask=live))) for m, aggs in MIXES.items()}
    for k in (int(x) for x in args.k.split(",")):
        keys = rng.integers(LO, LO + k, args.nl).astype(np.int64)
        table = Table([Column(dt.INT64, data=jnp.asarray(keys)),
                       Column.from_numpy(price),
                       Column(dt.INT64, data=jnp.asarray(wide))],
                      ["k", "v", "w"])
        lo = jnp.asarray(LO, jnp.int64)
        for mix in args.mixes.split(","):
            aggs = list(MIXES[mix])

            def dense(t, live, lo, aggs=aggs, k=k):
                return flat(A.groupby_dense(t, ["k"], aggs, lo, k,
                                            row_mask=live))

            want, compile_s, ms = timed(sort_fns[mix], table, live)
            want = _bytes(want)
            emit({"k": k, "mix": mix, "form": "sorted",
                  "compile_s": round(compile_s, 2), "ms_per_call": ms,
                  "groups": want[-1]})
            forms = [("dense", unguarded)]
            if k in guarded_k:
                forms.append(("dense_guarded", real_cond))
            for form, cond in forms:
                jax.lax.cond = cond
                try:
                    # a function of its own: jit caches traces by function
                    fn = jax.jit(lambda *a: dense(*a))
                    got, compile_s, ms = timed(fn, table, live, lo)
                finally:
                    jax.lax.cond = real_cond
                emit({"k": k, "mix": mix, "form": form,
                      "compile_s": round(compile_s, 2), "ms_per_call": ms,
                      "equals_sorted": _bytes(got) == want})
    return 0


if __name__ == "__main__":
    sys.exit(main())
