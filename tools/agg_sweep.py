#!/usr/bin/env python3
"""Chip sweeps behind ``ops/aggregate.py::DENSE_MAX_GROUPS`` and
``BUILD_SPARSE_MAX_ROWS``.

    python tools/agg_sweep.py [--nl 262144] [--k 16,32,...,2048]
                              [--mixes f64,i64] [--guarded 128] [--out FILE]
    python tools/agg_sweep.py --build-row [--nl 262144] [--nslots 145761]
                              [--live 0.005,0.05,0.5,1]
                              [--bk 2048,4096,8192,16384] [--out FILE]

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

``--build-row``: the build-row form's totals (``groupby_build_rows``) of
``nl`` rows into ``nslots`` build rows — the row count and a decimal sum,
int64 both, as TPC-H Q3's chunk program adds them — at each share of live
rows ``--live``: every row scattered (``full``: the form before the
compaction), and for each bucket ``K`` of ``--bk`` the live rows compacted
into ``K`` entries first, alone (``compacted``: right only where the live
rows fit) and under the guard whose other branch scatters every row
(``guarded``).  One JSON line per (share, form, K): compile seconds,
milliseconds per call, and whether the totals equal numpy's bit for bit.
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


def _unguarded(real_cond, branch: str):
    """``lax.cond`` with the guard whose other branch is the function
    ``branch`` left out: the kept branch's result; any other cond stays."""
    def cond(pred, true_fun, false_fun):
        if false_fun.__name__ == branch:
            return true_fun()
        return real_cond(pred, true_fun, false_fun)
    return cond


def _build_row_sweep(args, emit, timed) -> int:
    """``--build-row``: see the module's docstring."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_jni_tpu import Column, Table
    from spark_rapids_jni_tpu import dtypes as dt
    from spark_rapids_jni_tpu.ops import aggregate as A
    real_cond, n, ns = jax.lax.cond, args.nl, args.nslots
    kept, fns = A.BUILD_SPARSE_MAX_ROWS, {}
    aggs = [("v", "sum")]
    unguarded = _unguarded(real_cond, "full_form")

    def flat(rows, out):
        return rows, [(c.data, c.validity) for c in out]

    rng = np.random.default_rng(args.seed)
    # a decimal(38,4) revenue of up to 10**10 units a row, as Q3's product
    vals = rng.integers(1, 10**10, n).astype(np.int64)
    table = Table([Column(dt.decimal64(-4, 38), data=jnp.asarray(vals))],
                  ["v"])
    full = jax.jit(lambda t, live, slot: flat(*A._build_row_totals(
        [(t.column("v"), "sum")], live, slot, ns)))
    for share in (float(x) for x in args.live.split(",")):
        live = np.zeros(n, bool)
        live[rng.choice(n, int(round(share * n)), replace=False)] = True
        # a dead row carries any slot; a live one its build row's
        slot = rng.integers(0, ns, n).astype(np.int32)
        slot[~live] = rng.integers(-2**31, 2**31 - 1, int((~live).sum()))
        want_rows = np.zeros(ns, np.int64)
        np.add.at(want_rows, slot[live], 1)
        want_sum = np.zeros(ns, np.int64)
        np.add.at(want_sum, slot[live], vals[live])
        dl, ds = jnp.asarray(live), jnp.asarray(slot)

        def equal(rows, out):
            (data, valid), = out
            return bool(np.array_equal(np.asarray(rows), want_rows)
                        and np.array_equal(np.asarray(data), want_sum)
                        and np.array_equal(np.asarray(valid),
                                           want_rows > 0))

        base = {"live_share": share, "live_rows": int(live.sum())}
        got, compile_s, ms = timed(full, table, dl, ds)
        emit({**base, "form": "full", "compile_s": round(compile_s, 2),
              "ms_per_call": ms, "equals_numpy": equal(*got)})
        for k in (int(x) for x in args.bk.split(",")):
            for form, cond in (("compacted", unguarded),
                               ("guarded", real_cond)):
                # traced at its first share, with K and the cond it is
                # timed with; a function of its own: jit caches by function
                fn = fns.setdefault((k, form), jax.jit(
                    lambda t, live, slot: flat(*A.groupby_build_rows(
                        t, aggs, live, slot, ns)[:2])))
                A.BUILD_SPARSE_MAX_ROWS, jax.lax.cond = k, cond
                try:
                    got, compile_s, ms = timed(fn, table, dl, ds)
                finally:
                    A.BUILD_SPARSE_MAX_ROWS, jax.lax.cond = kept, real_cond
                emit({**base, "form": form, "k": k,
                      "compile_s": round(compile_s, 2), "ms_per_call": ms,
                      "equals_numpy": equal(*got)})
    return 0


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
    ap.add_argument("--build-row", action="store_true",
                    help="sweep the build-row form's compaction instead")
    ap.add_argument("--nslots", type=int, default=145_761)
    ap.add_argument("--live", default="0.005,0.05,0.5,1")
    ap.add_argument("--bk", default="2048,4096,8192,16384")
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

    if args.build_row:
        return _build_row_sweep(args, emit, timed)

    def flat(out):
        keys, aggs, ngroups = out
        return ((keys[0][2], keys[0][3]),
                [(c.data, c.validity) for c in aggs], ngroups)

    unguarded = _unguarded(real_cond, "sort_form")

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
