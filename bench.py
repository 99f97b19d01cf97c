"""Benchmarks over the BASELINE.md north-star configs.

Prints ONE JSON line.  Headline metric: RowConversion device throughput
(BASELINE configs[0]); ``extras`` carries CastStrings, HashAggregate and
Parquet-scan so the artifact records >=3 metrics per round.

Timing methodology: a value fetch is a host sync (its cost is not measured
on today's machine), so every device metric runs
K iterations inside one jitted ``fori_loop`` with a per-iteration salt
(defeats loop-invariant hoisting), reduced to one scalar fetch.  Rates are
fitted from two K values to cancel the fixed dispatch+fetch cost.  Where the
loop must materialize full-size output each iteration (RowConversion), the
carry xors in the output matrix — this *overstates* traffic by one
read+write of the carry per iteration, so reported GB/s is a lower bound on
the kernel's standalone rate.
"""

import json
import os
import sys
import time

import numpy as np

# Pinned baseline constants (VERDICT r3 #7): vs_baseline is measured/pinned,
# never measured/measured — see BENCH_BASELINES.json for provenance.
with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "BENCH_BASELINES.json")) as f:
    _PINS = json.load(f)


def pinned(metric: str) -> float:
    return _PINS[metric]["pinned_baseline"]


def fit_per_iter(make_loop, args, k1=16, k2=64):
    """min-of-3 wall times at two K values -> steady per-iteration seconds."""
    import jax
    ts = {}
    for k in (k1, k2):
        jf = jax.jit(make_loop(k))
        int(jf(*args))  # compile + warm
        best = min(_timed(jf, args) for _ in range(3))
        ts[k] = best
    per = (ts[k2] - ts[k1]) / (k2 - k1)
    if per <= 0:  # timing jitter; fall back to the conservative bound
        per = ts[k2] / k2
    return per


def _timed(jf, args):
    t0 = time.perf_counter()
    int(jf(*args))
    return time.perf_counter() - t0


# ---------------------------------------------------------------------------
# 1. RowConversion (headline, BASELINE configs[0])
# ---------------------------------------------------------------------------

def build_host_table(n, rng):
    return [
        ("i64", rng.integers(-2**62, 2**62, n).astype(np.int64), None),
        ("f64", rng.standard_normal(n), rng.random(n) > 0.1),
        ("i32", rng.integers(-2**31, 2**31 - 1, n).astype(np.int32), None),
        ("f32", rng.standard_normal(n).astype(np.float32), None),
        ("i16", rng.integers(-2**15, 2**15 - 1, n).astype(np.int16),
         rng.random(n) > 0.5),
        ("i8", rng.integers(-128, 128, n).astype(np.int8), None),
        ("bool", (rng.random(n) > 0.5), None),
        ("dec64", rng.integers(-10**15, 10**15, n).astype(np.int64), None),
    ]


def numpy_pack(cols, layout):
    """CPU Arrow-style row packer: strided assignment per column + validity."""
    n = len(cols[0][1])
    out = np.zeros((n, layout.row_size), np.uint8)
    for (name, data, valid), off in zip(cols, layout.offsets):
        if data.dtype == np.bool_:
            data = data.astype(np.uint8)
        b = data.view(np.uint8).reshape(n, data.dtype.itemsize)
        out[:, off:off + data.dtype.itemsize] = b
    vbytes = np.zeros((n, layout.num_validity_bytes), np.uint8)
    for i, (name, data, valid) in enumerate(cols):
        bit = np.uint8(1 << (i % 8))
        if valid is None:
            vbytes[:, i // 8] |= bit
        else:  # full-vector or, not boolean fancy indexing (4x faster)
            vbytes[:, i // 8] |= np.where(valid, bit, np.uint8(0))
    out[:, layout.validity_offset:layout.validity_offset
        + layout.num_validity_bytes] = vbytes
    return out


def bench_row_conversion(n=2_000_000):
    import jax
    import jax.numpy as jnp
    from spark_rapids_jni_tpu import dtypes as dt
    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.ops.row_conversion import (
        fixed_width_layout, _to_rows_bytes, _to_rows_wire)

    rng = np.random.default_rng(0)
    host_cols = build_host_table(n, rng)
    schema = [dt.INT64, dt.FLOAT64, dt.INT32, dt.FLOAT32, dt.INT16, dt.INT8,
              dt.BOOL8, dt.decimal64(-4)]
    layout = fixed_width_layout(schema)
    table = Table([Column.from_numpy(data, validity=valid, dtype=d)
                   for (name, data, valid), d in zip(host_cols, schema)])
    datas = tuple(c.data for c in table.columns)
    masks = tuple(c.validity for c in table.columns)
    nw = layout.row_size // 4

    def make_loop(K):
        def loop(d, m, acc):
            def body(i, acc):
                di = d[:2] + (d[2] ^ i.astype(jnp.int32),) + d[3:]
                return acc ^ _to_rows_wire(layout, di, m)
            out = jax.lax.fori_loop(jnp.int32(0), jnp.int32(K), body, acc)
            return out.sum(dtype=jnp.uint32)
        return loop

    acc0 = jnp.zeros((n * nw,), jnp.uint32)
    per = fit_per_iter(make_loop, (datas, masks, acc0))
    dev_gbps = n * layout.row_size / per / 1e9

    # Honest measured ceiling (r4's planes-only "ceiling" measured BELOW the
    # shipped op — a bound an op can beat is mis-measured).  This one is a
    # pure HBM stream under the SAME acc-xor harness (strictly simpler than
    # any op formulation: zero compute, perfectly coalesced), scaled by the
    # op's minimum-traffic ratio.  Per iteration the stream moves 3R bytes
    # (read x, read acc, write acc; R = output bytes); any to-rows
    # formulation must move >= I + 2R (read every input byte, read+write
    # acc), so its processed-bytes rate cannot exceed
    # stream_rate * 3R / (I + 2R).
    def make_ceiling(K):
        def loop(x, acc):
            def body(i, acc):
                # roll makes each iteration depend on the fully
                # materialized previous carry, so XLA can neither cancel
                # xor pairs nor fuse the K iterations into one read of x
                return jnp.roll(acc, 1) ^ x
            out = jax.lax.fori_loop(jnp.int32(0), jnp.int32(K), body, acc)
            return out.sum(dtype=jnp.uint32)
        return loop

    x0 = jnp.arange(n * nw, dtype=jnp.uint32)
    per_s = fit_per_iter(make_ceiling, (x0, acc0))
    stream_gbps = n * layout.row_size / per_s / 1e9
    in_bytes = sum(int(np.asarray(d).nbytes) for d in datas) + \
        sum(0 if m is None else n for m in masks)
    R = n * layout.row_size
    ceiling_gbps = stream_gbps * 3 * R / (in_bytes + 2 * R)

    # CPU Arrow-style baseline (best of 3)
    cpu_s = min(
        (lambda t0: (numpy_pack(host_cols, layout),
                     time.perf_counter() - t0))(time.perf_counter())[1]
        for _ in range(3))
    cpu_gbps = n * layout.row_size / cpu_s / 1e9

    # wire-bytes cross-check on a 100k slice against the numpy oracle
    ncheck = 100_000
    check = jax.jit(lambda d, m: _to_rows_bytes(layout, d, m))
    got = np.asarray(check(
        tuple(d[:ncheck] for d in datas),
        tuple(None if m is None else m[:ncheck] for m in masks)))
    ref = numpy_pack([(nm, d0[:ncheck], None if v0 is None else v0[:ncheck])
                      for nm, d0, v0 in host_cols], layout).reshape(-1)
    ok = bool((got == ref).all())
    return dev_gbps, cpu_gbps, ok, ceiling_gbps


def numpy_pack_var(i64, chars, lens, vlay):
    """CPU Arrow-style variable-width row packer (vectorized numpy): the
    long+string half of the configs[0] baseline."""
    base = vlay.base
    pad = (lens.astype(np.int64) + 7) // 8 * 8
    row_sizes = base.row_size + pad
    row_ends = np.cumsum(row_sizes)
    row_starts = row_ends - row_sizes
    out = np.zeros(int(row_ends[-1]), np.uint8)
    n = i64.shape[0]
    fixed_idx = row_starts[:, None] + np.arange(8)
    out[fixed_idx] = i64.view(np.uint8).reshape(n, 8)
    slot = np.empty((n, 8), np.uint8)
    slot[:, :4] = np.full((n,), base.row_size, np.uint32)[:, None].view(
        np.uint8).reshape(n, 4)
    slot[:, 4:] = lens.astype(np.uint32)[:, None].view(np.uint8).reshape(n, 4)
    out[row_starts[:, None] + np.arange(8, 16)] = slot
    out[row_starts + base.validity_offset] = 0x3  # both columns valid
    coff = np.zeros(n + 1, np.int64)
    np.cumsum(lens, out=coff[1:])
    within = np.arange(coff[-1]) - np.repeat(coff[:-1], lens)
    out[np.repeat(row_starts + base.row_size, lens) + within] = chars
    return out


def bench_row_conversion_strings(n=1_000_000):
    # 1M rows (not the fixed path's 2M): the wire-sort program's REMOTE
    # compile scales with the lane count and dominated bench wall time at
    # 2M (~10 min); GB/s is intensive in n (measured 0.140 vs 0.146)
    """BASELINE configs[0] at its specified shape: long + string columns."""
    import jax.numpy as jnp
    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.ops.row_conversion import (
        convert_to_rows, variable_width_layout)
    from spark_rapids_jni_tpu import dtypes as dt

    rng = np.random.default_rng(5)
    i64 = rng.integers(-2**62, 2**62, n).astype(np.int64)
    lens = rng.integers(4, 21, n).astype(np.int32)
    coff = np.zeros(n + 1, np.int64)
    np.cumsum(lens, out=coff[1:])
    chars = rng.integers(97, 123, int(coff[-1])).astype(np.uint8)
    table = Table([Column.from_numpy(i64),
                   Column.string(jnp.asarray(chars),
                                 jnp.asarray(coff.astype(np.int32)))],
                  ["l", "s"])
    blobs = convert_to_rows(table)  # compile + warm
    total = sum(int(np.asarray(b.offsets)[-1]) for b in blobs)

    # steady-state device rate, same fori_loop methodology as the fixed
    # headline (salt the long column; lengths are untouched so shapes and
    # the wire sort stay identical)
    import jax
    from spark_rapids_jni_tpu.ops.row_conversion import _to_rows_var_fused
    vlay = variable_width_layout(table.dtypes())
    soffs = (jnp.asarray(table.columns[1].offsets, jnp.int32),)
    schars = (jnp.asarray(table.columns[1].data, jnp.uint8),)
    masks = (None, None)
    total_words = total // 4

    def make_loop(K):
        def loop(d, acc):
            def body(i, acc):
                wire, _ = _to_rows_var_fused(
                    vlay, (max(8, (int(lens.max()) + 7) // 8 * 8),),
                    total_words,
                    (d ^ i.astype(jnp.int64), None), masks, soffs, schars)
                return acc ^ wire
            out = jax.lax.fori_loop(jnp.int32(0), jnp.int32(K), body, acc)
            return out.sum(dtype=jnp.uint32)
        return loop

    # ONE compiled loop (a second K would double the minutes-long remote
    # compile of the ~12M-lane wire sort); K=8 amortizes dispatch+fetch to <10%, and
    # dividing the whole wall time by K under-counts nothing — conservative
    acc0 = jnp.zeros((total_words,), jnp.uint32)
    K = 8
    jf = jax.jit(make_loop(K))
    args = (table.columns[0].data, acc0)
    int(jf(*args))  # compile + warm
    per = min(_timed(jf, args) for _ in range(3)) / K
    dev_gbps = total / per / 1e9

    t0 = time.perf_counter()
    ref = numpy_pack_var(i64, chars, lens, vlay)
    cpu_s = time.perf_counter() - t0
    cpu_gbps = total / cpu_s / 1e9
    # byte-exactness cross-check on a slice against the numpy oracle
    got = np.asarray(blobs[0].children[0].data).view(np.uint8)
    ok = bool((got[:1 << 16] == ref[:1 << 16]).all())
    return dev_gbps, cpu_gbps, ok


# ---------------------------------------------------------------------------
# 2. CastStrings: string -> int64 (north-star op)
# ---------------------------------------------------------------------------

def bench_cast_strings(n=2_000_000):
    import jax
    import jax.numpy as jnp
    from spark_rapids_jni_tpu.ops.cast_strings import _parse_number

    rng = np.random.default_rng(1)
    width = 18
    digits = rng.integers(0, 10, (n, width)).astype(np.uint8) + ord("0")
    mat = jnp.asarray(digits)
    lengths = jnp.full((n,), width, jnp.int32)

    def make_loop(K):
        def loop(mat, lengths):
            def body(i, acc):
                m = mat.at[:, -1].set((48 + i % 10).astype(jnp.uint8))
                p = _parse_number(m, lengths, True, False, False)
                return acc + p["digits"].sum(dtype=jnp.uint64).astype(
                    jnp.uint32) + p["syntax_ok"].sum(dtype=jnp.uint32)
            return jax.lax.fori_loop(jnp.int32(0), jnp.int32(K), body,
                                     jnp.uint32(0))
        return loop

    per = fit_per_iter(make_loop, (mat, lengths))
    dev_mrows = n / per / 1e6

    # CPU baseline: pandas vectorized string->int64 on the same strings
    import pandas as pd
    ser = pd.Series(digits.view(f"S{width}").ravel())
    t0 = time.perf_counter()
    ser.astype(np.int64)
    cpu_mrows = n / (time.perf_counter() - t0) / 1e6
    return dev_mrows, cpu_mrows


# ---------------------------------------------------------------------------
# 3. HashAggregate: groupby(sum, count) (BASELINE configs[2] shape, scaled)
# ---------------------------------------------------------------------------

def bench_hash_aggregate(n=2_000_000, nkeys=100_000):
    import jax
    import jax.numpy as jnp
    from spark_rapids_jni_tpu import dtypes as dt
    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.ops.aggregate import groupby_padded

    rng = np.random.default_rng(2)
    k = jnp.asarray(rng.integers(0, nkeys, n).astype(np.int64))
    v = jnp.asarray(rng.integers(-1000, 1000, n).astype(np.int64))

    def make_loop(K):
        def loop(k, v):
            def body(i, acc):
                tbl = Table([Column(dt.INT64, data=k ^ (i & 7)),
                             Column(dt.INT64, data=v)], ["k", "v"])
                _, aggs, ng = groupby_padded(
                    tbl, ["k"], [("v", "sum"), ("v", "count")])
                return acc + ng.astype(jnp.uint32) + \
                    aggs[0].data.sum(dtype=jnp.int64).astype(jnp.uint32)
            return jax.lax.fori_loop(jnp.int64(0), jnp.int64(K), body,
                                     jnp.uint32(0))
        return loop

    per = fit_per_iter(make_loop, (k, v), k1=8, k2=32)
    dev_mrows = n / per / 1e6

    import pandas as pd
    df = pd.DataFrame({"k": np.asarray(k), "v": np.asarray(v)})
    t0 = time.perf_counter()
    df.groupby("k").v.agg(["sum", "count"])
    cpu_mrows = n / (time.perf_counter() - t0) / 1e6
    return dev_mrows, cpu_mrows


# ---------------------------------------------------------------------------
# 4. Parquet scan (ParquetChunked north star)
# ---------------------------------------------------------------------------

def bench_parquet_scan(n=2_000_000):
    import shutil, tempfile, os
    import pyarrow as pa
    import pyarrow.parquet as pq
    from spark_rapids_jni_tpu.io import read_parquet

    rng = np.random.default_rng(3)
    tbl = pa.table({
        "a": pa.array(rng.integers(0, 10**9, n).astype(np.int64)),
        "b": pa.array(rng.standard_normal(n)),
        "c": pa.array(rng.integers(0, 100, n).astype(np.int32)),
    })
    d = tempfile.mkdtemp()
    path = os.path.join(d, "bench.parquet")
    pq.write_table(tbl, path, compression="snappy", row_group_size=250_000)
    nbytes = n * (8 + 8 + 4)
    from spark_rapids_jni_tpu.io import ParquetFile

    # host decode (the engine's own work; page decode + dict gather), using
    # the same threaded row-group fan-out ParquetFile.read uses
    from concurrent.futures import ThreadPoolExecutor
    f = ParquetFile(path)
    list(map(f._decode_group, range(1)))  # warm imports/mmap
    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=min(f.num_row_groups,
                                            os.cpu_count() or 4)) as ex:
        list(ex.map(f._decode_group, range(f.num_row_groups)))
    decode = nbytes / (time.perf_counter() - t0) / 1e6

    # measured host->device link rate (NOT assumed — VERDICT r3 weak #4:
    # the e2e number only means something next to the link it rides)
    import jax
    probe = np.random.default_rng(9).integers(0, 255, 24 << 20,
                                              dtype=np.uint8)
    x = jax.device_put(probe); float(x[0])  # warm
    link = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        x = jax.device_put(probe); float(x[0])
        link = max(link, probe.nbytes / (time.perf_counter() - t0) / 1e6)

    # end-to-end into device columns; bounded by the host->device link,
    # measured above and reported alongside
    t0 = time.perf_counter()
    out = read_parquet(path)
    float(out.columns[0].data.sum())  # wait for device residency
    e2e = nbytes / (time.perf_counter() - t0) / 1e6

    # repeated-scan rate through the staged single-transfer path: the
    # jitted unpack compiles on the first call (cached per schema), so a
    # warm scan is the NDS steady-state number.  Best-of-3: link
    # throughput swings run to run, and a single sample has recorded a
    # stall as the steady state
    read_parquet(path, staged=True)  # compile + first transfer
    e2e_staged = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        out = read_parquet(path, staged=True)
        float(out.columns[0].data.sum())
        e2e_staged = max(e2e_staged,
                         nbytes / (time.perf_counter() - t0) / 1e6)

    t0 = time.perf_counter()
    pq.read_table(path)
    arrow = nbytes / (time.perf_counter() - t0) / 1e6
    shutil.rmtree(d)
    return decode, e2e, e2e_staged, arrow, link


def bench_window(n=2_000_000):
    """Window rank + running sum (RANGE frame) vs single-threaded pandas."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.dtypes import INT64
    from spark_rapids_jni_tpu.ops.window import window

    rng = np.random.default_rng(4)
    p = rng.integers(0, 10_000, n).astype(np.int64)
    o = rng.integers(0, 1_000_000, n).astype(np.int64)
    v = rng.integers(-1000, 1000, n).astype(np.int64)
    pj, oj, vj = jnp.asarray(p), jnp.asarray(o), jnp.asarray(v)

    def make_loop(k):
        def body(i, carry):
            t = Table([Column(INT64, data=pj),
                       Column(INT64, data=oj + i),  # salt defeats hoisting
                       Column(INT64, data=vj)], ["p", "o", "v"])
            out = window(t, ["p"], ["o"], [(None, "rank"), ("v", "sum")])
            return carry + out["rank"].data[0] + out["sum_v"].data[-1]

        return lambda: jax.lax.fori_loop(0, k, body, jnp.int64(0))

    per = fit_per_iter(make_loop, ())
    dev_mrows = n / per / 1e6

    import pandas as pd
    df = pd.DataFrame({"p": p, "o": o, "v": v})
    t0 = time.perf_counter()
    s = df.sort_values(["p", "o"], kind="stable")
    s.groupby("p")["o"].rank(method="min")
    s.groupby("p")["v"].cumsum()
    cpu_mrows = n / (time.perf_counter() - t0) / 1e6
    return dev_mrows, cpu_mrows


def bench_distributed_join(n_left=1_000_000, n_right=250_000):
    """Shuffle + distributed SortMergeJoin, BASELINE configs[3].

    The deployment has one physical chip, so the 8-device exchange runs in
    a subprocess on the virtual CPU mesh (the same path dryrun_multichip
    validates); the single-chip metrics above stay on the TPU.  Reports
    Mrows/s of left-side input through shuffle+join, and the local
    single-device join rate on the same host for scale context.
    """
    import subprocess
    import os
    import sys as _sys
    script = f"""
import json, time
import numpy as np
import spark_rapids_jni_tpu
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.ops.join import inner_join
from spark_rapids_jni_tpu.parallel import make_mesh, distributed_join
from spark_rapids_jni_tpu.parallel.mesh import shard_table
from spark_rapids_jni_tpu.parallel.shuffle import shuffle_table_padded
rng = np.random.default_rng(3)
nl, nr = {n_left}, {n_right}
left = Table([Column.from_numpy(rng.integers(0, nr, nl).astype(np.int64)),
              Column.from_numpy(rng.integers(-100, 100, nl).astype(np.int64))],
             ["k", "v"])
right = Table([Column.from_numpy(rng.permutation(nr).astype(np.int64)),
               Column.from_numpy(np.arange(nr, dtype=np.int64))],
              ["k", "rv"])
mesh = make_mesh(8)
out = distributed_join(left, right, mesh, ["k"])   # warm (compile)
t0 = time.perf_counter(); out = distributed_join(left, right, mesh, ["k"])
drows = out.num_rows; dt_d = time.perf_counter() - t0
out2 = inner_join(left, right, ["k"])              # warm
t0 = time.perf_counter(); out2 = inner_join(left, right, ["k"])
dt_l = time.perf_counter() - t0
assert out.num_rows == out2.num_rows
# stage breakdown (VERDICT r3 #8): exchange-only cost on the same data,
# measured as the standalone shuffle of each side; join = total - exchange
lt = shard_table(left, mesh); rt = shard_table(right, mesh)
for t in (lt, rt): shuffle_table_padded(t, mesh, ["k"])  # warm
t0 = time.perf_counter()
sl, okl, _ = shuffle_table_padded(lt, mesh, ["k"])
sr, okr, _ = shuffle_table_padded(rt, mesh, ["k"])
float(np.asarray(okl)[0]); float(np.asarray(okr)[0])
dt_x = time.perf_counter() - t0
xbytes = sum(int(np.asarray(c.data).nbytes) for c in sl.columns) + \
         sum(int(np.asarray(c.data).nbytes) for c in sr.columns)
# padding efficiency: live rows over padded exchange slots (VERDICT r4 #7)
pad_eff = (nl + nr) / (sl.num_rows + sr.num_rows)
print(json.dumps({{"dist_mrows_s": nl / dt_d / 1e6,
                   "local_mrows_s": nl / dt_l / 1e6,
                   "exchange_s": dt_x, "total_s": dt_d,
                   "exchange_MB": xbytes / 1e6,
                   "padding_efficiency": pad_eff,
                   "rows_out": drows}}))
"""
    # hand the bench run's trace to the child (SRJT_TRACE_ID): its flight
    # recorder, timeline, and any post-mortem bundle join the parent's id
    from spark_rapids_jni_tpu.utils import blackbox
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               SRJT_TRACE_ID=(blackbox.current_trace()
                              or blackbox.new_trace_id()),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=8"),
               JAX_ENABLE_X64="1")
    env["PYTHONPATH"] = os.path.dirname(os.path.abspath(__file__))
    try:
        r = subprocess.run([_sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, timeout=900)
        lines = r.stdout.strip().splitlines()
        if r.returncode != 0 or not lines:
            print(f"distributed-join bench failed (rc={r.returncode}):\n"
                  f"{r.stderr[-2000:]}", file=_sys.stderr)
            return None
        return json.loads(lines[-1])
    except Exception as e:
        print(f"distributed-join bench failed: {e!r}", file=_sys.stderr)
        return None


def bench_engine_q5(n=200_000):
    """Whole-plan bridge dispatch vs per-op dispatch on a q5-lite shape.

    The engine's reason to exist (docs/ENGINE.md): on an RTT-dominated link
    every per-op call pays a round trip, so submitting the serialized plan
    in ONE ``PLAN_EXECUTE`` message amortizes the link out of the plan walk.
    Builds a tmpdir warehouse, runs scan+semi-join+agg+join+agg+sort both
    ways against one server, and reports cold (plan-cache miss: optimize +
    execute) vs warm (cache hit) plan dispatch plus the round-trip counts.
    No pinned baseline yet: first round with the engine in the tree.
    """
    import tempfile

    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_jni_tpu.bridge import BridgeClient, spawn_server
    from spark_rapids_jni_tpu.bridge import protocol as P
    from spark_rapids_jni_tpu.engine import Aggregate, Join, Scan, Sort

    rng = np.random.default_rng(11)
    with tempfile.TemporaryDirectory() as tmp:
        root = os.path.join(tmp, "wh")
        os.mkdir(root)
        pq.write_table(pa.table({
            "ss_sold_date_sk": pa.array(
                np.sort(rng.integers(0, 400, n)).astype(np.int64)),
            "ss_store_sk": pa.array(rng.integers(1, 13, n).astype(np.int64)),
            "ss_ext_sales_price": pa.array(rng.uniform(0.5, 300.0, n)),
        }), os.path.join(root, "store_sales.parquet"), row_group_size=20_000)
        # the date filter is pre-applied at write time: the bridge's per-op
        # surface has no comparison op, so both paths scan the kept range
        pq.write_table(pa.table({
            "d_date_sk": pa.array(np.arange(100, 300, dtype=np.int64)),
        }), os.path.join(root, "date_dim.parquet"))
        pq.write_table(pa.table({
            "s_store_sk": pa.array(np.arange(1, 13, dtype=np.int64)),
            "s_mgr": pa.array(np.arange(1, 13, dtype=np.int64) % 4),
        }), os.path.join(root, "store.parquet"))

        kept = Join(Scan(os.path.join(root, "store_sales.parquet")),
                    Scan(os.path.join(root, "date_dim.parquet")),
                    ["ss_sold_date_sk"], ["d_date_sk"], how="semi")
        totals = Aggregate(kept, ["ss_store_sk"],
                           [("ss_ext_sales_price", "sum"),
                            ("ss_ext_sales_price", "count")],
                           names=["sales", "n"])
        joined = Join(totals, Scan(os.path.join(root, "store.parquet")),
                      ["ss_store_sk"], ["s_store_sk"], how="inner")
        plan = Sort(Aggregate(joined, ["s_mgr"],
                              [("sales", "sum"), ("n", "sum")],
                              names=["sales", "n"]),
                    (("s_mgr", True),))

        sock = os.path.join(tmp, "tpub.sock")
        # this process runs jax itself (main()), so it holds the
        # accelerator: the server child is put on the CPU explicitly
        proc = spawn_server(sock, env={"JAX_PLATFORMS": "cpu"})
        try:
            c = BridgeClient(sock)
            t0 = time.perf_counter()
            h_cold = c.execute_plan(plan)
            t_cold = time.perf_counter() - t0
            t0 = time.perf_counter()
            h_warm = c.execute_plan(plan)
            t_warm = time.perf_counter() - t0
            plan_trips = 1  # each execute_plan was one _call

            before = c.round_trips
            t0 = time.perf_counter()
            sh = c.read_parquet(os.path.join(root, "store_sales.parquet"))
            dh = c.read_parquet(os.path.join(root, "date_dim.parquet"))
            th = c.read_parquet(os.path.join(root, "store.parquet"))
            kh = c.join(sh, dh, [0], [0], "semi")
            gh = c.groupby(kh, [1], [(2, P.AGG_SUM), (2, P.AGG_COUNT)])
            jh = c.join(gh, th, [0], [0], "inner")
            g2 = c.groupby(jh, [3], [(1, P.AGG_SUM), (2, P.AGG_SUM)])
            oh = c.sort(g2, [(0, True, None)])
            t_perop = time.perf_counter() - t0
            perop_trips = c.round_trips - before

            got = c.export_table(h_warm[0])
            want = c.export_table(oh)
            same = got.num_rows == want.num_rows and all(
                np.allclose(np.asarray(a.data), np.asarray(b.data))
                for a, b in zip(got.columns, want.columns))
            # prefix narrows the counter/hist/gauge blocks server-side;
            # the plan_cache block rides along regardless
            cache = c.metrics(prefix="bridge.")["plan_cache"]
            c.shutdown_server()
        except Exception as e:
            print(f"engine bench failed: {e!r}", file=sys.stderr)
            proc.kill()
            return None
        finally:
            proc.wait(timeout=30)
    return {"cold_ms": t_cold * 1e3, "warm_ms": t_warm * 1e3,
            "per_op_ms": t_perop * 1e3, "plan_round_trips": plan_trips,
            "per_op_round_trips": perop_trips, "results_match": same,
            "cache_hits": cache["hits"], "cache_misses": cache["misses"]}


def _pipeline_warehouse(root, n, rng):
    """q5-lite warehouse for the local-executor pipeline bench."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    pq.write_table(pa.table({
        "ss_sold_date_sk": pa.array(
            np.sort(rng.integers(0, 400, n)).astype(np.int64)),
        "ss_store_sk": pa.array(rng.integers(1, 13, n).astype(np.int64)),
        "ss_ext_sales_price": pa.array(rng.uniform(0.5, 300.0, n)),
        "ss_net_profit": pa.array(rng.uniform(-50.0, 120.0, n)),
    }), os.path.join(root, "store_sales.parquet"),
        row_group_size=max(1, n // 8))
    pq.write_table(pa.table({
        "d_date_sk": pa.array(np.arange(100, 300, dtype=np.int64)),
    }), os.path.join(root, "date_dim.parquet"))
    pq.write_table(pa.table({
        "s_store_sk": pa.array(np.arange(1, 13, dtype=np.int64)),
        "s_mgr": pa.array(np.arange(1, 13, dtype=np.int64) % 4),
    }), os.path.join(root, "store.parquet"))


def _pipeline_plans(root, chunk_bytes):
    """(q5-lite plan, chunked-scan aggregate plan) over the warehouse.

    The q5 filters survive optimization as real Filter nodes (the scan
    predicate only prunes row groups), so the fused executor has chains to
    compile; the chunked aggregate feeds the scan straight into a fused
    partial-groupby segment — the double-buffered streaming shape.
    """
    from spark_rapids_jni_tpu.engine import (Aggregate, Filter, Join, Scan,
                                             Sort, col, lit)
    dates_f = Filter(Scan(os.path.join(root, "date_dim.parquet")),
                     ("&", (">=", col("d_date_sk"), lit(100)),
                      ("<", col("d_date_sk"), lit(300))))
    sales = Scan(os.path.join(root, "store_sales.parquet"))
    kept = Filter(Join(sales, dates_f, ["ss_sold_date_sk"], ["d_date_sk"],
                       how="semi"),
                  ("&", (">", col("ss_net_profit"), lit(0.0)),
                   (">=", col("ss_sold_date_sk"), lit(100))))
    totals = Aggregate(kept, ["ss_store_sk"],
                       [("ss_ext_sales_price", "sum"),
                        ("ss_net_profit", "sum"),
                        ("ss_ext_sales_price", "count")],
                       names=["sales", "profit", "n"])
    joined = Join(totals, Scan(os.path.join(root, "store.parquet")),
                  ["ss_store_sk"], ["s_store_sk"], how="inner")
    q5 = Sort(Aggregate(joined, ["s_mgr"],
                        [("sales", "sum"), ("profit", "sum"), ("n", "sum")],
                        names=["sales", "profit", "n"]),
              (("s_mgr", True),))

    chunked = Aggregate(
        Filter(Scan(os.path.join(root, "store_sales.parquet"),
                    chunk_bytes=chunk_bytes),
               (">", col("ss_ext_sales_price"), lit(1.0))),
        ["ss_store_sk"],
        [("ss_ext_sales_price", "sum"), ("ss_net_profit", "sum"),
         ("ss_net_profit", "min"), ("ss_net_profit", "max"),
         ("ss_ext_sales_price", "count")],
        names=["sales", "profit", "lo", "hi", "n"])
    return q5, chunked


def _run_plan(opt, fused, prefetch):
    """One timed local execute; blocks until the result is ready."""
    import jax
    from spark_rapids_jni_tpu.engine import execute, new_stats
    stats = new_stats()
    t0 = time.perf_counter()
    out = execute(opt, stats, fused=fused, prefetch=prefetch)
    jax.block_until_ready([c.data for c in out.columns
                           if c.data is not None])
    return time.perf_counter() - t0, out, stats


def _tables_match(a, b) -> bool:
    if a.num_rows != b.num_rows or a.num_columns != b.num_columns:
        return False
    for ca, cb in zip(a.columns, b.columns):
        if not np.allclose(np.asarray(ca.data, np.float64),
                           np.asarray(cb.data, np.float64)):
            return False
    return True


def bench_engine_pipeline(n=600_000, chunk_bytes=512_000, smoke=False):
    """Fused-segment compilation + double-buffered streaming vs PR 1.

    Two comparisons on the LOCAL executor (no bridge — this measures the
    execution engine itself):

    - q5-lite, warm: node-by-node interpreter (``fused=False``, the PR 1
      executor) vs fused segments (Filter/Project/Aggregate chains as one
      jitted program each).  Cold fused time is reported too: it pays the
      segment trace+compile the ``engine.segment_cache`` then amortizes.
    - chunked-scan aggregate: serial chunk streaming (``prefetch=0``) vs
      double-buffered (``prefetch=2``) on the same fused plan, plus the
      interpreted loop both ways — overlap hides host decode behind device
      compute; the interpreted loop ALSO syncs per chunk, so it shows the
      overlap even when device compute is cheap.

    ``smoke=True``: tiny shapes, correctness cross-checks only, no timing
    claims — the CI hook that keeps the perf paths importable+runnable.
    """
    import tempfile

    from spark_rapids_jni_tpu.engine import optimize
    from spark_rapids_jni_tpu.engine.segment import SEGMENT_CACHE
    from spark_rapids_jni_tpu.ops.selection import sort_table
    from spark_rapids_jni_tpu.ops.order import SortKey

    rng = np.random.default_rng(13)
    with tempfile.TemporaryDirectory() as tmp:
        root = os.path.join(tmp, "wh")
        os.mkdir(root)
        _pipeline_warehouse(root, n, rng)
        q5, chunked = _pipeline_plans(root, chunk_bytes)
        q5_opt, ch_opt = optimize(q5), optimize(chunked)

        def sorted_by_key(t):
            return sort_table(t, [SortKey(t[t.names[0]], ascending=True)])

        # q5-lite: cold fused (segment trace+compile), then warm both ways
        t_cold, out_f, _ = _run_plan(q5_opt, fused=True, prefetch=0)
        t_fused = min(_run_plan(q5_opt, fused=True, prefetch=0)[0]
                      for _ in range(1 if smoke else 3))
        _run_plan(q5_opt, fused=False, prefetch=0)  # warm interp caches too
        t_interp, out_i, _ = _run_plan(q5_opt, fused=False, prefetch=0)
        if not smoke:
            t_interp = min(t_interp, *(
                _run_plan(q5_opt, fused=False, prefetch=0)[0]
                for _ in range(2)))
        q5_match = _tables_match(out_f, out_i)

        # chunked streaming aggregate: serial vs double-buffered.
        # A/B pairs interleaved and min-taken — on a saturated host the
        # run-to-run noise is the same order as the overlap win, and
        # alternating keeps cache/thermal drift out of the ratio.
        reps = 1 if smoke else 3
        _run_plan(ch_opt, fused=True, prefetch=0)   # compile warm-up
        _run_plan(ch_opt, fused=False, prefetch=0)  # warm interp loop
        t_serial = t_overlap = t_iserial = t_ioverlap = float("inf")
        out_s = st_s = out_o = st_o = out_is = out_io = None
        for _ in range(reps):
            dt, out_s, st_s = _run_plan(ch_opt, fused=True, prefetch=0)
            t_serial = min(t_serial, dt)
            dt, out_o, st_o = _run_plan(ch_opt, fused=True, prefetch=2)
            t_overlap = min(t_overlap, dt)
            dt, out_is, _ = _run_plan(ch_opt, fused=False, prefetch=0)
            t_iserial = min(t_iserial, dt)
            dt, out_io, _ = _run_plan(ch_opt, fused=False, prefetch=2)
            t_ioverlap = min(t_ioverlap, dt)
        stream_match = (_tables_match(sorted_by_key(out_s),
                                      sorted_by_key(out_o))
                        and _tables_match(sorted_by_key(out_s),
                                          sorted_by_key(out_is))
                        and _tables_match(sorted_by_key(out_is),
                                          sorted_by_key(out_io)))

    seg = SEGMENT_CACHE.stats()
    return {
        "q5_cold_fused_ms": t_cold * 1e3,
        "q5_warm_fused_ms": t_fused * 1e3,
        "q5_warm_interp_ms": t_interp * 1e3,
        "fused_vs_interp": t_interp / t_fused if t_fused else None,
        # headline overlap ratio: the per-chunk-sync streaming loop (PR 1's
        # serial streaming aggregate) — the consumer blocks on every chunk's
        # groupby sync, which is exactly the idle time double-buffered decode
        # hides.  The fused loop's consumer never blocks (async dispatch, one
        # sync at the combine), so on a single-core CPU host its A/B is a
        # wash — reported separately; where transfers are slow the fused
        # consumer DOES block on them, which is the deploy case for prefetch
        # (not measured on today's machine).
        "stream_serial_ms": t_iserial * 1e3,
        "stream_overlap_ms": t_ioverlap * 1e3,
        "overlap_vs_serial": t_iserial / t_ioverlap if t_ioverlap else None,
        "fused_stream_serial_ms": t_serial * 1e3,
        "fused_stream_overlap_ms": t_overlap * 1e3,
        "fused_overlap_vs_serial": (t_serial / t_overlap
                                    if t_overlap else None),
        "chunks": st_s["chunks"],
        "fused_streamed": bool(st_o["fused_segments"]),
        "results_match": bool(q5_match and stream_match),
        "segment_cache": {"hits": seg["hits"], "misses": seg["misses"],
                          "evictions": seg["evictions"]},
    }


def bench_engine_join(n=400_000, chunk_bytes=512_000, smoke=False):
    """Streamed probe join + streaming top-k vs their PR 2 fallbacks.

    Two A/B pairs on the LOCAL executor, interleaved min-of-reps like
    ``bench_engine_pipeline``:

    - chunked probe join: the fused path prepares the build side (hash +
      stable sort) ONCE via ``BUILD_CACHE`` and probes every chunk inside
      one jitted program, vs the interpreted per-chunk loop that re-runs
      the whole ``inner_join`` — build sort included — on every chunk.
      The cold-cache counter contract (``hits == chunks - 1``) is asserted
      here, not just in tests, so the bench can't silently measure the
      wrong path.
    - ORDER BY ... LIMIT k: the streamed ``TopK`` (capacity-k device
      buffer merged per chunk) vs materializing + fully sorting the table
      (``SRJT_TOPK=0`` semantics), same optimized plan.
    """
    import tempfile

    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_jni_tpu.engine import (Aggregate, BUILD_CACHE, Filter,
                                             Join, Limit, Scan, Sort, col,
                                             lit, optimize)
    from spark_rapids_jni_tpu.ops.order import SortKey
    from spark_rapids_jni_tpu.ops.selection import sort_table
    from spark_rapids_jni_tpu.utils.config import config as cfg
    from spark_rapids_jni_tpu.utils.config import refresh

    rng = np.random.default_rng(17)
    with tempfile.TemporaryDirectory() as tmp:
        root = os.path.join(tmp, "wh")
        os.mkdir(root)
        pq.write_table(pa.table({
            "k": pa.array(rng.integers(0, 2_000, n).astype(np.int64)),
            "v": pa.array(rng.uniform(-5.0, 50.0, n)),
        }), os.path.join(root, "fact.parquet"),
            row_group_size=max(1, n // 8))
        pq.write_table(pa.table({
            "dk": pa.array(np.arange(0, 2_000, dtype=np.int64)),
            "dv": pa.array((np.arange(0, 2_000) % 16).astype(np.int64)),
        }), os.path.join(root, "dim.parquet"))

        def fact_scan():
            return Filter(Scan(os.path.join(root, "fact.parquet"),
                               chunk_bytes=chunk_bytes),
                          (">", col("v"), lit(0.0)))

        j_opt = optimize(Aggregate(
            Join(fact_scan(), Scan(os.path.join(root, "dim.parquet")),
                 ["k"], ["dk"], how="inner"),
            ["dv"], [("v", "sum"), ("v", "count")], names=["s", "c"]))
        t_opt = optimize(Limit(Sort(fact_scan(), (("v", False),)), 32))

        def sorted_by_key(t):
            return sort_table(t, [SortKey(t[t.names[0]], ascending=True)])

        reps = 1 if smoke else 3
        _run_plan(j_opt, fused=True, prefetch=0)   # compile warm-up
        _run_plan(j_opt, fused=False, prefetch=0)  # warm interp loop
        t_cached = t_perchunk = float("inf")
        out_c = out_p = st_c = None
        for _ in range(reps):
            dt, out_c, st_c = _run_plan(j_opt, fused=True, prefetch=0)
            t_cached = min(t_cached, dt)
            dt, out_p, _ = _run_plan(j_opt, fused=False, prefetch=0)
            t_perchunk = min(t_perchunk, dt)
        join_match = _tables_match(sorted_by_key(out_c), sorted_by_key(out_p))

        # cold-cache counter contract: exactly one miss, then a hit per
        # remaining chunk
        BUILD_CACHE.clear()
        h0, m0 = BUILD_CACHE.hits, BUILD_CACHE.misses
        _, _, st_cold = _run_plan(j_opt, fused=True, prefetch=0)
        counters_ok = (st_cold["fused_segments"] == 1
                       and BUILD_CACHE.misses - m0 == 1
                       and BUILD_CACHE.hits - h0 == st_cold["chunks"] - 1)

        _run_plan(t_opt, fused=True, prefetch=0)  # warm-up
        t_stream = t_full = float("inf")
        out_ts = out_tf = st_ts = None
        for _ in range(reps):
            dt, out_ts, st_ts = _run_plan(t_opt, fused=True, prefetch=0)
            t_stream = min(t_stream, dt)
            cfg.topk = False
            try:
                dt, out_tf, _ = _run_plan(t_opt, fused=True, prefetch=0)
            finally:
                refresh()
            t_full = min(t_full, dt)
        # ordered compare: tie order is part of the top-k contract
        topk_match = _tables_match(out_ts, out_tf)

    return {
        "join_cached_build_ms": t_cached * 1e3,
        "join_per_chunk_build_ms": t_perchunk * 1e3,
        "cached_vs_per_chunk": (t_perchunk / t_cached
                                if t_cached else None),
        "topk_stream_ms": t_stream * 1e3,
        "topk_full_sort_ms": t_full * 1e3,
        "topk_vs_full_sort": t_full / t_stream if t_stream else None,
        "chunks": st_cold["chunks"],
        "join_streamed_fused": bool(st_c["fused_segments"]),
        "topk_streamed": bool(st_ts["topk"]),
        "build_cache_counters_ok": bool(counters_ok),
        "results_match": bool(join_match and topk_match),
        "build_cache": {k: v for k, v in BUILD_CACHE.stats().items()
                        if k != "maxsize"},
    }


def bench_engine_dist(n_fact=240_000, n_dim=2_000, smoke=False):
    """Partitioning-aware distributed planning: broadcast vs shuffle join.

    The deployment has one physical chip, so (like the SMJ bench above)
    the 8-device plans run in a subprocess on the virtual CPU mesh.  Four
    configurations of the same join+aggregate plan:

    - **broadcast**: dim under ``SRJT_BROADCAST_ROWS`` — the planner
      replicates the build side, probe chunks stream through the fused
      probe-join segment with zero probe-side exchange.
    - **exchange**: ``SRJT_BROADCAST_ROWS=0`` forces hash exchanges on
      both join sides (the partial agg still pushes below its exchange).
    - **smj**: the r5 shuffle+SortMergeJoin comparator
      (``distributed_join``) on the same data, join stage only —
      ``broadcast_vs_smj8`` is the stage-for-stage A/B against the
      broadcast-hash join stage the planner picks (replicate the build +
      shard-local hash probe) on the same in-memory tables.
    - **co-partitioned**: scans declared partitioned on the join keys,
      aggregate grouped on the partition key — must plan ZERO exchanges
      (verified, and the static census must match the executed count).

    Reports wall times, the broadcast_vs_smj8 / broadcast_vs_exchange
    ratios, exchange counts (static and executed), and result parity.
    """
    import subprocess
    import os
    import sys as _sys
    script = f"""
import json, os, tempfile, time
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import spark_rapids_jni_tpu
import jax
root = tempfile.mkdtemp()
rng = np.random.default_rng(9)
nf, nd = {n_fact}, {n_dim}
# a wide fact: the shuffle pays wire for every payload column, the
# broadcast join pays none of them (the representative star-schema case)
k = rng.integers(0, nd, nf)
v = np.round(rng.uniform(0, 100, nf), 3)
v2 = rng.integers(-100, 100, nf)
v3 = rng.integers(0, 1000, nf)
pq.write_table(pa.table({{"k": pa.array(k, pa.int64()),
                          "v": pa.array(v, pa.float64()),
                          "v2": pa.array(v2, pa.int64()),
                          "v3": pa.array(v3, pa.int64())}}),
               os.path.join(root, "fact.parquet"), row_group_size=32_000)
dk = np.arange(nd, dtype=np.int64)
pq.write_table(pa.table({{"dk": pa.array(dk), "grp": pa.array(dk % 7)}}),
               os.path.join(root, "dim.parquet"))

from spark_rapids_jni_tpu.engine import (Aggregate, Join, Scan, execute,
                                         new_stats, optimize)
from spark_rapids_jni_tpu.engine.verify import (check_partitioning,
                                                plan_exchanges, verify)
from spark_rapids_jni_tpu.utils.config import refresh
fact, dim = os.path.join(root, "fact.parquet"), os.path.join(root,
                                                             "dim.parquet")

def mkplan(**scan_kw):
    j = Join(Scan(fact, chunk_bytes=192_000, **scan_kw.get("f", {{}})),
             Scan(dim, **scan_kw.get("d", {{}})), ("k",), ("dk",), "inner")
    return Aggregate(j, ("grp",),
                     (("v", "sum"), ("v2", "sum"), ("v3", "sum"),
                      ("v", "count")),
                     ("total", "t2", "t3", "n"))

def timed(opt):
    stats = new_stats()
    execute(opt, new_stats())                       # warm (compile)
    t0 = time.perf_counter()
    out = execute(opt, stats)
    jax.block_until_ready([c.data for c in out.columns])
    return time.perf_counter() - t0, out, stats

def norm(t):
    cols = sorted(zip(t.names, (c.to_numpy() for c in t.columns)))
    order = np.argsort(cols[0][1], kind="stable")
    return [(n, np.round(a[order], 4).tolist()) for n, a in cols]

base_t, base, _ = timed(optimize(mkplan()))

optA = optimize(mkplan(), distribute=True)
exA = plan_exchanges(optA)
tA, outA, stA = timed(optA)

os.environ["SRJT_BROADCAST_ROWS"] = "0"
refresh()
optB = optimize(mkplan(), distribute=True)
exB = plan_exchanges(optB)
tB, outB, stB = timed(optB)

# per-device exchange attribution of the hash-exchange run just timed:
# the per-(src, dest) wire matrix must sum EXACTLY to the query's
# engine.exchange.wire_bytes counter (the invariant premerge asserts)
from spark_rapids_jni_tpu.utils import metrics as _m
dev_attrib = {{"matrix_matches": None, "skew": None, "max_dev_rows": None,
               "wire_matrix_sum": None, "wire_bytes_counter": None,
               "exchange_nodes": 0, "explain_skew_rendered": None}}
if _m.enabled():
    summ = _m.recent_summaries()[-1]
    ex_nodes = [n for n in summ["nodes"] if n.get("wire_matrix")]
    mat_sum = sum(sum(r) for n in ex_nodes for r in n["wire_matrix"])
    ctr = summ["counters"].get("engine.exchange.wire_bytes", 0)
    dev_attrib.update(
        exchange_nodes=len(ex_nodes),
        wire_matrix_sum=mat_sum, wire_bytes_counter=ctr,
        matrix_matches=bool(ex_nodes) and mat_sum == ctr,
        skew=max(n.get("skew") or 0.0 for n in ex_nodes)
        if ex_nodes else None,
        max_dev_rows=max(n.get("max_dev_rows") or 0 for n in ex_nodes)
        if ex_nodes else None)
    # and the rendered EXPLAIN ANALYZE must carry the skew columns on the
    # same forced-exchange plan shape (SRJT_DIST routes optimize())
    os.environ["SRJT_DIST"] = "1"
    refresh()
    from spark_rapids_jni_tpu.engine.explain import explain_analyze
    rep = explain_analyze(mkplan())
    dev_attrib["explain_skew_rendered"] = "skew=" in rep.text
    # the AQE evidence plane on the same report: every plan-node line must
    # carry the cardinality columns, and the decision footer's structural
    # entry count must equal the static census of the optimized plan
    from spark_rapids_jni_tpu.engine.verify import decision_census
    node_lines = [ln for ln in rep.text.splitlines()
                  if ln.strip() and not ln.lstrip().startswith("--")]
    cen = decision_census(optimize(mkplan(), distribute=True), dist=True)
    # runtime (adaptive:*) entries carry a path too but are deliberately
    # outside the static census — census counts PLANNED structure only
    pathed = sum(1 for d in rep.decisions
                 if "path" in d and not d.get("runtime"))
    dev_attrib["evidence"] = {{
        "node_lines_annotated": all("est_rows=" in ln and "q_error=" in ln
                                    for ln in node_lines),
        "decisions": len(rep.decisions),
        "decisions_pathed": pathed,
        "census": len(cen),
        "census_matches": pathed == len(cen),
        "footer_rendered":
            ("-- decisions (" + str(len(rep.decisions)) + "):") in rep.text,
    }}
    del os.environ["SRJT_DIST"]

del os.environ["SRJT_BROADCAST_ROWS"]
refresh()

# join-stage A/B on the same in-memory tables: the r5 comparator
# (shuffle both sides + SortMergeJoin) vs the broadcast-hash stage the
# planner picks (replicate the build, probe shard-locally)
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.ops.join import inner_join
from spark_rapids_jni_tpu.parallel import distributed_join, make_mesh
from spark_rapids_jni_tpu.parallel.mesh import broadcast_table
mesh = make_mesh(8)
lt = Table([Column.from_numpy(k.astype(np.int64)),
            Column.from_numpy(np.arange(nf, dtype=np.int64)),
            Column.from_numpy(v2.astype(np.int64)),
            Column.from_numpy(v3.astype(np.int64))],
           ["k", "v", "v2", "v3"])
rt = Table([Column.from_numpy(dk), Column.from_numpy(dk % 7)],
           ["k", "grp"])
distributed_join(lt, rt, mesh, ["k"])   # warm
t0 = time.perf_counter()
smj = distributed_join(lt, rt, mesh, ["k"])
tC = time.perf_counter() - t0
inner_join(lt, broadcast_table(rt, mesh), ["k"])   # warm
t0 = time.perf_counter()
bj = inner_join(lt, broadcast_table(rt, mesh), ["k"])
jax.block_until_ready([c.data for c in bj.columns])
tJ = time.perf_counter() - t0
assert bj.num_rows == smj.num_rows

optD = optimize(Aggregate(
    Join(Scan(fact, partitioned_by=("k",)),
         Scan(dim, partitioned_by=("dk",)), ("k",), ("dk",), "inner"),
    ("k",), (("v", "sum"),), ("total",)), distribute=True)
verify(optD)
check_partitioning(optD)
exD = plan_exchanges(optD)
stD = new_stats()
execute(optD, stD)

print(json.dumps({{
    "local_s": base_t, "broadcast_s": tA, "exchange_s": tB, "smj_s": tC,
    "bjoin_s": tJ,
    "ratios": {{"broadcast_vs_smj8": tC / tJ if tJ else None,
                "broadcast_vs_exchange": tB / tA if tA else None}},
    "exchanges": {{"broadcast_static": len(exA),
                   "broadcast_executed": stA["exchanges"],
                   "exchange_static": len(exB),
                   "exchange_executed": stB["exchanges"],
                   "copartitioned_static": len(exD),
                   "copartitioned_executed": stD["exchanges"]}},
    "smj_rows": smj.num_rows,
    "device_attrib": dev_attrib,
    "results_match": bool(norm(outA) == norm(base)
                          and norm(outB) == norm(base))}}))
"""
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=8"),
               JAX_ENABLE_X64="1")
    env["PYTHONPATH"] = os.path.dirname(os.path.abspath(__file__))
    try:
        r = subprocess.run([_sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, timeout=900)
        lines = r.stdout.strip().splitlines()
        if r.returncode != 0 or not lines:
            print(f"engine-dist bench failed (rc={r.returncode}):\n"
                  f"{r.stderr[-2000:]}", file=_sys.stderr)
            return None
        return json.loads(lines[-1])
    except Exception as e:
        print(f"engine-dist bench failed: {e!r}", file=_sys.stderr)
        return None


def bench_engine_fused_stage(n_fact=240_000, n_keys=2_000, smoke=False):
    """Whole-stage fusion across the exchange (SRJT_FUSE_EXCHANGE): the
    ``partial-agg -> hash Exchange -> final-agg`` sandwich lowered into ONE
    ``jax.jit(shard_map(...))`` program vs the host-orchestrated exchange
    path on the same plan (8-device virtual CPU mesh, subprocess like the
    other dist benches).

    The plan is the dist smoke shape: a chunked scan feeding the grouped
    aggregate (the host path streams the partial agg chunk-by-chunk and
    then orchestrates the exchange with two deliberate syncs; the fused
    path runs the whole stage as one program).  ``SRJT_FUSE_GROUPS`` is
    sized at 2x the workload's distinct-key count — the documented
    operator sizing for the static in-program exchange.

    Both paths are compile-warmed, then timed (min of 3).  A scan-only
    plan (same file, same chunking) is timed the same way and subtracted
    from both walls: the two paths pay an identical chunked parquet scan,
    so ``vs_host_exchange`` compares the exchange STAGE (partial agg ->
    exchange -> final agg) the fusion actually replaces; the raw
    end-to-end walls and their ratio (``vs_host_e2e``) are reported
    alongside.  Also reports the host-sync counter deltas of each timed
    run (the fused run must pay exactly its static ``verify.sync_budget``),
    the exchange census (static == executed on both paths), and bit-exact
    result parity.
    """
    import subprocess
    import os
    import sys as _sys
    script = f"""
import json, os, tempfile, time
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import spark_rapids_jni_tpu
import jax
root = tempfile.mkdtemp()
rng = np.random.default_rng(17)
nf, nk = {n_fact}, {n_keys}
k = rng.integers(0, nk, nf)
# quarter-grid floats: partial-then-combine sums are exactly representable,
# so fused-vs-host parity is bit-exact despite reduction-order differences
v = (rng.integers(0, 400, nf) * 0.25).astype(np.float64)
v2 = rng.integers(-100, 100, nf)
pq.write_table(pa.table({{"k": pa.array(k, pa.int64()),
                          "v": pa.array(v, pa.float64()),
                          "v2": pa.array(v2, pa.int64())}}),
               os.path.join(root, "fact.parquet"), row_group_size=32_000)
fact = os.path.join(root, "fact.parquet")

from spark_rapids_jni_tpu.engine import (Aggregate, Scan, execute,
                                         new_stats, optimize)
from spark_rapids_jni_tpu.engine.verify import plan_exchanges, sync_budget
from spark_rapids_jni_tpu.utils import tracing
from spark_rapids_jni_tpu.utils.config import config, refresh

def mkplan():
    return Aggregate(Scan(fact, chunk_bytes=192_000), ("k",),
                     (("v", "sum"), ("v2", "sum"), ("v", "count")),
                     ("total", "t2", "n"))

def syncs():
    return tracing.counters_snapshot("engine.host_sync") \\
        .get("engine.host_sync", 0)

def timed(opt):
    execute(opt, new_stats())                       # warm (compile)
    best, out, stats, dsync = None, None, None, None
    for _ in range(3):
        st = new_stats()
        s0 = syncs()
        t0 = time.perf_counter()
        o = execute(opt, st)
        jax.block_until_ready([c.data for c in o.columns])
        dt = time.perf_counter() - t0
        if best is None or dt < best:
            best, out, stats, dsync = dt, o, st, syncs() - s0
    return best, out, stats, dsync

def norm(t):
    cols = sorted(zip(t.names, (c.to_numpy() for c in t.columns)))
    order = np.argsort(cols[0][1], kind="stable")
    return [(n, np.asarray(a)[order].tolist()) for n, a in cols]

# scan-only baseline: both paths pay this identical chunked scan, so the
# exchange-stage comparison subtracts it from both walls (raw walls are
# reported too — nothing rides on the subtraction being hidden)
optS = optimize(Scan(fact, chunk_bytes=192_000), distribute=True)
tS, _, _, _ = timed(optS)

# host-orchestrated exchange (the pre-fusion distributed path)
optH = optimize(mkplan(), distribute=True)
exH = plan_exchanges(optH)
tH, outH, stH, syH = timed(optH)

# fused whole-stage program; the static group budget sized at 2x the
# workload's distinct keys (the documented operator sizing — overflow
# would fall back to the host path, which the dispatch counter catches)
os.environ["SRJT_FUSE_EXCHANGE"] = "1"
os.environ["SRJT_FUSE_GROUPS"] = str(2 * nk)
refresh()
optF = optimize(mkplan(), distribute=True)
exF = plan_exchanges(optF)
budget = sync_budget(optF, cfg=config)
d0 = tracing.counters_snapshot("engine.fused_stage.dispatches") \\
    .get("engine.fused_stage.dispatches", 0)
tF, outF, stF, syF = timed(optF)
dispatches = tracing.counters_snapshot("engine.fused_stage.dispatches") \\
    .get("engine.fused_stage.dispatches", 0) - d0
del os.environ["SRJT_FUSE_EXCHANGE"]
del os.environ["SRJT_FUSE_GROUPS"]
refresh()

print(json.dumps({{
    "host_s": tH, "fused_s": tF, "scan_s": tS,
    "vs_host_exchange": (tH - tS) / max(tF - tS, 1e-9),
    "vs_host_e2e": tH / tF if tF else None,
    "host_syncs": {{"host": syH, "fused": syF,
                    "fused_budget": sum(e["count"] for e in budget)}},
    "dispatches": dispatches,
    "exchanges": {{"host_static": len(exH),
                   "host_executed": stH["exchanges"],
                   "fused_static": len(exF),
                   "fused_executed": stF["exchanges"]}},
    "results_match": bool(norm(outF) == norm(outH))}}))
"""
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=8"),
               JAX_ENABLE_X64="1")
    env.pop("SRJT_FUSE_EXCHANGE", None)
    env.pop("SRJT_FUSE_GROUPS", None)
    env["PYTHONPATH"] = os.path.dirname(os.path.abspath(__file__))
    try:
        r = subprocess.run([_sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, timeout=900)
        lines = r.stdout.strip().splitlines()
        if r.returncode != 0 or not lines:
            print(f"engine-fused-stage bench failed (rc={r.returncode}):\n"
                  f"{r.stderr[-2000:]}", file=_sys.stderr)
            return None
        return json.loads(lines[-1])
    except Exception as e:
        print(f"engine-fused-stage bench failed: {e!r}", file=_sys.stderr)
        return None


def bench_engine_aqe(n_fact=240_000, n_keys=2_000, smoke=False):
    """Adaptive execution (SRJT_AQE) A/Bs on the virtual 8-device mesh.

    Two experiments, both with runtime rewrites verified and parity
    asserted against the AQE-off single-device plan:

    - **skewed vs balanced twin**: the same groupby-mean plan over two
      facts that differ only in key distribution (half the skewed fact
      sits on ONE key).  mean is non-decomposable, so the FULL input
      crosses the exchange on the group key — without AQE the hot
      destination inflates the padded all_to_all capacity for every
      device.  With ``SRJT_AQE=1`` the skew-split rule re-deals the hot
      destinations' rows round-robin; ``skew_ratio`` (skewed / balanced
      wall time, both AQE-on) is the headline, with the applied
      ``adaptive:skew_split`` ledger entry and the post-split
      ``engine.exchange.skew`` gauge as the structural evidence.
    - **repeat-query cold vs warmed**: a join whose build side is a
      selective Filter — the footer estimate (the UN-filtered row count)
      sits above the broadcast threshold so run 1 plans a shuffle join,
      but the measured actual sits below it.  Run 2 of the same source
      fingerprint reads run 1's profile (``SRJT_PROFILE_DIR``) and plans
      the broadcast join outright (``adaptive:history_warmed``);
      ``rerun_vs_first`` is warmed / cold wall time.

    Wall-clock ratios are gated report-only (BENCH_BASELINES.json —
    machine noise at smoke scale); the structural evidence on this line
    is what ci/premerge.sh asserts.
    """
    import subprocess
    import os
    import sys as _sys
    script = f"""
import json, os, tempfile, time
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import spark_rapids_jni_tpu
import jax
root = tempfile.mkdtemp()
rng = np.random.default_rng(21)
nf, nk = {n_fact}, {n_keys}

from spark_rapids_jni_tpu.engine import (Aggregate, Filter, Join, Scan, col,
                                         execute, lit, new_stats, optimize)
from spark_rapids_jni_tpu.engine.plan import Exchange, topo_nodes
from spark_rapids_jni_tpu.utils import metrics as _m
from spark_rapids_jni_tpu.utils.config import refresh

v = np.round(rng.uniform(0, 100, nf), 3)
k_bal = rng.integers(0, nk, nf)
k_skew = k_bal.copy()
k_skew[: nf // 2] = 3      # one hot key: half the fact routes to one device
for name, kk in (("bal", k_bal), ("skew", k_skew)):
    pq.write_table(pa.table({{"k": pa.array(kk, pa.int64()),
                              "v": pa.array(v, pa.float64())}}),
                   os.path.join(root, name + ".parquet"),
                   row_group_size=32_000)

def meanplan(path):
    # mean is non-decomposable: no partial pushes below the exchange, the
    # full input crosses the wire keyed on k — a hot key is a genuinely
    # hot destination device, the shape the skew-split rule exists for
    return Aggregate(Scan(path), ("k",), (("v", "mean"),), ("m",))

def timed(opt):
    stats = new_stats()
    execute(opt, new_stats())                       # warm (compile)
    t0 = time.perf_counter()
    out = execute(opt, stats)
    jax.block_until_ready([c.data for c in out.columns])
    return time.perf_counter() - t0, out, stats

def norm(t):
    cols = sorted(zip(t.names, (c.to_numpy() for c in t.columns)))
    order = np.argsort(cols[0][1], kind="stable")
    return [(n, np.round(a[order], 4).tolist()) for n, a in cols]

# -- skewed vs balanced twin, both under AQE --------------------------------
SKEW_THRESHOLD = 2.0
os.environ["SRJT_AQE"] = "1"
os.environ["SRJT_AQE_SKEW"] = str(SKEW_THRESHOLD)
refresh()
t_bal, out_bal, st_bal = timed(optimize(
    meanplan(os.path.join(root, "bal.parquet")), distribute=True))
opt_skew = optimize(meanplan(os.path.join(root, "skew.parquet")),
                    distribute=True)
t_skew, out_skew, st_skew = timed(opt_skew)
splits = [d for d in getattr(opt_skew, "_decisions", ())
          if d.get("kind") == "adaptive:skew_split" and d.get("triggered")]
# the gauge holds the LAST exchange's post-placement skew — the skewed
# run's split exchange, read before anything else executes
gauge_skew = (_m.gauges_snapshot("engine.exchange.skew")
              .get("engine.exchange.skew") if _m.enabled() else None)

os.environ["SRJT_AQE"] = "0"
refresh()
base_skew = execute(optimize(meanplan(os.path.join(root, "skew.parquet"))),
                    new_stats())
base_bal = execute(optimize(meanplan(os.path.join(root, "bal.parquet"))),
                   new_stats())
skew_parity = bool(norm(out_skew) == norm(base_skew)
                   and norm(out_bal) == norm(base_bal))

# -- repeat-query cold vs history-warmed ------------------------------------
# fresh store: the newest-profile-by-fingerprint lookup must see exactly
# run 1, not whatever the inherited smoke store holds
os.environ["SRJT_PROFILE_DIR"] = tempfile.mkdtemp(prefix="srjt-aqe-warm-")
os.environ["SRJT_AQE"] = "1"
os.environ["SRJT_BROADCAST_ROWS"] = "100"
refresh()
nd = 500
dk = np.arange(nd, dtype=np.int64)
pq.write_table(pa.table({{"dk": pa.array(dk), "grp": pa.array(dk % 7)}}),
               os.path.join(root, "dim.parquet"))
# a WIDE fact for the repeat-query A/B: the cold shuffle join pays wire
# for every payload column, the warmed broadcast join pays none of them —
# the same asymmetry the dist bench measures, here it is what makes run 2
# strictly faster rather than noise-level
pq.write_table(pa.table({{"k": pa.array(k_bal, pa.int64()),
                          "v": pa.array(v, pa.float64()),
                          "v2": pa.array(rng.integers(-100, 100, nf),
                                         pa.int64()),
                          "v3": pa.array(rng.integers(0, 1000, nf),
                                         pa.int64())}}),
               os.path.join(root, "warm.parquet"), row_group_size=32_000)

def joinplan():
    # the Filter keeps 50 of 500 dim rows; the footer estimate is the
    # UN-filtered 500 (> broadcast threshold 100) so the cold run plans a
    # shuffle join — the measured actual (50, under the threshold) is
    # what run 2 warms from
    dim = Filter(Scan(os.path.join(root, "dim.parquet")),
                 ("<", col("dk"), lit(50)))
    # unchunked probe: both plans materialize the fact once, so the A/B
    # isolates the planned exchange (what warming removes) instead of
    # mixing in per-chunk dispatch overhead on the shared-core mesh
    j = Join(Scan(os.path.join(root, "warm.parquet")),
             dim, ("k",), ("dk",), "inner")
    return Aggregate(j, ("grp",),
                     (("v", "sum"), ("v2", "sum"), ("v3", "sum"),
                      ("v", "count")),
                     ("total", "t2", "t3", "n"))

def kinds(opt):
    return sorted(e.kind for e in topo_nodes(opt) if isinstance(e, Exchange))

opt1 = optimize(joinplan(), distribute=True)
t1, out1, st1 = timed(opt1)
opt2 = optimize(joinplan(), distribute=True)    # reads run 1's profile
t2, out2, st2 = timed(opt2)
warmed = [d for d in getattr(opt2, "_decisions", ())
          if d.get("kind") == "adaptive:history_warmed"]
warm_parity = bool(norm(out1) == norm(out2))

print(json.dumps({{
    "balanced_s": t_bal, "skewed_s": t_skew,
    "skew_ratio": t_skew / t_bal if t_bal else None,
    "skew": {{"splits_applied": len(splits),
              "aqe_splits": st_skew["aqe_splits"],
              "pre_skew": splits[0].get("measured_skew") if splits else None,
              "post_skew": splits[0].get("post_skew") if splits else None,
              "gauge_skew": gauge_skew,
              "threshold": SKEW_THRESHOLD,
              "parity": skew_parity}},
    "first_s": t1, "rerun_s": t2,
    "rerun_vs_first": t2 / t1 if t1 else None,
    "warm": {{"warmed_entries": len(warmed),
              "choice": warmed[0].get("choice") if warmed else None,
              "run1_kinds": kinds(opt1), "run2_kinds": kinds(opt2),
              "run1_flips": st1["aqe_flips"],
              "run2_broadcast_planned": bool(
                  "broadcast" in kinds(opt2)
                  and "broadcast" not in kinds(opt1)),
              "faster": bool(t2 < t1),
              "parity": warm_parity}}}}))
"""
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=8"),
               JAX_ENABLE_X64="1",
               # gauge + profile evidence need the metrics layer on even
               # when the parent runs bare
               SRJT_METRICS="1")
    env["PYTHONPATH"] = os.path.dirname(os.path.abspath(__file__))
    try:
        r = subprocess.run([_sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, timeout=900)
        lines = r.stdout.strip().splitlines()
        if r.returncode != 0 or not lines:
            print(f"engine-aqe bench failed (rc={r.returncode}):\n"
                  f"{r.stderr[-2000:]}", file=_sys.stderr)
            return None
        return json.loads(lines[-1])
    except Exception as e:
        print(f"engine-aqe bench failed: {e!r}", file=_sys.stderr)
        return None


def _serving_plans(root, chunk_bytes, k, base=1.0):
    """k distinct-fingerprint chunked aggregates over the warehouse.

    Same shape (filter + partial groupby, the fused streaming segment),
    different filter literal per plan — so every plan is its own plan-cache
    / result-cache entry and its own scheduler fingerprint, like k tenants
    running k different queries of the same family.
    """
    from spark_rapids_jni_tpu.engine import Aggregate, Filter, Scan, col, lit
    sales = os.path.join(root, "store_sales.parquet")
    return [Aggregate(
        Filter(Scan(sales, chunk_bytes=chunk_bytes),
               (">", col("ss_ext_sales_price"), lit(base + 0.25 * i))),
        ["ss_store_sk"],
        [("ss_ext_sales_price", "sum"), ("ss_net_profit", "sum"),
         ("ss_ext_sales_price", "count")],
        names=["sales", "profit", "n"]) for i in range(k)]


def bench_engine_serving(n=240_000, clients=8, smoke=False):
    """Multi-tenant serving: N concurrent sessions vs the same N queries
    serial, plus the admission controller's shed path and the result-set
    cache, all against real subprocess servers (engine/scheduler.py,
    docs/SERVING.md).

    Server A (scheduler on, result cache OFF so every pass really
    executes): warm all plans once, then time a serial pass (one client,
    N queries back-to-back) vs a concurrent pass (N clients, one query
    each) of the SAME plans — per-trace results must be bit-exact across
    the two passes.  Reports per-query p50/p99 under contention, aggregate
    throughput, and the concurrent-vs-serial throughput ratio.

    Server B (1 session slot, SRJT_SLO_MS=1 so every run burns its error
    budget, profile store on, result cache on): a repeat plan over
    unchanged inputs must serve from the result cache (speedup = cold /
    warm), and while a long holder query occupies the only slot, a
    fingerprint with burn >= SRJT_ADMISSION_BURN must be shed immediately
    with the typed ``AdmissionRejectedError`` carrying trace_id + bundle
    pointer — the client-side contract for load-shedding.
    """
    import tempfile
    import threading

    from spark_rapids_jni_tpu.bridge import BridgeClient, spawn_server
    from spark_rapids_jni_tpu.utils.errors import AdmissionRejectedError

    rng = np.random.default_rng(29)
    out: dict = {}
    with tempfile.TemporaryDirectory() as tmp:
        root = os.path.join(tmp, "wh")
        os.mkdir(root)
        _pipeline_warehouse(root, n, rng)
        chunk = 64_000 if smoke else 512_000
        plans = _serving_plans(root, chunk, clients)

        # --- server A: serial vs concurrent on the same warm plans -------
        sock = os.path.join(tmp, "srv.sock")
        proc = spawn_server(sock, env={
            "JAX_PLATFORMS": "cpu",     # the parent holds the accelerator
            "SRJT_MAX_SESSIONS": str(clients),
            "SRJT_RESULT_CACHE": "0",   # measure execution, not the cache
        })
        try:
            warm = BridgeClient(sock)
            for p in plans:   # compile + warm jit caches once per plan
                for h in warm.execute_plan(p):
                    warm.release(h)

            serial_tabs = {}
            t0 = time.perf_counter()
            for i, p in enumerate(plans):
                hs = warm.execute_plan(p)
                serial_tabs[i] = warm.export_table(hs[0])
                for h in hs:
                    warm.release(h)
            serial_s = time.perf_counter() - t0
            warm.close()

            lat: dict = {}
            conc_tabs: dict = {}
            errs: list = []
            start = threading.Barrier(clients + 1)

            def one(i):
                try:
                    c = BridgeClient(sock)
                    start.wait()
                    q0 = time.perf_counter()
                    hs = c.execute_plan(plans[i])
                    conc_tabs[i] = c.export_table(hs[0])
                    lat[i] = time.perf_counter() - q0
                    for h in hs:
                        c.release(h)
                    c.close()
                except Exception as e:  # noqa: BLE001 — reported below
                    errs.append((i, repr(e)))

            ts = [threading.Thread(target=one, args=(i,), daemon=True)
                  for i in range(clients)]
            for t in ts:
                t.start()
            start.wait()
            t0 = time.perf_counter()
            for t in ts:
                t.join(timeout=300)
            concurrent_s = time.perf_counter() - t0

            parity = (not errs and len(conc_tabs) == clients and all(
                _tables_match(conc_tabs[i], serial_tabs[i])
                for i in range(clients)))
            c2 = BridgeClient(sock)
            sched = c2.serving_stats()["scheduler"]
            c2.shutdown_server()
        except Exception as e:
            print(f"engine-serving bench failed: {e!r}", file=sys.stderr)
            proc.kill()
            return None
        finally:
            proc.wait(timeout=30)

        samples = sorted(lat.values())
        p50 = samples[len(samples) // 2] if samples else 0.0
        p99 = samples[min(len(samples) - 1,
                          int(len(samples) * 0.99))] if samples else 0.0
        throughput = clients / concurrent_s if concurrent_s else 0.0
        serial_tp = clients / serial_s if serial_s else 0.0
        out.update({
            "clients": clients, "errors": errs,
            "parity": parity,
            "serial_s": serial_s, "concurrent_s": concurrent_s,
            "p50_ms": p50 * 1e3, "p99_ms": p99 * 1e3,
            "throughput_qps": throughput,
            "throughput_ratio": (throughput / serial_tp
                                 if serial_tp else None),
            "admitted": sched.get("admitted", 0),
            "rounds": sched.get("rounds", 0),
        })

        # --- server B: result cache + SLO-burn shed ----------------------
        prof_dir = os.path.join(tmp, "profiles")
        os.mkdir(prof_dir)
        sock2 = os.path.join(tmp, "srv2.sock")
        proc2 = spawn_server(sock2, env={
            "JAX_PLATFORMS": "cpu",     # the parent holds the accelerator
            "SRJT_MAX_SESSIONS": "1",
            "SRJT_ADMISSION_QUEUE_S": "2.0",
            "SRJT_RESULT_CACHE": "16",
            "SRJT_SLO_MS": "1",          # everything breaches: burn = 1.0
            "SRJT_PROFILE_DIR": prof_dir,
            # bundle dir so the typed shed error carries a post-mortem
            # pointer (the client-side contract: trace_id + bundle)
            "SRJT_BLACKBOX_DIR": os.path.join(tmp, "bb"),
        })
        try:
            c = BridgeClient(sock2)
            rc_plan = plans[0]
            t0 = time.perf_counter()
            for h in c.execute_plan(rc_plan):   # cold: executes + caches
                c.release(h)
            rc_cold = time.perf_counter() - t0
            t0 = time.perf_counter()
            for h in c.execute_plan(rc_plan):   # warm: result-cache hit
                c.release(h)
            rc_warm = time.perf_counter() - t0
            rc_hits = c.serving_stats()["result_cache"]["hits"]

            # burn plan: one profiled run (wall >> 1ms => burn 1.0), then
            # an mtime bump so the repeat MISSES the result cache and has
            # to face admission while the holder owns the only slot
            burn_plan = plans[1] if clients > 1 else plans[0]
            for h in c.execute_plan(burn_plan):
                c.release(h)
            sales = os.path.join(root, "store_sales.parquet")
            os.utime(sales)

            holder_plans = _serving_plans(root, 4_096, 3, base=100.0)
            holder_done = threading.Event()

            def hold(p):
                try:
                    hc = BridgeClient(sock2)
                    for h in hc.execute_plan(p):
                        hc.release(h)
                    hc.close()
                finally:
                    holder_done.set()

            shed = None
            for attempt, hp in enumerate(holder_plans):
                holder_done.clear()
                ht = threading.Thread(target=hold, args=(hp,), daemon=True)
                ht.start()
                time.sleep(0.4)   # let the holder take the slot
                if holder_done.is_set():
                    continue      # holder too fast: try a fresh one
                try:
                    hs = c.execute_plan(burn_plan)
                    for h in hs:
                        c.release(h)
                except AdmissionRejectedError as e:
                    shed = {"kind": e.kind, "retryable": e.retryable,
                            "trace_id": e.trace_id or "",
                            "bundle": getattr(e, "bundle_path", "") or "",
                            "message": str(e)[:120]}
                ht.join(timeout=300)
                if shed is not None:
                    break
            stats2 = c.serving_stats()
            c.shutdown_server()
        except Exception as e:
            print(f"engine-serving bench failed: {e!r}", file=sys.stderr)
            proc2.kill()
            return None
        finally:
            proc2.wait(timeout=30)

        out.update({
            "result_cache_cold_ms": rc_cold * 1e3,
            "result_cache_warm_ms": rc_warm * 1e3,
            "result_cache_speedup": (rc_cold / rc_warm) if rc_warm else None,
            "result_cache_hits": rc_hits,
            "shed": shed,
            "shed_count": stats2["scheduler"].get("shed", 0),
        })
    return out


def bench_parquet_device_decode(n=400_000, smoke=False):
    """Device-side Parquet decode (SRJT_DEVICE_DECODE): raw compressed
    pages shipped over the link and decoded in-kernel (ops/parquet_decode)
    vs the staged host path (pyarrow decode + pad + ship) on the same
    snappy+PLAIN int64 file.

    Three measurements:
      - kernel A/B on an incompressible file (random int64): a jitted
        ``decode_table`` over planned page chunks vs a warm
        ``ParquetChunkedReader.iter_staged`` pass, pyarrow alongside for
        scale; parity is bit-exact per row group against pyarrow's own
        decode.  The MB/s ratio is machine- and backend-dependent (on the
        CPU backend XLA's per-element gathers lose to pyarrow's SIMD
        decode shuffles), so it is gated report-only; correctness +
        engagement are the hard signal.
      - link bytes on a COMPRESSIBLE twin: compressed page bytes shipped
        (sum of ``DevicePageChunk.comp_bytes``) vs the uncompressed bytes
        the host path must move — the transfer-volume win the device path
        exists for.  The twin's snappy stream carries back-references, so
        this also runs the copy-resolution kernel at bench scale with
        bit-exact parity.
      - engine E2E: the same aggregate plan with the flag off vs on —
        bit-exact results, a ``scan:device_decode choice=device`` ledger
        entry covering every chunk with zero host fallbacks, and
        ``decode=device`` rendered on the EXPLAIN ANALYZE scan line.
    """
    import tempfile
    import time
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    import jax
    import spark_rapids_jni_tpu.utils.config as cfgmod
    from spark_rapids_jni_tpu.io import ParquetChunkedReader
    from spark_rapids_jni_tpu.io import parquet as pqio
    from spark_rapids_jni_tpu.ops import parquet_decode as pqd

    root = tempfile.mkdtemp(prefix="srjt-devdec-")
    rng = np.random.default_rng(5)
    path = os.path.join(root, "rand.parquet")
    pq.write_table(pa.table({
        "a": pa.array(rng.integers(0, 1 << 62, n), type=pa.int64()),
        "b": pa.array(rng.integers(0, 1 << 62, n), type=pa.int64()),
    }), path, row_group_size=max(n // 4, 1_000), compression="snappy",
        use_dictionary=False)

    def plan_all(pf):
        chunks = []
        for gi in range(pf.num_row_groups):
            c, reason = pqio.plan_device_group(pf, gi, None, 1 << 30)
            if c is None:
                raise RuntimeError(f"device plan rejected group {gi}: "
                                   f"{reason}")
            chunks.append(c)
        return chunks

    jfn = jax.jit(pqd.decode_table, static_argnums=1)

    def parity_all(path, chunks):
        pf_ref = pq.ParquetFile(path)
        for gi, c in enumerate(chunks):
            out = jfn(c.to_device(), c.geom)
            ref = pf_ref.read_row_group(gi)
            for nm, col in zip(out.names, out.columns):
                dev = np.asarray(col.data)[:c.nrows]
                if not np.array_equal(dev, ref[nm].to_numpy()):
                    return False
        return True

    pf = pqio.ParquetFile(path)
    chunks = plan_all(pf)
    parity = parity_all(path, chunks)

    # device timing: planes staged ahead (the engine's prefetch does the
    # same), the jitted decode is what's on the clock
    staged = [(c.to_device(), c.geom) for c in chunks]
    out = jfn(*staged[0])
    jax.block_until_ready([c.data for c in out.columns])  # warm compile
    t0 = time.perf_counter()
    for planes, geom in staged:
        out = jfn(planes, geom)
    jax.block_until_ready([c.data for c in out.columns])
    dev_s = time.perf_counter() - t0
    unc = sum(c.unc_bytes for c in chunks)

    def host_pass():
        rd = ParquetChunkedReader(path, pass_read_limit=1 << 30)
        last = None
        for tbl, _nv in rd.iter_staged():
            last = tbl
        jax.block_until_ready([c.data for c in last.columns])
        rd.close()

    host_pass()  # warm the unpack compile
    t0 = time.perf_counter()
    host_pass()
    host_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    pq.read_table(path)
    arrow_s = time.perf_counter() - t0

    # compressible twin: small repeating values -> snappy back-references
    cpath = os.path.join(root, "comp.parquet")
    pq.write_table(pa.table({
        "a": pa.array((np.arange(n) % 97).astype(np.int64)),
        "b": pa.array(np.repeat(np.arange(n // 100 + 1), 100)[:n]
                      .astype(np.int64)),
    }), cpath, row_group_size=max(n // 4, 1_000), compression="snappy",
        use_dictionary=False)
    cchunks = plan_all(pqio.ParquetFile(cpath))
    cparity = parity_all(cpath, cchunks)
    link_bytes = sum(c.comp_bytes for c in cchunks)
    host_bytes = sum(c.unc_bytes for c in cchunks)

    # engine E2E: flag off vs on, same plan, ledgered device engagement
    from spark_rapids_jni_tpu.engine import (Aggregate, Scan, execute,
                                             new_stats, optimize)
    from spark_rapids_jni_tpu.engine.explain import explain_analyze
    eplan = Aggregate(Scan(path, chunk_bytes=1 << 20), ["a"],
                      [("b", "max"), (None, "count_all")], names=["m", "c"])
    host_out = execute(optimize(eplan), new_stats())
    prev = os.environ.get("SRJT_DEVICE_DECODE")
    try:
        os.environ["SRJT_DEVICE_DECODE"] = "1"
        cfgmod.refresh()
        rep = explain_analyze(eplan, distribute=False)
    finally:
        if prev is None:
            os.environ.pop("SRJT_DEVICE_DECODE", None)
        else:
            os.environ["SRJT_DEVICE_DECODE"] = prev
        cfgmod.refresh()

    def norm(t):
        cols = {nm: np.asarray(c.data) for nm, c in zip(t.names, t.columns)}
        order = np.argsort(cols["a"], kind="stable")
        return [(nm, cols[nm][order].tolist()) for nm in sorted(cols)]

    dd = next((d for d in rep.decisions
               if d["kind"] == "scan:device_decode" and d.get("runtime")),
              {})
    return {
        "device_s": dev_s, "host_s": host_s, "arrow_s": arrow_s,
        "device_MBps": unc / dev_s / 1e6,
        "host_MBps": unc / host_s / 1e6,
        "arrow_MBps": unc / arrow_s / 1e6,
        "device_vs_host": host_s / dev_s if dev_s else None,
        "parity": bool(parity), "compressible_parity": bool(cparity),
        "link_bytes": link_bytes, "host_bytes": host_bytes,
        "link_ratio": link_bytes / host_bytes if host_bytes else None,
        "e2e_match": bool(norm(rep.result) == norm(host_out)),
        "ledger_choice": dd.get("choice"),
        "device_chunks": dd.get("device_chunks", 0),
        "host_fallbacks": dd.get("host_chunks", 0),
        "explain_decode": "decode=device" in rep.text,
    }


def smoke():
    """``bench.py --smoke``: tiny shapes through the fused + pipelined
    paths end-to-end, correctness-only (no timing assertions) — wired into
    ci/premerge.sh so perf-path exceptions fail fast in tier-1 budget."""
    import spark_rapids_jni_tpu  # noqa: F401  (enables x64)
    # profile store for the whole smoke run, BEFORE any bench executes: the
    # dist bench's subprocess inherits the env, so its exchange profiles
    # land in the same ring and the sixth line can report their skew
    if not os.environ.get("SRJT_PROFILE_DIR"):
        import tempfile
        os.environ["SRJT_PROFILE_DIR"] = tempfile.mkdtemp(
            prefix="srjt-smoke-profiles-")
        from spark_rapids_jni_tpu.utils.config import refresh
        refresh()
    res = bench_engine_pipeline(n=20_000, chunk_bytes=48_000, smoke=True)
    ok = bool(res and res["results_match"] and res["fused_streamed"]
              and res["chunks"] > 1)
    print(json.dumps({"metric": "engine_pipeline_smoke",
                      "ok": ok,
                      "chunks": res["chunks"] if res else None,
                      "segment_cache": res["segment_cache"] if res else None,
                      # absolute latencies (machine-dependent, gate with
                      # loose tolerance only) and dimensionless ratios
                      # (the portable signal) for ci/bench_gate.py
                      "latency_ms": {} if not res else {
                          "q5_warm_fused": round(res["q5_warm_fused_ms"], 3),
                          "q5_warm_interp": round(res["q5_warm_interp_ms"], 3),
                          "stream_serial": round(res["stream_serial_ms"], 3),
                          "stream_overlap": round(res["stream_overlap_ms"], 3),
                      },
                      "ratios": {} if not res else {
                          "fused_vs_interp": round(res["fused_vs_interp"], 4)
                          if res["fused_vs_interp"] else None,
                          "overlap_vs_serial":
                          round(res["overlap_vs_serial"], 4)
                          if res["overlap_vs_serial"] else None,
                      }}))
    jres = bench_engine_join(n=20_000, chunk_bytes=48_000, smoke=True)
    jok = bool(jres and jres["results_match"] and jres["join_streamed_fused"]
               and jres["topk_streamed"] and jres["build_cache_counters_ok"]
               and jres["chunks"] > 1)
    print(json.dumps({"metric": "engine_join_smoke",
                      "ok": jok,
                      "chunks": jres["chunks"] if jres else None,
                      "build_cache": jres["build_cache"] if jres else None,
                      "latency_ms": {} if not jres else {
                          "join_cached_build":
                          round(jres["join_cached_build_ms"], 3),
                          "topk_stream": round(jres["topk_stream_ms"], 3),
                      },
                      "ratios": {} if not jres else {
                          "cached_vs_per_chunk":
                          round(jres["cached_vs_per_chunk"], 4)
                          if jres["cached_vs_per_chunk"] else None,
                          "topk_vs_full_sort":
                          round(jres["topk_vs_full_sort"], 4)
                          if jres["topk_vs_full_sort"] else None,
                      }}))
    # third line: the observability layer itself — every execute() above ran
    # under a QueryMetrics, so with SRJT_METRICS on the snapshot must carry
    # per-query summaries (premerge greps this line for the block)
    from spark_rapids_jni_tpu.utils import metrics, timeline
    snap = metrics.snapshot()
    mok = (not metrics.enabled()) or bool(snap["queries"])
    print(json.dumps({"metric": "metrics_snapshot",
                      "ok": mok,
                      "enabled": metrics.enabled(),
                      **snap}))
    # fourth line: the timeline layer — with SRJT_TIMELINE on, the smoke
    # queries above must have produced trace events, and the dump (to
    # SRJT_TIMELINE_OUT, or a tempfile) must be valid Chrome trace JSON
    tok, tpath, tevents = True, None, 0
    if timeline.enabled():
        import tempfile
        tpath = os.environ.get("SRJT_TIMELINE_OUT")
        if not tpath:
            tpath = os.path.join(tempfile.gettempdir(),
                                 f"srjt-smoke-timeline-{os.getpid()}.json")
        trace = timeline.export()
        tevents = sum(1 for e in trace["traceEvents"] if e["ph"] != "M")
        timeline.dump(tpath)
        try:
            with open(tpath) as f:
                reloaded = json.load(f)
            tok = bool(tevents > 0 and reloaded["traceEvents"])
        except Exception:
            tok = False
    print(json.dumps({"metric": "timeline",
                      "ok": tok,
                      "enabled": timeline.enabled(),
                      "path": tpath,
                      "events": tevents}))
    # fifth line: the distributed planner — broadcast and hash-exchange
    # plans must match the single-device result, the static exchange
    # census must equal the executed count, and the co-partitioned plan
    # must carry ZERO exchanges (premerge asserts all three on this line)
    dres = bench_engine_dist(n_fact=60_000, n_dim=500, smoke=True)
    dattr = (dres or {}).get("device_attrib") or {}
    dok = bool(dres and dres["results_match"]
               and dres["exchanges"]["broadcast_static"]
               == dres["exchanges"]["broadcast_executed"]
               and dres["exchanges"]["exchange_static"]
               == dres["exchanges"]["exchange_executed"]
               and dres["exchanges"]["copartitioned_static"]
               == dres["exchanges"]["copartitioned_executed"] == 0
               # per-device attribution invariants (False fails; None =
               # metrics off, nothing to check)
               and dattr.get("matrix_matches") is not False
               and dattr.get("explain_skew_rendered") is not False
               # AQE evidence plane: cardinality columns on every node
               # line, decision footer count == static census (absent =
               # metrics off, nothing to check)
               and (dattr.get("evidence") or {}).get(
                   "node_lines_annotated") is not False
               and (dattr.get("evidence") or {}).get(
                   "census_matches") is not False)
    print(json.dumps({"metric": "engine_dist_smoke",
                      "ok": dok,
                      "exchanges": dres["exchanges"] if dres else None,
                      "device_attrib": dattr or None,
                      "latency_ms": {} if not dres else {
                          "broadcast": round(dres["broadcast_s"] * 1e3, 3),
                          "exchange": round(dres["exchange_s"] * 1e3, 3),
                          "smj8": round(dres["smj_s"] * 1e3, 3),
                      },
                      "ratios": {} if not dres else {
                          "broadcast_vs_smj8":
                          round(dres["ratios"]["broadcast_vs_smj8"], 4)
                          if dres["ratios"]["broadcast_vs_smj8"] else None,
                          "broadcast_vs_exchange":
                          round(dres["ratios"]["broadcast_vs_exchange"], 4)
                          if dres["ratios"]["broadcast_vs_exchange"]
                          else None,
                      }}))
    # fused whole-stage line: the partial-agg -> exchange -> final-agg
    # sandwich as ONE shard_map program (SRJT_FUSE_EXCHANGE) vs the
    # host-orchestrated exchange path — parity must be bit-exact, the
    # fused run must pay exactly its static sync_budget (and well under
    # the host path's count; premerge asserts < 5), and the exchange
    # census must stay static==executed on BOTH paths.  vs_host_exchange
    # is the report-only fused_stage.* gate key (BENCH_BASELINES.json)
    fres = bench_engine_fused_stage(n_fact=60_000, n_keys=500, smoke=True)
    fsync = (fres or {}).get("host_syncs") or {}
    fok = bool(fres and fres["results_match"]
               and fres.get("dispatches", 0) >= 1
               and fsync.get("fused") == fsync.get("fused_budget")
               and fres["exchanges"]["host_static"]
               == fres["exchanges"]["host_executed"]
               and fres["exchanges"]["fused_static"]
               == fres["exchanges"]["fused_executed"])
    print(json.dumps({"metric": "fused_stage",
                      "ok": fok,
                      "vs_host_exchange": round(fres["vs_host_exchange"], 4)
                      if fres and fres.get("vs_host_exchange") else None,
                      "host_syncs": fsync or None,
                      "dispatches": (fres or {}).get("dispatches"),
                      "exchanges": (fres or {}).get("exchanges"),
                      "results_match": (fres or {}).get("results_match"),
                      "vs_host_e2e": round(fres["vs_host_e2e"], 4)
                      if fres and fres.get("vs_host_e2e") else None,
                      "latency_ms": {} if not fres else {
                          "host_exchange": round(fres["host_s"] * 1e3, 3),
                          "fused": round(fres["fused_s"] * 1e3, 3),
                          "scan_baseline": round(fres["scan_s"] * 1e3, 3),
                      }}))
    # device-decode line (metric name "parquet" so the gate key flattens
    # to parquet.device_vs_host): compressed pages decoded in-kernel vs
    # the staged host path.  ok gates on what is machine-independent —
    # bit-exact parity (both datasets + engine E2E), every chunk decoded
    # on-device with zero fallbacks, decode=device rendered in EXPLAIN —
    # while the MB/s ratio and link ratio are report-only gate keys
    # (device_vs_host is backend-dependent: the CPU backend loses to
    # pyarrow's SIMD shuffles; the number exists to track drift, not to
    # assert the accelerator win at smoke scale)
    pdres = bench_parquet_device_decode(n=48_000, smoke=True)
    pdok = bool(pdres and pdres["parity"] and pdres["compressible_parity"]
                and pdres["e2e_match"]
                and pdres["ledger_choice"] == "device"
                and pdres["device_chunks"] >= 1
                and pdres["host_fallbacks"] == 0
                and pdres["explain_decode"]
                and pdres["link_ratio"] and pdres["link_ratio"] < 1.0)
    print(json.dumps({"metric": "parquet",
                      "ok": pdok,
                      "device_vs_host": round(pdres["device_vs_host"], 4)
                      if pdres and pdres.get("device_vs_host") else None,
                      "link_ratio": round(pdres["link_ratio"], 4)
                      if pdres and pdres.get("link_ratio") else None,
                      "device_chunks": (pdres or {}).get("device_chunks"),
                      "host_fallbacks": (pdres or {}).get("host_fallbacks"),
                      "parity": (pdres or {}).get("parity"),
                      "e2e_match": (pdres or {}).get("e2e_match"),
                      "latency_ms": {} if not pdres else {
                          "device": round(pdres["device_s"] * 1e3, 3),
                          "host": round(pdres["host_s"] * 1e3, 3),
                          "pyarrow": round(pdres["arrow_s"] * 1e3, 3),
                      },
                      "MBps": {} if not pdres else {
                          "device": round(pdres["device_MBps"], 1),
                          "host": round(pdres["host_MBps"], 1),
                          "pyarrow": round(pdres["arrow_MBps"], 1),
                      }}))
    # sixth line: adaptive execution — the skewed twin must apply at least
    # one verified skew split (post-split skew gauge under the threshold)
    # and the repeat query must plan run 2 from run 1's measured actuals,
    # with bit-parity everywhere.  skew_ratio / rerun_vs_first are the
    # report-only gate keys (aqe.* in BENCH_BASELINES.json)
    ares = bench_engine_aqe(n_fact=60_000, n_keys=500, smoke=True)
    askew = (ares or {}).get("skew") or {}
    awarm = (ares or {}).get("warm") or {}
    aok = bool(ares and askew.get("parity") and awarm.get("parity")
               and askew.get("splits_applied", 0) >= 1
               # gauge absent = metrics off in subprocess, nothing to check
               and (askew.get("gauge_skew") is None
                    or askew["gauge_skew"] < askew["threshold"])
               and awarm.get("warmed_entries", 0) >= 1
               and awarm.get("run2_broadcast_planned")
               and awarm.get("faster"))
    print(json.dumps({"metric": "aqe",
                      "ok": aok,
                      "skew_ratio": round(ares["skew_ratio"], 4)
                      if ares and ares.get("skew_ratio") else None,
                      "rerun_vs_first": round(ares["rerun_vs_first"], 4)
                      if ares and ares.get("rerun_vs_first") else None,
                      "latency_ms": {} if not ares else {
                          "balanced": round(ares["balanced_s"] * 1e3, 3),
                          "skewed": round(ares["skewed_s"] * 1e3, 3),
                          "first": round(ares["first_s"] * 1e3, 3),
                          "rerun": round(ares["rerun_s"] * 1e3, 3),
                      },
                      "skew": askew or None,
                      "warm": awarm or None}))
    # seventh line: multi-tenant serving — N concurrent bridge sessions
    # must return bit-exact per-trace results vs the serial pass, at least
    # one query must be shed with the typed admission error (trace +
    # bundle attached), and a repeat plan must serve from the result cache
    # well under its cold wall.  p99/throughput/shed_count are the
    # report-only serving.* gate keys (BENCH_BASELINES.json)
    sres = bench_engine_serving(n=24_000, clients=8, smoke=True)
    sshed = (sres or {}).get("shed") or {}
    sspeed = (sres or {}).get("result_cache_speedup")
    sok = bool(sres and sres.get("parity") and not sres.get("errors")
               and sres.get("admitted", 0) >= sres.get("clients", 8)
               and sshed.get("kind") == "resource"
               and sshed.get("retryable") is False
               and sshed.get("trace_id") and sshed.get("bundle")
               and sres.get("result_cache_hits", 0) >= 1
               and sspeed is not None and sspeed > 10.0)
    print(json.dumps({"metric": "serving",
                      "ok": sok,
                      "clients": (sres or {}).get("clients"),
                      "p50_ms": round(sres["p50_ms"], 3) if sres else None,
                      "p99_ms": round(sres["p99_ms"], 3) if sres else None,
                      "throughput": round(sres["throughput_qps"], 4)
                      if sres else None,
                      "throughput_ratio": round(sres["throughput_ratio"], 4)
                      if sres and sres.get("throughput_ratio") else None,
                      "shed_count": (sres or {}).get("shed_count"),
                      "result_cache_speedup": round(sspeed, 2)
                      if sspeed else None,
                      "latency_ms": {} if not sres else {
                          "serial_pass": round(sres["serial_s"] * 1e3, 3),
                          "concurrent_pass":
                              round(sres["concurrent_s"] * 1e3, 3),
                          "result_cache_cold":
                              round(sres["result_cache_cold_ms"], 3),
                          "result_cache_warm":
                              round(sres["result_cache_warm_ms"], 3),
                      },
                      "shed": sshed or None}))
    # roofline line: the fused row-conversion pipeline against the measured
    # stream ceiling at smoke scale — roofline_frac = achieved / ceiling is
    # dimensionless, so it tracks formulation regressions (extra passes,
    # lost fusion) without retuning for machine speed.  Report-only gate
    # key row_conversion.roofline_frac (BENCH_BASELINES.json); the r5
    # full-scale value was 0.071
    rc_dev, rc_cpu, rc_ok, rc_ceiling = bench_row_conversion(n=200_000)
    print(json.dumps({"metric": "row_conversion",
                      "ok": bool(rc_ok),
                      "GBps": round(rc_dev, 3),
                      "ceiling_GBps": round(rc_ceiling, 2),
                      "roofline_frac": round(rc_dev / rc_ceiling, 4)
                      if rc_ceiling else None,
                      "cpu_GBps": round(rc_cpu, 3)}))
    # profile-store line: every query above (this process AND the dist +
    # aqe subprocesses, via the inherited env) persisted a profile; the
    # store summary must carry the dist exchanges' skew
    from spark_rapids_jni_tpu.utils import profile
    psumm = profile.store_summary()
    pok = (not profile.enabled()) or (
        psumm["profiles"] > 0 and psumm["top_exchange_skew"] is not None)
    print(json.dumps({"metric": "profile_store",
                      "ok": pok,
                      "enabled": profile.enabled(),
                      **psumm}))
    # overhead line: the observability layer's own price — the same tiny
    # aggregate timed under SRJT_METRICS=0 and =1.  The on/off ratio is
    # gated report-only (machine noise dwarfs the per-chunk dict writes
    # at smoke scale); the line exists so a pathological regression in
    # the metrics hot path shows up in the bench artifact immediately.
    import tempfile
    import time as _time
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    from spark_rapids_jni_tpu.engine import (Aggregate, Scan, execute,
                                             new_stats, optimize)
    from spark_rapids_jni_tpu.utils.config import refresh as _refresh
    ov_dir = tempfile.mkdtemp(prefix="srjt-ov-")
    ov_path = os.path.join(ov_dir, "ov.parquet")
    rng = np.random.default_rng(3)
    pq.write_table(pa.table({
        "k": pa.array(rng.integers(0, 50, 20_000).astype(np.int64)),
        "v": pa.array(rng.uniform(0.0, 1.0, 20_000)),
    }), ov_path, row_group_size=2_000)
    ov_plan = Aggregate(Scan(ov_path, chunk_bytes=32_000), ["k"],
                        [("v", "sum")], names=["s"])
    ov_opt = optimize(ov_plan)
    prev_flag = os.environ.get("SRJT_METRICS")
    ov_ms = {}
    try:
        for flag in ("0", "1"):
            os.environ["SRJT_METRICS"] = flag
            _refresh()
            execute(ov_opt, new_stats())  # warm (compile)
            t0 = _time.perf_counter()
            for _ in range(3):
                with metrics.query("overhead"):
                    execute(ov_opt, new_stats())
            ov_ms[flag] = (_time.perf_counter() - t0) * 1e3 / 3
    finally:
        if prev_flag is None:
            os.environ.pop("SRJT_METRICS", None)
        else:
            os.environ["SRJT_METRICS"] = prev_flag
        _refresh()
    ov_ratio = (ov_ms["1"] / ov_ms["0"]) if ov_ms.get("0") else None
    vok = bool(ov_ratio and ov_ratio > 0)
    print(json.dumps({"metric": "metrics_overhead",
                      "ok": vok,
                      "latency_ms": {
                          "metrics_off": round(ov_ms.get("0", 0.0), 3),
                          "metrics_on": round(ov_ms.get("1", 0.0), 3),
                      },
                      "ratios": {"on_vs_off": round(ov_ratio, 4)
                                 if ov_ratio else None}}))
    # flight-recorder overhead line: the always-on blackbox ring's price —
    # the same aggregate timed under SRJT_BLACKBOX=0 and =1 (happy path:
    # ring appends only, no bundle is ever cut).  Report-only like
    # metrics_overhead; the line exists so a regression in the record()
    # fast path (utils/blackbox.py) shows up in the bench artifact.
    prev_bb = os.environ.get("SRJT_BLACKBOX")
    bb_ms = {}
    try:
        for flag in ("0", "1"):
            os.environ["SRJT_BLACKBOX"] = flag
            _refresh()
            execute(ov_opt, new_stats())  # warm (compile)
            t0 = _time.perf_counter()
            for _ in range(3):
                with metrics.query("bb_overhead"):
                    execute(ov_opt, new_stats())
            bb_ms[flag] = (_time.perf_counter() - t0) * 1e3 / 3
    finally:
        if prev_bb is None:
            os.environ.pop("SRJT_BLACKBOX", None)
        else:
            os.environ["SRJT_BLACKBOX"] = prev_bb
        _refresh()
    bb_ratio = (bb_ms["1"] / bb_ms["0"]) if bb_ms.get("0") else None
    bok = bool(bb_ratio and bb_ratio > 0)
    print(json.dumps({"metric": "blackbox_overhead",
                      "ok": bok,
                      "latency_ms": {
                          "blackbox_off": round(bb_ms.get("0", 0.0), 3),
                          "blackbox_on": round(bb_ms.get("1", 0.0), 3),
                      },
                      "ratios": {"on_vs_off": round(bb_ratio, 4)
                                 if bb_ratio else None}}))
    return 0 if (ok and jok and mok and tok and dok and fok and pdok
                 and aok and sok and rc_ok and pok and vok and bok) else 1


def main():
    import spark_rapids_jni_tpu  # noqa: F401  (enables x64)
    from spark_rapids_jni_tpu.utils.config import enable_compile_cache
    enable_compile_cache()

    dev_gbps, cpu_gbps, ok, ceiling = bench_row_conversion()
    vs_dev, vs_cpu, vs_ok = bench_row_conversion_strings()
    cast_dev, cast_cpu = bench_cast_strings()
    agg_dev, agg_cpu = bench_hash_aggregate()
    scan_decode, scan_e2e, scan_staged, scan_arrow, link = \
        bench_parquet_scan()
    win_dev, win_cpu = bench_window()
    smj = bench_distributed_join()
    eng = bench_engine_q5()
    pipe = bench_engine_pipeline()
    ejoin = bench_engine_join()
    edist = bench_engine_dist()
    efused = bench_engine_fused_stage()
    eaqe = bench_engine_aqe()
    eserv = bench_engine_serving()

    # vs_baseline is measured/PINNED (BENCH_BASELINES.json), so the ratio is
    # comparable across rounds; the live re-measure of each baseline is
    # reported as *_measured_now for drift visibility only.
    print(json.dumps({
        "metric": "row_conversion_to_rows_GBps" + ("" if ok else "_MISMATCH"),
        "value": round(dev_gbps, 3),
        "unit": "GB/s",
        "vs_baseline": round(
            dev_gbps / pinned("row_conversion_to_rows_GBps"), 3),
        "pinned_baseline": pinned("row_conversion_to_rows_GBps"),
        "roofline_frac": round(dev_gbps / ceiling, 3),
        "extras": {
            "row_conversion_ceiling_GBps": {
                "value": round(ceiling, 2),
                "note": "measured HBM stream (same harness) scaled by the "
                        "op's minimum-traffic ratio 3R/(I+2R): an upper "
                        "bound no formulation can beat (it cannot move "
                        "fewer bytes)"},
            "cpu_numpy_pack_measured_now_GBps": {"value": round(cpu_gbps, 3)},
            "row_conversion_long_string_1M_GBps" + ("" if vs_ok
                                                 else "_MISMATCH"): {
                "value": round(vs_dev, 3),
                "pinned_baseline": pinned("row_conversion_long_string_1M_GBps"),
                "vs_baseline": round(
                    vs_dev / pinned("row_conversion_long_string_1M_GBps"), 2),
                "cpu_measured_now": round(vs_cpu, 3),
                "note": "BASELINE configs[0] at its specified long+string "
                        "shape (variable-width UnsafeRow-style rows)"},
            "cast_strings_to_int64_Mrows_s": {
                "value": round(cast_dev, 2),
                "pinned_baseline": pinned("cast_strings_to_int64_Mrows_s"),
                "vs_baseline": round(
                    cast_dev / pinned("cast_strings_to_int64_Mrows_s"), 2),
                "cpu_measured_now": round(cast_cpu, 2)},
            "hash_aggregate_Mrows_s": {
                "value": round(agg_dev, 2),
                "pinned_baseline": pinned("hash_aggregate_Mrows_s"),
                "vs_baseline": round(
                    agg_dev / pinned("hash_aggregate_Mrows_s"), 2),
                "cpu_measured_now": round(agg_cpu, 2)},
            "parquet_scan_decode_MBps": {
                "value": round(scan_decode, 1),
                "pinned_baseline": pinned("parquet_scan_decode_MBps"),
                "vs_baseline": round(
                    scan_decode / pinned("parquet_scan_decode_MBps"), 3),
                "pyarrow_measured_now": round(scan_arrow, 1)},
            "parquet_scan_to_device_MBps": {
                "value": round(scan_e2e, 1),
                "link_MBps_measured": round(link, 1),
                "frac_of_link": round(scan_e2e / link, 3) if link else None},
            "parquet_scan_to_device_staged_warm_MBps": {
                "value": round(scan_staged, 1),
                "frac_of_link": round(scan_staged / link, 3) if link
                else None,
                "note": "repeated-scan steady state: one packed transfer "
                        "+ cached jitted unpack (io/staging.py)"},
            "window_rank_sum_Mrows_s": {
                "value": round(win_dev, 2),
                "pinned_baseline": pinned("window_rank_sum_Mrows_s"),
                "vs_baseline": round(
                    win_dev / pinned("window_rank_sum_Mrows_s"), 2),
                "cpu_measured_now": round(win_cpu, 2)},
            **({"shuffle_smj_8dev_cpu_mesh_Mrows_s": {
                "value": round(smj["dist_mrows_s"], 2),
                "pinned_baseline": pinned(
                    "shuffle_smj_8dev_cpu_mesh_Mrows_s"),
                "vs_baseline": round(
                    smj["dist_mrows_s"] / pinned(
                        "shuffle_smj_8dev_cpu_mesh_Mrows_s"), 3),
                "local_measured_now": round(smj["local_mrows_s"], 3),
                "breakdown_s": {
                    "exchange": round(smj["exchange_s"], 3),
                    "join": round(smj["total_s"] - smj["exchange_s"], 3),
                    "total": round(smj["total_s"], 3)},
                "exchange_MB": round(smj["exchange_MB"], 1),
                "padding_efficiency": {
                    "value": round(smj["padding_efficiency"], 3),
                    "note": "live rows / padded exchange slots (sent "
                            "bytes over live bytes inverse)"}}}
               if smj else {}),
            **({"engine_q5_plan_execute": {
                "cold_ms": round(eng["cold_ms"], 1),
                "warm_ms": round(eng["warm_ms"], 1),
                "per_op_dispatch_ms": round(eng["per_op_ms"], 1),
                "round_trips": {"plan": eng["plan_round_trips"],
                                "per_op": eng["per_op_round_trips"]},
                "plan_cache": {"hits": eng["cache_hits"],
                               "misses": eng["cache_misses"]},
                "results_match": eng["results_match"],
                "note": "q5-lite via ONE PLAN_EXECUTE message (cold = "
                        "plan-cache miss: optimize+execute; warm = cache "
                        "hit) vs the same query as per-op bridge calls; "
                        "no pinned baseline yet (first round with the "
                        "engine in the tree)"}}
               if eng else {}),
            **({"engine_pipeline": {
                "q5_cold_fused_ms": round(pipe["q5_cold_fused_ms"], 1),
                "q5_warm_fused_ms": round(pipe["q5_warm_fused_ms"], 1),
                "q5_warm_interp_ms": round(pipe["q5_warm_interp_ms"], 1),
                "fused_vs_interp": round(pipe["fused_vs_interp"], 3),
                "stream_serial_ms": round(pipe["stream_serial_ms"], 1),
                "stream_overlap_ms": round(pipe["stream_overlap_ms"], 1),
                "overlap_vs_serial": round(pipe["overlap_vs_serial"], 3),
                "fused_stream_serial_ms": round(
                    pipe["fused_stream_serial_ms"], 1),
                "fused_stream_overlap_ms": round(
                    pipe["fused_stream_overlap_ms"], 1),
                "fused_overlap_vs_serial": round(
                    pipe["fused_overlap_vs_serial"], 3),
                "chunks": pipe["chunks"],
                "results_match": pipe["results_match"],
                "segment_cache": pipe["segment_cache"],
                "note": "LOCAL executor. fused_vs_interp: warm fused "
                        "segments vs the PR 1 node-by-node interpreter on "
                        "the q5-lite shape (>1 means fused wins). "
                        "overlap_vs_serial: double-buffered (prefetch=2) "
                        "vs serial (prefetch=0) chunk streaming on the "
                        "chunked-scan aggregate's per-chunk-sync loop, "
                        "min of interleaved A/B pairs (>1 means overlap "
                        "wins); fused_* is the same A/B on the fused "
                        "streaming loop, whose consumer never blocks "
                        "per chunk — on a 1-core CPU host there is no "
                        "idle wait for the producer to hide behind, so "
                        "~1.0 is expected there until a real accelerator "
                        "link is in the loop"}}
               if pipe else {}),
            **({"engine_join": {
                "join_cached_build_ms": round(
                    ejoin["join_cached_build_ms"], 1),
                "join_per_chunk_build_ms": round(
                    ejoin["join_per_chunk_build_ms"], 1),
                "cached_vs_per_chunk": round(
                    ejoin["cached_vs_per_chunk"], 3),
                "topk_stream_ms": round(ejoin["topk_stream_ms"], 1),
                "topk_full_sort_ms": round(ejoin["topk_full_sort_ms"], 1),
                "topk_vs_full_sort": round(ejoin["topk_vs_full_sort"], 3),
                "chunks": ejoin["chunks"],
                "build_cache_counters_ok":
                    ejoin["build_cache_counters_ok"],
                "results_match": ejoin["results_match"],
                "build_cache": ejoin["build_cache"],
                "note": "LOCAL executor. cached_vs_per_chunk: streamed "
                        "inner join with the build side prepared once "
                        "(BUILD_CACHE, fused probe per chunk) vs the "
                        "interpreted loop re-hashing + re-sorting the "
                        "build every chunk (>1 means cached wins). "
                        "topk_vs_full_sort: streamed capacity-k TopK vs "
                        "materialize + full sort + slice on the same "
                        "optimized plan (>1 means streaming wins)"}}
               if ejoin else {}),
            **({"engine_dist": {
                "broadcast_s": round(edist["broadcast_s"], 3),
                "exchange_s": round(edist["exchange_s"], 3),
                "smj8_s": round(edist["smj_s"], 3),
                "broadcast_join_stage_s": round(edist["bjoin_s"], 3),
                "local_s": round(edist["local_s"], 3),
                "broadcast_vs_smj8": round(
                    edist["ratios"]["broadcast_vs_smj8"], 3),
                "broadcast_vs_exchange": round(
                    edist["ratios"]["broadcast_vs_exchange"], 3),
                "exchanges": edist["exchanges"],
                "results_match": edist["results_match"],
                "note": "partitioning-aware planner on the 8-device CPU "
                        "mesh: the same join+agg plan as a broadcast-hash "
                        "join (build replicated, probe streamed through "
                        "the fused segment) vs forced hash exchanges vs "
                        "the r5 shuffle+SMJ comparator (join stage only); "
                        "co-partitioned scans must plan zero exchanges"}}
               if edist else {}),
            **({"engine_fused_stage": {
                "host_exchange_s": round(efused["host_s"], 3),
                "fused_s": round(efused["fused_s"], 3),
                "scan_baseline_s": round(efused["scan_s"], 3),
                "vs_host_exchange": round(
                    efused["vs_host_exchange"], 3)
                if efused["vs_host_exchange"] else None,
                "vs_host_e2e": round(efused["vs_host_e2e"], 3)
                if efused["vs_host_e2e"] else None,
                "host_syncs": efused["host_syncs"],
                "dispatches": efused["dispatches"],
                "exchanges": efused["exchanges"],
                "results_match": efused["results_match"],
                "note": "SRJT_FUSE_EXCHANGE: the partial-agg -> hash "
                        "Exchange -> final-agg sandwich lowered into ONE "
                        "jit(shard_map) program (device-side murmur3 "
                        "placement, bucket scatter, all_to_all, combine) "
                        "vs the host-orchestrated exchange on the same "
                        "plan.  The fused run pays exactly its static "
                        "verify.sync_budget (one boundary sync), the "
                        "host path pays per-device gathers + a host "
                        "bucket sort + re-uploads; parity is bit-exact.  "
                        "vs_host_exchange isolates the exchange stage by "
                        "subtracting the separately-timed scan-only "
                        "baseline both paths share; vs_host_e2e is the "
                        "raw end-to-end wall ratio"}}
               if efused else {}),
            **({"engine_aqe": {
                "balanced_s": round(eaqe["balanced_s"], 3),
                "skewed_s": round(eaqe["skewed_s"], 3),
                "skew_ratio": round(eaqe["skew_ratio"], 3)
                if eaqe["skew_ratio"] else None,
                "first_s": round(eaqe["first_s"], 3),
                "rerun_s": round(eaqe["rerun_s"], 3),
                "rerun_vs_first": round(eaqe["rerun_vs_first"], 3)
                if eaqe["rerun_vs_first"] else None,
                "skew": eaqe["skew"],
                "warm": eaqe["warm"],
                "note": "SRJT_AQE=1 runtime rewrites on the 8-device CPU "
                        "mesh: skew_ratio is the skewed twin vs its "
                        "balanced twin (hot keys split + re-dealt at the "
                        "exchange, ~1.0 means the split erased the hot "
                        "device); rerun_vs_first is run 2 of the same "
                        "source fingerprint planned from run 1's measured "
                        "build actuals (profile history) vs the cold run "
                        "(<1.0 means warming won)"}}
               if eaqe else {}),
            **({"engine_serving": {
                "clients": eserv["clients"],
                "p50_ms": round(eserv["p50_ms"], 1),
                "p99_ms": round(eserv["p99_ms"], 1),
                "throughput_qps": round(eserv["throughput_qps"], 3),
                "throughput_ratio": round(eserv["throughput_ratio"], 3)
                if eserv["throughput_ratio"] else None,
                "serial_s": round(eserv["serial_s"], 3),
                "concurrent_s": round(eserv["concurrent_s"], 3),
                "parity": eserv["parity"],
                "admitted": eserv["admitted"],
                "shed_count": eserv["shed_count"],
                "result_cache_speedup": round(
                    eserv["result_cache_speedup"], 1)
                if eserv["result_cache_speedup"] else None,
                "note": "N concurrent bridge sessions (one PLAN_EXECUTE "
                        "each, distinct fingerprints) vs the same N "
                        "queries serial on one connection, warm jit "
                        "caches, result cache off — parity is bit-exact "
                        "per-trace results.  shed_count / "
                        "result_cache_speedup come from a second 1-slot "
                        "server with SRJT_SLO_MS=1: a burning fingerprint "
                        "is shed at admission with the typed error, and "
                        "a repeat plan over unchanged files serves from "
                        "the result-set cache (engine/scheduler.py, "
                        "docs/SERVING.md).  throughput_ratio ~1.0 (or "
                        "below) is expected on a CPU-only host: XLA's "
                        "intra-op threadpool already spends every core "
                        "on one query, so concurrency has no idle "
                        "device time to reclaim until a real "
                        "accelerator link is in the loop"}}
               if eserv else {}),
            "metrics_snapshot": _metrics_snapshot(),
        },
    }))


def _metrics_snapshot() -> dict:
    """The SRJT_METRICS layer's view of everything the bench just ran:
    flat counters, histograms/gauges, and the most recent per-query
    summaries (bounded — the full deque holds 32)."""
    from spark_rapids_jni_tpu.utils import metrics
    snap = metrics.snapshot()
    snap["enabled"] = metrics.enabled()
    snap["queries"] = metrics.recent_summaries(limit=8)
    return snap


if __name__ == "__main__":
    if "--smoke" in sys.argv[1:]:
        sys.exit(smoke())
    sys.exit(main())
