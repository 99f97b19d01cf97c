#!/usr/bin/env python
"""Chaos soak: the fault-injection matrix against the real pipeline.

The robustness acceptance test (docs/ROBUSTNESS.md): build the bench
warehouse, compute oracle results with no faults armed, then re-run the
same plans under a rotating ``SRJT_FAULTS`` schedule covering every
injection site x kind.  Each run must end one of exactly two ways:

- **parity** — the recovery layer absorbed the fault (retry, interpreted
  fallback, exchange degradation ladder) and the result matches the
  oracle bit-for-bit after key-sorting; or
- **typed error** — a classified, non-fatal ``utils.errors`` kind
  (transient / resource / cancelled) surfaced within the deadline.

Anything else fails the soak: a fatal/unclassified error, a hang (the
whole script runs under ``timeout`` in ci/nightly.sh), a result mismatch,
a leaked prefetch thread (``io.prefetch.reap_timeouts`` must stay 0), or
an orphaned spill file.

The flight recorder (utils/blackbox.py) is held to the same oracle: a
typed error must cut EXACTLY one post-mortem bundle whose trace_id
matches the one the raised exception carries (``e.trace_id``), a parity
run cuts at most one (the degradation ladder bundles too), the clean
oracle runs cut none, and the bundle directory stays bounded.

A concurrent-clients scenario repeats the contract under multi-tenant
contention: four bridge clients run distinct plans at once against a
subprocess server with faults armed in ITS env — an absorbed fault must
leave every client's result bit-exact (zero cross-session leakage), and
an unabsorbable fault must hand every client a typed error joined 1:1
to a fresh server-side bundle by trace id.

Run directly::

    JAX_PLATFORMS=cpu python ci/chaos_soak.py
    python ci/chaos_soak.py --rounds 2 --devices 2   # more soak, exchange on
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# the soak schedule: every site, both deterministic-nth and every-time
# rules, all three kinds.  timeout-kind sleeps are tiny (faults.HANG_S)
# so the soak stays fast; the point is that deadline plumbing engages.
SCHEDULE = [
    "parquet.chunk:1:io_error",
    "parquet.chunk:*:io_error",
    "parquet.chunk:2:oom",
    "parquet.prefetch:1:io_error",
    "parquet.prefetch:*:io_error",
    "staging.transfer:1:oom",
    "staging.transfer:2:io_error",
    "exchange.dispatch:1:oom",
    "exchange.dispatch:*:oom",
    "spill.write:1:io_error",
    "bridge.op:1:io_error",
    "parquet.chunk:1:timeout",
    "parquet.chunk:3:io_error,staging.transfer:1:oom",
]


def _sorted_columns(table, key):
    import numpy as np
    a = np.asarray(table.column(key).data)
    order = np.argsort(a, kind="stable")
    return [np.asarray(c.data)[order] for c in table.columns]


def _parity(base, out, key) -> bool:
    import numpy as np
    if base.num_rows != out.num_rows or base.num_columns != out.num_columns:
        return False
    for x, y in zip(_sorted_columns(base, key), _sorted_columns(out, key)):
        if not np.allclose(np.asarray(x, np.float64),
                           np.asarray(y, np.float64)):
            return False
    return True


def _parity_by_index(base, out, idx=0) -> bool:
    """Like ``_parity`` but key-sorts by column INDEX: tables exported
    over the bridge carry data only, no column names."""
    import numpy as np
    if base.num_rows != out.num_rows or base.num_columns != out.num_columns:
        return False

    def cols(t):
        order = np.argsort(np.asarray(t.columns[idx].data), kind="stable")
        return [np.asarray(c.data)[order] for c in t.columns]
    for x, y in zip(cols(base), cols(out)):
        if not np.allclose(np.asarray(x, np.float64),
                           np.asarray(y, np.float64)):
            return False
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=1,
                    help="full passes over the fault schedule")
    ap.add_argument("--rows", type=int, default=120_000)
    ap.add_argument("--devices", type=int, default=0,
                    help="virtual CPU device count (0 = leave as-is)")
    args = ap.parse_args(argv)

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.pop("SRJT_FAULTS", None)
    # chunk-boundary deadline: generous enough for cold jit compiles, small
    # enough that a real hang converts to a typed timeout well before the
    # nightly `timeout` wrapper SIGKILLs the soak
    os.environ["SRJT_QUERY_TIMEOUT_S"] = "120"
    os.environ["SRJT_RETRY_BACKOFF_S"] = "0.001"
    # post-mortem bundles: every typed error below must cut exactly one,
    # joined to the run by trace id (docs/OBSERVABILITY.md)
    bb_dir = tempfile.mkdtemp(prefix="srjt-chaos-bb-")
    os.environ["SRJT_BLACKBOX_DIR"] = bb_dir

    import numpy as np

    from spark_rapids_jni_tpu.engine import execute, optimize
    from spark_rapids_jni_tpu.engine.fuzz import (pipeline_plans,
                                                  pipeline_warehouse,
                                                  serving_plans)
    from spark_rapids_jni_tpu.utils import blackbox, errors, faults, tracing
    from spark_rapids_jni_tpu.utils.config import refresh

    refresh()
    rng = np.random.default_rng(7)
    root = tempfile.mkdtemp(prefix="srjt-chaos-")
    pipeline_warehouse(root, args.rows, rng)
    q5, chunked = pipeline_plans(root, chunk_bytes=256_000)
    plans = [("q5", optimize(q5), "s_mgr"),
             ("chunked", optimize(chunked), "ss_store_sk")]

    oracle = {name: execute(opt) for name, opt, _ in plans}
    thread_floor = threading.active_count()

    failures: list[str] = []
    # fault-free runs must not post-mortem anything
    if os.listdir(bb_dir):
        failures.append(
            f"clean oracle runs cut bundle(s): {os.listdir(bb_dir)}")
    runs = outcomes_parity = outcomes_typed = 0
    t_start = time.monotonic()
    for rnd in range(args.rounds):
        for spec in SCHEDULE:
            os.environ["SRJT_FAULTS"] = spec
            refresh()
            for name, opt, key in plans:
                faults.reset()
                runs += 1
                tag = f"round{rnd} [{spec}] {name}"
                before = set(os.listdir(bb_dir))
                try:
                    out = execute(opt)
                except Exception as e:  # noqa: BLE001 — the soak classifies
                    kind, _ = errors.classify(e)
                    fresh = sorted(set(os.listdir(bb_dir)) - before)
                    if kind == errors.KIND_FATAL:
                        failures.append(
                            f"{tag}: FATAL {type(e).__name__}: {e}")
                    else:
                        outcomes_typed += 1
                        tid = getattr(e, "trace_id", "")
                        if len(fresh) != 1:
                            failures.append(
                                f"{tag}: typed error cut {len(fresh)} "
                                f"bundle(s), want exactly 1: {fresh}")
                        else:
                            doc = blackbox.read_bundle(
                                os.path.join(bb_dir, fresh[0]))
                            if not tid or doc.get("trace_id") != tid:
                                failures.append(
                                    f"{tag}: bundle trace "
                                    f"{doc.get('trace_id')!r} != "
                                    f"client-observed {tid!r}")
                        print(f"  {tag}: typed error "
                              f"({kind}) {type(e).__name__} "
                              f"trace={tid[:12] or '?'}")
                    continue
                fresh = sorted(set(os.listdir(bb_dir)) - before)
                if len(fresh) > 1:  # 0 ok; 1 = degradation post-mortem
                    failures.append(
                        f"{tag}: parity run cut {len(fresh)} bundles: "
                        f"{fresh}")
                if _parity(oracle[name], out, key):
                    outcomes_parity += 1
                else:
                    failures.append(f"{tag}: result diverged from oracle")
    os.environ.pop("SRJT_FAULTS", None)
    refresh()
    faults.reset()

    # spill path under injection, with a real spill_dir: the sweep plus
    # finalizers must leave the directory empty
    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.parallel.mesh import make_mesh
    from spark_rapids_jni_tpu.parallel.spill import shuffle_table_spilled
    sd = tempfile.mkdtemp(prefix="srjt-chaos-spill-")
    st = Table([Column.from_numpy(
                    rng.integers(0, 64, 50_000).astype("int64")),
                Column.from_numpy(
                    rng.integers(-99, 99, 50_000).astype("int64"))],
               ["k", "v"])
    os.environ["SRJT_FAULTS"] = "spill.write:1:io_error"
    refresh()
    faults.reset()
    spilled = shuffle_table_spilled(st, make_mesh(), ["k"],
                                    hbm_budget_bytes=1 << 18, spill_dir=sd)
    if spilled.num_rows != st.num_rows:
        failures.append("spill: row count diverged under injection")
    del spilled  # finalizers unlink the memmaps
    import gc
    gc.collect()
    left = [n for n in os.listdir(sd) if n.startswith("spill-")]
    if left:
        failures.append(f"spill: {len(left)} file(s) left in {sd}: {left}")
    os.environ.pop("SRJT_FAULTS", None)
    refresh()

    # device-decode path under injection (SRJT_DEVICE_DECODE=1): the
    # chunked plan with the parquet.device_decode transfer seam faulted.
    # A one-shot transient is absorbed by the retry ladder; a persistent
    # fault and an OOM must re-plan the chunk onto the host decoder —
    # every case ends in bit-exact parity with the fault-free oracle,
    # never a FATAL, and the device path must prove it actually engaged
    # (counter delta > 0) so the scenario can't silently soak nothing
    from spark_rapids_jni_tpu.utils import metrics as _metrics
    os.environ["SRJT_DEVICE_DECODE"] = "1"
    dd0 = _metrics.snapshot()["counters"].get("io.device_decode.chunks", 0)
    for spec in ("parquet.device_decode:1:io_error",
                 "parquet.device_decode:*:io_error",
                 "parquet.device_decode:1:oom"):
        os.environ["SRJT_FAULTS"] = spec
        refresh()
        faults.reset()
        runs += 1
        tag = f"device-decode [{spec}]"
        try:
            out = execute(plans[1][1])
        except Exception as e:  # noqa: BLE001 — the soak classifies
            kind, _ = errors.classify(e)
            if kind == errors.KIND_FATAL:
                failures.append(f"{tag}: FATAL {type(e).__name__}: {e}")
            else:
                outcomes_typed += 1
                print(f"  {tag}: typed error ({kind}) {type(e).__name__}")
        else:
            if _parity(oracle["chunked"], out, "ss_store_sk"):
                outcomes_parity += 1
                print(f"  {tag}: parity under injection")
            else:
                failures.append(f"{tag}: result diverged from oracle")
    dd1 = _metrics.snapshot()["counters"].get("io.device_decode.chunks", 0)
    if _metrics.enabled() and dd1 <= dd0:
        failures.append("device-decode: scenario never engaged the device "
                        "path (io.device_decode.chunks did not move)")
    os.environ.pop("SRJT_DEVICE_DECODE", None)
    os.environ.pop("SRJT_FAULTS", None)
    refresh()
    faults.reset()

    # concurrent-clients scenario: the fault matrix under multi-tenant
    # contention (engine/scheduler.py).  Four bridge clients run four
    # distinct-fingerprint plans at once against a real subprocess server
    # with faults armed in the SERVER env.  Two sub-scenarios:
    #  - an nth-shot fault the recovery layer absorbs: every client must
    #    still get ITS OWN plan's result bit-exact (zero cross-session
    #    leakage — a retried chunk must never land in a neighbor's
    #    accumulator);
    #  - an every-time fault no ladder can absorb: every client must get
    #    a typed, classified error carrying its own trace id, and the
    #    server must cut EXACTLY one trace-joined bundle per typed error.
    from spark_rapids_jni_tpu.bridge import BridgeClient, spawn_server
    n_clients = 4
    conc_plans = serving_plans(root, 64_000, n_clients)
    conc_oracle = [execute(optimize(p)) for p in conc_plans]

    def _concurrent_pass(tag, fault_spec, bb):
        sock = os.path.join(tempfile.mkdtemp(prefix="srjt-chaos-srv-"),
                            "srv.sock")
        proc = spawn_server(sock, env={
            "SRJT_FAULTS": fault_spec,
            "SRJT_BLACKBOX_DIR": bb,
            "SRJT_RETRY_BACKOFF_S": "0.001",
            "SRJT_QUERY_TIMEOUT_S": "120",
        })
        results: dict = {}
        errs: dict = {}
        barrier = threading.Barrier(n_clients)

        def one(i):
            try:
                c = BridgeClient(sock)
                barrier.wait()
                hs = c.execute_plan(conc_plans[i])
                results[i] = c.export_table(hs[0])
                for h in hs:
                    c.release(h)
                c.close()
            except Exception as e:  # noqa: BLE001 — classified below
                errs[i] = e
        ts = [threading.Thread(target=one, args=(i,))
              for i in range(n_clients)]
        try:
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=300)
            ctl = BridgeClient(sock)
            ctl.shutdown_server()
        except Exception as e:  # noqa: BLE001 — the soak classifies
            failures.append(f"{tag}: harness error {e!r}")
            proc.kill()
        finally:
            proc.wait(timeout=30)
        return results, errs

    bb_absorb = tempfile.mkdtemp(prefix="srjt-chaos-bb-conc1-")
    res, errs = _concurrent_pass("concurrent/absorbed",
                                 "parquet.chunk:2:io_error", bb_absorb)
    runs += n_clients
    for i in range(n_clients):
        if i in errs:
            failures.append(f"concurrent/absorbed: client {i} errored "
                            f"({errs[i]!r}), want recovery parity")
        elif not _parity_by_index(conc_oracle[i], res[i]):
            failures.append(f"concurrent/absorbed: client {i} result "
                            "diverged from its oracle (cross-session "
                            "leakage or lost chunk)")
        else:
            outcomes_parity += 1
    print(f"  concurrent/absorbed: {len(res)}/{n_clients} parity under "
          f"nth-shot fault, {len(errs)} error(s)")

    bb_hard = tempfile.mkdtemp(prefix="srjt-chaos-bb-conc2-")
    res, errs = _concurrent_pass("concurrent/typed",
                                 "parquet.chunk:*:io_error", bb_hard)
    runs += n_clients
    bundles = {blackbox.read_bundle(os.path.join(bb_hard, f))
               .get("trace_id"): f for f in blackbox.list_bundles(bb_hard)}
    for i in range(n_clients):
        e = errs.get(i)
        if e is None:
            failures.append("concurrent/typed: client "
                            f"{i} succeeded under an every-time fault")
            continue
        kind, _ = errors.classify(e)
        if kind == errors.KIND_FATAL:
            failures.append(f"concurrent/typed: client {i} got FATAL "
                            f"{type(e).__name__}: {e}")
            continue
        outcomes_typed += 1
        tid = getattr(e, "trace_id", "")
        if not tid or tid not in bundles:
            failures.append(f"concurrent/typed: client {i} trace "
                            f"{tid!r} has no joined bundle "
                            f"(bundles: {sorted(bundles)})")
    if len(blackbox.list_bundles(bb_hard)) != len(errs):
        failures.append(
            f"concurrent/typed: {len(blackbox.list_bundles(bb_hard))} "
            f"bundle(s) for {len(errs)} typed error(s), want 1:1")
    print(f"  concurrent/typed: {len(errs)}/{n_clients} typed errors, "
          f"{len(bundles)} trace-joined bundle(s)")

    # leak checks: every prefetch producer must have been reaped inside
    # its join window, and no soak run may leave a live worker behind
    reaps = tracing.counters_snapshot("io.prefetch.reap_timeouts")
    if any(reaps.values()):
        failures.append(f"prefetch reap timeouts: {reaps}")
    time.sleep(0.2)  # producers parked on a full queue exit on drain/close
    leaked = threading.active_count() - thread_floor
    if leaked > 0:
        names = [t.name for t in threading.enumerate()]
        failures.append(f"{leaked} leaked thread(s): {names}")

    # bundle-dir bound: the writer prunes to its on-disk ring size
    n_bundles = len(blackbox.list_bundles(bb_dir))
    if n_bundles > blackbox._DIR_KEEP:
        failures.append(f"bundle dir unbounded: {n_bundles} files "
                        f"(cap {blackbox._DIR_KEEP})")

    wall = time.monotonic() - t_start
    print(f"chaos soak: {runs} runs in {wall:.1f}s — "
          f"{outcomes_parity} parity, {outcomes_typed} typed errors, "
          f"{n_bundles} bundle(s), {len(failures)} failure(s)")
    counters = tracing.counters_snapshot("engine.")
    for k in sorted(counters):
        if k.startswith(("engine.retries", "engine.degraded",
                         "engine.errors")):
            print(f"  {k} = {counters[k]}")
    for f in failures:
        print(f"  FAIL: {f}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
