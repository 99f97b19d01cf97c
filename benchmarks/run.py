#!/usr/bin/env python3
"""One run of one benchmark cell, through the served path.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process is a pure bridge client (numpy, pandas, pyarrow, sockets): it
initialises no jax backend.  ONE server child (`server_child.py`) holds the
chip.  The run writes the cell's warehouse from ``--seed``, starts the
child, warms the cell's own plan until an execution compiles nothing,
drives ``execute_plan`` + ``export_host`` in a closed loop for
``--seconds``, shuts the child down, compares EVERY result the window
produced with the plain pandas reference, and prints one JSON line.

A run that finds no TPU (or another device count than the cell asks for)
fails and prints no result: on the CPU it rehearses every request and
comparison at the configuration's cut row count, says so, and exits 1.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name `BENCHMARK.json`
gives (see README.md); nothing here names a cell.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()        # set-up is counted from process start

import argparse                 # noqa: E402
import importlib.util           # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import re                       # noqa: E402
import shutil                   # noqa: E402
import subprocess               # noqa: E402
import sys                      # noqa: E402
import tempfile                 # noqa: E402
import threading                # noqa: E402
import traceback                # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)

import compare                  # noqa: E402  (benchmarks/compare.py)

TRACE_SECONDS = 3.0             # the traced stretch, at the window's end
MAX_WARMUPS = 6                 # executions allowed until one compiles nothing
SERVER_UP_TIMEOUT_S = 300
# counters whose growth means a program was compiled or a cache was missed
COMPILE_COUNTERS = ("engine.segment.compile", "engine.segment_cache.miss",
                    "engine.fused_stage_cache.miss", "engine.build_cache.miss",
                    "engine.plan_cache.miss")


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T0:7.2f}s] {msg}", file=sys.stderr,
          flush=True)


class RunFailure(Exception):
    """The run cannot give a result; the message says why."""


# -- the cell, from data files -------------------------------------------------

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """`benchmarks/<kind>/<name>.py`, imported under its own name."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise RunFailure(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of `BENCHMARK.json`'s workloads with its files."""

    def __init__(self, name: str, bench: dict | None = None):
        self.bench = bench or load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise RunFailure(f"unknown workload {name!r}; BENCHMARK.json has "
                             f"{sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        cfg_entry = {c["name"]: c for c in self.bench["configs"]}[
            self.entry["config"]]
        self.config = load_json(os.path.join(ROOT, cfg_entry["file"]))
        self.traffic = load_json(os.path.join(
            HERE, "traffic", self.entry["traffic"] + ".json"))
        self.query = load_module("queries", self.config["query"])
        self.chips = int(self.entry["chips"])

    def metrics(self, group: str) -> list:
        """The metrics of `end_to_end` or `per_layer` this cell reports."""
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def rows(self, rehearsal: bool) -> dict:
        rows = {t: spec["rows"] for t, spec in self.config["tables"].items()}
        if rehearsal:
            rows.update(self.config["rehearsal_rows"])
        return rows


def write_tables(frames: dict, config: dict, root: str) -> dict:
    import pyarrow as pa
    import pyarrow.parquet as pq
    paths = {}
    for name, df in frames.items():
        groups = config["tables"][name]["row_groups"]
        paths[name] = os.path.join(root, f"{name}.parquet")
        pq.write_table(pa.Table.from_pandas(df, preserve_index=False),
                       paths[name],
                       compression=config["storage"]["compression"],
                       row_group_size=-(-len(df) // groups))
    return paths


# -- the server child ------------------------------------------------------------

def short_socket_path(run_dir: str) -> tuple:
    """(path the child binds, path this process connects to, cwd for the
    child).  AF_UNIX paths hold 107 bytes; a long TMPDIR is reached through
    the child's cwd on one side and an open directory on the other."""
    path = os.path.join(run_dir, "tpub.sock")
    if len(path.encode()) < 100:
        return path, path, None
    fd = os.open(run_dir, os.O_RDONLY | os.O_DIRECTORY)  # kept for the run
    return "tpub.sock", f"/proc/self/fd/{fd}/tpub.sock", run_dir


def child_env(config: dict, run_dir: str, trace: bool) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    # every program goes into the persistent cache, not only those that
    # took jax's default second to compile: a cell's second run compiles
    # nothing.  The directory is `enable_compile_cache()`'s: the one
    # JAX_COMPILATION_CACHE_DIR names, else <checkout>/.jax_cache.
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    env["SRJT_BLACKBOX_DIR"] = os.path.join(run_dir, "blackbox")
    env.update(config.get("server_env", {}))
    if trace:
        # the program's own switch: op_scope then writes its host spans
        # into the profiler's trace, on the device's clock
        env["SRJT_TRACE"] = "1"
    return env


def start_child(cell: Cell, run_dir: str, trace: bool,
                launcher: str, launcher_args: tuple = ()) -> tuple:
    bind_path, connect_path, cwd = short_socket_path(run_dir)
    cmd = [sys.executable, launcher, *launcher_args, "--socket", bind_path]
    if trace:
        os.makedirs(os.path.join(run_dir, "trace"))
        cmd += ["--trace-dir", os.path.join(run_dir, "trace")]
    proc = subprocess.Popen(cmd, env=child_env(cell.config, run_dir, trace),
                            cwd=cwd, stdout=sys.stderr)
    return proc, connect_path


def wait_until_up(proc, sock: str):
    from spark_rapids_jni_tpu.bridge import BridgeClient
    deadline = time.monotonic() + SERVER_UP_TIMEOUT_S
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RunFailure(f"server child died (rc={proc.returncode})")
        if os.path.exists(sock):
            try:
                c = BridgeClient(sock, timeout=900)
                c.ping()
                return c
            except (ConnectionError, OSError):
                pass
        time.sleep(0.02)
    raise RunFailure("server child did not come up")


def server_traceback(run_dir: str) -> str:
    """The newest post-mortem bundle's traceback (utils/blackbox.py)."""
    bundles = os.path.join(run_dir, "blackbox")
    names = sorted(os.listdir(bundles)) if os.path.isdir(bundles) else []
    if not names:
        return "(no post-mortem bundle)"
    with open(os.path.join(bundles, names[-1])) as f:
        return json.load(f).get("error", {}).get("traceback", "(none)")


# -- requests ------------------------------------------------------------------

def one_query(client, plan_blob: bytes) -> tuple:
    """The timed entry: PLAN_EXECUTE then the export of its result.
    Returns (seconds, columns); the handle is released untimed."""
    t = time.perf_counter()
    (handle,) = client.execute_plan(plan_blob)
    cols = client.export_host(handle)
    dt = time.perf_counter() - t
    client.release(handle)
    return dt, cols


def compile_count(snapshot: dict) -> int:
    return sum(int(snapshot["counters"].get(k, 0)) for k in COMPILE_COUNTERS)


def warm_up(client, plan_blob: bytes) -> dict:
    """The cell's plan on this seed's data until an execution compiles
    nothing and misses no cache.  The first is `first_query_s`."""
    out = {"executions": 0}
    before = compile_count(client.metrics())
    while out["executions"] < MAX_WARMUPS:
        dt, _ = one_query(client, plan_blob)
        out["executions"] += 1
        out.setdefault("first_query_s", dt)
        out["last_query_s"] = dt
        now = compile_count(client.metrics())
        log(f"warm-up {out['executions']}: {dt:.3f} s, compiles+misses "
            f"{before} -> {now}")
        if now == before and out["executions"] >= 2:
            return out
        before = now
    raise RunFailure(f"{MAX_WARMUPS} warm-up executions and the last still "
                     "compiled or missed a cache")


class ClosedLoop:
    """``clients`` callers, each sending its next request when the reply to
    the last has come, until the window closes.  A request in flight at the
    close completes and counts; the window then ends with it."""

    def __init__(self, sock: str, plan_blob: bytes, clients: int):
        from spark_rapids_jni_tpu.bridge import BridgeClient
        self.sock, self.blob = sock, plan_blob
        self.clients = [BridgeClient(sock, timeout=900)
                        for _ in range(clients)]
        self.samples: list = []     # (client, t_sent, seconds) — window clock
        self.results: list = []     # columns, or None where the request failed
        self.errors: list = []
        self._lock = threading.Lock()
        self._closing = threading.Event()
        self.t_start = self.t_end = 0.0

    def _client(self, i: int) -> None:
        from spark_rapids_jni_tpu.bridge import BridgeClient
        while not self._closing.is_set():
            t_sent = time.perf_counter() - self.t_start
            try:
                dt, cols = one_query(self.clients[i], self.blob)
            except Exception as e:  # noqa: BLE001 — a failed request counts
                dt, cols = None, None
                with self._lock:
                    self.errors.append(f"{type(e).__name__}: {e}")
                try:    # a failed client may be poisoned: take a new one
                    self.clients[i].close()
                    self.clients[i] = BridgeClient(self.sock, timeout=900)
                except OSError:
                    return
            with self._lock:
                self.samples.append((i, t_sent, dt))
                self.results.append(cols)

    def run(self, seconds: float, before_close=None) -> None:
        threads = [threading.Thread(target=self._client, args=(i,),
                                    name=f"bench-client-{i}")
                   for i in range(len(self.clients))]
        self.t_start = time.perf_counter()
        for t in threads:
            t.start()
        if before_close is None:
            time.sleep(seconds)
        else:
            before_close(self.t_start + seconds)
        self._closing.set()
        for t in threads:
            t.join()
        self.t_end = time.perf_counter()

    def close(self) -> None:
        for c in self.clients:
            c.close()


def log_window(loop: ClosedLoop, snap_end: dict) -> None:
    """To the log, never to the result: how the window's latencies lie,
    and where the server spent the last queries (OP_METRICS keeps 32)."""
    ok = sorted(dt for _, _, dt in loop.samples if dt is not None)
    if ok:
        log("latency ms: " + ", ".join(
            f"{name} {ok[min(len(ok) - 1, int(q * len(ok)))] * 1e3:.1f}"
            for name, q in (("min", 0), ("p10", .1), ("p50", .5),
                            ("p90", .9), ("max", 1))))
    recent = snap_end.get("queries") or []
    if recent:
        mean = {}
        for q in recent:
            parts = {"wall": q["wall_s"], **{
                f"{i}:{n.get('label')}": n.get("wall_s", 0.0)
                for i, n in enumerate(q.get("nodes", ()))},
                **q.get("timers", {})}
            for k, v in parts.items():
                mean[k] = mean.get(k, 0.0) + v / len(recent)
        log(f"server, mean of its last {len(recent)} queries, s: "
            + json.dumps({k: round(v, 4) for k, v in mean.items()}))


def traced_stretch(trace_dir: str):
    """The last TRACE_SECONDS of the window run under the profiler."""
    def wait_for(name: str, timeout: float) -> dict:
        path = os.path.join(trace_dir, name)
        deadline = time.monotonic() + timeout
        while not os.path.exists(path):
            if time.monotonic() > deadline:
                raise RunFailure(f"the child wrote no trace/{name}")
            time.sleep(0.005)
        return load_json(path)

    def before_close(t_close: float) -> None:
        time.sleep(max(0.0, t_close - TRACE_SECONDS - time.perf_counter()))
        open(os.path.join(trace_dir, "start"), "w").close()
        doc = wait_for("started", 120)
        if "error" in doc:
            raise RunFailure(f"profiler did not start: {doc['error']}")
        time.sleep(TRACE_SECONDS)
        open(os.path.join(trace_dir, "stop"), "w").close()

    return before_close, lambda: wait_for("done", 300)


# -- one run ---------------------------------------------------------------------

def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile of all the window's requests."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, -(-len(s) * q // 100) - 1))]


PERCENTILE_METRIC = re.compile(r"query_p(\d{1,2})_ms$")


def end_to_end(cell: Cell, loop: ClosedLoop, setup_s: float,
               fact_rows: int) -> dict:
    """The cell's end-to-end metrics, over ALL requests of the window.
    ``query_p<NN>_ms`` is the NN-th percentile of the latencies, so a tail
    is added to `BENCHMARK.json` by its name alone."""
    ok = [dt for _, _, dt in loop.samples if dt is not None]
    window_s = loop.t_end - loop.t_start
    out = {}
    for m in cell.metrics("end_to_end"):
        tail = PERCENTILE_METRIC.match(m["name"])
        if tail:
            value = percentile(ok, int(tail.group(1))) * 1e3
        elif m["name"] == "fact_rows_per_s":
            value = len(ok) * fact_rows / window_s
        elif m["name"] == "setup_s":
            value = setup_s
        else:
            raise RunFailure(f"no end-to-end metric {m['name']!r} here")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def per_layer(cell: Cell, ctx: dict) -> dict:
    out = {}
    for m in cell.metrics("per_layer"):
        value = load_module("layer_metrics", m["name"]).read(ctx)
        if value is not None:       # a reader that finds nothing says nothing
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_platform: str | None = "tpu",
             launcher: str = os.path.join(HERE, "server_child.py"),
             launcher_args: tuple = ()) -> dict | None:
    """Returns the result line's object, or None for a rehearsal that ran
    to its end on another platform than ``require_platform``."""
    cell = Cell(workload)
    run_dir = tempfile.mkdtemp(prefix="srjt_bench_")
    proc = client = loop = None
    try:
        proc, sock = start_child(cell, run_dir, trace, launcher,
                                 launcher_args)
        # the child takes seconds to reach the chip: make the data meanwhile
        frames = cell.query.tables(seed, cell.rows(rehearsal=False))
        client = wait_until_up(proc, sock)
        device = client.metrics()["device"]
        log(f"server child up; device {json.dumps(device)}")
        rehearsal = require_platform is not None \
            and device["platform"] != require_platform
        if rehearsal:
            log(f"REHEARSAL: platform {device['platform']!r} is not "
                f"{require_platform!r}; fact cut to "
                f"{cell.config['rehearsal_rows']}, no metric will be printed")
        if rehearsal or require_platform is None:
            frames = cell.query.tables(seed, cell.rows(rehearsal=True))
        paths = write_tables(frames, cell.config, run_dir)
        fact_rows = len(frames[cell.query.FACT])
        params = cell.traffic["params"]
        plan_blob = cell.query.plan(
            paths, params, cell.config["storage"]["chunk_bytes"]).serialize()
        log(f"warehouse: {cell.query.FACT} {fact_rows} rows, "
            f"{os.path.getsize(paths[cell.query.FACT]) >> 10} KiB")

        warm = warm_up(client, plan_blob)
        loop = ClosedLoop(sock, plan_blob, int(cell.traffic["clients"]))
        snap_start = client.metrics()
        setup_s = time.perf_counter() - T0
        if trace:
            before_close, trace_done = traced_stretch(
                os.path.join(run_dir, "trace"))
            loop.run(seconds, before_close)
            trace_doc = trace_done()
        else:
            loop.run(seconds)
            trace_doc = None
        snap_end = client.metrics()
        log(f"window {loop.t_end - loop.t_start:.3f} s: {len(loop.samples)} "
            f"requests, {len(loop.errors)} failed")
        for e in loop.errors[:3]:
            log(f"  failed request: {e}")
        if loop.errors:
            log(f"server-side traceback:\n{server_traceback(run_dir)}")
        log_window(loop, snap_end)
        live = client.live_count()
        loop.close()
        client.shutdown_server()
        client = None
        rc = proc.wait(timeout=180)
        proc = None
        if rc != 0:
            raise RunFailure(f"server child exited {rc}")

        # the window has closed and the child is gone: now the reference
        want = cell.query.reference(frames, params)
        checks = compare.compare(loop.results, want)
        checks["leaked_handles"] = {"value": live, "limit": 0}
        checks["degraded_or_fallback"] = {
            "value": sum(int(v) for k, v in snap_end["counters"].items()
                         if k.startswith("engine.degraded")
                         or k == "io.device_decode.fallbacks"), "limit": 0}
        correct = compare.verdict(checks)

        memory = snap_end["device"].get("memory") or {}
        result = {
            "correct": correct,
            "attempted": len(loop.samples),
            "failed": len(loop.errors),
            "metrics": {},
            "device": {"platform": device["platform"], "kind": device["kind"],
                       "count": device["count"],
                       "memory_peak_bytes": memory.get("peak_bytes_in_use")},
        }
        if trace:
            import trace_reduce
            reduced = trace_reduce.reduce_dir(trace_doc["log_dir"])
            keep = os.environ.get("BENCH_KEEP_TRACE")
            if keep:    # a debugging aid: the raw trace, for a look by hand
                shutil.copytree(trace_doc["log_dir"], os.path.join(
                    keep, f"{workload}.{seed}"), dirs_exist_ok=True)
            ctx = {"cell": cell, "warm": warm, "loop": loop,
                   "snap_start": snap_start, "snap_end": snap_end,
                   "trace": reduced, "trace_doc": trace_doc,
                   "fact_rows": fact_rows, "device": device,
                   "compile_count": compile_count,
                   "peaks": load_json(os.path.join(HERE, "peaks.json"))}
            if reduced is not None and reduced["busy_s"] > 0:
                result["device"]["busy_s"] = reduced["busy_s"]
                result["device"]["window_s"] = reduced["window_s"]
                result["breakdown"] = trace_reduce.breakdown(reduced)
            result["metrics"] = per_layer(cell, ctx)
        else:
            result["metrics"] = end_to_end(cell, loop, setup_s, fact_rows)
        result["checks"] = checks

        xb = sys.modules.get("jax._src.xla_bridge")
        if xb is not None and xb.backends_are_initialized():
            raise RunFailure("this process initialised a jax backend")
        for name, c in checks.items():
            log(f"check {name}: {c['value']} (limit {c['limit']})")
        log(f"correct: {correct}")
        if rehearsal:
            log(f"metrics a TPU run would print: {sorted(result['metrics'])}")
            log("REHEARSAL ran to its end: every request and comparison was "
                "made; no result, because the platform is not "
                f"{require_platform!r}")
            return None
        if require_platform is not None and device["count"] != cell.chips:
            raise RunFailure(f"the cell asks for {cell.chips} chip(s), the "
                             f"server saw {device['count']}")
        return result
    except Exception:
        if proc is not None and client is not None:
            log(f"server-side traceback:\n{server_traceback(run_dir)}")
        raise
    finally:
        if loop is not None:
            loop.close()
        if proc is not None:
            proc.kill()
            proc.wait()
        shutil.rmtree(run_dir, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except RunFailure as e:
        log(f"RUN FAILED: {e}")
        return 1
    except Exception:  # noqa: BLE001 — any failure is the verdict
        log(f"RUN FAILED:\n{traceback.format_exc()}")
        return 1
    if result is None:
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
