#!/usr/bin/env python3
"""The benchmark's launcher of the one process that holds the chip.

It runs the program's own server unchanged — ``enable_compile_cache()`` and
``BridgeServer(sock).serve_forever()`` — and adds the one thing the program
has no op for: with ``--trace-dir`` a side thread starts and stops
``jax.profiler`` when `run.py` asks through files in that directory.  Only
the process that holds the chip can trace it.

    <dir>/start    run.py: start tracing
    <dir>/started  child:  the profiler runs
    <dir>/stop     run.py: stop
    <dir>/done     child:  the trace is written (JSON: its directory, or
                           the error that kept the profiler from starting)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

POLL_S = 0.005


def _await(path: str, stop: threading.Event) -> bool:
    while not stop.is_set():
        if os.path.exists(path):
            return True
        time.sleep(POLL_S)
    return False


def _write(path: str, doc: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)       # the reader never sees half a file


def trace_on_request(trace_dir: str, stop: threading.Event) -> None:
    """One traced stretch per run: wait for `start`, trace until `stop`."""
    import jax
    if not _await(os.path.join(trace_dir, "start"), stop):
        return
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0    # no per-call Python events: they slow
    opts.host_tracer_level = 2      # the host; TraceAnnotations stay
    doc = {"log_dir": os.path.join(trace_dir, "profile")}
    try:
        jax.profiler.start_trace(doc["log_dir"], profiler_options=opts)
        _write(os.path.join(trace_dir, "started"), doc)
        # the span whose length is the traced window, on the trace's clock
        with jax.profiler.TraceAnnotation("bench.trace_window"):
            _await(os.path.join(trace_dir, "stop"), stop)
        jax.profiler.stop_trace()
    except Exception as e:  # noqa: BLE001 — the run reports it and fails
        doc["error"] = f"{type(e).__name__}: {e}"
        _write(os.path.join(trace_dir, "started"), doc)
    _write(os.path.join(trace_dir, "done"), doc)


def annotate_dispatch() -> None:
    """Traced runs only: every bridge request becomes a host span
    ``bridge.op.<name>`` in the profiler's trace, so that an idle gap of
    the device inside a request is told from one between requests (no
    span open: the server waits for the client).  The program's dispatch
    runs unchanged inside the span."""
    import jax
    from spark_rapids_jni_tpu.bridge import protocol
    from spark_rapids_jni_tpu.bridge.server import BridgeServer
    names = {v: k[3:].lower() for k, v in vars(protocol).items()
             if k.startswith("OP_") and isinstance(v, int)}
    inner = BridgeServer._dispatch

    def _dispatch(self, opcode, *args, **kwargs):
        with jax.profiler.TraceAnnotation(
                f"bridge.op.{names.get(opcode, opcode)}"):
            return inner(self, opcode, *args, **kwargs)

    BridgeServer._dispatch = _dispatch


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--socket", required=True)
    ap.add_argument("--trace-dir", default="")
    args = ap.parse_args()
    from spark_rapids_jni_tpu.bridge.server import BridgeServer, device_info
    from spark_rapids_jni_tpu.utils.config import enable_compile_cache
    cache_dir = enable_compile_cache()
    print(f"[server_child] device: {json.dumps(device_info())} "
          f"compile cache: {cache_dir}", file=sys.stderr, flush=True)
    stop = threading.Event()
    tracer = None
    if args.trace_dir:
        annotate_dispatch()
        tracer = threading.Thread(target=trace_on_request,
                                  args=(args.trace_dir, stop),
                                  name="bench-tracer", daemon=False)
        tracer.start()
    try:
        BridgeServer(args.socket).serve_forever()
    finally:
        stop.set()
        if tracer is not None:
            tracer.join(timeout=120)


if __name__ == "__main__":
    main()
