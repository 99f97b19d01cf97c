"""One worker process of the Parquet decode pool (io/decode_pool.py).

    python -m spark_rapids_jni_tpu.io.decode_worker <slab fd> [<slab fd> ...]

Started by `DecodePool` with ``subprocess`` (never by ``fork``: the serving
process holds the accelerator's runtime and its threads), with
``JAX_PLATFORMS=cpu`` in its environment before anything is imported — the
package imports jax, and a worker must never initialise a backend on the
chip its parent holds.  It reads one JSON request per line on stdin and
answers one JSON line per request on the descriptor that was its stdout:

    {"id", "path", "group", "columns", "slab", "size"}
      -> {"id", "cols": [[dtype, rows, values_off, validity_off|null], ...],
          "tally": [chunks, pages, runs, dense_chunks], "s": seconds,
          "copy_s": the part of them that copied into the slab}
      or {"id", "error": "<Type>: <message>"}

The decode is `ParquetFile._decode_group`, the call the streamed scan made
on its producer thread before there was a pool: same code, same bytes.  The
columns' buffers are copied into slab ``slab`` (an anonymous shared file
whose descriptor the worker inherited; ``size``: what the parent has grown
it to), 64-byte aligned, values then validity.

The worker exits when stdin reaches its end: the parent closed the pool, or
died — also by SIGKILL, which closes its end of the pipe.  (No
``PR_SET_PDEATHSIG``: it fires when the THREAD that started the child ends,
and workers are started from short-lived threads.)
"""

from __future__ import annotations

import json
import mmap
import os
import sys
import time

FILES_KEPT = 16


def main(argv: list) -> int:
    slab_fds = [int(a) for a in argv]
    # the reply pipe is ours alone: whatever an import prints goes to stderr
    reply = os.fdopen(os.dup(1), "wb", buffering=0)
    os.dup2(2, 1)

    import numpy as np

    import jax
    import jax._src.xla_bridge as xb

    from ..utils.config import config
    from .decode_pool import align
    from .parquet import ParquetFile, _DecodeTally

    maps: dict = {}     # slab index -> mmap of its current size
    files: dict = {}    # path -> ((mtime, size), ParquetFile): one footer parse

    def slab(index: int, size: int) -> mmap.mmap:
        m = maps.get(index)
        if m is None or len(m) < size:
            m = maps[index] = mmap.mmap(slab_fds[index], size)
        return m

    def decode(req: dict) -> dict:
        t0 = time.perf_counter()
        path = req["path"]
        st = os.stat(path)
        key, pf = files.get(path, (None, None))
        if key != (st.st_mtime_ns, st.st_size):     # new, or rewritten
            files.pop(path, None)
            if len(files) >= FILES_KEPT:
                files.pop(next(iter(files)))
            key, pf = files[path] = ((st.st_mtime_ns, st.st_size),
                                     ParquetFile(path))
        tally = _DecodeTally()
        hosts = pf._decode_group(req["group"], req["columns"], tally)
        t1 = time.perf_counter()
        m = slab(req["slab"], req["size"])
        cols, off = [], 0
        for h in hosts:
            if h.values is None:
                raise TypeError(f"{h.schema.name}: not a fixed-width column")
            placed = []
            for arr in (h.values, h.validity):
                if arr is None:
                    placed.append(None)
                    continue
                if off + arr.nbytes > len(m):
                    raise ValueError("row group larger than its slab")
                np.frombuffer(m, arr.dtype, len(arr), off)[:] = arr
                placed.append(off)
                off = align(off + arr.nbytes)
            cols.append([h.values.dtype.str, len(h.values)] + placed)
        return {"id": req["id"], "cols": cols,
                "tally": [tally.chunks, tally.pages, tally.runs,
                          tally.dense_chunks],
                "s": time.perf_counter() - t0,
                "copy_s": time.perf_counter() - t1}

    # up: the imports are done, and what they initialised is told (tests
    # hold a worker to the CPU backend and to no profiler)
    reply.write(json.dumps({
        "hello": os.getpid(), "backends": sorted(xb._backends),
        "jax_platforms": jax.config.jax_platforms,
        "trace": bool(config.trace)}).encode() + b"\n")
    for line in iter(sys.stdin.buffer.readline, b""):
        req = json.loads(line)
        try:
            out = decode(req)
        except Exception as e:  # noqa: BLE001 — the parent decodes the group
            # itself and raises what this was, with its own type
            out = {"id": req["id"], "error": f"{type(e).__name__}: {e}"}
        reply.write(json.dumps(out).encode() + b"\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
