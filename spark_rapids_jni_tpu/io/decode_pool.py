"""A pool of worker PROCESSES that decode Parquet row groups.

`ParquetFile._decode_group` is python per page and per run header plus numpy
calls over a few thousand values: it holds the interpreter's lock for most
of its time, so N streams decoding on N threads of one server convoy on
that lock while the host's other cores idle (PERF.md section 6, PR 33 /
34).  A process has an interpreter of its own.  The streamed scan
(`ParquetChunkedReader._host_slices`) hands its next few row groups to this
pool and takes the decoded columns out of shared memory.

- **Workers** (`io/decode_worker.py`) are long-lived children started with
  ``subprocess`` — never ``fork``: the serving process holds the
  accelerator's runtime and its threads — with ``JAX_PLATFORMS=cpu`` and
  without ``SRJT_TRACE`` in their environment, so none can touch the chip
  or the profiler.  They leave when their request pipe closes: at
  `shutdown`, at interpreter exit, and when the parent is killed.
- **Slabs** are anonymous shared files (``memfd_create``: no name, so
  nothing is left behind whatever kills the parent), made once, inherited
  by every worker, grown on demand and reused: no segment per row group.
- **One FIFO** carries the requests of every live stream to whichever
  worker is idle, so sessions share the workers in arrival order.
- What a caller holds is a `Ticket`: `wait` for its columns, then
  `release` it — at any time, also unanswered; the slab returns when the
  worker has let go of it.

Nothing per-query crosses into a worker: counters, spans and the retry
policy stay with the caller (io/parquet.py).
"""

from __future__ import annotations

import atexit
import collections
import itertools
import json
import mmap
import os
import subprocess
import sys
import threading
import time

import numpy as np

from ..utils.config import child_environ, config, logger
from ..utils.errors import TransientError

#: row groups a stream keeps in the pool beyond the one it waits for: a
#: worker takes 12-14 ms over a group and the stream asks for one every
#: 5-6 ms since the staging costs 3 ms and not 6-7 (PERF.md section 6,
#: PR 37), so three in flight just keep up and the fourth is the slack - the
#: stream's wait for a group reads 0.8-1.9 ms where it read 0.25-1.7; host
#: memory per stream is (READ_AHEAD + 1) slabs of one decoded group each
READ_AHEAD = 3
#: every buffer in a slab starts on a multiple of this
ALIGN = 64
#: slab sizes are rounded up to this, so a file's groups share one size
SLAB_QUANTUM = 1 << 20
#: seconds a worker gets to import the package and say hello
START_TIMEOUT_S = 120.0


def align(n: int) -> int:
    return (n + ALIGN - 1) & ~(ALIGN - 1)


class WorkerLost(TransientError):
    """The worker that held this row group died: the group is decoded
    again (`retry_call` at the site ``parquet.chunk``), never half used."""


def default_workers() -> int:
    """Half the cores this process may run on, at most 8: the decode of
    one stream keeps about two workers busy, and the serving process's own
    threads need the rest."""
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:  # not Linux
        cores = os.cpu_count() or 2
    return max(1, min(8, cores // 2))


def slab_bytes(itemsizes, rows: int) -> int:
    """What the columns of one row group need in a slab at most: values
    and a validity byte per row, each 64-byte aligned."""
    return sum(align(rows * size) + align(rows) for size in itemsizes)


class Ticket:
    """One row group's place in the pool.  State, under the pool's lock:
    queued -> running -> done, or released at any point."""

    __slots__ = ("request", "slab", "done", "reply", "lost", "running",
                 "released")

    def __init__(self, request: dict, slab: "_Slab"):
        self.request = request
        self.slab = slab
        self.done = threading.Event()
        self.reply: dict | None = None
        self.lost = False
        self.running = False
        self.released = False


class _Slab:
    def __init__(self, index: int):
        self.index = index
        self.fd = os.memfd_create(f"srjt-decode-{index}")
        self.size = 0
        self.buf: mmap.mmap | None = None

    def grow(self, size: int) -> None:
        size = -(-size // SLAB_QUANTUM) * SLAB_QUANTUM
        os.ftruncate(self.fd, size)
        # the old map is dropped, not closed: a view of it may still live
        self.buf = mmap.mmap(self.fd, size)
        self.size = size

    def close(self) -> None:
        os.close(self.fd)       # the map lives as long as a view of it


class _Worker:
    def __init__(self, proc: subprocess.Popen):
        self.proc = proc
        self.ticket: Ticket | None = None
        self.hello: dict | None = None     # what it said when it came up


class DecodePool:
    """``workers`` decode processes over ``slabs`` shared buffers: by
    default a full window for every session the server admits at once
    (``SRJT_MAX_SESSIONS``); a stream that finds none free decodes that
    group itself."""

    def __init__(self, workers: int | None = None, slabs: int | None = None):
        self.size = default_workers() if workers is None else int(workers)
        self._lock = threading.Lock()
        self._slabs = [_Slab(i) for i in range(
            slabs or (READ_AHEAD + 1) * config.max_sessions)]
        self._free = list(self._slabs)
        self._fifo: collections.deque = collections.deque()
        self._workers: list = []
        self._idle: list = []
        self._threads: list = []
        self._ids = itertools.count(1)
        self._closing = False
        self._started = False

    # -- life -----------------------------------------------------------------

    def start(self) -> None:
        """Start the workers, each on a thread of its own that then reads
        its replies; returns at once.  Until one has said hello `submit`
        returns None and the caller decodes the group itself."""
        with self._lock:
            if self._started or self._closing:
                return
            self._started = True
            for _ in range(self.size):
                self._spawn()

    def _spawn(self) -> None:
        """(lock held)"""
        t = threading.Thread(target=self._run_worker, daemon=True,
                             name="srjt-decode-pool")
        self._threads.append(t)
        t.start()

    def wait_ready(self, timeout: float = START_TIMEOUT_S,
                   workers: int | None = None) -> bool:
        """Block until ``workers`` (default: all) have said hello."""
        want = self.size if workers is None else workers
        deadline = time.monotonic() + timeout
        while self.live() < want and time.monotonic() < deadline:
            time.sleep(0.01)
        return self.live() >= want

    def live(self) -> int:
        with self._lock:
            return sum(w.hello is not None for w in self._workers)

    def hellos(self) -> list:
        """What each live worker said of itself when it came up: ``hello``
        (its pid), ``backends`` (the jax backends its imports initialised),
        ``jax_platforms``, ``trace``."""
        with self._lock:
            return [w.hello for w in self._workers if w.hello]

    def slabs_free(self) -> int:
        with self._lock:
            return len(self._free)

    def slabs_total(self) -> int:
        return len(self._slabs)

    def shutdown(self, timeout: float = 5.0) -> None:
        """Close every worker's request pipe, wait for the workers to
        leave, kill what stays; idempotent.  Unanswered tickets read
        `WorkerLost`."""
        with self._lock:
            if self._closing:
                return
            self._closing = True
            workers = list(self._workers)
            threads = list(self._threads)
            while self._fifo:
                self._settle(self._fifo.popleft(), None)
        for w in workers:
            try:
                w.proc.stdin.close()
            except OSError:
                pass
        for w in workers:
            try:
                w.proc.wait(timeout)
            except subprocess.TimeoutExpired:
                w.proc.kill()
                w.proc.wait()
        for t in threads:
            t.join(timeout)
        for s in self._slabs:
            s.close()

    # -- a worker's thread: start it, then read what it answers ---------------

    def _run_worker(self) -> None:
        env = child_environ()
        env["JAX_PLATFORMS"] = "cpu"    # before the child imports anything
        env.pop("SRJT_TRACE", None)
        # a decode allocates and frees a group's arrays (a few MB each): by
        # default glibc hands them back to the kernel every time and the
        # next group faults them in again, page by page — twice the decode's
        # time in a fresh process (PERF.md section 6, PR 35).  A worker does
        # nothing else: it keeps what it freed.
        env.setdefault("MALLOC_MMAP_THRESHOLD_", str(32 << 20))
        env.setdefault("MALLOC_TRIM_THRESHOLD_", str(256 << 20))
        fds = [s.fd for s in self._slabs]
        try:
            proc = subprocess.Popen(
                [sys.executable, "-m", "spark_rapids_jni_tpu.io.decode_worker"]
                + [str(fd) for fd in fds],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
                pass_fds=fds)
        except OSError as e:
            logger().warning("decode pool: no worker could be started (%s); "
                             "row groups are decoded in this process", e)
            return
        w = _Worker(proc)
        with self._lock:
            if self._closing:
                proc.stdin.close()
            self._workers.append(w)
        try:
            for line in iter(proc.stdout.readline, b""):
                reply = json.loads(line)
                with self._lock:
                    if "hello" in reply:
                        w.hello = reply
                    else:
                        self._answered(w, reply)
                    self._idle.append(w)
                    self._pump()
        finally:
            proc.stdout.close()
            with self._lock:
                self._lost(w)
            if proc.poll() is None and not self._closing:
                proc.kill()
            proc.wait()

    def _settle(self, t: Ticket, reply: dict | None) -> None:
        """(lock held)  No worker holds the ticket any more: wake whoever
        waits for it — ``reply`` None reads `WorkerLost` — or, if nobody
        does, take its slab back."""
        t.running = False
        if t.released:
            self._free.append(t.slab)
            return
        t.reply, t.lost = reply, reply is None
        t.done.set()

    def _answered(self, w: _Worker, reply: dict) -> None:
        """(lock held)"""
        t, w.ticket = w.ticket, None
        if t is None or reply.get("id") != t.request["id"]:
            raise RuntimeError(f"decode worker {w.proc.pid}: reply {reply!r} "
                               "matches no request")
        self._settle(t, reply)

    def _lost(self, w: _Worker) -> None:
        """(lock held)  The worker's pipe closed: fail what it held, and
        put another in its place — unless it never came up, which would
        only repeat."""
        self._workers.remove(w)
        if w in self._idle:
            self._idle.remove(w)
        t, w.ticket = w.ticket, None
        if t is not None:
            self._settle(t, None)
        if self._closing:
            return
        if w.hello:
            logger().warning("decode pool: worker %d left (rc=%s); replaced",
                             w.proc.pid, w.proc.poll())
            self._spawn()
        else:
            logger().warning("decode pool: worker %d never came up (rc=%s)",
                             w.proc.pid, w.proc.poll())

    def _pump(self) -> None:
        """(lock held)  Hand queued requests to idle workers, in order."""
        while self._fifo and self._idle and not self._closing:
            w = self._idle.pop()
            t = self._fifo.popleft()
            w.ticket = t
            t.running = True
            try:
                w.proc.stdin.write(json.dumps(t.request).encode() + b"\n")
                w.proc.stdin.flush()
            except OSError:
                # it died idle: its reader thread sees the end of its pipe
                # and fails this ticket with what else the worker held
                pass

    # -- the caller's side ----------------------------------------------------

    def submit(self, path: str, group: int, columns, nbytes: int
               ) -> Ticket | None:
        """Queue one row group's decode; None if the pool cannot take it
        now — no worker up, or every slab in use — and the caller decodes
        it itself."""
        with self._lock:
            if self._closing or not self._free \
                    or not any(w.hello for w in self._workers):
                return None
            slab = self._free.pop()
            if slab.size < nbytes:
                slab.grow(nbytes)
            t = Ticket({"id": next(self._ids), "path": path, "group": group,
                        "columns": columns, "slab": slab.index,
                        "size": slab.size}, slab)
            self._fifo.append(t)
            self._pump()
            return t

    def wait(self, t: Ticket, cancel=None):
        """The ticket's columns: ``(columns, tally, seconds)`` with
        ``columns`` a list of ``(values, validity | None)`` views of the
        slab, valid until `release`; None if the worker could not decode
        the group (the caller's own decode then raises what it was).
        Raises `WorkerLost` if the worker died, and what ``cancel.check``
        raises once the query is cancelled."""
        while not t.done.wait(0.1):
            if cancel is not None:
                cancel.check()
        if t.lost:
            raise WorkerLost("decode worker died with row group "
                             f"{t.request['group']} of {t.request['path']}")
        reply = t.reply
        if "error" in reply:
            logger().warning("decode pool: %s (row group %d of %s); decoding "
                             "in this process", reply["error"],
                             t.request["group"], t.request["path"])
            return None
        buf = t.slab.buf
        cols = [(np.frombuffer(buf, dtype, rows, voff),
                 None if moff is None
                 else np.frombuffer(buf, np.bool_, rows, moff))
                for dtype, rows, voff, moff in reply["cols"]]
        return cols, reply["tally"], reply["s"]

    def release(self, t: Ticket) -> None:
        """The caller is finished with the ticket, answered or not: the
        slab returns to the pool as soon as no worker writes to it."""
        with self._lock:
            if t.released:
                return
            t.released = True
            if t.running:
                return              # its worker's thread returns the slab
            try:
                self._fifo.remove(t)
            except ValueError:
                pass
            if not self._closing:
                self._free.append(t.slab)


# -- the process-wide pool ---------------------------------------------------------

_shared: DecodePool | None = None
_shared_failed = False
_shared_lock = threading.Lock()


def shared() -> DecodePool | None:
    """The pool every streamed scan of this process uses, started at the
    first call (the workers come up behind it: `DecodePool.start`).  None
    if the process cannot have one — no ``memfd_create``, no descriptors —
    which is logged once; the scans then decode as they did without it."""
    global _shared, _shared_failed
    with _shared_lock:
        if _shared is None and not _shared_failed:
            try:
                _shared = DecodePool()
            except (AttributeError, OSError) as e:
                _shared_failed = True
                logger().warning("decode pool: cannot be made (%s); row "
                                 "groups are decoded in this process", e)
                return None
        pool = _shared
    if pool is not None:
        pool.start()
    return pool


def install(pool: DecodePool | None) -> DecodePool | None:
    """Put ``pool`` in the shared pool's place and return what was there
    (tests; the caller shuts down what it made)."""
    global _shared, _shared_failed
    with _shared_lock:
        old, _shared = _shared, pool
        _shared_failed = False
        return old


@atexit.register
def _shutdown_shared() -> None:
    pool = install(None)
    if pool is not None:
        pool.shutdown(timeout=2.0)
