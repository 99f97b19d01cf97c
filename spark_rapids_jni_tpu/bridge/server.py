"""The device server: handle-table owner and op dispatcher.

TPU-native analog of the reference's native side of the JNI boundary: where
``RowConversionJni.cpp`` unwraps a jlong into a ``cudf::table_view*`` in the
same address space (reference RowConversionJni.cpp:31), this server owns a
``HandleTable`` mapping opaque u64 ids to device-resident ``Table`` /
``Column`` objects (jax.Arrays in HBM) and executes ops named by opcode.
Per-op traffic is handles only; bulk host columns stage through shared
memory at import/export (bridge/__init__ docstring).

Error discipline mirrors ``CATCH_STD`` + ``JNI_NULL_CHECK``
(reference RowConversionJni.cpp:27,40,65): every dispatch wraps in
try/except and returns STATUS_ERROR with the message; unknown handles raise
KeyError -> error response, never a crash.

Run: ``python -m spark_rapids_jni_tpu.bridge.server --socket /tmp/tpub.sock``
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import socket
import struct
import threading
import time

import numpy as np
# Loaded here, on the thread that imports the server (the process's main
# thread under ``main``), never first inside a connection thread: pyarrow's
# allocator (mimalloc) ties its process-wide state to the thread that loads
# it, and once that thread exits the next thread to allocate through
# pyarrow (io/parquet.py's snappy codec) dies with SIGSEGV.  The io modules
# that use pyarrow load lazily on a connection's first scan, so without
# this the first client's thread would be that owner.
import pyarrow  # noqa: F401

from . import protocol as P
from . import shm as shmlib
from ..columnar import Column, Table
from ..dtypes import DType, TypeId
from ..utils.tracing import op_scope

_COLDESC = P.COLDESC
_STRDESC = P.STRDESC


def _error_body(e: Exception, trace_id: str = "", bundle: str = "") -> bytes:
    """STATUS_ERROR payload for one failed op.

    Plan-verification failures ship as a JSON document carrying the check
    code + node path (the client reconstructs a ``PlanVerificationError``);
    everything else ships the error-taxonomy JSON (kind + retryable bit +
    type + message, utils.errors.to_wire) so the client can reconstruct a
    typed error and its retry layer can tell transient from fatal without
    string-matching.  Both shapes carry the trace_id and the post-mortem
    bundle path (utils/blackbox.py) when known, so a failed call is
    joinable to server telemetry from the client side alone."""
    import json

    from ..engine.verify import PlanVerificationError
    if isinstance(e, PlanVerificationError):
        doc = {"error": "plan_verification", **e.to_dict()}
    else:
        from ..utils import errors
        doc = errors.to_wire(e)
    if trace_id and not doc.get("trace_id"):
        doc["trace_id"] = trace_id
    if bundle and not doc.get("bundle"):
        doc["bundle"] = bundle
    return json.dumps(doc).encode()


class HandleTable:
    """u64 id -> device object; the process-local analog of JNI jlong handles.

    Internally locked: with PLAN_EXECUTE bodies running concurrently
    (engine/scheduler.py) the table is written from many worker threads,
    and ``put``'s id-allocate-then-store must be atomic or two sessions
    could mint the same handle."""

    def __init__(self):
        self._next = 1
        self._objs: dict[int, object] = {}
        self._lock = threading.Lock()

    def put(self, obj) -> int:
        with self._lock:
            h = self._next
            self._next += 1
            self._objs[h] = obj
        return h

    def get(self, h: int):
        try:
            with self._lock:
                return self._objs[h]
        except KeyError:
            raise KeyError(f"invalid or released handle {h}") from None

    def release(self, h: int) -> None:
        with self._lock:
            gone = self._objs.pop(h, None) is None
        if gone:
            raise KeyError(f"invalid or released handle {h}")

    def live_count(self) -> int:
        with self._lock:
            return len(self._objs)


def _parse_columns(payload: bytes, off: int, ncols: int, buf) -> list[Column]:
    """Build device columns from shm-resident Arrow-layout buffers."""
    import jax.numpy as jnp
    cols = []
    for _ in range(ncols):
        tid, scale, n, hasv, doff, dlen, voff, vlen = _COLDESC.unpack_from(
            payload, off)
        off += _COLDESC.size
        dtype = DType(TypeId(tid), scale)
        # .copy() everywhere: frombuffer views pin the mmap and would make
        # the caller's buf.close() raise BufferError
        validity = None
        if hasv:
            vraw = np.frombuffer(buf, np.uint8, vlen, voff).copy()
            validity = jnp.asarray(vraw.astype(np.bool_))
        if dtype.is_string:
            ooff, olen = _STRDESC.unpack_from(payload, off)
            off += _STRDESC.size
            chars = np.frombuffer(buf, np.uint8, dlen, doff).copy()
            offsets = np.frombuffer(buf, np.int32, olen // 4, ooff).copy()
            cols.append(Column.string(chars, offsets, validity))
        else:
            host = np.frombuffer(buf, dtype.storage, n, doff).copy()
            cols.append(Column.fixed(dtype, host, validity))
    return cols, off


def _export_column_desc(exp: shmlib.SegmentWriter, col: Column) -> bytes:
    """Write one column's buffers into the exporter, return its descriptor."""
    n = col.size
    hasv = col.validity is not None
    voff = vlen = 0
    if hasv:
        voff, vlen = exp.add(np.asarray(col.validity).astype(np.uint8).tobytes())
    if col.dtype.is_string:
        chars = b"" if col.data is None else np.asarray(col.data).tobytes()
        doff, dlen = exp.add(chars)
        ooff, olen = exp.add(np.asarray(col.offsets, np.int32).tobytes())
        return _COLDESC.pack(int(col.dtype.id), col.dtype.scale, n, hasv,
                             doff, dlen, voff, vlen) + _STRDESC.pack(ooff, olen)
    # fixed-width: device buffer bytes ARE the wire bytes (FLOAT64 stores
    # IEEE bit patterns as int64 — identical bytes to the doubles)
    doff, dlen = exp.add(np.asarray(col.data).tobytes())
    return _COLDESC.pack(int(col.dtype.id), col.dtype.scale, n, hasv,
                         doff, dlen, voff, vlen)


class BridgeServer:
    """Serves many clients concurrently (thread per connection).

    A Spark executor JVM runs many task threads; the reference handles the
    matching concurrency with per-thread CUDA streams (reference pom.xml:80).
    Here each connection gets a thread.  ``_dispatch_lock`` serializes the
    *small* ops (handle plumbing, imports/exports, per-op engine shims) —
    each is one JAX dispatch anyway, so slicing that critical section
    thinner buys nothing.  PLAN_EXECUTE is the exception: whole plans run
    for seconds and the engine below is concurrency-safe (locked caches,
    per-query metrics contexts, the fair-share scheduler), so plan bodies
    run OUTSIDE the dispatch lock on their connection threads and the
    scheduler — not this lock — provides admission control and
    interleaving.  OP_CANCEL / OP_QUERY_STATUS / OP_SHUTDOWN stay lock-free
    in ``_client_loop`` as before.  The shared mutable state a concurrent
    plan can touch (handle table, export map, op counters) is individually
    locked.
    """

    def __init__(self, sock_path: str):
        self.sock_path = sock_path
        self.handles = HandleTable()
        self._exports_lock = threading.Lock()
        self._exports: dict[str, object] = {}  # shm name -> mmap (lock held)
        self._exp_counter = 0
        self._dispatch_lock = threading.Lock()
        self._shutdown = threading.Event()
        self._conns_lock = threading.Lock()
        self._conns: set[socket.socket] = set()
        # cancellation registry: live CancelTokens of in-flight
        # PLAN_EXECUTEs, keyed to their query's trace_id; OP_CANCEL
        # (handled outside the dispatch lock) flips every one of them,
        # or only the given trace's when the payload names one
        self._tokens_lock = threading.Lock()
        self._active_tokens: dict[object, str] = {}
        # observability (SURVEY §5 metrics/logging): per-op counters the
        # client reads over OP_METRICS; slf4j-analog logger from utils.config
        self._metrics_lock = threading.Lock()
        self._metrics = {"ops": {}, "errors": 0, "busy_s": 0.0}
        # lazily built on the first PLAN_EXECUTE (imports the engine)
        self._plan_cache = None
        self._last_plan_stats: dict = {}
        self._last_plan_summary: dict = {}
        from ..utils.config import logger
        self._log = logger()

    # -- op implementations ------------------------------------------------
    def _op_import_table(self, payload: bytes) -> bytes:
        (nlen,) = struct.unpack_from("<I", payload, 0)
        name = payload[4:4 + nlen].decode()
        (ncols,) = struct.unpack_from("<I", payload, 4 + nlen)
        buf = shmlib.attach(name)
        try:
            cols, _ = _parse_columns(payload, 8 + nlen, ncols, buf)
        finally:
            buf.close()
        h = self.handles.put(Table(cols))
        return struct.pack("<Q", h)

    def _op_to_rows(self, payload: bytes) -> bytes:
        (h,) = struct.unpack_from("<Q", payload)
        table = self.handles.get(h)
        if not isinstance(table, Table):
            raise TypeError(f"handle {h} is not a table")
        from ..ops.row_conversion import convert_to_rows
        blobs = convert_to_rows(table)
        out = [self.handles.put(b) for b in blobs]
        return struct.pack("<I", len(out)) + b"".join(
            struct.pack("<Q", x) for x in out)

    def _op_from_rows(self, payload: bytes) -> bytes:
        h, ncols = struct.unpack_from("<QI", payload)
        col = self.handles.get(h)
        if not isinstance(col, Column):
            raise TypeError(f"handle {h} is not a column")
        schema = []
        off = 12
        for _ in range(ncols):
            tid, scale = struct.unpack_from("<ii", payload, off)
            off += 8
            schema.append(DType(TypeId(tid), scale))
        from ..ops.row_conversion import convert_from_rows
        table = convert_from_rows(col, schema)
        return struct.pack("<Q", self.handles.put(table))

    def _new_export_name(self) -> str:
        with self._exports_lock:
            self._exp_counter += 1
            n = self._exp_counter
        return f"tpub-exp-{os.getpid()}-{n}"

    def _op_export_table(self, payload: bytes, trace_id: str = "") -> bytes:
        (h,) = struct.unpack_from("<Q", payload)
        table = self.handles.get(h)
        if not isinstance(table, Table):
            raise TypeError(f"handle {h} is not a table")
        name = self._new_export_name()
        exp = shmlib.SegmentWriter(name)
        from ..utils.memory import table_nbytes
        # the device -> host fetch: where a request's last wait for the
        # device falls (`bridge.op.export_table_s` is its timer)
        with op_scope("bridge.export", bytes=table_nbytes(table),
                      trace_id=trace_id):
            descs = [_export_column_desc(exp, c) for c in table.columns]
        m = exp.finish()
        with self._exports_lock:
            self._exports[name] = m
        nameb = name.encode()
        return (struct.pack("<I", len(nameb)) + nameb +
                struct.pack("<QI", exp.size, table.num_columns) +
                b"".join(descs))

    def _op_export_column(self, payload: bytes) -> bytes:
        """Export one LIST<INT8> row-blob column (offsets + child bytes)."""
        (h,) = struct.unpack_from("<Q", payload)
        col = self.handles.get(h)
        if not isinstance(col, Column) or col.dtype.id != TypeId.LIST:
            raise TypeError(f"handle {h} is not a LIST column")
        name = self._new_export_name()
        exp = shmlib.SegmentWriter(name)
        ooff, olen = exp.add(np.asarray(col.offsets, np.int32).tobytes())
        child = col.children[0]
        doff, dlen = exp.add(np.asarray(child.data).tobytes())
        m = exp.finish()
        with self._exports_lock:
            self._exports[name] = m
        nameb = name.encode()
        return (struct.pack("<I", len(nameb)) + nameb +
                struct.pack("<QqQQQQ", exp.size, col.size,
                            ooff, olen, doff, dlen))

    def _op_free_shm(self, payload: bytes) -> bytes:
        (nlen,) = struct.unpack_from("<I", payload, 0)
        name = payload[4:4 + nlen].decode()
        with self._exports_lock:
            m = self._exports.pop(name, None)
        if m is not None:
            m.close()
        shmlib.unlink(name)
        return b""

    def _op_table_meta(self, payload: bytes) -> bytes:
        (h,) = struct.unpack_from("<Q", payload)
        table = self.handles.get(h)
        if not isinstance(table, Table):
            raise TypeError(f"handle {h} is not a table")
        out = struct.pack("<Iq", table.num_columns, table.num_rows)
        for c in table.columns:
            out += struct.pack("<ii", int(c.dtype.id), c.dtype.scale)
        return out

    # -- engine ops beyond row conversion ---------------------------------
    # (VERDICT r4 missing #1: a JVM client could row-convert and nothing
    # else; these expose the engine the way the reference's per-op JNI
    # shims expose cudf — handle in, handle out, CATCH_STD at the rim.)

    def _get_table(self, h: int) -> Table:
        t = self.handles.get(h)
        if not isinstance(t, Table):
            raise TypeError(f"handle {h} is not a table")
        return t

    def _get_col(self, h: int) -> Column:
        c = self.handles.get(h)
        if isinstance(c, Table):
            if c.num_columns != 1:
                raise TypeError(f"handle {h} is a {c.num_columns}-column "
                                "table, not a column")
            return c.columns[0]
        if not isinstance(c, Column):
            raise TypeError(f"handle {h} is not a column")
        return c

    def _op_get_column(self, payload: bytes) -> bytes:
        h, idx = struct.unpack_from("<QI", payload)
        table = self._get_table(h)
        if idx >= table.num_columns:
            raise IndexError(f"column {idx} out of range "
                             f"({table.num_columns} columns)")
        return struct.pack("<Q", self.handles.put(table.columns[idx]))

    def _op_make_table(self, payload: bytes) -> bytes:
        (n,) = struct.unpack_from("<I", payload)
        cols = [self._get_col(struct.unpack_from("<Q", payload, 4 + 8 * i)[0])
                for i in range(n)]
        return struct.pack("<Q", self.handles.put(Table(cols)))

    def _op_hash(self, payload: bytes) -> bytes:
        h, kind, seed = struct.unpack_from("<QBi", payload)
        table = self._get_table(h)
        from ..ops.hash import murmur3_hash, xxhash64
        if kind == 0:
            out = murmur3_hash(table, seed)
        elif kind == 1:
            out = xxhash64(table, seed)
        else:
            raise ValueError(f"unknown hash kind {kind}")
        return struct.pack("<Q", self.handles.put(out))

    def _op_cast_strings(self, payload: bytes) -> bytes:
        h, tid, scale, ansi, strip = struct.unpack_from("<QiiBB", payload)
        col = self._get_col(h)
        dtype = DType(TypeId(tid), scale)
        if strip:
            from ..ops.strings import trim
            col = trim(col)
        # one dispatch owner: ops.cast.cast routes every string direction
        # (integer/float/decimal/bool) with Spark semantics
        from ..ops.cast import cast
        out = cast(col, dtype, ansi=bool(ansi))
        return struct.pack("<Q", self.handles.put(out))

    def _op_groupby(self, payload: bytes) -> bytes:
        h, nk = struct.unpack_from("<QI", payload)
        off = 12
        kidx = list(struct.unpack_from(f"<{nk}I", payload, off)) if nk else []
        off += 4 * nk
        (na,) = struct.unpack_from("<I", payload, off)
        off += 4
        aggs = []
        for _ in range(na):
            ci, ac = struct.unpack_from("<IB", payload, off)
            off += 5
            if ac not in P.AGG_NAMES:
                raise ValueError(f"unknown aggregation code {ac}")
            aggs.append((int(ci), P.AGG_NAMES[ac]))
        table = self._get_table(h)
        names = [f"c{i}" for i in range(table.num_columns)]
        named = Table(list(table.columns), names)
        from ..ops.aggregate import groupby
        out = groupby(named, [names[i] for i in kidx],
                      [(names[ci] if op != "count_all" else None, op)
                       for ci, op in aggs])
        return struct.pack("<Q", self.handles.put(out))

    def _op_join(self, payload: bytes) -> bytes:
        lh, rh, how = struct.unpack_from("<QQB", payload)
        (nk,) = struct.unpack_from("<I", payload, 17)
        lidx = struct.unpack_from(f"<{nk}I", payload, 21) if nk else ()
        ridx = struct.unpack_from(f"<{nk}I", payload, 21 + 4 * nk) \
            if nk else ()
        if how not in P.JOIN_NAMES:
            raise ValueError(f"unknown join type {how}")
        left = self._get_table(lh)
        right = self._get_table(rh)
        lnames = [f"l{i}" for i in range(left.num_columns)]
        rnames = [f"r{i}" for i in range(right.num_columns)]
        from ..ops.join import sort_merge_join
        out = sort_merge_join(
            Table(list(left.columns), lnames),
            Table(list(right.columns), rnames),
            [lnames[i] for i in lidx], [rnames[i] for i in ridx],
            how=P.JOIN_NAMES[how])
        return struct.pack("<Q", self.handles.put(out))

    def _op_read_parquet(self, payload: bytes) -> bytes:
        (plen,) = struct.unpack_from("<I", payload)
        path = payload[4:4 + plen].decode()
        off = 4 + plen
        (nc,) = struct.unpack_from("<I", payload, off)
        off += 4
        cols = []
        for _ in range(nc):
            (ln,) = struct.unpack_from("<I", payload, off)
            off += 4
            cols.append(payload[off:off + ln].decode())
            off += ln
        from ..io import read_parquet
        out = read_parquet(path, columns=cols or None)
        return struct.pack("<Q", self.handles.put(out))

    def _op_sort(self, payload: bytes) -> bytes:
        h, nk = struct.unpack_from("<QI", payload)
        off = 12
        keys = []
        for _ in range(nk):
            ci, asc, nf = struct.unpack_from("<IBB", payload, off)
            off += 6
            keys.append((int(ci), bool(asc),
                         None if nf == 2 else bool(nf)))
        table = self._get_table(h)
        from ..ops.order import SortKey
        from ..ops.selection import sort_table
        out = sort_table(table, [SortKey(table.columns[ci], ascending=asc,
                                         nulls_first=nf)
                                 for ci, asc, nf in keys])
        return struct.pack("<Q", self.handles.put(out))

    def _op_filter(self, payload: bytes) -> bytes:
        h, mh = struct.unpack_from("<QQ", payload)
        table = self._get_table(h)
        mask = self._get_col(mh)
        if mask.dtype.id != TypeId.BOOL8:
            raise TypeError("filter mask must be a BOOL8 column")
        if mask.size != table.num_rows:
            raise ValueError(f"mask has {mask.size} rows, table "
                             f"{table.num_rows}")
        from ..ops.selection import apply_boolean_mask
        out = apply_boolean_mask(table, mask)  # null mask rows drop (SQL)
        return struct.pack("<Q", self.handles.put(out))

    def _op_concat(self, payload: bytes) -> bytes:
        (nt,) = struct.unpack_from("<I", payload)
        tabs = [self._get_table(struct.unpack_from("<Q", payload,
                                                   4 + 8 * i)[0])
                for i in range(nt)]
        from ..ops.selection import concat_tables
        return struct.pack("<Q", self.handles.put(concat_tables(tabs)))

    def _op_plan_execute(self, payload: bytes, trace_id: str = "") -> bytes:
        """Whole-plan dispatch: one message runs a multi-op plan DAG.

        The serve-heavy-traffic counterpart to the per-op methods above:
        instead of N round-trips the client ships one serialized logical
        plan; the server-side ``PlanCache`` optimizes it once per
        fingerprint (hits skip optimization AND reuse warm jit caches) and
        the executor runs it against local io/ops.  Result table handles
        come back in the one reply.  The whole run executes under the
        client's trace scope (``trace_id`` from the v2 frame header, or a
        server-minted one for v1 clients) so server spans, the flight
        recorder, and any post-mortem bundle all join on the client's id.

        Multi-tenant serving (engine/scheduler.py): this op runs OUTSIDE
        ``_dispatch_lock``, so N clients execute plans concurrently.  The
        path through here is, in order: (1) result-set cache — a repeat of
        a finished plan over unchanged input files serves the cached table
        without touching the scheduler or the executor; (2) SLO-aware
        admission — ``SCHEDULER.admit`` queues or sheds
        (``AdmissionRejectedError``) when ``SRJT_MAX_SESSIONS`` sessions
        are live; (3) execution with the admitted ``QuerySession`` threaded
        through ``RecoveryPolicy``, so every chunk boundary is a fair-share
        gate and OOM consults the session budget first.
        """
        (plen,) = struct.unpack_from("<I", payload)
        blob = payload[4:4 + plen]
        from ..engine import deserialize
        from ..utils import blackbox
        with blackbox.query_scope(trace_id, label="plan_execute") as scope:
            # outside `wall_s`; timed since PR 38 (`bridge.plan.decode_s`)
            with op_scope("bridge.plan.decode", timed=True,
                          bytes=plen) as sp:
                plan = deserialize(blob)
                from ..utils.config import config
                if config.verify:
                    # build-time checks up front: a bad plan (unknown
                    # column, join dtype mismatch, ...) becomes a structured
                    # error reply carrying the check code + node path
                    # (_error_body), not an executor traceback from deep
                    # inside a chunk loop
                    from ..engine import verify
                    with op_scope("bridge.plan.verify", timed=True):
                        verify(plan)
                from ..engine.plan import topo_nodes
                sp.stat(nodes=len(topo_nodes(plan)))
            if self._plan_cache is None:
                from ..engine import PlanCache
                self._plan_cache = PlanCache()
            from ..engine.cache import RESULT_CACHE, data_version
            from ..utils import metrics
            from ..utils.config import config as _cfg
            from ..utils.errors import CancelToken
            stats: dict = {}
            # per-query cancellation: registered while the plan runs so a
            # concurrent OP_CANCEL (or the SRJT_QUERY_TIMEOUT_S deadline)
            # can stop it at the next chunk boundary — keyed by trace so a
            # second connection can cancel exactly this query
            tok = CancelToken(_cfg.query_timeout_s or None)
            with self._tokens_lock:
                self._active_tokens[tok] = scope.trace_id
            try:
                # the query context opens inside `engine.plan.prepare` and
                # outlives it: the stack holds it to the end of the run
                with contextlib.ExitStack() as running:
                    # decode's end -> `engine.execute`'s start, without the
                    # wait for admission (`engine.sched.queue_wait` is its
                    # span): fingerprint, result-cache probe, plan cache
                    with op_scope("engine.plan.prepare",
                                  timed=True) as prepare:
                        fp = plan.fingerprint()
                        # plan-cache / result-cache lookups run inside the
                        # query context so their hits/misses are attributed
                        # to the query that caused them (OP_METRICS
                        # `queries`)
                        qm = running.enter_context(
                            metrics.query(f"plan:{fp[:12]}"))
                        if qm is not None:
                            qm.trace_id = scope.trace_id
                            # stamp the submitted-plan fingerprint so
                            # persisted profiles key SLO burn by plan, not
                            # "(none)" — the admission controller's shed
                            # signal depends on it
                            qm.fingerprint = fp
                            qm.source_fingerprint = fp
                        out, version = None, None
                        if RESULT_CACHE.enabled:
                            # before admission on purpose: a cache hit costs
                            # no device work, so it serves even when the
                            # scheduler would queue or shed a real execution
                            version = data_version(plan)
                            out = RESULT_CACHE.get(fp, version)
                            if out is not None:
                                stats["served_from_cache"] = True
                        if out is None:
                            # before admission too (PR 38): a lookup — once
                            # per plan shape an optimization — that needs no
                            # session.  `hit`: the shape has executed before
                            compiled = self._plan_cache.get(plan)
                            prepare.stat(hit=int(compiled.executions > 0))
                    if out is None:
                        from ..engine.scheduler import SCHEDULER
                        session = SCHEDULER.admit(
                            fingerprint=fp, trace_id=scope.trace_id)
                        try:
                            with op_scope("engine.execute", timed=True):
                                out = compiled.execute(
                                    stats=stats, cancel=tok, session=session)
                        finally:
                            session.release()
                        if RESULT_CACHE.enabled and version is not None:
                            RESULT_CACHE.put(fp, version, out)
                    if qm is not None:
                        qm.note_stats(stats)
            finally:
                with self._tokens_lock:
                    self._active_tokens.pop(tok, None)
        # N connection threads end plans at once: the pair is one plan's,
        # written and read (`_op_metrics`) under the one lock
        summary = qm.summary() if qm is not None else None
        with self._metrics_lock:
            self._last_plan_stats = stats
            if summary is not None:
                self._last_plan_summary = summary
        h = self.handles.put(out)
        return struct.pack("<I", 1) + struct.pack("<Q", h)

    def _cancel_active(self, trace_id: str = "") -> int:
        """Flip in-flight PLAN_EXECUTE tokens; returns how many.

        An empty ``trace_id`` flips every one (the v1 empty-payload
        behavior); otherwise only the tokens registered under that trace."""
        with self._tokens_lock:
            toks = [t for t, tid in self._active_tokens.items()
                    if not trace_id or tid == trace_id]
        for t in toks:
            t.cancel("cancelled via bridge OP_CANCEL")
        return len(toks)

    # -- dispatch loop -----------------------------------------------------
    def _dispatch(self, opcode: int, payload: bytes,
                  trace_id: str = "") -> bytes:
        from ..utils import faults
        faults.check("bridge.op")
        if opcode == P.OP_PING:
            return b"pong"
        if opcode == P.OP_IMPORT_TABLE:
            return self._op_import_table(payload)
        if opcode == P.OP_TO_ROWS:
            return self._op_to_rows(payload)
        if opcode == P.OP_FROM_ROWS:
            return self._op_from_rows(payload)
        if opcode == P.OP_EXPORT_TABLE:
            return self._op_export_table(payload, trace_id)
        if opcode == P.OP_EXPORT_COLUMN:
            return self._op_export_column(payload)
        if opcode == P.OP_RELEASE:
            (h,) = struct.unpack_from("<Q", payload)
            self.handles.release(h)
            return b""
        if opcode == P.OP_LIVE_COUNT:
            return struct.pack("<I", self.handles.live_count())
        if opcode == P.OP_FREE_SHM:
            return self._op_free_shm(payload)
        if opcode == P.OP_TABLE_META:
            return self._op_table_meta(payload)
        if opcode == P.OP_METRICS:
            return self._op_metrics(payload)
        if opcode == P.OP_GET_COLUMN:
            return self._op_get_column(payload)
        if opcode == P.OP_MAKE_TABLE:
            return self._op_make_table(payload)
        if opcode == P.OP_HASH:
            return self._op_hash(payload)
        if opcode == P.OP_CAST_STRINGS:
            return self._op_cast_strings(payload)
        if opcode == P.OP_GROUPBY:
            return self._op_groupby(payload)
        if opcode == P.OP_JOIN:
            return self._op_join(payload)
        if opcode == P.OP_READ_PARQUET:
            return self._op_read_parquet(payload)
        if opcode == P.OP_SORT:
            return self._op_sort(payload)
        if opcode == P.OP_FILTER:
            return self._op_filter(payload)
        if opcode == P.OP_CONCAT:
            return self._op_concat(payload)
        if opcode == P.OP_PLAN_EXECUTE:
            return self._op_plan_execute(payload, trace_id)
        raise ValueError(f"unknown opcode {opcode}")

    def _op_metrics(self, payload: bytes = b"") -> bytes:
        import json
        # optional payload = UTF-8 name prefix: narrows the counter /
        # histogram / gauge blocks so pollers that chart one family
        # (an exchange scrape, an exporter's engine.stream.* panel)
        # don't ship the whole registry.  Empty payload = everything,
        # byte-compatible with pre-prefix clients.
        prefix = payload.decode("utf-8") if payload else ""
        with self._metrics_lock:
            snap = {"ops": dict(self._metrics["ops"]),
                    "errors": self._metrics["errors"],
                    "busy_s": round(self._metrics["busy_s"], 6)}
            last_stats = self._last_plan_stats
            last_summary = self._last_plan_summary
        from ..utils.memory import runtime_memory_stats
        # None where the backend reports no allocator stats (the CPU)
        snap["device"] = {**device_info(),
                          "memory": runtime_memory_stats()}
        snap["live_handles"] = self.handles.live_count()
        with self._exports_lock:
            snap["open_exports"] = len(self._exports)
        if self._plan_cache is not None:
            snap["plan_cache"] = self._plan_cache.stats()
            snap["last_plan"] = dict(last_stats)
            if last_summary:
                snap["last_plan_summary"] = dict(last_summary)
            # serving state: who is live/queued/shed, and whether repeat
            # queries are being served from the result-set cache — only
            # populated once the engine is imported (first PLAN_EXECUTE)
            from ..engine.cache import RESULT_CACHE
            from ..engine.scheduler import SCHEDULER
            snap["scheduler"] = SCHEDULER.stats()
            snap["result_cache"] = RESULT_CACHE.stats()
        # engine-wide observability: the flat monotonic counters plus the
        # SRJT_METRICS layer (histograms as [le, count] pairs, gauges, and
        # recent per-query summaries) — all JSON-native by construction
        from ..utils import metrics, timeline, tracing
        snap["counters"] = tracing.counters_snapshot(prefix)
        snap["histograms"] = metrics.histograms_snapshot(prefix)
        snap["gauges"] = metrics.gauges_snapshot(prefix)
        snap["queries"] = metrics.recent_summaries()
        # per-device exchange attribution: the dev-suffixed gauges grouped
        # into one block JNI-side pollers can chart without name parsing
        dev_gauges = metrics.gauges_snapshot("engine.exchange.dev")
        if dev_gauges:
            snap["devices"] = {
                "exchange_rows": {k.split(".")[2][3:]: v
                                  for k, v in dev_gauges.items()
                                  if k.endswith(".rows")},
                "skew": metrics.gauges_snapshot("engine.exchange.skew")
                .get("engine.exchange.skew"),
                "straggler_share":
                    metrics.gauges_snapshot("engine.exchange.straggler")
                    .get("engine.exchange.straggler_share")}
        from ..utils import profile
        if profile.enabled():
            snap["profile_store"] = profile.store_summary()
        if timeline.enabled():
            # Chrome trace-event JSON, ready for chrome://tracing/Perfetto
            snap["timeline"] = timeline.export()
        # flight-recorder health + SLO burn (utils/blackbox.py): the SLO
        # block is the same shape prometheus_text renders as gauges, so a
        # JNI-side poller and the exporter agree by construction
        from ..utils import blackbox
        snap["blackbox"] = blackbox.ring_stats()
        if blackbox.slo_enabled():
            snap["slo"] = blackbox.slo_report()
        return json.dumps(snap).encode()

    def serve_forever(self) -> None:
        srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        # bound and LISTENING under another name, then renamed: whoever
        # finds the path can connect (a connect between `bind` and `listen`
        # is refused, and callers wait for the path to appear)
        pending = self.sock_path + "~"
        for stale in (pending, self.sock_path):
            try:
                os.unlink(stale)
            except FileNotFoundError:
                pass
        srv.bind(pending)
        srv.listen(16)
        os.rename(pending, self.sock_path)
        workers: list[threading.Thread] = []
        try:
            while not self._shutdown.is_set():
                try:
                    conn, _ = srv.accept()
                except OSError:
                    break  # socket closed by the shutdown handler
                t = threading.Thread(target=self._serve_client, args=(conn,),
                                     daemon=True)
                t.start()
                workers = [w for w in workers if w.is_alive()]
                workers.append(t)
        finally:
            srv.close()
            # unblock workers parked in recv on idle connections, then wait
            with self._conns_lock:
                for c in list(self._conns):
                    try:
                        c.shutdown(socket.SHUT_RDWR)
                    except OSError:
                        pass
            for t in workers:
                t.join(timeout=5)
            try:
                os.unlink(self.sock_path)
            except FileNotFoundError:
                pass
            with self._exports_lock:
                leftover = list(self._exports.items())
            for name, m in leftover:
                try:
                    m.close()
                    shmlib.unlink(name)
                except (BufferError, OSError) as e:
                    # a straggler worker still maps it; best-effort — but
                    # counted, so the skew telemetry can see stragglers
                    # that outlive their exchange
                    from ..utils import metrics as _metrics
                    _metrics.count("bridge.straggler_remaps")
                    self._log.debug("straggler remap of %s: %s", name, e)

    def _serve_client(self, conn: socket.socket) -> None:
        with self._conns_lock:
            self._conns.add(conn)
        try:
            self._client_loop(conn)
        finally:
            with self._conns_lock:
                self._conns.discard(conn)

    def _client_loop(self, conn: socket.socket) -> None:
        from ..utils.config import config as _cfg
        # per-op socket deadline (SRJT_BRIDGE_TIMEOUT_S): a wedged peer
        # can't park this worker thread in recv forever.  An idle timeout
        # between requests is not an error — loop and wait again.
        conn.settimeout(_cfg.bridge_timeout_s or None)
        # `bridge.conn.idle`: reply written -> the next request read, on a
        # connection that has served one — in a closed loop the client's
        # turnaround plus the socket.  It stays open across idle timeouts,
        # so it is entered and left by hand; a peer that leaves ends no
        # turnaround, and the thread ends with its last wait unobserved.
        # The wait after a metrics poll is the poller's period: no span
        idle, last_tid = None, None
        with conn:
            while not self._shutdown.is_set():
                if idle is None and last_tid is not None:
                    idle = op_scope("bridge.conn.idle", timed=True,
                                    trace_id=last_tid)
                    idle.__enter__()
                try:
                    opcode, payload, tid, span = P.recv_frame(conn)
                except socket.timeout:
                    continue  # idle connection; re-check shutdown and wait
                except ConnectionError:
                    return  # client went away; others keep running
                if idle is not None:
                    idle.__exit__(None, None, None)
                idle = None
                last_tid = tid if opcode != P.OP_METRICS else None
                # replies mirror the request's protocol version: a traced
                # (v2) request gets a traced reply echoing its ids, a v1
                # request gets a byte-identical-to-before v1 reply — old
                # clients keep working unmodified
                trace = (tid, span) if tid else None
                if opcode == P.OP_CANCEL:
                    # outside the dispatch lock, like OP_SHUTDOWN: the
                    # whole point is to interrupt a PLAN_EXECUTE that is
                    # holding that lock right now.  Payload = optional
                    # trace_id hex: empty flips everything (v1 behavior),
                    # otherwise only that trace's query.
                    n = self._cancel_active(
                        payload.decode("utf-8", "replace").strip())
                    self._log.info("OP_CANCEL flipped %d token(s)", n)
                    try:
                        P.send_msg(conn, P.STATUS_OK, struct.pack("<I", n),
                                   trace=trace)
                    except OSError:  # dead OR slow peer (send deadline)
                        return
                    continue
                if opcode == P.OP_QUERY_STATUS:
                    # outside the dispatch lock, like OP_CANCEL: the point
                    # is to observe a PLAN_EXECUTE that is holding that
                    # lock right now.  Reads only the progress registry's
                    # host-side dicts — zero device syncs added.  Payload =
                    # optional trace_id hex narrowing to that one query.
                    import json as _json
                    from ..utils import metrics as _metrics
                    queries = _metrics.progress_snapshot()
                    want = payload.decode("utf-8", "replace").strip()
                    if want:
                        queries = [q for q in queries
                                   if q.get("trace_id") == want]
                    body = _json.dumps({"queries": queries}).encode()
                    try:
                        P.send_msg(conn, P.STATUS_OK, body, trace=trace)
                    except OSError:  # dead OR slow peer (send deadline)
                        return
                    continue
                if opcode == P.OP_SHUTDOWN:
                    try:
                        P.send_msg(conn, P.STATUS_OK, trace=trace)
                    except OSError:  # dead OR slow peer (send deadline)
                        pass
                    self._shutdown.set()
                    # unblock the accept() loop
                    try:
                        poke = socket.socket(socket.AF_UNIX,
                                             socket.SOCK_STREAM)
                        poke.connect(self.sock_path)
                        poke.close()
                    except OSError:
                        pass
                    return
                # header received -> reply written, on the profiler's
                # clock and (timed) in `bridge.op.<name>_s`
                op_name = P.OP_NAMES.get(opcode, opcode)
                with op_scope(f"bridge.op.{op_name}", timed=True,
                              trace_id=tid):
                    if not self._serve_op(conn, opcode, payload, tid,
                                          trace):
                        return

    def _serve_op(self, conn: socket.socket, opcode: int, payload: bytes,
                  tid: str, trace) -> bool:
        """Dispatch one request and write its reply; False when the
        client is gone and the connection should be dropped."""
        try:
            t0 = time.perf_counter()
            if opcode == P.OP_PLAN_EXECUTE:
                # the concurrent path: plan bodies run for seconds
                # and the engine below is concurrency-safe, so N
                # sessions execute in parallel on their connection
                # threads — the scheduler (admission + fair-share
                # gates), not this lock, arbitrates between them
                out = self._dispatch(opcode, payload, tid)
            else:
                with self._dispatch_lock:
                    out = self._dispatch(opcode, payload, tid)
            with self._metrics_lock:
                ops = self._metrics["ops"]
                ops[opcode] = ops.get(opcode, 0) + 1
                self._metrics["busy_s"] += time.perf_counter() - t0
        except Exception as e:  # noqa: BLE001 — CATCH_STD analog
            with self._metrics_lock:
                self._metrics["errors"] += 1
            self._log.warning("op %d failed: %s: %s", opcode,
                              type(e).__name__, e)
            # post-mortem before replying: the executor's own
            # bundle (if any) wins via e.bundle_path; otherwise
            # this writes one for pre-executor failures (bad plan,
            # bad handle) under the client's trace
            from ..utils import blackbox
            bundle = getattr(e, "bundle_path", "") or \
                blackbox.post_mortem(f"bridge.op:{opcode}", exc=e,
                                     trace_id=tid) or ""
            status, resp = P.STATUS_ERROR, _error_body(
                e, trace_id=getattr(e, "trace_id", "") or tid,
                bundle=bundle)
        else:
            status, resp = P.STATUS_OK, out
        try:
            P.send_msg(conn, status, resp, trace=trace)
        except OSError:
            # client died mid-reply, or a slow client tripped the
            # send deadline (socket.timeout is an OSError): drop
            # this connection cleanly, keep serving others
            return False
        return True


def serve(sock_path: str) -> None:
    BridgeServer(sock_path).serve_forever()


def device_info() -> dict:
    """The devices this process computes on, as jax reports them."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main() -> None:
    ap = argparse.ArgumentParser(description="TPU bridge device server")
    ap.add_argument("--socket", required=True)
    args = ap.parse_args()
    # This process holds the accelerator (one process per chip): the
    # platform is jax's own choice unless JAX_PLATFORMS narrows it.
    from ..utils.config import enable_compile_cache
    cache_dir = enable_compile_cache()
    print(f"[bridge-server] device: {json.dumps(device_info())} "
          f"compile cache: {cache_dir}", flush=True)
    serve(args.socket)


if __name__ == "__main__":
    main()
