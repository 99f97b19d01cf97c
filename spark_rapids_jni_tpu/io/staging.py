"""Single-transfer device staging for fixed-width column sets.

The GDS role (reference CMakeLists.txt:176-199 — cuFile exists to keep the
storage->device path off the bounce-buffer critical path).  The scan path
packs EVERY column buffer (values and validity) into ONE contiguous uint32
host buffer, ships it in a single ``device_put``, and slices/bitcasts each
column back out on device in one fused XLA program: one transfer instead
of one per buffer.

The host pack writes each column once into a reused transfer buffer
(``_BlobPool``).  For a chunk of three 8-byte columns, 240,034 rows padded
to the 262,144 bucket (6.29 MB), one process alone on a v5e host
(PERF.md section 6, PR 37): pack 0.32 ms, the put's call 0.33 ms (it
returns before the transfer has read the buffer: put + unpack take 3.6 ms
to finish), the unpack's dispatch 0.39 ms, a whole call 1.66 ms — 4.40 ms
with the pack that padded by ``concatenate`` and copied through ``tobytes``,
a ``bytearray`` and ``bytes``, and 65-68 ms against 2.3-2.5 ms with four
threads at once, since three of those four copies held the interpreter's
lock.  Inside the server, next to the serve thread, the same call reads
2.9-3.6 ms (``scan_stage_ms``), 1.0 ms of it the pack.

Word-level unpacking mirrors the row-conversion wire tricks
(ops/row_conversion.py): 8-byte types rebuild from u32 pairs via the same
``bitcast_convert_type`` the wire path uses (proven on TPU, where only
<=32-bit bitcasts exist), sub-word types extract lanes by shifts.
"""

from __future__ import annotations

import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtypes as dt
from ..columnar import Column, Table
from ..utils import faults, metrics
from ..utils.config import config
from ..utils.tracing import op_scope


@functools.partial(jax.jit, static_argnums=(1,))
def _unpack(words: jnp.ndarray, plan: tuple):
    """One fused unpack of the staged u32 buffer into per-column arrays.

    ``plan``: per entry (kind, word_off, word_len, n) with kind one of
    'w8' (8-byte scalars), 'w4', 'w2', 'w1'.
    """
    outs = []
    for kind, off, wlen, n in plan:
        w = jax.lax.dynamic_slice(words, (off,), (wlen,))
        if kind == "w8":
            pairs = w.reshape(n, 2)
            outs.append(jax.lax.bitcast_convert_type(pairs, jnp.int64))
        elif kind == "w4":
            outs.append(w)
        elif kind == "w2":
            half = jnp.stack([w & jnp.uint32(0xFFFF),
                              w >> jnp.uint32(16)], axis=1)
            outs.append(half.reshape(-1)[:n].astype(jnp.uint16))
        else:  # w1
            lanes = jnp.stack([(w >> jnp.uint32(8 * j)) & jnp.uint32(0xFF)
                               for j in range(4)], axis=1)
            outs.append(lanes.reshape(-1)[:n].astype(jnp.uint8))
    return tuple(outs)


def _as_storage(a, storage: str):
    """An unpacked array in its column's device storage (``"bool"``: a
    validity plane): a bitcast where the width is the same."""
    storage = jnp.dtype(storage)
    if a.dtype == storage:
        return a
    if storage != jnp.bool_ and a.dtype.itemsize == storage.itemsize:
        return jax.lax.bitcast_convert_type(a, storage)
    return a.astype(storage)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _trim(arrays: tuple, n_rows: int, storages: tuple) -> tuple:
    """``_unpack``'s arrays cut back from the row bucket to the true row
    count, each in its storage: ONE launch for an unpadded read (a
    dimension table, once per query), apart from ``_unpack`` so that the
    unpack stays one program per (schema, row bucket)."""
    return tuple(_as_storage(a[:n_rows], st)
                 for a, st in zip(arrays, storages))


def _bucket(n: int) -> int:
    """Next power of two >= n: the staged unpack compiles once per
    (schema, row bucket), not once per exact file size — scanning many
    same-schema files of nearby sizes reuses one compiled program."""
    b = 1024
    while b < n:
        b *= 2
    return b


def _itemsize(dtype) -> int:
    return np.dtype(dtype.storage).itemsize if not dtype.is_decimal \
        else dtype.itemsize


def _plan_for(specs):
    """The (plan, total_words) ``stage_fixed_table`` uses for these specs —
    computed WITHOUT packing: the blob's size is known before a buffer is
    taken for it, and callers can ask whether the unpack program is
    already compiled before paying for the pack."""
    plan = []
    off = 0
    n_rows = len(specs[0][2]) if specs else 0
    padded = _bucket(n_rows)

    def push(itemsize, kind):
        nonlocal off
        wlen = padded * itemsize // 4 if itemsize >= 4 else \
            (padded * itemsize + 3) // 4
        plan.append((kind, off, wlen, padded))
        off += wlen

    for name, dtype, values, validity in specs:
        size = _itemsize(dtype)
        push(size, {8: "w8", 4: "w4", 2: "w2", 1: "w1"}[size])
        if validity is not None:
            push(1, "w1")
    return tuple(plan), off


_ready_plans: set = set()
_warming: set = set()
_failed_plans: set = set()
_plans_lock = threading.Lock()


def plan_ready(specs) -> bool:
    """True when the staged unpack for these specs is already compiled —
    the first-touch gate: a cold scan should not stall on a compile when
    per-column transfers can ship now."""
    plan, total = _plan_for(specs)
    with _plans_lock:
        return (plan, total) in _ready_plans


def warm_plan_async(specs) -> None:
    """Compile the staged unpack for these specs on a background thread so
    the NEXT scan of this (schema, row-bucket) takes the single-transfer
    path.  Idempotent; never blocks the caller."""
    plan, total = _plan_for(specs)
    key = (plan, total)
    with _plans_lock:
        if key in _ready_plans or key in _warming or key in _failed_plans:
            return
        _warming.add(key)

    def work():
        try:
            # Invoke the live jitted callable on a dummy buffer: this is what
            # populates jax.jit's DISPATCH cache for (shape, plan).  The
            # previous .lower().compile() built a throwaway AOT executable —
            # the next stage_fixed_table still paid the full trace+compile,
            # defeating the warm.
            out = _unpack(jnp.zeros((total,), jnp.uint32), plan)
            jax.block_until_ready(out)
            with _plans_lock:
                _ready_plans.add(key)
        except Exception as e:  # noqa: BLE001 — backend may reject the plan
            # memoize the failure: re-spawning a doomed multi-second compile
            # on every scan would burn CPU forever with zero diagnostics
            with _plans_lock:
                _failed_plans.add(key)
            from ..utils.config import logger
            logger().warning("staged unpack compile failed (%d cols); "
                             "scans stay per-column: %s: %s",
                             len(specs), type(e).__name__, e)
        finally:
            with _plans_lock:
                _warming.discard(key)

    # explicitly not a daemon (the flag is inherited, and the bridge's
    # connection threads are daemons): a process that exits mid-compile
    # waits for it instead of tearing the runtime down under a live
    # compile, which aborts
    threading.Thread(target=work, daemon=False).start()


def _pack_into(blob: np.ndarray, specs, plan) -> None:
    """Write every column of ``specs`` once into its slice of ``blob`` (the
    layout ``plan`` gives) and zero what the values do not cover: the pad
    rows up to the bucket and a sub-word tail.  Slice assignment on a typed
    view converts a strided or byte-swapped input as it copies, and numpy
    drops the interpreter's lock for copies of this size."""
    raw = blob.view(np.uint8)
    entries = iter(plan)

    def write(arr: np.ndarray, itemsize: int):
        _, off, wlen, _ = next(entries)
        if arr.dtype.itemsize != itemsize:
            raise TypeError(f"staging: {arr.dtype} values for a "
                            f"{itemsize}-byte column")
        dst = raw[off * 4:(off + wlen) * 4]
        used = len(arr) * itemsize
        dst[:used].view(arr.dtype.newbyteorder("="))[:] = arr
        dst[used:] = 0

    for _, dtype, values, validity in specs:
        write(np.asarray(values), _itemsize(dtype))
        if validity is not None:
            v = np.asarray(validity)
            write(v if v.dtype.itemsize == 1 else v.astype(np.uint8), 1)


#: most bytes the free list keeps, idle buffers and those it waits for
#: together: 3 chunks in flight per stream (one consumed, one queued, one
#: packed) x 4 streams x the 8 MiB chunk budget's 6.3 MB blob is 76 MB
POOL_MAX_BYTES = 128 << 20


class _BlobPool:
    """Process-wide free list of host transfer buffers, keyed by size.

    Process-wide and not per producer thread: every query starts a new
    producer (a new malloc arena), and what a reused buffer saves is the
    page faults of 6 MB of fresh memory per chunk.  ``jnp.asarray(host)``
    may return before the transfer has read the host memory, and on the
    CPU backend may alias it, so a buffer comes back together with the
    ``_unpack`` outputs made from it and is handed out again only once
    they all report ready — polled when the next buffer is asked for,
    never waited on.  Until then the list keeps those outputs alive: at
    most one chunk period longer than the stream does, and the last
    chunks' until the next scan."""

    def __init__(self):
        self._lock = threading.Lock()
        self._idle: list = []   # buffers nobody uses, oldest first
        self._lent: list = []   # (buffer, the device arrays made from it)
        self._bytes = 0         # of both together

    def take(self, words: int):
        """(buffer of ``words`` uint32, reused?) — the caller's alone until
        ``give``; one it never gives back is simply forgotten."""
        with self._lock:
            pending = []
            for buf, arrays in self._lent:
                # `is_ready` of a deleted (donated) array crashes, and a
                # deleted array says nothing of the transfer: forget it
                if any(a.is_deleted() for a in arrays):
                    self._bytes -= buf.nbytes
                elif all(a.is_ready() for a in arrays):
                    self._idle.append(buf)
                else:
                    pending.append((buf, arrays))
            self._lent = pending
            for i in reversed(range(len(self._idle))):
                if self._idle[i].size == words:
                    buf = self._idle.pop(i)
                    self._bytes -= buf.nbytes
                    return buf, True
        return np.empty(words, np.uint32), False

    def give(self, buf: np.ndarray, arrays: tuple) -> None:
        """Back from ``take``, with the device arrays whose readiness says
        the device is done with it.  Over the cap the idle buffers go
        first, oldest first; if that is not enough this one does."""
        with self._lock:
            while self._bytes + buf.nbytes > POOL_MAX_BYTES and self._idle:
                self._bytes -= self._idle.pop(0).nbytes
            if self._bytes + buf.nbytes <= POOL_MAX_BYTES:
                self._lent.append((buf, arrays))
                self._bytes += buf.nbytes

    def held_bytes(self) -> int:
        with self._lock:
            return self._bytes


_pool = _BlobPool()


def stage_fixed_table(specs, padded: bool = False):
    """``specs``: list of (name, dtype, values_np, validity_np_or_None) for
    fixed-width dtypes only.  One host pack, ONE device transfer, one fused
    device unpack; returns the device Table.

    Rows are padded host-side to a power-of-two bucket so the jitted
    unpack's shapes (and hence its compile) are shared across file sizes;
    outputs are cut back to the true row count on device, in one launch
    (``_trim``).

    ``padded=True`` keeps the bucket-padded form and returns
    ``(Table, n_rows)`` instead: pad rows carry zeroed values and False
    validity.  This is the chunk-pipeline form — every same-schema chunk
    shares ONE shape class, so fused plan segments (engine/segment.py)
    compile once and mask rows ``>= n_rows`` instead of slicing."""
    if any(dtype.id == dt.TypeId.DECIMAL128 for _, dtype, _, _ in specs):
        raise TypeError("DECIMAL128 staging unsupported; use the "
                        "column-at-a-time path")
    plan, total_words = _plan_for(specs)
    n_rows = len(specs[0][2]) if specs else 0
    blob, reused = _pool.take(total_words)
    if reused:
        metrics.count("io.scan.stage.reused")
    else:
        metrics.count("io.scan.stage.fresh")
    # pack + the one device_put + the unpack's dispatch
    with op_scope("io.scan.stage", timed=True, bytes=total_words * 4,
                  reused=int(reused)):
        faults.check("staging.transfer")
        # pure host copying, so `pack_s`'s sum - cpu_sum is the producer
        # standing in line for the interpreter.  Observed by hand, not a
        # span: a millisecond-long annotation on this thread took the
        # consumer's launches from `engine.fused_segment` in the
        # benchmark's thread-blind naming (PERF.md section 6, PR 38)
        t0 = time.perf_counter()
        c0 = time.thread_time() if config.trace else None  # as a timed span
        _pack_into(blob, specs, plan)
        cpu = None if c0 is None else time.thread_time() - c0
        metrics.observe("io.scan.stage.pack_s", time.perf_counter() - t0,
                        cpu)
        words = jnp.asarray(blob)  # ONE put
        arrays = _unpack(words, plan)
        _pool.give(blob, arrays)
        with _plans_lock:
            _ready_plans.add((plan, total_words))
        # per unpacked array, the storage it is handed on in
        storages = []
        for _, dtype, _, validity in specs:
            storages.append(jnp.dtype(dtype.device_storage).name)
            if validity is not None:
                storages.append("bool")
        if padded:
            arrays = [_as_storage(a, st) for a, st in zip(arrays, storages)]
        else:   # one launch for the table, not three eager ones a column
            arrays = _trim(arrays, n_rows, tuple(storages))
        cols, names = [], []
        ai = 0
        for name, dtype, _, validity in specs:
            data, valid = arrays[ai], None
            ai += 1
            if validity is not None:
                valid = arrays[ai]
                ai += 1
            cols.append(Column(dtype, data=data, validity=valid))
            names.append(name)
        out = Table(cols, names)
        return (out, n_rows) if padded else out
