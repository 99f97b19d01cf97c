"""Single-transfer device staging for fixed-width column sets.

The GDS role (reference CMakeLists.txt:176-199 — cuFile exists to keep the
storage->device path off the bounce-buffer critical path).  The scan path
packs EVERY column buffer (values and validity) into ONE contiguous uint32
host buffer, ships it in a single ``device_put``, and slices/bitcasts each
column back out on device in one fused XLA program: one transfer instead
of one per buffer.  What the single transfer saves on today's machine is
not measured.

Word-level unpacking mirrors the row-conversion wire tricks
(ops/row_conversion.py): 8-byte types rebuild from u32 pairs via the same
``bitcast_convert_type`` the wire path uses (proven on TPU, where only
<=32-bit bitcasts exist), sub-word types extract lanes by shifts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtypes as dt
from ..columnar import Column, Table
from ..utils import faults
from ..utils.tracing import op_scope


def _pad4(b: bytes) -> bytes:
    r = len(b) % 4
    return b if r == 0 else b + b"\0" * (4 - r)


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


def _bucket(n: int) -> int:
    """Next power of two >= n: the staged unpack compiles once per
    (schema, row bucket), not once per exact file size — scanning many
    same-schema files of nearby sizes reuses one compiled program."""
    b = 1024
    while b < n:
        b *= 2
    return b


def _plan_for(specs):
    """The (plan, total_words) ``stage_fixed_table`` will use for these
    specs — computed WITHOUT packing, so callers can ask whether the
    unpack program is already compiled before paying for the pack."""
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
        size = np.dtype(dtype.storage).itemsize if not dtype.is_decimal \
            else dtype.itemsize
        kind = {8: "w8", 4: "w4", 2: "w2", 1: "w1"}[size]
        push(size, kind)
        if validity is not None:
            push(1, "w1")
    return tuple(plan), off


_ready_plans: set = set()
_warming: set = set()
_failed_plans: set = set()
_plans_lock = __import__("threading").Lock()


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
    import threading
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


def stage_fixed_table(specs, padded: bool = False):
    """``specs``: list of (name, dtype, values_np, validity_np_or_None) for
    fixed-width dtypes only.  One host pack, ONE device transfer, one fused
    device unpack; returns the device Table.

    Rows are padded host-side to a power-of-two bucket so the jitted
    unpack's shapes (and hence its compile) are shared across file sizes;
    outputs are sliced back to the true row count on device.

    ``padded=True`` keeps the bucket-padded form and returns
    ``(Table, n_rows)`` instead: pad rows carry zeroed values and False
    validity.  This is the chunk-pipeline form — every same-schema chunk
    shares ONE shape class, so fused plan segments (engine/segment.py)
    compile once and mask rows ``>= n_rows`` instead of slicing."""
    if any(dtype.id == dt.TypeId.DECIMAL128 for _, dtype, _, _ in specs):
        raise TypeError("DECIMAL128 staging unsupported; use the "
                        "column-at-a-time path")
    # pack + the one device_put + the unpack's dispatch; `bytes` is the
    # blob's size, known from the plan before anything is packed
    with op_scope("io.scan.stage", timed=True,
                  bytes=_plan_for(specs)[1] * 4):
        faults.check("staging.transfer")
        blob = bytearray()
        plan = []
        posts = []  # (name, dtype, has_valid, n)
        n_rows = len(specs[0][2]) if specs else 0
        bucket = _bucket(n_rows)

        def push(arr: np.ndarray, kind: str):
            arr = np.ascontiguousarray(arr)
            if len(arr) < bucket:
                arr = np.concatenate(
                    [arr, np.zeros(bucket - len(arr), arr.dtype)])
            off = len(blob) // 4
            b = _pad4(arr.tobytes())
            blob.extend(b)
            plan.append((kind, off, len(b) // 4, bucket))

        for name, dtype, values, validity in specs:
            size = np.dtype(dtype.storage).itemsize if not dtype.is_decimal \
                else dtype.itemsize
            kind = {8: "w8", 4: "w4", 2: "w2", 1: "w1"}[size]
            push(values, kind)
            if validity is not None:
                push(np.asarray(validity, np.uint8), "w1")
            posts.append((name, dtype, validity is not None, len(values)))

        words = jnp.asarray(np.frombuffer(bytes(blob), np.uint32))  # ONE put
        arrays = _unpack(words, tuple(plan))
        with _plans_lock:
            _ready_plans.add((tuple(plan), len(blob) // 4))
        cols, names = [], []
        ai = 0
        for name, dtype, has_valid, n in posts:
            data = arrays[ai] if padded else arrays[ai][:n]
            ai += 1
            storage = jnp.dtype(dtype.device_storage)
            if data.dtype != storage:
                if data.dtype.itemsize == storage.itemsize:
                    data = jax.lax.bitcast_convert_type(data, storage)
                else:
                    data = data.astype(storage)
            valid = None
            if has_valid:
                v = arrays[ai]
                valid = (v if padded else v[:n]).astype(jnp.bool_)
                ai += 1
            cols.append(Column(dtype, data=data, validity=valid))
            names.append(name)
        out = Table(cols, names)
        return (out, n_rows) if padded else out
