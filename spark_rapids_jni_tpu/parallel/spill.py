"""Spill-capable shuffle: exchanges larger than HBM, in bounded passes.

The storage half of the GDS role (reference CMakeLists.txt:176-199 builds
cufilejni so SPILL and shuffle files move storage<->device without bounce
buffers): when the two-phase counts say the received payload would blow an
HBM budget, the exchange runs as MULTIPLE passes over within-destination
rank windows.  Each pass is the ordinary jitted shuffle program
(parallel/shuffle.py) at a small per-pass capacity with a row mask
selecting its window — dead rows are never sent — and each pass's received
rows leave the device immediately: into host arrays, or numpy memmaps
under ``spill_dir`` when even host RAM is too small.  Row identity and
order are deterministic (pass-major, then destination order), so
downstream consumers can stream chunk-at-a-time (the Spark shuffle-file
reader pattern) or materialize.

Fixed-width columns only (the wire planes the exchange moves); STRING
columns should be dictionary-encoded (ops/dictionary) or exploded
(parallel/stringplane) by the caller — at spill scale a padded-bucket
string plane is exactly the buffer you do not want twice in memory.
"""

from __future__ import annotations

import functools
import itertools
import os
import weakref

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from ..columnar import Column, Table
from ..ops.row_conversion import fixed_width_layout, _from_planes
from .mesh import ROW_AXIS, axis_size
from .shuffle import (cap_bucket, key_specs_for, make_shuffle,
                      partition_counts, _spec_columns, partition_ids_specs)
from ..utils import faults, metrics, timeline
from ..utils.errors import retry_call
from ..utils.tracing import traced


@functools.lru_cache(maxsize=32)
def make_dest_ranks(mesh: Mesh, key_specs: tuple, axis: str = ROW_AXIS):
    """Per-shard program: (datas, masks, n_valid) -> (rank within dest,
    live mask).

    One stable 2-operand sort per shard, same formulation as the bucket
    pack; computed ONCE so every spill pass reuses the ranks instead of
    re-sorting.  Rows at global index >= n_valid are pad rows
    (pad_to_multiple): they get live=False and never enter a pass window.
    """
    ndev = axis_size(mesh, axis)

    def shard_fn(datas, masks, n_valid):
        cols = _spec_columns(key_specs, datas, masks)
        dest = partition_ids_specs(cols, key_specs, ndev)
        n = dest.shape[0]
        shard_idx = jax.lax.axis_index(axis).astype(jnp.int64)
        gid = shard_idx * n + jnp.arange(n, dtype=jnp.int64)
        live = gid < n_valid
        dest = jnp.where(live, dest, jnp.int32(ndev))  # pads rank last
        idx = jnp.arange(n, dtype=jnp.int32)
        sd, si = jax.lax.sort((dest, idx), num_keys=1, is_stable=True)
        first = jnp.concatenate([jnp.ones((1,), jnp.bool_), sd[1:] != sd[:-1]])
        run_start = jax.lax.cummax(jnp.where(first, idx, jnp.int32(-1)))
        srank = idx - run_start
        _, rank = jax.lax.sort((si, srank), num_keys=1, is_stable=True)
        return rank, live

    spec = P(axis)
    return jax.jit(shard_map(shard_fn, mesh=mesh, in_specs=(spec, spec, P()),
                             out_specs=(spec, spec), check_vma=False))


_SPILL_SEQ = itertools.count(1)


def _unlink_quiet(path):
    try:
        os.unlink(path)
    except OSError:
        pass


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True  # exists, owned by someone else
    return True


def sweep_orphans(spill_dir: str) -> int:
    """Unlink spill files left by dead processes; returns the count.

    The happy path reclaims via ``weakref.finalize`` on the memmap, but a
    crashed query never runs its finalizers — its ``spill-<pid>-...npy``
    files survive in ``spill_dir`` forever.  Names carry the owning pid,
    so liveness is one ``kill(pid, 0)`` probe; our own files and those of
    live processes are never touched.
    """
    try:
        names = os.listdir(spill_dir)
    except OSError:
        return 0
    me = os.getpid()
    reaped = 0
    for name in names:
        if not (name.startswith("spill-") and name.endswith(".npy")):
            continue
        parts = name.split("-")
        try:
            pid = int(parts[1])
        except (IndexError, ValueError):
            continue
        if pid == me or _pid_alive(pid):
            continue
        path = os.path.join(spill_dir, name)
        try:
            os.unlink(path)
            reaped += 1
        except OSError:
            continue
    if reaped:
        metrics.count("parallel.spill.orphans_reaped", reaped)
        from ..utils.config import logger
        logger().warning("reaped %d orphaned spill file(s) in %s",
                         reaped, spill_dir)
    return reaped


def _spill_buffers(schema, total_rows, spill_dir):
    """Per-column output buffers: RAM numpy, or memmaps under spill_dir."""
    from ..dtypes import TypeId
    datas, valids = [], []
    for i, dtp in enumerate(schema):
        npdt = np.dtype(dtp.device_storage)
        shape = (total_rows, 2) if dtp.id == TypeId.DECIMAL128 \
            else (total_rows,)
        if spill_dir is None:
            datas.append(np.empty(shape, npdt))
        else:
            # unique per call: a fixed name would silently overwrite the
            # buffers backing a still-live earlier spill result
            mm = np.lib.format.open_memmap(
                os.path.join(spill_dir,
                             f"spill-{os.getpid()}-{next(_SPILL_SEQ)}"
                             f"-col{i}.npy"),
                mode="w+", dtype=npdt, shape=shape)
            # reclaim disk when the buffer dies: unlink-while-mapped is
            # safe on POSIX (views keep the base memmap alive)
            weakref.finalize(mm, _unlink_quiet, mm.filename)
            datas.append(mm)
        valids.append(np.ones(total_rows, np.bool_))
    return datas, valids


@traced("shuffle_table_spilled")
def shuffle_table_spilled(table: Table, mesh: Mesh, keys: list,
                          hbm_budget_bytes: int,
                          spill_dir: str | None = None,
                          axis: str = ROW_AXIS,
                          key_specs: tuple | None = None):
    """Shuffle by key hash with the device working set bounded by
    ``hbm_budget_bytes``; returns a HOST-resident compacted Table (numpy
    buffers, or memmaps under ``spill_dir``, unlinked automatically when
    the result is garbage-collected).

    Row placement is identical to ``shuffle_table_padded`` (Spark
    HashPartitioning); output rows appear pass-major, destination-shard
    order within a pass — deterministic, so streamed consumers can
    re-group.  The result's buffers are HOST arrays (jnp lifts them back
    to the device lazily if an op touches them — re-loading spilled data
    is the consumer's explicit choice, as with Spark shuffle files).
    """
    if any(c.dtype.is_string for c in table.columns):
        raise TypeError(
            "spilled shuffle is fixed-width only; dictionary-encode "
            "(ops/dictionary) or explode (parallel/stringplane) first")
    from .mesh import pad_to_multiple, shard_table
    if spill_dir is not None:
        sweep_orphans(spill_dir)
    ndev = axis_size(mesh, axis)
    n_valid = table.num_rows
    if table.num_rows % ndev:
        # pad internally with masked null rows (never sent, never output)
        table, n_valid = pad_to_multiple(table, ndev)
    st = shard_table(table, mesh, axis)
    layout = fixed_width_layout(st.dtypes())
    if key_specs is None:
        key_specs = key_specs_for(st, keys, None)

    counts = partition_counts(st, mesh, list(keys), axis,
                              n_valid_rows=n_valid, key_specs=key_specs)
    max_cap = int(counts.max())          # the one-shot capacity
    row_bytes = layout.row_size
    # per-pass capacity from the budget: a pass holds the received block
    # (ndev*ndev*cap*row_bytes of planes) plus the send block of the same
    # size in flight
    budget_rows = max(32, int(hbm_budget_bytes // (2 * ndev * ndev *
                                                   row_bytes)))
    # round DOWN to a power of two: rounding up could double the pass's
    # device block and bust the budget — the one thing this path promises
    cap_slice = 1 << (budget_rows.bit_length() - 1)
    cap_slice = min(cap_slice, cap_bucket(max(max_cap, 1)))
    npasses = max(1, -(-max_cap // cap_slice))

    ranks_fn = make_dest_ranks(mesh, key_specs, axis)
    datas = tuple(c.data for c in st.columns)
    masks = tuple(c.validity for c in st.columns)
    rank, live = ranks_fn(datas, masks, jnp.int64(n_valid))

    total = int(np.asarray(counts).sum())
    out_datas, out_valids = _spill_buffers(st.dtypes(), total, spill_dir)
    buffer_bytes = sum(d.nbytes for d in out_datas) + \
        sum(v.nbytes for v in out_valids)
    metrics.count("parallel.spill.spills")
    metrics.count("parallel.spill.passes", npasses)
    metrics.gauge_max("parallel.spill.buffer_bytes", buffer_bytes)
    metrics.observe("parallel.spill.pass_capacity_rows", cap_slice)
    fn = make_shuffle(mesh, layout, key_specs, cap_slice, axis)
    written = 0

    def run_pass(p, window):
        # writes land at offsets fixed by the pre-pass ``written``, so a
        # transient failure replays the whole pass idempotently
        faults.check("spill.write")
        planes_in, ok, ovf = fn(datas, masks, window)
        if int(ovf):
            raise RuntimeError(
                f"spill pass {p} overflow ({int(ovf)} rows)"
                " — counts pass disagrees with payload")
        d_in, m_in = _from_planes(layout, list(planes_in))
        okn = np.asarray(ok)
        keep = np.flatnonzero(okn)
        nlive = keep.shape[0]
        for ci, (d, m) in enumerate(zip(d_in, m_in)):
            dn = np.asarray(d)
            out_datas[ci][written:written + nlive] = dn[keep] if \
                dn.ndim == 1 else dn[keep].reshape(nlive, *dn.shape[1:])
            out_valids[ci][written:written + nlive] = \
                np.asarray(m)[keep]
        return nlive

    for p in range(npasses):
        lo, hi = p * cap_slice, (p + 1) * cap_slice
        window = (rank >= lo) & (rank < hi) & live
        with timeline.span("parallel.spill.pass",
                           {"pass": p, "capacity": int(cap_slice)}):
            nlive = retry_call(lambda: run_pass(p, window), "spill.write")
            written += nlive
            metrics.count("parallel.spill.bytes_spilled",
                          nlive * (row_bytes + len(out_valids)))
        metrics.mem_checkpoint()
    assert written == total, (written, total)

    cols = []
    for dtp, d, v in zip(st.dtypes(), out_datas, out_valids):
        cols.append(Column(dtp, data=d,  # host-resident: that's the point
                           validity=None if v.all() else v))
    return Table(cols, st.names)
