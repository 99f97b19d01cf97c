"""Hash-partition shuffle: row blobs over ICI all-to-all.

The heart of the exchange layer (BASELINE.json north_star: "hash-partition
shuffle ... as ICI all-to-all across a pod").  Architecture mirrors the
reference's split of labor — RowConversion packs rows, the shuffle moves
them (RowConversion.java:28-31 documents row blobs as the hand-off format to
Spark's shuffle) — except both halves now live in one jitted XLA program:

    per shard:  dest = pmod(murmur3(keys), ndev)          (Spark partitioning)
                word planes (ops/row_conversion._build_planes)
                sort-based bucket pack into (nw, ndev, capacity) planes
    exchange:   one dense lax.all_to_all block over the mesh axis (ICI)
    per shard:  received padded word planes + row mask (+ overflow count)

Static shapes everywhere: each source shard may send at most ``capacity``
rows to each destination.  Capacity comes from a TWO-PHASE exchange (SURVEY
§7 hard part #3): phase 1 is a counts-only pass (hash + bincount + an
ndev-vector all_gather), phase 2 the payload all_to_all compiled at the
counts-derived capacity (power-of-two bucketed so compiled programs are
reused).  Overflow is still counted as a defense-in-depth invariant, but
with counts-based sizing it is structurally zero.  (The reference's analog
of this bound: the 2^31-byte batch ceiling it splits output to —
row_conversion.cu:476-511 — except ours is measured, not guessed.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from ..columnar import Column, Table
from ..ops.hash import murmur3_hash
from ..ops.row_conversion import (RowLayout, _build_planes,
                                  _from_planes)
from .mesh import ROW_AXIS, axis_size
from .stringplane import explode_strings, reassemble_strings
from ..utils import faults, metrics, timeline
from ..utils.tracing import op_scope, traced


def partition_ids(key_table: Table, num_partitions: int) -> jnp.ndarray:
    """Spark HashPartitioning: pmod(murmur3_hash(keys, 42), n)."""
    h = murmur3_hash(key_table).data  # int32
    m = h % jnp.int32(num_partitions)
    return jnp.where(m < 0, m + jnp.int32(num_partitions), m)


def partition_ids_specs(cols, key_specs, num_partitions: int) -> jnp.ndarray:
    """Spark HashPartitioning over possibly-EXPLODED key columns.

    ``key_specs`` (static, per original key): ("fixed", idx, dtype) or
    ("string", len_idx, (word_idx, ...)) into ``cols``.  String keys hash
    their UTF-8 bytes (Spark UTF8String murmur3) reconstructed from the
    exploded (length, words) group — wire-exact partition placement, the
    interop half of keeping the row-blob format bit-exact
    (RowConversion.java:28-48).
    """
    from ..ops.hash import murmur3_hash_specs
    hs = tuple(("fixed", s[1]) if s[0] == "fixed" else s for s in key_specs)
    h = jax.lax.bitcast_convert_type(
        murmur3_hash_specs(cols, hs), jnp.int32)
    m = h % jnp.int32(num_partitions)
    return jnp.where(m < 0, m + jnp.int32(num_partitions), m)


def key_specs_for(table: Table, keys, plan) -> tuple:
    """Static key specs for ``partition_ids_specs`` over a possibly-exploded
    table: ``keys`` are the ORIGINAL key names (or indices when nothing was
    exploded), ``plan`` the StringPlan (or None)."""
    from .stringplane import LEN_SUFFIX, WORD_SUFFIX
    spec_of = dict(zip(plan.names, plan.specs)) if plan is not None else {}
    names = list(table.names or [f"c{i}" for i in range(table.num_columns)])
    out = []
    for k in keys:
        s = spec_of.get(k, ("fixed",)) if isinstance(k, str) else ("fixed",)
        if s[0] == "string":
            li = names.index(f"{k}{LEN_SUFFIX}")
            out.append(("string", li,
                        tuple(names.index(f"{k}{WORD_SUFFIX}{i}")
                              for i in range(s[1]))))
        else:
            i = names.index(k) if isinstance(k, str) else int(k)
            out.append(("fixed", i, table.columns[i].dtype))
    return tuple(out)


def _spec_columns(key_specs, datas, masks):
    """Columns referenced by ``key_specs``, built from raw shard buffers
    (positions not referenced stay None)."""
    from ..dtypes import INT32 as _I32DT, UINT32 as _U32DT
    cols = [None] * len(datas)

    def put(i, dtype):
        if cols[i] is None:
            cols[i] = Column(dtype, data=datas[i],
                             validity=None if masks[i] is None else masks[i])

    for s in key_specs:
        if s[0] == "fixed":
            put(s[1], s[2])
        else:
            put(s[1], _I32DT)
            for i in s[2]:
                put(i, _U32DT)
    return cols


def _bucket_pack_planes(planes, dest: jnp.ndarray, row_mask, ndev: int,
                        capacity: int):
    """Scatter-free bucket pack: rows into per-destination slots.

    Sort-carried rather than scatter-based (docs/PERF.md: TPU scatters
    serialize), but the payload planes are never sorted: ONE stable
    2-operand sort of (dest, row-index) groups the row *indices* by
    destination, a one-hot reduction counts rows per destination, and the
    (ndev, capacity) send grid fills by GATHER — slot (d, r) reads sorted
    position start[d] + r.  Each u32 plane moves exactly once (the gather)
    instead of riding two (nw+2)-operand sorts of n + ndev*capacity
    elements, which dominated the exchange cost.

    ``planes`` is the word-major row decomposition (nw dense u32[n]
    vectors — never the lane-padded (n, nw) matrix).  Returns (send_planes
    [(ndev, capacity) u32 per word], ok (ndev, capacity) bool, overflow
    scalar = live rows that didn't fit their destination bucket).
    """
    n = dest.shape[0]
    if n == 0:
        ok = jnp.zeros((ndev, capacity), jnp.bool_)
        send = [jnp.zeros((ndev, capacity), p.dtype) for p in planes]
        return send, ok, jnp.int32(0)
    if row_mask is not None:
        dest = jnp.where(row_mask, dest, jnp.int32(ndev))
    idx = jnp.arange(n, dtype=jnp.int32)
    sd, si = jax.lax.sort((dest, idx), num_keys=1, is_stable=True)
    # rows per destination from the sorted runs: ndev binary-search queries
    # over sd (ndev-independent in n — a one-hot reduction would be
    # Theta(ndev*n) at pod scale, a bincount scatter-add would serialize
    # on TPU)
    d = jnp.arange(ndev, dtype=jnp.int32)
    start = jnp.searchsorted(sd, d, side="left").astype(jnp.int32)
    cnt = jnp.searchsorted(sd, d, side="right").astype(jnp.int32) - start
    r = jnp.arange(capacity, dtype=jnp.int32)[None, :]
    src = start[:, None] + r                       # (ndev, capacity)
    ok = r < jnp.minimum(cnt, capacity)[:, None]
    rows = jnp.take(si, jnp.clip(src, 0, max(n - 1, 0)).reshape(-1))
    okf = ok.reshape(-1)
    send = [jnp.where(okf, jnp.take(p, rows), jnp.zeros((), p.dtype))
            .reshape(ndev, capacity) for p in planes]
    overflow = jnp.sum(jnp.maximum(cnt - capacity, 0))
    return send, ok, overflow


def device_load_stats(dest_rows) -> dict:
    """Skew/straggler attribution from per-destination row counts.

    ``dest_rows`` is any sequence of rows landing on each device (one
    entry per device).  Skew is max/mean destination load — 1.0 is a
    perfectly balanced exchange, ndev is everything-on-one-device; the
    straggler share (max - mean)/max is the fraction of the slowest
    device's work the mesh sits idle for (the all_to_all completes at the
    pace of its fullest destination).  Shared by the shuffle counts pass
    and the executor's Exchange attribution so both report identically.
    """
    import numpy as np
    rows = np.asarray(dest_rows, dtype=np.int64).reshape(-1)
    ndev = max(1, rows.size)
    total = int(rows.sum()) if rows.size else 0
    mean = total / ndev
    mx = int(rows.max()) if rows.size else 0
    skew = (mx / mean) if mean > 0 else 1.0
    straggler = ((mx - mean) / mx) if mx > 0 else 0.0
    return {"dev_rows": [int(r) for r in rows],
            "total_rows": total,
            "max_dev_rows": mx,
            "mean_dev_rows": round(mean, 3),
            "skew": round(float(skew), 6),
            "straggler_share": round(float(straggler), 6)}


def cap_bucket(count: int) -> int:
    """Round a counts-derived capacity up to a power-of-two bucket (>=32).

    Buckets bound the number of distinct compiled programs the two-phase
    exchange can create (capacity is a static shape).
    """
    cap = 32
    while cap < count:
        cap *= 2
    return cap


def cap_bucket_fine(count: int) -> int:
    """Round up to a quarter-power-of-two bucket (1, 1.25, 1.5, 1.75 x 2^k).

    For the big data-dependent capacities (join pair counts) the 2x
    worst-case padding of ``cap_bucket`` is real sort work; quarter buckets
    cap padding waste at 25% for at most 4x the distinct compiled programs.
    """
    cap = 32
    while cap < count:
        cap *= 2
    if cap >= 128:
        for frac in (4, 5, 6, 7):
            fine = cap // 8 * frac
            if fine >= count:
                return fine
    return cap


@functools.lru_cache(maxsize=64)
def make_partition_counts(mesh: Mesh, key_specs: tuple,
                          axis: str = ROW_AXIS, masked: bool = False):
    """Phase 1 of the two-phase exchange: per-(src, dest) row counts.

    SURVEY.md §7 hard part #3 (ragged all-to-all with static shapes): rather
    than guessing a capacity and retrying on overflow, a cheap counts pass
    (hash + bincount + all_gather of an ndev-vector — no payload movement)
    sizes the payload exchange exactly.  ``key_specs`` comes from
    ``key_specs_for`` (Spark-exact hashing incl. exploded string keys).
    Returns fn(datas, masks[, n_valid]) -> int32[ndev, ndev] with row s =
    counts shard s sends to each dest.
    """
    # the body runs on an lru_cache miss only: one build of a program
    metrics.count("engine.exchange.program_build")
    ndev = axis_size(mesh, axis)

    def shard_fn(datas, masks, n_valid=None):
        cols = _spec_columns(key_specs, datas, masks)
        dest = partition_ids_specs(cols, key_specs, ndev)
        if n_valid is not None:
            n_local = dest.shape[0]
            shard_idx = jax.lax.axis_index(axis).astype(jnp.int64)
            gid = shard_idx * n_local + jnp.arange(n_local, dtype=jnp.int64)
            dest = jnp.where(gid < n_valid, dest, jnp.int32(ndev))
        counts = jnp.zeros((ndev,), jnp.int32).at[dest].add(1, mode="drop")
        return counts[None]

    spec = P(axis)
    if masked:
        return jax.jit(shard_map(
            shard_fn, mesh=mesh, in_specs=(spec, spec, P()),
            out_specs=spec, check_vma=False))
    return jax.jit(shard_map(
        lambda d, m: shard_fn(d, m), mesh=mesh,
        in_specs=(spec, spec), out_specs=spec, check_vma=False))


def partition_counts(table: Table, mesh: Mesh, keys: list,
                     axis: str = ROW_AXIS, n_valid_rows=None,
                     key_specs: tuple | None = None):
    """Host wrapper over ``make_partition_counts`` for a sharded table.

    The returned array has reached the host — a deliberate sync the engine
    Exchange paths label via ``metrics.host_sync("exchange-counts-sizing")``
    at THEIR call sites (here would also tag the distributed.py/spill.py
    callers, whose syncs ``verify.sync_budget`` does not model).
    """
    import numpy as np
    if key_specs is None:
        key_specs = key_specs_for(table, keys, None)
    fn = make_partition_counts(mesh, key_specs, axis,
                               masked=n_valid_rows is not None)
    datas = tuple(c.data for c in table.columns)
    masks = tuple(c.validity for c in table.columns)
    out = fn(datas, masks, jnp.int64(n_valid_rows)) \
        if n_valid_rows is not None else fn(datas, masks)
    return np.asarray(out)


def exchange_planes(planes, dest, row_mask, ndev: int, capacity: int,
                    axis: str):
    """Bucket-pack word planes and move them over ICI as ONE dense block.

    The single exchange primitive shared by the raw shuffle and the
    distributed groupby/join plans: pack -> stack (nw, ndev, cap) ->
    all_to_all(split/concat axis 1) -> per-word receive planes.  Returns
    (planes_in tuple of u32[ndev*capacity], row mask, overflow scalar).
    """
    send, ok, overflow = _bucket_pack_planes(planes, dest, row_mask, ndev,
                                             capacity)
    block = jnp.stack(send, axis=0)
    recv = jax.lax.all_to_all(block, axis, 1, 1)
    rok = jax.lax.all_to_all(ok, axis, 0, 0)
    planes_in = tuple(recv[w].reshape(ndev * capacity)
                      for w in range(len(planes)))
    return planes_in, rok.reshape(ndev * capacity), overflow


@functools.lru_cache(maxsize=64)
def make_shuffle(mesh: Mesh, layout: RowLayout, key_specs: tuple,
                 capacity: int, axis: str = ROW_AXIS,
                 donate: bool = False, split: tuple | None = None):
    """Build the jitted shard_map shuffle for a fixed schema.

    Returns fn(datas, masks, row_mask) -> (planes_in, ok, overflow): the
    received word planes (tuple of u32[ndev*capacity] per row word — feed
    ``_from_planes``), the live-row mask, and the global overflow count.
    ``key_specs`` from ``key_specs_for`` — string keys partition by Spark
    UTF8String murmur3 over their exploded words.

    ``donate=True`` donates the input buffers to XLA (donate_argnums — the
    async-dispatch/donation half of the reference's per-thread-stream
    overlap, SURVEY §2.3 "PP"): the send buffers reuse the table's HBM, so
    a shuffle's working set is ~1x instead of 2x.  Callers must not touch
    the donated table afterwards.

    ``split`` = ``(hot_dests, salt)`` is the AQE skew-split secondary
    assignment (engine/adaptive.py): rows hashed to a hot destination are
    re-dealt round-robin across ALL devices by a salted per-shard running
    index.  The deal bounds each destination's share of a shard's hot rows
    at ceil(hot/ndev) — unlike a salted re-hash, an adversarial single-key
    distribution cannot overflow a counts-projected capacity.  Placement
    stops being key-deterministic for hot rows, so only consumers that
    merge the full exchange output (or re-combine per key afterwards) may
    ask for it.  Static (part of the compile cache key), like capacity.
    """
    metrics.count("engine.exchange.program_build")  # a miss, as above
    ndev = axis_size(mesh, axis)

    def shard_fn(datas, masks, row_mask):
        cols = _spec_columns(key_specs, datas, masks)
        dest = partition_ids_specs(cols, key_specs, ndev)
        if split is not None:
            hot, salt = split
            is_hot = dest == jnp.int32(hot[0])
            for h in hot[1:]:
                is_hot = is_hot | (dest == jnp.int32(h))
            if row_mask is not None:
                # dead (pad) rows must not advance the deal: the capacity
                # projection counted live rows only
                is_hot = is_hot & row_mask
            # stagger the deal start by source shard: shards with few hot
            # rows would otherwise ALL open at dest salt and re-concentrate
            # what the split is meant to spread.  The per-(src, dest) bound
            # is rotation-invariant — still at most ceil(hot_s / ndev)
            shard_idx = jax.lax.axis_index(axis).astype(jnp.int32)
            hot_idx = jnp.cumsum(is_hot.astype(jnp.int32)) - 1
            dest = jnp.where(
                is_hot,
                (jnp.int32(salt) + shard_idx + hot_idx) % jnp.int32(ndev),
                dest)
        planes = _build_planes(layout, datas, masks)
        planes_in, rok, overflow = exchange_planes(planes, dest, row_mask,
                                                   ndev, capacity, axis)
        return planes_in, rok, jax.lax.psum(overflow, axis)

    spec = P(axis)
    return jax.jit(shard_map(
        shard_fn, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=(spec, spec, P()),
        check_vma=False,
    ), donate_argnums=(0, 1) if donate else ())


@traced("shuffle_table_padded")
def shuffle_table_padded(table: Table, mesh: Mesh, keys: list,
                         capacity: int | None = None,
                         axis: str = ROW_AXIS, donate: bool = False,
                         live=None, key_specs: tuple | None = None,
                         split: tuple | None = None):
    """Shuffle a row-sharded table by key hash.

    Returns (padded Table [ndev * ndev * capacity global rows], row mask
    Column-less bool array, overflow scalar).  Rows land on the partition
    owning pmod(murmur3(keys), ndev); padding rows have mask False.

    ``live``: optional bool row mask — dead rows (e.g. pad_to_multiple
    padding) are never sent.

    STRING columns (keys or payloads) cross the exchange in padded-bucket
    form (stringplane): exploded to fixed-width, shuffled inside the row
    blobs, reassembled on the way out.  String-key partitioning is Spark's
    UTF8String murmur3 over the original bytes (reconstructed on device
    from the exploded words — ``partition_ids_specs``), so partition
    placement interoperates with Spark's HashPartitioning wire-exactly.

    ``key_specs``: pre-computed ``key_specs_for`` result for callers whose
    table is ALREADY exploded (the engine exchange explodes once globally
    so every chunk shares one layout) — overrides the local computation so
    string keys still hash Spark-exactly.

    ``split``: AQE skew-split ``(hot_dests, salt)`` — see ``make_shuffle``.
    The internal counts pass sizes capacity for the UNSPLIT placement, so
    splitting callers must pass the projected capacity explicitly.
    """
    from ..ops.row_conversion import fixed_width_layout
    if split is not None and capacity is None:
        raise ValueError("split requires an explicitly projected capacity")
    plan = None
    if any(c.dtype.is_string for c in table.columns):
        names0 = table.names or [f"c{i}" for i in range(table.num_columns)]
        keys = [k if isinstance(k, str) else names0[int(k)] for k in keys]
        table, plan = explode_strings(table)
        from .mesh import shard_table
        table = shard_table(table, mesh, axis)  # strings couldn't shard before
    layout = fixed_width_layout(table.dtypes())
    ndev = axis_size(mesh, axis)
    if key_specs is None:
        key_specs = key_specs_for(table, keys, plan)
    if capacity is None:
        # two-phase exchange: counts pass sizes the payload pass exactly.
        # The counts fetch is a DELIBERATE host sync (they must reach the
        # host to become phase 2's static capacity) — whitelisted in
        # engine/verify.SYNC_WHITELIST; the AST lint holds the label honest
        metrics.host_sync(label="exchange-counts-sizing")
        with op_scope("engine.sync_wait", timed=True,
                      label="exchange-counts-sizing"):
            counts_mat = partition_counts(table, mesh, list(keys), axis,
                                          key_specs=key_specs)
        capacity = cap_bucket(int(counts_mat.max()))
        if metrics.enabled():
            # the counts matrix is already on host — per-device skew
            # attribution costs nothing extra (no added syncs)
            st = device_load_stats(counts_mat.sum(axis=0))
            metrics.gauge_set("parallel.shuffle.skew", st["skew"])
            metrics.gauge_set("parallel.shuffle.max_dev_rows",
                              st["max_dev_rows"])
            for r in st["dev_rows"]:
                metrics.observe("parallel.shuffle.dev_rows", r)
    fn = make_shuffle(mesh, layout, key_specs, capacity, axis, donate,
                      split)
    # exchange observability: every slot of the padded all_to_all crosses
    # the interconnect whether live or not, so slots x row_size IS the
    # wire traffic
    metrics.count("parallel.shuffle.exchanges")
    metrics.count("parallel.shuffle.exchange_bytes",
                  ndev * ndev * capacity * layout.row_size)
    metrics.observe("parallel.shuffle.capacity_rows", capacity)
    datas = tuple(c.data for c in table.columns)
    masks = tuple(c.validity for c in table.columns)
    with timeline.span("parallel.shuffle.exchange",
                       {"capacity": int(capacity),
                        "wire_bytes": int(ndev * ndev * capacity *
                                          layout.row_size)}):
        planes_in, ok, overflow = fn(datas, masks, live)
    datas_out, masks_out = _from_planes(layout, list(planes_in))
    cols = [Column(dt, data=d, validity=m)
            for dt, d, m in zip(layout.schema, datas_out, masks_out)]
    out = Table(cols, table.names)
    if plan is not None:
        out = reassemble_strings(out, plan)
    return out, ok, overflow


def shuffle_chunks_pipelined(chunks, mesh: Mesh, keys: list,
                             capacity: int | None = None, depth: int = 1,
                             axis: str = ROW_AXIS, donate: bool = False,
                             key_specs: tuple | None = None,
                             split: tuple | None = None):
    """Exchange a stream of table chunks with dispatch-ahead overlap.

    The engine's double-buffered chunk pipeline applied to the shuffle
    exchange: the all_to_all for chunk k+1 is DISPATCHED before chunk k is
    yielded, so while the consumer's join/merge of chunk k runs (device
    compute plus its host-side compaction sync), the next exchange is
    already in the device queue — jax's async dispatch provides the
    overlap; this generator just keeps up to ``depth`` exchanges in front
    of the consumer.  ``depth=1`` is classic double buffering; ``depth=0``
    degenerates to the serial exchange-then-merge loop.

    ``chunks`` yields row-sharded Tables (or ``(Table, live_mask)`` pairs,
    same contract as ``shuffle_table_padded``).  Pass ``capacity`` sized
    from global counts so ONE compiled shuffle program serves the whole
    stream; with ``capacity=None`` each chunk runs its own counts pass
    (still correct, but differently-filled chunks may compile more than
    one program).  ``donate=True`` passes through to ``make_shuffle``'s
    buffer donation: each chunk's send buffers reuse its table's HBM (1x
    working set) — callers must not touch a chunk after yielding it.
    ``key_specs`` passes through to ``shuffle_table_padded`` for streams of
    already-exploded chunks (Spark-exact string-key placement).  ``split``
    passes through the AQE skew-split assignment (requires ``capacity``).

    Yields ``(padded Table, ok mask, overflow)`` per chunk, in order.
    """
    from collections import deque
    inflight: deque = deque()
    for item in chunks:
        tbl, live = item if isinstance(item, tuple) else (item, None)
        faults.check("exchange.dispatch")
        out = shuffle_table_padded(tbl, mesh, list(keys), capacity=capacity,
                                   axis=axis, donate=donate, live=live,
                                   key_specs=key_specs, split=split)
        inflight.append(out)
        # dispatch-ahead depth: how many exchanges sit in the device queue
        # in front of the consumer (the pipeline's high-water mark)
        metrics.gauge_max("parallel.shuffle.dispatch_ahead", len(inflight))
        if len(inflight) > max(0, int(depth)):
            yield inflight.popleft()
    while inflight:
        yield inflight.popleft()
