"""Distributed query plans: shuffle-then-aggregate, shuffle-then-join.

The two classic Spark exchange plans, each expressed as ONE jittable XLA
program over the mesh:

- GROUP BY (GpuHashAggregate + GpuShuffleExchange):
      local groupby_padded -> row-blob all_to_all -> final groupby_padded
- equi-join (GpuShuffledHashJoin / SortMergeJoin, BASELINE configs[3]):
      both sides hash-partition over all_to_all (co-partitioning)
      -> shard-local padded sorted-probe join (ops.join.inner_join_padded)

Everything stays in HBM; the exchanges ride ICI.  Outputs are padded per
shard (static shapes) with live-row masks; ``distributed_groupby`` /
``distributed_join`` compact at the host boundary, the ``build_*``
constructors return the pure shard_map programs for pjit pipelines (the
dryrun/benchmark entries).  STRING columns cross the mesh in padded-bucket
form (stringplane.explode_strings).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from ..columnar import Column, Table
from ..dtypes import DType, TypeId, INT64, FLOAT64
from ..ops.aggregate import groupby_padded
from ..ops.row_conversion import fixed_width_layout, _build_planes, \
    _from_planes
from .mesh import ROW_AXIS, axis_size
from ..utils.tracing import traced
from .shuffle import (partition_ids, partition_ids_specs, key_specs_for,
                      cap_bucket, cap_bucket_fine, exchange_planes,
                      partition_counts)

# (partial op emitted by the local pass, final re-aggregation op)
_REAGG = {"sum": "sum", "count": "sum", "count_all": "sum",
          "min": "min", "max": "max", "sumsq": "sum", "fsum": "sum"}


def _expand_aggs(aggs):
    """mean decomposes into (sum, count) partials + a final divide;
    var/std into (fsum, sumsq, count) partials + a final moment combine."""
    partial_specs = []   # (col_ref, op) for the local pass
    final_plan = []      # ("direct", i, op) | ("mean", si, ci)
    for ref, op in aggs:  # | ("var"/"std", si, qi, ci)
        if op == "mean":
            si = len(partial_specs)
            partial_specs.append((ref, "sum"))
            ci = len(partial_specs)
            partial_specs.append((ref, "count"))
            final_plan.append(("mean", si, ci))
        elif op in ("var", "std"):
            si = len(partial_specs)
            partial_specs.append((ref, "fsum"))
            qi = len(partial_specs)
            partial_specs.append((ref, "sumsq"))
            ci = len(partial_specs)
            partial_specs.append((ref, "count"))
            final_plan.append((op, si, qi, ci))
        else:
            if op not in _REAGG:
                raise ValueError(
                    f"aggregation {op!r} is not supported in the "
                    "distributed groupby (no partial/re-aggregation "
                    f"decomposition); supported: "
                    f"{sorted(_REAGG) + ['mean', 'var', 'std']}")
            i = len(partial_specs)
            partial_specs.append((ref, op))
            final_plan.append(("direct", i, _REAGG[op]))
    return partial_specs, final_plan


def _padded_table(out_keys, out_aggs, key_names):
    cols, names = [], []
    for spec, nm in zip(out_keys, key_names):
        if spec[0] == "string":
            # internal invariant, not a user-facing limit: the public entry
            # points (distributed_groupby/distributed_join) explode STRING
            # columns into fixed-width (len, word...) columns before building
            # this program (stringplane.explode_strings), so no string spec
            # can reach the exchange
            raise AssertionError(
                "string key reached the distributed exchange unexploded; "
                "use distributed_groupby/distributed_join (they explode "
                "strings via stringplane), or explode_strings() first")
        _, dtype, data, valid = spec
        cols.append(Column(dtype, data=data, validity=valid))
        names.append(nm if isinstance(nm, str) else f"key{nm}")
    for i, c in enumerate(out_aggs):
        cols.append(c)
        names.append(f"agg{i}")
    return Table(cols, names)


@functools.lru_cache(maxsize=64)
def build_distributed_groupby(mesh: Mesh, schema: tuple, names: tuple,
                              key_names: tuple, aggs: tuple,
                              capacity: int, axis: str = ROW_AXIS,
                              masked: bool = False,
                              key_specs: tuple | None = None):
    """Compile-once distributed GROUP BY for a fixed schema.

    Returns fn(datas, masks[, n_valid]) -> (key+agg padded buffers, live
    mask, ngroups per shard, overflow) operating on row-sharded column
    buffers.

    With ``masked=True`` the function takes a traced scalar ``n_valid`` (the
    original, pre-padding global row count) so ONE compiled program serves
    any row count at a fixed padded shape.  Rows at global index >= n_valid
    are pad_to_multiple null rows and are masked out of the local partial
    pass — without this they would form a spurious null-key group and
    corrupt genuine null-key aggregates.
    """
    ndev = axis_size(mesh, axis)
    partial_specs, final_plan = _expand_aggs(aggs)
    # var/std moment partials are computed over globally mean-shifted values
    # (variance is shift-invariant; without the shift the (Σx², Σx) combine
    # cancels catastrophically when |mean| >> std, e.g. timestamp columns)
    shift_idx = set()
    for plan in final_plan:
        if plan[0] in ("var", "std"):
            shift_idx.update((plan[1], plan[2]))

    def shard_fn(datas, masks, n_valid=None):
        shard_tbl = Table([Column(dt, data=d, validity=m)
                           for dt, d, m in zip(schema, datas, masks)],
                          list(names))
        n_local = shard_tbl.num_rows
        if n_valid is None:
            row_mask = None
        else:
            # shards are contiguous row ranges: shard i owns global rows
            # [i * n_local, (i+1) * n_local)
            shard_idx = jax.lax.axis_index(axis).astype(jnp.int64)
            global_row = shard_idx * n_local + jnp.arange(n_local,
                                                          dtype=jnp.int64)
            row_mask = global_row < n_valid
        specs = list(partial_specs)
        if shift_idx:
            from ..ops.aggregate import _float64_vals
            live = row_mask if row_mask is not None \
                else jnp.ones((n_local,), jnp.bool_)
            shifted = {}
            for i in shift_idx:
                ref = partial_specs[i][0]
                if ref not in shifted:
                    c = shard_tbl.column(ref)
                    vf = _float64_vals(c, c.data)
                    ok = c.valid_mask() & live
                    gs = jax.lax.psum(jnp.sum(jnp.where(ok, vf, 0.0)), axis)
                    gc = jax.lax.psum(jnp.sum(ok.astype(jnp.int64)), axis)
                    gm = gs / jnp.maximum(gc, 1).astype(jnp.float64)
                    shifted[ref] = Column.fixed(FLOAT64, vf - gm,
                                                validity=c.validity)
                specs[i] = (shifted[ref], partial_specs[i][1])
        # 1. local partial aggregation (padded to shard rows)
        out_keys, out_aggs, ng_local = groupby_padded(
            shard_tbl, list(key_names), specs, row_mask=row_mask)
        live_local = jnp.arange(n_local, dtype=jnp.int32) < ng_local

        partial_tbl = _padded_table(out_keys, out_aggs, key_names)
        playout = fixed_width_layout(partial_tbl.dtypes())
        pdatas = tuple(c.data for c in partial_tbl.columns)
        pmasks = tuple(c.validity for c in partial_tbl.columns)

        # 2. exchange partial groups by key hash (word planes over ICI);
        # string keys partition by Spark UTF8String murmur3 over their
        # exploded words (partition_ids_specs)
        if key_specs is not None:
            dest = partition_ids_specs(list(partial_tbl.columns),
                                       key_specs, ndev)
        else:
            key_cols = [partial_tbl.column(i) for i in range(len(key_names))]
            dest = partition_ids(Table(key_cols), ndev)
        planes = _build_planes(playout, pdatas, pmasks)
        planes_in, mask_in, overflow = exchange_planes(
            planes, dest, live_local, ndev, capacity, axis)

        # 3. final aggregation over received partials
        rdatas, rmasks = _from_planes(playout, planes_in)
        rtbl = Table([Column(dt, data=d, validity=m) for dt, d, m in
                      zip(playout.schema, rdatas, rmasks)],
                     list(partial_tbl.names))
        final_specs = []
        for plan in final_plan:
            if plan[0] == "mean":
                final_specs.append((f"agg{plan[1]}", "sum"))
                final_specs.append((f"agg{plan[2]}", "sum"))
            elif plan[0] in ("var", "std"):
                final_specs.append((f"agg{plan[1]}", "sum"))
                final_specs.append((f"agg{plan[2]}", "sum"))
                final_specs.append((f"agg{plan[3]}", "sum"))
            else:
                final_specs.append((f"agg{plan[1]}", plan[2]))
        fkeys, faggs, ng = groupby_padded(rtbl, list(key_names), final_specs,
                                          row_mask=mask_in)

        # 4. assemble outputs; resolve means
        out_cols = []
        fi = 0
        for plan in final_plan:
            if plan[0] == "mean":
                s, c = faggs[fi], faggs[fi + 1]
                fi += 2
                sv = s.float_values() if s.dtype.id == TypeId.FLOAT64 \
                    else s.data.astype(jnp.float64)
                m = sv / jnp.maximum(c.data, 1).astype(jnp.float64)
                valid = (c.data > 0) if s.validity is None \
                    else (s.validity & (c.data > 0))
                out_cols.append(Column.fixed(FLOAT64, m, validity=valid))
            elif plan[0] in ("var", "std"):
                s, q, c = faggs[fi], faggs[fi + 1], faggs[fi + 2]
                fi += 3
                sv = s.float_values()
                qv = q.float_values()
                nf = jnp.maximum(c.data, 1).astype(jnp.float64)
                var = jnp.maximum(
                    (qv - sv * sv / nf) / jnp.maximum(nf - 1.0, 1.0), 0.0)
                data = jnp.sqrt(var) if plan[0] == "std" else var
                out_cols.append(Column.fixed(FLOAT64, data,
                                             validity=c.data > 1))
            else:
                out_cols.append(faggs[fi])
                fi += 1
        # arrays only across the shard_map boundary (dtypes are static,
        # reconstructed by the caller from the plan)
        key_data = tuple(spec[2] for spec in fkeys)
        key_valid = tuple(spec[3] for spec in fkeys)
        agg_data = tuple(c.data for c in out_cols)
        agg_valid = tuple(c.valid_mask() for c in out_cols)
        live_out = jnp.arange(ndev * capacity, dtype=jnp.int32) < ng
        return (key_data, key_valid, agg_data, agg_valid, live_out,
                jnp.reshape(ng, (1,)), jax.lax.psum(overflow, axis))

    spec = P(axis)
    if masked:
        return jax.jit(shard_map(
            shard_fn, mesh=mesh, in_specs=(spec, spec, P()),
            out_specs=(spec, spec, spec, spec, spec, spec, P()),
            check_vma=False))
    return jax.jit(shard_map(
        lambda datas, masks: shard_fn(datas, masks), mesh=mesh,
        in_specs=(spec, spec),
        out_specs=(spec, spec, spec, spec, spec, spec, P()),
        check_vma=False))


# ---------------------------------------------------------------------------
# distributed SortMergeJoin: co-partition by key hash, join locally per shard
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def build_distributed_join(mesh: Mesh, lschema: tuple, lnames: tuple,
                           rschema: tuple, rnames: tuple,
                           on_left: tuple, on_right: tuple, how: str,
                           lcap: int, rcap: int, jcap: int,
                           axis: str = ROW_AXIS,
                           lkey_specs: tuple | None = None,
                           rkey_specs: tuple | None = None):
    """Compile-once distributed equi-join for fixed schemas.

    The physical plan Spark runs as GpuShuffledHashJoin/SortMergeJoin
    (BASELINE configs[3]) as ONE jitted shard_map program: both sides
    hash-partition by join key over ICI all_to_all (co-partitioning), then
    each shard joins its partitions locally with the padded sorted-probe
    join (ops.join.inner_join_padded).  Returns fn(ldatas, lmasks, rdatas,
    rmasks) -> (lsel, rsel, live, rvalid, counts, overflows) where lsel/rsel
    index the *exchanged* padded shard tables whose buffers are also
    returned; the host wrapper assembles and compacts.
    """
    from ..ops.join import inner_join_padded
    ndev = axis_size(mesh, axis)
    llayout = fixed_width_layout(list(lschema))
    rlayout = fixed_width_layout(list(rschema))

    def exchange(layout, names, schema, datas, masks, key_names, cap,
                 kspecs):
        tbl = Table([Column(dt_, data=d, validity=m)
                     for dt_, d, m in zip(schema, datas, masks)], list(names))
        if kspecs is not None:
            dest = partition_ids_specs(list(tbl.columns), kspecs, ndev)
        else:
            keys = [tbl.column(k) for k in key_names]
            dest = partition_ids(Table(keys), ndev)
        planes = _build_planes(layout, datas, masks)
        planes_in, live_in, overflow = exchange_planes(
            planes, dest, None, ndev, cap, axis)
        d_in, m_in = _from_planes(layout, list(planes_in))
        tbl_in = Table([Column(dt_, data=d, validity=m)
                        for dt_, d, m in zip(layout.schema, d_in, m_in)],
                       list(names))
        return tbl_in, live_in, overflow

    def shard_fn(ldatas, lmasks, rdatas, rmasks):
        ltbl, llive, lovf = exchange(llayout, lnames, lschema, ldatas,
                                     lmasks, on_left, lcap, lkey_specs)
        rtbl, rlive, rovf = exchange(rlayout, rnames, rschema, rdatas,
                                     rmasks, on_right, rcap, rkey_specs)
        # pack=False: the host wrapper compacts by mask, so the
        # front-packing compaction sort would be pure waste
        li, ri, jlive, npairs, jovf = inner_join_padded(
            ltbl, rtbl, list(on_left), list(on_right), jcap,
            left_live=llive, right_live=rlive, pack=False)

        if how in ("inner", "left", "right", "full"):
            nl = ndev * lcap
            nr = ndev * rcap
            lvalid = jnp.ones(jlive.shape, jnp.bool_)
            rvalid = jlive
            live = jlive
            # matched masks over the ORIGINAL pair arrays, before any
            # outer-extension concatenation below changes their length
            matched_l = jnp.zeros((nl,), jnp.bool_)
            matched_r = jnp.zeros((nr,), jnp.bool_)
            if jcap:
                matched_l = matched_l.at[li].max(jlive)
                matched_r = matched_r.at[ri].max(jlive)
            if how in ("left", "full"):
                li = jnp.concatenate([li, jnp.arange(nl, dtype=jnp.int32)])
                ri = jnp.concatenate([ri, jnp.zeros((nl,), jnp.int32)])
                lvalid = jnp.concatenate([lvalid, jnp.ones((nl,), jnp.bool_)])
                rvalid = jnp.concatenate([rvalid, jnp.zeros((nl,), jnp.bool_)])
                live = jnp.concatenate(
                    [live, llive & jnp.logical_not(matched_l)])
            if how in ("right", "full"):
                li = jnp.concatenate([li, jnp.zeros((nr,), jnp.int32)])
                ri = jnp.concatenate([ri, jnp.arange(nr, dtype=jnp.int32)])
                lvalid = jnp.concatenate([lvalid, jnp.zeros((nr,), jnp.bool_)])
                rvalid = jnp.concatenate([rvalid, jnp.ones((nr,), jnp.bool_)])
                live = jnp.concatenate(
                    [live, rlive & jnp.logical_not(matched_r)])
            lsel = tuple(jnp.take(c.data, li, axis=0) for c in ltbl.columns)
            lselv = tuple(jnp.take(c.valid_mask(), li) & lvalid
                          for c in ltbl.columns)
            rsel = tuple(jnp.take(c.data, ri, axis=0) for c in rtbl.columns)
            rselv = tuple(jnp.take(c.valid_mask(), ri) & rvalid
                          for c in rtbl.columns)
            if how in ("right", "full"):
                # coalesce key columns shard-side: rows missing on the left
                # (right-extra rows) take the right side's key value, so the
                # host wrapper's drop-right-keys projection stays correct
                lsel, lselv = list(lsel), list(lselv)
                for lk_name, rk_name in zip(on_left, on_right):
                    i = list(lnames).index(lk_name)
                    j = list(rnames).index(rk_name)
                    rkey = jnp.take(rtbl.columns[j].data, ri, axis=0)
                    lmask = lvalid.reshape(
                        lvalid.shape + (1,) * (rkey.ndim - 1))
                    lsel[i] = jnp.where(lmask, lsel[i], rkey)
                    lselv[i] = jnp.where(
                        lvalid, lselv[i],
                        jnp.take(rtbl.columns[j].valid_mask(), ri) & rvalid)
                lsel, lselv = tuple(lsel), tuple(lselv)
            nrows = jnp.sum(live.astype(jnp.int32))
            return (lsel, lselv, rsel, rselv, live, jnp.reshape(nrows, (1,)),
                    jax.lax.psum(lovf + rovf, axis),
                    jax.lax.psum(jovf, axis))

        # semi / anti: left rows with (no) matching key on the co-partition
        nl = ndev * lcap
        matched = jnp.zeros((nl,), jnp.bool_)
        if jcap:
            matched = matched.at[li].max(jlive)
        keep = llive & (matched if how == "semi" else jnp.logical_not(matched))
        lsel = tuple(c.data for c in ltbl.columns)
        lselv = tuple(c.valid_mask() for c in ltbl.columns)
        nrows = jnp.sum(keep.astype(jnp.int32))
        return (lsel, lselv, (), (), keep, jnp.reshape(nrows, (1,)),
                jax.lax.psum(lovf + rovf, axis), jax.lax.psum(jovf, axis))

    spec = P(axis)
    return jax.jit(shard_map(
        shard_fn, mesh=mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=(spec, spec, spec, spec, spec, spec, P(), P()),
        check_vma=False))


@traced("distributed_join")
def distributed_join(left: Table, right: Table, mesh: Mesh, on_left,
                     on_right=None, how: str = "inner",
                     capacity: int | None = None,
                     join_capacity: int | None = None,
                     suffixes=("", "_r"), axis: str = ROW_AXIS) -> Table:
    """Distributed equi-join (inner/left/right/full/semi/anti); compacts to a
    host Table.

    Both sides are hash-partitioned on the join keys over the mesh, then
    joined shard-locally — the 8-chip shuffle + SortMergeJoin plan of
    BASELINE configs[3].  Outer rows (left/right/full) are shard-local
    correct because co-partitioning puts every occurrence of a key on one
    shard.  STRING columns travel in padded-bucket form; string JOIN KEYS
    are exploded at one common bucket width across both sides (the word
    count is part of the key identity — different widths would partition
    the same string to different shards).  ``capacity`` bounds rows
    received per (source, dest) pair per side; ``join_capacity`` bounds
    candidate pairs per shard.  Overflow raises with the counts, never
    silently drops.
    """
    from .mesh import pad_to_multiple, shard_table
    from .stringplane import explode_strings, reassemble_strings
    from ..ops.strings_common import string_width_bucket
    on_right = list(on_right or on_left)
    on_left = list(on_left)
    ndev = axis_size(mesh, axis)

    def _key_width(t, k):
        c = t.column(k)
        return string_width_bucket(c) if c.dtype.is_string else None

    lov, rov = {}, {}
    for lk, rk in zip(on_left, on_right):
        wl, wr = _key_width(left, lk), _key_width(right, rk)
        if wl is not None or wr is not None:
            w = max(wl or 0, wr or 0)
            lov[lk], rov[rk] = w, w

    def prep(t, keys, overrides):
        plan = None
        if any(c.dtype.is_string for c in t.columns):
            t, plan = explode_strings(t, width_overrides=overrides)
            keys = plan.exploded_keys(keys)
        if t.num_rows % ndev:
            t, _ = pad_to_multiple(t, ndev)
            # padded rows are all-null: null keys never match (SQL equi-join)
        t = shard_table(t, mesh, axis)
        return t, keys, plan

    lt, lkeys, lplan = prep(left, on_left, lov)
    rt, rkeys, rplan = prep(right, on_right, rov)
    if len(lkeys) != len(rkeys):
        raise TypeError(
            f"join key shapes disagree after explosion: {lkeys} vs {rkeys} "
            "(string keys must pair with string keys)")
    # Spark-exact partitioning: string keys hash their UTF-8 bytes, and
    # CO-PARTITIONING demands the two sides agree — the byte hash does by
    # construction (the exploded-representation hash only agreed because
    # widths were forced equal)
    lkey_specs = key_specs_for(lt, on_left, lplan)
    rkey_specs = key_specs_for(rt, on_right, rplan)
    auto_cap = capacity is None
    auto_jcap = join_capacity is None
    if auto_cap:
        # two-phase exchange: counts are exact for joins (no pre-agg dedup);
        # each side sized independently (builder takes lcap/rcap)
        lcounts = partition_counts(lt, mesh, lkeys, axis,
                                   key_specs=lkey_specs)
        rcounts = partition_counts(rt, mesh, rkeys, axis,
                                   key_specs=rkey_specs)
        lcap = cap_bucket(int(lcounts.max()))
        rcap = cap_bucket(int(rcounts.max()))
        if auto_jcap:
            # candidate pairs per shard start at (received left + received
            # right) rows — exact for FK-style joins, and the overflow
            # retry below right-sizes heavy-duplicate keys.  Fine buckets:
            # jcap is the largest sort in the program, so 2x pow2 padding
            # is real work.
            recv = int(lcounts.sum(axis=0).max() + rcounts.sum(axis=0).max())
            join_capacity = cap_bucket_fine(recv)
    else:
        lcap = rcap = capacity
    if auto_jcap and join_capacity is None:
        join_capacity = 2 * ndev * max(lcap, rcap)

    lnames = tuple(lt.names or [f"l{i}" for i in range(lt.num_columns)])
    rnames = tuple(rt.names or [f"r{i}" for i in range(rt.num_columns)])
    largs = (tuple(c.data for c in lt.columns),
             tuple(c.validity for c in lt.columns))
    rargs = (tuple(c.data for c in rt.columns),
             tuple(c.validity for c in rt.columns))
    # Join cardinality is data-dependent; the counted overflows say exactly
    # how much was missing, so auto-sized capacities retry right-sized
    # (explicitly passed capacities are contracts and raise instead).
    for _attempt in range(8):
        fn = build_distributed_join(
            mesh, tuple(lt.dtypes()), lnames, tuple(rt.dtypes()), rnames,
            tuple(lkeys), tuple(rkeys), how, lcap, rcap,
            join_capacity, axis, lkey_specs, rkey_specs)
        (lsel, lselv, rsel, rselv, live, _n, xovf, jovf) = fn(
            *largs, *rargs)
        if int(xovf) > 0:
            # structurally unreachable with counts-based sizing; kept as a
            # defense-in-depth invariant for explicitly passed capacities
            if not auto_cap:
                raise RuntimeError(
                    f"distributed_join exchange overflow ({int(xovf)} rows); "
                    f"rerun with larger capacity (got {lcap}/{rcap})")
            lcap = 2 * lcap + (int(xovf) + ndev - 1) // ndev
            rcap = 2 * rcap + (int(xovf) + ndev - 1) // ndev
            if auto_jcap:
                join_capacity = 2 * ndev * max(lcap, rcap)
            continue
        if int(jovf) > 0:
            if not auto_jcap:
                raise RuntimeError(
                    f"distributed_join pair overflow ({int(jovf)} candidate "
                    f"pairs); rerun with larger join_capacity "
                    f"(got {join_capacity})")
            join_capacity = join_capacity + int(jovf) + 63 & ~63
            continue
        break
    else:
        raise RuntimeError("distributed_join failed to size its exchange")

    live_np = np.asarray(live)
    def compact(specs, valids, schema, names):
        cols = []
        for dt_, d, v in zip(schema, specs, valids):
            dn = np.asarray(d)[live_np]
            vn = np.asarray(v)[live_np]
            cols.append(Column(dt_, data=jnp.asarray(dn),
                               validity=None if vn.all() else jnp.asarray(vn)))
        return Table(cols, list(names))

    ltab = compact(lsel, lselv, lt.dtypes(), lnames)
    if lplan is not None:
        ltab = reassemble_strings(ltab, lplan)
    if how in ("semi", "anti"):
        return ltab
    rtab = compact(rsel, rselv, rt.dtypes(), rnames)
    if rplan is not None:
        rtab = reassemble_strings(rtab, rplan)
    # drop right key columns; suffix collisions (cudf/Spark projection shape)
    keep = [i for i, nm in enumerate(rtab.names) if nm not in on_right]
    lout_names = list(ltab.names)
    out_cols = list(ltab.columns)
    out_names = lout_names[:]
    for i in keep:
        nm = rtab.names[i]
        out_cols.append(rtab.columns[i])
        out_names.append(nm + (suffixes[1] if nm in lout_names else ""))
    return Table(out_cols, out_names)


@functools.lru_cache(maxsize=8)
def build_distributed_cross(mesh: Mesh, axis: str = ROW_AXIS):
    """Compile-once distributed cross join: left row-sharded, right
    replicated (the Spark BroadcastNestedLoopJoin/CartesianProduct plan
    shape — no exchange at all; each shard pairs its left rows with the
    full right side)."""
    def shard_fn(ldatas, lmasks, llive, rdatas, rmasks):
        nl = ldatas[0].shape[0]
        nr = rdatas[0].shape[0]
        li = jnp.repeat(jnp.arange(nl, dtype=jnp.int32), nr)
        ri = jnp.tile(jnp.arange(nr, dtype=jnp.int32), nl)
        def sel(datas, masks, idx):
            d = tuple(jnp.take(x, idx, axis=0) for x in datas)
            v = tuple(jnp.ones(idx.shape, jnp.bool_) if m is None
                      else jnp.take(m, idx) for m in masks)
            return d, v
        lsel, lselv = sel(ldatas, lmasks, li)
        rsel, rselv = sel(rdatas, rmasks, ri)
        live = jnp.take(llive, li)
        return lsel, lselv, rsel, rselv, live
    spec = P(axis)
    return jax.jit(shard_map(
        shard_fn, mesh=mesh,
        in_specs=(spec, spec, spec, P(), P()),
        out_specs=(spec, spec, spec, spec, spec), check_vma=False))


@traced("distributed_cross_join")
def distributed_cross_join(left: Table, right: Table, mesh: Mesh,
                           suffixes=("", "_r"), axis: str = ROW_AXIS) -> Table:
    """Distributed Cartesian product; compacts to a host Table.

    Left is row-sharded over the mesh, right is replicated to every shard
    (no collective traffic).  Output row order is shard-major and otherwise
    unspecified, as in Spark."""
    from .mesh import pad_to_multiple, shard_table
    from .stringplane import explode_strings, reassemble_strings
    ndev = axis_size(mesh, axis)
    lt, lplan = (explode_strings(left)
                 if any(c.dtype.is_string for c in left.columns)
                 else (left, None))
    rt, rplan = (explode_strings(right)
                 if any(c.dtype.is_string for c in right.columns)
                 else (right, None))
    n_orig = lt.num_rows
    if lt.num_rows % ndev:
        lt, n_orig = pad_to_multiple(lt, ndev)
    llive = jnp.arange(lt.num_rows, dtype=jnp.int64) < n_orig
    lt = shard_table(lt, mesh, axis)
    llive = jax.device_put(
        llive, jax.sharding.NamedSharding(mesh, P(axis)))
    fn = build_distributed_cross(mesh, axis)
    lsel, lselv, rsel, rselv, live = fn(
        tuple(c.data for c in lt.columns),
        tuple(c.validity for c in lt.columns), llive,
        tuple(c.data for c in rt.columns),
        tuple(c.validity for c in rt.columns))
    live_np = np.asarray(live)

    def compact(specs, valids, schema, names):
        cols = []
        for dt_, d, v in zip(schema, specs, valids):
            dn = np.asarray(d)[live_np]
            vn = np.asarray(v)[live_np]
            cols.append(Column(dt_, data=jnp.asarray(dn),
                               validity=None if vn.all() else jnp.asarray(vn)))
        return Table(cols, list(names))

    lnames = list(lt.names or [f"l{i}" for i in range(lt.num_columns)])
    rnames = list(rt.names or [f"r{i}" for i in range(rt.num_columns)])
    ltab = compact(lsel, lselv, lt.dtypes(), lnames)
    rtab = compact(rsel, rselv, rt.dtypes(), rnames)
    if lplan is not None:
        ltab = reassemble_strings(ltab, lplan)
    if rplan is not None:
        rtab = reassemble_strings(rtab, rplan)
    out_cols = list(ltab.columns)
    out_names = list(ltab.names)
    for nm, c in zip(rtab.names, rtab.columns):
        out_cols.append(c)
        out_names.append(nm + (suffixes[1] if nm in ltab.names else ""))
    return Table(out_cols, out_names)


@functools.lru_cache(maxsize=64)
def build_distributed_window(mesh: Mesh, schema: tuple, names_in: tuple,
                             partition_by: tuple, order_by: tuple,
                             nspecs: tuple, axis: str = ROW_AXIS):
    """Compile-once per-shard window program (jitted shard_map), keyed on
    the static plan like make_shuffle / build_distributed_groupby."""
    from ..ops.window import window as _window

    def order_key(tbl, k):
        if isinstance(k, tuple):  # (name, ascending)
            from ..ops.order import SortKey
            return SortKey(tbl.column(k[0]), ascending=k[1])
        return k

    def _win_shard(datas, masks, okm):
        tbl = Table([Column(dt_, data=d, validity=m)
                     for dt_, d, m in zip(schema, datas, masks)],
                    list(names_in))
        out = _window(tbl, list(partition_by),
                      [order_key(tbl, k) for k in order_by],
                      [tuple(s) for s in nspecs], live=okm)
        new = out.columns[tbl.num_columns:]
        return (tuple(c.data for c in new),
                tuple(c.valid_mask() for c in new))

    return jax.jit(shard_map(
        _win_shard, mesh=mesh, in_specs=(P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis)), check_vma=False))


@traced("distributed_window")
def distributed_window(table: Table, mesh: Mesh, partition_by: list,
                       order_by: list, specs: list, names: list | None = None,
                       axis: str = ROW_AXIS) -> Table:
    """Distributed window functions: co-partition by the partition keys over
    the mesh, then run ops.window shard-locally (exact — a window never
    crosses partitions, and a partition never crosses shards).

    Key lists must be column names (SortKey descending wrappers are applied
    shard-side for ``order_by`` via (name, False) tuples).  Returns a
    compacted host Table (row order unspecified, as in Spark).
    """
    from .mesh import pad_to_multiple, shard_table
    from .shuffle import shuffle_table_padded
    ndev = axis_size(mesh, axis)
    t = table
    live = None
    if t.num_rows % ndev:
        t, n_orig = pad_to_multiple(t, ndev)
        live = jnp.arange(t.num_rows, dtype=jnp.int64) < n_orig
    st = shard_table(t, mesh, axis)
    shuffled, ok, overflow = shuffle_table_padded(
        st, mesh, list(partition_by), axis=axis, live=live)
    if int(overflow):
        raise RuntimeError(f"window shuffle overflow: {int(overflow)} rows")

    names_in = tuple(shuffled.names or
                     [f"c{i}" for i in range(shuffled.num_columns)])
    schema = tuple(shuffled.dtypes())
    nspecs = tuple(tuple(s) for s in specs)
    win_fn = build_distributed_window(mesh, schema, names_in,
                                      tuple(partition_by), tuple(order_by),
                                      nspecs, axis)
    datas = tuple(c.data for c in shuffled.columns)
    masks = tuple(c.validity for c in shuffled.columns)
    wdata, wvalid = win_fn(datas, masks, ok)

    keep = np.flatnonzero(np.asarray(ok))
    out_cols = [Column(c.dtype,
                       data=jnp.asarray(np.asarray(c.data)[keep]),
                       validity=None if c.validity is None else
                       jnp.asarray(np.asarray(c.validity)[keep]))
                for c in shuffled.columns]
    from ..ops.window import default_window_names, window_out_dtype
    wcols = []
    for wi, (ref, op, *rest) in enumerate(nspecs):
        d = np.asarray(wdata[wi])[keep]
        v = np.asarray(wvalid[wi])[keep]
        dtype = window_out_dtype(
            None if ref is None else shuffled.column(ref).dtype, op)
        wcols.append(Column(dtype, data=jnp.asarray(d),
                            validity=jnp.asarray(v)))
    wnames = list(names) if names is not None \
        else default_window_names(nspecs)
    return Table(out_cols + wcols, list(names_in) + wnames)


def agg_out_dtype(col_dtype: DType, op: str) -> DType:
    """Result dtype of an aggregation (mirrors ops.aggregate._agg_column)."""
    if op in ("count", "count_all"):
        return INT64
    if op in ("mean", "var", "std", "sumsq", "fsum"):
        return FLOAT64
    if op in ("min", "max"):
        return col_dtype
    if op == "sum":
        if col_dtype.id in (TypeId.FLOAT32, TypeId.FLOAT64):
            return FLOAT64
        return col_dtype if col_dtype.is_decimal else INT64
    raise ValueError(op)


@traced("distributed_groupby")
def distributed_groupby(table: Table, mesh: Mesh, key_names: list,
                        aggs: list, capacity: int | None = None,
                        axis: str = ROW_AXIS,
                        n_valid_rows: int | None = None) -> Table:
    """GROUP BY over a row-sharded table; compacts to a host-side Table.

    Non-mesh-divisible tables are padded internally with masked null rows.
    Callers who pre-padded with ``pad_to_multiple`` must pass the original
    row count as ``n_valid_rows`` so padding rows don't aggregate as data.

    STRING columns (keys or counted values) ride the mesh in padded-bucket
    form (stringplane.explode_strings): exploded before sharding, grouped as
    (length, byte-word) multi-keys, reassembled on the way out.
    """
    from .mesh import pad_to_multiple, shard_table
    ndev = axis_size(mesh, axis)

    orig_keys = list(key_names)
    orig_aggs = list(aggs)
    plan = None
    if any(c.dtype.is_string for c in table.columns):
        from .stringplane import explode_strings, reassemble_strings, \
            StringPlan
        table, plan = explode_strings(table)
        spec_of = dict(zip(plan.names, plan.specs))
        key_names = plan.exploded_keys(orig_keys)
        aggs = []
        for ref, op in orig_aggs:
            if spec_of.get(ref, ("fixed",))[0] == "string":
                if op not in ("count", "count_all"):
                    raise TypeError(
                        "string value aggregation not supported; "
                        "dictionary-encode first (ops.dictionary)")
                aggs.append((f"{ref}#len", op))  # same validity as the string
            else:
                aggs.append((ref, op))
    if table.num_rows % ndev:
        if n_valid_rows is not None:
            raise ValueError("table rows not mesh-divisible; pad first or "
                             "let distributed_groupby pad (omit n_valid_rows)")
        table, n_valid_rows = pad_to_multiple(table, ndev)
        table = shard_table(table, mesh, axis)
    elif plan is not None:
        # strings couldn't shard before explosion; place the exploded
        # fixed-width buffers on the mesh now
        table = shard_table(table, mesh, axis)
    # Spark-exact partition hashing (string keys by UTF8 murmur3): specs
    # over the full exploded table for the counts pass, and over the
    # partial-group table (keys lead its columns) for the exchange
    tbl_specs = key_specs_for(table, orig_keys, plan)
    kcols = Table([table.column(k) for k in key_names], list(key_names))
    partial_specs = key_specs_for(kcols, orig_keys, plan)
    if capacity is None:
        # two-phase exchange: raw-row partition counts upper-bound the
        # partial-group rows each shard sends (local agg only dedups)
        counts = partition_counts(table, mesh, list(key_names), axis,
                                  n_valid_rows=n_valid_rows,
                                  key_specs=tbl_specs)
        shard_rows = table.num_rows // ndev
        capacity = min(cap_bucket(int(counts.max())),
                       cap_bucket(shard_rows))
    fn = build_distributed_groupby(
        mesh, tuple(table.dtypes()),
        tuple(table.names or [f"c{i}" for i in range(table.num_columns)]),
        tuple(key_names), tuple(aggs), capacity, axis,
        masked=n_valid_rows is not None, key_specs=partial_specs)
    datas = tuple(c.data for c in table.columns)
    masks = tuple(c.validity for c in table.columns)
    if n_valid_rows is not None:
        (key_data, key_valid, agg_data, agg_valid, live, _ng,
         overflow) = fn(datas, masks, jnp.int64(n_valid_rows))
    else:
        (key_data, key_valid, agg_data, agg_valid, live, _ng,
         overflow) = fn(datas, masks)
    if int(overflow) > 0:
        raise RuntimeError(
            f"shuffle capacity overflow ({int(overflow)} rows); rerun with "
            f"larger capacity (got {capacity})")

    live_np = np.asarray(live)
    key_dtypes = [table.column(k).dtype for k in key_names]
    agg_dtypes = [agg_out_dtype(table.column(ref).dtype, op)
                  for ref, op in aggs]
    cols = []
    agg_out_names = [f"{op}_{ref}" for ref, op in orig_aggs]
    names = list(key_names) + agg_out_names
    for dtype, data, valid in zip(
            key_dtypes + agg_dtypes,
            list(key_data) + list(agg_data),
            list(key_valid) + list(agg_valid)):
        d = np.asarray(data)[live_np]
        v = np.asarray(valid)[live_np]
        cols.append(Column(dtype, data=jnp.asarray(d),
                           validity=None if v.all() else jnp.asarray(v)))
    result = Table(cols, names)
    if plan is not None:
        # fold exploded key columns back into strings
        out_specs = tuple([spec_of[k] for k in orig_keys]
                          + [("fixed",)] * len(orig_aggs))
        out_plan = StringPlan(tuple(orig_keys + agg_out_names), out_specs)
        result = reassemble_strings(result, out_plan)
    return result
