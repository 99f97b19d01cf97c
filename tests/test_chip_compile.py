"""AOT compiles for a DESCRIBED TPU v5e chip: the programs ``chip_smoke.py``
runs, at their real widths, handed to the installed TPU compiler with no
chip attached.  What the compiler refuses here it would refuse on the chip,
at no chip time.  Nothing runs, so these say nothing about results or speed.

All such compiles live in this one file: the process that describes the
topology loads the TPU library and keeps it, so a second file (another
xdist worker) could not.  The topology is described inside a module-scoped,
non-autouse fixture — never at import or collection.

Code that asks ``jax.default_backend()`` still sees the CPU here, so the one
such switch on this path (``utils/floatbits.py``: native f64 bitcast on the
CPU, arithmetic bit assembly elsewhere) is steered to its TPU branch by the
tests, and every program is jitted fresh so no CPU-branch trace is reused.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from spark_rapids_jni_tpu import Column, Table
from spark_rapids_jni_tpu import dtypes as dt
from spark_rapids_jni_tpu.utils import floatbits

HBM_BYTES = 16 * 1024 ** 3       # one v5e chip
SF1_ROWS = 2_880_404             # TPC-DS SF1 store_sales
# Every lax.sort in a program costs the TPU compiler about a minute once the
# operand passes a few tens of thousands of rows (measured here: 74 s for one
# 2-operand u32 sort at 65,536 rows, 10 s for the whole groupby at 8,192), so
# the sort-carried programs compile at a small row count and real column
# widths; the sort-free row conversion compiles at the full table.
CHUNK_ROWS = 4_096


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_branches(monkeypatch):
    """Cache off around the compiles (an entry compiled for a described
    chip cannot be read back without one) and the TPU branch of floatbits."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    monkeypatch.setattr(floatbits, "_native_f64_bitcast", lambda: False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def on(sharding, tree):
    """Arrays / ShapeDtypeStructs of a pytree -> shapes placed by ``sharding``."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def compile_for_chip(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert need < HBM_BYTES, f"{need} bytes do not fit one chip's HBM"
    return compiled


def sales_chunk(n):
    """The smoke's store_sales chunk as the staged scan hands it over:
    i64, i64, f64-as-bits, each with a validity plane."""
    z = np.zeros(n, np.int64)
    v = np.ones(n, np.bool_)
    return Table([Column(dt.INT64, data=z, validity=v),
                  Column(dt.INT64, data=z, validity=v),
                  Column(dt.FLOAT64, data=z, validity=v)],
                 ["ss_sold_date_sk", "ss_store_sk", "ss_ext_sales_price"])


@pytest.fixture(scope="module")
def q5_calls(tmp_path_factory):
    """The smoke's plan run small on the CPU, with every call of its two
    compiled programs recorded: the fused chunk segment and the merge of
    the streamed partials."""
    import chip_smoke as cs
    from spark_rapids_jni_tpu.engine import PlanCache
    from spark_rapids_jni_tpu.engine import segment as seg

    calls = {"chunk": [], "merge": []}
    chunk, merge = seg.CompiledSegment.__call__, seg.CompiledCombine.__call__

    def chunk_call(self, table, nvalid=None, prepared=(), lo=None):
        calls["chunk"].append((self, table, tuple(prepared)))
        return chunk(self, table, nvalid, prepared, lo)

    def merge_call(self, partials, nreal):
        calls["merge"].append((self, partials))
        return merge(self, partials, nreal)

    wh = cs.make_warehouse(str(tmp_path_factory.mktemp("q5")), 6_000, seed=1)
    try:
        seg.CompiledSegment.__call__ = chunk_call
        seg.CompiledCombine.__call__ = merge_call
        PlanCache().get(cs.q5_lite(wh["paths"])).execute(stats={})
    finally:
        seg.CompiledSegment.__call__ = chunk
        seg.CompiledCombine.__call__ = merge
    return calls


def test_fused_q5_chunk_segment(q5_calls, one_chip, tpu_branches):
    """The fused chunk program of the smoke's plan — filter + semi-join
    probe + partial groupby — found by running the real plan small on the
    CPU, then compiled for the chip at the same shapes."""
    from spark_rapids_jni_tpu.engine import segment as seg
    assert q5_calls["chunk"], "the plan ran no fused segment"
    compiled, table, prepared = q5_calls["chunk"][0]
    assert compiled.segment.agg is not None and compiled.segment.joins
    assert compiled.agg_form == "dense/16"     # 12 stores
    compile_for_chip(seg._build_fn(compiled.segment, compiled),
                     on(one_chip, table),
                     jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
                     on(one_chip, prepared),
                     jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip))


def _dense_program(q5_calls, slots):
    """The smoke's chunk program with its aggregate's dense form over
    ``slots`` key slots: (program, its recorded table and builds)."""
    from spark_rapids_jni_tpu.engine import segment as seg
    compiled, table, prepared = q5_calls["chunk"][0]
    dense = seg.CompiledSegment(compiled.key, compiled.segment,
                                compiled.key_dtypes, compiled.probes, slots)
    return seg._build_fn(dense.segment, dense), table, prepared


@pytest.mark.parametrize("slots", [16, 128])
def test_dense_chunk_program_with_its_guard(q5_calls, one_chip, tpu_branches,
                                            slots):
    """The chunk program whose aggregate takes the dense form — 16 slots
    (SF1's 12 stores), 128 (SF10's 102) — with the guard's other branch,
    the sort form: at the sort-carried programs' small row count."""
    fn, table, prepared = _dense_program(q5_calls, slots)
    rows = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        (CHUNK_ROWS,) + a.shape[1:], a.dtype, sharding=one_chip), table)
    text = compile_for_chip(
        fn, rows, jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        on(one_chip, prepared),
        jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip)).as_text()
    assert " conditional(" in text and " sort(" in text


@pytest.mark.parametrize("slots", [16, 128])
def test_dense_chunk_program_real_chunk(q5_calls, one_chip, tpu_branches,
                                        monkeypatch, slots):
    """The dense form alone (the guard's sort branch left out) at the
    benchmark's real chunk, 262,144 rows: sort-free, so it compiles at
    full size in seconds, with no sort and no gather."""
    fn, table, prepared = _dense_program(q5_calls, slots)
    monkeypatch.setattr(jax.lax, "cond", lambda pred, dense, sort: dense())
    rows = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        (262_144,) + a.shape[1:], a.dtype, sharding=one_chip), table)
    text = compile_for_chip(
        fn, rows, jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        on(one_chip, prepared),
        jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip)).as_text()
    assert " sort(" not in text and " gather(" not in text


def test_q5_merge_of_streamed_partials(q5_calls, one_chip, tpu_branches):
    """The merge program of the same plan at the benchmark's shape: 11 or
    12 chunks of an SF1 row group fill the 16-partial bucket, each partial
    padded to the chunk's 262,144-row bucket and cut to 64 groups — three
    ``lax.sort``s over 1,024 rows, which is what keeps its compile short."""
    from spark_rapids_jni_tpu.engine import segment as seg
    ((merge, partials),) = q5_calls["merge"]
    assert len(partials) == 16
    slots = 262_144
    real = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((slots,), a.dtype, sharding=one_chip),
        partials)
    cap = merge.key[1][0]
    assert cap == 64
    compile_for_chip(
        seg._build_combine_fn(merge.segment.agg, merge.key_dtypes, cap,
                              merge),
        real, jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))


def test_q5_fold_of_a_long_stream(q5_calls, one_chip, tpu_branches):
    """What a stream of more than 16 chunks adds (the benchmark's SF10
    cell, 108 chunks): the merge of ONE merged partial — a fold's output,
    16 x 128 slots with its group count where a chunk's partial has its
    mask — and 15 padded partials of the chunk's bucket, cut to the 128
    slots that 102 stores need: sorts over 2,048 rows."""
    from spark_rapids_jni_tpu.engine import segment as seg
    ((merge, partials),) = q5_calls["merge"]
    cap, slots = 128, 262_144

    def shaped(rows, tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            (rows,), a.dtype, sharding=one_chip), tree)

    count = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    # q5 checks no overflow: a merged partial's flag is None, as a chunk's
    merged = shaped(seg.COMBINE_ARITY * cap, partials[0][:3]) + (count, None)
    filled = (merged,) + (shaped(slots, partials[0]),) \
        * (seg.COMBINE_ARITY - 1)
    assert seg._partial_class(merged)[0] == ("merged", 2_048)
    compile_for_chip(
        seg._build_combine_fn(merge.segment.agg, merge.key_dtypes, cap,
                              merge), filled, count)


@pytest.mark.parametrize("config_name,kinds", (
    ("nds_q5lite_sf1", {"Join", "Aggregate", "Sort"}),
    ("nds_q55lite_sf1", {"TopK"})))
def test_tail_programs_of_the_benchmark(tmp_path, one_chip, tpu_branches,
                                        config_name, kinds):
    """The ``tail`` stage of q5-lite (join the store, group by manager,
    sort) and of q55-lite (top-k of the brands), found by running the
    benchmark's plan at its rehearsal rows (``tests/test_tail_program.py``'s
    warehouse, 11 chunks) on the CPU as one device, then compiled for the chip at the shapes it ran at — which ARE the cell's:
    the padded partial's slots come from the groups (16 x 64 for 11 or 12
    chunks of 12 stores or 150 brands), not from the fact's rows."""
    from spark_rapids_jni_tpu.engine import execute, lower, optimize
    from spark_rapids_jni_tpu.engine import segment as seg
    from spark_rapids_jni_tpu.engine.executor import lowering_flags
    import test_tail_program as tp      # tests/ is on the path (rootdir)
    calls = []
    launch = seg.CompiledTail.__call__

    def tail_call(self, part, dims):
        calls.append((self, part, dims))
        return launch(self, part, dims)

    query, _, paths = tp._warehouse(str(tmp_path), config_name, 7, 11)
    opt = optimize(query.plan(paths, tp.OPEN[config_name], 64 << 20))
    physical = lower(opt, **{**lowering_flags(), "ndev": 1})
    try:
        seg.CompiledTail.__call__ = tail_call
        execute(physical)
    finally:
        seg.CompiledTail.__call__ = launch
    ((compiled, part, dims),) = calls
    assert {type(n).__name__ for n in compiled.tail.nodes} == kinds
    assert part[0][0].shape == (1_024,)
    program = compile_for_chip(
        seg._build_tail_fn(compiled.tail, compiled), on(one_chip, part),
        tuple((on(one_chip, t), jax.ShapeDtypeStruct((), jnp.int32,
                                                     sharding=one_chip))
              for t, _ in dims))
    assert "tpu_custom_call" not in program.as_text()    # XLA, no kernel


def test_groupby_padded_chunk(one_chip, tpu_branches):
    from spark_rapids_jni_tpu.ops.aggregate import groupby_padded

    def step(t):
        _, aggs, ngroups = groupby_padded(
            t, ["ss_store_sk"], [("ss_ext_sales_price", "sum"),
                                 ("ss_ext_sales_price", "count")])
        return tuple(a.data for a in aggs), ngroups

    compile_for_chip(step, on(one_chip, sales_chunk(CHUNK_ROWS)))


def test_probe_join_prepared_chunk(one_chip, tpu_branches, monkeypatch):
    """The rank probe of a chunk against a build one row above
    ``PROBE_COMPARE_MAX_BUILD`` (the method a large build keeps) by its
    exact int64 keys — one gather from the direct-address table, or with
    the table forced off a ``searchsorted``; no sort in the program either
    way — and the device half of the builds: the exact one's sort and its
    table, and the hashed one's (hash + sort) that a build keyed otherwise
    takes."""
    from spark_rapids_jni_tpu.ops import join as J
    from spark_rapids_jni_tpu.ops.hash import xxhash64
    nr = J.PROBE_COMPARE_MAX_BUILD + 1
    dates = Table([Column(dt.INT64,
                          data=jnp.arange(2_451_545, 2_451_545 + nr))],
                  ["d_date_sk"])
    keys = Table([Column(dt.INT64, data=np.zeros(CHUNK_ROWS, np.int64),
                         validity=np.ones(CHUNK_ROWS, np.bool_))],
                 ["ss_sold_date_sk"])
    for table in (True, False):
        if not table:
            monkeypatch.setattr(J, "DIRECT_MAX_SLOTS", 0)
        pb = J.prepare_build(dates, ["d_date_sk"])
        assert J.probe_method(pb.nr, pb.rk.columns) == "rank" and pb.exact
        assert (pb.direct is not None) == table
        probe = compile_for_chip(J.probe_join_prepared, on(one_chip, keys),
                                 on(one_chip, pb))
        text = probe.as_text()
        assert " sort(" not in text and (" while(" in text) != table
    compile_for_chip(lambda t: J._build_sort(xxhash64(t).data),
                     on(one_chip, dates))
    compile_for_chip(lambda c: J._exact_build_sort(c, None),
                     on(one_chip, dates.columns[0]))
    compile_for_chip(lambda c, k: J._direct_table(c, None, 1 << 14, k),
                     on(one_chip, dates.columns[0]),
                     jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip))


def test_probe_compare_real_chunk(one_chip, tpu_branches):
    """The compare probe at the benchmark's real shape — a 262,144-row
    chunk bucket against a 512-row build, with the payload select of an
    inner join: sort-free, so it compiles at full size in seconds; one
    fused compare-reduce, nothing of ``nl x nr`` materialized."""
    from spark_rapids_jni_tpu.ops import join as J
    nl, nr = 262_144, 512
    build = Table([Column(dt.INT64, data=jnp.arange(nr, dtype=jnp.int64)),
                   Column(dt.INT64, data=jnp.arange(nr, dtype=jnp.int64))],
                  ["i_item_sk", "i_brand_id"])
    pb = J.prepare_build(build, ["i_item_sk"])
    assert J.probe_method(pb.nr, pb.rk.columns) == "compare"
    keys = Table([Column(dt.INT64, data=np.zeros(nl, np.int64),
                         validity=np.ones(nl, np.bool_))], ["ss_item_sk"])
    live = np.ones(nl, np.bool_)

    def step(keys, pb, live):
        ri, matched = J.probe_join_prepared(keys, pb, left_live=live)
        brand = J.select_build_rows(pb.payload.column("i_brand_id"), ri)
        return ri, matched, brand.data, brand.validity

    compiled = compile_for_chip(step, on(one_chip, keys), on(one_chip, pb),
                                on(one_chip, live))
    text = compiled.as_text()
    assert " sort(" not in text and " gather(" not in text
    assert "tpu_custom_call" not in text        # XLA, no kernel
    assert compiled.memory_analysis().temp_size_in_bytes < nl * nr // 8


def test_sort_chunk(one_chip, tpu_branches):
    from spark_rapids_jni_tpu.ops.order import SortKey
    from spark_rapids_jni_tpu.ops.selection import sort_table
    compile_for_chip(
        lambda t: sort_table(t, [SortKey(t.columns[0], ascending=True)]),
        on(one_chip, sales_chunk(CHUNK_ROWS)))


def test_fixed_width_rows_sf1(one_chip, tpu_branches):
    """The reference's one op at the smoke's full table: 2,880,404 rows x
    (i64, i64, f64) with validity, to the wire image and back."""
    from spark_rapids_jni_tpu.ops import row_conversion as rc
    layout = rc.fixed_width_layout([dt.INT64, dt.INT64, dt.FLOAT64])
    datas = tuple(jax.ShapeDtypeStruct((SF1_ROWS,), jnp.int64,
                                       sharding=one_chip) for _ in range(3))
    masks = tuple(jax.ShapeDtypeStruct((SF1_ROWS,), jnp.bool_,
                                       sharding=one_chip) for _ in range(3))
    to_rows = compile_for_chip(
        lambda d, m: rc._to_rows_wire(layout, d, m), datas, masks)
    assert "tpu_custom_call" not in to_rows.as_text()  # XLA, no kernel
    words = SF1_ROWS * layout.row_size // 4
    compile_for_chip(
        lambda w: rc._from_planes(layout, rc._from_wire(layout, w, SF1_ROWS)),
        jax.ShapeDtypeStruct((words,), jnp.uint32, sharding=one_chip))


def test_staged_unpack(one_chip, tpu_branches):
    from spark_rapids_jni_tpu.io import staging
    rows = 32_768
    z = np.zeros(rows, np.int64)
    v = np.ones(rows, np.bool_)
    specs = [("a", dt.INT64, z, v), ("b", dt.INT64, z, v),
             ("c", dt.FLOAT64, z.astype(np.float64), v)]
    plan, total = staging._plan_for(specs)
    compile_for_chip(lambda w: staging._unpack.__wrapped__(w, plan),
                     jax.ShapeDtypeStruct((total,), jnp.uint32,
                                          sharding=one_chip))


def test_host_exchange_on_four_chips(topo, tpu_branches):
    """The two programs of the host exchange on a Mesh of the four
    described chips, at the smoke's partial-aggregate shape (12 stores)."""
    from spark_rapids_jni_tpu.ops.row_conversion import fixed_width_layout
    from spark_rapids_jni_tpu.parallel import shuffle as sh
    from spark_rapids_jni_tpu.parallel.mesh import ROW_AXIS
    mesh = Mesh(np.array(topo.devices), (ROW_AXIS,))
    assert mesh.size == 4
    rows = NamedSharding(mesh, PartitionSpec(ROW_AXIS))
    part = Table([Column(dt.INT64, data=np.zeros(12, np.int64)),
                  Column(dt.FLOAT64, data=np.zeros(12, np.int64)),
                  Column(dt.INT64, data=np.zeros(12, np.int64))],
                 ["ss_store_sk", "sales", "n"])
    specs = sh.key_specs_for(part, ["ss_store_sk"], None)
    datas = tuple(jax.ShapeDtypeStruct((12,), jnp.int64, sharding=rows)
                  for _ in range(3))
    masks = tuple(jax.ShapeDtypeStruct((12,), jnp.bool_, sharding=rows)
                  for _ in range(3))
    n_valid = jax.ShapeDtypeStruct(
        (), jnp.int64, sharding=NamedSharding(mesh, PartitionSpec()))
    sh.make_partition_counts(mesh, specs, masked=True) \
        .lower(datas, masks, n_valid).compile()
    shuffle = sh.make_shuffle(mesh, fixed_width_layout(part.dtypes()), specs,
                              sh.cap_bucket(3)) \
        .lower(datas, masks, masks[0]).compile()
    assert "all-to-all" in shuffle.as_text()


def test_q6_chunk_program_and_keyless_merge(tmp_path, one_chip,
                                            tpu_branches):
    """TPC-H Q6's chunk program (PR 40: five typed comparisons, a decimal
    multiply with its overflow check, a keyless masked sum) at the cell's
    real chunk — 262,144 rows, the bucket of 250,051 — and the merge of 16
    one-slot partials: sort-free, so both compile in seconds at full size."""
    import importlib.util
    import json

    from spark_rapids_jni_tpu.engine import execute, optimize
    from spark_rapids_jni_tpu.engine import segment as seg
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def load(rel, name):
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(root, "benchmarks", rel))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    q6 = load("queries/tpch_q6.py", "chipc_q6")
    run = load("run.py", "chipc_run")
    with open(os.path.join(root, "benchmarks", "configs",
                           "tpch_q6_sf1.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "benchmarks", "traffic",
                           "q6_1994.json")) as f:
        params = json.load(f)["params"]
    frames = q6.tables(5, {"lineitem": 4_800})
    paths = run.write_tables(frames, cfg, str(tmp_path))
    calls = {"chunk": [], "merge": []}
    chunk, merge = seg.CompiledSegment.__call__, seg.CompiledCombine.__call__

    def chunk_call(self, table, nvalid=None, prepared=(), lo=None):
        calls["chunk"].append((self, table))
        return chunk(self, table, nvalid, prepared, lo)

    def merge_call(self, partials, nreal):
        calls["merge"].append((self, partials))
        return merge(self, partials, nreal)

    try:
        seg.CompiledSegment.__call__ = chunk_call
        seg.CompiledCombine.__call__ = merge_call
        execute(optimize(q6.plan(paths, params, cfg["storage"]
                                 ["chunk_bytes"])))
    finally:
        seg.CompiledSegment.__call__ = chunk
        seg.CompiledCombine.__call__ = merge
    compiled, table = calls["chunk"][0]
    assert compiled.segment.agg.keys == () and compiled.exprs == 10
    rows = 262_144
    real = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        (rows,) + a.shape[1:], a.dtype, sharding=one_chip), table)
    count = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compile_for_chip(seg._build_fn(compiled.segment, compiled), real, count,
                     ())
    m, partials = calls["merge"][0]
    assert len(partials) == seg.COMBINE_ARITY and m.key[1][0] == 1
    compile_for_chip(
        seg._build_combine_fn(m.segment.agg, m.key_dtypes, 1, m),
        on(one_chip, partials), count)


def test_q3_chunk_program(tmp_path, one_chip, tpu_branches):
    """TPC-H Q3's programs at the cell's SF1 shapes: the chunk program (the
    rank probe of a build above ``PROBE_COMPARE_MAX_BUILD`` by its exact
    int64 keys, read from its direct-address table, the decimal product,
    the group-by in the build-row form: a scatter-add into the build's
    slots), the running sum of the stream's partials, the groups'
    compaction and the ``tail``'s top 10 by selection — none of them
    sorts — and the table's own program.  Found by running the plan small
    on the CPU, compiled with the chunk's rows at 262,144, the build's at
    145,761, the groups' slots at 16,384 and the table's at 2**23; the
    temporaries are noted against the chip's HBM."""
    import importlib.util
    import json

    from spark_rapids_jni_tpu.engine import execute, lower, optimize
    from spark_rapids_jni_tpu.engine import segment as seg
    from spark_rapids_jni_tpu.engine.executor import lowering_flags
    from spark_rapids_jni_tpu.ops import join as J
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def load(rel, name):
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(root, "benchmarks", rel))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    q3 = load("queries/tpch_q3.py", "chipc_q3")
    run = load("run.py", "chipc_run3")
    with open(os.path.join(root, "benchmarks", "configs",
                           "tpch_q3_sf1.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "benchmarks", "traffic",
                           "q3_building.json")) as f:
        params = json.load(f)["params"]
    # 90,000 orders: a build of 8,746 rows, just above the compare's 8,192
    frames = q3.tables(3, {"lineitem": 360_000, "orders": 90_000,
                           "customer": 9_000})
    paths = run.write_tables(frames, cfg, str(tmp_path))
    calls: dict = {"chunk": [], "add": [], "groups": [], "tail": []}
    chunk, tail = seg.CompiledSegment.__call__, seg.CompiledTail.__call__
    add, groups = seg._add_build_rows, seg._build_row_groups

    def chunk_call(self, table, nvalid=None, prepared=(), lo=None):
        calls["chunk"].append((self, table, tuple(prepared)))
        return chunk(self, table, nvalid, prepared, lo)

    def tail_call(self, part, dims):
        calls["tail"].append((self, part, dims))
        return tail(self, part, dims)

    def add_call(acc, part, checked):
        calls["add"].append((acc, part, checked))
        return add(acc, part, checked)

    def groups_call(acc, pb, sources, cap):
        calls["groups"].append((acc, pb, sources, cap))
        return groups(acc, pb, sources, cap)

    try:
        seg.CompiledSegment.__call__ = chunk_call
        seg.CompiledTail.__call__ = tail_call
        seg._add_build_rows, seg._build_row_groups = add_call, groups_call
        opt = optimize(q3.plan(paths, params, cfg["storage"]["chunk_bytes"]))
        execute(lower(opt, **{**lowering_flags(), "ndev": 1}))
    finally:
        seg.CompiledSegment.__call__ = chunk
        seg.CompiledTail.__call__ = tail
        seg._add_build_rows, seg._build_row_groups = add, groups
    compiled, table, prepared = calls["chunk"][0]
    (pb,) = prepared
    assert compiled.probes == ("direct",) and pb.exact
    assert pb.nr == 8_746 > J.PROBE_COMPARE_MAX_BUILD
    assert compiled.agg_form == "build" and len(compiled.key_dtypes) == 3
    (acc, _, sources, cap), = calls["groups"]
    # the cell's shapes: a 262,144-row chunk, 145,761 build rows (and the
    # offsets of a string payload column), ~11,300 groups in 16,384 slots,
    # the direct-address table of orders keys up to 6,000,000 in 2**23
    size = {table.num_rows: 262_144, pb.nr: 145_761, pb.nr + 1: 145_762,
            cap: 16_384, pb.direct.shape[0]: 1 << 23}

    def at_sf1(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            tuple(size.get(d, d) if i == 0 else d
                  for i, d in enumerate(a.shape)), a.dtype,
            sharding=one_chip), tree)

    count = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    a, p, checked = calls["add"][0]
    (tl, part, dims), = calls["tail"]
    programs = [
        compile_for_chip(seg._build_fn(compiled.segment, compiled),
                         at_sf1(table), count, at_sf1(prepared)),
        compile_for_chip(lambda x, y: add(x, y, checked), at_sf1(a),
                         at_sf1(p)),
        compile_for_chip(lambda x, y: groups(x, y, sources, 16_384),
                         at_sf1(acc), at_sf1(pb)),
        compile_for_chip(seg._build_tail_fn(tl.tail, tl), at_sf1(part),
                         tuple((at_sf1(t), count) for t, _ in dims))]
    for program in programs:
        text = program.as_text()
        assert " sort(" not in text and "tpu_custom_call" not in text
        # tens of MB at most against 16 GB; PERF.md has the cell's count
        assert program.memory_analysis().temp_size_in_bytes < HBM_BYTES // 64
    # the table's scatter sorts its 145,761 indices on the chip: a prepare
    # program, run once per process and build, beside the build's own sort
    table = compile_for_chip(
        lambda c, k: J._direct_table(c, None, 1 << 23, k),
        at_sf1(pb.rk.columns[0]), at_sf1(pb.kmin))
    assert table.memory_analysis().output_size_in_bytes == 4 << 23
