"""The deployment of the benchmark's cell ``q5lite_sf10_year`` (configuration
``nds_q5lite_sf10``: NDS q5-lite at TPC-DS SF10, a 108-chunk stream) and what
it forced in the program — a streamed aggregate that FOLDS its padded partials
as it runs (``engine/segment.py::StreamedPartials``) — on the CPU.

The cell's plan over a 240,000-row warehouse with the configuration's 102
stores and 120 row groups (one chunk each, as at full size), cut to files of
1, 15, 16, 17, 33 and 108 chunks and served by ONE bridge child — with the
date window opened to every sale, so that every chunk holds all the stores,
and once per seed with the cell's own ``year`` parameters (a third of its 108
chunks lie before the window and hand in empty partials):

- (a) for three seeds every result equals the plain pandas reference and the
  one-merge result (the same rows in 16 row groups) byte for byte;
- (b) a stream of at most 16 chunks leaves the counts it always left
  (``engine.host_sync`` 2, one merge, no fold), every chunk program's
  aggregate in the dense form (``engine.agg.dense``); a longer one folds
  ``ceil((chunks - 16) / 15)`` times, pays one ``combine-fold-sizing`` sync
  per fold, holds at most 16 padded partials, and launches ``folds + 1``
  merges;
- (c) no stream compiles a merge of more than 16 partials, and streams of 17,
  33 and 108 chunks run the same two merge programs: after the first, none
  misses the segment cache;
- (d) a file whose later chunks bring keys the first 16 never had, and one
  whose merged partial outgrows every chunk's, are answered exactly, with no
  degraded or interpreted step;
- (e) ``min`` / ``max`` / ``count_all`` / ``count`` / integer ``sum`` and a
  nullable key through two folds, in process, against pandas;
- (f) the fold's spans under ``SRJT_TRACE=1``;
- the configuration file's counts, and the benchmark's four new readers on
  known inputs (``None`` where there is nothing to read).
"""

import importlib.util
import json
import os
import sys
import types

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_jni_tpu.bridge import BridgeClient
from spark_rapids_jni_tpu.bridge.client import spawn_server
from spark_rapids_jni_tpu.engine import (Aggregate, Scan, execute, new_stats,
                                         optimize)
from spark_rapids_jni_tpu.engine import segment as sg
from spark_rapids_jni_tpu.utils import config as cfg
from spark_rapids_jni_tpu.utils import metrics, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
SEEDS = (7, 20, 2147483777)
CELL = "q5lite_sf10_year"
ARITY = sg.COMBINE_ARITY


def _load(path, name):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
ENTRY = {w["name"]: w for w in BENCHMARK["workloads"]}[CELL]
CONFIG = _json("configs", ENTRY["config"] + ".json")
PARAMS = _json("traffic", ENTRY["traffic"] + ".json")["params"]
QUERY = _load(os.path.join(BENCH, "queries", CONFIG["query"] + ".py"),
              "sf10test_query")

GROUPS = CONFIG["tables"][QUERY.FACT]["row_groups"]         # 120
GROUP_ROWS = 2_000
FACT_ROWS = GROUPS * GROUP_ROWS                             # 240,000
PRUNED = 12                     # row groups below the traffic's `fact_lo`
CUTS = (1, 15, 16, 17, 33, GROUPS - PRUNED)     # chunks streamed
#: the cell's parameters with the date window opened to every sale
LIVE = {**PARAMS, "window_lo": QUERY.SOLD_LO, "window_hi": QUERY.SOLD_HI}


def folds_of(chunks: int) -> int:
    """Mid-stream merges of a stream of ``chunks`` chunks: the first when a
    17th chunk comes, then one per 15 more."""
    return 0 if chunks <= ARITY else -(-(chunks - ARITY) // (ARITY - 1))


def _write(df, path, row_group_size):
    pq.write_table(pa.Table.from_pandas(df, preserve_index=False), path,
                   compression=CONFIG["storage"]["compression"],
                   row_group_size=row_group_size)
    return path


def _cut(frames, chunks):
    """The warehouse with the fact cut to the first ``chunks`` row groups
    that the cell's ``fact_lo`` leaves of it (the whole file for 108)."""
    if chunks == GROUPS - PRUNED:
        return frames
    lo = PRUNED * GROUP_ROWS
    fact = frames[QUERY.FACT].iloc[lo:lo + chunks * GROUP_ROWS]
    return {**frames, QUERY.FACT: fact.reset_index(drop=True)}


class _Served:
    """One bridge child with the configuration's ``server_env``."""

    def __init__(self, root):
        self.root = root
        sock = os.path.join(root, "sf10.sock")
        self.proc = spawn_server(sock, env=dict(CONFIG["server_env"]),
                                 timeout=180)
        self.client = BridgeClient(sock, timeout=900)
        self.n = 0

    def paths(self, frames, fact_groups):
        """The three files; the fact in ``fact_groups`` row groups."""
        self.n += 1
        out = {}
        for name, df in frames.items():
            groups = fact_groups if name == QUERY.FACT \
                else CONFIG["tables"][name]["row_groups"]
            out[name] = _write(
                df, os.path.join(self.root, f"{name}.{self.n}.parquet"),
                -(-len(df) // groups))
        return out

    def query(self, paths, params=LIVE):
        """(exported columns, the query's own summary)."""
        blob = QUERY.plan(paths, params,
                          CONFIG["storage"]["chunk_bytes"]).serialize()
        (h,) = self.client.execute_plan(blob)
        cols = self.client.export_host(h)
        self.client.release(h)
        mine = [q for q in self.client.metrics()["queries"]
                if q.get("trace_id") == self.client.trace_id][-1]
        assert mine["outcome"]["status"] == "ok"
        return cols, mine

    def close(self):
        try:
            self.client.shutdown_server()
            self.proc.wait(timeout=60)
        finally:
            self.client.close()
            if self.proc.poll() is None:
                self.proc.kill()
                self.proc.wait()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Per seed and cut: the reference, the first (cold) and a second (warm)
    execution with their summaries, and the one-merge result.  The longest
    stream of the first seed runs first, so every later stream finds its
    merge programs compiled.  Last per seed, under ``"year"``: the whole
    file with the cell's own parameters."""
    child = _Served(str(tmp_path_factory.mktemp("sf10")))
    rows = {t: spec["rows"] for t, spec in CONFIG["tables"].items()}
    rows[QUERY.FACT] = FACT_ROWS
    runs, whole = {}, None
    try:
        for seed in SEEDS:
            frames = QUERY.tables(seed, rows)
            for chunks in sorted(CUTS, reverse=True):
                cut = _cut(frames, chunks)
                groups = GROUPS if chunks == GROUPS - PRUNED else chunks
                paths = child.paths(cut, groups)
                whole = paths if cut is frames else whole
                first, cold = child.query(paths)
                again, warm = child.query(paths)
                one, one_q = child.query(child.paths(cut, min(groups, ARITY)))
                runs[seed, chunks] = {
                    "want": QUERY.reference(cut, LIVE), "first": first,
                    "again": again, "cold": cold, "warm": warm, "one": one,
                    "one_query": one_q}
            first, cold = child.query(whole, PARAMS)
            runs[seed, "year"] = {"want": QUERY.reference(frames, PARAMS),
                                  "first": first, "cold": cold}
        runs["end"] = child.client.metrics()
        runs["live_handles"] = child.client.live_count()
    finally:
        child.close()
    return runs


def _same_bytes(cols, want):
    assert len(cols) == len(want.columns)
    for name, (_, got, valid) in zip(want.columns, cols):
        assert valid is None or np.asarray(valid).all()
        ref = want[name].to_numpy()
        assert got.dtype == ref.dtype and got.tobytes() == ref.tobytes(), name


every_run = pytest.mark.parametrize(
    "seed,chunks", [(s, c) for s in SEEDS for c in CUTS])


# -- (a) folded == one merge == pandas, byte for byte ----------------------------

@every_run
def test_result_equals_reference_and_one_merge(served, seed, chunks):
    run = served[seed, chunks]
    assert run["cold"]["stats"]["chunks"] == chunks
    assert min(chunks, ARITY - 1) <= run["one_query"]["stats"]["chunks"] \
        <= ARITY
    assert run["one_query"]["counters"].get("engine.combine.folds", 0) == 0
    # every sale but the 171 of the first row group below `fact_lo`
    assert len(run["want"]) == 4 and run["want"]["n"].sum() \
        == chunks * GROUP_ROWS - 171
    for cols in (run["first"], run["again"], run["one"]):
        _same_bytes(cols, run["want"])
    assert served["live_handles"] == 0


# -- (b) the counts ----------------------------------------------------------------

@every_run
def test_counts_of_a_stream(served, seed, chunks):
    folds = folds_of(chunks)
    assert [folds_of(n) for n in (1, 16, 17, 31, 32, 33, 108, 1080)] \
        == [0, 0, 1, 1, 2, 2, 7, 71]
    for q in (served[seed, chunks]["cold"], served[seed, chunks]["warm"]):
        c, h = q["counters"], q["histograms"]
        assert c["engine.host_sync"] == 2 + folds
        assert c.get("engine.combine.folds", 0) == folds
        assert c.get("engine.combine.replay", 0) \
            + c.get("engine.combine.compile", 0) == folds + 1
        assert h["engine.combine_s"]["count"] == folds + 1
        assert h["engine.sync_wait_s"]["count"] == 2 + folds
        held = h["engine.stream.partials_held"]
        assert held["count"] == 1
        assert held["max"] == min(chunks, ARITY) <= ARITY
        assert c["engine.segment.replay"] \
            + c.get("engine.segment.compile", 0) == chunks
        # 102 stores: every chunk program's aggregate is the dense form
        # over 128 key slots
        assert c.get("engine.agg.dense", 0) == chunks
        assert c.get("engine.agg.sorted", 0) == 0
        # one transfer buffer per staged blob, from the free list or new:
        # the chunks' and the dimension scans'
        assert c.get("io.scan.stage.reused", 0) \
            + c.get("io.scan.stage.fresh", 0) \
            == h["io.scan.stage_s"]["count"] \
            == h["io.scan.stage.pack_s"]["count"] >= chunks
        # PR 38: the tail's own waits, without the folds' (inside the stream)
        tail_wait = h["engine.post_stream.sync_wait_s"]
        assert tail_wait["count"] == 1
        if folds:
            assert 0 <= tail_wait["sum"] < h["engine.sync_wait_s"]["sum"]
        else:
            assert tail_wait["sum"] == pytest.approx(
                h["engine.sync_wait_s"]["sum"], abs=1e-9)
        assert tail_wait["sum"] <= h["engine.post_stream_s"]["sum"]
        for name in ("engine.precompute_s", "engine.stream.open_s",
                     "engine.stream.close_s", "engine.plan.prepare_s"):
            assert h[name]["count"] == 1, name
        assert h["engine.precompute_s"]["sum"] + h["engine.stream_s"]["sum"] \
            + h["engine.post_stream_s"]["sum"] <= h["engine.execute_s"]["sum"]
    warm = served[seed, chunks]["warm"]["counters"]
    assert warm["engine.combine.replay"] == folds + 1
    assert warm.get("engine.segment_cache.miss", 0) == 0
    assert warm.get("engine.segment.compile", 0) == 0


def test_nothing_degraded_and_the_totals_add_up(served):
    end = served["end"]
    assert not [k for k in end["counters"] if k.startswith("engine.degraded")]
    assert end["counters"].get("io.device_decode.fallbacks", 0) == 0
    # per seed: every cut cold and warm, its one-merge twin (folds nothing),
    # and the whole file once more with the cell's own parameters
    assert end["counters"]["engine.combine.folds"] == len(SEEDS) * (
        2 * sum(folds_of(c) for c in CUTS) + folds_of(CUTS[-1]))
    held = end["histograms"]["engine.stream.partials_held"]
    assert held["count"] == len(SEEDS) * (3 * len(CUTS) + 1)
    assert held["max"] == ARITY


@pytest.mark.parametrize("seed", SEEDS)
def test_the_cells_own_traffic(served, seed):
    """``year`` as the cell sends it: 108 chunks, the first 36 before the
    date window (empty partials, capacity 64), the stores' 102 groups from
    the third fold on (capacity 128) — a merge sized for what it holds."""
    run = served[seed, "year"]
    _same_bytes(run["first"], run["want"])
    assert 0 < run["want"]["n"].sum() < (GROUPS - PRUNED) * GROUP_ROWS
    c = run["cold"]["counters"]
    assert run["cold"]["stats"]["chunks"] == GROUPS - PRUNED
    assert c["engine.combine.folds"] == 7 and c["engine.host_sync"] == 9
    assert c["engine.agg.dense"] == GROUPS - PRUNED == 108
    assert c.get("engine.agg.sorted", 0) == 0
    # 16 empty partials; a merged one with 15, at either capacity and with
    # the slots either leaves it (the last is the open window's own program)
    assert c.get("engine.combine.compile", 0) == (3 if seed == SEEDS[0] else 0)


# -- (c) two merge programs, whatever the length ----------------------------------

@pytest.mark.parametrize("chunks", [33, 17, 16, 15])
def test_streams_share_the_longest_streams_programs(served, chunks):
    """After the 108-chunk stream of the first seed has run, a shorter one
    compiles no merge and misses the segment cache nowhere, on its FIRST
    execution too: 16 padded partials, and a merged one with 15 padded."""
    assert served[SEEDS[0], 108]["cold"]["counters"][
        "engine.combine.compile"] == 2
    assert served[SEEDS[0], 1]["cold"]["counters"][
        "engine.combine.compile"] == 1      # one partial: a program of its own
    cold = served[SEEDS[0], chunks]["cold"]["counters"]
    assert cold.get("engine.combine.compile", 0) == 0
    assert cold.get("engine.segment_cache.miss", 0) == 0
    assert cold.get("engine.segment.compile", 0) == 0


def test_later_seeds_compile_nothing(served):
    for seed in SEEDS[1:]:
        for chunks in CUTS + ("year",):
            c = served[seed, chunks]["cold"]["counters"]
            assert c.get("engine.combine.compile", 0) == 0, (seed, chunks)
            assert c.get("engine.segment_cache.miss", 0) == 0, (seed, chunks)


def _merge_entries():
    return [c for c in sg.SEGMENT_CACHE.snapshot_keys()
            if c[0].endswith("+combine")]


# -- (e) every combine op and a nullable key through two folds, in process --------

FOLD_AGGS = [("i", "min"), ("f", "max"), (None, "count_all"), ("f", "count"),
             ("i", "sum"), ("f", "sum")]
FOLD_NAMES = ["min_i", "max_f", "count_all", "count_f", "sum_i", "sum_f"]
FOLD_CHUNKS = 40


@pytest.fixture(scope="module")
def folded_in_process(tmp_path_factory):
    """40 chunks of 256 rows, a key with nulls, values with nulls; prices on
    the 1/4096 grid, so a float sum does not depend on its order."""
    root = tmp_path_factory.mktemp("fold")
    rng = np.random.default_rng(36)
    n = FOLD_CHUNKS * 256
    key = rng.integers(0, 90, n).astype(np.float64)
    key[rng.random(n) < 0.1] = np.nan
    f = rng.integers(2, 20_000 * 4096, n).astype(np.float64) / 4096
    f[rng.random(n) < 0.2] = np.nan
    df = pd.DataFrame({"k": pd.array(key, dtype="Int64"), "f": f,
                       "i": rng.integers(-1000, 1000, n).astype(np.int64)})
    path = str(root / "fact.parquet")
    pq.write_table(pa.Table.from_pandas(df, preserve_index=False), path,
                   row_group_size=256)
    plan = optimize(Aggregate(Scan(path, chunk_bytes=1 << 20), ["k"],
                              FOLD_AGGS, names=FOLD_NAMES))
    sg.SEGMENT_CACHE.clear()
    before = {k: tracing.counter_value(k) for k in (
        "engine.combine.folds", "engine.host_sync", "engine.degraded")}
    stats = new_stats()
    got = execute(plan, stats, fused=True)
    grew = {k: tracing.counter_value(k) - v for k, v in before.items()}
    merges = _merge_entries()
    interp = execute(plan, new_stats(), fused=False)
    return {"df": df, "got": got, "interp": interp, "stats": stats,
            "grew": grew, "merges": merges}


def _frame(table):
    out = {}
    for name, c in zip(table.names, table.columns):
        out[name] = np.where(c.validity_numpy(),
                             c.to_numpy().astype(np.float64), np.nan)
    return pd.DataFrame(out).sort_values("k", na_position="last") \
        .reset_index(drop=True)


def test_in_process_stream_folds_twice(folded_in_process):
    run = folded_in_process
    assert run["stats"]["chunks"] == FOLD_CHUNKS
    assert run["stats"]["fused_segments"] == 1
    assert run["grew"] == {"engine.combine.folds": folds_of(FOLD_CHUNKS),
                           "engine.host_sync": 2 + folds_of(FOLD_CHUNKS),
                           "engine.degraded": 0}
    assert folds_of(FOLD_CHUNKS) == 2
    # no merge program takes more than 16 partials: (capacity, key dtypes,
    # the class of every partial) is the second part of its cache key
    assert len(run["merges"]) == 2
    assert {len(key[1][2]) for key in run["merges"]} == {ARITY}
    merged = [[p[0] for p in key[1][2]] for key in run["merges"]]
    assert sorted(sum(isinstance(s, tuple) for s in m) for m in merged) \
        == [0, 1]


@pytest.mark.parametrize("name", ["k"] + FOLD_NAMES)
def test_each_combine_op_through_the_fold(folded_in_process, name):
    df = folded_in_process["df"]
    g = df.groupby("k", dropna=False)
    want = pd.DataFrame({
        "min_i": g["i"].min(), "max_f": g["f"].max(), "count_all": g.size(),
        "count_f": g["f"].count(), "sum_i": g["i"].sum(),
        "sum_f": g["f"].sum(min_count=1)}).reset_index() \
        .sort_values("k", na_position="last").reset_index(drop=True)
    got = _frame(folded_in_process["got"])
    interp = _frame(folded_in_process["interp"])
    assert len(got) == len(want) == 91           # 90 keys and the null key
    for other in (want, interp):
        a = got[name].to_numpy(dtype=np.float64)
        b = other[name].to_numpy(dtype=np.float64, na_value=np.nan)
        assert np.array_equal(a, b, equal_nan=True), name


# -- (d) an early capacity that a later merge outgrows -----------------------------

def _late_keys(frames, rng):
    """The first 16 chunks sell in 20 stores only (capacity 64); every later
    chunk in all 102 (capacity 128)."""
    fact = frames[QUERY.FACT].copy()
    early = ARITY * GROUP_ROWS
    fact.loc[:early - 1, "ss_store_sk"] = rng.integers(1, 21, early)
    return {**frames, QUERY.FACT: fact}


def _rotating_keys(frames, rng):
    """Every chunk sells in 30 stores of its own stretch of the 102, so no
    chunk passes the capacity 64 and the merged partial does."""
    fact = frames[QUERY.FACT].copy()
    chunk = np.arange(len(fact)) // GROUP_ROWS
    fact["ss_store_sk"] = (chunk * 9 + rng.integers(0, 30, len(fact))) \
        % 102 + 1
    return {**frames, QUERY.FACT: fact}


@pytest.fixture(scope="module")
def outgrown(tmp_path_factory):
    child = _Served(str(tmp_path_factory.mktemp("late")))
    rows = {t: spec["rows"] for t, spec in CONFIG["tables"].items()}
    rows[QUERY.FACT] = 40 * GROUP_ROWS
    # every row inside the date window and above `fact_lo`: 40 live chunks
    params = {**PARAMS, "window_lo": QUERY.SOLD_LO,
              "window_hi": QUERY.SOLD_HI, "fact_lo": QUERY.SOLD_LO}
    out = {}
    try:
        for name, skew in (("late", _late_keys), ("rotating", _rotating_keys)):
            frames = skew(QUERY.tables(36, rows), np.random.default_rng(5))
            cols, q = child.query(child.paths(frames, 40), params)
            out[name] = {"cols": cols, "query": q, "frames": frames,
                         "want": QUERY.reference(frames, params)}
        out["end"] = child.client.metrics()
    finally:
        child.close()
    return out


@pytest.mark.parametrize("name", ["late", "rotating"])
def test_a_merge_that_outgrows_an_earlier_capacity_is_exact(outgrown, name):
    run = outgrown[name]
    _same_bytes(run["cols"], run["want"])
    assert run["want"]["n"].sum() == 40 * GROUP_ROWS
    c = run["query"]["counters"]
    assert run["query"]["stats"]["chunks"] == 40
    assert c["engine.combine.folds"] == folds_of(40) == 2
    assert c["engine.host_sync"] == 4
    stores = run["frames"][QUERY.FACT]["ss_store_sk"].to_numpy() \
        .reshape(40, GROUP_ROWS)
    per_chunk = [len(np.unique(row)) for row in stores]
    assert len(np.unique(stores)) == 102
    assert max(per_chunk[:ARITY]) <= 64 < 102
    assert (max(per_chunk) > 64) == (name == "late")
    # the first fold is sized at 64 slots a partial, the later merges at 128
    # (a chunk's 102 stores; or the merged partial's, no chunk passing 30):
    # one program more than a file of steady keys compiles — which the second
    # file finds compiled — and no fallback
    assert c.get("engine.combine.compile", 0) == (3 if name == "late" else 0)
    assert not [k for k in outgrown["end"]["counters"]
                if k.startswith("engine.degraded")]
    assert not run["query"].get("degradations")


# -- (f) the fold's spans ------------------------------------------------------------

class _Annotation:
    """Stands in for ``jax.profiler.TraceAnnotation`` (as in
    test_combine_program.py): records the spans in the order they close."""

    log: list = []

    def __init__(self, name, **stats):
        self.rec = {"name": name, "stats": stats}

    def set_metadata(self, **stats):
        self.rec["stats"].update(stats)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        _Annotation.log.append(self.rec)
        return False


def test_fold_spans(tmp_path, monkeypatch):
    """Under ``SRJT_TRACE=1`` a 33-chunk stream leaves, inside
    ``engine.stream``, per fold one ``combine-fold-sizing`` wait and one
    ``engine.combine`` with ``fold`` and ``final=0``; after it the final
    merge (``final=1``) between ``combine-sizing`` and the compaction."""
    import jax
    rng = np.random.default_rng(1)
    n = 33 * 128
    path = str(tmp_path / "fact.parquet")
    pq.write_table(pa.table({
        "k": pa.array(rng.integers(0, 9, n).astype(np.int64)),
        "i": pa.array(rng.integers(-100, 100, n).astype(np.int64)),
    }), path, row_group_size=128)
    plan = optimize(Aggregate(Scan(path, chunk_bytes=1 << 20), ["k"],
                              [("i", "sum")], names=["s"]))
    monkeypatch.setenv("SRJT_TRACE", "1")
    monkeypatch.setenv("SRJT_RESULT_CACHE", "0")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    cfg.refresh()
    try:
        execute(plan, new_stats(), fused=True)          # compiles
        _Annotation.log = []
        with metrics.query("sf10-spans") as qm:
            stats = new_stats()
            execute(plan, stats, fused=True)
        log = _Annotation.log
    finally:
        monkeypatch.undo()
        cfg.refresh()
    assert stats["chunks"] == 33
    names = [r["name"] for r in log]
    stream_closed = names.index("engine.stream")
    waits = [(i, r["stats"]["label"]) for i, r in enumerate(log)
             if r["name"] == "engine.sync_wait"]
    assert [label for _, label in waits] == [
        "combine-fold-sizing", "combine-fold-sizing", "combine-sizing",
        "groupby-compaction"]
    assert [i < stream_closed for i, _ in waits] == [True, True, False, False]
    merges = [(i, r["stats"]) for i, r in enumerate(log)
              if r["name"] == "engine.combine"]
    assert [{k: s[k] for k in ("partials", "cap", "final")} | (
        {"fold": s["fold"]} if "fold" in s else {}) for _, s in merges] == [
        {"partials": 16, "cap": 64, "final": 0, "fold": 1},
        {"partials": 16, "cap": 64, "final": 0, "fold": 2},
        {"partials": 3, "cap": 64, "final": 1, "fold": 3}]
    assert [i < stream_closed for i, _ in merges] == [True, True, False]
    # each merge after its own sizing wait, no chunk program between them
    # (the producer thread's spans may close there)
    for (m, _), (w, _) in zip(merges, waits):
        assert w < m and "engine.fused_segment" not in names[w:m]
    s = qm.summary()
    assert s["counters"]["engine.combine.folds"] == 2
    assert s["histograms"]["engine.combine_s"]["count"] == 3
    assert s["histograms"]["engine.stream.partials_held"]["max"] == ARITY


@pytest.mark.parametrize("chunks", [17, 108])
def test_tail_waits_are_all_waits_less_the_folds(tmp_path, monkeypatch,
                                                 chunks):
    """`engine.post_stream.sync_wait_s` is `engine.sync_wait_s` less exactly
    the waits observed before the stream ended — one per fold."""
    rng = np.random.default_rng(chunks)
    n = chunks * 128
    path = str(tmp_path / "fact.parquet")
    pq.write_table(pa.table({
        "k": pa.array(rng.integers(0, 9, n).astype(np.int64)),
        "i": pa.array(rng.integers(-100, 100, n).astype(np.int64)),
    }), path, row_group_size=128)
    plan = optimize(Aggregate(Scan(path, chunk_bytes=1 << 20), ["k"],
                              [("i", "sum")], names=["s"]))
    execute(plan, new_stats())      # compiled: the run below is a warm one
    seen = []
    real = metrics.observe

    def observe(name, value, cpu=None):
        seen.append((name, value))
        real(name, value, cpu)

    monkeypatch.setattr(metrics, "observe", observe)
    monkeypatch.setattr(tracing, "_observe", observe)
    with metrics.query("folds") as qm:
        stats = new_stats()
        execute(plan, stats)
    assert stats["chunks"] == chunks
    names = [name for name, _ in seen]
    end = names.index("engine.stream_s")
    in_stream = [v for name, v in seen[:end] if name == "engine.sync_wait_s"]
    after = [v for name, v in seen[end:] if name == "engine.sync_wait_s"]
    assert len(in_stream) == folds_of(chunks) and len(after) == 2
    (tail_wait,) = [v for name, v in seen
                    if name == "engine.post_stream.sync_wait_s"]
    assert tail_wait == pytest.approx(sum(after), abs=1e-12)
    h = qm.summary()["histograms"]
    assert h["engine.sync_wait_s"]["sum"] - tail_wait \
        == pytest.approx(sum(in_stream), abs=1e-12)
    assert h["engine.post_stream.sync_wait_s"]["count"] == 1


def test_a_short_stream_has_no_fold_stat(tmp_path, monkeypatch):
    import jax
    path = str(tmp_path / "fact.parquet")
    pq.write_table(pa.table({
        "k": pa.array(np.arange(16 * 64, dtype=np.int64) % 7),
        "i": pa.array(np.arange(16 * 64, dtype=np.int64)),
    }), path, row_group_size=64)
    plan = optimize(Aggregate(Scan(path, chunk_bytes=1 << 20), ["k"],
                              [("i", "max")], names=["m"]))
    monkeypatch.setenv("SRJT_TRACE", "1")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    cfg.refresh()
    try:
        _Annotation.log = []
        stats = new_stats()
        execute(plan, stats, fused=True)
        log = _Annotation.log
    finally:
        monkeypatch.undo()
        cfg.refresh()
    assert stats["chunks"] == ARITY
    (merge,) = [r["stats"] for r in log if r["name"] == "engine.combine"]
    assert "fold" not in merge
    assert (merge["partials"], merge["final"]) == (ARITY, 1)
    assert [r["stats"]["label"] for r in log
            if r["name"] == "engine.sync_wait"] \
        == ["combine-sizing", "groupby-compaction"]


# -- the configuration file ------------------------------------------------------------

def test_configuration_counts():
    fact = CONFIG["tables"][QUERY.FACT]
    assert (fact["rows"], fact["row_groups"]) == (28_800_991, 120)
    assert CONFIG["tables"]["store"]["rows"] == 102
    assert CONFIG["tables"]["date_dim"]["rows"] == 73_049
    assert CONFIG["reduced"] == [] and CONFIG["scale_factor"] == 10
    assert CONFIG["server_env"] == {"SRJT_RESULT_CACHE": "0"}
    assert CONFIG["query"] == "nds_q5lite"
    sf1 = _json("configs", "nds_q5lite_sf1.json")
    for key in ("storage", "guarantees", "deployment", "server_env",
                "rehearsal_rows", "query"):
        assert CONFIG[key] == sf1[key], key
    assert ENTRY["chips"] == 1 and ENTRY["traffic"] == "year"
    entry = {c["name"]: c for c in BENCHMARK["configs"]}[ENTRY["config"]]
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    assert "table 3-2" in entry["source"] and "query5" in entry["source"]


def test_fact_lo_keeps_108_of_120_row_groups():
    """What the reader's pruning does at full size, from the dates alone: a
    row group is skipped when its last date is below ``fact_lo``."""
    fact = CONFIG["tables"][QUERY.FACT]
    dates = QUERY.sold_dates(fact["rows"])
    size = -(-fact["rows"] // fact["row_groups"])       # run.py's rule
    assert size == 240_009
    last = dates[np.minimum(np.arange(1, fact["row_groups"] + 1) * size,
                            fact["rows"]) - 1]
    kept = int((last >= PARAMS["fact_lo"]).sum())
    assert kept == 108 and folds_of(kept) == 7
    assert last[11] == 2_450_998 < PARAMS["fact_lo"] <= last[12]
    # the same 8 MiB chunks: a row group is one chunk, in SF1's row bucket
    assert size * 3 * 8 <= CONFIG["storage"]["chunk_bytes"]
    from spark_rapids_jni_tpu.ops.parquet_decode import bucket
    assert bucket(size) == bucket(240_034) == 262_144


def test_new_metrics_list_the_new_cell_alone():
    new = {m["name"]: m for m in BENCHMARK["per_layer"]
           if m.get("workloads") == [CELL]}
    assert sorted(new) == ["combine_device_ms", "combine_ms", "hbm_peak_mb",
                           "stream_partials_held"]
    assert {m["moves"] for m in new.values()} == {"fact_rows_per_s"}
    # PR 38's six request-path readers list the six cells they were
    # accepted with, this one among them (a later cell is not appended to
    # an accepted entry)
    accepted_with = ["q5lite_sf1_year", "q55lite_sf1_nov1999",
                     "q5lite_sf1_14day", "q5lite_sf1_mesh4", "q5lite_sf1_c4",
                     CELL]
    for m in BENCHMARK["per_layer"]:
        if m["name"] not in new and m.get("workloads") != accepted_with:
            assert CELL not in m.get("workloads", ()), m["name"]
    # the request path's six, and the keyed chunk aggregate's form
    assert sum(m.get("workloads") == accepted_with
               for m in BENCHMARK["per_layer"]) == 7
    e2e = [m["name"] for m in BENCHMARK["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]]
    assert e2e == ["fact_rows_per_s", "setup_s"]


# -- the four readers -------------------------------------------------------------------

def _reader(name):
    return _load(os.path.join(BENCH, "layer_metrics", name + ".py"),
                 "sf10test_" + name).read


def _ctx(start=None, end=None, queries=4, trace=None, memory=None):
    loop = types.SimpleNamespace(
        samples=[(0, float(i), 1.0) for i in range(queries)] + [(0, 9.0, None)],
        t_start=0.0, t_end=8.0, clients=[])
    return {"loop": loop, "trace": trace,
            "snap_start": {"histograms": start or {}, "counters": {}},
            "snap_end": {"histograms": end or {}, "counters": {},
                         "device": {"memory": memory}}}


@pytest.mark.parametrize("memory,want", [
    ({"peak_bytes_in_use": 256 * 2 ** 20, "bytes_in_use": 1}, 256.0),
    ({"bytes_in_use": 1}, None), (None, None)])
def test_reader_hbm_peak_mb(memory, want):
    assert _reader("hbm_peak_mb")(_ctx(memory=memory)) == want
    if memory is None:
        ctx = _ctx()
        del ctx["snap_end"]["device"]
        assert _reader("hbm_peak_mb")(ctx) is None


@pytest.mark.parametrize("start,end,want", [
    ({"sum": 32.0, "count": 2}, {"sum": 96.0, "count": 6}, 16.0),
    (None, {"sum": 33.0, "count": 3}, 11.0),
    ({"sum": 32.0, "count": 2}, {"sum": 32.0, "count": 2}, None),
    (None, None, None)])
def test_reader_stream_partials_held(start, end, want):
    name = "engine.stream.partials_held"
    ctx = _ctx({name: start} if start else {}, {name: end} if end else {})
    assert _reader("stream_partials_held")(ctx) == want


@pytest.mark.parametrize("start,end,queries,want", [
    ({"sum": 1.0, "count": 8}, {"sum": 1.4, "count": 40}, 4, 100.0),
    (None, {"sum": 0.2, "count": 8}, 1, 200.0),
    ({"sum": 1.0, "count": 8}, {"sum": 1.0, "count": 8}, 4, None),
    (None, {"sum": 0.2, "count": 8}, 0, None),
    (None, None, 4, None)])
def test_reader_combine_ms(start, end, queries, want):
    name = "engine.combine_s"
    ctx = _ctx({name: start} if start else {}, {name: end} if end else {},
               queries=queries)
    got = _reader("combine_ms")(ctx)
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("scopes,want", [
    ({"engine.combine": 0.003, "engine.fused_segment": 0.9}, 2.0),
    ({"engine.fused_segment": 0.9}, None), (None, None)])
def test_reader_combine_device_ms(scopes, want):
    """0.003 device-seconds in a 3 s stretch, 8 s window, 4 queries: 2 ms."""
    trace = None if scopes is None else {"scopes": scopes, "window_s": 3.0}
    got = _reader("combine_device_ms")(_ctx(trace=trace))
    assert got == (None if want is None else pytest.approx(want))
