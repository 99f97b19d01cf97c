"""Static-analysis subsystem tests (engine/verify.py + tools/srjt_lint.py).

Three layers, mirroring docs/ANALYSIS.md:

- plan verifier: every build-time check has a failing-plan AND a
  passing-plan case; errors are structured (code + node path);
  ``optimize`` re-verifies after every rewrite rule, so a deliberately
  broken rule raises ``rewrite-schema-change`` instead of producing a
  wrong answer; ``SRJT_VERIFY=0`` turns the whole layer off.
- compiled-artifact lint: the smoke plans' fused segments lower to clean
  jaxprs; the static sync budget is EXACTLY the three whitelisted host
  syncs and cross-checks the runtime ``engine.host_sync`` counter; an
  injected ``float()`` inside a traced path is caught statically; the
  shape-class census flags a fingerprint retraced across too many row
  buckets.
- repo AST lint: the tools/srjt_lint.py rules fire on synthetic sources
  and the CLI exits nonzero on a non-baselined violation.
"""

import importlib.util
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_jni_tpu.engine import (
    Aggregate, Filter, Join, Limit, Project, Scan, Sort, TopK,
    PlanVerificationError, col, lit, node_label, optimize, verify,
)
from spark_rapids_jni_tpu.engine import executor, optimizer
from spark_rapids_jni_tpu.engine import plan as plan_mod
from spark_rapids_jni_tpu.engine.verify import (
    SYNC_WHITELIST, check_sync_budget, lint_plan_artifacts,
    lint_segment_cache, sync_budget,
)
from spark_rapids_jni_tpu.utils import metrics
from spark_rapids_jni_tpu.utils import config as config_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    """Same two-table layout as test_engine_plan's fixture."""
    root = tmp_path_factory.mktemp("verify")
    pq.write_table(pa.table({
        "f_key": pa.array(np.arange(100, dtype=np.int64)),
        "f_store": pa.array(np.arange(100, dtype=np.int64) % 7),
        "f_price": pa.array(np.arange(100, dtype=np.float64)),
        "f_unused": pa.array(np.zeros(100, np.int64)),
    }), root / "fact.parquet")
    pq.write_table(pa.table({
        "d_key": pa.array(np.arange(100, dtype=np.int64)),
        "d_name": pa.array([f"n{i}" for i in range(100)]),
        "d_unused": pa.array(np.zeros(100, np.int64)),
    }), root / "dim.parquet")
    return root


@pytest.fixture(scope="module")
def warehouse(tmp_path_factory):
    """The q5-lite pipeline warehouse + plans, at test size."""
    from spark_rapids_jni_tpu.engine.fuzz import (pipeline_plans,
                                                  pipeline_warehouse)
    root = str(tmp_path_factory.mktemp("wh"))
    rng = np.random.default_rng(7)
    pipeline_warehouse(root, 2000, rng)
    q5, chunked = pipeline_plans(root, 24_000)
    return {"q5": q5, "chunked": chunked}


# -- verifier checks: failing plan + passing plan per code ------------------

_CHECK_MATRIX = [
    # (check code, failing builder, passing builder)
    ("unknown-column",
     lambda f, d: Filter(Scan(f), (">", col("nope"), lit(1))),
     lambda f, d: Filter(Scan(f), (">", col("f_key"), lit(1)))),
    ("unknown-column",
     lambda f, d: Project(Scan(f), ("f_key", "ghost")),
     lambda f, d: Project(Scan(f), ("f_key", "f_price"))),
    ("unknown-column",
     lambda f, d: Scan(f, columns=("f_key", "ghost")),
     lambda f, d: Scan(f, columns=("f_key",))),
    ("unknown-column",
     lambda f, d: Aggregate(Scan(f), ("ghost",), (("f_price", "sum"),)),
     lambda f, d: Aggregate(Scan(f), ("f_store",), (("f_price", "sum"),))),
    ("unknown-column",
     lambda f, d: Sort(Scan(f), (("ghost", True),)),
     lambda f, d: Sort(Scan(f), (("f_key", True),))),
    ("unknown-column",
     lambda f, d: Join(Scan(f), Scan(d), ("f_key",), ("ghost",)),
     lambda f, d: Join(Scan(f), Scan(d), ("f_key",), ("d_key",))),
    ("join-key-dtype-mismatch",
     lambda f, d: Join(Scan(f), Scan(d), ("f_price",), ("d_key",)),
     lambda f, d: Join(Scan(f), Scan(d), ("f_key",), ("d_key",))),
    ("join-key-dtype-mismatch",
     lambda f, d: Join(Scan(d), Scan(f), ("d_name",), ("f_key",)),
     lambda f, d: Join(Scan(d), Scan(f), ("d_key",), ("f_key",))),
    ("invalid-cast",
     lambda f, d: Filter(Scan(d), (">", col("d_name"), lit(3))),
     # string vs string comparison is fine (the optimizer's right-side
     # push test relies on it)
     lambda f, d: Filter(Scan(d), ("==", col("d_name"), lit("n7")))),
    ("invalid-cast",
     lambda f, d: Filter(Scan(d), ("&", col("d_name"), col("d_key"))),
     lambda f, d: Filter(Scan(d), ("&", (">", col("d_key"), lit(1)),
                                   ("<", col("d_key"), lit(9))))),
    ("aggregate-over-string",
     lambda f, d: Aggregate(Scan(d), ("d_key",), (("d_name", "sum"),)),
     # order stats / counts over strings are legal
     lambda f, d: Aggregate(Scan(d), ("d_key",), (("d_name", "min"),
                                                  ("d_name", "count")))),
]


@pytest.mark.parametrize("code,bad,good",
                         _CHECK_MATRIX,
                         ids=[f"{c}-{i}" for i, (c, _, _)
                              in enumerate(_CHECK_MATRIX)])
def test_check_matrix(files, code, bad, good):
    f, d = files / "fact.parquet", files / "dim.parquet"
    with pytest.raises(PlanVerificationError) as ei:
        verify(bad(f, d))
    assert ei.value.code == code
    assert ei.value.node_path.startswith("root")
    assert verify(good(f, d)) is not None  # passing twin type-checks


def test_error_structure_and_node_path(files):
    deep = Limit(Filter(Scan(files / "fact.parquet"),
                        (">", col("nope"), lit(0))), 5)
    with pytest.raises(PlanVerificationError) as ei:
        verify(deep)
    e = ei.value
    assert (e.code, e.node_path) == ("unknown-column", "root.child")
    assert "nope" in e.message
    # wire round trip (the bridge ships errors this way)
    back = PlanVerificationError.from_dict(e.to_dict())
    assert (back.code, back.node_path, back.message) == \
        (e.code, e.node_path, e.message)
    assert "unknown-column at root.child" in str(back)


def test_unknown_scan_schema_is_tolerated():
    # missing files verify as "schema unknown" (None), not an error — the
    # executor keeps owning I/O failures
    assert verify(Scan("/nonexistent/q.parquet")) is None
    assert verify(Filter(Scan("/nonexistent/q.parquet"),
                         (">", col("anything"), lit(1)))) is None


def test_join_output_schema_suffixes_and_semi(files):
    f, d = files / "fact.parquet", files / "dim.parquet"
    fact2 = Scan(f)
    # self-join: colliding non-key right columns pick up the _r suffix
    out = verify(Join(Scan(f), fact2, ("f_key",), ("f_store",)))
    assert "f_key_r" in out and "f_price_r" in out
    # semi joins output only the left schema
    semi = verify(Join(Scan(f), Scan(d), ("f_key",), ("d_key",), "semi"))
    assert list(semi) == ["f_key", "f_store", "f_price", "f_unused"]


def test_optimize_rejects_bad_plan_before_execution(files):
    with pytest.raises(PlanVerificationError) as ei:
        optimize(Filter(Scan(files / "fact.parquet"),
                        (">", col("nope"), lit(1))))
    assert ei.value.code == "unknown-column"


def test_broken_rewrite_rule_is_caught(files, monkeypatch):
    plan = Filter(Scan(files / "fact.parquet"), (">", col("f_key"), lit(3)))
    monkeypatch.setattr(
        optimizer, "_push_filters",
        lambda node, schema, memo: Project(node, ("f_key",)))
    with pytest.raises(PlanVerificationError) as ei:
        optimize(plan)
    assert ei.value.code == "rewrite-schema-change"
    assert "push_filters" in ei.value.message


def test_srjt_verify_flag_disables(files, monkeypatch):
    plan = Filter(Scan(files / "fact.parquet"), (">", col("f_key"), lit(3)))
    monkeypatch.setattr(
        optimizer, "_push_filters",
        lambda node, schema, memo: Project(node, ("f_key",)))
    monkeypatch.setenv("SRJT_VERIFY", "0")
    config_mod.refresh()
    try:
        out = optimize(plan)  # verification off: mangled plan flows through
        assert isinstance(out, Project)
    finally:
        monkeypatch.delenv("SRJT_VERIFY")
        config_mod.refresh()
    assert config_mod.config.verify


def _plan_corpus(files):
    """Every optimizer-test plan shape over the shared fixture tables."""
    f, d = files / "fact.parquet", files / "dim.parquet"
    fact, dim = Scan(f), Scan(d)
    return [
        Aggregate(Join(Scan(f), Scan(d), ["f_key"], ["d_key"], how="inner"),
                  ["d_name"], [("f_price", "sum")], names=["sales"]),
        Filter(Join(Scan(f), Scan(d), ["f_key"], ["d_key"], how="semi"),
               ("&", (">=", col("f_key"), lit(10)),
                ("<", col("f_key"), lit(60)))),
        Filter(Join(Scan(f), Scan(d), ["f_key"], ["d_key"], how="inner"),
               ("==", col("d_name"), lit("n7"))),
        Sort(Limit(Aggregate(
            Join(Scan(f, chunk_bytes=1 << 16), Scan(d), ["f_key"],
                 ["d_key"], how="semi"),
            ["f_store"], [("f_price", "sum")], names=["sales"]), 100),
            (("sales", False),)),
        Limit(Sort(Scan(f), (("f_price", False),)), 10),
        TopK(Filter(Scan(f, chunk_bytes=1 << 14),
                    (">", col("f_price"), lit(5.0))),
             (("f_price", False),), 7),
        Project(Filter(Scan(f), ("not", ("==", col("f_store"), lit(3)))),
                ("f_key", "f_price")),
        Aggregate(Scan(f), [], [("f_price", "mean"), ("f_price", "var"),
                                (None, "count_all")]),
    ]


def test_verify_optimize_property(files):
    # the property the RewriteChecker enforces, observed from outside:
    # for every corpus plan, optimize() runs its per-rule checks clean and
    # the optimized plan re-verifies to the SAME root schema
    for p in _plan_corpus(files):
        base = verify(p)
        opt = optimize(p)
        after = verify(opt)
        assert base is not None and list(base.items()) == list(after.items())


# -- dispatch exhaustiveness + node_label -----------------------------------

def test_dispatch_tables_are_exhaustive():
    from spark_rapids_jni_tpu.engine import explain
    from spark_rapids_jni_tpu.engine import verify as verify_fn  # noqa: F401
    import importlib
    verify_mod = importlib.import_module(
        "spark_rapids_jni_tpu.engine.verify")
    node_classes = set(plan_mod._NODE_TYPES.values())
    assert set(executor._EXEC_DISPATCH) == node_classes
    assert set(explain._DESCRIBE) == node_classes
    assert set(verify_mod._INFER) == node_classes


def test_node_label_agrees_everywhere(files):
    s = Scan(files / "fact.parquet")
    assert node_label(s) == "scan"
    assert node_label(Limit(s, 1)) == "limit"
    # explain renders and metrics spans use the same labels
    from spark_rapids_jni_tpu.engine.explain import explain_analyze
    rep = explain_analyze(Limit(Filter(s, (">", col("f_key"), lit(90))), 3))
    all_labels = {cls.__name__.lower()
                  for cls in plan_mod._NODE_TYPES.values()}
    assert {n["label"] for n in rep.nodes} <= all_labels
    assert rep.result.num_rows == 3


# -- compiled-artifact lint -------------------------------------------------

def test_sync_budget_matches_whitelist_and_runtime(warehouse):
    opt = {k: optimize(p) for k, p in warehouse.items()}
    entries, bad = check_sync_budget(list(opt.values()))
    assert bad == []
    # the pinned contract: exactly 3 deliberate syncs across the smoke
    # pair — q5's map-segment boundary compaction, the chunked stream's
    # combine sizing + groupby compaction.  The exchange-* whitelist
    # entries only fire on distributed plans (test_engine_dist covers
    # those), so local plans exercise the non-exchange subset exactly.
    assert sum(e["count"] for e in entries) == 3
    active = sorted(e["site"] for e in entries if e["count"])
    assert active == ["combine-sizing", "groupby-compaction",
                      "segment-boundary-compaction"]
    assert set(active) <= set(SYNC_WHITELIST)
    # runtime cross-check: executing both plans pays exactly the counter
    # the static model predicts
    ran = 0
    for p in opt.values():
        with metrics.query("verify-sync-crosscheck") as qm:
            executor.execute(p)
        ran += qm.summary()["counters"].get("engine.host_sync", 0)
    assert ran == 3


def test_q5_sync_budget_detail(warehouse):
    q5 = optimize(warehouse["q5"])
    entries = sync_budget(q5)
    assert [(e["site"], e["count"]) for e in entries] == \
        [("segment-boundary-compaction", 1)]
    chunked = optimize(warehouse["chunked"])
    assert sorted((e["site"], e["count"]) for e in sync_budget(chunked)) == \
        [("combine-sizing", 1), ("groupby-compaction", 1)]


def test_sync_budget_of_a_plan_with_a_tail_is_two(warehouse):
    """Lowered for one device, a Sort above the chunked stream's Aggregate
    is a ``tail`` stage: the stream pays its sizing, the tail the ONE
    compaction of both — and the run pays exactly those; for this
    process's mesh the same plan is charged what it was."""
    chunked = optimize(Sort(warehouse["chunked"], (("ss_store_sk", True),)))
    assert sorted((e["site"], e["count"], e["path"])
                  for e in sync_budget(chunked, ndev=1)) == \
        [("combine-sizing", 1, "root.child"), ("tail-compaction", 1, "root")]
    assert sorted(e["site"] for e in sync_budget(chunked) if e["count"]) == \
        ["combine-sizing", "groupby-compaction"]
    entries, bad = check_sync_budget([chunked], ndev=1)
    assert bad == [] and "tail-compaction" in SYNC_WHITELIST
    from spark_rapids_jni_tpu.engine import lower
    physical = lower(chunked, **{**executor.lowering_flags(), "ndev": 1})
    with metrics.query("verify-tail-crosscheck") as qm:
        executor.execute(physical)
    assert qm.summary()["counters"]["engine.host_sync"] == 2


def test_smoke_plans_fused_and_prefetched_match_interpreter(warehouse):
    """Both smoke plans give one answer fused or interpreted, serial or
    double-buffered; the chunked plan streams its chunks through a fused
    segment."""
    def by_key(t):   # both plans' first column is a unique group key
        cols = np.stack([np.asarray(c.to_numpy(), np.float64)
                         for c in t.columns])
        return cols[:, np.argsort(cols[0], kind="stable")]

    for name, plan in warehouse.items():
        opt = optimize(plan)
        want = None
        for fused in (True, False):
            for prefetch in (0, 2):
                stats = executor.new_stats()
                got = by_key(executor.execute(opt, stats, fused=fused,
                                              prefetch=prefetch))
                want = got if want is None else want
                np.testing.assert_allclose(got, want, rtol=1e-9,
                                           err_msg=f"{name} {fused} "
                                                   f"{prefetch}")
                if name == "chunked":
                    assert stats["chunks"] > 1
                    assert bool(stats["fused_segments"]) == fused


def test_artifact_lint_clean_on_smoke_plans(warehouse):
    for name, p in warehouse.items():
        rep = lint_plan_artifacts(optimize(p))
        assert rep["violations"] == [], (name, rep)
        linted = [s for s in rep["segments"] if "skipped" not in s]
        assert linted and all(s["ok"] for s in linted)
        assert all(s["primitives"] > 0 for s in linted)


def test_artifact_lint_catches_injected_item(warehouse, monkeypatch):
    # the acceptance scenario: a synthetic .item()/float() smuggled into
    # the traced filter evaluator fails the STATIC lint, no execution
    from spark_rapids_jni_tpu.engine import expr as expr_mod
    orig = expr_mod.evaluate

    def bad_eval(expr, table, ovf):
        vals, valid, dt = orig(expr, table, ovf)
        if hasattr(vals, "sum"):
            float(vals.sum())  # concretizes the tracer
        return vals, valid, dt

    monkeypatch.setattr(expr_mod, "evaluate", bad_eval)
    rep = lint_plan_artifacts(optimize(warehouse["q5"]))
    codes = {v["code"] for v in rep["violations"]}
    assert "host-concretization" in codes


def test_shape_class_census(files):
    import jax.numpy as jnp

    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.dtypes import INT64
    from spark_rapids_jni_tpu.engine.segment import (SegmentCache,
                                                     build_segment,
                                                     parent_counts)
    p = Project(Filter(Scan(files / "fact.parquet"),
                       (">", col("f_key"), lit(10))), ("f_key",))
    seg = build_segment(p, parent_counts(p))
    assert seg is not None
    cache = SegmentCache(maxsize=64)
    # 10 distinct power-of-two row buckets -> 10 shape classes
    for rows in (1, 2, 3, 5, 9, 17, 33, 65, 129, 257):
        t = Table([Column(INT64, data=jnp.zeros((rows,), jnp.int64))],
                  ["f_key"])
        cache.get(seg, t)
    flagged = lint_segment_cache(cache, max_shape_classes=8)
    assert len(flagged) == 1
    assert flagged[0]["code"] == "shape-class-explosion"
    assert flagged[0]["shape_classes"] == 10
    assert lint_segment_cache(cache, max_shape_classes=16) == []


# -- repo AST lint (tools/srjt_lint.py) -------------------------------------

def _load_srjt_lint():
    spec = importlib.util.spec_from_file_location(
        "srjt_lint", os.path.join(ROOT, "tools", "srjt_lint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_ast_rules_fire_on_synthetic_sources():
    import ast
    lint = _load_srjt_lint()
    wl = tuple(SYNC_WHITELIST)

    def run(src, relpath):
        fl = lint._FileLint(relpath, wl)
        fl.visit(ast.parse(src))
        return [v["code"] for v in fl.out]

    traced = "spark_rapids_jni_tpu/engine/expr.py"
    assert run("def evaluate(e, t, o):\n    return float(x.sum())\n",
               traced) == ["traced-host-op"]
    assert run("def evaluate(e, t, o):\n    return x.item()\n",
               traced) == ["traced-host-op"]
    assert run("def evaluate(e, t, o):\n    return np.asarray(x)\n",
               traced) == ["traced-host-op"]
    # literal casts and code outside traced functions are fine
    assert run("def evaluate(e, t, o):\n    return float('nan')\n",
               traced) == []
    assert run("def helper(x):\n    return x.item()\n", traced) == []
    # host-sync sites need whitelisted literal labels
    eng = "spark_rapids_jni_tpu/engine/segment.py"
    assert run("metrics.host_sync()\n", eng) == ["host-sync-site"]
    assert run("metrics.host_sync(label='rogue-sync')\n",
               eng) == ["host-sync-site"]
    assert run("metrics.host_sync(label='combine-sizing')\n", eng) == []
    # env reads outside utils/config.py
    assert run("import os\nv = os.environ.get('X')\n",
               eng) == ["config-env-read"]
    assert run("import os\nv = os.environ.get('X')\n",
               "spark_rapids_jni_tpu/utils/config.py") == []


def test_repo_is_lint_clean_modulo_baseline(tmp_path):
    lint = _load_srjt_lint()
    violations = lint.ast_pass(tuple(SYNC_WHITELIST))
    violations += lint.dispatch_pass()
    baseline_path = os.path.join(ROOT, "ci", "lint-baseline.json")
    import json
    with open(baseline_path) as f:
        grandfathered = set(json.load(f)["grandfathered"])
    fresh = [v for v in violations
             if lint.baseline_key(v) not in grandfathered]
    assert fresh == [], fresh
    # The baseline burned down to empty (the historical env reads now route
    # through utils/config.py) and must stay that way — new grandfathering
    # is a regression, not a migration.
    assert grandfathered == set()
    # CLI discipline: clean against the shipped baseline, and an empty one
    # is now equivalent.  The nonzero-exit path is exercised against a
    # synthetic violation in tests/test_fuzz.py.
    assert lint.main(["--baseline", baseline_path]) == 0
    empty = tmp_path / "empty-baseline.json"
    empty.write_text('{"grandfathered": []}')
    assert lint.main(["--baseline", str(empty)]) == 0
