#!/usr/bin/env python
"""Repo lint for the engine's static invariants (docs/ANALYSIS.md pass 3).

Seven stdlib-``ast`` rules over ``spark_rapids_jni_tpu/`` + ``tools/``:

- **traced-host-op** — no ``.item()`` / ``float()`` / ``bool()`` / ``int()``
  / ``np.asarray`` / ``.tolist()`` / ``jax.device_get`` /
  ``.block_until_ready()`` inside the segment-traced code paths
  (``segment._build_fn`` / ``segment._probe_join_node`` /
  ``expr.evaluate`` and its helpers): any of these concretizes a tracer,
  turning the zero-sync fused chunk program into a per-chunk host
  round-trip.
- **config-env-read** — ``os.environ`` / ``os.getenv`` only in
  ``utils/config.py``; everything else reads the ``config`` singleton so
  ``refresh()`` stays the one switchboard.  Env *writes*
  (``os.environ.setdefault``/``os.environ[k] = v`` — how the CLI tools pin
  ``JAX_PLATFORMS`` before the first jax import) are exempt.  Pre-existing
  read sites are grandfathered in ``ci/lint-baseline.json``.
- **unlocked-global-write** — ahead of AQE's runtime re-planning (a second
  thread touching planner state), any write to a module-level mutable
  container (dict/list/set/deque assignments at module scope) from inside a
  function must sit under a ``with <lock>:`` block — mutating method calls
  (``append``/``update``/``setdefault``/...), subscript stores, ``del``,
  augmented assigns, and rebinds via ``global``.  Two exemptions: writes at
  module scope (import-time is single-threaded) and functions whose
  docstring carries the ``(lock held)`` convention (see faults._arm),
  which asserts the caller already owns the lock.
- **host-sync-site** — every ``metrics.host_sync(...)`` call site must
  carry a ``label=`` that is a literal member of ``verify.SYNC_WHITELIST``:
  adding a fourth deliberate sync means adding it to the whitelist, in
  one reviewable diff.
- **bare-except** — no bare ``except:`` under ``bridge/`` / ``engine/`` /
  ``parallel/`` / ``utils/`` / ``tools/``: the recovery layer
  (engine/recovery.py) dispatches on the ``utils/errors`` taxonomy, and a
  bare catch swallows cancellation and resource exhaustion
  indistinguishably.
- **executor-import** — no module imports an underscore name of
  ``engine/executor.py``, and ``engine/physical.py`` / ``engine/verify.py``
  import nothing from it: the choice of stage form is ``physical.lower``'s
  alone, and the verifier budgets what it returns.
- **unregistered-metric** — every literal metric name recorded through
  ``metrics.count/observe/gauge_set/gauge_max/time_add`` /
  ``tracing.count``, every ``op_scope(<name>, timed=True)`` (it observes
  ``<name>_s``) and every literal ``node_set`` span label must
  appear in the generated catalog ``docs/METRICS.md``; f-string names
  catalog with ``<var>`` placeholders.  A name in the catalog with no
  remaining call site flags ``stale-metric``.  Regenerate with
  ``--write-metrics`` — the catalog diff IS the metric-rename review.

Plus two import-time passes:

- **dispatch exhaustiveness** — every class in ``plan._NODE_TYPES`` must be
  registered in ``executor._EXEC_DISPATCH``, ``explain._DESCRIBE``,
  ``verify._INFER``, ``verify._NULLS`` (nullability lattice), and
  ``fuzz._ORACLE`` (pandas differential oracle) — a new plan node can't
  silently miss a layer.
- **``--segments``** — build the q5-lite pipeline warehouse
  (``engine/fuzz.py::pipeline_warehouse``) in a tempdir, lower
  the optimized q5-lite + chunked plans' fused segments to jaxprs
  (``verify.lint_plan_artifacts``, nothing executes) and assert the static
  sync budget is EXACTLY the three whitelisted host syncs.  ``--full``
  extends the plan set with a chunked join + top-k shape (nightly).

Usage::

    python tools/srjt_lint.py --baseline ci/lint-baseline.json
    python tools/srjt_lint.py --segments --baseline ci/lint-baseline.json
    python tools/srjt_lint.py --write-baseline   # regenerate the baseline
    python tools/srjt_lint.py --write-metrics    # regenerate docs/METRICS.md

Violations not covered by the baseline exit nonzero.
"""

from __future__ import annotations

import argparse
import ast
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = "spark_rapids_jni_tpu"

#: file (repo-relative) -> function names whose bodies are jax-traced
TRACED_FUNCS = {
    f"{PKG}/engine/segment.py": {"_build_fn", "_probe_join_node",
                                 "_build_fused_fn", "_build_decode_fn"},
    f"{PKG}/engine/expr.py": {"evaluate", "_arith", "_align", "_rescale",
                              "_as_float", "_estimate", "_flag", "_i64",
                              "project", "column_of", "sum_check",
                              "any_flag"},
}

#: modules the executor is built on: they import nothing from it
_BELOW_EXECUTOR = (f"{PKG}/engine/physical.py", f"{PKG}/engine/verify.py")

#: attribute calls that concretize a tracer / pull data to host
#: subtrees where a bare `except:` is a lint violation — the failure-domain
#: hardening (engine/recovery.py) depends on every catch being classifiable
_NO_BARE_EXCEPT = (f"{PKG}/bridge/", f"{PKG}/engine/", f"{PKG}/parallel/",
                   f"{PKG}/utils/", "tools/")

_HOST_ATTR_CALLS = {"item", "tolist", "block_until_ready"}
#: builtin casts that concretize when applied to a traced array
_HOST_NAME_CALLS = {"float", "int", "bool"}

#: constructors whose module-level assignment marks a name as shared
#: mutable state for the unlocked-global-write rule
_MUTABLE_CTORS = {"dict", "list", "set", "defaultdict", "deque",
                  "OrderedDict", "Counter", "WeakValueDictionary"}
#: method calls that mutate a container in place
_MUTATING_METHODS = {"append", "appendleft", "add", "update", "setdefault",
                     "pop", "popitem", "popleft", "clear", "extend",
                     "insert", "remove", "discard"}
#: identifier substrings that mark a `with` context as a mutual-exclusion
#: guard (threading.Lock/RLock/Condition naming conventions in this repo)
_LOCKISH = ("lock", "cond", "mutex", "_cv")
#: docstring marker asserting the caller already holds the guarding lock
_LOCK_HELD_DOC = "(lock held)"

#: registry entry points whose first argument is a metric name, and the
#: catalog kind each registers under (docs/METRICS.md)
_METRIC_FNS = {"count": "counter", "observe": "histogram",
               "gauge_set": "gauge", "gauge_max": "gauge",
               "time_add": "timer"}
#: receiver names that denote the metrics/tracing registries at call sites
#: (bridge/server.py imports the module as `_metrics`)
_METRIC_BASES = {"metrics", "_metrics", "tracing"}
#: repo-relative path of the generated metric-name catalog
METRICS_DOC = os.path.join("docs", "METRICS.md")


def _literal_metric_name(arg) -> "str | None":
    """A metric-name argument as a catalogable string: literal strings
    verbatim, f-strings with each interpolation normalized to a ``<var>``
    placeholder (so ``f"engine.errors.{kind}"`` catalogs once as
    ``engine.errors.<kind>``), fully dynamic expressions -> None
    (plumbing forwarders like ``tracing.count(name, n)`` are not call
    sites)."""
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value
    if isinstance(arg, ast.JoinedStr):
        parts = []
        for v in arg.values:
            if isinstance(v, ast.Constant):
                parts.append(str(v.value))
            elif isinstance(v, ast.FormattedValue):
                inner = v.value
                if isinstance(inner, ast.Name):
                    parts.append(f"<{inner.id}>")
                elif isinstance(inner, ast.Attribute):
                    parts.append(f"<{inner.attr}>")
                else:
                    parts.append("<?>")
        return "".join(parts)
    return None


def _module_mutable_globals(tree: ast.Module) -> set:
    """Names bound at module scope to a mutable container literal/ctor."""
    names: set = set()
    for stmt in tree.body:
        if isinstance(stmt, ast.Assign):
            targets, value = stmt.targets, stmt.value
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            targets, value = [stmt.target], stmt.value
        else:
            continue
        mutable = isinstance(value, (ast.Dict, ast.List, ast.Set,
                                     ast.ListComp, ast.SetComp,
                                     ast.DictComp)) or (
            isinstance(value, ast.Call) and (
                (isinstance(value.func, ast.Name)
                 and value.func.id in _MUTABLE_CTORS) or
                (isinstance(value.func, ast.Attribute)
                 and value.func.attr in _MUTABLE_CTORS)))
        if not mutable:
            continue
        for t in targets:
            if isinstance(t, ast.Name) and \
                    not any(s in t.id.lower() for s in _LOCKISH):
                names.add(t.id)
    return names


def _is_os_environ(node) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == "environ"
            and isinstance(node.value, ast.Name) and node.value.id == "os")


def _mentions_lock(expr) -> bool:
    for n in ast.walk(expr):
        ident = n.id if isinstance(n, ast.Name) else \
            n.attr if isinstance(n, ast.Attribute) else None
        if ident is not None and \
                any(s in ident.lower() for s in _LOCKISH):
            return True
    return False


def _violation(code: str, path: str, line: int, detail: str) -> dict:
    return {"code": code, "file": path, "line": line, "detail": detail}


def baseline_key(v: dict) -> str:
    # line numbers excluded so unrelated edits above a grandfathered
    # site don't churn the baseline
    return f"{v['code']}|{v['file']}|{v['detail']}"


class _FileLint(ast.NodeVisitor):
    def __init__(self, relpath: str, whitelist: tuple,
                 mutable_globals: set = frozenset()):
        self.relpath = relpath
        self.traced = TRACED_FUNCS.get(relpath, set())
        self.whitelist = whitelist
        self.mutable_globals = mutable_globals
        self.out: list = []
        self.metric_sites: list = []  # (name, kind, relpath, line)
        self._traced_depth = 0
        self._func_depth = 0
        self._lock_depth = 0
        self._global_decls: set = set()
        self._env_writes: set = set()  # id()s of exempt os.environ nodes

    def visit_FunctionDef(self, node):
        entered = node.name in self.traced
        if entered:
            self._traced_depth += 1
        doc = ast.get_docstring(node)
        held = doc is not None and _LOCK_HELD_DOC in doc
        if held:
            self._lock_depth += 1
        self._func_depth += 1
        saved_decls = self._global_decls
        self._global_decls = set(saved_decls)
        self.generic_visit(node)
        self._global_decls = saved_decls
        self._func_depth -= 1
        if held:
            self._lock_depth -= 1
        if entered:
            self._traced_depth -= 1

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_With(self, node):
        locked = any(_mentions_lock(item.context_expr)
                     for item in node.items)
        if locked:
            self._lock_depth += 1
        self.generic_visit(node)
        if locked:
            self._lock_depth -= 1

    visit_AsyncWith = visit_With

    def visit_Global(self, node: ast.Global) -> None:
        self._global_decls.update(node.names)

    # -- unlocked-global-write ---------------------------------------------

    def _flag_global_write(self, name: str, lineno: int, how: str) -> None:
        if name not in self.mutable_globals:
            return
        if self._func_depth == 0 or self._lock_depth > 0:
            return  # import-time init / guarded by a lock context
        self.out.append(_violation(
            "unlocked-global-write", self.relpath, lineno,
            f"{how} of module global {name!r} outside a lock context "
            f"(wrap in `with <lock>:` or document `(lock held)`)"))

    def _check_store_target(self, target, lineno: int) -> None:
        if isinstance(target, ast.Subscript) and \
                isinstance(target.value, ast.Name):
            self._flag_global_write(target.value.id, lineno,
                                    "subscript store")
        elif isinstance(target, ast.Name) and \
                target.id in self._global_decls:
            self._flag_global_write(target.id, lineno, "rebind")
        elif isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._check_store_target(elt, lineno)

    def visit_Assign(self, node: ast.Assign) -> None:
        for t in node.targets:
            if isinstance(t, ast.Subscript) and _is_os_environ(t.value):
                self._env_writes.add(id(t.value))  # env WRITE: exempt
            self._check_store_target(t, node.lineno)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self._check_store_target(node.target, node.lineno)
        self.generic_visit(node)

    def visit_Delete(self, node: ast.Delete) -> None:
        for t in node.targets:
            if isinstance(t, ast.Subscript) and \
                    isinstance(t.value, ast.Name):
                self._flag_global_write(t.value.id, node.lineno,
                                        "subscript delete")
            if isinstance(t, ast.Subscript) and _is_os_environ(t.value):
                self._env_writes.add(id(t.value))
        self.generic_visit(node)

    def _check_traced_call(self, node: ast.Call) -> None:
        fn = node.func
        if isinstance(fn, ast.Attribute):
            if fn.attr in _HOST_ATTR_CALLS:
                self.out.append(_violation(
                    "traced-host-op", self.relpath, node.lineno,
                    f".{fn.attr}() in traced code"))
            elif fn.attr in ("asarray", "array") and \
                    isinstance(fn.value, ast.Name) and fn.value.id == "np":
                self.out.append(_violation(
                    "traced-host-op", self.relpath, node.lineno,
                    f"np.{fn.attr}() in traced code"))
            elif fn.attr == "device_get":
                self.out.append(_violation(
                    "traced-host-op", self.relpath, node.lineno,
                    "jax.device_get() in traced code"))
        elif isinstance(fn, ast.Name) and fn.id in _HOST_NAME_CALLS:
            if not (node.args and isinstance(node.args[0], ast.Constant)):
                self.out.append(_violation(
                    "traced-host-op", self.relpath, node.lineno,
                    f"{fn.id}() cast in traced code"))

    def _check_host_sync(self, node: ast.Call) -> None:
        fn = node.func
        if not (isinstance(fn, ast.Attribute) and fn.attr == "host_sync"
                and isinstance(fn.value, ast.Name)
                and fn.value.id == "metrics"):
            return
        labels = [kw.value.value for kw in node.keywords
                  if kw.arg == "label"
                  and isinstance(kw.value, ast.Constant)]
        if not labels or labels[0] not in self.whitelist:
            self.out.append(_violation(
                "host-sync-site", self.relpath, node.lineno,
                f"metrics.host_sync label {labels[0]!r} not in "
                f"SYNC_WHITELIST" if labels else
                "metrics.host_sync without a whitelisted literal label="))

    # -- unregistered-metric -----------------------------------------------

    def _collect_metric(self, node: ast.Call) -> None:
        fn = node.func
        if getattr(fn, "id", getattr(fn, "attr", "")) == "op_scope" \
                and node.args and any(
                    kw.arg == "timed" and isinstance(kw.value, ast.Constant)
                    and kw.value.value is True for kw in node.keywords):
            # a timed span observes the histogram `<span>_s`
            name = _literal_metric_name(node.args[0])
            if name is not None:
                self.metric_sites.append(
                    (name + "_s", "histogram", self.relpath, node.lineno))
            return
        if not isinstance(fn, ast.Attribute):
            return
        if fn.attr in _METRIC_FNS and isinstance(fn.value, ast.Name) \
                and fn.value.id in _METRIC_BASES and node.args:
            name = _literal_metric_name(node.args[0])
            if name is not None:
                self.metric_sites.append(
                    (name, _METRIC_FNS[fn.attr], self.relpath, node.lineno))
        elif fn.attr == "node_set" and len(node.args) >= 2:
            label = _literal_metric_name(node.args[1])
            if label is not None:
                self.metric_sites.append(
                    (label, "span", self.relpath, node.lineno))

    def visit_Call(self, node: ast.Call) -> None:
        if self._traced_depth:
            self._check_traced_call(node)
        self._check_host_sync(node)
        self._collect_metric(node)
        fn = node.func
        if isinstance(fn, ast.Attribute):
            if isinstance(fn.value, ast.Name) and \
                    fn.attr in _MUTATING_METHODS:
                self._flag_global_write(fn.value.id, node.lineno,
                                        f".{fn.attr}() call")
            if fn.attr == "setdefault" and _is_os_environ(fn.value):
                self._env_writes.add(id(fn.value))  # env WRITE: exempt
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        if self.relpath != f"{PKG}/utils/config.py" and \
                isinstance(node.value, ast.Name) and node.value.id == "os" \
                and node.attr in ("environ", "getenv") \
                and id(node) not in self._env_writes:
            self.out.append(_violation(
                "config-env-read", self.relpath, node.lineno,
                f"os.{node.attr} outside utils/config.py"))
        self.generic_visit(node)

    def visit_ExceptHandler(self, node: ast.ExceptHandler) -> None:
        # failure-domain code must classify what it catches (utils/errors
        # taxonomy): a bare `except:` swallows cancellation and OOM alike,
        # so none are allowed in the recovery-bearing subtrees
        if node.type is None and self.relpath.startswith(_NO_BARE_EXCEPT):
            self.out.append(_violation(
                "bare-except", self.relpath, node.lineno,
                "bare `except:` in failure-domain code (catch a type; "
                "see utils/errors taxonomy)"))
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        # the stage-form choice lives in engine/physical.py: a layer below
        # the executor that reaches up for it (or for any private name of
        # it) is a second copy of the choice waiting to drift
        if (node.module or "").split(".")[-1] == "executor":
            private = [a.name for a in node.names if a.name.startswith("_")]
            below = self.relpath in _BELOW_EXECUTOR
            if private or below:
                self.out.append(_violation(
                    "executor-import", self.relpath, node.lineno,
                    f"imports {private or 'from'} engine/executor.py"
                    + (" (a layer below it)" if below else "")))


def _metric_catalog(sites: list) -> dict:
    """Aggregate (name, kind, file, line) sites into
    name -> {"kinds": set, "files": set}."""
    cat: dict = {}
    for name, kind, relpath, _line in sites:
        e = cat.setdefault(name, {"kinds": set(), "files": set()})
        e["kinds"].add(kind)
        e["files"].add(relpath)
    return cat


def _registered_metrics(doc_path: str) -> set:
    """Names from the catalog's table rows (first backticked token of
    each ``| `name` | ...`` line); prose backticks don't register."""
    names: set = set()
    if not os.path.exists(doc_path):
        return names
    with open(doc_path) as f:
        for line in f:
            if line.startswith("| `") and line.count("`") >= 2:
                names.add(line.split("`", 2)[1])
    return names


def render_metrics_doc(catalog: dict) -> str:
    lines = [
        "# Metric-name catalog",
        "",
        "Generated by `python tools/srjt_lint.py --write-metrics` from the",
        "literal names at `metrics.count` / `observe` / `gauge_set` /",
        "`gauge_max` / `time_add` / `tracing.count` / `node_set` call",
        "sites and from `op_scope(<name>, timed=True)` spans (histogram",
        "`<name>_s`); `<var>` marks an f-string interpolation (one row per",
        "template, however many concrete names it expands to).  Do not",
        "edit by hand: a call site recording a name missing here fails",
        "the lint (`unregistered-metric`), and a row with no remaining",
        "call site fails it too (`stale-metric`) — every metric rename is",
        "one reviewable catalog diff.",
        "",
        "The benchmark's per-layer readers (`benchmarks/layer_metrics/`)",
        "read these names; root `PERF.md` §3 says which reads which.  The",
        "newest three read the probe join of cell `tpch_q3_sf1_building`:",
        "`probe_fused_pct` is the growth of `engine.probe.compare` +",
        "`engine.probe.rank` over that of those two + `engine.probe.interp`,",
        "in %: the share of streamed probe joins that ran inside a chunk",
        "program.  `probe_rank_roofline` is the query module's",
        "`probe_bytes_needed` times the growth of `engine.probe.rank` per",
        "query, over the chip's HBM rate, over the device time per query of",
        "the ops under `engine.fused_segment/probe_rank` in the trace.",
        "`probe_direct_pct` is the growth of `engine.probe.direct` over that",
        "of `engine.probe.rank`, in %: the share of rank probes that read",
        "the build's direct-address table.",
        "",
        "| name | kind | call sites |",
        "|---|---|---|",
    ]
    for name in sorted(catalog):
        e = catalog[name]
        lines.append(f"| `{name}` | {', '.join(sorted(e['kinds']))} | "
                     f"{', '.join(sorted(e['files']))} |")
    lines += ["", f"{len(catalog)} names."]
    return "\n".join(lines) + "\n"


def metrics_doc_pass(catalog: dict, doc_path: str) -> list:
    """Two-way diff of the call-site catalog against docs/METRICS.md."""
    registered = _registered_metrics(doc_path)
    rel = os.path.relpath(doc_path, REPO)
    out: list = []
    for name in sorted(set(catalog) - registered):
        site = sorted(catalog[name]["files"])[0]
        out.append(_violation(
            "unregistered-metric", site, 0,
            f"metric name `{name}` not in {rel} "
            f"(regenerate: tools/srjt_lint.py --write-metrics)"))
    for name in sorted(registered - set(catalog)):
        out.append(_violation(
            "stale-metric", rel, 0,
            f"catalog entry `{name}` has no remaining call site "
            f"(regenerate: tools/srjt_lint.py --write-metrics)"))
    return out


def ast_pass(whitelist: tuple, roots: tuple = (PKG, "tools"),
             sites_out: "list | None" = None) -> list:
    violations: list = []
    sites: list = []
    for root in roots:
        for dirpath, dirnames, filenames in os.walk(
                os.path.join(REPO, root)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fname in sorted(filenames):
                if not fname.endswith(".py"):
                    continue
                full = os.path.join(dirpath, fname)
                rel = os.path.relpath(full, REPO)
                with open(full) as f:
                    tree = ast.parse(f.read(), filename=rel)
                lint = _FileLint(rel, whitelist,
                                 _module_mutable_globals(tree))
                lint.visit(tree)
                violations += lint.out
                sites += lint.metric_sites
    if sites_out is not None:
        sites_out.extend(sites)
    violations += metrics_doc_pass(_metric_catalog(sites),
                                   os.path.join(REPO, METRICS_DOC))
    return violations


def dispatch_pass() -> list:
    import importlib

    from spark_rapids_jni_tpu.engine import executor, explain, plan

    # engine/__init__ re-exports the verify() function under the submodule's
    # name, so resolve the module through importlib
    verify_mod = importlib.import_module("spark_rapids_jni_tpu.engine.verify")
    fuzz_mod = importlib.import_module("spark_rapids_jni_tpu.engine.fuzz")
    tables = (("executor._EXEC_DISPATCH", executor._EXEC_DISPATCH),
              ("explain._DESCRIBE", explain._DESCRIBE),
              ("verify._INFER", verify_mod._INFER),
              ("verify._NULLS", verify_mod._NULLS),
              ("fuzz._ORACLE", fuzz_mod._ORACLE))
    out: list = []
    for cls in plan._NODE_TYPES.values():
        for name, table in tables:
            if cls not in table:
                out.append(_violation(
                    "dispatch-missing", f"{PKG}/engine/plan.py", 0,
                    f"{cls.__name__} not registered in {name}"))
    for name, table in tables:
        for cls in table:
            if cls not in plan._NODE_TYPES.values():
                out.append(_violation(
                    "dispatch-missing", f"{PKG}/engine/plan.py", 0,
                    f"{name} entry {cls.__name__} is not a plan node"))
    return out


#: the smoke pair's exact budget: q5's one fused map segment + the chunked
#: plan's streamed agg (sizing + compaction) — 3 syncs, one per whitelisted
#: site (docs/OBSERVABILITY.md's "3 deliberate host syncs")
SMOKE_EXPECTED_SYNCS = 3

#: the fused dist smoke sandwich's exact budget: the whole partial-agg ->
#: hash-exchange -> final-agg stage is ONE shard_map program paying ONE
#: groupby-compaction boundary sync (the host-orchestrated path pays 4)
FUSED_SMOKE_EXPECTED_SYNCS = 1


def _fused_plan(tmp: str):
    """The dist smoke sandwich for the fused-exchange jaxpr lint."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    from spark_rapids_jni_tpu.engine import Aggregate, Scan
    rng = np.random.default_rng(13)
    n = 4000
    fact = os.path.join(tmp, "lint_fused.parquet")
    pq.write_table(pa.table({
        "k": pa.array(rng.integers(0, 512, n).astype(np.int64)),
        "v": pa.array(rng.integers(0, 400, n) * 0.25),
    }), fact)
    return Aggregate(Scan(fact), ("k",),
                     (("v", "sum"), ("v", "count")), ("total", "n"))


def _full_plans(tmp: str):
    """The nightly extension: a chunked join + top-k plan pair."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    from spark_rapids_jni_tpu.engine import (Aggregate, Filter, Join, Limit,
                                             Scan, Sort, col, lit)
    rng = np.random.default_rng(11)
    n = 4000
    fact = os.path.join(tmp, "lint_fact.parquet")
    dim = os.path.join(tmp, "lint_dim.parquet")
    pq.write_table(pa.table({
        "k": pa.array(rng.integers(0, 2000, n).astype(np.int64)),
        "v": pa.array(rng.uniform(-5, 50, n)),
    }), fact, row_group_size=n // 8)
    pq.write_table(pa.table({
        "dk": pa.array(np.arange(2000, dtype=np.int64)),
        "grp": pa.array((np.arange(2000) % 7).astype(np.int64)),
    }), dim)
    fscan = Scan(fact, chunk_bytes=24_000)
    join_agg = Aggregate(
        Join(Filter(fscan, (">", col("v"), lit(0.0))), Scan(dim),
             ("k",), ("dk",), "inner"),
        ("grp",), (("v", "sum"), ("v", "count")), ("total", "n"))
    topk = Limit(Sort(Scan(fact, chunk_bytes=24_000),
                      (("v", False), ("k", True))), 32)
    return {"join_agg": join_agg, "topk": topk}


def segments_pass(full: bool = False) -> list:
    import tempfile

    import numpy as np

    sys.path.insert(0, REPO)
    from spark_rapids_jni_tpu.engine import optimize
    from spark_rapids_jni_tpu.engine.fuzz import (pipeline_plans,
                                                  pipeline_warehouse)
    from spark_rapids_jni_tpu.engine.verify import (check_sync_budget,
                                                    lint_plan_artifacts)
    out: list = []
    with tempfile.TemporaryDirectory() as tmp:
        rng = np.random.default_rng(7)
        pipeline_warehouse(tmp, 4000, rng)
        q5, chunked = pipeline_plans(tmp, 48_000)
        plans = {"q5": optimize(q5), "chunked": optimize(chunked)}
        entries, bad = check_sync_budget(list(plans.values()))
        smoke_syncs = sum(e["count"] for e in entries)
        for e in bad:
            out.append(_violation("unwhitelisted-host-sync", "<smoke>", 0,
                                  f"{e['site']} at {e['path']}"))
        if smoke_syncs != SMOKE_EXPECTED_SYNCS:
            out.append(_violation(
                "sync-budget-mismatch", "<smoke>", 0,
                f"smoke plans budget {smoke_syncs} syncs, expected "
                f"{SMOKE_EXPECTED_SYNCS} "
                f"({[(e['site'], e['count']) for e in entries]})"))
        if full:
            plans.update({k: optimize(p)
                          for k, p in _full_plans(tmp).items()})
        for name, plan in plans.items():
            rep = lint_plan_artifacts(plan)
            for v in rep["violations"]:
                out.append(_violation(v["code"], f"<plan:{name}>", 0,
                                      f"{v.get('path', '?')}: "
                                      f"{v.get('detail', '')}"))
            nseg = sum(1 for s in rep["segments"] if "skipped" not in s)
            print(f"srjt-lint: {name}: {nseg} segment artifact(s) linted, "
                  f"{len(rep['violations'])} violation(s)")

        # the fused-exchange artifact: optimize the dist smoke sandwich
        # under SRJT_FUSE_EXCHANGE and lint the whole jit(shard_map)
        # program (verify.lint_fused_stage: no callbacks, no host
        # concretization inside the collectives, all_to_all present) plus
        # its exact one-sync budget
        import jax
        from spark_rapids_jni_tpu.utils.config import config as _cfg
        saved = _cfg.fuse_exchange
        _cfg.fuse_exchange = True
        try:
            fused_opt = optimize(_fused_plan(tmp), distribute=True)
            entries, bad = check_sync_budget([fused_opt])
            for e in bad:
                out.append(_violation(
                    "unwhitelisted-host-sync", "<dist-fused>", 0,
                    f"{e['site']} at {e['path']}"))
            fused_syncs = sum(e["count"] for e in entries)
            ndev = len(jax.devices())
            if ndev > 1 and fused_syncs != FUSED_SMOKE_EXPECTED_SYNCS:
                out.append(_violation(
                    "sync-budget-mismatch", "<dist-fused>", 0,
                    f"fused smoke budget {fused_syncs} syncs, expected "
                    f"{FUSED_SMOKE_EXPECTED_SYNCS} "
                    f"({[(e['site'], e['count']) for e in entries]})"))
            rep = lint_plan_artifacts(fused_opt)
            for v in rep["violations"]:
                out.append(_violation(v["code"], "<plan:dist-fused>", 0,
                                      f"{v.get('path', '?')}: "
                                      f"{v.get('detail', '')}"))
            fused_arts = [s for s in rep["segments"]
                          if s.get("kind") == "fused-stage"]
            if ndev > 1 and not any("skipped" not in s for s in fused_arts):
                out.append(_violation(
                    "missing-fused-artifact", "<plan:dist-fused>", 0,
                    "no fused-stage jaxpr linted on a multi-device mesh"))
            print(f"srjt-lint: dist-fused: "
                  f"{len(fused_arts)} fused-stage artifact(s), budget "
                  f"{fused_syncs} sync(s) on {ndev} device(s)")
        finally:
            _cfg.fuse_exchange = saved

        # the device-decode artifact: plan real page geometry off the
        # warehouse fact file and lint the fused scan+decode program
        # (verify.lint_decode_segment) — the decode prefix must splice
        # into the scan segment with ZERO added host syncs or callbacks
        from spark_rapids_jni_tpu.engine.physical import lower
        from spark_rapids_jni_tpu.engine.verify import lint_decode_segment
        from spark_rapids_jni_tpu.io.parquet import (ParquetFile,
                                                     plan_device_group)
        copt = plans["chunked"]
        seg = lower(copt, fuse=True, fuse_exchange=False,
                    ndev=1).stage_at(copt).segment
        chunk, reason = plan_device_group(
            ParquetFile(os.path.join(tmp, "store_sales.parquet")), 0,
            None, 1 << 30)
        if seg is None or chunk is None:
            out.append(_violation(
                "missing-decode-artifact", "<plan:chunked>", 0,
                f"no fused scan+decode jaxpr to lint "
                f"(segment={seg is not None}, plan reason={reason})"))
        else:
            rep = lint_decode_segment(seg, chunk.geom)
            for v in rep["violations"]:
                out.append(_violation(v["code"], "<decode:chunked>", 0,
                                      v.get("detail", "")))
            print(f"srjt-lint: device-decode: fused scan+decode jaxpr, "
                  f"{rep['primitives']} primitive(s), "
                  f"{len(rep['violations'])} violation(s)")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", default=None,
                    help="JSON baseline of grandfathered violation keys")
    ap.add_argument("--write-baseline", action="store_true",
                    help="rewrite --baseline (default ci/lint-baseline.json)"
                         " from the current violations")
    ap.add_argument("--write-metrics", action="store_true",
                    help="regenerate docs/METRICS.md from the metric-name "
                         "call sites")
    ap.add_argument("--segments", action="store_true",
                    help="also jaxpr-lint the smoke plans' fused segments")
    ap.add_argument("--full", action="store_true",
                    help="with --segments: extend to a chunked join/top-k "
                         "plan pair")
    args = ap.parse_args(argv)

    # import-time passes need the engine importable without a device
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if args.segments or args.full:
        # the fused-exchange artifact needs a multi-device mesh to lower
        # its shard_map program; must be set before jax initializes
        os.environ.setdefault("XLA_FLAGS",
                              "--xla_force_host_platform_device_count=8")
    sys.path.insert(0, REPO)
    from spark_rapids_jni_tpu.engine.verify import SYNC_WHITELIST

    sites: list = []
    violations = ast_pass(tuple(SYNC_WHITELIST), sites_out=sites)
    if args.write_metrics:
        doc_path = os.path.join(REPO, METRICS_DOC)
        catalog = _metric_catalog(sites)
        os.makedirs(os.path.dirname(doc_path), exist_ok=True)
        with open(doc_path, "w") as f:
            f.write(render_metrics_doc(catalog))
        print(f"srjt-lint: wrote {len(catalog)} metric name(s) to "
              f"{os.path.relpath(doc_path, REPO)}")
        return 0
    violations += dispatch_pass()
    if args.segments or args.full:
        violations += segments_pass(full=args.full)

    baseline_path = args.baseline or os.path.join(REPO, "ci",
                                                  "lint-baseline.json")
    if args.write_baseline:
        keys = sorted({baseline_key(v) for v in violations})
        with open(baseline_path, "w") as f:
            json.dump({"grandfathered": keys}, f, indent=2)
            f.write("\n")
        print(f"srjt-lint: wrote {len(keys)} baseline key(s) to "
              f"{baseline_path}")
        return 0

    grandfathered: set = set()
    if args.baseline and os.path.exists(args.baseline):
        with open(args.baseline) as f:
            grandfathered = set(json.load(f).get("grandfathered", []))

    fresh = [v for v in violations if baseline_key(v) not in grandfathered]
    old = len(violations) - len(fresh)
    for v in fresh:
        print(f"srjt-lint: {v['code']}: {v['file']}:{v['line']}: "
              f"{v['detail']}")
    print(f"srjt-lint: {len(fresh)} new violation(s), {old} grandfathered")
    return 1 if fresh else 0


if __name__ == "__main__":
    sys.exit(main())
