"""Static plan verification + compiled-artifact linting.

The engine's pre-execution analysis layer (docs/ANALYSIS.md), playing the
role the reference repo's JNI shim plays at the Java boundary: type-check
the work BEFORE any kernel runs.  Two of the three lint passes live here
(the third — the repo AST lint — is ``tools/srjt_lint.py``):

1. **Plan verifier** — schema/dtype inference propagated bottom-up over the
   plan DAG.  Every plan-node class has an ``infer_schema`` rule in the
   ``_INFER`` dispatch table (the exhaustiveness lint asserts it stays
   total), producing an ordered ``{name: DType}`` for the node's output.
   Build-time checks fire during inference — unknown columns, join-key
   dtype-family mismatches, invalid casts (string vs non-string
   comparisons), aggregating strings with numeric ops — raising a
   structured :class:`PlanVerificationError` that carries the node path
   from the root (``root.child.left`` ...).  ``optimizer.optimize`` runs a
   :class:`RewriteChecker` after every rewrite rule, so a rule that changes
   the root output schema is an immediate failure instead of a wrong
   result, and ``bridge/server`` PLAN_EXECUTE verifies before executing.

2. **Compiled-artifact linter** — ``lint_plan_artifacts`` takes the
   stages the executor will run from ``physical.lower`` (the one owner of
   that choice), lowers each fused stage's program to a jaxpr with
   ``jax.make_jaxpr`` over a zero-filled input table — tracing only,
   nothing executes — and statically asserts the chunk-program contract:
   no host callbacks (``pure_callback`` etc.), no trace-time
   concretization (a ``.item()``/``float()`` smuggled into a traced path
   fails the lint, not a production run), prepared-build pytree args
   device-resident, and the deliberate host-sync budget.  ``sync_budget``
   charges every stage what ``physical.SYNC_CHARGES`` says its kind pays
   (the "deliberate host syncs" contract of docs/OBSERVABILITY.md).
   ``lint_segment_cache`` flags fingerprints whose
   compiled-variant count says unpadded dynamic shapes are exploding the
   (fingerprint, shape-class) SEGMENT_CACHE.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ..dtypes import BOOL8, FLOAT64, INT64, LIST, DType
from .physical import lower
from .plan import (ARITH_OPS, ORDER_SENSITIVE_AGGS, Aggregate, Exchange,
                   Filter, Join, Limit, PlanNode, Project, Scan, Sort, TopK,
                   co_partitioned, expr_columns, node_label, node_paths,
                   partitioning, topo_nodes)

#: the deliberate host-sync sites the engine is allowed to pay
#: (metrics.host_sync labels; the AST lint in tools/srjt_lint.py rejects
#: any new metrics.host_sync call site outside this whitelist)
SYNC_WHITELIST = (
    "segment-boundary-compaction",  # run_map_segment's survivor count
    "combine-sizing",               # the final merge's max(ngroups) fetch
    "combine-fold-sizing",          # the same fetch of a mid-stream fold
    "groupby-compaction",           # _compact_padded's ngroups fetch
    "tail-compaction",              # run_tail's row count + result fetch
    "exchange-counts-sizing",       # hash exchange phase-1 counts fetch
    "exchange-compaction",          # hash exchange ok-mask fetch + compact
)

#: jaxpr primitives that would smuggle host work into a chunk program
_FORBIDDEN_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "infeed", "outfeed",
})

#: aggregate ops that require a numeric (or decimal) input column
_NUMERIC_AGGS = frozenset({"sum", "mean", "var", "std", "sumsq", "fsum"})

#: the two-point nullability lattice flowing through the abstract
#: interpreter: ``"never"`` (proven non-null by footer stats or a filter
#: over the column) ⊑ ``"maybe"`` (top — anything unproven).  A rewrite
#: moving a root column between the two is ``rewrite-nullability-change``.
NULL_NEVER = "never"
NULL_MAYBE = "maybe"

#: past ±2^53 a float64 can no longer represent every integer, so a
#: comparison that promotes an integral column (or integral literal) into
#: the float domain silently collapses neighbouring values
_FLOAT64_EXACT_INT = 2 ** 53


class PlanVerificationError(ValueError):
    """A plan failed a build-time check.

    Structured so the bridge can ship it as a machine-parseable error
    reply: ``code`` names the check (``unknown-column``,
    ``join-key-dtype-mismatch``, ``invalid-cast``, ``overflow-unsafe-cast``,
    ``aggregate-over-string``, ``arithmetic-over-string``,
    ``date-decimal-mix``, ``invalid-arithmetic``, ``order-sensitive-exchange``,
    ``rewrite-schema-change``, ``rewrite-nullability-change``,
    ``unknown-node``), ``node_path`` locates the offending node from the
    root (``root.child.left`` ...).
    """

    def __init__(self, code: str, node_path: str, message: str):
        self.code = code
        self.node_path = node_path
        self.message = message
        super().__init__(f"{code} at {node_path}: {message}")

    def to_dict(self) -> dict:
        return {"code": self.code, "node_path": self.node_path,
                "message": self.message}

    @classmethod
    def from_dict(cls, d: dict) -> "PlanVerificationError":
        return cls(d.get("code", "unknown"), d.get("node_path", "?"),
                   d.get("message", ""))


class SchemaResolver:
    """Caches scan-file footer schemas as ordered ``{name: DType}``.

    Unreadable/missing files resolve to ``None`` (schema unknown): the
    verifier then skips schema-dependent checks for that subtree and the
    executor surfaces the I/O error at run time, exactly as before — a
    missing file is an execution failure, not a plan-verification one.
    """

    def __init__(self):
        self._files: dict = {}
        self._nulls: dict = {}

    def file_nullability(self, node: Scan) -> Optional[dict]:
        """Footer-derived nullability facts: ``{name: "never"|"maybe"}``.

        A parquet column whose every row group carries statistics with a
        zero null count is proven ``"never"`` null; a missing stats block,
        an unknown null count, or a non-parquet source degrades to
        ``"maybe"`` (the lattice top).  Unreadable files resolve to
        ``None``, exactly like :meth:`file_schema`.
        """
        key = (node.format, node.path)
        if key not in self._nulls:
            try:
                if node.format == "parquet":
                    from ..io import ParquetFile
                    pf = ParquetFile(node.path)
                    out = {}
                    for c in pf.schema:
                        never = pf.num_row_groups > 0
                        for gi in range(pf.num_row_groups):
                            st = pf.group_stats(gi, c.name)
                            if st is None or st[2] is None or st[2] > 0:
                                never = False
                                break
                        out[c.name] = NULL_NEVER if never else NULL_MAYBE
                    self._nulls[key] = out
                else:
                    from ..io import ORCFile
                    self._nulls[key] = {nm: NULL_MAYBE for nm, _dt
                                        in ORCFile(node.path).schema}
            except Exception:
                self._nulls[key] = None
        nl = self._nulls[key]
        return None if nl is None else dict(nl)

    def file_schema(self, node: Scan) -> Optional[dict]:
        key = (node.format, node.path)
        if key not in self._files:
            try:
                if node.format == "parquet":
                    from ..io import ParquetFile
                    self._files[key] = {c.name: c.dtype
                                        for c in ParquetFile(node.path).schema}
                else:
                    from ..io import ORCFile
                    self._files[key] = dict(ORCFile(node.path).schema)
            except Exception:
                self._files[key] = None
        sc = self._files[key]
        return None if sc is None else dict(sc)


# -- dtype classification ---------------------------------------------------

def _lit_dtype(value) -> Optional[DType]:
    if isinstance(value, bool):
        return BOOL8
    if isinstance(value, int):
        return INT64
    if isinstance(value, float):
        return FLOAT64
    if isinstance(value, str):
        from ..dtypes import STRING
        return STRING
    return None  # None/other literals: unknown, checks skip


def _cast_family(dt: Optional[DType]) -> Optional[str]:
    """Coarse comparability family: comparisons may mix anything scalar
    (ints, floats, bools, timestamps-as-ints) but never string vs
    non-string or nested."""
    if dt is None:
        return None
    if dt.is_string:
        return "string"
    if dt.is_nested:
        return "nested"
    return "scalar"

def _key_family(dt: Optional[DType]) -> Optional[str]:
    """Join-key family: stricter than comparability because equi-joins
    hash the RAW storage — int64 and float64 keys hash differently, so an
    integral-vs-floating key pair silently matches nothing."""
    if dt is None:
        return None
    if dt.is_string:
        return "string"
    if dt.is_decimal:
        return ("decimal", dt.scale)
    if dt.is_timestamp:
        return "timestamp"
    if dt.is_floating:
        return "floating"
    if dt.is_numeric or dt.id.name == "BOOL8":
        return "integral"
    return "other"


def _agg_out_dtype(op: str, dt: Optional[DType]) -> Optional[DType]:
    """Output dtype of one aggregate op (mirrors ops.aggregate)."""
    if op in ("count", "count_all"):
        return INT64
    if op in ("mean", "var", "std", "sumsq", "fsum"):
        return FLOAT64
    if op == "collect_list":
        return LIST
    if dt is None:
        return None
    if op == "sum":
        if dt.is_floating:
            return FLOAT64
        if dt.is_integral:
            return INT64
        if dt.is_decimal:   # Spark: decimal(min(38, p+10), s)
            from .expr import sum_type
            return sum_type(dt)
        return dt
    return dt  # min/max/first/last


# -- expression type checking -----------------------------------------------

def _expr_dtype(expr, schema: dict, path: str,
                node: PlanNode) -> Optional[DType]:
    """Dtype of a filter expression over ``schema``; raises on unknown
    columns and string-vs-non-string comparisons (the invalid-cast check —
    the executor would lower these to a nonsense jnp comparison)."""
    head = expr[0]
    if head == "col":
        if expr[1] not in schema:
            raise PlanVerificationError(
                "unknown-column", path,
                f"{node_label(node)} references unknown column {expr[1]!r} "
                f"(available: {sorted(schema)})")
        return schema[expr[1]]
    if head == "lit":
        return _lit_dtype(expr[1])
    if head in ("lit_decimal", "lit_date"):
        from .expr import literal
        value, dt = literal(expr)
        if head == "lit_decimal" and not _fits_int64(value):
            raise PlanVerificationError(
                "overflow-unsafe-cast", path,
                f"{node_label(node)}: decimal literal {expr!r} does not fit "
                "the engine's int64 decimal storage")
        return dt
    if head == "not":
        _expr_dtype(expr[1], schema, path, node)
        return BOOL8
    a = _expr_dtype(expr[1], schema, path, node)
    b = _expr_dtype(expr[2], schema, path, node)
    from .expr import ExprTypeError, arith_dtype, compare_check
    # a plain literal is typed by its value (Spark's rule), not as INT64
    (ta, va), (tb, vb) = ((None, e[1]) if e[0] == "lit" else (t, None)
                          for e, t in ((expr[1], a), (expr[2], b)))
    try:
        if head in ARITH_OPS:
            if (ta is None and va is None) or (tb is None and vb is None):
                return None     # schema unknown: the run decides
            return arith_dtype(head, ta, tb, va, vb)
        if head not in ("&", "|"):
            compare_check(head, ta, tb, va, vb)
    except ExprTypeError as e:
        raise PlanVerificationError(e.code, path,
                                    f"{node_label(node)}: {e}") from None
    if head in ("&", "|"):
        for side in (a, b):
            if side is not None and (side.is_string or side.is_nested):
                raise PlanVerificationError(
                    "invalid-cast", path,
                    f"{node_label(node)}: boolean operator {head!r} over "
                    f"non-boolean operand {side!r}")
        return BOOL8
    fa, fb = _cast_family(a), _cast_family(b)
    if "nested" in (fa, fb):
        raise PlanVerificationError(
            "invalid-cast", path,
            f"{node_label(node)}: comparison {head!r} over nested type")
    if fa is not None and fb is not None and fa != fb:
        raise PlanVerificationError(
            "invalid-cast", path,
            f"{node_label(node)}: comparison {head!r} between {a!r} and "
            f"{b!r} — string vs non-string needs an explicit cast")
    if "string" in (fa, fb) and head not in ("==", "!="):
        raise PlanVerificationError(
            "invalid-cast", path,
            f"{node_label(node)}: ordering comparison {head!r} over STRING "
            f"operands — the string kernel set defines only ==/!=")
    for lit_side, dt_side in ((expr[1], b), (expr[2], a)):
        if lit_side[0] == "lit":
            _check_lit_overflow(head, dt_side, lit_side[1], path, node)
    return BOOL8


def _check_lit_overflow(head, col_dt: Optional[DType], value, path: str,
                        node: PlanNode) -> None:
    """Cast/overflow legality of one ``col <op> lit`` comparison: the
    executor lowers both sides into the column's jnp domain, so a literal
    the domain cannot represent exactly makes the comparison silently
    wrong instead of merely slow (``overflow-unsafe-cast``)."""
    if col_dt is None or isinstance(value, bool):
        return
    if col_dt.is_decimal and isinstance(value, int):
        # the literal is brought to the column's scale: units must fit
        if not _fits_int64(value * 10 ** -col_dt.scale):
            raise PlanVerificationError(
                "overflow-unsafe-cast", path,
                f"{node_label(node)}: literal {value} at the {col_dt!r} "
                f"column's scale overflows int64 in comparison {head!r}")
    elif col_dt.is_integral and isinstance(value, int):
        info = np.iinfo(col_dt.storage)
        if not (int(info.min) <= value <= int(info.max)):
            raise PlanVerificationError(
                "overflow-unsafe-cast", path,
                f"{node_label(node)}: literal {value} overflows the "
                f"{col_dt!r} column domain [{info.min}, {info.max}] in "
                f"comparison {head!r}")
    elif col_dt.is_integral and isinstance(value, float):
        if abs(value) > _FLOAT64_EXACT_INT:
            raise PlanVerificationError(
                "overflow-unsafe-cast", path,
                f"{node_label(node)}: float literal {value!r} promotes the "
                f"{col_dt!r} column to float64 beyond the 2^53 exact-integer "
                f"range in comparison {head!r}")
    elif col_dt.is_floating and isinstance(value, int):
        if abs(value) > _FLOAT64_EXACT_INT:
            raise PlanVerificationError(
                "overflow-unsafe-cast", path,
                f"{node_label(node)}: integer literal {value} is not exactly "
                f"representable as {col_dt!r} (past 2^53) in comparison "
                f"{head!r}")


def _fits_int64(v: int) -> bool:
    return -2 ** 63 <= v < 2 ** 63


# -- per-node infer_schema rules (the verifier dispatch table) --------------

class _Ctx:
    __slots__ = ("resolver", "memo", "nmemo")

    def __init__(self, resolver: SchemaResolver):
        self.resolver = resolver
        self.memo: dict = {}
        self.nmemo: dict = {}


def _infer_scan(node: Scan, path: str, ctx: _Ctx) -> Optional[dict]:
    file_schema = ctx.resolver.file_schema(node)
    if node.predicate is not None and file_schema is not None:
        pcol = node.predicate[0]
        if pcol not in file_schema:
            raise PlanVerificationError(
                "unknown-column", path,
                f"scan pruning predicate over unknown column {pcol!r} "
                f"(file has: {sorted(file_schema)})")
        pdt = file_schema[pcol]
        if pdt is not None and (pdt.is_string or pdt.is_nested):
            raise PlanVerificationError(
                "invalid-cast", path,
                f"scan pruning predicate needs a numeric column, "
                f"{pcol!r} is {pdt!r}")
    if node.partitioned_by is not None and file_schema is not None:
        missing = [c for c in node.partitioned_by if c not in file_schema]
        if missing:
            raise PlanVerificationError(
                "unknown-column", path,
                f"scan partitioned_by references unknown column(s) "
                f"{missing} (file has: {sorted(file_schema)})")
    if node.columns is not None:
        if file_schema is None:
            # names known, dtypes not: unknown-column checks still work
            return {c: None for c in node.columns}
        missing = [c for c in node.columns if c not in file_schema]
        if missing:
            raise PlanVerificationError(
                "unknown-column", path,
                f"scan selects unknown column(s) {missing} "
                f"(file has: {sorted(file_schema)})")
        return {c: file_schema[c] for c in node.columns}
    return file_schema


def _infer_filter(node: Filter, path: str, ctx: _Ctx) -> Optional[dict]:
    child = _infer(node.child, path + ".child", ctx)
    if child is not None:
        _expr_dtype(node.predicate, child, path, node)
    return child


def _infer_project(node: Project, path: str, ctx: _Ctx) -> Optional[dict]:
    child = _infer(node.child, path + ".child", ctx)
    if child is None:
        return None
    missing = [c for c in node.columns if isinstance(c, str)
               and c not in child]
    if missing:
        raise PlanVerificationError(
            "unknown-column", path,
            f"project selects unknown column(s) {missing} "
            f"(child has: {sorted(child)})")
    return {name: child[e[1]] if e[0] == "col"
            else _expr_dtype(e, child, path, node)
            for name, e in node.items}


def _infer_join(node: Join, path: str, ctx: _Ctx) -> Optional[dict]:
    left = _infer(node.left, path + ".left", ctx)
    right = _infer(node.right, path + ".right", ctx)
    if node.how != "cross":
        for keys, schema, side in ((node.left_keys, left, "left"),
                                   (node.right_keys, right, "right")):
            if schema is None:
                continue
            for k in keys:
                if k not in schema:
                    raise PlanVerificationError(
                        "unknown-column", path,
                        f"join {side} key {k!r} not in {side} input "
                        f"(has: {sorted(schema)})")
        if left is not None and right is not None:
            for lk, rk in zip(node.left_keys, node.right_keys):
                lf, rf = _key_family(left[lk]), _key_family(right[rk])
                if lf is not None and rf is not None and lf != rf:
                    raise PlanVerificationError(
                        "join-key-dtype-mismatch", path,
                        f"join key {lk!r} ({left[lk]!r}) vs {rk!r} "
                        f"({right[rk]!r}): families {lf} vs {rf} hash "
                        f"differently and would silently match nothing")
    if node.how in ("semi", "anti"):
        return left
    if left is None or right is None:
        return None
    rkeys = set(node.right_keys) if node.how != "cross" else set()
    out = dict(left)
    for nm, dt in right.items():
        if nm in rkeys:
            continue
        out[nm + ("_r" if nm in left else "")] = dt
    return out


def _infer_aggregate(node: Aggregate, path: str, ctx: _Ctx) -> Optional[dict]:
    if any(op in ORDER_SENSITIVE_AGGS for _c, op in node.aggs):
        below = node.child
        while isinstance(below, (Filter, Project, Limit)):
            below = below.child  # order-preserving unaries
        if isinstance(below, Exchange) and below.kind == "hash":
            raise PlanVerificationError(
                "order-sensitive-exchange", path,
                f"order-sensitive aggregate "
                f"({[op for _c, op in node.aggs if op in ORDER_SENSITIVE_AGGS]}) "
                f"fed by a hash exchange: the shuffle destroys the row order "
                f"first/last/collect_list depend on")
    child = _infer(node.child, path + ".child", ctx)
    if child is None:
        return None
    for k in node.keys:
        if k not in child:
            raise PlanVerificationError(
                "unknown-column", path,
                f"aggregate key {k!r} not in input (has: {sorted(child)})")
    out = {k: child[k] for k in node.keys}
    for (cname, op), outname in zip(node.aggs, node.names):
        if cname is None:
            out[outname] = INT64  # count_all
            continue
        if cname not in child:
            raise PlanVerificationError(
                "unknown-column", path,
                f"aggregate {op!r} over unknown column {cname!r} "
                f"(input has: {sorted(child)})")
        dt = child[cname]
        if dt is not None and op in _NUMERIC_AGGS and \
                (dt.is_string or dt.is_nested):
            raise PlanVerificationError(
                "aggregate-over-string", path,
                f"aggregate {op!r} needs a numeric column, "
                f"{cname!r} is {dt!r}")
        out[outname] = _agg_out_dtype(op, dt)
    return out


def _check_order_keys(node, keys, path: str, ctx: _Ctx) -> Optional[dict]:
    child = _infer(node.child, path + ".child", ctx)
    if child is not None:
        for c, _asc in keys:
            if c not in child:
                raise PlanVerificationError(
                    "unknown-column", path,
                    f"{node_label(node)} key {c!r} not in input "
                    f"(has: {sorted(child)})")
    return child


def _infer_sort(node: Sort, path: str, ctx: _Ctx) -> Optional[dict]:
    return _check_order_keys(node, node.keys, path, ctx)


def _infer_topk(node: TopK, path: str, ctx: _Ctx) -> Optional[dict]:
    return _check_order_keys(node, node.keys, path, ctx)


def _infer_limit(node: Limit, path: str, ctx: _Ctx) -> Optional[dict]:
    return _infer(node.child, path + ".child", ctx)


def _infer_exchange(node: Exchange, path: str, ctx: _Ctx) -> Optional[dict]:
    """Exchange is schema-transparent: output columns/dtypes equal the
    child's.  Hash keys must exist in the child schema — a key the executor
    can't hash is a build-time error, not a runtime KeyError."""
    child = _infer(node.child, path + ".child", ctx)
    if node.kind == "hash" and child is not None:
        missing = [k for k in node.keys if k not in child]
        if missing:
            raise PlanVerificationError(
                "unknown-column", path,
                f"exchange hash key(s) {missing} not in input "
                f"(has: {sorted(child)})")
    return child


#: plan-node class -> infer_schema rule; tools/srjt_lint.py asserts this
#: stays exhaustive over plan._NODE_TYPES
_INFER = {
    Scan: _infer_scan,
    Filter: _infer_filter,
    Project: _infer_project,
    Join: _infer_join,
    Aggregate: _infer_aggregate,
    Sort: _infer_sort,
    Limit: _infer_limit,
    TopK: _infer_topk,
    Exchange: _infer_exchange,
}


def _infer(node: PlanNode, path: str, ctx: _Ctx) -> Optional[dict]:
    if id(node) in ctx.memo:
        return ctx.memo[id(node)]
    fn = _INFER.get(type(node))
    if fn is None:
        raise PlanVerificationError(
            "unknown-node", path,
            f"plan node {type(node).__name__} has no infer_schema rule "
            f"(register it in verify._INFER)")
    out = fn(node, path, ctx)
    ctx.memo[id(node)] = out
    return out


def verify(plan: PlanNode,
           resolver: Optional[SchemaResolver] = None) -> Optional[dict]:
    """Type-check ``plan`` bottom-up; returns the root output schema as an
    ordered ``{name: DType}`` (``None`` when no scan schema resolved).

    Raises :class:`PlanVerificationError` on the first violated build-time
    check, carrying the check code and the node path from the root.
    """
    return _infer(plan, "root", _Ctx(resolver or SchemaResolver()))


def schema_view(resolver: Optional[SchemaResolver] = None):
    """``node -> {name: DType} | None`` over one plan's nodes, inference
    shared between calls (``verify`` infers from scratch each time)."""
    ctx = _Ctx(resolver or SchemaResolver())
    return lambda node: _infer(node, "root", ctx)


# -- nullability abstract interpretation ------------------------------------

def _nulls_scan(node: Scan, path: str, ctx: _Ctx) -> Optional[dict]:
    nl = ctx.resolver.file_nullability(node)
    if nl is None:
        return None
    if node.columns is not None:
        return {c: nl.get(c, NULL_MAYBE) for c in node.columns}
    return nl


def _nulls_filter(node: Filter, path: str, ctx: _Ctx) -> Optional[dict]:
    child = _nulls(node.child, path + ".child", ctx)
    if child is None:
        return None
    # the executor ANDs the validity of EVERY predicate-referenced column
    # into the keep-mask (engine/expr.py::evaluate), so survivors are
    # proven non-null in those columns regardless of the operator tree
    out = dict(child)
    for c in expr_columns(node.predicate):
        if c in out:
            out[c] = NULL_NEVER
    return out


def _nulls_project(node: Project, path: str, ctx: _Ctx) -> Optional[dict]:
    child = _nulls(node.child, path + ".child", ctx)
    if child is None:
        return None
    # a computed column is null where any column it reads is
    return {name: NULL_NEVER if all(child.get(c) == NULL_NEVER
                                    for c in expr_columns(e)) else NULL_MAYBE
            for name, e in node.items
            if e[0] != "col" or e[1] in child}


def _nulls_join(node: Join, path: str, ctx: _Ctx) -> Optional[dict]:
    left = _nulls(node.left, path + ".left", ctx)
    right = _nulls(node.right, path + ".right", ctx)
    if node.how in ("semi", "anti"):
        return left
    if left is None or right is None:
        return None
    # outer joins pad the unmatched side with nulls, widening every one of
    # its columns to "maybe" — the precise fact the lattice exists to track
    if node.how in ("left", "full"):
        right = {c: NULL_MAYBE for c in right}
    if node.how in ("right", "full"):
        left = {c: NULL_MAYBE for c in left}
    rkeys = set(node.right_keys) if node.how != "cross" else set()
    out = dict(left)
    for nm, nu in right.items():
        if nm in rkeys:
            continue
        out[nm + ("_r" if nm in left else "")] = nu
    return out


def _nulls_aggregate(node: Aggregate, path: str, ctx: _Ctx) -> Optional[dict]:
    child = _nulls(node.child, path + ".child", ctx)
    if child is None:
        return None
    out = {k: child.get(k, NULL_MAYBE) for k in node.keys}
    for (cname, op), outname in zip(node.aggs, node.names):
        if op in ("count", "count_all") or op == "collect_list":
            out[outname] = NULL_NEVER  # counts and lists always materialize
        elif cname is None:
            out[outname] = NULL_NEVER
        elif not node.keys:
            out[outname] = NULL_MAYBE  # one row, an empty input's: NULL
        else:
            out[outname] = child.get(cname, NULL_MAYBE)
    return out


def _nulls_child(node, path: str, ctx: _Ctx) -> Optional[dict]:
    """Sort/Limit/TopK/Exchange: row-set reshapes, nullability-transparent."""
    return _nulls(node.child, path + ".child", ctx)


#: plan-node class -> nullability rule; tools/srjt_lint.py asserts this
#: stays exhaustive over plan._NODE_TYPES, like _INFER
_NULLS = {
    Scan: _nulls_scan,
    Filter: _nulls_filter,
    Project: _nulls_project,
    Join: _nulls_join,
    Aggregate: _nulls_aggregate,
    Sort: _nulls_child,
    Limit: _nulls_child,
    TopK: _nulls_child,
    Exchange: _nulls_child,
}


def _nulls(node: PlanNode, path: str, ctx: _Ctx) -> Optional[dict]:
    if id(node) in ctx.nmemo:
        return ctx.nmemo[id(node)]
    fn = _NULLS.get(type(node))
    if fn is None:
        raise PlanVerificationError(
            "unknown-node", path,
            f"plan node {type(node).__name__} has no nullability rule "
            f"(register it in verify._NULLS)")
    out = fn(node, path, ctx)
    ctx.nmemo[id(node)] = out
    return out


def infer_nullability(plan: PlanNode,
                      resolver: Optional[SchemaResolver] = None
                      ) -> Optional[dict]:
    """Abstract interpretation over the nullability lattice: the root's
    ``{name: "never"|"maybe"}``, or ``None`` when no scan footer resolved.

    Companion pass to :func:`verify` — where ``verify`` proves dtype
    shape, this proves null behaviour, so :class:`RewriteChecker` can
    reject a rewrite that silently turns a proven-non-null column nullable
    (or claims the reverse) even though the dtypes still line up.
    """
    return _nulls(plan, "root", _Ctx(resolver or SchemaResolver()))


class RewriteChecker:
    """Asserts optimizer rewrites preserve the root output schema AND the
    root nullability vector.

    Built on the ORIGINAL plan (which also runs the build-time checks up
    front); ``check(rule, plan)`` re-verifies after each rule and raises
    ``rewrite-schema-change`` if the root schema moved, or
    ``rewrite-nullability-change`` if a root column's position in the
    nullability lattice moved — an optimizer bug caught at plan time
    instead of a silently wrong result.
    """

    def __init__(self, plan: PlanNode):
        self.resolver = SchemaResolver()
        self.base = verify(plan, self.resolver)
        self.base_nulls = infer_nullability(plan, self.resolver)

    def check(self, rule: str, plan: PlanNode) -> None:
        after = verify(plan, self.resolver)
        if self.base is not None and after is not None:
            if list(self.base.items()) != list(after.items()):
                raise PlanVerificationError(
                    "rewrite-schema-change", "root",
                    f"optimizer rule {rule!r} changed the root schema from "
                    f"{list(self.base)} to {list(after)}")
        after_nulls = infer_nullability(plan, self.resolver)
        if self.base_nulls is not None and after_nulls is not None:
            if self.base_nulls != after_nulls:
                moved = sorted(set(self.base_nulls.items())
                               ^ set(after_nulls.items()))
                raise PlanVerificationError(
                    "rewrite-nullability-change", "root",
                    f"optimizer rule {rule!r} changed root nullability: "
                    f"{moved}")


# -- pass 2: compiled-artifact lint -----------------------------------------

def plan_exchanges(plan: PlanNode) -> list:
    """Static census of the Exchange nodes in a plan, in postorder — one
    entry ``{"path", "kind", "keys"}`` per node.  The executor bumps
    ``stats["exchanges"]`` once per Exchange regardless of degenerate
    early-outs (1 device, 0 rows), so ``len(plan_exchanges(p))`` equals the
    executed count exactly — ci/premerge.sh asserts that on the smoke
    artifact."""
    paths = node_paths(plan)
    return [{"path": paths[id(n)], "kind": n.kind, "keys": list(n.keys)}
            for n in topo_nodes(plan) if isinstance(n, Exchange)]


def decision_census(plan: PlanNode, dist: bool | None = None) -> list:
    """Static census of decision-evidencing structures in an OPTIMIZED
    plan, in postorder — one entry ``{"kind", "path"}`` per structure.

    The planner's structural decisions all leave a fingerprint in the
    plan shape: a broadcast choice is an ``Exchange(broadcast)``, a hash
    placement is an ``Exchange(hash)``, a partial-agg split is the
    ``Aggregate(Exchange(hash, Aggregate))`` sandwich (whose inner
    exchange belongs to the split, not counted separately), a TopK
    rewrite is the ``TopK`` node, and an order-sensitive revert is a
    distributed Aggregate still carrying order-sensitive ops.  So for a
    planner-optimized plan (no hand-placed exchanges) this census equals,
    kind for kind, the structural entries of the plan's ``_decisions``
    ledger — ``tests/test_evidence_plane.py`` asserts exactly that
    against the EXPLAIN footer.  Elimination/fold decisions remove
    structure and are deliberately absent here.

    ``dist`` gates the order-sensitive-revert entries (the revert only
    happens when exchange planning ran); default follows ``SRJT_DIST``.
    """
    if dist is None:
        from ..utils.config import config
        dist = config.distribute
    from .plan import ORDER_SENSITIVE_AGGS
    paths = node_paths(plan)
    partial_exchanges = set()
    for n in topo_nodes(plan):
        if isinstance(n, Aggregate) and isinstance(n.child, Exchange) \
                and n.child.kind == "hash" \
                and isinstance(n.child.child, Aggregate) \
                and tuple(n.child.child.keys) == tuple(n.keys) \
                and tuple(n.child.child.names) == tuple(n.names):
            partial_exchanges.add(id(n.child))
    out = []
    for n in topo_nodes(plan):
        if isinstance(n, TopK):
            out.append({"kind": "topk", "path": paths[id(n)]})
        elif isinstance(n, Scan) and getattr(n, "_decode_pages", False):
            # SRJT_DEVICE_DECODE page-routing stamp: the structure IS the
            # attribute (fingerprint-neutral), but it evidences a planner
            # decision, so the ledger entry must get a census path too
            out.append({"kind": "scan:device_decode", "path": paths[id(n)]})
        elif isinstance(n, Exchange):
            if id(n) in partial_exchanges:
                continue  # owned by the combine Aggregate's split entry
            out.append({"kind": "broadcast" if n.kind == "broadcast"
                        else "shuffle", "path": paths[id(n)]})
        elif isinstance(n, Aggregate):
            if isinstance(n.child, Exchange) \
                    and id(n.child) in partial_exchanges:
                out.append({"kind": "partial_agg", "path": paths[id(n)]})
            elif dist and any(op in ORDER_SENSITIVE_AGGS
                              for _, op in n.aggs):
                out.append({"kind": "order_sensitive_revert",
                            "path": paths[id(n)]})
    return out


def check_partitioning(plan: PlanNode) -> None:
    """Partitioning-consistency check for distributed plans.

    Only meaningful once Exchanges are placed (a plan with none is a plain
    single-device plan and vacuously consistent).  Raises
    ``partitioning-mismatch`` when a Join's two sides are hash-placed on
    different key sets (matching rows could sit on different devices) or an
    Aggregate's child is hash-placed on keys that are not a subset of the
    group keys (a group's rows would be split across devices)."""
    if not any(isinstance(n, Exchange) for n in topo_nodes(plan)):
        return
    paths = node_paths(plan)
    memo: dict = {}
    # an Aggregate feeding an Exchange is a partial by construction (the
    # partial-agg pushdown splits one grouped agg into partial-below /
    # combine-above); its per-device split groups are intended, so the
    # subset check applies only to the combine side
    partial_aggs = {id(n.child) for n in topo_nodes(plan)
                    if isinstance(n, Exchange)}
    for node in topo_nodes(plan):
        if isinstance(node, Join) and node.how != "cross":
            lp = partitioning(node.left, memo)
            rp = partitioning(node.right, memo)
            if rp.kind == "broadcast":
                continue
            if lp.kind == "hash" and rp.kind == "hash" and \
                    not co_partitioned(lp, rp, node.left_keys,
                                       node.right_keys):
                raise PlanVerificationError(
                    "partitioning-mismatch", paths[id(node)],
                    f"join inputs hash-placed on {list(lp.keys)} vs "
                    f"{list(rp.keys)} but joined on "
                    f"{list(node.left_keys)}={list(node.right_keys)}: "
                    f"matching rows may sit on different devices")
        elif isinstance(node, Aggregate) and node.keys \
                and id(node) not in partial_aggs:
            p = partitioning(node.child, memo)
            if p.kind == "hash" and not set(p.keys) <= set(node.keys):
                raise PlanVerificationError(
                    "partitioning-mismatch", paths[id(node)],
                    f"aggregate groups on {list(node.keys)} but its input "
                    f"is hash-placed on {list(p.keys)}: groups would be "
                    f"split across devices")


def _lowered(plan: PlanNode, resolver: "SchemaResolver", cfg,
             ndev: Optional[int]) -> tuple:
    """``(physical plan, sync entries)``: ``physical.lower`` under ``cfg``
    (default: the live config) for an ``ndev``-device mesh (default: this
    process's) with the verifier's schema inference as its resolver, and
    what ``sync_budget`` charges its stages."""
    if cfg is None:
        from ..utils.config import config as cfg
    if ndev is None:
        import jax
        ndev = len(jax.devices())
    physical = lower(plan, fuse=cfg.fuse, fuse_exchange=cfg.fuse_exchange,
                     ndev=ndev, resolver=lambda node: verify(node, resolver))
    entries: list = []
    for st in physical.run_stages():
        sites = physical.sync_sites(st)
        if st.vetoed:
            entries.append({"site": "interpreted-fallback",
                            "path": st.path, "count": 0})
            continue
        if st.kind == "fused-stage" and cfg.aqe \
                and getattr(st.stage.exchange, "_aqe_split", False):
            sites = ("exchange-counts-sizing",) + sites
        entries += [{"site": site, "path": st.path, "count": 1}
                    for site in sites]
    return physical, entries


def sync_budget(plan: PlanNode, resolver: Optional[SchemaResolver] = None,
                cfg=None, ndev: Optional[int] = None) -> list:
    """Static model of the deliberate host syncs an optimized plan pays —
    one entry per sync, ``site`` naming the whitelisted call site, ``path``
    the stage that pays it.  Charges each stage of ``physical.lower`` what
    ``physical.SYNC_CHARGES`` says its kind pays; equals the runtime
    ``engine.host_sync`` counter less ``engine.combine.folds`` — a keyed
    ``stream-agg`` of more than ``segment.COMBINE_ARITY`` chunks pays one
    ``combine-fold-sizing`` per fold, and how many chunks pruning leaves
    is known at run time only (16 + 15 k chunks: k folds); a keyless one
    folds without a fetch.

    ``ndev`` is the mesh size the stages are lowered for (default: this
    process's — pass it to model a target mesh from a different host).
    The budget is EXACT, not an upper bound: a 0-row hash exchange runs the
    same two-sync shuffle over its empty planes, and the fused stage pays
    its one boundary compaction even for empty inputs
    (``segment.fused_pad``'s dead-row synthesis).  A ``fused-stage`` is
    charged one ``exchange-counts-sizing`` more when AQE is on and its
    exchange carries ``_aqe_split`` (the escape-hatch probe ALWAYS pays its
    counts fetch before picking the fused or host program).  A stage whose
    schema (read through ``resolver``) the executor's veto will demote is
    one ``interpreted-fallback`` entry of count 0; a demoted sandwich is
    followed by its exchange's and its partial's own charges.  The
    overflow/AQE-routed host fallbacks are runtime re-plans outside this
    model.  One upper-bound case remains: an agg SEGMENT whose input turns
    out empty at runtime falls back to the interpreted groupby and pays no
    sync where this model charges one."""
    return _lowered(plan, resolver or SchemaResolver(), cfg, ndev)[1]


def check_sync_budget(plans, cfg=None, ndev: Optional[int] = None) -> tuple:
    """``(entries, violations)`` over a set of optimized plans: every
    entry with a nonzero count must name a whitelisted sync site."""
    entries: list = []
    for p in plans:
        entries += sync_budget(p, cfg=cfg, ndev=ndev)
    bad = [e for e in entries
           if e["count"] and e["site"] not in SYNC_WHITELIST]
    return entries, bad


class _TraceProbe:
    """Stands in for CompiledSegment when tracing without executing
    (``_build_fn`` ticks ``traces`` inside the traced function)."""

    __slots__ = ("traces",)

    dense_k = None      # the aggregate's sort form: no key domain is known
    build_row = None

    def __init__(self):
        self.traces = 0


def _zero_table(schema: Optional[dict], rows: int = 8):
    """A zero-filled device Table matching ``schema`` — just enough
    structure for make_jaxpr to trace a segment program over it."""
    if schema is None:
        return None
    import jax.numpy as jnp

    from ..columnar import Column, Table
    from ..dtypes import TypeId
    cols, names = [], []
    for nm, dt in schema.items():
        if dt is None:
            return None
        if dt.is_string:
            cols.append(Column.string(jnp.zeros((0,), jnp.uint8),
                                      jnp.zeros((rows + 1,), jnp.int32)))
        elif dt.id == TypeId.DECIMAL128:
            cols.append(Column(dt, data=jnp.zeros((rows, 2), jnp.int64)))
        elif dt.is_fixed_width:
            cols.append(Column(dt, data=jnp.zeros((rows,),
                                                  dt.device_storage)))
        else:
            return None
        names.append(nm)
    return Table(cols, names)


def _collect_primitives(jaxpr) -> list:
    """All primitive names in a jaxpr, descending into sub-jaxprs
    (pjit/scan/cond bodies)."""
    out: list = []
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    out += _collect_primitives(inner)
                elif hasattr(sub, "eqns"):
                    out += _collect_primitives(sub)
    return out


def device_resident(tree) -> bool:
    """True when every pytree leaf is a device array (the prepared-build
    contract: builds enter the chunk program without host round-trips)."""
    import jax
    leaves = jax.tree_util.tree_leaves(tree)
    return all(isinstance(leaf, jax.Array) for leaf in leaves)


def _lint_traced(report: dict, fn, *args, require: tuple = ()) -> dict:
    """Lower ``fn(*args)`` to a jaxpr WITHOUT executing it and lint the
    artifact into ``report``: the trace must succeed (a ``.item()`` /
    ``float()`` on a tracer fails here, statically), no forbidden
    host-callback primitives, every ``require``d primitive present,
    static output shapes."""
    import jax
    try:
        closed = jax.make_jaxpr(fn)(*args)
    except Exception as e:  # noqa: BLE001 — any trace failure is the finding
        kind = type(e).__name__
        host = any(t in kind for t in
                   ("Concretization", "TracerArrayConversion",
                    "TracerBoolConversion", "TracerIntegerConversion"))
        report["ok"] = False
        report["violations"].append({
            "code": "host-concretization" if host else "trace-failure",
            "detail": f"{kind}: {e}"[:400]})
        return report
    prims = _collect_primitives(closed.jaxpr)
    report["primitives"] = len(prims)
    bad = [{"code": "forbidden-primitive", "detail": pname}
           for pname in sorted(set(prims) & _FORBIDDEN_PRIMITIVES)]
    bad += [{"code": "missing-collective",
             "detail": f"lowered without {pname} — the exchange traced away"}
            for pname in require if pname not in prims]
    for var in closed.jaxpr.outvars:
        shape = getattr(getattr(var, "aval", None), "shape", ())
        if not all(isinstance(d, int) for d in shape):
            bad.append({"code": "dynamic-shape",
                        "detail": f"output aval shape {shape} is not static"})
    report["violations"] += bad
    report["ok"] = not bad
    return report


def lint_segment(seg, input_table, builds: tuple = ()) -> dict:
    """Jaxpr-lint one segment's program over ``input_table``."""
    import jax.numpy as jnp

    from . import segment as sg
    report = {"fingerprint": seg.fingerprint()[:12], "ok": True,
              "violations": [], "primitives": 0}
    return _lint_traced(report, sg._build_fn(seg, _TraceProbe()),
                        input_table, jnp.int32(input_table.num_rows),
                        tuple(builds))


def lint_decode_segment(seg, geom, builds: tuple = ()) -> dict:
    """`lint_segment` for the fused scan-decode program: lower the
    decompress -> unpack -> segment chain over ZERO-filled page planes of
    ``geom`` and lint the one artifact.  The decode prefix is pure array
    code driven by trace-time-static page tables, so the fused program
    must carry exactly the segment's own syncs — any forbidden callback
    or dynamic shape here means the decode path smuggled in a host
    boundary the plain segment doesn't have."""
    import jax.numpy as jnp

    from ..ops.parquet_decode import zero_planes
    from . import segment as sg
    report = {"fingerprint": seg.fingerprint()[:12], "ok": True,
              "violations": [], "primitives": 0, "decode": True}
    return _lint_traced(report, sg._build_decode_fn(seg, _TraceProbe(), geom),
                        zero_planes(geom), jnp.int32(1), tuple(builds))


def lint_fused_stage(stage, input_table, mesh=None, axis=None) -> dict:
    """Jaxpr-lint a fused stage's whole ``jit(shard_map(...))`` program,
    collectives included; the ``all_to_all`` must actually be present — a
    fused stage whose exchange traced away would silently compute
    shard-local answers."""
    import jax
    import jax.numpy as jnp

    from ..parallel.mesh import ROW_AXIS, axis_size, make_mesh
    from . import segment as sg
    axis = axis or ROW_AXIS
    report = {"fingerprint": stage.fingerprint()[:12], "ok": True,
              "violations": [], "primitives": 0}
    if mesh is None:
        ndev = len(jax.devices())
        if ndev <= 1:
            report["skipped"] = ("single-device process: no mesh to lower "
                                 "the shard_map program on")
            return report
        mesh = make_mesh(ndev)
    ndev = axis_size(mesh, axis)
    padded, _ = sg.fused_pad(input_table.select(stage.sel_names()), ndev)
    in_dtypes = tuple(c.dtype for c in padded.columns)
    key_dtypes = tuple(padded.column(k).dtype for k in stage.combine.keys)
    # a fresh entry, NOT cache.get: linting must not pollute the process
    # cache with entries whose trace counter the executor never sees
    compiled = sg.CompiledFusedStage(
        ("lint",), stage, mesh, axis, in_dtypes, key_dtypes,
        padded.num_rows // ndev)
    return _lint_traced(
        report, compiled.jfn, tuple(c.data for c in padded.columns),
        tuple(c.validity for c in padded.columns),
        jnp.int64(padded.num_rows), require=("all_to_all",))


def lint_tail(tail, source_schema: dict, builds: tuple,
              rows: int = 8) -> dict:
    """Jaxpr-lint a ``tail`` stage's program (``segment._build_tail_fn``)
    over a zero-filled padded partial of ``source_schema`` (the streamed
    Aggregate's output: keys, then aggregates) and zero-filled build
    Tables: every operator above the stream in one artifact, no host
    callback, no concretization, static output shapes."""
    import types

    import jax.numpy as jnp

    from ..columnar import Column
    from . import segment as sg
    nk = len(tail.source.keys)
    dts = list(source_schema.values())
    ones = jnp.ones((rows,), jnp.bool_)
    part = (tuple(jnp.zeros((rows,), dt.device_storage) for dt in dts[:nk]),
            (ones,) * nk,
            tuple(Column(dt, data=jnp.zeros((rows,), dt.device_storage),
                         validity=ones) for dt in dts[nk:]),
            jnp.int32(0))
    probe = types.SimpleNamespace(traces=0, key_dtypes=tuple(dts[:nk]))
    report = {"fingerprint": tail.fingerprint()[:12], "ok": True,
              "violations": [], "primitives": 0,
              "nodes": [node_label(n) for n in tail.nodes], "capacity": rows}
    return _lint_traced(report, sg._build_tail_fn(tail, probe), part,
                        sg.tail_dims(tail, builds))


def lint_plan_artifacts(plan: PlanNode,
                        resolver: Optional[SchemaResolver] = None,
                        rows: int = 8, cfg=None) -> dict:
    """Pass-2 entry point: enumerate the fused segments of an OPTIMIZED
    plan, jaxpr-lint each one over a zero-filled input, check prepared
    builds stay device-resident, and attach the static sync budget.

    Returns ``{"segments": [...], "syncs": [...], "violations": [...]}``;
    an empty ``violations`` list is the pass."""
    resolver = resolver or SchemaResolver()
    reports: list = []
    violations: list = []
    physical, syncs = _lowered(plan, resolver, cfg, None)
    for st in physical.run_stages():
        if st.stage is not None:  # fused-stage
            stage = st.stage
            schema = verify(stage.partial.child, resolver)
            tbl = _zero_table(schema, rows)
            if tbl is None or st.vetoed:
                reports.append({"path": st.path, "kind": st.kind,
                                "skipped": "input schema unknown or stage "
                                           "demoted at runtime"})
                continue
            rep = lint_fused_stage(stage, tbl)
            rep["path"], rep["kind"] = st.path, st.kind
            reports.append(rep)
            violations += [{**v, "path": st.path}
                           for v in rep.get("violations", ())]
            continue
        if st.tail is not None:
            tail = st.tail
            schema = verify(tail.source, resolver)
            bts = [_zero_table(verify(j.right, resolver), rows)
                   for j in tail.joins()]
            if st.vetoed or schema is None or any(b is None for b in bts):
                reports.append({"path": st.path, "kind": st.kind,
                                "skipped": "input schema unknown or tail "
                                           "demoted at runtime"})
                continue
            rep = lint_tail(tail, schema, tuple(bts), rows)
            rep["path"], rep["kind"] = st.path, st.kind
            reports.append(rep)
            violations += [{**v, "path": st.path}
                           for v in rep["violations"]]
            continue
        seg = st.segment
        if seg is None:
            continue  # no compiled artifact of the stage's own
        schema = verify(seg.input, resolver)
        tbl = _zero_table(schema, rows)
        if tbl is None or st.vetoed:
            reports.append({"path": st.path, "kind": st.kind,
                            "skipped": "input schema unknown or segment "
                                       "interpreted at runtime"})
            continue
        builds: tuple = ()
        joins = seg.joins()
        if joins:
            bts = [_zero_table(verify(j.right, resolver), rows)
                   for j in joins]
            if any(b is None for b in bts):
                reports.append({"path": st.path, "kind": st.kind,
                                "skipped": "build-side schema unknown"})
                continue
            from ..ops.join import prepare_build
            builds = tuple(prepare_build(bt, list(j.right_keys))
                           for j, bt in zip(joins, bts))
            for j, pb in zip(joins, builds):
                if not device_resident(pb):
                    violations.append({
                        "code": "host-resident-build", "path": st.path,
                        "detail": f"prepared build for join keys "
                                  f"{list(j.right_keys)} has non-device "
                                  f"pytree leaves"})
        rep = lint_segment(seg, tbl, builds)
        rep["path"], rep["kind"] = st.path, st.kind
        reports.append(rep)
        violations += [{**v, "path": st.path} for v in rep["violations"]]
    violations += [{"code": "unwhitelisted-host-sync", "path": e["path"],
                    "detail": e["site"]}
                   for e in syncs
                   if e["count"] and e["site"] not in SYNC_WHITELIST]
    return {"segments": reports, "syncs": syncs, "violations": violations}


def lint_segment_cache(cache=None, max_shape_classes: int = 8) -> list:
    """Shape-class-explosion census over a SegmentCache: a fingerprint
    compiled under more than ``max_shape_classes`` distinct shape classes
    means unpadded dynamic shapes are retracing per chunk instead of
    re-entering one executable (io/staging.py's power-of-two buckets exist
    to prevent exactly this)."""
    if cache is None:
        from .segment import SEGMENT_CACHE
        cache = SEGMENT_CACHE
    by_fp: dict = {}
    for fp, sc, bsc in cache.snapshot_keys():
        by_fp.setdefault(fp, set()).add((sc, bsc))
    return [{"code": "shape-class-explosion", "fingerprint": fp[:12],
             "shape_classes": len(v),
             "detail": f"{len(v)} compiled shape variants "
                       f"(> {max_shape_classes}): inputs are not padding "
                       f"to stable row buckets"}
            for fp, v in sorted(by_fp.items()) if len(v) > max_shape_classes]
