"""Seeded plan-space fuzzer + differential rewrite-soundness harness.

The generative half of the plan-algebra soundness analyzer
(docs/ANALYSIS.md): every optimizer rule, the AQE rules that rewrite
plans mid-query among them, gets adversarial coverage over
random valid plans instead of the handful of shapes the tests happen
to build.  Four pieces:

1. **Warehouse generator** — a tiny seeded parquet star schema
   (``gen_warehouse``): one fact table with integer keys of differing
   cardinality, a string key, quarter-valued float64 measures (every
   value is ``n/4``, so sums/mins/maxes stay exactly representable and
   executor parity can be asserted bit-for-bit regardless of reduction
   order), a DECIMAL(9,2) measure (its frame holds int64 units), plus
   dimension tables keyed by each family.  The dataframes are kept in
   memory as the oracle's base relations.  Beside it, a fixed q5-lite
   warehouse and its plans (``pipeline_warehouse``, ``pipeline_plans``,
   ``serving_plans``), shared by the segment lint, the chaos soak and
   the verifier's tests.

2. **Plan generator** — ``gen_plan`` synthesizes a random valid plan
   over all 9 ``plan._NODE_TYPES``: scans with column subsets,
   filters over a random operator tree, projects (with computed ``*``,
   ``+``, ``-`` columns over the decimal and integer columns), joins in
   every key family (int/string) and how (inner/left/semi/anti/cross),
   aggregates (with or without group keys; order-sensitive
   ``first``/``last`` over order-deterministic chains), sorts/top-k with
   a unique tiebreak suffix (so LIMIT cutoffs are deterministic across
   executors), and occasionally a hand-placed hash Exchange in the two
   partitioning-sound positions (under an Aggregate on a subset of its
   group keys, or under a Sort).

3. **Differential harness** — ``run_case`` sweeps one plan across the
   flag matrix (interpreted / fused / distributed-shuffle /
   distributed-broadcast / distributed-AQE via ``SRJT_FUSE``/
   ``SRJT_DIST``/``SRJT_BROADCAST_ROWS``/``SRJT_AQE``),
   asserting after every variant: ``verify()`` passes on the optimized
   plan, the stamped decision ledger equals ``verify.decision_census``
   (for plans without hand-placed structure), the static sync budget
   stays inside ``SYNC_WHITELIST``, the stage forms ``physical.lower``
   chose are the ones that ran (``stage_census``: exchanges executed,
   segments run and host syncs paid equal the lowered kinds' charges),
   engine variants agree bit-exactly, and all agree with a pandas
   oracle evaluated over the in-memory frames.
   The AQE variant plans every join as a shuffle then lets the runtime
   rules (engine/adaptive.py) flip/split mid-query — parity proves the
   rewrites content-exact, and every applied rewrite must match its
   stats counter with a triggered ledger entry.

4. **Shrinker** — ``shrink`` greedily minimizes a failing plan
   (replace a node by its child, drop filter conjuncts, drop
   aggregates, drop sort keys) while the same check keeps failing,
   yielding the smallest repro to store next to the seed.

Everything is driven by ``numpy.random.default_rng([seed, case])`` —
the same seed replays the same corpus byte-for-byte, which is what
lets ci/nightly.sh hand a one-line repro (seed + minimal plan JSON) to
whoever broke an optimizer rule.
"""

from __future__ import annotations

import contextlib
import json
import os
from decimal import Decimal
from typing import Callable, Optional

import numpy as np

from ..utils.config import config
from .plan import (Aggregate, Exchange, Filter, Join, Limit, PlanNode,
                   Project, Scan, Sort, TopK, col, expr_columns, lit,
                   lit_decimal, rebuild, topo_nodes)

#: string pool for the string key family (small cardinality, fixed order)
_STRINGS = ("ash", "birch", "cedar", "dome", "elm", "fir")

#: low-cardinality columns eligible as group/sort keys, by table
_LOW_CARD = ("k1", "k2", "sk", "dgrp", "skey")

#: aggregate ops the fuzzer emits (var/std/collect_list excluded: their
#: results are not bit-comparable across reduction orders / executors)
_AGG_OPS = ("sum", "count", "count_all", "min", "max", "mean")
_ORDER_OPS = ("first", "last")

#: decimal columns of the warehouse and their scales (frames hold units);
#: a generated column of scale s has the kind ``dec<s>``
_DECIMALS = {"d": 2}

#: ledger kinds that leave structure behind (mirror verify.decision_census)
_STRUCTURAL_KINDS = frozenset(
    {"broadcast", "shuffle", "partial_agg", "topk", "order_sensitive_revert"})


# -- warehouse ---------------------------------------------------------------

def _quarters(rng, n, lo=-400, hi=400) -> np.ndarray:
    """float64 values on the 1/4 grid: exactly representable, and their
    sums stay exact, so cross-executor comparison can demand equality."""
    return rng.integers(lo, hi, n).astype(np.int64) / 4.0


def gen_warehouse(root, rng) -> dict:
    """Write the seeded star schema under ``root``; returns the catalog
    ``{name: {"path", "df"}}`` with the oracle's in-memory frames."""
    import pandas as pd
    import pyarrow as pa
    import pyarrow.parquet as pq

    os.makedirs(str(root), exist_ok=True)
    n = int(rng.integers(48, 160))
    fact = pd.DataFrame({
        "k1": rng.integers(0, 8, n).astype(np.int64),
        "k2": rng.integers(0, 5, n).astype(np.int64),
        "sk": np.array(_STRINGS, dtype=object)[rng.integers(
            0, len(_STRINGS), n)],
        "v": _quarters(rng, n),
        "w": rng.integers(-50, 50, n).astype(np.int32),
        "rid": np.arange(n, dtype=np.int64),
    })
    dk1 = np.arange(8, dtype=np.int64)
    dimfull = pd.DataFrame({           # covers every k1: left joins stay
        "dk1": dk1,                    # null-free against it
        "dv": _quarters(rng, len(dk1)),
        "dgrp": (dk1 % 3).astype(np.int64),
    })
    dk2 = np.sort(rng.choice(5, size=3, replace=False)).astype(np.int64)
    dimpart = pd.DataFrame({           # covers ~60% of k2: semi/anti have
        "dk2": dk2,                    # real survivors AND real drops
        "du": rng.integers(0, 100, len(dk2)).astype(np.int64),
    })
    dimstr = pd.DataFrame({            # string key family, full coverage
        "skey": np.array(_STRINGS, dtype=object),
        "sv": _quarters(rng, len(_STRINGS)),
    })
    # drawn last, so the other columns are what they were before it
    fact["d"] = rng.integers(-5000, 5000, n).astype(np.int64)
    cat = {}
    for name, df in (("fact", fact), ("dimfull", dimfull),
                     ("dimpart", dimpart), ("dimstr", dimstr)):
        path = str(root / f"{name}.parquet")
        table = pa.Table.from_pandas(df, preserve_index=False)
        for c, scale in _DECIMALS.items():
            if c in df:     # the file holds the decimal, the frame its units
                table = table.set_column(
                    table.schema.get_field_index(c), c,
                    pa.array([Decimal(int(u)).scaleb(-scale) for u in df[c]],
                             pa.decimal128(9, scale)))
        pq.write_table(table, path, row_group_size=max(8, len(df) // 4))
        cat[name] = {"path": path, "df": df}
    return cat


def pipeline_warehouse(root, n, rng):
    """q5-lite warehouse: ``store_sales`` (``n`` rows, 8 row groups),
    ``date_dim`` and ``store``, written under ``root``."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    pq.write_table(pa.table({
        "ss_sold_date_sk": pa.array(
            np.sort(rng.integers(0, 400, n)).astype(np.int64)),
        "ss_store_sk": pa.array(rng.integers(1, 13, n).astype(np.int64)),
        "ss_ext_sales_price": pa.array(rng.uniform(0.5, 300.0, n)),
        "ss_net_profit": pa.array(rng.uniform(-50.0, 120.0, n)),
    }), os.path.join(root, "store_sales.parquet"),
        row_group_size=max(1, n // 8))
    pq.write_table(pa.table({
        "d_date_sk": pa.array(np.arange(100, 300, dtype=np.int64)),
    }), os.path.join(root, "date_dim.parquet"))
    pq.write_table(pa.table({
        "s_store_sk": pa.array(np.arange(1, 13, dtype=np.int64)),
        "s_mgr": pa.array(np.arange(1, 13, dtype=np.int64) % 4),
    }), os.path.join(root, "store.parquet"))


def pipeline_plans(root, chunk_bytes):
    """(q5-lite plan, chunked-scan aggregate plan) over the warehouse.

    The q5 filters survive optimization as real Filter nodes (the scan
    predicate only prunes row groups), so the fused executor has chains to
    compile; the chunked aggregate feeds the scan straight into a fused
    partial-groupby segment — the double-buffered streaming shape.
    """
    dates_f = Filter(Scan(os.path.join(root, "date_dim.parquet")),
                     ("&", (">=", col("d_date_sk"), lit(100)),
                      ("<", col("d_date_sk"), lit(300))))
    sales = Scan(os.path.join(root, "store_sales.parquet"))
    kept = Filter(Join(sales, dates_f, ["ss_sold_date_sk"], ["d_date_sk"],
                       how="semi"),
                  ("&", (">", col("ss_net_profit"), lit(0.0)),
                   (">=", col("ss_sold_date_sk"), lit(100))))
    totals = Aggregate(kept, ["ss_store_sk"],
                       [("ss_ext_sales_price", "sum"),
                        ("ss_net_profit", "sum"),
                        ("ss_ext_sales_price", "count")],
                       names=["sales", "profit", "n"])
    joined = Join(totals, Scan(os.path.join(root, "store.parquet")),
                  ["ss_store_sk"], ["s_store_sk"], how="inner")
    q5 = Sort(Aggregate(joined, ["s_mgr"],
                        [("sales", "sum"), ("profit", "sum"), ("n", "sum")],
                        names=["sales", "profit", "n"]),
              (("s_mgr", True),))

    chunked = Aggregate(
        Filter(Scan(os.path.join(root, "store_sales.parquet"),
                    chunk_bytes=chunk_bytes),
               (">", col("ss_ext_sales_price"), lit(1.0))),
        ["ss_store_sk"],
        [("ss_ext_sales_price", "sum"), ("ss_net_profit", "sum"),
         ("ss_net_profit", "min"), ("ss_net_profit", "max"),
         ("ss_ext_sales_price", "count")],
        names=["sales", "profit", "lo", "hi", "n"])
    return q5, chunked


def serving_plans(root, chunk_bytes, k, base=1.0):
    """k distinct-fingerprint chunked aggregates over the warehouse.

    Same shape (filter + partial groupby, the fused streaming segment),
    different filter literal per plan — so every plan is its own plan-cache
    / result-cache entry and its own scheduler fingerprint, like k tenants
    running k different queries of the same family.
    """
    sales = os.path.join(root, "store_sales.parquet")
    return [Aggregate(
        Filter(Scan(sales, chunk_bytes=chunk_bytes),
               (">", col("ss_ext_sales_price"), lit(base + 0.25 * i))),
        ["ss_store_sk"],
        [("ss_ext_sales_price", "sum"), ("ss_net_profit", "sum"),
         ("ss_ext_sales_price", "count")],
        names=["sales", "profit", "n"]) for i in range(k)]


# -- plan generation ---------------------------------------------------------

class _Rel:
    """Generator state for one relation under construction: the plan
    node plus the facts later stages need to stay valid — column kinds,
    a column set whose combination is unique (None once lost), and
    whether row order is still scan-deterministic (a prerequisite for
    order-sensitive aggregates to be oracle-comparable)."""

    __slots__ = ("node", "kinds", "unique", "ordered")

    def __init__(self, node, kinds, unique, ordered):
        self.node = node
        self.kinds = kinds      # {name: "i64"|"i32"|"f64"|"str"|"dec<s>"}
        self.unique = unique    # tuple of column names, or None
        self.ordered = ordered  # bool


#: literal domain per generated column (lo, hi) for numerics; the
#: generator occasionally draws just outside to produce empty results
_DOMAINS = {
    "k1": (0, 8), "k2": (0, 5), "w": (-50, 50), "v": (-100.0, 100.0),
    "rid": (0, 160), "dk1": (0, 8), "dgrp": (0, 3), "dk2": (0, 5),
    "du": (0, 100), "dv": (-100.0, 100.0), "sv": (-100.0, 100.0),
    "d": (-50, 50),
}


def _gen_lit(rng, c: str, kind: str):
    if kind == "str":
        return str(_STRINGS[int(rng.integers(0, len(_STRINGS)))])
    lo, hi = _DOMAINS.get(c, (0, 100))
    span = hi - lo
    if kind.startswith("dec") and rng.random() < 0.5:
        # an exact decimal literal, at two places
        v = int(rng.integers((lo - span // 8) * 100, (hi + span // 8) * 100))
        return lit_decimal(f"{v / 100:.2f}")
    if kind == "f64":
        return float(int(rng.integers((lo - span // 8) * 4,
                                      (hi + span // 8) * 4 + 1)) / 4.0)
    return int(rng.integers(lo - max(1, span // 8),
                            hi + max(1, span // 8) + 1))


def _gen_pred(rng, kinds: dict, depth: int = 0) -> tuple:
    """Random predicate tree over the current columns."""
    r = rng.random()
    if depth < 2 and r < 0.35:
        op = ("&", "|")[int(rng.integers(0, 2))]
        return (op, _gen_pred(rng, kinds, depth + 1),
                _gen_pred(rng, kinds, depth + 1))
    if depth < 2 and r < 0.45:
        return ("not", _gen_pred(rng, kinds, depth + 1))
    cols = sorted(kinds)
    c = cols[int(rng.integers(0, len(cols)))]
    kind = kinds[c]
    if kind == "str":
        cmp = ("==", "!=")[int(rng.integers(0, 2))]
    else:
        cmp = (">=", "<=", ">", "<", "==", "!=")[int(rng.integers(0, 6))]
    v = _gen_lit(rng, c, kind)
    return (cmp, col(c), v if isinstance(v, tuple) else lit(v))


#: join specs: key column on the current relation -> (dim table, dim key,
#: dim column kinds, allowed hows).  dimpart's partial key coverage means
#: left joins against it would manufacture nulls, so it only offers the
#: null-free hows.
_JOINS = {
    "k1": ("dimfull", "dk1", {"dv": "f64", "dgrp": "i64"},
           ("inner", "left", "semi", "anti")),
    "k2": ("dimpart", "dk2", {"du": "i64"}, ("inner", "semi", "anti")),
    "sk": ("dimstr", "skey", {"sv": "f64"},
           ("inner", "left", "semi", "anti")),
}


def _stage_filter(rng, rel: _Rel, cat) -> _Rel:
    rel.node = Filter(rel.node, _gen_pred(rng, rel.kinds))
    return rel


def _stage_project(rng, rel: _Rel, cat) -> _Rel:
    keep = set(rel.unique or ())
    rest = [c for c in rel.kinds if c not in keep]
    for c in rest:
        if rng.random() < 0.7:
            keep.add(c)
    cols = [c for c in rel.kinds if c in keep]  # preserve order
    if not cols:
        return rel
    kinds = {c: rel.kinds[c] for c in cols}
    nums = [c for c in rel.kinds
            if rel.kinds[c] in ("i64", "i32") or rel.kinds[c][:3] == "dec"]
    if nums and rng.random() < 0.5:
        # computed columns: arithmetic over the decimal and int columns
        for _ in range(int(rng.integers(1, 3))):
            expr = _gen_arith(rng, nums)
            name = f"e{len(kinds)}"
            while name in kinds or name in rel.kinds:
                name += "x"
            cols.append((name, expr))
            scale = _arith_scale(expr, rel.kinds)
            kinds[name] = f"dec{scale}" if scale is not None else "i64"
    rel.node = Project(rel.node, tuple(cols))
    rel.kinds = kinds
    return rel


def _gen_arith(rng, nums: list, depth: int = 0) -> tuple:
    """``a op b`` over the integer-valued columns and small literals."""
    def operand():
        r = rng.random()
        if depth < 1 and r < 0.2:
            return _gen_arith(rng, nums, depth + 1)
        if r < 0.75:
            return col(nums[int(rng.integers(0, len(nums)))])
        if r < 0.9:
            return lit(int(rng.integers(-9, 10)))
        return lit_decimal(f"{int(rng.integers(-999, 1000)) / 100:.2f}")
    return (("*", "+", "-")[int(rng.integers(0, 3))], operand(), operand())


def _arith_scale(expr, kinds: dict):
    """The decimal scale of an arithmetic result (None: an integer)."""
    head = expr[0]
    if head == "col":
        k = kinds[expr[1]]
        return int(k[3:]) if k.startswith("dec") else None
    if head == "lit":
        return None
    if head == "lit_decimal":
        return expr[3]
    a, b = _arith_scale(expr[1], kinds), _arith_scale(expr[2], kinds)
    if a is None and b is None:
        return None
    a, b = a or 0, b or 0
    return a + b if head == "*" else max(a, b)


def _stage_join(rng, rel: _Rel, cat) -> _Rel:
    # a dim whose payload columns are already present was joined before;
    # skipping it keeps output names collision-free for the oracle
    avail = [k for k in _JOINS if k in rel.kinds
             and not any(c in rel.kinds for c in _JOINS[k][2])]
    if not avail:
        return rel
    key = avail[int(rng.integers(0, len(avail)))]
    dim, dkey, dkinds, hows = _JOINS[key]
    how = hows[int(rng.integers(0, len(hows)))]
    right = Scan(cat[dim]["path"])
    rel.node = Join(rel.node, right, (key,), (dkey,), how)
    if how in ("inner", "left"):
        # dim keys are unique, so multiplicity stays 1 and left-side
        # uniqueness survives; row order is no longer oracle-comparable
        rel.kinds = {**rel.kinds, **dkinds}
        rel.ordered = False
    return rel


def _stage_cross(rng, rel: _Rel, cat) -> _Rel:
    # cross joins only against the 3-row dimpart, to bound blowup
    if "du" in rel.kinds:
        return rel
    rel.node = Join(rel.node, Scan(cat["dimpart"]["path"]), (), (), "cross")
    rel.kinds = {**rel.kinds, "dk2": "i64", "du": "i64"}
    u = rel.unique
    rel.unique = tuple(u) + ("dk2",) if u else None
    rel.ordered = False
    return rel


def _stage_aggregate(rng, rel: _Rel, cat) -> _Rel:
    keycand = [c for c in rel.kinds if c in _LOW_CARD]
    # no group key at all: one row (Spark's ungrouped aggregate)
    nk = int(rng.integers(0, min(2, len(keycand)) + 1))
    keys = sorted(rng.choice(keycand, size=nk, replace=False).tolist()) \
        if nk else []
    numeric = [c for c in rel.kinds
               if rel.kinds[c] != "str" and c not in keys]
    ops = list(_AGG_OPS)
    if rel.ordered and rng.random() < 0.35:
        ops += list(_ORDER_OPS)
    aggs, names, kinds = [], [], {k: rel.kinds[k] for k in keys}
    has_order = False
    for i in range(int(rng.integers(1, 4))):
        op = ops[int(rng.integers(0, len(ops)))]
        if op == "count_all":
            aggs.append((None, op))
        else:
            if not numeric:
                continue
            c = numeric[int(rng.integers(0, len(numeric)))]
            aggs.append((c, op))
        nm = f"a{i}"
        names.append(nm)
        has_order = has_order or op in _ORDER_OPS
        if op in ("count", "count_all"):
            kinds[nm] = "i64"
        elif op == "mean":
            kinds[nm] = "f64"
        elif op == "sum":
            k = rel.kinds.get(aggs[-1][0])
            kinds[nm] = k if k == "f64" or k.startswith("dec") else "i64"
        else:
            kinds[nm] = rel.kinds.get(aggs[-1][0], "i64")
    if not aggs:
        aggs, names = [(None, "count_all")], ["a0"]
        kinds["a0"] = "i64"
    child = rel.node
    manual = False
    if keys and not has_order and rng.random() < 0.18:
        # partitioning-sound hand-placed shuffle: hash keys must be a
        # subset of the group keys (verify.check_partitioning)
        nx = int(rng.integers(1, len(keys) + 1))
        xkeys = sorted(rng.choice(keys, size=nx, replace=False).tolist())
        child = Exchange(child, tuple(xkeys), "hash")
        manual = True
    rel.node = Aggregate(child, tuple(keys), tuple(aggs), tuple(names))
    rel.kinds = kinds
    rel.unique = tuple(keys)
    rel.ordered = False
    if manual:
        object.__setattr__(rel.node, "_fuzz_manual_exchange", True)
    return rel


def _sort_keys(rng, rel: _Rel) -> tuple:
    """Random sort keys with the unique-combination suffix appended, so
    any LIMIT cutoff above is a total order (deterministic across
    executors and the oracle)."""
    cols = sorted(rel.kinds)
    n = int(rng.integers(1, min(2, len(cols)) + 1))
    picked = rng.choice(cols, size=n, replace=False).tolist()
    keys = [(c, bool(rng.integers(0, 2))) for c in picked]
    for u in rel.unique or ():
        if u not in picked:
            keys.append((u, True))
    return tuple(keys)


def _stage_order(rng, rel: _Rel, cat) -> _Rel:
    """Terminal ordering stage: Sort, Limit(Sort) (the fuse_topk shape),
    a direct TopK, or a Sort over a hand-placed hash exchange."""
    if rel.unique is None:
        return rel
    keys = _sort_keys(rng, rel)
    r = rng.random()
    if r < 0.30:
        rel.node = Sort(rel.node, keys)
    elif r < 0.55:
        rel.node = Limit(Sort(rel.node, keys), int(rng.integers(1, 24)))
    elif r < 0.75:
        rel.node = TopK(rel.node, keys, int(rng.integers(1, 24)))
    elif r < 0.85:
        inner = Exchange(rel.node, (keys[0][0],), "hash")
        object.__setattr__(inner, "_fuzz_manual_exchange", True)
        rel.node = Sort(inner, keys)
    rel.ordered = True
    return rel


def gen_plan(rng, cat) -> PlanNode:
    """One random valid plan over the catalog (all 9 node types
    reachable).  Same rng state -> same plan, always."""
    kinds = {"k1": "i64", "k2": "i64", "sk": "str", "v": "f64",
             "w": "i32", "rid": "i64", "d": "dec2"}
    scan_cols = None
    if rng.random() < 0.3:
        drop = ("v", "w")[int(rng.integers(0, 2))]
        scan_cols = tuple(c for c in kinds if c != drop)
        kinds = {c: kinds[c] for c in scan_cols}
    rel = _Rel(Scan(cat["fact"]["path"], columns=scan_cols),
               kinds, ("rid",), True)
    stages = (_stage_filter, _stage_join, _stage_project, _stage_cross)
    weights = (0.42, 0.30, 0.18, 0.10)
    for _ in range(int(rng.integers(1, 5))):
        rel = rng.choice(stages, p=weights)(rng, rel, cat)
    if rng.random() < 0.55:
        rel = _stage_aggregate(rng, rel, cat)
        if rng.random() < 0.35:
            rel = _stage_filter(rng, rel, cat)
    return _stage_order(rng, rel, cat).node


def has_manual_structure(plan: PlanNode) -> bool:
    """True when the UNOPTIMIZED plan carries hand-placed Exchange or
    TopK nodes — shapes whose structure predates the planner, so the
    ledger==census invariant (which models planner-made structure only)
    does not apply."""
    return any(isinstance(n, (Exchange, TopK)) for n in topo_nodes(plan))


# -- pandas oracle -----------------------------------------------------------

_PD_CMP = {">=": "__ge__", "<=": "__le__", ">": "__gt__", "<": "__lt__",
           "==": "__eq__", "!=": "__ne__"}


def _eval_pd(expr, df, scales: dict):
    """``(values, scale)``: a decimal's values are int64 units of
    ``10**-scale``, anything else has the scale None.  Comparisons and
    ``+``/``-`` bring both sides to the larger scale, ``*`` adds them —
    Spark's rules, on units."""
    head = expr[0]
    if head == "col":
        return df[expr[1]], scales.get(expr[1])
    if head == "lit":
        return expr[1], None
    if head == "lit_decimal":
        return expr[1], expr[3]
    if head == "not":
        return ~_eval_pd(expr[1], df, scales)[0], None
    (a, sa), (b, sb) = (_eval_pd(e, df, scales) for e in expr[1:])
    if head == "&":
        return a & b, None
    if head == "|":
        return a | b, None
    if head == "*":
        return a * b, None if sa is None and sb is None \
            else (sa or 0) + (sb or 0)
    s = None
    if sa is not None or sb is not None:    # both to the larger scale
        s = max(sa or 0, sb or 0)
        a, b = a * 10 ** (s - (sa or 0)), b * 10 ** (s - (sb or 0))
    if head == "+":
        return a + b, s
    if head == "-":
        return a - b, s
    return getattr(a, _PD_CMP[head])(b), None


def _oracle_scan(node: Scan, env):
    df = env[str(node.path)]
    if node.columns is not None:
        df = df[list(node.columns)]
    return df.copy()  # scan.predicate only prunes row groups


def _oracle_filter(node: Filter, env):
    df = _oracle(node.child, env)
    mask, _ = _eval_pd(node.predicate, df, env["scales"])
    mask = np.asarray(mask, dtype=bool)
    for c in expr_columns(node.predicate):  # a NULL operand drops the row
        mask = mask & df[c].notna().to_numpy()
    return df[mask]


def _oracle_project(node: Project, env):
    df = _oracle(node.child, env)
    out = {}
    for name, e in node.items:
        v, scale = _eval_pd(e, df, env["scales"])
        if e[0] != "col":
            env["scales"][name] = scale
        out[name] = v if not np.isscalar(v) else np.full(len(df), v)
    import pandas as pd
    return pd.DataFrame(out, index=df.index)


def _oracle_join(node: Join, env):
    left = _oracle(node.left, env)
    right = _oracle(node.right, env)
    lk, rk = list(node.left_keys), list(node.right_keys)
    if node.how in ("semi", "anti"):
        hit = left.merge(right[rk].drop_duplicates(), left_on=lk,
                         right_on=rk, how="inner")
        key = left[lk].apply(tuple, axis=1) if len(lk) > 1 else left[lk[0]]
        seen = set(hit[lk].apply(tuple, axis=1)) if len(lk) > 1 \
            else set(hit[lk[0]])
        mask = key.isin(seen)
        return left[mask if node.how == "semi" else ~mask]
    if node.how == "cross":
        out = left.merge(right, how="cross")
    else:
        out = left.merge(right, left_on=lk, right_on=rk, how=node.how,
                         suffixes=("", "_r"))
    drop = [k for k in rk if k not in left.columns]
    return out.drop(columns=drop)


_PD_AGG = {"sum": "sum", "min": "min", "max": "max", "mean": "mean",
           "count": "count", "first": "first", "last": "last"}


def _oracle_aggregate(node: Aggregate, env):
    import pandas as pd
    df = _oracle(node.child, env)
    scales = env["scales"]
    for (cname, op), outname in zip(node.aggs, node.names):
        if op in ("sum", "min", "max", "first", "last"):
            scales[outname] = scales.get(cname)
    if not node.keys:
        # one row, an empty input's too: sum/min/max/mean NULL, counts 0
        row = {}
        for (cname, op), outname in zip(node.aggs, node.names):
            if op == "count_all":
                row[outname] = len(df)
            elif op == "count":
                row[outname] = int(df[cname].notna().sum())
            elif not len(df):
                row[outname] = None
            elif op == "mean" and scales.get(cname):
                row[outname] = df[cname].sum() / len(df) \
                    / 10 ** scales[cname]
            else:
                row[outname] = df[cname].agg(_PD_AGG[op])
        return pd.DataFrame({k: pd.Series([v], dtype=object if v is None
                                          else None)
                             for k, v in row.items()})
    g = df.groupby(list(node.keys), sort=False, dropna=False)
    pieces = {}
    for (cname, op), outname in zip(node.aggs, node.names):
        if op == "count_all":
            pieces[outname] = g.size()
        elif op == "mean" and scales.get(cname):
            pieces[outname] = g[cname].mean() / 10 ** scales[cname]
        else:
            pieces[outname] = g[cname].agg(_PD_AGG[op])
    out = pd.DataFrame(pieces).reset_index()
    return out[list(node.keys) + list(node.names)]


def _oracle_sort(node: Sort, env):
    df = _oracle(node.child, env)
    return df.sort_values([c for c, _ in node.keys],
                          ascending=[a for _, a in node.keys],
                          kind="mergesort")


def _oracle_limit(node: Limit, env):
    return _oracle(node.child, env).head(node.n)


def _oracle_topk(node: TopK, env):
    df = _oracle(node.child, env)
    return df.sort_values([c for c, _ in node.keys],
                          ascending=[a for _, a in node.keys],
                          kind="mergesort").head(node.n)


def _oracle_exchange(node: Exchange, env):
    return _oracle(node.child, env)  # repartitioning preserves the multiset


#: plan-node class -> reference semantics; tools/srjt_lint.py asserts
#: this stays exhaustive over plan._NODE_TYPES, like verify._INFER
_ORACLE = {
    Scan: _oracle_scan,
    Filter: _oracle_filter,
    Project: _oracle_project,
    Join: _oracle_join,
    Aggregate: _oracle_aggregate,
    Sort: _oracle_sort,
    Limit: _oracle_limit,
    TopK: _oracle_topk,
    Exchange: _oracle_exchange,
}


def _oracle(node: PlanNode, env):
    fn = _ORACLE.get(type(node))
    if fn is None:
        raise TypeError(f"no oracle rule for {type(node).__name__} "
                        f"(register it in fuzz._ORACLE)")
    return fn(node, env)


def oracle(plan: PlanNode, cat) -> "object":
    """Reference result of the UNOPTIMIZED plan over the in-memory
    frames, as a pandas DataFrame."""
    env = {e["path"]: e["df"] for e in cat.values()}
    env["scales"] = dict(_DECIMALS)     # name -> decimal scale, as it grows
    return _oracle(plan, env).reset_index(drop=True)


# -- differential harness ----------------------------------------------------

#: the flag matrix: every generated plan runs under each of these;
#: broadcast_rows=0 forces shuffle joins, the huge threshold forces
#: broadcast, so both distributed join strategies are exercised per plan
VARIANTS = (
    {"name": "interp", "fuse": False, "distribute": False},
    {"name": "fused", "fuse": True, "distribute": False},
    {"name": "dist-shuffle", "fuse": True, "distribute": True,
     "broadcast_rows": 0},
    {"name": "dist-broadcast", "fuse": True, "distribute": True,
     "broadcast_rows": 1_000_000},
    # AQE adversary: plan every join as a shuffle (broadcast_rows=0), then
    # let the runtime rules rewrite mid-query — every eligible build flips
    # to broadcast (aqe_broadcast_rows) and every measurable skew splits
    # (aqe_skew at the 1.0 floor).  Parity vs the non-AQE variants asserts
    # the rewrites are content-exact; the adaptive-ledger check asserts
    # every applied rewrite left a triggered entry behind
    {"name": "dist-aqe", "fuse": True, "distribute": True,
     "broadcast_rows": 0, "aqe": True, "aqe_broadcast_rows": 1_000_000,
     "aqe_skew": 1.0},
    # whole-stage fusion: the partial/final aggregate sandwich lowers to
    # ONE jit(shard_map) program (SRJT_FUSE_EXCHANGE).  Bit-exact parity
    # vs every other variant asserts the in-program exchange is
    # content-exact; the stage-census check asserts the lowered
    # exchange still ticks stats["exchanges"]; the sync-whitelist check
    # covers the fused-stage budget entries
    {"name": "dist-fused", "fuse": True, "distribute": True,
     "broadcast_rows": 0, "fuse_exchange": True},
)

#: extra variants the nightly sweep adds on top of VARIANTS
FULL_VARIANTS = VARIANTS + (
    {"name": "dist-nofuse", "fuse": False, "distribute": True,
     "broadcast_rows": 0},
    # fusion composed with the AQE adversary: the counts probe routes hot
    # stages to the host path where the skew split still fires, cold ones
    # into the fused program — parity and the adaptive-ledger invariant
    # hold either way
    {"name": "dist-fused-aqe", "fuse": True, "distribute": True,
     "broadcast_rows": 0, "fuse_exchange": True, "aqe": True,
     "aqe_broadcast_rows": 1_000_000, "aqe_skew": 1.0},
)


@contextlib.contextmanager
def _flags(**kw):
    """Temporarily set config fields (the sweep axis).  Field mutation,
    not env vars: the flag matrix must not leak into child state."""
    saved = {k: getattr(config, k) for k in kw}
    try:
        for k, v in kw.items():
            setattr(config, k, v)
        yield
    finally:
        for k, v in saved.items():
            setattr(config, k, v)


class SoundnessFailure(Exception):
    """One differential-harness check failed for one (plan, variant)."""

    def __init__(self, check: str, variant: str, message: str):
        self.check = check
        self.variant = variant
        super().__init__(f"[{check}] under {variant}: {message}")


def _as_frame(table):
    import pandas as pd
    names = table.names or [f"c{i}" for i in range(table.num_columns)]
    cols = {}
    for n, c in zip(names, table.columns):
        if c.dtype.is_string:
            cols[n] = np.array(c.to_pylist(), dtype=object)
        else:
            cols[n] = np.asarray(c.to_numpy())
            valid = c.validity_numpy()
            if not valid.all():     # a NULL (an ungrouped sum of nothing)
                cols[n] = np.array([v if ok else None for v, ok
                                    in zip(cols[n].tolist(), valid)],
                                   dtype=object)
    return pd.DataFrame(cols)


def _canonical(df):
    """Row-multiset canonical form: stable-sorted by every column."""
    if not len(df.columns):
        return df.reset_index(drop=True)
    return df.sort_values(list(df.columns),
                          kind="mergesort").reset_index(drop=True)


def _frames_match(a, b, exact: bool) -> Optional[str]:
    """None when equal as row multisets (same column order), else a
    short description of the first difference."""
    import pandas as pd
    if list(a.columns) != list(b.columns):
        return f"column order {list(a.columns)} != {list(b.columns)}"
    if len(a) != len(b):
        return f"row count {len(a)} != {len(b)}"
    ca, cb = _canonical(a), _canonical(b)
    kw = {"check_exact": True} if exact \
        else {"check_exact": False, "rtol": 1e-9, "atol": 1e-9}
    try:
        pd.testing.assert_frame_equal(ca, cb, check_dtype=False, **kw)
    except AssertionError as e:
        return str(e).split("\n")[0][:200]
    return None


def _check_ledger(opt, dist: bool) -> Optional[str]:
    """Structural ledger entries must equal decision_census, kind for
    kind and path for path (the PR 12 invariant, now fuzzed)."""
    from .verify import decision_census
    led = sorted((d["kind"], d.get("path"))
                 for d in getattr(opt, "_decisions", ())
                 if d["kind"] in _STRUCTURAL_KINDS)
    cen = sorted((c["kind"], c["path"])
                 for c in decision_census(opt, dist=dist))
    if led != cen:
        return f"ledger {led} != census {cen}"
    return None


def stage_census(physical, stats: dict, qm=None) -> Optional[str]:
    """What ``physical.lower`` chose against what the run reports: None
    when they agree, else the first difference.

    From ``stats`` alone, exactly: the exchanges executed, whether
    anything streamed, whether a top-k did.  With the run's QueryMetrics
    (``qm``) also the fused segments run and the ``engine.host_sync``
    counter against the stages' sync sites (+ one sizing sync per fold of
    a long stream, ``engine.combine.folds``) — less the stages a veto demoted that
    the static side can name: a ``Stage.vetoed`` segment (schema), an
    ``agg`` segment over an empty input (its span's ``rows_in``), a stream
    that staged no chunk.  A ``stream-agg`` whose consumed nodes have spans
    of their own ran interpreted; unless ``vetoed`` said so that is a
    difference (the unique-build veto would read so too: no plan of the
    suite or the fuzzer streams over a build with duplicate hashes).  A
    ``tail`` is charged with ``PhysicalPlan.sync_sites`` (its stream
    compacts nothing): over a stream that staged no chunk it demotes, which
    the static side can name (``PhysicalPlan.demotion``); otherwise a run
    without ``engine.tail.compiled`` is a difference (a build matched twice
    demotes at run time only, as the stream's unique-build veto does).  A
    per-chunk re-walk runs the segments under it once per chunk, so those
    counts are then lower bounds.  Ladder steps and AQE rewrites change
    forms mid-run: with either, only the ``stats`` checks apply."""
    stages = [st for st in physical.run_stages()
              if not ((st.stage is not None or st.tail is not None)
                      and st.vetoed)]
    kinds = [st.kind for st in stages]
    want = {"exchanges": sum(k.startswith("exchange-") or k == "fused-stage"
                             for k in kinds),
            "streamed": any(st.scan is not None for st in stages),
            "topk": "stream-topk" in kinds}
    got = {k: stats[k] for k in want}
    if got != want:
        return f"stats {got} != lowered {want}"
    if qm is None or stats.get("degradations") or config.aqe:
        return None
    spans = qm.node_spans
    rewalk = False
    ran = []
    top = stages[0]
    if top.tail is not None:
        # the one veto of a tail the static side can name besides
        # ``vetoed``: a stream that staged no chunk hands it a Table
        if not stats["chunks"]:
            stages = physical.demotion(top) + stages[1:]
        elif not qm.counters.get("engine.tail.compiled", 0):
            return f"tail at {top.path} ran interpreted"
    for st in stages:
        if st.scan is not None:
            walked = st.kind != "stream-agg" or st.vetoed
            # (a stream that staged no chunk walks its nodes once, over an
            # empty chunk, for the output's schema)
            if not walked and stats["chunks"] \
                    and any(id(n) in spans for n in st.nodes
                            if n is not st.node):
                return f"stream-agg at {st.path} ran interpreted"
            rewalk = rewalk or (walked and stats["chunks"] > 1)
            if walked or not stats["chunks"]:
                continue
        elif st.vetoed or (st.kind == "agg"
                           and not spans[id(st.node)]["rows_in"]):
            continue
        ran.append(st)
    want = {"fused_segments": sum(st.segment is not None for st in ran),
            "host_syncs": sum(len(physical.sync_sites(st)) for st in ran)
            # a keyed stream's folds are sized; a keyless one's are not
            + (qm.counters.get("engine.combine.folds", 0)
               if any(st.kind == "stream-agg" and st.node.keys
                      for st in ran) else 0)}
    got = {"fused_segments": stats["fused_segments"],
           "host_syncs": qm.counters.get("engine.host_sync", 0)}
    if any(got[k] < want[k] for k in want) or (got != want and not rewalk):
        return (f"ran {got} {'<' if rewalk else '!='} lowered {want} "
                f"({sorted(set(kinds))})")
    return None


def run_case(plan: PlanNode, cat, variants=VARIANTS,
             optimize_fn: Optional[Callable] = None) -> None:
    """Run one plan through the full differential matrix; raises
    :class:`SoundnessFailure` on the first violated invariant.

    ``optimize_fn`` overrides ``optimizer.optimize`` — the
    broken-rule-injection tests pass a sabotaged pipeline here and
    assert the harness catches it.
    """
    from ..utils import metrics
    from . import optimizer
    from .executor import execute, lowering_flags, new_stats
    from .physical import lower
    from .verify import (SYNC_WHITELIST, SchemaResolver, sync_budget,
                         verify)
    opt_fn = optimize_fn or optimizer.optimize
    manual = has_manual_structure(plan)
    ref = oracle(plan, cat)
    results = []
    for v in variants:
        name = v["name"]
        flags = {k: val for k, val in v.items() if k != "name"}
        dist = bool(flags.get("distribute", False))
        with _flags(verify=True, **flags):
            try:
                opt = opt_fn(plan, distribute=dist)
            except Exception as e:
                raise SoundnessFailure("optimize", name, repr(e)[:300])
            try:
                verify(opt)
            except Exception as e:
                raise SoundnessFailure("verify-after-rewrite", name,
                                       repr(e)[:300])
            if not manual:
                bad = _check_ledger(opt, dist)
                if bad:
                    raise SoundnessFailure("ledger-census", name, bad)
            for e in sync_budget(opt, cfg=config):
                if e["count"] and e["site"] not in SYNC_WHITELIST:
                    raise SoundnessFailure(
                        "sync-whitelist", name,
                        f"unwhitelisted sync {e['site']} at {e['path']}")
            stats = new_stats()
            try:
                with metrics.query(f"fuzz:{name}") as qm:
                    tbl = execute(opt, stats)
            except Exception as e:
                raise SoundnessFailure("execute", name, repr(e)[:300])
            resolver = SchemaResolver()
            bad = stage_census(
                lower(opt, **lowering_flags(),
                      resolver=lambda n: verify(n, resolver)), stats, qm)
            if bad:
                raise SoundnessFailure("stage-census", name, bad)
            if flags.get("aqe"):
                # runtime rewrites must leave evidence: every applied
                # flip/split bumped its stats counter AND recorded a
                # triggered ledger entry — the two move in lockstep or
                # an adaptive rewrite ran unaccounted.  Structural
                # entries must still equal the census (adaptive kinds
                # are runtime-only, outside _STRUCTURAL_KINDS).
                if not manual:
                    bad = _check_ledger(opt, dist)
                    if bad:
                        raise SoundnessFailure("ledger-census-post-aqe",
                                               name, bad)
                rt = [d for d in getattr(opt, "_decisions", ())
                      if d.get("runtime")]
                flips = sum(1 for d in rt
                            if d["kind"] == "adaptive:broadcast_flip"
                            and d.get("triggered"))
                splits = sum(1 for d in rt
                             if d["kind"] == "adaptive:skew_split"
                             and d.get("triggered"))
                if flips != stats.get("aqe_flips", 0) \
                        or splits != stats.get("aqe_splits", 0):
                    raise SoundnessFailure(
                        "adaptive-ledger", name,
                        f"triggered ledger (flips={flips}, "
                        f"splits={splits}) != stats "
                        f"(flips={stats.get('aqe_flips', 0)}, "
                        f"splits={stats.get('aqe_splits', 0)})")
            results.append((name, _as_frame(tbl)))
    base_name, base = results[0]
    for name, frame in results[1:]:
        bad = _frames_match(base, frame, exact=True)
        if bad:
            raise SoundnessFailure("executor-parity", name,
                                   f"{name} != {base_name}: {bad}")
    bad = _frames_match(base, ref, exact=False)
    if bad:
        raise SoundnessFailure("oracle-parity", base_name,
                               f"engine != pandas oracle: {bad}")


# -- shrinker ----------------------------------------------------------------

def _replace(root: PlanNode, target: PlanNode,
             sub: PlanNode) -> PlanNode:
    """New tree with ``target`` (by identity) swapped for ``sub``."""
    if root is target:
        return sub
    changes = {}
    for f in ("child", "left", "right"):
        c = getattr(root, f, None)
        if isinstance(c, PlanNode):
            r = _replace(c, target, sub)
            if r is not c:
                changes[f] = r
    return rebuild(root, **changes) if changes else root


def _conjuncts(expr) -> list:
    if expr[0] == "&":
        return _conjuncts(expr[1]) + _conjuncts(expr[2])
    return [expr]


def _candidates(plan: PlanNode):
    """Structurally smaller variants of ``plan``, coarsest first."""
    for n in topo_nodes(plan):
        child = getattr(n, "child", None)
        if isinstance(child, PlanNode):
            yield _replace(plan, n, child)
        if isinstance(n, Join):
            yield _replace(plan, n, n.left)
    for n in topo_nodes(plan):
        if isinstance(n, Filter):
            parts = _conjuncts(n.predicate)
            if len(parts) > 1:
                for i in range(len(parts)):
                    kept = parts[:i] + parts[i + 1:]
                    pred = kept[0]
                    for p in kept[1:]:
                        pred = ("&", pred, p)
                    yield _replace(plan, n, Filter(n.child, pred))
        elif isinstance(n, Aggregate) and len(n.aggs) > 1:
            for i in range(len(n.aggs)):
                yield _replace(
                    plan, n,
                    Aggregate(n.child, n.keys,
                              n.aggs[:i] + n.aggs[i + 1:],
                              n.names[:i] + n.names[i + 1:]))
        elif isinstance(n, (Sort, TopK)) and len(n.keys) > 1:
            for i in range(len(n.keys)):
                yield _replace(plan, n,
                               rebuild(n, keys=n.keys[:i] + n.keys[i + 1:]))


def shrink(plan: PlanNode, fails: Callable) -> PlanNode:
    """Greedy fixpoint minimization: adopt any structurally smaller
    candidate for which ``fails(candidate)`` still returns truthy (the
    caller pins "same check code" inside ``fails``), until no candidate
    improves.  ``fails`` must treat an INVALID candidate (verify error
    on the unoptimized plan, oracle crash) as not-failing, so the
    shrinker never walks out of the valid-plan space."""
    cur = plan
    improved = True
    while improved:
        improved = False
        for cand in _candidates(cur):
            if cand is None or cand is cur:
                continue
            if len(topo_nodes(cand)) >= len(topo_nodes(cur)):
                continue
            try:
                if fails(cand):
                    cur = cand
                    improved = True
                    break
            except Exception:
                continue  # candidate invalid or check crashed: skip
    return cur


# -- corpus driver -----------------------------------------------------------

def same_check_fails(cat, check: str, variants=VARIANTS) -> Callable:
    """A ``fails`` predicate for :func:`shrink`: candidate must be a
    valid plan AND reproduce the same failing check code."""
    from .verify import verify

    def _fails(cand: PlanNode) -> bool:
        try:
            verify(cand)
            oracle(cand, cat)
        except Exception:
            return False  # invalid candidate, not a repro
        try:
            run_case(cand, cat, variants)
        except SoundnessFailure as e:
            return e.check == check
        return False

    return _fails


def run_corpus(seed: int, count: int, root, variants=VARIANTS,
               optimize_fn: Optional[Callable] = None,
               log: Optional[Callable] = None,
               shrink_failures: bool = True) -> dict:
    """The fuzzing loop: one seeded warehouse, ``count`` generated
    plans, each swept through the variant matrix.  Returns
    ``{"seed", "cases", "failures": [...]}`` where each failure carries
    the case index, the check, the message, and the SHRUNK minimal plan
    as canonical JSON — exactly what ci/nightly.sh persists as the
    repro artifact."""
    wrng = np.random.default_rng([seed, 0])
    cat = gen_warehouse(root, wrng)
    failures = []
    for i in range(count):
        rng = np.random.default_rng([seed, i + 1])
        plan = gen_plan(rng, cat)
        try:
            run_case(plan, cat, variants, optimize_fn=optimize_fn)
        except SoundnessFailure as e:
            minimal = plan
            if shrink_failures and optimize_fn is None:
                minimal = shrink(plan, same_check_fails(cat, e.check,
                                                        variants))
            elif shrink_failures:
                # injected-rule runs shrink against the same sabotaged
                # pipeline, not the stock optimizer
                def _fails(cand, _check=e.check):
                    try:
                        run_case(cand, cat, variants,
                                 optimize_fn=optimize_fn)
                    except SoundnessFailure as se:
                        return se.check == _check
                    return False
                minimal = shrink(plan, _fails)
            failures.append({
                "seed": seed, "case": i, "check": e.check,
                "variant": e.variant, "message": str(e),
                "plan_nodes": len(topo_nodes(plan)),
                "minimal_nodes": len(topo_nodes(minimal)),
                "minimal_plan": json.loads(
                    minimal.serialize().decode("utf-8")),
            })
            if log:
                log(f"case {i}: FAIL {e.check} "
                    f"({len(topo_nodes(plan))} -> "
                    f"{len(topo_nodes(minimal))} nodes)")
        else:
            if log and (i + 1) % 10 == 0:
                log(f"case {i + 1}/{count}: ok")
    return {"seed": seed, "cases": count, "failures": failures}
