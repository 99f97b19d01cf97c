"""The comparison that decides `correct`.

Every result the timed window produced is compared with the plain pandas
reference of the same query on the same data.  The configurations state
exact 64-bit integers and doubles, so the comparison is exact: each number
below has the limit 0.

    results_differing   results of the window that are not the reference
                        (a failed request, another shape, dtype or null
                        mask, or any value that differs)
    max_rel_gap         the widest |served - reference| / max(|reference|, 1)
                        over every value of every result
    results_compared    how many results were compared (its limit is a
                        floor: a window that produced none proves nothing)

The control (`control.py`, `tests/test_control.py`) is the same reference
computed in float32, the nearest precision below the float64 the
configurations state, put in the program's place: it reads a gap above 0.
"""

from __future__ import annotations

import numpy as np


def result_gap(cols, want) -> float:
    """0.0 where the exported columns equal the reference frame exactly,
    else the widest relative gap (inf where they cannot be compared)."""
    if cols is None or len(cols) != want.shape[1]:
        return float("inf")
    worst = 0.0
    for (_dtype, data, validity), name in zip(cols, want.columns):
        ref = want[name].to_numpy()
        if validity is not None and not bool(np.all(validity)):
            return float("inf")         # the data has no nulls
        if not isinstance(data, np.ndarray) or data.shape != ref.shape \
                or data.dtype != ref.dtype:
            return float("inf")
        if len(ref) == 0 or np.array_equal(data, ref):
            continue
        with np.errstate(all="ignore"):
            gap = np.abs(data.astype(np.float64) - ref.astype(np.float64)) \
                / np.maximum(np.abs(ref.astype(np.float64)), 1.0)
        gap = float(np.nanmax(gap)) if not np.all(np.isnan(gap)) \
            else float("inf")
        # values that differ only beyond float64's reach still differ
        worst = max(worst, gap if gap > 0.0 else np.finfo(np.float64).tiny)
    return worst


def compare(results: list, want) -> dict:
    """``results``: one list of exported columns per request of the
    window (None where the request failed)."""
    gaps = [result_gap(cols, want) for cols in results]
    return {
        "results_compared": {"value": len(gaps), "limit": 1, "at_least": True},
        "results_differing": {"value": sum(g != 0.0 for g in gaps),
                              "limit": 0},
        "max_rel_gap": {"value": max(gaps, default=float("inf")),
                        "limit": 0.0},
    }


def verdict(checks: dict) -> bool:
    return all(c["value"] >= c["limit"] if c.get("at_least")
               else c["value"] <= c["limit"] for c in checks.values())
