"""The comparison that decides ``correct``: an answer the system gave
against the plain reference's answer to the same query.

Two numbers are read from each comparison:

* ``float_rel_err``: the largest relative difference over every float
  cell, ``|got - ref| / |ref|``;
* ``exact_mismatch``: how many key, count or integer cells differ, plus
  the difference in the number of rows.

Rows are aligned by the template's key columns. Answers whose columns or
row counts differ cannot be aligned: they read ``float_rel_err = 1``. A
template that declares its whole groups (``groups`` in
``queries/<template>.py``) is also compared on every group its AGG
produced, before a filter on the aggregates drops some, through the same
two numbers.
"""
from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

READINGS = ("float_rel_err", "exact_mismatch")


def _sorted(ans: Dict[str, np.ndarray], keys: Sequence[str]):
    order = np.lexsort([np.asarray(ans[k]) for k in reversed(keys)])
    return {c: np.asarray(v)[order] for c, v in ans.items()}


def columns(ans: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """An answer as named columns: a query that ends in a filter or a
    selection returns one column of packed records."""
    if len(ans) == 1:
        (col,) = ans.values()
        col = np.asarray(col)
        if col.dtype.names is not None:
            return {f: col[f] for f in col.dtype.names}
    return ans


def compare(got: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
            keys: Sequence[str]) -> Dict[str, float]:
    got = columns(got)
    n_got = len(next(iter(got.values()))) if got else 0
    n_ref = len(next(iter(ref.values())))
    if set(got) != set(ref) or n_got != n_ref:
        return {"float_rel_err": 1.0,
                "exact_mismatch": abs(n_got - n_ref) + max(n_got, n_ref)}
    g, r = _sorted(got, keys), _sorted(ref, keys)
    worst, mismatch = 0.0, 0
    for col in ref:
        a, b = g[col], r[col]
        if b.dtype.kind == "f":
            a = a.astype(np.float64)
            b = b.astype(np.float64)
            denom = np.maximum(np.abs(b), np.finfo(np.float64).tiny)
            rel = np.abs(a - b) / denom
            rel = np.where(np.isnan(rel), 1.0, rel)
            if rel.size:
                worst = max(worst, float(rel.max()))
        else:
            mismatch += int(np.count_nonzero(a != b))
    return {"float_rel_err": worst, "exact_mismatch": mismatch}


def tapped_groups(outputs) -> Dict[str, np.ndarray]:
    """The groups of the AGG outputs a query's tap kept (each a list of
    partitions, each a list of column batches), as named columns."""
    batches = [b for out in outputs or () for part in out for b in part]
    if not batches:
        return {}
    return {c: np.concatenate([np.asarray(b[c]) for b in batches])
            for c in batches[0].names}


def combine(readings: Sequence[Dict[str, float]]) -> Dict[str, float]:
    """The run's readings: the worst float difference and the total of
    mismatched exact cells over every compared answer."""
    return {"float_rel_err": max((r["float_rel_err"] for r in readings),
                                 default=0.0),
            "exact_mismatch": sum(r["exact_mismatch"] for r in readings)}


def within(readings: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(readings[k] <= limits[k] for k in READINGS)
