"""Plain numpy helpers shared by the templates' references. They import
nothing of the system under test."""
from __future__ import annotations

import numpy as np

# 1998-12-01 in days since 1970-01-01 (Q1's reference date)
Q1_END = int(np.datetime64("1998-12-01", "D").astype(np.int64))


def day(iso: str) -> int:
    return int(np.datetime64(iso, "D").astype(np.int64))


def group_sums(inv: np.ndarray, n_groups: int, values: np.ndarray,
               dtype) -> np.ndarray:
    """Per-group sums of ``values`` accumulated in ``dtype``: pairwise
    (``np.sum``) over each group's rows when there are few groups, and in
    row order (``np.add.reduceat``) when there are many."""
    v = values.astype(dtype, copy=False)
    if n_groups <= 64:
        return np.array([np.sum(v[inv == g], dtype=dtype)
                         for g in range(n_groups)], dtype=dtype)
    order = np.argsort(inv, kind="stable")
    starts = np.searchsorted(inv[order], np.arange(n_groups))
    return np.add.reduceat(v[order], starts).astype(dtype, copy=False)
