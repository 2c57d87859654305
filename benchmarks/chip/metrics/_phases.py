"""Shared arithmetic of the readers of the spans inside the op spans
(``agg:*`` in the aggregation, ``stage:*`` in the fused stages) and of the
host-to-device byte counter. A program that records none of them reads
nothing."""
from __future__ import annotations

import numpy as np

from metrics._lib import traced

H2D_COUNTER = "device.h2d.bytes.total"


def phase_ms(run, names):
    """Mean ms per traced query of the ``kernel`` spans named in
    ``names``; nothing where no traced query records one."""
    per_query, found = [], False
    for q in traced(run):
        total = 0
        for sp in q.trace.spans:
            if sp.cat == "kernel" and sp.name in names:
                total += sp.dur_ns
                found = True
        per_query.append(total)
    return float(np.mean(per_query)) / 1e6 if found else None


def h2d_mb(run):
    """Bytes handed to the device over the window (the counter's
    difference), per completed query, in MB (1e6 bytes)."""
    done = sum(q.ok for q in run.queries)
    moved = run.counters.get(H2D_COUNTER)
    if moved is None or done == 0:
        return None
    return moved / done / 1e6
