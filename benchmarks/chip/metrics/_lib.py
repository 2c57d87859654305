"""Shared arithmetic of the metric readers."""
from __future__ import annotations

import numpy as np

from trace_reduce import overlap, union


def traced(run):
    return [q for q in run.queries if q.ok and q.trace is not None]


def op_ms(trace, pred) -> float:
    """Milliseconds of the op spans whose label passes ``pred``."""
    return sum(sp.dur_ns for sp in trace.spans
               if sp.cat == "op" and pred(sp.name)) / 1e6


def mean_per_query(run, fn):
    qs = traced(run)
    if not qs:
        return None
    return float(np.mean([fn(q.trace) for q in qs]))


def idle_pct(run):
    d = run.device
    if d is None or d["window_ns"] <= 0:
        return None
    return 100.0 * (1.0 - d["busy_ns"] / d["window_ns"])


def rows_rate(run):
    """Lineitem rows scanned by every completed query, over the time from
    the window's start to the end of its last query."""
    t0, t1 = run.window
    rows = sum(q.spec.rows for q in run.queries if q.ok)
    if t1 <= t0 or rows == 0:
        return None
    return rows / ((t1 - t0) / 1e9)


def agg_ms(run):
    """Mean ms per query of the op spans whose label contains ``AGG``."""
    return mean_per_query(run, lambda t: op_ms(t, lambda n: "AGG" in n))


def stage_ms(run):
    """Mean ms per query of the op spans whose label lacks ``AGG``."""
    return mean_per_query(run, lambda t: op_ms(t, lambda n: "AGG" not in n))


def device_s_in_ops(run, qs, pred) -> float:
    """Seconds of device time (the union of operation intervals, averaged
    over the chips traced) that fell inside the op spans of the queries
    ``qs`` whose label passes ``pred``."""
    spans = union([(sp.t0, sp.t1) for q in qs for sp in q.trace.spans
                   if sp.cat == "op" and pred(sp.name)])
    busy = run.device["busy"]
    return sum(overlap(b, spans) for b in busy.values()) / len(busy) / 1e9


def roofline(run, work_key: str, pred):
    """The least time the declared work ``work_key`` takes at HBM peak, as
    a share (%) of the device time inside the op spans whose label passes
    ``pred``. Nothing where no device time fell inside them."""
    qs = traced(run)
    if not qs or run.peak is None or run.device is None:
        return None
    spent = device_s_in_ops(run, qs, pred)
    least = sum(q.work[work_key] for q in qs) / run.peak["hbm_bytes_per_s"]
    return 100.0 * least / spent if spent > 0 else None
