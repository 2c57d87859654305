"""Shared arithmetic of the readers of the join and its exchanges: the
JOIN op spans, the ``join:*`` spans inside them, the exchange spans they
enclose, and the shuffle byte counter. A program that records none of
them reads nothing."""
from __future__ import annotations

import numpy as np

from metrics._lib import traced

SHUFFLE_COUNTER = "exchange.shuffle.bytes.total"
PROBE_PHASES = ("join:build", "join:probe", "join:gather")


def _is_join(sp) -> bool:
    return sp.cat == "op" and "JOIN" in sp.name


def _enclosing_op(sp, by_id):
    p = sp.parent
    while p is not None:
        up = by_id[(sp.rank, p)]
        if up.cat == "op":
            return up
        p = up.parent
    return None


def per_query_ms(run, pick):
    """Mean ms per traced query of the spans ``pick(span, by_id)``
    selects; nothing where no traced query records one."""
    per_query, found = [], False
    for q in traced(run):
        by_id = {(sp.rank, sp.id): sp for sp in q.trace.spans}
        total = 0
        for sp in q.trace.spans:
            if pick(sp, by_id):
                total += sp.dur_ns
                found = True
        per_query.append(total)
    return float(np.mean(per_query)) / 1e6 if found else None


def join_op(sp, by_id) -> bool:
    return _is_join(sp)


def join_phase(sp, by_id) -> bool:
    return sp.cat == "kernel" and sp.name in PROBE_PHASES


def join_exchange(sp, by_id) -> bool:
    if sp.cat != "exchange" or not sp.name.startswith(("x:shuffle:",
                                                       "x:bcast:")):
        return False
    op = _enclosing_op(sp, by_id)
    return op is not None and _is_join(op)


def shuffle_mb(run):
    """Bytes exchanges moved over the window (the counter's difference),
    per completed query, in MB (1e6 bytes)."""
    done = sum(q.ok for q in run.queries)
    moved = run.counters.get(SHUFFLE_COUNTER)
    if moved is None or done == 0:
        return None
    return moved / done / 1e6
