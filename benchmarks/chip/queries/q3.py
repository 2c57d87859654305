"""TPC-H Q3, shipping priority (TPC-H v3 clause 2.4.3)::

    SELECT l_orderkey, SUM(l_extendedprice*(1-l_discount)) AS revenue,
           o_orderdate, o_shippriority
    FROM customer, orders, lineitem
    WHERE c_mktsegment = ':SEGMENT' AND c_custkey = o_custkey
      AND l_orderkey = o_orderkey AND o_orderdate < date ':DATE'
      AND l_shipdate > date ':DATE'
    GROUP BY l_orderkey, o_orderdate, o_shippriority
    ORDER BY revenue DESC, o_orderdate
    LIMIT 10

Parameters (clause 2.4.3.3): ``segment`` indexes the five segments of
clause 4.2.2.13, ``date`` is a day of March 1995. Ties on revenue and
date fall to the other columns, ascending, as the system orders them.

The answer keeps 10 of about 11,600 groups at SF 1. Which groups the AGG
makes depends on the parameters, and the harness asks a template's
``groups`` for one set per template, so this one declares none: its
whole groups (:func:`groups_for`) are compared in the self-tests.
"""
from __future__ import annotations

import numpy as np

from queries._ref import day

KEYS = ("orderkey",)
# base columns read, and their bytes a row
READS = {"c_custkey": 8, "c_mktsegment": 10,
         "o_orderkey": 8, "o_custkey": 8, "o_orderdate": 4,
         "o_shippriority": 4,
         "l_orderkey": 8, "l_extendedprice": 8, "l_discount": 8,
         "l_shipdate": 4}
SEGMENTS = (b"AUTOMOBILE", b"BUILDING", b"FURNITURE", b"MACHINERY",
            b"HOUSEHOLD")
MARCH_1995 = day("1995-03-01")
LIMIT = 10


def _date(p: dict) -> int:
    return MARCH_1995 + int(p["date"]) - 1


def build(session, sets: dict, schema, p: dict):
    """The query as a user writes it against the system: ``sets`` maps
    each table to its set name and schema."""
    from repro.core import agg
    segment, date = SEGMENTS[int(p["segment"])], _date(p)
    customer = session.read(*sets["customer"]).filter(
        lambda c, _s=segment: c.mktsegment == _s)
    orders = session.read(*sets["orders"]).filter(
        lambda o, _d=date: o.orderdate < _d)
    lineitem = session.read(*sets["lineitem"]).filter(
        lambda l, _d=date: l.shipdate > _d)
    return (customer.join(orders, on=lambda c, o: c.custkey == o.custkey)
            .join(lineitem, on=lambda co, l: co.orderkey == l.orderkey)
            .group_by("orderkey", "orderdate", "shippriority")
            .agg(revenue=agg.sum(
                lambda r: r.extendedprice * (1 - r.discount)))
            .order_by(("revenue", "desc"), "orderdate")
            .limit(LIMIT))


def _joined(cols: dict, p: dict):
    """The lines of the qualifying orders: each line's row in lineitem
    and its order's row in orders, in lineitem's row order (both tables
    are in orderkey order, as dbgen writes them)."""
    segment, date = SEGMENTS[int(p["segment"])], _date(p)
    cust = cols["c_custkey"][cols["c_mktsegment"] == segment]
    o_rows = np.flatnonzero((cols["o_orderdate"] < date)
                            & np.isin(cols["o_custkey"], cust))
    okey = cols["o_orderkey"][o_rows]
    lkey = cols["l_orderkey"]
    l_rows = np.flatnonzero((cols["l_shipdate"] > date)
                            & np.isin(lkey, okey))
    return l_rows, o_rows[np.searchsorted(okey, lkey[l_rows])]


def groups_for(cols: dict, p: dict, dtype) -> dict:
    """Every group the AGG makes for the parameter set ``p``, with sums
    accumulated in ``dtype`` in lineitem's row order."""
    dt = np.dtype(dtype)
    l_rows, o_rows = _joined(cols, p)
    e = cols["l_extendedprice"][l_rows].astype(dt)
    d = cols["l_discount"][l_rows].astype(dt)
    rev = e * (dt.type(1) - d)
    # an order is one group (orderkey fixes date and priority), its lines
    # one run of rows
    keys, starts = np.unique(cols["l_orderkey"][l_rows], return_index=True)
    first = o_rows[starts]
    sums = (np.add.reduceat(rev, starts) if len(rev)
            else np.zeros(0, dt))
    return {"orderkey": keys,
            "orderdate": cols["o_orderdate"][first],
            "shippriority": cols["o_shippriority"][first],
            "revenue": sums.astype(dt, copy=False)}


def references(cols: dict, params: list, dtype) -> list:
    """The answer for each parameter set: the first ``LIMIT`` groups by
    revenue descending, date ascending, then the other columns."""
    out = []
    for p in params:
        g = groups_for(cols, p, dtype)
        order = np.lexsort([g["shippriority"], g["orderkey"],
                            g["orderdate"], -g["revenue"]])[:LIMIT]
        out.append({k: v[order] for k, v in g.items()})
    return out


def work(cols: dict, p: dict) -> dict:
    """The logical work of one query: lineitem rows, and the bytes the
    three scans read at the least."""
    n = {t: len(cols[f"{t}_orderkey" if t != "c" else "c_custkey"])
         for t in ("c", "o", "l")}
    scan = sum(n[c[0]] * b for c, b in READS.items())
    return {"rows": n["l"], "scan_bytes": scan}
