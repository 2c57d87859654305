"""TPC-H Q1, pricing summary report (TPC-H v3 clause 2.4.1)::

    SELECT l_returnflag, l_linestatus, SUM(l_quantity),
           SUM(l_extendedprice), SUM(l_extendedprice*(1-l_discount)),
           SUM(l_extendedprice*(1-l_discount)*(1+l_tax)), AVG(l_quantity),
           AVG(l_extendedprice), AVG(l_discount), COUNT(*)
    FROM lineitem WHERE l_shipdate <= date '1998-12-01' - :DELTA days
    GROUP BY l_returnflag, l_linestatus
"""
from __future__ import annotations

import numpy as np

from queries._ref import Q1_END, group_sums

KEYS = ("returnflag", "linestatus")
# base columns read, and their bytes a row
READS = {"returnflag": 1, "linestatus": 1, "quantity": 8,
         "extendedprice": 8, "discount": 8, "tax": 8, "shipdate": 4}
AGG_TERMS = 8          # the aggregates Q1 declares
KEY_BYTES = 2


def build(session, set_name: str, schema, p: dict):
    """The query as a user writes it against the system."""
    from repro.core import agg
    cutoff = Q1_END - int(p["delta"])
    return (session.read(set_name, schema)
            .filter(lambda l, _c=cutoff: l.shipdate <= _c)
            .group_by("returnflag", "linestatus")
            .agg(sum_qty=agg.sum("quantity"),
                 sum_base_price=agg.sum("extendedprice"),
                 sum_disc_price=agg.sum(
                     lambda l: l.extendedprice * (1 - l.discount)),
                 sum_charge=agg.sum(
                     lambda l: l.extendedprice * (1 - l.discount)
                     * (1 + l.tax)),
                 avg_qty=agg.mean("quantity"),
                 avg_price=agg.mean("extendedprice"),
                 avg_disc=agg.mean("discount"),
                 count_order=agg.count()))


def references(cols: dict, params: list, dtype) -> list:
    """The answer for each parameter set, with every float accumulated in
    ``dtype``."""
    return [_one(cols, p, np.dtype(dtype)) for p in params]


def _one(cols, p, dt):
    m = cols["shipdate"] <= Q1_END - int(p["delta"])
    rf, ls = cols["returnflag"][m], cols["linestatus"][m]
    code = rf.view(np.uint8).astype(np.int64) * 256 + ls.view(np.uint8)
    ucode, inv = np.unique(code, return_inverse=True)
    n = len(ucode)
    q = cols["quantity"][m].astype(dt)
    e = cols["extendedprice"][m].astype(dt)
    d = cols["discount"][m].astype(dt)
    t = cols["tax"][m].astype(dt)
    one = dt.type(1)
    disc_price = e * (one - d)
    charge = disc_price * (one + t)
    count = np.bincount(inv, minlength=n).astype(np.int64)
    s_q = group_sums(inv, n, q, dt)
    s_e = group_sums(inv, n, e, dt)
    s_d = group_sums(inv, n, d, dt)
    return {
        "returnflag": (ucode // 256).astype(np.uint8).view("S1"),
        "linestatus": (ucode % 256).astype(np.uint8).view("S1"),
        "sum_qty": s_q, "sum_base_price": s_e,
        "sum_disc_price": group_sums(inv, n, disc_price, dt),
        "sum_charge": group_sums(inv, n, charge, dt),
        "avg_qty": s_q / count, "avg_price": s_e / count,
        "avg_disc": s_d / count, "count_order": count,
    }


def work(cols: dict, p: dict) -> dict:
    """The logical work of one query: rows in, rows into the AGG, and the
    bytes each moves at the least."""
    n = len(cols["shipdate"])
    agg_rows = int(np.count_nonzero(
        cols["shipdate"] <= Q1_END - int(p["delta"])))
    return {"rows": n, "scan_bytes": n * sum(READS.values()),
            "agg_rows": agg_rows,
            "agg_bytes": agg_rows * (AGG_TERMS * 8 + KEY_BYTES)}
