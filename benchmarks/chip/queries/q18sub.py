"""The subquery of TPC-H Q18, large volume customer (TPC-H v3 clause
2.4.18), with the order's total price the outer query reports::

    SELECT l_orderkey, SUM(l_quantity) AS sum_qty,
           SUM(l_extendedprice*(1+l_tax)*(1-l_discount)) AS totalprice
    FROM lineitem GROUP BY l_orderkey HAVING SUM(l_quantity) > :QUANTITY

``totalprice`` is O_TOTALPRICE as clause 4.2.3 defines it from the
order's lines. It is the template's float aggregate: quantities are
whole numbers, so their sums are exact in any float precision.

HAVING keeps a few dozen of the 1.5M orders at SF 1, so the check also
compares every group the AGG produced (``groups``) with the reference.
"""
from __future__ import annotations

import numpy as np

KEYS = ("orderkey",)
READS = {"orderkey": 8, "quantity": 8, "extendedprice": 8, "tax": 8,
         "discount": 8}
AGG_TERMS = 2
KEY_BYTES = 8


def build(session, set_name: str, schema, p: dict):
    from repro.core import agg
    return (session.read(set_name, schema)
            .group_by("orderkey")
            .agg(sum_qty=agg.sum("quantity"),
                 totalprice=agg.sum(
                     lambda l: l.extendedprice * (1 + l.tax)
                     * (1 - l.discount)))
            .filter(lambda r, _q=int(p["quantity"]): r.sum_qty > _q))


def groups(cols: dict, dtype) -> dict:
    """Every order's group before HAVING, with its sums accumulated in
    ``dtype`` in row order."""
    dt = np.dtype(dtype)
    okey = cols["orderkey"]
    order = np.argsort(okey, kind="stable")
    sk = okey[order]
    starts = np.flatnonzero(np.r_[True, sk[1:] != sk[:-1]])
    one = dt.type(1)
    e, t, d = (cols[c][order].astype(dt)
               for c in ("extendedprice", "tax", "discount"))
    price = e * (one + t) * (one - d)
    return {"orderkey": sk[starts],
            "sum_qty": np.add.reduceat(cols["quantity"][order].astype(dt),
                                       starts),
            "totalprice": np.add.reduceat(price, starts)}


def references(cols: dict, params: list, dtype) -> list:
    g = groups(cols, dtype)
    out = []
    for p in params:
        m = g["sum_qty"] > int(p["quantity"])
        out.append({k: v[m] for k, v in g.items()})
    return out


def work(cols: dict, p: dict) -> dict:
    n = len(cols["orderkey"])
    return {"rows": n, "scan_bytes": n * sum(READS.values()),
            "agg_rows": n, "agg_bytes": n * (AGG_TERMS * 8 + KEY_BYTES)}
