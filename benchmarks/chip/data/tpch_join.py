"""TPC-H ``customer``, ``orders`` and ``lineitem`` after the TPC-H
specification v3, clause 4.2.3, each column at its clause 1.4.1 width.

Lineitem is :mod:`data.tpch_lineitem`'s, for the same seed. Orders replays
that module's first draws (the lines per order, then each order's date),
so every order's ``orderdate`` is the date its lines were drawn from and
its ``totalprice`` and ``orderstatus`` follow from its lines; its customer
is never a multiple of 3, as clause 4.2.3 asks. The rest is drawn from a
stream of its own. Packed rows: customer 231 B, orders 142 B, lineitem
153 B. Pure numpy: nothing here imports the system under test.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from data import tpch_lineitem as li

CUSTOMERS_PER_SF = 150_000
CLERKS_PER_SF = 1_000
# clause 4.2.2.13
SEGMENTS = np.array([b"AUTOMOBILE", b"BUILDING", b"FURNITURE",
                     b"MACHINERY", b"HOUSEHOLD"], "S10")
PRIORITIES = np.array([b"1-URGENT", b"2-HIGH", b"3-MEDIUM",
                       b"4-NOT SPECIFIED", b"5-LOW"], "S15")
# the characters of a random v-string (clause 4.2.2.7)
VCHARS = np.frombuffer(b"abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
                       b"0123456789,. ", np.uint8)
TEXTS_IN_POOL = 1 << 16

CUSTOMER = np.dtype([
    ("custkey", np.int64), ("name", "S25"), ("address", "S40"),
    ("nationkey", np.int64), ("phone", "S15"), ("acctbal", np.float64),
    ("mktsegment", "S10"), ("comment", "S117")])
ORDERS = np.dtype([
    ("orderkey", np.int64), ("custkey", np.int64), ("orderstatus", "S1"),
    ("totalprice", np.float64), ("orderdate", np.int32),
    ("orderpriority", "S15"), ("clerk", "S15"), ("shippriority", np.int32),
    ("comment", "S79")])
DTYPES = {"customer": CUSTOMER, "orders": ORDERS, "lineitem": li.DTYPE}
PREFIX = {"customer": "c_", "orders": "o_", "lineitem": "l_"}


@dataclasses.dataclass
class Tables:
    """One tenant's three tables. Its ``dtype`` and length are
    lineitem's: the rows a query is counted by."""
    customer: np.ndarray
    orders: np.ndarray
    lineitem: np.ndarray

    @property
    def dtype(self) -> np.dtype:
        return self.lineitem.dtype

    def __len__(self) -> int:
        return len(self.lineitem)

    def tables(self) -> dict:
        return {"customer": self.customer, "orders": self.orders,
                "lineitem": self.lineitem}


def _child(seed, k: int) -> np.random.SeedSequence:
    """A stream of the seed's own, apart from the one lineitem draws."""
    ss = (seed if isinstance(seed, np.random.SeedSequence)
          else np.random.SeedSequence(seed))
    return np.random.SeedSequence(ss.entropy, spawn_key=(*ss.spawn_key, k))


def tagged(prefix: bytes, nums: np.ndarray, width: int,
           digits: int = 9) -> np.ndarray:
    """``prefix`` followed by ``nums`` zero-padded to ``digits`` digits
    ("Customer#000000001"), as fixed text of ``width`` bytes."""
    out = np.zeros((len(nums), width), np.uint8)
    out[:, :len(prefix)] = np.frombuffer(prefix, np.uint8)
    powers = 10 ** np.arange(digits - 1, -1, -1, dtype=np.int64)
    out[:, len(prefix):len(prefix) + digits] = \
        (nums[:, None] // powers) % 10 + ord("0")
    return out.view(f"S{width}").ravel()


def text_pool(rng: np.random.Generator, n: int, lo: int, hi: int,
              width: int) -> np.ndarray:
    """``n`` texts of ``lo``-``hi`` characters cut from a run of the clause
    4.2.2.10 grammar's words (lineitem's list), as fixed text of
    ``width`` bytes."""
    text = " ".join(np.array(li.WORDS)[rng.integers(0, len(li.WORDS),
                                                    1 << 16)])
    raw = np.frombuffer(text.encode(), np.uint8)
    start = rng.integers(0, len(raw) - width, n)
    length = rng.integers(lo, hi + 1, n)
    cut = raw[start[:, None] + np.arange(width)]
    cut[np.arange(width) >= length[:, None]] = 0
    return cut.view(f"S{width}").ravel()


def _pooled(rng, n, lo, hi, width):
    pool = text_pool(rng, TEXTS_IN_POOL, lo, hi, width)
    return pool[rng.integers(0, TEXTS_IN_POOL, n)]


def _customers(rng: np.random.Generator, n: int) -> np.ndarray:
    rec = np.empty(n, CUSTOMER)
    key = np.arange(1, n + 1, dtype=np.int64)
    nation = rng.integers(0, 25, n)
    rec["custkey"] = key
    rec["name"] = tagged(b"Customer#", key, 25)
    # random v-string [10, 40]
    length = rng.integers(10, 41, n)
    addr = VCHARS[rng.integers(0, len(VCHARS), (n, 40))]
    addr[np.arange(40) >= length[:, None]] = 0
    rec["address"] = addr.view("S40").ravel()
    rec["nationkey"] = nation
    # clause 4.2.2.9: CC-LLL-LLL-LLLL, CC = nationkey + 10
    phone = np.zeros((n, 15), np.uint8)
    for at, (val, digits) in zip((0, 3, 7, 11), (
            (nation + 10, 2), (rng.integers(100, 1000, n), 3),
            (rng.integers(100, 1000, n), 3),
            (rng.integers(1000, 10000, n), 4))):
        phone[:, at:at + digits] = tagged(b"", val, digits, digits) \
            .view(np.uint8).reshape(n, digits)
    phone[:, [2, 6, 10]] = ord("-")
    rec["phone"] = phone.view("S15").ravel()
    rec["acctbal"] = rng.integers(-99999, 1000000, n) / 100.0
    rec["mktsegment"] = SEGMENTS[rng.integers(0, len(SEGMENTS), n)]
    # text string [73]: 0.4 x 73 to 1.6 x 73 characters
    rec["comment"] = _pooled(rng, n, 29, 116, 117)
    return rec


def _orders(rng: np.random.Generator, sf: float, counts: np.ndarray,
            odate: np.ndarray, lineitem: np.ndarray,
            n_cust: int) -> np.ndarray:
    n = len(counts)
    idx = np.arange(n, dtype=np.int64)
    starts = np.cumsum(counts) - counts
    rec = np.empty(n, ORDERS)
    rec["orderkey"] = (idx // 8) * 32 + idx % 8 + 1
    # uniform over the customers whose key is not a multiple of 3
    j = rng.integers(0, n_cust - n_cust // 3, n)
    rec["custkey"] = j + j // 2 + 1
    shipped = np.add.reduceat(
        (lineitem["linestatus"] == b"F").astype(np.int64), starts)
    rec["orderstatus"] = np.where(shipped == counts, b"F",
                                  np.where(shipped == 0, b"O", b"P"))
    e, t, d = (lineitem[c] for c in ("extendedprice", "tax", "discount"))
    rec["totalprice"] = np.add.reduceat(e * (1 + t) * (1 - d), starts)
    rec["orderdate"] = odate
    rec["orderpriority"] = PRIORITIES[rng.integers(0, len(PRIORITIES), n)]
    clerks = max(1, round(CLERKS_PER_SF * sf))
    rec["clerk"] = tagged(b"Clerk#", rng.integers(1, clerks + 1, n), 15)
    rec["shippriority"] = 0
    # text string [49]
    rec["comment"] = _pooled(rng, n, 19, 78, 79)
    return rec


def generate(sf: float, seed) -> Tables:
    """The three tables at scale factor ``sf``; ``seed`` is anything
    ``np.random.SeedSequence`` takes, or one."""
    lineitem = li.generate(sf, seed)
    # lineitem's first draws, replayed: the lines of each order and the
    # date each order's lines were drawn from
    rng = np.random.default_rng(seed)
    n_orders = max(1, round(li.ORDERS_PER_SF * sf))
    n_lines = max(n_orders, round(li.LINES_PER_SF * sf))
    counts = rng.permutation(li.lines_per_order(n_orders, n_lines))
    odate = rng.integers(li.STARTDATE, li.ENDDATE - 151 + 1, n_orders)
    own = np.random.default_rng(_child(seed, 1))
    n_cust = max(3, round(CUSTOMERS_PER_SF * sf))
    customer = _customers(own, n_cust)
    orders = _orders(own, sf, counts, odate, lineitem, n_cust)
    return Tables(customer, orders, lineitem)


def columns(tables: Tables, names=None) -> dict:
    """Every table's columns under distinct names (``c_``, ``o_``, ``l_``
    before the field's name), as plain contiguous columns: ``names``, or
    all of them."""
    out = {}
    for table, rec in tables.tables().items():
        for f in rec.dtype.names:
            name = PREFIX[table] + f
            if names is None or name in names:
                out[name] = np.ascontiguousarray(rec[f])
    return out
