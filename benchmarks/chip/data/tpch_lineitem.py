"""TPC-H ``lineitem`` after the TPC-H specification v3, clause 4.2.3.

All 16 columns are made, each at its width in clause 1.4.1: identifiers
int64, the line number int32, decimals float64, dates int32 (days since
1970-01-01), fixed text at its size (``S1``, ``S25``, ``S10``) and the
comment, a variable text of at most 44 characters, at that width
(``S44``): 153 B a packed row. Every seed gets the same number of orders
and the same multiset of lines per order, so the row count and the page
layout are the same on every seed; the seed picks the order of those
counts and every value. Pure numpy: nothing here imports the system
under test.
"""
from __future__ import annotations

import numpy as np

ORDERS_PER_SF = 1_500_000
LINES_PER_SF = 6_001_215          # dbgen's lineitem count at SF 1
PARTS_PER_SF = 200_000
SUPPLIERS_PER_SF = 10_000

STARTDATE = int(np.datetime64("1992-01-01", "D").astype(np.int64))
CURRENTDATE = int(np.datetime64("1995-06-17", "D").astype(np.int64))
ENDDATE = int(np.datetime64("1998-12-31", "D").astype(np.int64))

# clause 4.2.2.13
INSTRUCTIONS = np.array([b"DELIVER IN PERSON", b"COLLECT COD", b"NONE",
                         b"TAKE BACK RETURN"], "S25")
MODES = np.array([b"REG AIR", b"AIR", b"RAIL", b"SHIP", b"TRUCK", b"MAIL",
                  b"FOB"], "S10")
# words of the text grammar of clause 4.2.2.10, a sample of each list
WORDS = ("foxes ideas theodolites pinto beans instructions dependencies "
         "excuses platelets asymptotes courts dolphins multipliers sauternes "
         "warthogs frets dinos attainments somas packages accounts requests "
         "deposits sleep wake are cajole haggle nag use boost affix detect "
         "integrate maintain nod was lose sublate solve thrash promise "
         "engage hinder print x-ray breach eat grow impress mold poach "
         "serve run dazzle snooze doze unwind kindle play hang believe "
         "furious sly careful blithe quick fluffy slow quiet ruthless thin "
         "close dogged daring brave stealthy permanent enticing idle busy "
         "regular final ironic even bold silent sometimes always never "
         "furiously slyly carefully blithely quickly fluffily slowly "
         "quietly ruthlessly thinly closely doggedly daringly bravely "
         "about above according to across after against along alongside "
         "of among around at atop before behind beneath beside besides "
         "between beyond by despite during except for from in inside "
         "instead of into near of on outside over past since through "
         "throughout to toward under until up upon without with within").split()
COMMENTS_IN_POOL = 1 << 16

DTYPE = np.dtype([
    ("orderkey", np.int64), ("partkey", np.int64), ("suppkey", np.int64),
    ("linenumber", np.int32), ("quantity", np.float64),
    ("extendedprice", np.float64), ("discount", np.float64),
    ("tax", np.float64), ("returnflag", "S1"), ("linestatus", "S1"),
    ("shipdate", np.int32), ("commitdate", np.int32),
    ("receiptdate", np.int32), ("shipinstruct", "S25"),
    ("shipmode", "S10"), ("comment", "S44")])


def lines_per_order(n_orders: int, n_lines: int) -> np.ndarray:
    """A fixed multiset of 1-7 lines per order summing to ``n_lines``
    (independent of the seed)."""
    counts = 1 + np.arange(n_orders, dtype=np.int64) % 7
    diff = n_lines - int(counts.sum())
    room = np.flatnonzero(counts < 7) if diff > 0 else np.flatnonzero(
        counts > 1)
    if abs(diff) > len(room):
        raise ValueError(f"{n_lines} lines cannot fill {n_orders} orders "
                         "at 1-7 lines each")
    pick = room[np.linspace(0, len(room) - 1, abs(diff)).astype(np.int64)]
    counts[pick] += 1 if diff > 0 else -1
    return counts


def retail_price_cents(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE (clause 4.2.3) in cents."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def supplier(partkey: np.ndarray, i: np.ndarray, n_supp: int) -> np.ndarray:
    """L_SUPPKEY of the ``i``-th (0-3) supplier of a part (clause 4.2.3)."""
    return (partkey + i * (n_supp // 4 + (partkey - 1) // n_supp)) \
        % n_supp + 1


def comment_pool(rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` texts of 10-43 characters cut from a run of the grammar's
    words."""
    text = " ".join(np.array(WORDS)[rng.integers(0, len(WORDS), 1 << 16)])
    raw = np.frombuffer(text.encode(), np.uint8)
    width = DTYPE["comment"].itemsize
    start = rng.integers(0, len(raw) - width, n)
    length = rng.integers(10, width, n)
    cut = raw[start[:, None] + np.arange(width)]
    cut[np.arange(width) >= length[:, None]] = 0
    return cut.view(f"S{width}").ravel()


def generate(sf: float, seed) -> np.ndarray:
    """Lineitem rows at scale factor ``sf`` as one packed record array of
    :data:`DTYPE`, in orderkey order (dbgen's order). ``seed`` is anything
    ``np.random.default_rng`` takes."""
    rng = np.random.default_rng(seed)
    n_orders = max(1, round(ORDERS_PER_SF * sf))
    n_lines = max(n_orders, round(LINES_PER_SF * sf))
    counts = rng.permutation(lines_per_order(n_orders, n_lines))
    n = int(counts.sum())
    order = np.repeat(np.arange(n_orders, dtype=np.int64), counts)
    first = np.repeat(np.cumsum(counts) - counts, counts)
    # sparse keys: the first 8 of every 32
    okey = (order // 8) * 32 + order % 8 + 1
    odate = rng.integers(STARTDATE, ENDDATE - 151 + 1, n_orders)[order]
    ship = odate + rng.integers(1, 122, n)
    receipt = ship + rng.integers(1, 31, n)
    qty = rng.integers(1, 51, n)
    partkey = rng.integers(1, max(1, round(PARTS_PER_SF * sf)) + 1, n)
    n_supp = max(4, round(SUPPLIERS_PER_SF * sf))

    rec = np.empty(n, DTYPE)
    rec["orderkey"] = okey
    rec["partkey"] = partkey
    rec["suppkey"] = supplier(partkey, rng.integers(0, 4, n), n_supp)
    rec["linenumber"] = np.arange(n) - first + 1
    rec["quantity"] = qty
    rec["extendedprice"] = qty * retail_price_cents(partkey) / 100.0
    rec["discount"] = rng.integers(0, 11, n) / 100.0
    rec["tax"] = rng.integers(0, 9, n) / 100.0
    rec["returnflag"] = np.where(receipt <= CURRENTDATE,
                                 np.where(rng.integers(0, 2, n) == 0,
                                          b"R", b"A"), b"N")
    rec["linestatus"] = np.where(ship > CURRENTDATE, b"O", b"F")
    rec["shipdate"] = ship
    rec["commitdate"] = odate + rng.integers(30, 91, n)
    rec["receiptdate"] = receipt
    rec["shipinstruct"] = INSTRUCTIONS[rng.integers(0, len(INSTRUCTIONS), n)]
    rec["shipmode"] = MODES[rng.integers(0, len(MODES), n)]
    rec["comment"] = comment_pool(rng, COMMENTS_IN_POOL)[
        rng.integers(0, COMMENTS_IN_POOL, n)]
    return rec


def columns(rec: np.ndarray, names=None) -> dict:
    """The records as plain contiguous columns (what the references
    read): ``names``, or all of them."""
    return {name: np.ascontiguousarray(rec[name])
            for name in (names or DTYPE.names)}
