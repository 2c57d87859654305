"""The lineitem generator keeps to TPC-H clause 4.2.3."""
import numpy as np
import pytest

import harness

gen = harness.load_module(harness.HERE / "data" / "tpch_lineitem.py",
                          "bench_data")


@pytest.fixture(scope="module", params=[1, 2**31 + 12345])
def rec(request):
    return gen.generate(0.01, request.param)


def test_row_count_is_the_same_on_every_seed(rec):
    assert len(rec) == round(gen.LINES_PER_SF * 0.01)
    counts = np.bincount(np.unique(rec["orderkey"], return_inverse=True)[1])
    assert counts.min() >= 1 and counts.max() <= 7
    assert len(counts) == round(gen.ORDERS_PER_SF * 0.01)


def test_columns_within_the_spec(rec):
    assert rec.dtype.itemsize == 153
    assert len(rec.dtype.names) == 16
    assert set(np.unique(rec["quantity"])) <= set(range(1, 51))
    assert np.isin(np.round(rec["discount"] * 100), np.arange(11)).all()
    assert np.isin(np.round(rec["tax"] * 100), np.arange(9)).all()
    assert rec["shipdate"].min() >= gen.STARTDATE + 1
    assert rec["shipdate"].max() <= gen.ENDDATE - 151 + 121
    # sparse keys: the first 8 of every 32
    assert (((rec["orderkey"] - 1) % 32) < 8).all()
    # extendedprice = quantity x the part's retail price
    cents = np.round(rec["extendedprice"] * 100) / rec["quantity"]
    assert (cents >= 90000).all() and (cents <= 90000 + 20000 + 99900).all()
    # linestatus from shipdate; returnflag N when not yet received
    # (receipt is 1-30 days after shipping)
    open_ = rec["shipdate"] > gen.CURRENTDATE
    assert (rec["linestatus"][open_] == b"O").all()
    assert (rec["linestatus"][~open_] == b"F").all()
    assert (rec["returnflag"][open_] == b"N").all()
    received = rec["shipdate"] + 30 <= gen.CURRENTDATE
    assert (rec["returnflag"][received] != b"N").all()
    assert set(np.unique(rec["returnflag"])) == {b"A", b"N", b"R"}


def test_the_other_columns_within_the_spec(rec):
    sf = 0.01
    assert rec["partkey"].min() >= 1
    assert rec["partkey"].max() <= gen.PARTS_PER_SF * sf
    assert rec["suppkey"].min() >= 1
    assert rec["suppkey"].max() <= gen.SUPPLIERS_PER_SF * sf
    # each part has four suppliers
    per_part = np.unique(np.stack([rec["partkey"], rec["suppkey"]], 1),
                         axis=0)
    assert np.bincount(per_part[:, 0]).max() <= 4
    # line numbers run 1..lines of the order
    okey = rec["orderkey"]
    starts = np.flatnonzero(np.r_[True, okey[1:] != okey[:-1]])
    assert (rec["linenumber"][starts] == 1).all()
    step = np.diff(rec["linenumber"])
    assert ((step == 1) | (np.r_[False, okey[2:] != okey[1:-1]])).all()
    # commitdate = orderdate + 30..90, shipdate = orderdate + 1..121
    lag = rec["commitdate"].astype(np.int64) - rec["shipdate"]
    assert lag.min() >= 30 - 121 and lag.max() <= 90 - 1
    assert (rec["receiptdate"] - rec["shipdate"] >= 1).all()
    assert (rec["receiptdate"] - rec["shipdate"] <= 30).all()
    assert set(np.unique(rec["shipinstruct"])) == set(gen.INSTRUCTIONS)
    assert set(np.unique(rec["shipmode"])) == set(gen.MODES)
    lengths = np.char.str_len(rec["comment"])
    assert lengths.min() >= 10 and lengths.max() <= 43


def test_same_seed_same_rows():
    a, b = gen.generate(0.001, 99), gen.generate(0.001, 99)
    assert a.tobytes() == b.tobytes()
    assert a.tobytes() != gen.generate(0.001, 100).tobytes()
