"""The three-table generator keeps to TPC-H clause 4.2.3; Q3's numpy
reference agrees with the system on its host backend, answer and whole
groups; a broken join or ordering, and the float32 control, fail the
check."""
import numpy as np
import pytest

import control
import harness
from check import compare, tapped_groups

gen = harness.load_module(harness.HERE / "data" / "tpch_join.py",
                          "bench_data_join")
q3 = harness.load_module(harness.HERE / "queries" / "q3.py", "bench_q_q3")
SF = 0.01
PARAMS = [{"segment": 1, "date": 15}, {"segment": 4, "date": 1},
          {"segment": 0, "date": 31}]


@pytest.fixture(scope="module", params=[1, 2**31 + 12345])
def tables(request):
    return gen.generate(SF, np.random.SeedSequence([request.param, 0]))


def test_row_counts_and_widths_are_the_same_on_every_seed(tables):
    assert len(tables.customer) == round(gen.CUSTOMERS_PER_SF * SF)
    assert len(tables.orders) == round(gen.li.ORDERS_PER_SF * SF)
    assert len(tables) == len(tables.lineitem) == \
        round(gen.li.LINES_PER_SF * SF)
    assert tables.dtype == gen.li.DTYPE
    widths = {t: r.dtype.itemsize for t, r in tables.tables().items()}
    assert widths == {"customer": 231, "orders": 142, "lineitem": 153}


def test_keys_refer_to_rows_that_exist(tables):
    c, o, li = tables.customer, tables.orders, tables.lineitem
    np.testing.assert_array_equal(c["custkey"], np.arange(1, len(c) + 1))
    assert np.isin(o["custkey"], c["custkey"]).all()
    assert (o["custkey"] % 3 != 0).all()
    assert (np.diff(o["orderkey"]) > 0).all()
    assert np.isin(li["orderkey"], o["orderkey"]).all()
    assert np.isin(o["orderkey"], li["orderkey"]).all()


def test_orders_follow_from_their_lines(tables):
    o, li = tables.orders, tables.lineitem
    at = np.searchsorted(o["orderkey"], li["orderkey"])
    lag = li["shipdate"] - o["orderdate"][at]
    assert lag.min() >= 1 and lag.max() <= 121
    assert o["orderdate"].min() >= gen.li.STARTDATE
    assert o["orderdate"].max() <= gen.li.ENDDATE - 151
    price = li["extendedprice"] * (1 + li["tax"]) * (1 - li["discount"])
    np.testing.assert_allclose(
        o["totalprice"], np.bincount(at, price, minlength=len(o)),
        rtol=1e-12)
    shipped = np.bincount(at, li["linestatus"] == b"F", minlength=len(o))
    lines = np.bincount(at, minlength=len(o))
    want = np.where(shipped == lines, b"F",
                    np.where(shipped == 0, b"O", b"P"))
    np.testing.assert_array_equal(o["orderstatus"], want)


def test_text_columns_within_the_spec(tables):
    c, o = tables.customer, tables.orders
    assert set(np.unique(c["mktsegment"])) == set(gen.SEGMENTS)
    assert tuple(gen.SEGMENTS) == q3.SEGMENTS
    assert set(np.unique(o["orderpriority"])) == set(gen.PRIORITIES)
    assert c["name"][0] == b"Customer#000000001"
    phone = c["phone"].astype("U15")
    assert all(len(p) == 15 and p[2] == p[6] == p[10] == "-"
               for p in phone[:50])
    np.testing.assert_array_equal(
        [int(p[:2]) for p in phone], c["nationkey"] + 10)
    assert (c["acctbal"] >= -999.99).all() and (c["acctbal"] <= 9999.99).all()
    clerk = np.char.lstrip(np.char.lstrip(o["clerk"], b"Clerk#"), b"0")
    assert 1 <= clerk.astype(np.int64).min()
    assert clerk.astype(np.int64).max() <= max(1, round(1000 * SF))
    assert (o["shippriority"] == 0).all()
    for col, lo, hi in ((c["address"], 10, 40), (c["comment"], 29, 116),
                        (o["comment"], 19, 78)):
        n = np.char.str_len(col)
        assert n.min() >= lo and n.max() <= hi


def test_columns_are_named_apart():
    t = gen.generate(0.001, 3)
    cols = gen.columns(t)
    assert len(cols) == sum(len(r.dtype.names)
                            for r in t.tables().values())
    assert set(gen.columns(t, ["o_orderdate", "l_orderkey"])) == \
        {"o_orderdate", "l_orderkey"}
    assert set(q3.READS) <= set(cols)


def test_same_seed_same_tables():
    a, b = gen.generate(0.001, 99), gen.generate(0.001, 99)
    for x, y in zip(a.tables().values(), b.tables().values()):
        assert x.tobytes() == y.tobytes()
    assert a.orders.tobytes() != gen.generate(0.001, 100).orders.tobytes()


# ------------------------------------------------------- system vs numpy
@pytest.fixture(scope="module")
def deployed():
    deploy = harness.load_module(
        harness.HERE / "deploy" / "local_session_tables.py", "bench_dep3")
    t = gen.generate(SF, np.random.SeedSequence([2024, 0]))
    conf = dict(harness.load_cell("q3_sf1").config, expr_backend="numpy")
    schema = harness._schema(t.dtype, conf["schema"])
    dep = deploy.Deployment(conf, schema, False)
    dep.load([t])
    tap = []
    dep.tap_aggregates(tap)
    return dep, schema, gen.columns(t), tap


def _run(deployed, p):
    dep, schema, cols, tap = deployed
    session, sets = dep.client(0)
    tap.clear()
    got = q3.build(session, sets, schema, p).collect()
    return got, tapped_groups(tap)


@pytest.mark.parametrize("p", PARAMS)
def test_reference_matches_the_host_backend(deployed, p):
    cols = deployed[2]
    got, groups = _run(deployed, p)
    assert sorted(got) == ["orderdate", "orderkey", "revenue",
                           "shippriority"]
    (ref,) = q3.references(cols, [p], np.float64)
    whole = q3.groups_for(cols, p, np.float64)
    assert len(ref["orderkey"]) == q3.LIMIT
    assert len(whole["orderkey"]) > 5 * q3.LIMIT
    for a, b in ((got, ref), (groups, whole)):
        r = compare(a, b, q3.KEYS)
        assert r["exact_mismatch"] == 0
        assert r["float_rel_err"] < 1e-12
    # the system's rows come in the reference's order
    np.testing.assert_array_equal(got["orderkey"], ref["orderkey"])


def test_a_dropped_join_row_fails_the_whole_groups(deployed, monkeypatch):
    from repro.core import executor, relops
    probe = relops.probe_join

    def drop_one(op, lvl, rvl):
        out = probe(op, lvl, rvl)
        if out is None or out[1] < 2:
            return out
        res, n = out
        return res.filtered(np.arange(n) != n // 2, res.names), n - 1
    monkeypatch.setattr(executor, "probe_join", drop_one)
    cols = deployed[2]
    _, groups = _run(deployed, PARAMS[0])
    r = compare(groups, q3.groups_for(cols, PARAMS[0], np.float64),
                q3.KEYS)
    assert r["exact_mismatch"] > 0 or r["float_rel_err"] > 1e-10


# ------------------------------------------------- the run's check (CPU)
def small_cell():
    cell = harness.load_cell("q3_sf1")
    cell.config["scale_factor"] = SF
    return cell


def run(cell):
    return harness.run(cell, 2**31 + 77, 1.0, False, require_tpu=False)


def test_a_sound_run_is_correct():
    res = run(small_cell())
    assert res["correct"], res["check"]
    assert res["check"]["answers"]["value"] == res["attempted"] > 0


def _ascending(orig):
    def spec(op):
        keys, limit = orig(op)
        return tuple((f, not d) for f, d in keys), limit
    return spec


def _drop_half(orig):
    def probe(op, lvl, rvl):
        out = orig(op, lvl, rvl)
        if out is None:
            return out
        res, n = out
        keep = np.arange(n) % 2 == 0
        return res.filtered(keep, res.names), int(keep.sum())
    return probe


@pytest.mark.parametrize("fault", ["wrong_order", "drop_join_rows"])
def test_a_broken_path_is_not_correct(fault, monkeypatch):
    from repro.core import executor, relops
    if fault == "wrong_order":
        monkeypatch.setattr(relops, "_order_spec",
                            _ascending(relops._order_spec))
    else:
        monkeypatch.setattr(executor, "probe_join",
                            _drop_half(relops.probe_join))
    res = run(small_cell())
    assert not res["correct"], res["check"]


def test_the_control_fails_the_limits():
    cell = small_cell()
    r = control.readings(cell, 2**31 + 5)
    limits = cell.workload["limits"]
    assert any(r[k] > limits[k] for k in r), r
    assert np.isfinite(r["float_rel_err"])
