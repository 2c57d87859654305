"""Each template's numpy reference agrees with the system on its host
backend (``expr_backend="numpy"``) at a tiny scale."""
import numpy as np
import pytest

import harness
from check import compare

gen = harness.load_module(harness.HERE / "data" / "tpch_lineitem.py",
                          "bench_data")
CASES = [("q1", {"delta": 90}), ("q1", {"delta": 60}),
         ("q18sub", {"quantity": 150}), ("q18sub", {"quantity": 312})]


@pytest.fixture(scope="module")
def loaded():
    from repro.core import Session
    rec = gen.generate(0.01, 2024)
    schema = harness._schema(rec.dtype, "TpchLineitem")
    sess = Session(num_partitions=4, expr_backend="numpy")
    return sess, sess.load("lineitem", rec, schema).set_name, schema, rec


@pytest.mark.parametrize("template,params", CASES)
def test_reference_matches_the_host_backend(loaded, template, params):
    sess, set_name, schema, rec = loaded
    mod = harness.load_module(harness.HERE / "queries" / f"{template}.py",
                              f"bench_q_{template}")
    got = mod.build(sess, set_name, schema, params).collect()
    (ref,) = mod.references(gen.columns(rec), [params], np.float64)
    r = compare(got, ref, mod.KEYS)
    assert r["exact_mismatch"] == 0
    assert r["float_rel_err"] < 1e-12
    if template != "q18sub" or params["quantity"] < 300:
        assert len(next(iter(ref.values()))) > 0


def test_whole_groups_match_the_host_backend(loaded):
    from repro.core import agg
    sess, set_name, schema, rec = loaded
    mod = harness.load_module(harness.HERE / "queries" / "q18sub.py",
                              "bench_q_q18sub")
    got = (sess.read(set_name, schema).group_by("orderkey")
           .agg(sum_qty=agg.sum("quantity"),
                totalprice=agg.sum(lambda l: l.extendedprice * (1 + l.tax)
                                   * (1 - l.discount)))).collect()
    ref = mod.groups(gen.columns(rec), np.float64)
    assert len(ref["orderkey"]) == round(gen.ORDERS_PER_SF * 0.01)
    r = compare(got, ref, mod.KEYS)
    assert r["exact_mismatch"] == 0
    assert r["float_rel_err"] < 1e-12
