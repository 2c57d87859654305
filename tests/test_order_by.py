"""``order_by(...).limit(n)``: the first ``n`` records in a lexicographic
order of mixed ascending and descending keys, ties on every listed key
broken by the other fields in schema order.

Every backend (interp, numpy, jax), one and four partitions, and thread
workers return the rows a numpy ``lexsort`` oracle picks, in its order;
planlint infers the op's output dtypes; the chain keeps the input's
schema, so it composes with grouped results and further filters.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import Session, UnknownColumnError, agg
from repro.objectmodel.schema import Field, record

Row = record("OrdRow", {"a": Field(np.int64), "b": Field(np.float64),
                        "t": Field(np.dtype("S4")), "d": Field(np.int32)})


def _rows(n=900, seed=11):
    """Few distinct values per field, so many rows tie on every listed key
    and some are duplicates in every field."""
    rng = np.random.default_rng(seed)
    out = np.zeros(n, Row.dtype)
    out["a"] = rng.integers(0, 4, n)
    out["b"] = rng.integers(-2, 3, n) / 2
    out["t"] = rng.choice([b"x", b"yy", b"zzz", b""], n)
    out["d"] = rng.integers(0, 6, n)
    return out


def _oracle(rec, keys, n):
    """numpy's lexsort over the listed keys (descending ones negated),
    then every other field ascending in schema order."""
    listed = [f for f, _ in keys]
    order = [(f, d == "desc") for f, d in keys] + [
        (f, False) for f in rec.dtype.names if f not in listed]
    cols = [-rec[f] if desc else rec[f] for f, desc in order]
    pick = rec[np.lexsort(cols[::-1])[:n]]
    return {f: pick[f] for f in rec.dtype.names}


def _assert_rows(got, want):
    assert list(got) == list(want)
    for f in want:
        assert got[f].dtype == want[f].dtype, f
        np.testing.assert_array_equal(got[f], want[f], err_msg=f)


KEYS = [(("a", "desc"), ("b", "asc")),
        (("b", "desc"), ("t", "asc"), ("a", "desc")),
        (("d", "asc"),)]


def _session(backend, where):
    if where == "threads":
        return Session(expr_backend=backend, backend="workers",
                       num_workers=4, worker_kind="thread")
    return Session(expr_backend=backend, num_partitions=where)


@pytest.mark.parametrize("where", [1, 4, "threads"])
@pytest.mark.parametrize("backend", ["interp", "numpy", "jax"])
@pytest.mark.parametrize("keys", KEYS, ids=["a-b", "b-t-a", "d"])
@pytest.mark.parametrize("n", [7, 5000])
def test_matches_the_lexsort_oracle(n, keys, backend, where):
    rows = _rows()
    sess = _session(backend, where)
    ds = sess.load("rows", rows, Row)
    got = (ds.filter(lambda r: r.d != 5)
           .order_by(*[k if d == "asc" else (k, d) for k, d in keys])
           .limit(n).collect())
    _assert_rows(got, _oracle(rows[rows["d"] != 5], keys, n))


@pytest.mark.parametrize("backend", ["interp", "numpy", "jax"])
def test_empty_input_keeps_the_schema(backend):
    sess = Session(expr_backend=backend, num_partitions=4)
    ds = sess.load("rows", _rows(), Row)
    q = ds.filter(lambda r: r.a > 100).order_by(("b", "desc")).limit(3)
    got = q.collect()
    assert list(got) == list(Row.fields)
    assert all(len(v) == 0 for v in got.values())
    assert {c: v.dtype for c, v in got.items()} == q.check().output_schema


def test_limit_zero_and_a_repeated_query():
    sess = Session(num_partitions=4)
    ds = sess.load("rows", _rows(), Row)
    assert all(len(v) == 0 for v in
               ds.order_by("a").limit(0).collect().values())
    q = ds.order_by(("a", "desc"), "t").limit(5)
    first = q.collect()
    _assert_rows(q.collect(), first)  # the plan cache replays it


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_schema_pass_infers_the_output_dtypes(backend):
    sess = Session(expr_backend=backend, num_partitions=4)
    q = sess.load("rows", _rows(), Row).order_by("t").limit(4)
    report = q.check()
    assert report.output_schema == {f: Row.dtype.fields[f][0]
                                    for f in Row.fields}
    got = q.collect()
    assert {c: v.dtype for c, v in got.items()} == report.output_schema
    assert q.schema is Row


@pytest.mark.parametrize("where", [4, "threads"])
def test_orders_a_grouped_result_then_filters_it(where):
    """Q3's ending: group on several keys, sum, order by the sum
    descending and a key ascending; a filter after the limit repacks the
    columns into records of the same schema."""
    rows = _rows(n=3000, seed=5)
    sess = _session("numpy", where)
    ds = sess.load("rows", rows, Row)
    grouped = (ds.group_by("a", "t")
               .agg(total=agg.sum("b"), n=agg.count()))
    top = grouped.order_by(("total", "desc"), "a").limit(5)
    got = top.collect()
    assert list(got) == ["a", "t", "total", "n"]
    want = {}
    for a in range(4):
        for t in (b"", b"x", b"yy", b"zzz"):
            m = (rows["a"] == a) & (rows["t"] == t)
            if m.any():
                want[(a, t)] = (rows["b"][m].sum(), int(m.sum()))
    best = sorted(want.items(), key=lambda kv: (-kv[1][0], kv[0][0],
                                                kv[0][1]))[:5]
    assert [(int(a), t) for a, t in zip(got["a"], got["t"])] == \
        [k for k, _ in best]
    np.testing.assert_allclose(got["total"], [v[0] for _, v in best])
    kept = top.filter(lambda g: g.n > 0).collect()
    assert len(kept) == 1
    rec = next(iter(kept.values()))
    assert rec.dtype.names == ("a", "t", "total", "n")
    np.testing.assert_array_equal(rec["a"], got["a"])


def test_rejects_malformed_orderings():
    sess = Session()
    ds = sess.load("rows", _rows(), Row)
    with pytest.raises(UnknownColumnError):
        ds.order_by("nope")
    with pytest.raises(TypeError):
        ds.order_by(("a", "down"))
    with pytest.raises(ValueError):
        ds.order_by("a", ("a", "desc"))
    with pytest.raises(ValueError):
        ds.order_by()
    with pytest.raises(ValueError):
        ds.order_by("a").limit(-1)
    untyped = ds.select(lambda r: r.a)
    with pytest.raises(ValueError):
        untyped.order_by("a")


@pytest.mark.parametrize("runtime", ["fork", "socket", "service"])
def test_every_worker_runtime_gives_the_oracle_rows(runtime):
    import multiprocessing
    if (runtime in ("fork", "socket")
            and "fork" not in multiprocessing.get_all_start_methods()):
        pytest.skip("fork start method unavailable")
    rows = _rows()
    keys = KEYS[1]
    want = _oracle(rows, keys, 9)
    if runtime == "service":
        from repro.service import QueryService
        with QueryService(num_workers=3, launch="thread") as svc:
            svc.wait_ready(30)
            ds = Session.connect(svc).load("rows", rows, Row)
            got = ds.order_by(*[(k, d) for k, d in keys]).limit(9).collect()
    else:
        sess = Session(backend="workers", num_workers=3,
                       worker_kind=runtime)
        ds = sess.load("rows", rows, Row)
        got = ds.order_by(*[(k, d) for k, d in keys]).limit(9).collect()
    _assert_rows(got, want)
