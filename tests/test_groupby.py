"""Declarative grouped aggregation: ``group_by().agg()``.

The equivalence matrix — byte-identical results across
``expr_backend ∈ {interp, numpy, jax}`` × ``backend ∈ {local, workers}`` —
plus empty-group/empty-input edge cases, the legacy ``aggregate()``
compatibility contract, typed chaining off grouped results, and a
hypothesis property test over random key/value/combiner sets.
"""
import numpy as np
import pytest

from repro.core import Session, UnknownColumnError, agg, constant
from repro.objectmodel.schema import Record, S, f64, i64

EXPR_BACKENDS = ("interp", "numpy", "jax")


class GRow(Record):
    k1: i64
    k2: S(2)
    v1: f64
    v2: i64


def _rows(n=400, seed=0):
    rng = np.random.default_rng(seed)
    return GRow.pack(k1=rng.integers(0, 7, n),
                     k2=rng.choice([b"aa", b"bb", b"cc"], n),
                     v1=rng.normal(0, 100, n),
                     v2=rng.integers(-50, 50, n))


def _assert_bytes_equal(results):
    ref = results[0]
    for other in results[1:]:
        assert set(ref) == set(other)
        for col in ref:
            x, y = np.asarray(ref[col]), np.asarray(other[col])
            assert x.dtype == y.dtype, col
            assert x.shape == y.shape, col
            assert x.tobytes() == y.tobytes(), col


def _matrix_collect(build, records, schema=GRow, parts=3):
    """Run a query over every expr backend × executor backend; assert all
    six results byte-identical, return the reference."""
    results = []
    for be in EXPR_BACKENDS:
        for kw in ({"num_partitions": parts},
                   {"backend": "workers", "num_workers": parts}):
            sess = Session(expr_backend=be, **kw)
            ds = sess.load("g", records, schema)
            results.append(build(ds).collect())
    _assert_bytes_equal(results)
    return results[0]


def _reference_groups(records, mask=None):
    """Insertion-order-free reference: key tuple -> row array."""
    sub = records if mask is None else records[mask]
    out = {}
    for row in sub:
        out.setdefault((row["k1"], row["k2"]), []).append(row)
    return {k: np.stack(v) for k, v in out.items()}


# ------------------------------------------------------ equivalence matrix
def test_multi_aggregate_matrix_byte_identical_and_correct():
    records = _rows()
    r = _matrix_collect(
        lambda ds: (ds.filter(lambda g: g.v2 > -40)
                      .group_by("k1", "k2")
                      .agg(total=agg.sum("v1"),
                           lo=agg.min("v1"),
                           hi=agg.max("v1"),
                           n=agg.count(),
                           avg_v2=agg.mean("v2"),
                           rev=agg.sum(lambda g: g.v1 * g.v2))),
        records)
    assert sorted(r) == ["avg_v2", "hi", "k1", "k2", "lo", "n", "rev",
                         "total"]
    assert np.asarray(r["n"]).dtype == np.int64
    assert np.asarray(r["avg_v2"]).dtype == np.float64
    ref = _reference_groups(records, records["v2"] > -40)
    got = {(k1, k2): i for i, (k1, k2) in
           enumerate(zip(np.asarray(r["k1"]), np.asarray(r["k2"])))}
    assert set(got) == set(ref)
    for key, rows in ref.items():
        i = got[key]
        assert np.isclose(r["total"][i], rows["v1"].sum())
        assert r["lo"][i] == rows["v1"].min()
        assert r["hi"][i] == rows["v1"].max()
        assert r["n"][i] == len(rows)
        assert np.isclose(r["avg_v2"][i], rows["v2"].mean())
        assert np.isclose(r["rev"][i], (rows["v1"] * rows["v2"]).sum())


def test_tpch_q1_matrix_byte_identical(tmp_path):
    from repro.apps.tpch import q1_pricing_summary
    from repro.data.synthetic import tpch_q1_lineitems
    lines = tpch_q1_lineitems(3000, seed=5)
    results = []
    for be in EXPR_BACKENDS:
        for kw in ({"num_partitions": 3},
                   {"backend": "workers", "num_workers": 3}):
            sess = Session(expr_backend=be, **kw)
            ds = sess.load("lineitem", lines)
            results.append(q1_pricing_summary(
                sess.store, ds.set_name, session=sess).collect())
    _assert_bytes_equal(results)
    r = results[0]
    assert len(r) == 10  # 2 key columns + 8 aggregate columns
    assert (np.asarray(r["count_order"]).sum()
            == (lines["shipdate"] <= 9400).sum())


def _device_form_calls():
    from repro.obs.metrics import METRICS
    return {f: METRICS.counter(f"agg.device_reduce.{f}.total")
            for f in ("dense", "scatter")}


def _wide_rows(n_keys, seed):
    """Rows whose ``k1`` takes ``n_keys`` values, each about twice."""
    rng = np.random.default_rng(seed)
    n = 2 * n_keys
    return GRow.pack(k1=rng.integers(0, n_keys, n),
                     k2=rng.choice([b"aa"], n),
                     v1=rng.normal(0, 100, n),
                     v2=rng.integers(-50, 50, n))


@pytest.mark.parametrize("form", ["scatter", "dense"])
def test_device_segment_reducer_bit_identical_when_forced(monkeypatch,
                                                          form):
    """On a CPU jax backend the device reducer is cost-gated off; force it
    on (REPRO_AGG_DEVICE=1) and pin down its results against the host
    scatters, in each form. The scatter form (more groups a partition
    than ``_DENSE_SEGS_MAX``) accumulates in row order on the CPU: byte-
    identical. The dense form adds floats in a tree: keys, counts,
    integer sums, min and max stay byte-identical, float sums and means
    agree within 1e-12 relative."""
    from repro.core.relops import _DENSE_SEGS_MAX, device_segment_reducer
    assert device_segment_reducer(("sum",), force=True) is not None
    parts = 3
    records = (_wide_rows(2 * parts * _DENSE_SEGS_MAX, seed=8)
               if form == "scatter" else _rows(500, seed=8))
    build = lambda ds: (ds.group_by("k1", "k2")  # noqa: E731
                          .agg(s=agg.sum("v1"), lo=agg.min("v1"),
                               hi=agg.max("v2"), m=agg.mean("v1"),
                               n=agg.count(), t=agg.sum("v2")))
    host = Session(num_partitions=parts, expr_backend="numpy")
    ref = build(host.load("g", records, GRow)).collect()
    monkeypatch.setenv("REPRO_AGG_DEVICE", "1")
    dev = Session(num_partitions=parts, expr_backend="jax")
    before = _device_form_calls()
    got = build(dev.load("g", records, GRow)).collect()
    after = _device_form_calls()
    other = "dense" if form == "scatter" else "scatter"
    # one call for each partition that holds rows, all in one form
    assert after[form] > before[form]
    assert after[other] == before[other]
    if form == "scatter":
        _assert_bytes_equal([ref, got])
        return
    floats = ("s", "m")
    _assert_bytes_equal([{c: v for c, v in r.items() if c not in floats}
                         for r in (ref, got)])
    for c in floats:
        x, y = np.asarray(ref[c]), np.asarray(got[c])
        assert x.dtype == y.dtype == np.float64
        np.testing.assert_allclose(y, x, rtol=1e-12, atol=0)


def test_device_reducer_form_follows_the_segment_count():
    """Up to ``_DENSE_SEGS_MAX`` padded slots the dense form, above it the
    scatter form; each call counts once under its form."""
    from repro.core.relops import _DENSE_SEGS_MAX, device_segment_reducer
    red = device_segment_reducer(("sum", "max"), force=True)
    for n, form in ((5, "dense"), (_DENSE_SEGS_MAX, "dense"),
                    (_DENSE_SEGS_MAX + 1, "scatter")):
        inv = np.arange(n)
        vals = [np.ones(n, np.int64), np.arange(n, dtype=np.float64)]
        before = _device_form_calls()
        acc, hi = red(inv, n, vals)
        after = _device_form_calls()
        assert {f: after[f] - before[f] for f in after} == {
            f: int(f == form) for f in after}
        assert acc.tolist() == [1] * n and hi.tolist() == list(range(n))


def _dense_case(seed):
    """One partition's reducer inputs: 37 rows (padded to 64) into 11
    slots, slots 3 and 10 empty; value columns of every accumulator
    kind, one with a trailing dimension."""
    from repro.core.relops import _DENSE_SEGS_MAX
    rng = np.random.default_rng(seed)
    n, rows = 11, 37
    assert n <= _DENSE_SEGS_MAX
    inv = rng.choice([s for s in range(n) if s not in (3, 10)], rows)
    vals = {"i64": rng.integers(-1 << 40, 1 << 40, rows),
            "i32": rng.integers(-1000, 1000, rows).astype(np.int32),
            "bool": rng.random(rows) > 0.5,
            "ones": np.ones(rows, np.int64),
            "f64": rng.normal(0, 1e3, rows),
            "f64x2": rng.normal(0, 1e3, (rows, 2)),
            "f32": rng.normal(0, 1e3, rows).astype(np.float32)}
    return inv, n, vals


def test_dense_reducer_exact_accumulators_bit_identical_to_host():
    from repro.core.relops import _COMBINE, device_segment_reducer
    inv, n, vals = _dense_case(seed=21)
    cols = [("sum", vals["i64"]), ("sum", vals["i32"]),
            ("sum", vals["bool"]), ("sum", vals["ones"])]
    cols += [(c, vals[k]) for c in ("min", "max")
             for k in ("f64", "f64x2", "i64", "f32")]
    combs = tuple(c for c, _ in cols)
    got = device_segment_reducer(combs, force=True)(
        inv, n, [v for _, v in cols])
    for (comb, v), g in zip(cols, got):
        want = _COMBINE[comb](None, inv, v, n)
        assert g.dtype == want.dtype and g.shape == want.shape
        assert g.tobytes() == want.tobytes(), (comb, v.dtype)
    # empty slots keep the initial value; padded rows land nowhere
    sums, mins, maxs = got[:4], got[4:8], got[8:]
    assert all(a[[3, 10]].tolist() == [0, 0] for a in sums)
    assert all(np.isposinf(a[[3, 10]]).all() for a in mins)
    assert all(np.isneginf(a[[3, 10]]).all() for a in maxs)
    assert got[3].sum() == len(inv)


def test_dense_reducer_float_sums_within_1e12_of_host():
    from repro.core.relops import _COMBINE, device_segment_reducer
    inv, n, vals = _dense_case(seed=22)
    cols = [vals["f64"], vals["f64x2"], vals["f32"]]
    got = device_segment_reducer(("sum",) * 3, force=True)(inv, n, cols)
    for v, g in zip(cols, got):
        want = _COMBINE["sum"](None, inv, v, n)
        assert g.dtype == want.dtype == np.float64
        assert g.shape == want.shape
        np.testing.assert_allclose(g, want, rtol=1e-12, atol=0)


# ----------------------------------------------------------- edge cases
def test_empty_input_and_empty_groups():
    records = _rows(0)
    r = _matrix_collect(
        lambda ds: ds.group_by("k1").agg(n=agg.count(), s=agg.sum("v1")),
        records)
    assert all(len(np.asarray(v)) == 0 for v in r.values())
    # non-empty input, but the filter kills every row
    r = _matrix_collect(
        lambda ds: (ds.filter(lambda g: g.v2 > 10_000)
                      .group_by("k1").agg(n=agg.count())),
        _rows(64))
    assert all(len(np.asarray(v)) == 0 for v in r.values())


def test_single_row_and_constant_key_global_aggregate():
    records = _rows(1, seed=3)
    r = _matrix_collect(
        lambda ds: ds.group_by("k1").agg(n=agg.count(), m=agg.mean("v1")),
        records)
    assert np.asarray(r["n"]).tolist() == [1]
    assert np.isclose(np.asarray(r["m"])[0], records["v1"][0])
    # global aggregate via a constant key
    records = _rows(128, seed=4)
    r = _matrix_collect(
        lambda ds: (ds.group_by(lambda g: constant(0))
                      .agg(total=agg.sum("v2"), n=agg.count())),
        records)
    assert np.asarray(r["total"]).tolist() == [records["v2"].sum()]
    assert np.asarray(r["n"]).tolist() == [128]


def test_boolean_indicator_sum_counts_not_saturates():
    """Regression: agg.sum / agg.mean over a boolean indicator expression
    must count/average it (int64 / float64 accumulators), not saturate a
    bool accumulator at True."""
    records = _rows(200, seed=6)
    r = _matrix_collect(
        lambda ds: (ds.group_by("k1")
                      .agg(pos=agg.sum(lambda g: g.v1 > 0),
                           frac=agg.mean(lambda g: g.v1 > 0))),
        records)
    assert np.asarray(r["pos"]).dtype == np.int64
    assert np.asarray(r["frac"]).dtype == np.float64
    for k, pos, frac in zip(np.asarray(r["k1"]), np.asarray(r["pos"]),
                            np.asarray(r["frac"])):
        sub = records["v1"][records["k1"] == k] > 0
        assert pos == sub.sum()
        assert np.isclose(frac, sub.mean())
    # the forced device path handles bool accumulators the same way
    from repro.core.relops import device_segment_reducer
    red = device_segment_reducer(("sum",), force=True)
    out, = red(np.array([0, 0, 1]), 2, [np.array([True, True, False])])
    assert out.dtype == np.int64 and out.tolist() == [2, 0]


# --------------------------------------------------- legacy compatibility
@pytest.mark.parametrize("combiner", ["sum", "min", "max"])
def test_legacy_aggregate_wrapper_matches_group_by(combiner):
    records = _rows()
    sess = Session(num_partitions=3)
    ds = sess.load("g", records, GRow)
    old = ds.aggregate(key="k1", value="v1", combiner=combiner).collect()
    new = (ds.group_by("k1")
             .agg(value=getattr(agg, combiner)("v1")).collect())
    assert sorted(old) == ["key", "value"]
    # same values under the legacy fixed column names vs the named form
    assert np.asarray(old["key"]).tobytes() == \
        np.asarray(new["k1"]).tobytes()
    assert np.asarray(old["value"]).tobytes() == \
        np.asarray(new["value"]).tobytes()


def test_legacy_aggregate_accepts_mean():
    records = _rows()
    sess = Session(num_partitions=2)
    ds = sess.load("g", records, GRow)
    r = ds.aggregate(key="k1", value="v1", combiner="mean").collect()
    ref = _rows()
    for k, m in zip(np.asarray(r["key"]), np.asarray(r["value"])):
        assert np.isclose(m, ref["v1"][ref["k1"] == k].mean())


# ------------------------------------------------------- typed chaining
def test_grouped_result_is_typed_and_chains():
    sess = Session(num_partitions=3)
    ds = sess.load("g", _rows(), GRow)
    g = ds.group_by("k1", "k2").agg(total=agg.sum("v1"), n=agg.count())
    assert g.schema is not None
    assert g.schema.fields == ("k1", "k2", "total", "n")
    assert g.schema.field_types["n"].dtype == np.int64
    # a typo'd column downstream of the agg fails at the chain call
    with pytest.raises(UnknownColumnError, match=r"\[k1, k2, total, n\]"):
        g.filter(lambda r: r.totl > 0)
    # filter + top_k chain off the grouped result, on every backend pair
    r = _matrix_collect(
        lambda d: (d.group_by("k1", "k2")
                    .agg(total=agg.sum("v1"), n=agg.count())
                    .filter(lambda r: r.n > 10)
                    .top_k(3, score="total", payload="k1")),
        _rows())
    assert len(np.asarray(r["score"])) == 3


def test_grouped_result_joins_and_regroups():
    sess = Session(num_partitions=2)
    records = _rows()
    ds = sess.load("g", records, GRow)
    per_pair = ds.group_by("k1", "k2").agg(s=agg.sum("v2"))
    # second-level aggregation over the grouped result
    per_k1 = per_pair.group_by("k1").agg(pairs=agg.count(),
                                         total=agg.sum("s"))
    r = per_k1.collect()
    ref = _reference_groups(records)
    for k, n, tot in zip(np.asarray(r["k1"]), np.asarray(r["pairs"]),
                         np.asarray(r["total"])):
        keys = [key for key in ref if key[0] == k]
        assert n == len(keys)
        assert tot == sum(ref[key]["v2"].sum() for key in keys)


def test_grouped_write_materializes_named_columns():
    sess = Session(num_partitions=2)
    ds = sess.load("g", _rows(), GRow)
    (ds.group_by("k1").agg(total=agg.sum("v1"), n=agg.count())
       .write("summary").collect())
    recs = sess.store.get_set("summary").all_records()
    assert sorted(recs.dtype.names) == ["k1", "n", "total"]


def test_grouped_key_dtypes_match_declared_schema():
    """Regression: emitted key columns must keep the source column dtype
    (i32 keys stay i32, S(2) keys stay S2 even when every value is
    shorter), so the synthesized group schema is truthful and a typed
    write → read round-trip validates."""
    from repro.objectmodel.schema import i32, record
    Narrow = record("NarrowKeyRow", k=i32, tag=S(2), v=f64)
    recs = Narrow.pack(k=np.arange(40) % 5,
                       tag=[b"a", b"b"] * 20,
                       v=np.arange(40, dtype=np.float64))
    for kw in ({"num_partitions": 2},
               {"backend": "workers", "num_workers": 2}):
        sess = Session(**kw)
        ds = sess.load("n", recs, Narrow)
        g = ds.group_by("k", "tag").agg(s=agg.sum("v"))
        out = g.collect()
        assert np.asarray(out["k"]).dtype == np.int32
        assert np.asarray(out["tag"]).dtype == np.dtype("S2")
        assert g.schema.field_types["k"].dtype == np.int32
    # typed round-trip: materialize, read back under the group schema
    name = sess.fresh_set_name("grp")
    ds.group_by("k", "tag").agg(s=agg.sum("v")).write(name).collect()
    back = sess.read(name, g.schema)
    assert back.schema is g.schema


# ---------------------------------------------------------- validation
def test_group_by_and_agg_validation_errors():
    sess = Session(num_partitions=2)
    ds = sess.load("g", _rows(16), GRow)
    with pytest.raises(ValueError, match="at least one key"):
        ds.group_by()
    with pytest.raises(UnknownColumnError):
        ds.group_by("nope")
    with pytest.raises(ValueError, match="distinct"):
        ds.group_by("k1", "k1")
    with pytest.raises(ValueError, match="at least one named aggregate"):
        ds.group_by("k1").agg()
    with pytest.raises(TypeError, match="AggTerm"):
        ds.group_by("k1").agg(total="v1")
    with pytest.raises(ValueError, match="collides"):
        ds.group_by("k1").agg(k1=agg.count())
    with pytest.raises(UnknownColumnError):
        ds.group_by("k1").agg(total=agg.sum("nope"))
    from repro.core import AggTerm
    with pytest.raises(ValueError, match="unknown aggregate kind"):
        AggTerm("median", "v1")
    with pytest.raises(ValueError, match="unknown aggregate kind"):
        ds.aggregate(key="k1", value="v1", combiner="avg")
    from repro.core import AggregateComp
    with pytest.raises(ValueError, match="unknown combiner"):
        AggregateComp(combiner="avg")


# ------------------------------------------------- property-based matrix
def _check_random_query(keys, outs, n, seed, parts=2):
    """One random grouped query: matrix byte-equivalence + a plain python
    reference for every aggregate column (shared by the deterministic
    sample loop and the hypothesis property test)."""
    records = _rows(n, seed=seed)
    named = {f"o{i}": (getattr(agg, k)(v) if k != "count" else agg.count())
             for i, (k, v) in enumerate(outs)}
    r = _matrix_collect(lambda ds: ds.group_by(*keys).agg(**named),
                        records, parts=parts)
    groups = {}
    for row in records:
        groups.setdefault(tuple(row[k] for k in keys), []).append(row)
    got_keys = list(zip(*(np.asarray(r[k]).tolist() for k in keys)))
    assert set(got_keys) == set(groups)
    for i, key in enumerate(got_keys):
        rows = np.stack(groups[key])
        for j, (kind, v) in enumerate(outs):
            x = np.asarray(r[f"o{j}"])[i]
            if kind == "count":
                assert x == len(rows)
            elif kind == "sum":
                assert np.isclose(x, rows[v].sum())
            elif kind == "mean":
                assert np.isclose(x, rows[v].mean())
            elif kind == "min":
                assert x == rows[v].min()
            else:
                assert x == rows[v].max()


def test_sampled_random_key_value_combiner_sets():
    """Deterministic sample of the same space the hypothesis test walks,
    so environments without hypothesis still cover it (the pattern of
    tests/test_exprc.py)."""
    rng = np.random.default_rng(9)
    all_kinds = ["sum", "min", "max", "count", "mean"]
    for case in range(8):
        keys = (["k1"], ["k2"], ["k1", "k2"])[case % 3]
        n_outs = int(rng.integers(1, 5))
        outs = [(all_kinds[int(rng.integers(0, 5))],
                 ("v1", "v2")[int(rng.integers(0, 2))])
                for _ in range(n_outs)]
        _check_random_query(keys, outs, n=int(rng.integers(0, 150)),
                            seed=case)


def test_random_key_value_combiner_sets_property():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    kinds = st.sampled_from(["sum", "min", "max", "count", "mean"])
    key_cols = st.lists(st.sampled_from(["k1", "k2"]), min_size=1,
                        max_size=2, unique=True)
    val_cols = st.sampled_from(["v1", "v2"])

    @settings(max_examples=12, deadline=None)
    @given(keys=key_cols,
           outs=st.lists(st.tuples(kinds, val_cols), min_size=1,
                         max_size=4),
           n=st.integers(0, 120), seed=st.integers(0, 5))
    def check(keys, outs, n, seed):
        _check_random_query(keys, outs, n, seed)

    check()
