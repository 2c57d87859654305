"""Typed record schemas + compiled lambda stages.

Three pillars, each enforced bit-for-bit:

1. **Cross-backend equivalence** — the interpreted (`interp`), fused-numpy
   (`numpy`) and jitted (`jax`) expression backends produce byte-identical
   results: deterministic chains, the TPC-H entry points, and a hypothesis
   property suite over random term trees and record batches.
2. **Typed schemas** — Record declaration, packing/validation, typed
   column access (including fields that shadow LambdaArg attributes),
   graph-build-time UnknownColumnError, the synthesized pair schema behind
   ``join(project=None)``.
3. **Plan caching** — the physical plan cached alongside the logical plan
   and invalidated by the store stats_version; the process-wide kernel LRU
   shared across sessions.
"""
import numpy as np
import pytest

from repro.core import (Session, UnknownColumnError, kernel_cache_info,
                        make_lambda, register_method, reset_kernel_cache)
from repro.objectmodel import PagedStore
from repro.objectmodel.schema import (Record, S, f64, i64, pair_schema,
                                      record, vector)

BACKENDS = ("interp", "numpy", "jax")


class TRow(Record):
    a: i64
    b: i64
    c: f64
    tag: S(4)


register_method("TRow", "getA")(lambda rows: rows["a"])


def _rows(n=300, seed=0):
    rng = np.random.default_rng(seed)
    return TRow.pack(a=rng.integers(-100, 100, n),
                     b=rng.integers(-100, 100, n),
                     c=rng.normal(0, 10, n),
                     tag=rng.choice([b"x", b"y", b"z"], n))


def _assert_bytes_equal(results):
    ref = results[0]
    for other in results[1:]:
        assert set(ref) == set(other)
        for col in ref:
            x, y = np.asarray(ref[col]), np.asarray(other[col])
            assert x.dtype == y.dtype, col
            assert x.shape == y.shape, col
            assert x.tobytes() == y.tobytes(), col


def _collect_all(build, records=None, schema=TRow, num_partitions=3):
    results = []
    for be in BACKENDS:
        sess = Session(num_partitions=num_partitions, expr_backend=be)
        ds = sess.load("t", _rows() if records is None else records, schema)
        results.append(build(sess, ds).collect())
    _assert_bytes_equal(results)
    return results[0]


# ------------------------------------------------- backend equivalence
def test_filter_select_chain_equivalent_across_backends():
    r = _collect_all(lambda s, ds: (
        ds.filter(lambda t: t.a > -50)
          .filter(lambda t: (t.b < 80) | (t.a == 0))
          .filter(lambda t: ~(t.c > 25.0))
          .select(lambda t: t.a * 3 + t.b - t.a * t.b)))
    assert len(next(iter(r.values()))) > 0


def test_method_call_and_bytes_compare_equivalent():
    _collect_all(lambda s, ds: (
        ds.filter(lambda t: t.tag == b"x")
          .select(lambda t: t.col("a") + t.b)))


def test_division_and_empty_batches_equivalent():
    # division by zero produces inf/nan identically; an always-false filter
    # exercises zero-row outputs through every backend
    _collect_all(lambda s, ds: ds.select(lambda t: t.c / t.a))
    r = _collect_all(lambda s, ds: (
        ds.filter(lambda t: t.a > 1000).select(lambda t: t.a + 1)))
    assert len(next(iter(r.values()))) == 0


@pytest.mark.parametrize("be", BACKENDS)
def test_empty_set_output_keeps_computed_dtypes(be):
    # a set with no rows still yields typed (not float64) empty columns,
    # as the analyzer infers them: the hypothesis counterexample n=0
    sess = Session(num_partitions=1, expr_backend=be)
    ds = sess.load("t", _rows(0), TRow)
    for q in (ds.select(lambda t: t.a),
              ds.filter(lambda t: t.b > 0).select(lambda t: t.a * 2)):
        r = q.collect()
        assert {c: v.dtype for c, v in r.items()} == q.check().output_schema
        assert all(v.dtype == np.int64 and len(v) == 0 for v in r.values())


def test_agg_topk_and_native_barrier_equivalent():
    _collect_all(lambda s, ds: (
        ds.filter(lambda t: t.a > -90)
          .aggregate(key=lambda t: t.tag,
                     value=lambda t: make_lambda(
                         t, lambda rows: rows["a"] * rows["b"], "ab"))))
    _collect_all(lambda s, ds: ds.top_k(7, score="c", payload="a"))


def test_join_equivalent_across_backends_and_algorithms():
    left = TRow.pack(a=np.arange(50) % 7, b=np.arange(50),
                     c=np.zeros(50), tag=[b"l"] * 50)
    Dim = record("TDim", k=i64, w=i64)
    dim = Dim.pack(k=np.arange(7), w=np.arange(7) * 10)
    for threshold in (2 << 30, 0):
        results = []
        for be in BACKENDS:
            sess = Session(num_partitions=3, expr_backend=be,
                           broadcast_threshold_bytes=threshold)
            lds = sess.load("l", left, TRow)
            rds = sess.load("d", dim, Dim)
            results.append(
                lds.join(rds, on=lambda t, d: t.a == d.k).collect())
        _assert_bytes_equal(results)


def test_tpch_entry_points_equivalent_across_backends():
    from repro.apps.tpch import (customers_per_supplier, load_tpch,
                                 topk_jaccard)
    from repro.data.synthetic import denormalized_tpch
    cust, lines, n_supp, n_parts = denormalized_tpch(60, seed=7)
    results = []
    for be in BACKENDS:
        sess = Session(num_partitions=4, expr_backend=be)
        _, ln = load_tpch(sess.store, cust, lines, session=sess)
        cps = customers_per_supplier(sess.store, ln, n_parts, session=sess)
        q = np.unique(lines["partkey"][:24])
        ids, scores = topk_jaccard(sess.store, ln, n_parts, q, k=9,
                                   session=sess)
        results.append((cps, ids, scores))
    (cps0, ids0, sc0) = results[0]
    for cps, ids, scores in results[1:]:
        assert ids0.tobytes() == ids.tobytes()
        assert sc0.tobytes() == scores.tobytes()
        assert set(cps0) == set(cps)
        for supp in cps0:
            assert set(cps0[supp]) == set(cps[supp])
            for c in cps0[supp]:
                assert np.array_equal(cps0[supp][c], cps[supp][c])


# -------------------------------------------------- random term trees
# (the hypothesis-driven property suite lives in
# tests/test_exprc_properties.py; this deterministic variant samples the
# same AST space so environments without hypothesis still cover it)
def test_sampled_random_term_trees_byte_identical_across_backends():
    from exprc_trees import collect_tree_query, sample_query
    rng = np.random.default_rng(42)
    for case in range(12):
        preds, proj = sample_query(rng)
        n = int(rng.integers(0, 250))
        parts = int(rng.integers(1, 5))
        results = collect_tree_query(
            Session, _rows(n, seed=case), TRow, BACKENDS, preds, proj,
            parts)
        _assert_bytes_equal(results)


# --------------------------------------------------------- typed schemas
def test_schema_registration_and_pack_roundtrip():
    P = record("SchemaTestPoint", x=f64, n=i64)
    assert record("SchemaTestPoint", x=f64, n=i64) is P  # dedup
    with pytest.raises(ValueError, match="different layout"):
        record("SchemaTestPoint", x=f64, n=f64)
    rec = P.pack(x=[1.5, 2.5], n=[1, 2])
    assert rec.dtype == P.dtype and len(rec) == 2
    with pytest.raises(ValueError, match="missing fields"):
        P.pack(x=[1.0])
    with pytest.raises(TypeError, match="dtype"):
        P.validate(np.zeros(3, np.dtype([("x", np.float32),
                                         ("n", np.int64)])))


def test_load_and_read_validate_layout_against_schema():
    sess = Session()
    bad = np.zeros(4, np.dtype([("a", np.int32)]))
    with pytest.raises(TypeError, match="TRow"):
        sess.load("t", bad, TRow)
    ds = sess.load("t", _rows(8), TRow)
    Other = record("TRowOther", z=i64)
    with pytest.raises(TypeError, match="does not match schema"):
        sess.read(ds.set_name, Other)


def test_create_set_returns_typed_dataset():
    sess = Session(num_partitions=2)
    ds = sess.create_set(TRow)
    assert ds.schema is TRow
    sess.store.send_data(ds.set_name, _rows(32, seed=3))
    out = ds.filter(lambda t: t.a >= 0).select("a").to_numpy()
    ref = _rows(32, seed=3)["a"]
    assert np.array_equal(np.sort(out), np.sort(ref[ref >= 0]))


def test_unknown_column_raises_at_graph_build_time():
    sess = Session()
    ds = sess.load("t", _rows(8), TRow)
    with pytest.raises(UnknownColumnError, match=r"\[a, b, c, tag\]"):
        ds.filter(lambda t: t.salry > 0)  # typo'd column, no collect needed
    with pytest.raises(UnknownColumnError):
        ds.select("salry")
    with pytest.raises(UnknownColumnError):
        ds.aggregate(key="a", value=lambda t: t.col("nope"))
    # LambdaArg's own attribute names are NOT an escape hatch on typed
    # args: a non-field access must raise, never return an engine value
    for shadowed in ("name", "slot", "type_name"):
        with pytest.raises(UnknownColumnError):
            ds.filter(lambda t, _s=shadowed: getattr(t, _s) == 0)


def test_schema_fields_shadowing_lambdaarg_attributes_resolve():
    Shadow = record("ShadowRow", name=S(8), slot=i64, term=i64, col=i64)
    recs = Shadow.pack(name=[f"n{i}".encode() for i in range(6)],
                       slot=np.arange(6), term=np.arange(6) * 2,
                       col=np.arange(6) * 3)
    sess = Session(num_partitions=2)
    ds = sess.load("shadow", recs, Shadow)
    # every shadowed name is a plain attribute access on a typed dataset
    r = (ds.filter(lambda e: (e.slot >= 3) & (e.term >= 0) & (e.col >= 0))
           .select(lambda e: e.name).to_numpy())
    assert np.array_equal(np.sort(r),
                          np.sort(recs["name"][recs["slot"] >= 3]))


def test_default_join_projection_synthesizes_pair_schema():
    L = record("JoinL", k=i64, v=f64)
    R = record("JoinR", k=i64, w=i64)
    pair = pair_schema(L, R)
    assert pair.fields == ("k", "v", "joinr_k", "w")
    sess = Session(num_partitions=2)
    lds = sess.load("l", L.pack(k=np.arange(10) % 3,
                                v=np.arange(10) * 1.5), L)
    rds = sess.load("r", R.pack(k=np.arange(3), w=np.arange(3) * 10), R)
    joined = lds.join(rds, on=lambda a, b: a.k == b.k)
    assert joined.schema is pair
    # the joined dataset stays typed: chain on a right-side field
    out = joined.filter(lambda p: p.w >= 10).collect()
    col = np.asarray(next(iter(out.values())))
    assert col.dtype == pair.dtype
    ref_rows = [(k, v, k, k * 10) for k, v in
                zip(np.arange(10) % 3, np.arange(10) * 1.5) if k * 10 >= 10]
    assert sorted(map(tuple, col.tolist())) == sorted(ref_rows)


def test_default_join_projection_requires_typed_inputs():
    sess = Session()
    lds = sess.load("l", _rows(8), TRow)
    untyped = sess.load("u", _rows(8))
    with pytest.raises(ValueError, match="typed datasets on both sides"):
        lds.join(untyped, on=lambda a, b: a.a == b.col("a"))


# ------------------------------------------------------------ plan caches
def test_physical_plan_cached_and_invalidated_by_store_stats():
    sess = Session(num_partitions=2, broadcast_threshold_bytes=3000)
    left = sess.load("l", _rows(200, seed=1), TRow)
    dim = record("PhysDim", k=i64, w=i64)
    right = sess.load("d", dim.pack(k=np.arange(5), w=np.arange(5)), dim)
    q = left.join(right, on=lambda t, d: t.a == d.k)
    q.collect()
    assert sess.physical_plan_cache_info() == {"hits": 0, "misses": 1}
    assert sess.executor.stats.broadcast_joins == 1  # small build side
    ver = sess.store.stats_version
    q.collect()
    assert sess.physical_plan_cache_info() == {"hits": 1, "misses": 1}
    assert sess.store.stats_version == ver  # cache hit moved no statistics
    # growing the build side moves stats_version -> plan re-derived, and
    # the broadcast decision flips to a hash-partition shuffle
    sess.store.send_data(right.set_name,
                         dim.pack(k=np.arange(500), w=np.arange(500)))
    q.collect()
    assert sess.physical_plan_cache_info() == {"hits": 1, "misses": 2}
    assert sess.executor.stats.hash_partition_joins == 1


def test_kernel_cache_distinguishes_const_dtypes():
    """Regression: 2, 2.0 and True hash/compare equal, but the inferred
    const dtype is baked into a compiled kernel — numerically-equal
    constants of different types must not share a cache entry."""
    from repro.core import constant
    reset_kernel_cache()
    for be in ("numpy", "jax"):
        outs = []
        for k in (2, 2.0):
            sess = Session(num_partitions=2, expr_backend=be)
            ds = sess.load("t", _rows(16), TRow)
            outs.append(ds.select(
                lambda t, _k=k: t.col("a") * constant(_k)).to_numpy())
        assert outs[0].dtype == np.int64, be
        assert outs[1].dtype == np.float64, be


def test_create_set_refuses_reserved_names():
    store = PagedStore()
    s1, s2 = Session(store=store), Session(store=store)
    reserved = s1.fresh_set_name("pts")
    with pytest.raises(ValueError, match="reserved"):
        s2.create_set(TRow, name=reserved)


def test_scanset_rejects_non_record_classes():
    from repro.core import ScanSet
    with pytest.raises(TypeError, match="Record schema"):
        ScanSet("db", "s", dict)


def test_fork_workers_reject_jax_expr_backend():
    with pytest.raises(ValueError, match="fork.*jax|jax.*fork"):
        Session(backend="workers", num_workers=2, worker_kind="fork",
                expr_backend="jax")


def test_kernel_cache_shared_across_sessions():
    reset_kernel_cache()

    def run(be):
        sess = Session(num_partitions=2, expr_backend=be)
        ds = sess.load("t", _rows(64), TRow)
        return (ds.filter(lambda t: t.a > 0)
                  .select(lambda t: t.a + t.b).collect())

    run("numpy")
    misses = kernel_cache_info()["misses"]
    assert misses >= 1
    run("numpy")  # second session: plan cache cold, kernel cache warm
    info = kernel_cache_info()
    assert info["misses"] == misses
    assert info["hits"] >= 1


def test_volcano_executor_stays_on_interpreter():
    from repro.core import NaiveExecutor
    sess = Session(executor_cls=NaiveExecutor, num_partitions=2)
    assert sess.executor.expr_backend == "interp"
    ds = sess.load("t", _rows(40), TRow)
    out = ds.filter(lambda t: t.a > 0).select("a").to_numpy()
    ref = _rows(40)["a"]
    assert np.array_equal(np.sort(out), np.sort(ref[ref > 0]))
