"""The columnar ``AggMap`` against a dict-of-lists reference.

The reference below is the per-group dict map the columnar one replaced:
one dict entry per key (a Python scalar, or a tuple for several key
columns) holding the list of accumulated values, folded, split, merged
and emitted one group at a time. Both share group discovery
(``relops._unique_keys``) and the per-batch scatters; everything after
that — fold, split by key hash, merge in rank order, emit, the wire
codec — must give the same groups in the same order, the same key
dtypes and bit-identical accumulators.
"""
import numpy as np
import pytest

from repro.core import Session, agg
from repro.core import relops
from repro.core.relops import AggMap, AggSpec, stable_key_hash
from repro.dist.protocol import decode_agg_map, encode_agg_map
from repro.obs.metrics import METRICS

_MERGE2 = {"sum": lambda a, b: a + b, "max": np.maximum, "min": np.minimum}


class DictMap:
    """Reference: one dict entry per group, in insertion order."""

    def __init__(self, spec, key_dtypes=None):
        self.spec = spec
        self.data = {}
        self.key_dtypes = key_dtypes

    def absorb(self, key_cols, val_cols):
        if len(key_cols[0]) == 0:
            return
        if self.key_dtypes is None:
            self.key_dtypes = [c.dtype for c in key_cols]
        cols, inv = relops._unique_keys(key_cols)
        keys = (cols[0].tolist() if len(cols) == 1
                else list(zip(*(c.tolist() for c in cols))))
        accs = [relops._COMBINE[c](None, inv, v, len(keys))
                for c, v in zip(self.spec.combiners, val_cols)]
        for i, k in enumerate(keys):
            self._fold(k, [a[i] for a in accs])

    def _fold(self, k, vals):
        cur = self.data.get(k)
        self.data[k] = vals if cur is None else [
            _MERGE2[c](old, v)
            for c, old, v in zip(self.spec.combiners, cur, vals)]

    def merge(self, other):
        if self.key_dtypes is None:
            self.key_dtypes = other.key_dtypes
        for k, vals in other.data.items():
            self._fold(k, vals)

    def split_by_key_hash(self, P):
        out = [DictMap(self.spec, self.key_dtypes) for _ in range(P)]
        for k, v in self.data.items():
            out[stable_key_hash(k) % P].data[k] = v
        return out

    def nbytes(self):
        return sum(np.asarray(v).nbytes
                   for vals in self.data.values() for v in vals)

    def emit(self):
        if not self.data:
            return None
        keys = list(self.data)
        out = {}
        for i, kn in enumerate(self.spec.key_names):
            col = keys if self.spec.n_keys == 1 else [k[i] for k in keys]
            out[kn] = np.array(col, dtype=self.key_dtypes[i])
        accs = [np.stack([np.asarray(vals[j]) for vals in
                          self.data.values()])
                for j in range(len(self.spec.combiners))]
        for name, fin in zip(self.spec.out_names, self.spec.finalize):
            if "/" in fin:
                i, j = map(int, fin.split("/"))
                out[name] = accs[i] / accs[j]
            else:
                out[name] = accs[int(fin)]
        return out


def _assert_same(got, ref):
    if ref is None:
        assert got is None
        return
    assert list(got.names) == list(ref)
    for name, r in ref.items():
        g = np.asarray(got[name])
        assert g.dtype == r.dtype, name
        assert g.shape == r.shape, name
        assert g.tobytes() == r.tobytes(), name
        assert g.flags.writeable, name


def _key_col(kind, rng, n):
    if kind == "int32":
        return rng.integers(-20, 20, n).astype(np.int32)
    if kind == "int64":
        return rng.integers(-2**40, 2**40, 12)[rng.integers(0, 12, n)]
    if kind == "float64":
        # -0.0 and 0.0 are one group; which one a group keeps is part of
        # what is compared
        pool = np.array([-0.0, 0.0, 1.5, -2.25, 1e300, 3.0])
        return pool[rng.integers(0, len(pool), n)]
    # S8 with trailing NULs: short values padded to the declared width
    pool = np.array([b"", b"a", b"ab", b"a\x00b", b"abcdefgh", b"zz"],
                    dtype="S8")
    return pool[rng.integers(0, len(pool), n)]


KEY_SETS = [("int32",), ("int64",), ("float64",), ("S8",),
            ("int32", "S8"), ("float64", "int64"),
            ("S8", "float64", "int32")]
# (combiners, finalize, value shapes): scalar and vector accumulators,
# sum/min/max, and the mean's "i/j"
ACC_SETS = [
    (("sum",), ("0",), [()]),
    (("min", "max"), ("0", "1"), [(), ()]),
    (("sum", "sum"), ("0/1", "1"), [(), "count"]),
    (("sum", "max", "min"), ("0", "1", "2"), [(3,), (2,), ()]),
]


def _batch(keys, accs, ints, rng, n):
    key_cols = [_key_col(k, rng, n) for k in keys]
    vals = []
    for shape, is_int in zip(accs[2], ints):
        if shape == "count":  # a mean's denominator
            v = np.ones(n, np.int64)
        elif is_int:
            v = rng.integers(-1000, 1000, (n,) + shape)
        else:
            v = rng.normal(0, 1e3, (n,) + shape)
            v[rng.random(n) < 0.2] = -0.0
        vals.append(v)
    return key_cols, vals


@pytest.mark.parametrize("P", [1, 3, 4])
@pytest.mark.parametrize("accs", ACC_SETS, ids=lambda a: ",".join(a[0]))
@pytest.mark.parametrize("keys", KEY_SETS, ids="-".join)
def test_columnar_map_matches_dict_reference(keys, accs, P):
    rng = np.random.default_rng(
        [P, KEY_SETS.index(keys), ACC_SETS.index(accs)])
    names = tuple(f"k{i}" for i in range(len(keys)))
    spec = AggSpec(key_names=names, combiners=accs[0], finalize=accs[1],
                   out_names=tuple(f"o{i}" for i in range(len(accs[1]))))
    # integer values for some sums (one dtype a column, as in a plan)
    ints = [c == "sum" and rng.random() < 0.5 for c in accs[0]]
    partials, refs = [], []
    for _ in range(P):  # one map per rank, several absorbs into each
        m, r = AggMap(spec), DictMap(spec)
        for n in rng.integers(0, 60, 3):
            key_cols, vals = _batch(keys, accs, ints, rng, int(n))
            m.absorb(key_cols, vals)
            r.absorb(key_cols, vals)
        _assert_same(m.emit(), r.emit())
        partials.append(m.split_by_key_hash(P))
        refs.append(r.split_by_key_hash(P))
    for dst in range(P):
        shares = [split[dst] for split in partials]
        final_ref = DictMap(spec)
        for share, ref in zip(shares, (split[dst] for split in refs)):
            _assert_same(share.emit(), ref.emit())
            assert share.nbytes() == ref.nbytes()
            final_ref.merge(ref)
        _assert_same(AggMap.merged(spec, shares).emit(), final_ref.emit())


def test_absorb_into_a_non_empty_map_folds_in_order():
    spec = AggSpec(("k",), ("sum", "max"), ("0", "1"), ("s", "m"))
    m, r = AggMap(spec), DictMap(spec)
    for keys, vals in [([3, 1, 3], [-0.0, 2.0, -0.0]),
                       ([2, 3], [5.0, 1e-300]),
                       ([1, 4], [0.1, 0.2])]:
        k, v = np.array(keys, np.int64), np.array(vals)
        m.absorb([k], [v, v])
        r.absorb([k], [v, v])
    _assert_same(m.emit(), r.emit())
    assert np.asarray(m.emit()["k"]).tolist() == [1, 3, 2, 4]


def test_merge_starts_from_each_groups_first_value():
    # a scatter never makes a -0.0 sum, but a merge must keep one that
    # it is handed: the fold starts from the first value, not from 0.0
    spec = AggSpec(("k",), ("sum", "min"), ("0", "1"), ("s", "m"))
    parts = [AggMap(spec, [np.array([1, 2])],
                    [np.array([-0.0, 1.0]), np.array([-0.0, 0.0])]),
             AggMap(spec, [np.array([2, 3])],
                    [np.array([-0.0, -0.0]), np.array([-0.0, 5.0])])]
    ref = DictMap(spec)
    for m in parts:
        part = DictMap(spec, [m.keys[0].dtype])
        for i, k in enumerate(m.keys[0].tolist()):
            part.data[k] = [a[i] for a in m.accs]
        ref.merge(part)
    got = AggMap.merged(spec, parts).emit()
    _assert_same(got, ref.emit())
    assert np.signbit(got["s"]).tolist() == [True, False, True]


@pytest.mark.parametrize("kinds", [("int64",), ("float64",), ("S8",),
                                   ("int32", "float64", "S8"),
                                   ("S8", "int64"), ("bool", "float64")])
def test_vectorised_key_hash_equals_stable_key_hash(kinds):
    rng = np.random.default_rng(5)
    n = 200
    cols = [rng.random(n) < 0.5 if k == "bool" else _key_col(k, rng, n)
            for k in kinds]
    before = METRICS.counter("agg.split.scalar_hash.total")
    got = relops._key_hash(cols)
    rows = (cols[0].tolist() if len(cols) == 1
            else list(zip(*(c.tolist() for c in cols))))
    assert got.dtype == np.int64
    assert got.tolist() == [stable_key_hash(k) for k in rows]
    # the split routes each group as the per-key hash did
    spec = AggSpec(tuple(f"k{i}" for i in range(len(cols))), ("sum",),
                   ("0",), ("s",))
    m = AggMap(spec)
    m.absorb(cols, [np.ones(n)])
    for p, part in enumerate(m.split_by_key_hash(3)):
        for h in relops._key_hash(part.keys).tolist():
            assert h % 3 == p
    assert METRICS.counter("agg.split.scalar_hash.total") == before


def test_object_keys_take_the_counted_scalar_hash():
    spec = AggSpec(("k",), ("sum",), ("0",), ("s",))
    m = AggMap(spec)
    m.absorb([np.array(["x", "yy", "x"])], [np.array([1.0, 2.0, 3.0])])
    before = METRICS.counter("agg.split.scalar_hash.total")
    parts = m.split_by_key_hash(2)
    assert METRICS.counter("agg.split.scalar_hash.total") == before + 1
    for p, part in enumerate(parts):
        assert all(stable_key_hash(k) % 2 == p
                   for k in np.asarray(part.keys[0]).tolist())


@pytest.mark.parametrize("key_dtype", ["int32", "S5"])
def test_wire_codec_round_trip_keeps_dtypes(key_dtype):
    rng = np.random.default_rng(3)
    keys = (rng.integers(0, 50, 300).astype(np.int32) if key_dtype == "int32"
            else np.array([b"ab", b"abcde", b"", b"x"], "S5")[
                rng.integers(0, 4, 300)])
    spec = AggSpec(("k", "j"), ("sum", "min", "sum"), ("0/2", "1"),
                   ("avg", "lo"))
    m = AggMap(spec)
    m.absorb([keys, rng.integers(0, 3, 300)],
             [rng.normal(size=300), rng.normal(size=(300, 2)),
              np.ones(300, np.int64)])
    back = decode_agg_map(encode_agg_map(m), spec)
    assert back.keys[0].dtype == np.dtype(key_dtype)
    for a, b in zip(m.keys + m.accs, back.keys + back.accs):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()
    emitted = m.emit()
    _assert_same(back.emit(), {n: np.asarray(emitted[n])
                               for n in emitted.names})
    # an empty partial never goes on the wire
    assert encode_agg_map(AggMap(spec)) is None
    assert any(encode_agg_map(p) is None for p in m.split_by_key_hash(64))


def test_worker_runtime_agg_byte_identical_to_local_many_groups():
    rng = np.random.default_rng(11)
    n = 6000
    dt = np.dtype([("k", np.int32), ("s", "S6"), ("v", np.float64)])
    recs = np.zeros(n, dt)
    recs["k"] = rng.integers(0, 1500, n)
    recs["s"] = np.array([b"a", b"bb", b"ccc"])[rng.integers(0, 3, n)]
    recs["v"] = rng.normal(0, 100, n)

    def q(ds):
        return ds.group_by("k", "s").agg(total=agg.sum("v"),
                                         hi=agg.max("v"),
                                         avg=agg.mean("v"),
                                         n=agg.count())

    results = []
    for kw in ({"num_partitions": 4},
               {"backend": "workers", "num_workers": 4}):
        sess = Session(**kw)
        results.append(q(sess.load("t", recs, type_name="AggT")).collect())
    local, workers = results
    assert len(np.asarray(local["k"])) >= 1000
    assert set(local) == set(workers)
    for col in local:
        x, y = np.asarray(local[col]), np.asarray(workers[col])
        assert x.dtype == y.dtype and x.tobytes() == y.tobytes(), col
    assert np.asarray(local["s"]).dtype == np.dtype("S6")


def test_merged_of_no_maps_is_empty():
    spec = AggSpec(("k",), ("sum",), ("0",), ("s",))
    m = AggMap.merged(spec, [AggMap(spec), AggMap(spec)])
    assert len(m) == 0 and m.emit() is None
    assert [len(p) for p in m.split_by_key_hash(3)] == [0, 0, 0]
