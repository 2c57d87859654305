"""Per-partition relational operator kernels (paper §5.2, Appendix C/D).

Everything here operates on ONE partition's data — a vector list or a list
of vector-list batches — with no knowledge of where partitions live or how
they exchange data. The one op layer (:class:`~repro.core.executor
.Executor`, which the distributed worker runtime subclasses) calls these
kernels on every backend, so the backends differ only in partition
*placement* and *exchange*, never in operator semantics. That is what
makes byte-identical results across backends a structural
property rather than a testing accident.

Kernels:

* :func:`stage_eval` / :func:`batch_kernel` — the compiled pipeline stages
  (APPLY / FILTER / FLATTEN / HASH) over one vector-list batch;
* :func:`hash_col` — stable vectorized key hashing (drives both the HASH
  op and shuffle destinations);
* :func:`split_by_hash` / :func:`bucket_by_hash` — partition one batch,
  or one partition's batches, by ``hash % P`` (the shuffle kernel: what
  goes on the wire is decided here, identically for the in-memory and
  the transport exchange);
* :func:`probe_join` — sort-probe equi-join of two co-partitioned sides;
* :class:`AggMap` — PC's pre-aggregation map (a "combiner page"),
  columnar (key and accumulator arrays, folded, split, merged and
  emitted with vectorised numpy), generalized to multi-column keys and
  named multi-aggregate accumulators
  (:class:`AggSpec` parses the AGG op's plan); on the jax expression
  backend the per-batch reduction runs on device through
  :func:`device_segment_reducer` (one fused segment-reduce kernel);
* :func:`greedy_page_placement` — least-loaded-by-bytes page placement,
  shared by the local scan partitioner and ``dist.placement``;
* :func:`batch_topk` / :func:`merge_topk` — per-partition top-k and the
  global gather-merge;
* :func:`order_partition` / :func:`merge_ordered` — ``ORDER BY ... LIMIT``:
  per-partition pre-selection and the gather-merge, both in the total
  order :func:`order_select` fixes;
* :func:`count_shuffle` / :func:`exchange_span` — every exchange's byte
  accounting and span;
* :func:`assemble_output` — the OUTPUT contract (column concat in
  partition-then-batch order, row count, single-column write-back);
* :func:`concat_batches` / :func:`bytes_of` — glue.
"""
from __future__ import annotations

import contextlib
import struct
import threading
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.lambdas import METHOD_REGISTRY
from repro.core.tcap import TCAPOp
from repro.obs.metrics import METRICS
from repro.obs.trace import current, watch_compiles
from repro.objectmodel.vectorlist import VectorList

__all__ = [
    "AggMap", "AggSpec", "SHUFFLE_COUNTER", "assemble_output",
    "batch_kernel", "batch_topk", "bucket_by_hash", "bytes_of",
    "concat_batches", "count_shuffle", "device_segment_reducer",
    "exchange_span", "greedy_page_placement", "hash_col", "merge_ordered",
    "merge_topk", "order_partition", "order_select", "probe_join",
    "split_by_hash", "stable_key_hash", "stage_eval",
]

_FNV_OFFSET = 0xcbf29ce484222325
_FNV_PRIME = 0x100000001b3
_U64 = (1 << 64) - 1


_SPLITMIX_PRIME = 0xFF51AFD7ED558CCD  # == np.int64(-49064778989728563)


def _fnv1a(data: bytes) -> int:
    """FNV-1a 64-bit, folded into int64 range."""
    h = _FNV_OFFSET
    for byte in data:
        h = ((h ^ byte) * _FNV_PRIME) & _U64
    return h - (1 << 64) if h >= (1 << 63) else h


def _asr64(u: int, s: int) -> int:
    """Arithmetic shift right of a 64-bit two's-complement pattern held
    in an unsigned Python int (sign bit replicates, as numpy ``>>`` on
    int64 does)."""
    if u & (1 << 63):
        return ((u >> s) | ((_U64 << (64 - s)) & _U64)) & _U64
    return u >> s


def _splitmix64(u: int) -> int:
    """Scalar twin of :func:`hash_col`'s int64 mix — bit-identical to
    ``(x ^ (x >> 33)) * prime; x ^ (x >> 29)`` in wrapping int64
    arithmetic, folded into int64 range."""
    u &= _U64
    u = ((u ^ _asr64(u, 33)) * _SPLITMIX_PRIME) & _U64
    u ^= _asr64(u, 29)
    return u - (1 << 64) if u >= (1 << 63) else u


def stable_key_hash(k) -> int:
    """Process-independent scalar key hash, bit-identical per element to
    the vectorized :func:`hash_col` on a column of the same keys. Two
    properties hang off this:

    * Python salts built-in str/bytes hashing per process
      (PYTHONHASHSEED), which would route the same key to different
      destinations on independent worker processes — silently splitting
      groups and losing join matches under the socket transport's
      connect mode. Hence FNV-1a for bytes/str.
    * planlint's partitioning pass (PL201/PL202) elides exchanges when a
      stream is already placed by an equivalent routing: the AGG family
      routes by this function while the JOIN family routes by
      ``hash_col``, so the two must be the *same* hash or co-partitioned
      facts could never survive a hash-partition JOIN. int/bool take the
      splitmix64-style mix; floats hash their float64 bit pattern with
      ``-0.0`` normalized to ``+0.0`` (matching ``hash_col``) so equal
      keys co-route.
    """
    if isinstance(k, tuple):
        h = _FNV_OFFSET
        for item in k:
            h = ((h ^ (stable_key_hash(item) & _U64)) * _FNV_PRIME) & _U64
        return h - (1 << 64) if h >= (1 << 63) else h
    if isinstance(k, bytes):  # np.bytes_ is a bytes subclass
        return _fnv1a(k)
    if isinstance(k, str):    # np.str_ is a str subclass
        return _fnv1a(k.encode("utf-8", "surrogatepass"))
    if isinstance(k, (bool, np.bool_)) or isinstance(k, (int, np.integer)):
        return _splitmix64(int(k))
    if isinstance(k, (float, np.floating)):
        # the float64 bit pattern, with -0.0 -> +0.0 (hash_col adds 0.0
        # for the same normalization); NaNs hash by payload bits
        bits = struct.unpack("=q", struct.pack("=d", float(k) + 0.0))[0]
        return _splitmix64(bits)
    return hash(k)


def hash_col(col: np.ndarray) -> np.ndarray:
    """Stable vectorized key hashing (process-independent: shuffle
    routing derived from these values must agree across worker processes
    that share no hash salt)."""
    if col.dtype.kind in "biu":
        x = col.astype(np.int64, copy=True)
        x = (x ^ (x >> 33)) * np.int64(-49064778989728563)  # splitmix64-ish
        return x ^ (x >> 29)
    if col.dtype.kind == "f":
        # + 0.0 normalizes -0.0 to +0.0 before taking bits, so equal
        # float keys co-route (and match stable_key_hash's scalar path)
        return hash_col((col.astype(np.float64) + 0.0).view(np.int64))
    if col.dtype.kind == "S" and len(col):
        return _fnv1a_bytes_col(col)
    return np.fromiter((stable_key_hash(x) for x in col.tolist()),
                       np.int64, count=len(col))


def _fnv1a_bytes_col(col: np.ndarray) -> np.ndarray:
    """FNV-1a folded across a fixed-width bytes column, vectorized over
    rows (``itemsize`` numpy passes instead of a per-byte Python loop
    per element — the hot path for string-keyed shuffles). Bit-identical
    to ``stable_key_hash`` on each element: trailing NUL padding is
    excluded exactly the way ``.tolist()`` strips it, so an S8 and an
    S16 column holding the same logical key hash alike (join sides of
    different declared widths co-partition)."""
    w = col.dtype.itemsize
    mat = np.ascontiguousarray(col).view(np.uint8).reshape(len(col), w)
    rev_nonzero = mat[:, ::-1] != 0
    lengths = np.where(rev_nonzero.any(axis=1),
                       w - rev_nonzero.argmax(axis=1), 0)
    h = np.full(len(col), _FNV_OFFSET, dtype=np.uint64)
    prime = np.uint64(_FNV_PRIME)
    for j in range(w):
        # uint64 arithmetic wraps mod 2**64, matching the scalar fold
        h = np.where(j < lengths,
                     (h ^ mat[:, j].astype(np.uint64)) * prime, h)
    return h.view(np.int64)


def stage_eval(op: TCAPOp, cols: Sequence[np.ndarray],
               n_rows: int = 1) -> np.ndarray:
    t = op.info["type"]
    if t == "attAccess":
        return cols[0][op.info["attName"]]
    if t == "methodCall":
        fn = METHOD_REGISTRY[(op.info["onType"], op.info["methodName"])]
        return fn(cols[0])
    if t == "native":
        return op.info["fn"](*cols)
    if t == "const":
        n = len(cols[0]) if cols else n_rows
        return np.full(n, op.info["value"])
    if t == "rename":
        return cols[0]
    if t == "pack":
        # grouped-aggregation outputs chained into a downstream op: pack
        # the named columns into one structured record column, field order
        # = AGG output order (matches the synthesized group schema)
        names = op.info["fields"].split(",")
        arrs = [np.asarray(c) for c in cols]
        rec = np.zeros(len(arrs[0]), np.dtype(
            [(nm, a.dtype, a.shape[1:]) for nm, a in zip(names, arrs)]))
        for nm, a in zip(names, arrs):
            rec[nm] = a
        return rec
    if t in ("cmp", "bool", "arith"):
        o = op.info["op"]
        if o == "!":
            return np.logical_not(cols[0])
        a, b = cols
        return {
            "==": lambda: a == b, "!=": lambda: a != b,
            ">": lambda: a > b, ">=": lambda: a >= b,
            "<": lambda: a < b, "<=": lambda: a <= b,
            "&&": lambda: np.logical_and(a, b),
            "||": lambda: np.logical_or(a, b),
            "+": lambda: a + b, "-": lambda: a - b,
            "*": lambda: a * b, "/": lambda: a / b,
        }[o]()
    raise ValueError(f"unknown stage type {t}")


def _flatten(op: TCAPOp, vl: VectorList) -> VectorList:
    objcol = vl[op.apply_cols[0]]
    counts = np.fromiter((len(x) for x in objcol), np.int64,
                         count=len(objcol))
    out = VectorList()
    flat = (np.concatenate([np.asarray(x) for x in objcol])
            if counts.sum() else np.empty(0))
    out.append(op.out_cols[0], flat)
    for c in op.copy_cols:
        out.append(c, np.repeat(vl[c], counts))
    return out


def batch_kernel(op: TCAPOp) -> Callable[[VectorList], VectorList]:
    """The per-batch transform for a pipelined (non-exchange) TCAP op."""
    if op.op == "APPLY":
        if op.new_cols:
            return lambda vl: vl.extended(
                op.copy_cols, op.new_cols[0],
                stage_eval(op, [vl[c] for c in op.apply_cols],
                           vl.num_rows or 0))
        return lambda vl: vl.project(op.copy_cols)
    if op.op == "FILTER":
        return lambda vl: vl.filtered(
            np.asarray(vl[op.apply_cols[0]], bool), op.copy_cols)
    if op.op == "FLATTEN":
        return lambda vl: _flatten(op, vl)
    if op.op == "HASH":
        return lambda vl: vl.extended(
            op.copy_cols, op.new_cols[0],
            hash_col(np.asarray(vl[op.apply_cols[0]])))
    raise ValueError(f"{op.op} is not a per-batch pipelined op")


def split_by_hash(vl: VectorList, hash_name: str, P: int
                  ) -> List[Optional[VectorList]]:
    """Partition one batch by ``hash % P``; ``None`` where no rows land
    (nothing goes on the wire for that destination)."""
    h = np.asarray(vl[hash_name])
    dest = (h % P + P) % P
    out: List[Optional[VectorList]] = []
    for p in range(P):
        mask = dest == p
        out.append(vl.filtered(mask, vl.names) if mask.any() else None)
    return out


def bucket_by_hash(batches: Sequence[VectorList], hash_name: str, P: int
                   ) -> List[List[VectorList]]:
    """One partition's side of a hash shuffle: per destination, the rows
    of ``batches`` that route there (:func:`split_by_hash`), in batch
    order. Both exchanges split through this."""
    buckets: List[List[VectorList]] = [[] for _ in range(P)]
    for vl in batches:
        for p, sub in enumerate(split_by_hash(vl, hash_name, P)):
            if sub is not None:
                buckets[p].append(sub)
    return buckets


def probe_join(op: TCAPOp, lvl: VectorList, rvl: VectorList
               ) -> Optional[Tuple[VectorList, int]]:
    """Sort-probe equi-join of two co-partitioned sides; returns the joined
    batch and its row count, or ``None`` when either side is empty. Its
    three phases record ``join:build`` (ordering the build side's codes),
    ``join:probe`` (the searches and the row indices) and ``join:gather``
    (both sides' columns copied into the result)."""
    lh, rh = op.apply_cols[0], op.apply_cols2[0]
    if lvl.num_rows in (None, 0) or rvl.num_rows in (None, 0):
        return None
    rec = current()
    with rec.span("join:build", cat="kernel"):
        rcode = np.asarray(rvl[rh])
        order = np.argsort(rcode, kind="stable")
        rsorted = rcode[order]
    with rec.span("join:probe", cat="kernel"):
        lcode = np.asarray(lvl[lh])
        lo = np.searchsorted(rsorted, lcode, "left")
        hi = np.searchsorted(rsorted, lcode, "right")
        counts = hi - lo
        l_idx = np.repeat(np.arange(len(lcode)), counts)
        starts = np.repeat(lo, counts)
        within = np.arange(len(starts)) - np.repeat(
            np.cumsum(counts) - counts, counts)
        r_idx = order[starts + within]
    with rec.span("join:gather", cat="kernel"):
        res = VectorList()
        for c in op.copy_cols:
            res.append(c, np.asarray(lvl[c])[l_idx])
        for c in op.copy_cols2:
            res.append(c, np.asarray(rvl[c])[r_idx])
    return res, len(l_idx)


# ------------------------------------------------------------ aggregation
_COMBINE = {
    "sum": lambda acc, inv, vals, n: _scatter_add(acc, inv, vals, n),
    "max": lambda acc, inv, vals, n: _scatter_minmax(acc, inv, vals, n,
                                                     np.maximum),
    "min": lambda acc, inv, vals, n: _scatter_minmax(acc, inv, vals, n,
                                                     np.minimum),
}

# the pairwise combine of accumulated values, applied in row order (the
# map merge, and an absorb into a non-empty map)
_MERGE_AT = {"sum": np.add.at, "max": np.maximum.at, "min": np.minimum.at}


def sum_acc_dtype(dtype: np.dtype) -> np.dtype:
    """Accumulator dtype of a ``sum`` over values of ``dtype``: floats
    widen to float64, bools widen to int64 (summing an indicator counts
    it — ``np.add.at`` on a bool accumulator would saturate at True),
    other integers keep their dtype. Single source for the host scatter,
    the device reducer, and the group-schema synthesis."""
    if dtype.kind == "f":
        return np.result_type(dtype, np.float64)
    if dtype.kind == "b":
        return np.dtype(np.int64)
    return dtype


def _scatter_add(acc, inv, vals, n):
    if acc is None:
        acc = np.zeros((n,) + vals.shape[1:], dtype=sum_acc_dtype(vals.dtype))
    np.add.at(acc, inv, vals)
    return acc


def _scatter_minmax(acc, inv, vals, n, fn):
    init = -np.inf if fn is np.maximum else np.inf
    if acc is None:
        acc = np.full((n,) + vals.shape[1:], init, dtype=np.float64)
    fn.at(acc, inv, vals)
    return acc


@dataclass(frozen=True)
class AggSpec:
    """The parsed plan of one generalized AGG op: which output columns are
    keys, the combiner of every accumulator column, and how accumulators
    finalize into the named outputs (``"i"`` emits accumulator *i*;
    ``"i/j"`` divides — the mean composite)."""

    key_names: Tuple[str, ...]
    combiners: Tuple[str, ...]
    finalize: Tuple[str, ...]
    out_names: Tuple[str, ...]

    @classmethod
    def from_op(cls, op: TCAPOp) -> "AggSpec":
        nk = int(op.info["nkeys"])
        return cls(key_names=tuple(op.out_cols[:nk]),
                   combiners=tuple(op.info["combiners"].split(",")),
                   finalize=tuple(op.info["finalize"].split(",")),
                   out_names=tuple(op.out_cols[nk:]))

    @property
    def n_keys(self) -> int:
        return len(self.key_names)

    def key_cols(self, op: TCAPOp) -> Tuple[str, ...]:
        return op.apply_cols[:self.n_keys]

    def acc_cols(self, op: TCAPOp) -> Tuple[str, ...]:
        return op.apply_cols[self.n_keys:]


def _sortable(c: np.ndarray) -> np.ndarray:
    """``c`` as an array that sorts and compares like it, fast: an
    ``S1``/``S2``/``S4``/``S8`` column as a big-endian unsigned view
    (lexicographic bytes == big-endian integer order, and integer argsort
    is ~2x faster than the generic string compare loop); any other
    column as itself."""
    if c.dtype.kind == "S" and c.dtype.itemsize in (1, 2, 4, 8):
        return c.view(f">u{c.dtype.itemsize}")
    return c


def _col_unique(c: np.ndarray):
    """``np.unique(..., return_inverse=True)`` over :func:`_sortable`'s
    view. The unique values are viewed back, so callers always see the
    original dtype."""
    u, inv = np.unique(_sortable(c), return_inverse=True)
    return u.view(c.dtype), inv


def _unique_keys(key_cols: Sequence[np.ndarray]):
    """(unique key columns, inverse index) for one partition's rows: one
    array per key column in the source dtype, the groups in lexicographic
    key order. Multi-key grouping runs per-column integer coding — one
    cheap ``np.unique`` per column, combined into one int64 code — which
    is ~4x faster than a structured-array sort and yields the identical
    lexicographic group order (the combined code sorts by (code0, code1,
    ...) = per-column sorted order). Every backend runs exactly this
    function, so group order is deterministic by construction. Falls back
    to the structured sort when the code space could overflow int64."""
    if len(key_cols) == 1:
        uniq, inv = _col_unique(np.asarray(key_cols[0]))
        return [uniq], inv
    cols = [np.asarray(c) for c in key_cols]
    uniqs, codes, space = [], [], 1
    if all(c.ndim == 1 for c in cols):
        for c in cols:
            u, code = _col_unique(c)
            uniqs.append(u)
            codes.append(code)
            space *= max(len(u), 1)  # python int: overflow-safe check
    if uniqs and space < (1 << 62):
        combined = codes[0].astype(np.int64)
        for u, code in zip(uniqs[1:], codes[1:]):
            combined = combined * len(u) + code
        ucomb, inv = np.unique(combined, return_inverse=True)
        parts = []
        idx = ucomb
        for u in reversed(uniqs[1:]):
            parts.append(idx % len(u))
            idx = idx // len(u)
        parts.append(idx)
        parts.reverse()
        return [u[i] for u, i in zip(uniqs, parts)], inv
    packed = np.empty(len(cols[0]), dtype=np.dtype(
        [(f"k{i}", c.dtype, c.shape[1:]) for i, c in enumerate(cols)]))
    for i, c in enumerate(cols):
        packed[f"k{i}"] = c
    uniq, inv = np.unique(packed, return_inverse=True)
    return [uniq[f"k{i}"].copy() for i in range(len(cols))], inv


def _first_occurrence(key_cols: Sequence[np.ndarray]):
    """``(first, inv)`` for rows grouped by equal key tuples, the groups
    in the order of their first row: ``first[g]`` is group *g*'s first
    row (ascending), ``inv`` maps every row to its group. This is a
    dict's insertion order over the rows, and the first row's key is the
    one a dict keeps (``-0.0`` and ``0.0`` are one group)."""
    c = np.asarray(key_cols[0])
    code = (_sortable(c) if len(key_cols) == 1 and c.ndim == 1
            else _unique_keys(key_cols)[1])
    # return_index sorts stably: each group's index is its first row
    _, first, inv = np.unique(code, return_index=True, return_inverse=True)
    order = np.argsort(first)
    rank = np.empty_like(order)
    rank[order] = np.arange(len(order))
    return first[order], rank[inv]


def _key_hash(key_cols: Sequence[np.ndarray]) -> np.ndarray:
    """``stable_key_hash`` of every row's key, bit-identical: one key
    column hashes as :func:`hash_col`; several fold their ``hash_col``
    values with FNV-1a in wrapping uint64 arithmetic, as
    ``stable_key_hash`` folds a tuple. Columns ``hash_col`` hashes one
    element at a time (object, unicode, ...) count
    ``agg.split.scalar_hash.total``."""
    hashes = []
    for c in key_cols:
        if c.dtype.kind not in "biufS":
            METRICS.inc("agg.split.scalar_hash.total")
        hashes.append(hash_col(c))
    if len(hashes) == 1:
        return hashes[0]
    h = np.full(len(hashes[0]), _FNV_OFFSET, dtype=np.uint64)
    prime = np.uint64(_FNV_PRIME)
    for x in hashes:
        h = (h ^ x.view(np.uint64)) * prime
    return h.view(np.int64)


def _writable(a: np.ndarray) -> np.ndarray:
    return a if a.flags.writeable and a.flags.c_contiguous else a.copy()


class AggMap:
    """A pre-aggregation map (the per-thread PC ``Map`` on a combiner page),
    generalized to multi-column keys and multiple named accumulators.

    Columnar: ``keys`` holds one array per key column in the source dtype,
    ``accs`` one ``(n_groups, ...)`` array per accumulator column of the
    AGG op, row *g* of each being group *g*. Group order is insertion
    order everywhere (absorb batches in batch order, merge peers in rank
    order) — both exchanges preserve it, which is what keeps final AGG
    output ordering identical across backends.
    """

    def __init__(self, spec: AggSpec,
                 keys: Sequence[np.ndarray] = (),
                 accs: Sequence[np.ndarray] = ()):
        self.spec = spec
        self.keys: List[np.ndarray] = list(keys)
        self.accs: List[np.ndarray] = list(accs)
        self._keys_span = None

    def __len__(self) -> int:
        """The number of groups."""
        return len(self.keys[0]) if self.keys else 0

    def absorb(self, key_cols: Sequence[np.ndarray],
               val_cols: Sequence[np.ndarray],
               reducer: Optional[Callable] = None) -> None:
        """Fold one batch in: group rows by key, scatter-combine every
        accumulator column. ``reducer`` (the jax segment-reduce kernel)
        replaces the numpy scatter for the per-batch reduction when set;
        it receives ``(inv, n_groups, val_arrays)`` and must return one
        ``(n_groups, ...)`` array per accumulator — or ``None`` to decline
        (non-numeric dtypes), falling back to numpy."""
        rec = current()
        # absorb_batches hands over its open agg:keys span, so that one
        # span covers the concatenation and the grouping
        keys_span, self._keys_span = self._keys_span, None
        with keys_span or rec.span("agg:keys", cat="kernel"):
            if len(np.asarray(key_cols[0])) == 0:
                return
            keys, inv = _unique_keys(key_cols)
        n = len(keys[0])
        vals = [np.asarray(v) for v in val_cols]
        # the device reducer records its own agg:put and agg:wait spans
        accs = reducer(inv, n, vals) if reducer is not None else None
        if accs is None:
            with rec.span("agg:scatter", cat="kernel"):
                accs = [_COMBINE[comb](None, inv, v, n)
                        for comb, v in zip(self.spec.combiners, vals)]
        with rec.span("agg:fold", cat="kernel"):
            part = AggMap(self.spec, keys, accs)
            folded = AggMap.merged(self.spec, [self, part])
            self.keys, self.accs = folded.keys, folded.accs

    def absorb_batches(self, batches: Sequence[VectorList],
                       key_cols: Sequence[str],
                       acc_cols: Sequence[str],
                       reducer: Optional[Callable] = None) -> None:
        """One absorb over a partition's concatenated rows — a single
        group discovery + one (fused, possibly on-device) scatter per
        partition. The op layer pre-aggregates every partition through
        exactly this method, so the float association order (row order
        within the partition) is identical on every backend."""
        if not batches:
            return
        with contextlib.ExitStack() as stack:
            stack.enter_context(current().span("agg:keys", cat="kernel"))
            keys = [np.concatenate([np.asarray(vl[c]) for vl in batches])
                    for c in key_cols]
            vals = [np.concatenate([np.asarray(vl[c]) for vl in batches])
                    for c in acc_cols]
            self._keys_span = stack.pop_all()
        self.absorb(keys, vals, reducer=reducer)

    @classmethod
    def merged(cls, spec: AggSpec, parts: Sequence["AggMap"]) -> "AggMap":
        """The maps ``parts`` merged in list order: the groups in order of
        first occurrence over the parts' groups; each accumulator starts
        from its first occurrence's value (so a lone ``-0.0`` passes
        through unchanged) and folds the later ones in pairwise, in order
        — bit-identical to folding the parts into a dict entry by entry."""
        parts = [m for m in parts if m]
        if not parts:
            return cls(spec)
        if len(parts) == 1:
            return cls(spec, parts[0].keys, parts[0].accs)
        keys = [np.concatenate(c) for c in zip(*(m.keys for m in parts))]
        first, inv = _first_occurrence(keys)
        rest = np.ones(len(inv), bool)
        rest[first] = False
        accs = []
        for comb, cols in zip(spec.combiners, zip(*(m.accs for m in parts))):
            vals = np.concatenate(cols)
            acc = vals[first]
            _MERGE_AT[comb](acc, inv[rest], vals[rest])
            accs.append(acc)
        return cls(spec, [k[first] for k in keys], accs)

    def split_by_key_hash(self, P: int) -> List["AggMap"]:
        """Partition this map's groups by ``stable_key_hash(key) % P``
        (the AGG shuffle kernel — process-independent, so connect-mode
        workers with different hash salts route each key identically),
        computed for all keys at once by :func:`_key_hash`; group order
        is preserved within each split."""
        if not self:
            return [AggMap(self.spec) for _ in range(P)]
        dest = _key_hash(self.keys) % P
        order = np.argsort(dest, kind="stable")
        bounds = np.searchsorted(dest[order], np.arange(P + 1))
        out = []
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            idx = order[lo:hi]
            out.append(AggMap(self.spec, [k[idx] for k in self.keys],
                              [a[idx] for a in self.accs]))
        return out

    def nbytes(self) -> int:
        """Accumulator payload size (what an AGG partial puts on the wire
        in the local simulation's accounting)."""
        return sum(a.nbytes for a in self.accs)

    def emit(self) -> Optional[VectorList]:
        """The final AGG output batch for this partition (``None`` if the
        partition holds no groups): key columns, then every named output
        finalized from its accumulator(s); every column writable."""
        if not self:
            return None
        out = VectorList()
        for name, k in zip(self.spec.key_names, self.keys):
            out.append(name, _writable(k))
        for name, fin in zip(self.spec.out_names, self.spec.finalize):
            if "/" in fin:
                i, j = map(int, fin.split("/"))
                out.append(name, self.accs[i] / self.accs[j])
            else:
                out.append(name, _writable(self.accs[int(fin)]))
        return out


# --------------------------------------- device (jax) segment reduction
# bounded FIFO of jitted segment kernels, keyed by (combiners, dtypes,
# pow2 rows, pow2 segs); cleared together with the exprc kernel LRU
# (exprc.reset_kernel_cache calls reset_segment_kernels). Lock-guarded:
# thread-backend workers hit the reducer concurrently.
_SEG_KERNELS: Dict[Tuple, Callable] = {}
_SEG_KERNELS_CAP = 64
_SEG_LOCK = threading.Lock()
# the largest padded segment count reduced in the dense form; above it
# the scatter form. The dense form's work grows with rows x segs, a TPU's
# scatter is a serial loop over rows whatever segs is: on a TPU v5e, at
# 2**21 rows of Q1's 11 columns, the dense form is the faster up to 8192
# slots and the slower from 16384 (benchmarks/sweep_segment_reduce.py).
_DENSE_SEGS_MAX = 8192


def reset_segment_kernels() -> None:
    with _SEG_LOCK:
        _SEG_KERNELS.clear()


def _pow2(n: int) -> int:
    return max(8, 1 << max(0, int(n - 1).bit_length()))


def _segment_kernel(combiners: Tuple[str, ...],
                    acc_dtypes: Sequence[np.dtype], segs: int) -> Callable:
    """The jitted ``segment_reduce(inv, *vals)``: every accumulator column
    reduced into ``segs`` slots in one device program; a row whose ``inv``
    is out of range (the padding carries ``segs``) is dropped. Its form
    follows ``segs``: up to ``_DENSE_SEGS_MAX`` a masked dense reduction
    over all rows and slots (``where(inv == slot, v, 0)`` summed, or
    ``v``/``±inf`` under min/max), which the device runs in parallel;
    above it a ``.at[inv].add/min/max`` scatter."""
    import jax
    import jax.numpy as jnp

    dense = segs <= _DENSE_SEGS_MAX

    def segment_reduce(inv_d, *vals_d):
        if dense:
            # slots fit in int32, so the compare of every row with every
            # slot skips the emulated 64-bit one
            hit = (inv_d.astype(jnp.int32)[:, None]
                   == jnp.arange(segs, dtype=jnp.int32))
        outs = []
        for comb, v, dt in zip(combiners, vals_d, acc_dtypes):
            v = v.astype(dt)
            init = {"sum": 0, "max": -jnp.inf, "min": jnp.inf}[comb]
            if dense:
                m = hit.reshape(hit.shape + (1,) * (v.ndim - 1))
                masked = jnp.where(m, v[:, None], jnp.asarray(init, dt))
                red = {"sum": jnp.sum, "max": jnp.max, "min": jnp.min}[comb]
                outs.append(red(masked, axis=0).astype(dt))
            else:
                acc = jnp.full((segs,) + v.shape[1:], init, dt)
                red = {"sum": acc.at[inv_d].add, "max": acc.at[inv_d].max,
                       "min": acc.at[inv_d].min}[comb]
                outs.append(red(v, mode="drop"))
        return tuple(outs)

    return jax.jit(segment_reduce)


def device_segment_reducer(combiners: Tuple[str, ...],
                           force: bool = False) -> Optional[Callable]:
    """The fused on-device pre-aggregation for ``expr_backend="jax"``: one
    jitted kernel (:func:`_segment_kernel`) reducing every accumulator
    column of a partition in a single call, under ``enable_x64`` with
    accumulator dtypes matching the host scatters (f64 floats, i64
    integers and bools). Group discovery (``np.unique``) stays on host —
    it is what fixes the deterministic key order — only the reduction
    itself runs on device. Rows and segment counts are padded to
    power-of-two buckets (padded rows carry ``inv = segs`` and land in no
    slot) so XLA retraces O(log²) times, not once per partition shape.

    The padded segment count alone picks the kernel's form: at most
    ``_DENSE_SEGS_MAX`` slots, a masked dense reduction (a tree over rows,
    parallel on the device); more, a scatter (on a TPU a serial loop over
    rows). Each call counts one ``agg.device_reduce.dense.total`` or
    ``agg.device_reduce.scatter.total``.

    Integer and count sums, min and max are order-free, so both forms
    give them bit-identical to the host scatters. Float sums: the dense
    form adds in a tree, not in the host's row order, and a TPU emulates
    f64 and orders its scatter as it likes, so float sums may differ
    from the host's in the last bits: within 1e-12 relative in the CPU's
    dense form (test-pinned); for TPC-H Q1 on a TPU v5e about 1e-14 in
    the dense form, 3e-11 in the scatter form. The CPU's scatter form
    accumulates in row order and is bit-identical (test-pinned).

    Like the physical planner's broadcast decision, the offload must win
    on modeled cost: XLA's *CPU* scatter is ~50x slower per element than
    ``np.add.at``, so on a CPU-only jax backend this returns ``None`` and
    pre-aggregation stays on the host scatters (set ``force=True`` — or
    ``REPRO_AGG_DEVICE=1`` in the environment — to offload regardless;
    the equivalence tests do, to pin down the device path's results). On
    an accelerator backend the device path engages by default.

    The returned reducer itself returns ``None`` per call for non-numeric
    value dtypes (caller falls back to the numpy scatter)."""
    import os

    import jax

    if (not (force or os.environ.get("REPRO_AGG_DEVICE") == "1")
            and jax.default_backend() == "cpu"):
        return None
    watch_compiles()

    def reducer(inv: np.ndarray, n: int, vals: List[np.ndarray]):
        if any(v.dtype.kind not in "biuf" or v.dtype.names is not None
               for v in vals):
            return None
        acc_dtypes = [sum_acc_dtype(v.dtype) if c == "sum"
                      else np.dtype(np.float64)
                      for c, v in zip(combiners, vals)]
        rows, segs = _pow2(len(inv)), _pow2(n)
        key = (combiners, tuple(str(d) for d in acc_dtypes),
               tuple((str(v.dtype), v.shape[1:]) for v in vals),
               rows, segs)
        with _SEG_LOCK:
            kern = _SEG_KERNELS.get(key)
        if kern is None:
            kern = _segment_kernel(combiners, acc_dtypes, segs)
            with _SEG_LOCK:
                while len(_SEG_KERNELS) >= _SEG_KERNELS_CAP:
                    _SEG_KERNELS.pop(next(iter(_SEG_KERNELS)))
                _SEG_KERNELS[key] = kern
        form = "dense" if segs <= _DENSE_SEGS_MAX else "scatter"
        METRICS.inc(f"agg.device_reduce.{form}.total")
        rec = current()
        with rec.span("agg:put", cat="kernel"):
            inv_p = np.full(rows, segs, np.int64)
            inv_p[:len(inv)] = inv
            vals_p = []
            for v in vals:
                vp = np.zeros((rows,) + v.shape[1:], v.dtype)
                vp[:len(v)] = v
                vals_p.append(vp)
            METRICS.inc("device.h2d.bytes.total",
                        inv_p.nbytes + sum(vp.nbytes for vp in vals_p))
            with jax.enable_x64(True):
                outs = kern(inv_p, *vals_p)
        with rec.span("agg:wait", cat="kernel"):
            return [np.asarray(o)[:n] for o in outs]

    return reducer


# ------------------------------------------------------------------ top-k
def batch_topk(op: TCAPOp, vl: VectorList
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-batch top-k: the local pre-selection before the gather-merge."""
    k = int(op.info["k"])
    scol, pcol = op.apply_cols
    s = np.asarray(vl[scol])
    idx = np.argsort(-s, kind="stable")[:k]
    return s[idx], np.asarray(vl[pcol])[idx]


def merge_topk(op: TCAPOp, best_s: Sequence[np.ndarray],
               best_p: Sequence[np.ndarray]) -> Optional[VectorList]:
    """Gather-merge of per-batch top-k candidates (concatenation order is
    the tie-break, so callers must append in partition-then-batch order)."""
    if not best_s:
        return None
    k = int(op.info["k"])
    s = np.concatenate(list(best_s))
    p = np.concatenate(list(best_p))
    idx = np.argsort(-s, kind="stable")[:k]
    return VectorList({"score": s[idx], "payload": p[idx]})


# --------------------------------------------------------------- order by
def order_select(rec: np.ndarray, keys: Sequence[Tuple[str, bool]],
                 limit: int) -> np.ndarray:
    """The first ``limit`` records of the structured array ``rec`` in the
    order of ``keys``, each ``(field, descending)``. Ties on every listed
    key are broken by the record's other fields in schema order, each
    ascending, so which rows are kept and their order depend only on the
    rows: never on the backend, the runtime, the partitioning or the
    batches. NaN sorts above every number (last ascending, first
    descending); a vector field compares element by element."""
    listed = {f for f, _ in keys}
    rest = [(f, False) for f in rec.dtype.names if f not in listed]
    cols = []  # most significant first
    for f, descending in (*keys, *rest):
        c = rec[f]
        for part in ([c] if c.ndim == 1 else c.reshape(len(c), -1).T):
            if descending:
                # ranks order every dtype alike, bytes included
                part = -np.unique(part, return_inverse=True)[1].reshape(-1)
            cols.append(part)
    return rec[np.lexsort(cols[::-1])[:limit]]


def _order_spec(op: TCAPOp) -> Tuple[Tuple[Tuple[str, bool], ...], int]:
    keys = op.info["keys"].split(",")
    desc = [d == "1" for d in op.info["desc"].split(",")]
    return tuple(zip(keys, desc)), int(op.info["limit"])


def order_partition(op: TCAPOp, batches: Sequence[VectorList]
                    ) -> Optional[np.ndarray]:
    """A SORT op's pre-selection over one partition's batches (span
    ``sort:select``): its first ``limit`` records, or ``None`` for a
    partition without batches."""
    if not batches:
        return None
    with current().span("sort:select", cat="kernel"):
        rec = np.concatenate([np.asarray(vl[op.apply_cols[0]])
                              for vl in batches])
        return order_select(rec, *_order_spec(op))


def merge_ordered(op: TCAPOp, candidates: Sequence[np.ndarray]
                  ) -> Optional[VectorList]:
    """A SORT op's gather-merge of the partitions' pre-selections (span
    ``sort:merge``): the first ``limit`` records overall, unpacked into
    one column per field, named as the op's output columns."""
    if not candidates:
        return None
    with current().span("sort:merge", cat="kernel"):
        rec = order_select(np.concatenate(list(candidates)),
                           *_order_spec(op))
        return VectorList({f: np.ascontiguousarray(rec[f])
                           for f in op.out_cols})


# --------------------------------------------------------------- exchange
#: the process-wide count of the bytes exchanges put on the wire
SHUFFLE_COUNTER = "exchange.shuffle.bytes.total"


def count_shuffle(stats, nbytes: int) -> None:
    """Account ``nbytes`` an exchange moved: the query's
    ``stats.shuffle_bytes`` and, in the process that moved them,
    :data:`SHUFFLE_COUNTER`."""
    stats.shuffle_bytes += nbytes
    METRICS.inc(SHUFFLE_COUNTER, nbytes)


@contextlib.contextmanager
def exchange_span(kind: str, tag: str, stats):
    """The span ``x:{kind}:{tag}`` (category ``exchange``) of one exchange,
    its ``bytes`` the shuffle bytes counted inside it."""
    sb0 = stats.shuffle_bytes
    with current().span(f"x:{kind}:{tag}", cat="exchange", tag=tag) as sp:
        yield
    sp.set(bytes=stats.shuffle_bytes - sb0)


# ----------------------------------------------------------------- output
def assemble_output(op: TCAPOp, batches: Sequence[VectorList], stats,
                    store, write_outputs: bool) -> Dict[str, np.ndarray]:
    """The OUTPUT contract, shared by both backends: concatenate the
    projected columns (callers pass batches in partition-then-batch
    order), record ``rows_output``, and persist a single packed column
    under the OUTPUT set name when write-back is on."""
    cols: Dict[str, List[np.ndarray]] = {c: [] for c in op.apply_cols}
    for vl in batches:
        for c in op.apply_cols:
            cols[c].append(np.asarray(vl[c]))
    out = {c: (np.concatenate(v) if v else np.empty(0))
           for c, v in cols.items()}
    stats.rows_output = len(next(iter(out.values()))) if out else 0
    set_name = op.info["set"]
    if len(out) == 1 and write_outputs:
        rec = next(iter(out.values()))
        if set_name not in store.sets and rec.dtype != object:
            store.send_data(set_name, rec)
    return out


# -------------------------------------------------------------- placement
def greedy_page_placement(page_bytes: Sequence[int], P: int) -> List[int]:
    """Destination partition per page: each page (in storage order) goes to
    the currently least-loaded-by-bytes partition, ties broken by lowest
    rank. With equal-size pages this degenerates to exactly the old
    round-robin ``i % P``; with skewed page sizes it keeps byte loads
    balanced. Shared by the local simulation's ``Executor._scan`` and the
    distributed ``dist.placement`` so the two backends always shard
    identically — byte-identical results stay a structural property."""
    loads = [0] * P
    dest: List[int] = []
    for sz in page_bytes:
        w = min(range(P), key=lambda i: loads[i])
        dest.append(w)
        loads[w] += int(sz)
    return dest


# ------------------------------------------------------------------- glue
def concat_batches(batches: Sequence[VectorList]) -> VectorList:
    """The batches' rows in order, as one batch: one concatenation per
    column (copying pairwise would copy the rows once per batch)."""
    batches = list(batches)
    if len(batches) <= 1:
        return batches[0] if batches else VectorList()
    return VectorList({c: np.concatenate([np.asarray(b[c]) for b in batches])
                       for c in batches[0].names})


def bytes_of(vl: VectorList) -> int:
    total = 0
    for _, c in vl.items():
        arr = np.asarray(c)
        total += arr.nbytes if arr.dtype != object else len(arr) * 64
    return total
