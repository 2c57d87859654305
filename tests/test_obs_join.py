"""Spans inside the join and the SORT op, and the shuffle byte counter.

What must hold, locally and on thread workers:

* ``join:build``, ``join:probe`` and ``join:gather`` are category
  ``kernel`` and sit under a JOIN op span; with the JOIN's exchange
  spans they cover at least 95% of the JOIN op spans' time on a sizeable
  hash-partition join;
* ``sort:select`` and ``sort:merge`` sit under the SORT op span;
* ``exchange.shuffle.bytes.total`` moves by exactly the query's
  ``stats.shuffle_bytes`` (join shuffles, broadcasts, AGG partials,
  gathers);
* with tracing off nothing is recorded;
* one fixed query (a hash or broadcast join, an AGG with partials and an
  ORDER BY ... LIMIT) records exactly the span tree and moves exactly the
  shuffle bytes in ``golden_op_layer.json``, locally and on two thread
  workers: the op layer is shared, and only the exchange differs.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from repro.core import Session, agg
from repro.core.relops import SHUFFLE_COUNTER
from repro.obs import METRICS, NULL
from repro.objectmodel.schema import Field, record

Fact = record("JFact", {"k": Field(np.int64), "v": Field(np.float64),
                        "pad": Field(np.dtype("S40"))})
Dim = record("JDim", {"key": Field(np.int64), "w": Field(np.int64)})
JOIN_PHASES = ("join:build", "join:probe", "join:gather")


def _tables(n_fact=200_000, n_dim=50_000, seed=7):
    rng = np.random.default_rng(seed)
    fact = np.zeros(n_fact, Fact.dtype)
    fact["k"] = rng.integers(0, n_dim, n_fact)
    fact["v"] = rng.random(n_fact)
    dim = np.zeros(n_dim, Dim.dtype)
    dim["key"] = np.arange(n_dim)
    dim["w"] = rng.integers(0, 9, n_dim)
    return fact, dim


def _backends():
    yield pytest.param({"num_partitions": 4}, id="local")
    yield pytest.param({"backend": "workers", "num_workers": 4,
                        "worker_kind": "thread"}, id="thread")


def _query(sess, fact, dim):
    f = sess.load("fact", fact, Fact)
    d = sess.load("dim", dim, Dim)
    return (f.join(d, on=lambda a, b: a.k == b.key)
            .filter(lambda p: p.w > 2)
            .group_by("w").agg(total=agg.sum("v"), n=agg.count())
            .order_by(("total", "desc")).limit(3))


def _ancestors(trace, sp):
    by_id = {(s.rank, s.id): s for s in trace.spans}
    out = []
    while sp.parent is not None:
        sp = by_id[(sp.rank, sp.parent)]
        out.append(sp)
    return out


def _covered(spans):
    """The length of the union of the spans' intervals."""
    total, end = 0, None
    for t0, t1 in sorted((sp.t0, sp.t1) for sp in spans):
        if end is None or t0 > end:
            total += t1 - t0
            end = t1
        elif t1 > end:
            total += t1 - end
            end = t1
    return total


@pytest.mark.parametrize("kw", _backends())
def test_join_and_sort_spans_sit_under_their_op(kw):
    fact, dim = _tables(n_fact=5000, n_dim=500)
    sess = Session(trace=True, broadcast_threshold_bytes=0, **kw)
    _query(sess, fact, dim).collect()
    trace = sess.last_trace
    names = {sp.name for sp in trace.spans}
    assert set(JOIN_PHASES) | {"sort:select", "sort:merge"} <= names
    for sp in trace.spans:
        if not sp.name.startswith(("join:", "sort:")):
            continue
        assert sp.cat == "kernel"
        ops = [a.name for a in _ancestors(trace, sp) if a.cat == "op"]
        assert len(ops) == 1
        assert ops[0].endswith(":JOIN" if sp.name.startswith("join:")
                               else ":SORT")


@pytest.mark.parametrize("kw", _backends())
def test_join_phases_and_exchanges_cover_the_join_op(kw):
    fact, dim = _tables()
    sess = Session(trace=True, broadcast_threshold_bytes=0, **kw)
    _query(sess, fact, dim).collect()
    trace = sess.last_trace
    by_id = {(s.rank, s.id): s for s in trace.spans}
    joins = [sp for sp in trace.spans if sp.cat == "op"
             and sp.name.endswith(":JOIN")]
    assert joins
    inner = {(j.rank, j.id): [] for j in joins}
    for sp in trace.spans:
        if sp.name in JOIN_PHASES or sp.cat == "exchange":
            for a in _ancestors(trace, sp):
                if (a.rank, a.id) in inner:
                    inner[(a.rank, a.id)].append(sp)
                    break
    op_ns = sum(j.dur_ns for j in joins)
    covered = sum(_covered(inner[(j.rank, j.id)]) for j in joins)
    assert covered >= 0.95 * op_ns
    assert all(by_id[(sp.rank, sp.parent)] for sp in joins)


@pytest.mark.parametrize("threshold", [0, 2 << 30],
                         ids=["hash_partition", "broadcast"])
@pytest.mark.parametrize("kw", _backends())
def test_shuffle_counter_moves_with_the_stats(kw, threshold):
    fact, dim = _tables(n_fact=20_000, n_dim=2000)
    sess = Session(broadcast_threshold_bytes=threshold, **kw)
    q = _query(sess, fact, dim)
    before = METRICS.counter(SHUFFLE_COUNTER)
    q.collect()
    moved = METRICS.counter(SHUFFLE_COUNTER) - before
    assert sess.last_stats.shuffle_bytes > 0
    assert moved == sess.last_stats.shuffle_bytes


def test_tracing_off_records_nothing():
    fact, dim = _tables(n_fact=5000, n_dim=500)
    sess = Session(num_partitions=4, broadcast_threshold_bytes=0)
    traced = Session(num_partitions=4, broadcast_threshold_bytes=0,
                     trace=True)
    got = _query(sess, fact, dim).collect()
    want = _query(traced, fact, dim).collect()
    assert sess.last_trace is None and NULL.spans == []
    for c in want:
        np.testing.assert_array_equal(got[c], want[c])


GOLDEN = json.loads(
    (Path(__file__).parent / "golden_op_layer.json").read_text())
GOLDEN_RUNTIMES = {"local": {"num_partitions": 4},
                   "thread": {"backend": "workers", "num_workers": 2,
                              "worker_kind": "thread"}}
GOLDEN_THRESHOLDS = {"hash_partition": 0, "broadcast": 2 << 30}


@pytest.mark.parametrize("case", list(GOLDEN))
def test_span_shape_and_shuffle_bytes_match_golden(case):
    runtime, algo = case.split("-")
    fact, dim = _tables(n_fact=20_000, n_dim=2000)
    sess = Session(trace=True, broadcast_threshold_bytes=GOLDEN_THRESHOLDS[
        algo], **GOLDEN_RUNTIMES[runtime])
    _query(sess, fact, dim).collect()
    assert sess.last_stats.shuffle_bytes == GOLDEN[case]["shuffle_bytes"]
    assert ([list(s) for s in sess.last_trace.shape()]
            == GOLDEN[case]["shape"])
