"""The readers of the joins and their exchanges: each sums only its own
spans (``join_shuffle_ms`` only the exchanges inside JOIN op spans),
reads nothing without a traced query or without the spans, and
``shuffle_mb`` divides the window's counter difference by the completed
queries."""
import importlib
from types import SimpleNamespace

import pytest

MS = 10**6


def _reader(name):
    return importlib.import_module(f"metrics.{name}").read


def _span(sid, name, cat, dur_ms, parent=None):
    return SimpleNamespace(id=sid, rank=None, parent=parent, name=name,
                           cat=cat, t0=0, t1=dur_ms * MS,
                           dur_ns=dur_ms * MS)


def _spans(scale):
    """A two-join query's spans, each a distinct power of two of ms (so a
    sum names the spans it took)."""
    s = scale
    return [
        _span(0, "execute", "phase", 1000 * s),
        _span(1, "op0-5:APPLY+FILTER", "op", 1 * s, 0),
        _span(2, "op6:JOIN", "op", 2 * s, 0),
        _span(3, "x:shuffle:6:L", "exchange", 4 * s, 2),
        _span(4, "x:shuffle:6:R", "exchange", 8 * s, 2),
        _span(5, "join:build", "kernel", 16 * s, 2),
        _span(6, "join:probe", "kernel", 32 * s, 2),
        _span(7, "join:gather", "kernel", 64 * s, 2),
        _span(8, "op9:JOIN", "op", 128 * s, 0),
        _span(9, "x:bcast:9:build", "exchange", 256 * s, 8),
        _span(10, "join:probe", "kernel", 512 * s, 8),
        _span(11, "op12:AGG", "op", 1024 * s, 0),
        _span(12, "x:shuffle:12:partials", "exchange", 2048 * s, 11),
        _span(13, "agg:merge", "kernel", 4096 * s, 12),
        _span(14, "op13:SORT", "op", 8192 * s, 0),
        _span(15, "sort:select", "kernel", 16384 * s, 14),
    ]


WANT = {"join_op_ms": 2 + 128, "join_probe_ms": 16 + 32 + 64 + 512,
        "join_shuffle_ms": 4 + 8 + 256}


def _query(spans, ok=True, traced=True):
    return SimpleNamespace(ok=ok, trace=(SimpleNamespace(spans=spans)
                                         if traced else None))


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_sums_only_its_own_spans(metric):
    run = SimpleNamespace(queries=[_query(_spans(1)), _query(_spans(3)),
                                   _query(_spans(100), ok=False)])
    assert _reader(metric)(run) == pytest.approx(WANT[metric] * 2)


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_reads_nothing_without_its_spans(metric):
    assert _reader(metric)(SimpleNamespace(queries=[])) is None
    untraced = [_query(_spans(1), traced=False)]
    assert _reader(metric)(SimpleNamespace(queries=untraced)) is None
    # a program without joins: Q1's op, exchange and kernel spans
    q1 = [_span(0, "execute", "phase", 10),
          _span(1, "op1-8:APPLY+FILTER", "op", 1, 0),
          _span(2, "op9:AGG", "op", 2, 0),
          _span(3, "x:shuffle:9:partials", "exchange", 4, 2),
          _span(4, "agg:keys", "kernel", 8, 2)]
    assert _reader(metric)(SimpleNamespace(queries=[_query(q1)])) is None


def test_shuffle_mb_divides_the_window_difference_by_completed_queries():
    read = _reader("shuffle_mb")
    queries = [_query([], traced=False) for _ in range(4)]
    queries.append(_query([], ok=False, traced=False))
    run = SimpleNamespace(queries=queries, counters={
        "exchange.shuffle.bytes.total": 8_000_000,
        "shuffle.bytes.total": 10**12, "device.h2d.bytes.total": 5})
    assert read(run) == pytest.approx(2.0)


def test_shuffle_mb_reads_nothing_without_the_counter_or_a_query():
    read = _reader("shuffle_mb")
    done = [_query([], traced=False)]
    assert read(SimpleNamespace(queries=done, counters={
        "shuffle.bytes.total": 7})) is None
    assert read(SimpleNamespace(
        queries=[], counters={"exchange.shuffle.bytes.total": 5})) is None
