"""The readers of the spans inside the op spans and of the host-to-device
byte counter: each sums only its own spans, reads nothing without a
traced query or without the spans, and ``h2d_mb`` divides the window's
counter difference by the completed queries."""
import importlib
from types import SimpleNamespace

import pytest

READS = {
    "agg_keys_ms": ("agg:keys",),
    "agg_fold_ms": ("agg:fold", "agg:emit"),
    "agg_merge_ms": ("agg:split", "agg:merge"),
    "stage_host_ms": ("stage:pre", "stage:post"),
    "device_put_ms": ("stage:put", "agg:put"),
    "device_wait_ms": ("stage:wait", "agg:wait"),
}
PHASES = sorted({n for names in READS.values() for n in names}
                | {"agg:scatter", "compile"})


def _reader(name):
    return importlib.import_module(f"metrics.{name}").read


def _span(name, dur_ns, cat="kernel"):
    return SimpleNamespace(name=name, cat=cat, t0=0, t1=dur_ns,
                           dur_ns=dur_ns)


def _query(spans, ok=True, traced=True):
    return SimpleNamespace(ok=ok, trace=(SimpleNamespace(spans=spans)
                                         if traced else None))


def _phase_spans(scale):
    """One span of every phase, each a distinct power of two of ms (so a
    sum names the spans it took), under op spans of their own."""
    spans = [_span("op1-8:APPLY+FILTER", 10**9, "op"),
             _span("op9:AGG", 10**9, "op"),
             _span("x:shuffle:9:partials", 10**9, "exchange")]
    for k, name in enumerate(PHASES):
        spans.append(_span(name, scale * (2 ** k) * 10**6))
    return spans


def _ms(names, scale):
    return sum(scale * 2 ** PHASES.index(n) for n in names)


@pytest.mark.parametrize("metric", sorted(READS))
def test_reader_sums_only_its_own_spans(metric):
    run = SimpleNamespace(queries=[_query(_phase_spans(1)),
                                   _query(_phase_spans(3)),
                                   _query(_phase_spans(100), ok=False)])
    want = (_ms(READS[metric], 1) + _ms(READS[metric], 3)) / 2
    assert _reader(metric)(run) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(READS))
def test_reader_reads_nothing_without_its_spans(metric):
    # no traced query, then traced queries of a program that records
    # only op and exchange spans (the spans of the same name in another
    # category do not count)
    assert _reader(metric)(SimpleNamespace(queries=[])) is None
    untraced = [_query(_phase_spans(1), traced=False)]
    assert _reader(metric)(SimpleNamespace(queries=untraced)) is None
    ops_only = [_query([sp for sp in _phase_spans(1) if sp.cat != "kernel"]
                       + [_span(n, 10**6, "op") for n in READS[metric]])]
    assert _reader(metric)(SimpleNamespace(queries=ops_only)) is None


def test_reader_counts_a_traced_query_without_the_phase_as_zero():
    run = SimpleNamespace(queries=[_query(_phase_spans(2)),
                                   _query([_span("agg:keys", 0)])])
    assert _reader("stage_host_ms")(run) == pytest.approx(
        _ms(READS["stage_host_ms"], 2) / 2)


def test_h2d_mb_divides_the_window_difference_by_completed_queries():
    read = _reader("h2d_mb")
    queries = [_query([], ok=True, traced=False) for _ in range(3)]
    queries.append(_query([], ok=False, traced=False))
    run = SimpleNamespace(queries=queries,
                          counters={"device.h2d.bytes.total": 6_000_000,
                                    "shuffle.bytes.total": 10**12})
    assert read(run) == pytest.approx(2.0)


def test_h2d_mb_reads_nothing_without_the_counter_or_a_query():
    read = _reader("h2d_mb")
    done = [_query([], traced=False)]
    assert read(SimpleNamespace(queries=done, counters={})) is None
    assert read(SimpleNamespace(
        queries=[], counters={"device.h2d.bytes.total": 5})) is None
    failed = [_query([], ok=False, traced=False)]
    assert read(SimpleNamespace(
        queries=failed, counters={"device.h2d.bytes.total": 5})) is None
