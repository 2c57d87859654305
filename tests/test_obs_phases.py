"""Spans inside the AGG and the fused stages, the host-to-device byte
counter, compile spans and counts, and profiler annotations.

On the jax expression backend with the device segment reducer forced on
(``REPRO_AGG_DEVICE=1``), what must hold:

* every sub-op span (``agg:*``, ``stage:*``, ``compile``) is category
  ``kernel`` and sits under the op span whose work it times — never in a
  category that the op readers or the analyze table account (``op``,
  ``wait``, ``plan``, ``driver``), and the op spans themselves are
  unchanged, locally and on thread workers;
* ``device.h2d.bytes.total`` counts the padded bytes of both uploads;
* a new power-of-two bucket records a ``compile`` span and counts in
  ``jax.compiles.total``; a warm repeat records and counts nothing;
* tracing off records nothing and changes no answer;
* a ``jax.profiler`` trace holds one annotation per span, in order.
"""
from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

from repro.core import Session, agg
from repro.core.exprc import _bucket, reset_kernel_cache
from repro.core.relops import _pow2
from repro.obs import METRICS, NULL, watch_compiles

EMP_DT = np.dtype([("dept", np.int64), ("salary", np.int64)])
STAGE = "op1-8:APPLY+APPLY+APPLY+FILTER+APPLY+APPLY+APPLY+APPLY"
AGG = "op9:AGG"
# the op spans of the query below, as the executor recorded them before
# any sub-op span existed
OPS = ["op0:SCAN", STAGE, AGG, "op10:OUTPUT"]
STAGE_PHASES = ("stage:pre", "stage:put", "stage:wait", "stage:post")
AGG_PHASES = ("agg:keys", "agg:put", "agg:wait", "agg:fold",
              "agg:split", "agg:merge", "agg:emit")
ACCOUNTED = ("op", "wait", "plan", "driver")


@pytest.fixture(autouse=True)
def _device_reducer(monkeypatch):
    monkeypatch.setenv("REPRO_AGG_DEVICE", "1")


def _emps(n=600, seed=3):
    rng = np.random.default_rng(seed)
    out = np.zeros(n, EMP_DT)
    out["dept"] = rng.integers(0, 8, n)
    out["salary"] = rng.integers(1, 1000, n)
    return out


def _query(sess, rows=_emps()):
    return (sess.load("emps", rows, type_name="Emp")
            .filter(lambda e: e.salary > 100)
            .group_by("dept")
            .agg(total=agg.sum("salary"), n=agg.count()))


def _backends():
    yield pytest.param({}, id="local")
    yield pytest.param({"backend": "workers", "num_workers": 2}, id="thread")


def _traced(kw, **extra):
    sess = Session(expr_backend="jax", trace=True, **kw, **extra)
    answer = _query(sess).collect()
    return sess.last_trace, answer


def _ancestors(trace, sp):
    by_id = {(s.rank, s.id): s for s in trace.spans}
    out = []
    while sp.parent is not None:
        sp = by_id[(sp.rank, sp.parent)]
        out.append(sp)
    return out


def _delta(before, name):
    return METRICS.counter(name) - before.get(name, 0)


# ------------------------------------------------------------ span shape
@pytest.mark.parametrize("kw", _backends())
def test_sub_op_spans_sit_under_their_op(kw):
    trace, _ = _traced(kw)
    names = {sp.name for sp in trace.spans}
    assert set(STAGE_PHASES + AGG_PHASES) <= names
    for sp in trace.spans:
        if sp.name not in STAGE_PHASES + AGG_PHASES + ("compile",):
            continue
        assert sp.cat == "kernel" and sp.cat not in ACCOUNTED
        ops = [a.name for a in _ancestors(trace, sp) if a.cat == "op"]
        if sp.name.startswith("stage:"):
            assert ops == [STAGE]
        elif sp.name.startswith("agg:"):
            assert ops == [AGG]
        else:  # a compile lands under the op that launched it
            assert ops in ([STAGE], [AGG])


@pytest.mark.parametrize("kw", _backends())
def test_op_spans_unchanged_by_the_sub_op_spans(kw):
    trace, _ = _traced(kw)
    by_id = {(s.rank, s.id): s for s in trace.spans}
    ranks = trace.ranks() or [None]
    for r in ranks:
        ops = [(sp.name, by_id[(r, sp.parent)].name) for sp in trace.spans
               if sp.rank == r and sp.cat == "op"]
        parent = "execute" if r is None else "worker"
        assert ops == [(name, parent) for name in OPS]
    # the numpy backend runs the same plan with no stage phases or device
    # reduction: every span outside category kernel matches it
    ref = Session(expr_backend="numpy", trace=True, **kw)
    _query(ref).collect()

    def outside(t):
        return [row for row, sp in zip(t.shape(), t.spans)
                if sp.cat != "kernel"]

    assert outside(trace) == outside(ref.last_trace)


def test_one_span_per_partition_and_phase_not_per_group():
    rows = _emps(n=5000)
    sess = Session(expr_backend="jax", trace=True, num_partitions=4,
                   vector_rows=512)
    _query(sess, rows).collect()
    t = sess.last_trace
    count = {n: len(t.find(n)) for n in STAGE_PHASES + AGG_PHASES}
    batches = -(-len(rows) // 512)
    # one stage phase per batch; group discovery, upload, read-back and
    # fold once per partition that holds rows; one split per partial and
    # one merge and one emit per final partition — never one per group
    # (8 groups)
    assert {count[n] for n in STAGE_PHASES} == {batches}
    held = count["agg:keys"]
    assert 1 <= held <= 4
    assert [count[n] for n in ("agg:put", "agg:wait", "agg:fold")] == \
        [held] * 3
    assert count["agg:split"] == count["agg:merge"] == \
        count["agg:emit"] == 4


# ---------------------------------------------------- host-to-device bytes
@pytest.mark.parametrize("kw", _backends())
def test_h2d_counter_counts_padded_uploads(kw):
    rows = _emps()
    kept = int((rows["salary"] > 100).sum())
    # the fused stage uploads two int64 columns (salary and the constant
    # 100) padded to the batch's bucket; the reducer uploads the int64
    # inverse index and two int64 accumulator inputs (salary, the count's
    # ones) padded to the partition's bucket
    want = _bucket(len(rows)) * 2 * 8 + _pow2(kept) * 3 * 8
    sess = Session(expr_backend="jax", **kw)
    ds = _query(sess, rows)
    ds.collect()  # compiles outside the measured run
    before = METRICS.snapshot()["counters"]
    ds.collect()
    assert _delta(before, "device.h2d.bytes.total") == want == 40960


# --------------------------------------------------------------- compiles
def test_new_bucket_compiles_and_warm_repeat_does_not():
    reset_kernel_cache()
    watch_compiles()
    sess = Session(expr_backend="jax", trace=True)
    small = _query(sess, _emps(n=600))
    small.collect()  # buckets of 1024 rows
    big = _query(sess, _emps(n=3000, seed=4))
    before = METRICS.snapshot()["counters"]
    big.collect()  # new buckets: 4096 rows
    compiles = sess.last_trace.find("compile")
    assert compiles and all(sp.cat == "kernel" for sp in compiles)
    assert all(sp.dur_ns > 0 and sp.attrs.get("fun") for sp in compiles)
    assert _delta(before, "jax.compiles.total") == len(compiles)
    assert _delta(before, "jax.compile_ms.total") > 0
    before = METRICS.snapshot()["counters"]
    big.collect()
    assert sess.last_trace.find("compile") == []
    assert _delta(before, "jax.compiles.total") == 0


def test_kernel_compile_span_is_gone():
    reset_kernel_cache()
    trace, _ = _traced({})
    assert trace.find("compile")  # this run compiled
    assert trace.find("kernel:compile") == []


def test_compile_listener_registers_once():
    import jax._src.monitoring as monitoring
    from repro.obs.trace import _on_duration
    for _ in range(3):
        watch_compiles()
    assert monitoring.get_event_duration_listeners().count(
        _on_duration) == 1


# ------------------------------------------------------------ tracing off
@pytest.mark.parametrize("kw", _backends())
def test_tracing_off_records_nothing_and_answers_match(kw):
    sess = Session(expr_backend="jax", **kw)
    off = _query(sess).collect()
    assert sess.last_trace is None and NULL.spans == []
    _, on = _traced(kw)
    assert list(off) == list(on)
    for k in off:
        assert np.asarray(off[k]).tobytes() == np.asarray(on[k]).tobytes()


# --------------------------------------------------- profiler annotations
def _host_events(log_dir: Path, names):
    from jax.profiler import ProfileData
    pb = sorted(log_dir.rglob("*.xplane.pb"))[-1]
    data = ProfileData.from_file(str(pb))
    return sorted(((e.start_ns, e.name) for plane in data.planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name in names), key=lambda ev: ev[0])


def test_profiler_trace_holds_the_spans_in_order(tmp_path):
    import jax
    sess = Session(expr_backend="jax", trace=True)
    ds = _query(sess)
    ds.collect()  # warm: the profiled run compiles nothing
    jax.profiler.start_trace(str(tmp_path))
    try:
        ds.collect()
    finally:
        jax.profiler.stop_trace()
    spans = sess.last_trace.spans
    names = {sp.name for sp in spans}
    got = [n for _, n in _host_events(tmp_path, names)]
    assert got == [sp.name for sp in sorted(spans, key=lambda s: s.t0)]
    assert STAGE in got and "agg:wait" in got


def test_profiler_trace_untouched_with_tracing_off(tmp_path):
    import jax
    sess = Session(expr_backend="jax")
    ds = _query(sess)
    ds.collect()
    jax.profiler.start_trace(str(tmp_path))
    try:
        ds.collect()
    finally:
        jax.profiler.stop_trace()
    names = {"query", "execute", STAGE, AGG} | set(STAGE_PHASES + AGG_PHASES)
    assert _host_events(tmp_path, names) == []
