"""The roofline readers divide the declared work by the device time that
fell inside the query's op spans, read from the trace."""
from types import SimpleNamespace

import pytest

from metrics import _lib


def _span(name, t0, t1, cat="op"):
    return SimpleNamespace(name=name, cat=cat, t0=t0, t1=t1,
                           dur_ns=t1 - t0)


def _run(busy):
    spans = [_span("query", 0, 1000, "query"),
             _span("op0-9:SCAN,APPLY", 100, 400),
             _span("op10:AGG", 400, 900),
             _span("x:shuffle:10:partials", 800, 900, "exchange")]
    q = SimpleNamespace(ok=True, trace=SimpleNamespace(spans=spans),
                        work={"agg_bytes": 819, "scan_bytes": 1638})
    return SimpleNamespace(queries=[q], device={"busy": busy},
                           peak={"hbm_bytes_per_s": 819e9})


def test_roofline_over_device_time_inside_the_spans():
    # 1 ns of work at peak each 819 B; device busy 300-600 (300 ns),
    # of which 200 ns inside the AGG span and 300 ns inside any op span
    run = _run({"/device:TPU:0": [[50, 80], [300, 600], [950, 990]]})
    assert _lib.roofline(run, "agg_bytes", lambda n: "AGG" in n) == \
        pytest.approx(100.0 * 1 / 200)
    assert _lib.roofline(run, "scan_bytes", lambda n: True) == \
        pytest.approx(100.0 * 2 / 300)


def test_roofline_reads_nothing_without_device_time_in_the_spans():
    run = _run({"/device:TPU:0": [[0, 90], [950, 990]]})
    assert _lib.roofline(run, "agg_bytes", lambda n: "AGG" in n) is None
    run.device = None
    assert _lib.roofline(run, "agg_bytes", lambda n: "AGG" in n) is None
