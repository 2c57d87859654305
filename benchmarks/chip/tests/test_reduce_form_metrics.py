"""The readers of the segment reducer's form counters: calls in each form
over the window, per completed query; nothing from a program that counts
neither form."""
import importlib
from types import SimpleNamespace

import pytest


def _read(name, counters, done=2, failed=1):
    queries = ([SimpleNamespace(ok=True)] * done
               + [SimpleNamespace(ok=False)] * failed)
    run = SimpleNamespace(queries=queries, counters=counters)
    return importlib.import_module(f"metrics.{name}").read(run)


def test_each_form_per_completed_query():
    counters = {"agg.device_reduce.dense.total": 8,
                "agg.device_reduce.scatter.total": 2,
                "device.h2d.bytes.total": 10**9}
    assert _read("agg_dense_calls", counters) == pytest.approx(4.0)
    assert _read("agg_scatter_calls", counters) == pytest.approx(1.0)


def test_a_form_never_taken_reads_zero():
    counters = {"agg.device_reduce.scatter.total": 8}
    assert _read("agg_dense_calls", counters) == 0
    assert _read("agg_scatter_calls", counters) == pytest.approx(4.0)


@pytest.mark.parametrize("name", ["agg_dense_calls", "agg_scatter_calls"])
def test_nothing_without_the_counters_or_a_completed_query(name):
    assert _read(name, {"device.h2d.bytes.total": 5}) is None
    assert _read(name, {"agg.device_reduce.dense.total": 4}, done=0) is None
