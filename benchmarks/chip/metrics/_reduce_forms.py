"""Calls of the device segment reducer per completed query, by the form
that the padded segment count chose (``dense`` or ``scatter``), from the
window's differences of the program's ``agg.device_reduce.<form>.total``
counters. A program that counts neither form reads nothing."""
from __future__ import annotations

FORMS = ("dense", "scatter")


def counter(form: str) -> str:
    return f"agg.device_reduce.{form}.total"


def calls_per_query(run, form: str):
    done = sum(q.ok for q in run.queries)
    if done == 0 or not any(counter(f) in run.counters for f in FORMS):
        return None
    return run.counters.get(counter(form), 0) / done
