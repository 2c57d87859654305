"""Executor aggregation: device segment-reducer calls in the scatter form
(for many groups) per completed query."""
from metrics._reduce_forms import calls_per_query


def read(run):
    return calls_per_query(run, "scatter")
