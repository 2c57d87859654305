"""Executor aggregation: device segment-reducer calls in the dense form (a
masked reduction over every row and slot, for few groups) per completed
query."""
from metrics._reduce_forms import calls_per_query


def read(run):
    return calls_per_query(run, "dense")
