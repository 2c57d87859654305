"""Joins and exchanges, the join proper: mean ms per traced query of the
``join:build`` (ordering the build side's codes), ``join:probe`` (the
searches and row indices) and ``join:gather`` (both sides' columns into
the result) spans."""
from metrics._joins import join_phase, per_query_ms


def read(run):
    return per_query_ms(run, join_phase)
