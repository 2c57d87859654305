"""Joins and exchanges: mean ms per traced query of the op spans whose
label has ``JOIN`` (the build and probe, and the exchanges they hold)."""
from metrics._joins import join_op, per_query_ms


def read(run):
    return per_query_ms(run, join_op)
