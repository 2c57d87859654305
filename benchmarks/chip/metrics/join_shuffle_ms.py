"""Joins and exchanges, the exchanges of the joins: mean ms per traced
query of the ``x:shuffle:*`` and ``x:bcast:*`` spans inside JOIN op spans
(split by key hash, routing, concatenation)."""
from metrics._joins import join_exchange, per_query_ms


def read(run):
    return per_query_ms(run, join_exchange)
