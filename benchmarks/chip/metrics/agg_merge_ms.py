"""Executor aggregation, the partials' exchange: mean ms per traced query
of the ``agg:split`` (``split_by_key_hash``) and ``agg:merge`` spans."""
from metrics._phases import phase_ms


def read(run):
    return phase_ms(run, ("agg:split", "agg:merge"))
