"""Executor aggregation, per-group host loops: mean ms per traced query of
the ``agg:fold`` (reduced values into the map's dict) and ``agg:emit``
(the map to output columns) spans."""
from metrics._phases import phase_ms


def read(run):
    return phase_ms(run, ("agg:fold", "agg:emit"))
