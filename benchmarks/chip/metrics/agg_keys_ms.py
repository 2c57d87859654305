"""Executor aggregation, group discovery: mean ms per traced query of the
``agg:keys`` spans (a partition's rows concatenated, ``np.unique``)."""
from metrics._phases import phase_ms


def read(run):
    return phase_ms(run, ("agg:keys",))
