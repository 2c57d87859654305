"""Fused stages, host work: mean ms per traced query of the ``stage:pre``
(prologue: column extraction from the records) and ``stage:post``
(epilogue and mask compaction) spans."""
from metrics._phases import phase_ms


def read(run):
    return phase_ms(run, ("stage:pre", "stage:post"))
