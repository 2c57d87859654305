"""Host-device transfer, read-back: mean ms per traced query of the
``stage:wait`` and ``agg:wait`` spans (waiting for the device program and
downloading its outputs)."""
from metrics._phases import phase_ms


def read(run):
    return phase_ms(run, ("stage:wait", "agg:wait"))
