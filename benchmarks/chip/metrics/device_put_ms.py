"""Host-device transfer, upload: mean ms per traced query of the
``stage:put`` and ``agg:put`` spans (padding, upload and launch of the
fused-stage core and of the segment reducer)."""
from metrics._phases import phase_ms


def read(run):
    return phase_ms(run, ("stage:put", "agg:put"))
