"""Device: the share of the traced window in which no operation ran on the
chip (1 - union of the XLA Ops intervals / window)."""
from metrics._lib import idle_pct as read  # noqa: F401
