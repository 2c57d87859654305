"""Fused stages: mean ms per query of the op spans whose label lacks
``AGG`` (scan, fused filter and expression stages, output)."""
from metrics._lib import stage_ms as read  # noqa: F401
