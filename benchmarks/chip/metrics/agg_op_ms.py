"""Executor, aggregation: mean ms per query of the op spans whose label
contains ``AGG`` (AggMap group discovery and merge, segment reduction)."""
from metrics._lib import agg_ms as read  # noqa: F401
