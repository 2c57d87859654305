"""Lineitem rows scanned per second over the window (closed loop: to the
end of its last query)."""
from metrics._lib import rows_rate as read  # noqa: F401
