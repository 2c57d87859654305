"""Joins and exchanges: MB (1e6 bytes) exchanges moved between
partitions per completed query (the window's difference of the program's
``exchange.shuffle.bytes.total`` counter: join shuffles and broadcasts,
AGG partials)."""
from metrics._joins import shuffle_mb as read  # noqa: F401
