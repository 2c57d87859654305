"""Kernels: the least time to read the base columns the template reads
(rows x their bytes) at HBM peak, as a share of the device time
(profiler trace) that fell inside all the query's op spans."""
from metrics._lib import roofline


def read(run):
    return roofline(run, "scan_bytes", lambda n: True)
