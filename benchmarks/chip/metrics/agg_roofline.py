"""Kernels: the least time of the AGG's declared work (rows into the AGG x
(aggregate terms x 8 B + key bytes)) at HBM peak, as a share of the
device time (profiler trace) that fell inside the AGG op spans."""
from metrics._lib import roofline


def read(run):
    return roofline(run, "agg_bytes", lambda n: "AGG" in n)
