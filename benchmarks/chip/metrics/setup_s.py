"""Set-up time: process start, data from the seed, loading, and one
warm-up of every query the window sends (compiles included)."""


def read(run):
    return run.setup_s
