"""Closed loop, one client: each query starts when the one before it
ends. No query starts after the window; one already started runs to its
end, and the window then ends with it."""
from __future__ import annotations

import time

import numpy as np


def schedule(workload: dict, specs: list, rng: np.random.Generator,
             seconds: float) -> list:
    """A long cycle of the query specs: every block is one permutation of
    all of them, so each seed runs the same set in another order."""
    n_blocks = 256
    return [specs[i] for _ in range(n_blocks)
            for i in rng.permutation(len(specs))]


def run(ctx) -> list:
    out = []
    t0 = time.monotonic_ns()
    end = t0 + int(ctx.seconds * 1e9)
    for spec in ctx.schedule:
        now = time.monotonic_ns()
        if now >= end:
            break
        out.append(ctx.execute(spec, due=now))
    ctx.window = (t0, max([q.done for q in out], default=t0))
    return out
