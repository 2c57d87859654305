#!/usr/bin/env python3
"""Device time of the segment reducer's two forms over the segment count.

Builds ``relops._segment_kernel`` for TPC-H Q1's eleven accumulator columns
(seven float64 and four int64 sums) once in the dense form and once in the
scatter form at every power-of-two ``segs`` in ``--segs``, reduces one
partition of ``--rows`` padded rows already on the device, and times each
call from launch to ``block_until_ready`` (median of ``--repeat``, after
one warm-up call that compiles). The form is chosen by setting
``relops._DENSE_SEGS_MAX`` around the build, as the reducer would for
that many slots. Both forms' answers are compared with the host's
float64 sums (``np.bincount``): integer sums must be equal (else the exit
code is 1), float sums report their largest relative difference. Prints
one JSON line per ``segs``.

    python3 benchmarks/sweep_segment_reduce.py              # on the chip
    JAX_PLATFORMS=cpu python3 benchmarks/sweep_segment_reduce.py \\
        --rows 4096 --segs 8 64 --allow-cpu                   # rehearsal
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import relops  # noqa: E402

# Q1's accumulators in the order its AGG declares them: four sums, three
# means (a sum and a count each), one count
Q1_ACC = ("f8", "f8", "f8", "f8", "f8", "i8", "f8", "i8", "f8", "i8", "i8")


def _inputs(rows: int, segs: int, rng: np.random.Generator):
    """One padded partition: 5% of the rows are padding (``inv = segs``),
    the rest spread evenly over the slots."""
    live = rows - rows // 20
    inv = np.full(rows, segs, np.int64)
    inv[:live] = rng.integers(0, segs, live)
    vals = [rng.uniform(1, 1e5, rows) if d == "f8"
            else rng.integers(0, 60, rows) for d in Q1_ACC]
    return inv, vals


def _time_form(segs: int, dense: bool, args, repeat: int):
    saved = relops._DENSE_SEGS_MAX
    relops._DENSE_SEGS_MAX = segs if dense else segs - 1
    try:
        kern = relops._segment_kernel(("sum",) * len(Q1_ACC),
                                      [np.dtype(d) for d in Q1_ACC], segs)
    finally:
        relops._DENSE_SEGS_MAX = saved
    with jax.enable_x64(True):
        t0 = time.perf_counter()
        outs = jax.block_until_ready(kern(*args))
        compile_s = time.perf_counter() - t0
        times = []
        for _ in range(repeat):
            t0 = time.perf_counter()
            jax.block_until_ready(kern(*args))
            times.append(time.perf_counter() - t0)
    return [np.asarray(o) for o in outs], statistics.median(times), compile_s


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, default=1 << 21)
    ap.add_argument("--segs", type=int, nargs="+",
                    default=[1 << k for k in range(3, 15)])
    ap.add_argument("--repeat", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="run on a CPU backend (a rehearsal: no timing "
                         "it prints is a device time)")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        print(f"no TPU (platform {dev.platform!r})", file=sys.stderr)
        return 1
    rng = np.random.default_rng(args.seed)
    ok = True
    for segs in args.segs:
        inv, vals = _inputs(args.rows, segs, rng)
        with jax.enable_x64(True):
            on_dev = [jax.device_put(a) for a in [inv] + vals]
        dense, dense_s, dense_c = _time_form(segs, True, on_dev, args.repeat)
        scat, scat_s, scat_c = _time_form(segs, False, on_dev, args.repeat)
        # the host's float64 sums; an empty slot reads 0 everywhere
        ref = [np.bincount(inv, v, minlength=segs + 1)[:segs] for v in vals]
        tiny = np.finfo(np.float64).tiny
        line = {"rows": args.rows, "segs": segs,
                "dense_ms": dense_s * 1e3, "scatter_ms": scat_s * 1e3,
                "dense_compile_s": dense_c, "scatter_compile_s": scat_c,
                "device": {"platform": dev.platform,
                           "kind": dev.device_kind}}
        for form, outs in (("dense", dense), ("scatter", scat)):
            line[f"{form}_ints_equal"] = all(
                np.array_equal(o, r.astype(np.int64))
                for o, r, d in zip(outs, ref, Q1_ACC) if d == "i8")
            line[f"{form}_float_rel_err"] = max(
                float(np.max(np.abs(o - r) / np.maximum(np.abs(r), tiny)))
                for o, r, d in zip(outs, ref, Q1_ACC) if d == "f8")
            ok = ok and line[f"{form}_ints_equal"]
        print(json.dumps(line), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
