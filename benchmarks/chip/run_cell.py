#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result.

Usage, from the root of a checkout::

    python3 benchmarks/chip/run_cell.py --workload q1_sf1 --seed 7 \\
        --seconds 51 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics, read from a profiler trace
and the system's spans), ``device``, with ``--trace 1`` a ``breakdown``,
and last ``check``: each number compared with its limit. Exits 1 with no
result when JAX finds no TPU or fewer chips than the cell asks for.

JAX's persistent compilation cache lives in ``<checkout>/.jax_cache``
unless ``JAX_COMPILATION_CACHE_DIR`` is set.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
# every program the run compiles is kept, however quickly it compiled
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
sys.path[:0] = [str(HERE), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    cell = harness.load_cell(args.workload)
    try:
        harness.device_info(cell.entry["chips"])
    except harness.NoDevice as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         t_start=T_START)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
