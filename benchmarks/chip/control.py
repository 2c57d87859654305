#!/usr/bin/env python3
"""The control of a cell's check: the plain reference put in the system's
place and computed one precision lower than the configuration states
(float32 for float64), then compared like any answer of a run.

For every query a run can send — each tenant, template and parameter set
of the seed — the float32 answer is held against the float64 reference,
and the run's readings are printed beside the cell's limits. A sound
limit lets the system pass and fails this control.

Usage, from the root of a checkout::

    python3 benchmarks/chip/control.py --workload q1_sf1 --seeds 1 2 3
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
LOWER = {"float64": np.float32}


def readings(cell, seed: int) -> dict:
    """The control's readings for one seed at the configuration's size."""
    import harness
    from check import combine, compare
    conf, wl = cell.config, cell.workload
    gen = harness.load_module(HERE / "data" / f"{conf['generator']}.py",
                              "bench_data")
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1 << 20]))
    params = {m["template"]: harness.draw_params(m, rng) for m in wl["mix"]}
    low = LOWER[conf["float_precision"]]
    out = []
    for t in range(conf["tenants"]):
        cols = gen.columns(gen.generate(conf["scale_factor"],
                                        np.random.SeedSequence([seed, t])))
        for name, plist in params.items():
            mod = harness.load_module(HERE / "queries" / f"{name}.py",
                                      f"bench_q_{name}")
            ref = mod.references(cols, plist, np.float64)
            ctl = mod.references(cols, plist, low)
            out += [compare(c, r, mod.KEYS) for c, r in zip(ctl, ref)]
            if hasattr(mod, "groups"):
                out.append(compare(mod.groups(cols, low),
                                   mod.groups(cols, np.float64), mod.KEYS))
    return combine(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]
    import harness
    cell = harness.load_cell(args.workload)
    limits = cell.workload["limits"]
    failed_all = True
    for seed in args.seeds:
        r = readings(cell, seed)
        fails = [k for k in r if r[k] > limits[k]]
        failed_all &= bool(fails)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "readings": r, "limits": limits,
                          "fails": fails}))
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
