"""Benchmark orchestrator — one entry per paper table/figure. Prints
``name,us_per_call,derived`` CSV; ``--json out.json`` also writes a
machine-readable report (git rev, timestamp, per-row parsed ``k=v``
derived fields) for tracking results across commits.

  Table 2  -> bench_linalg       (lilLinAlg: gram / lsq / NN)
  Table 3  -> bench_oo           (TPC-H objects: cps / top-k Jaccard)
  Tables 4-6 -> bench_ml         (LDA / GMM / k-means per iteration)
  §8.4/T8  -> bench_objectmodel  (zero-copy movement)
  kernels  -> bench_kernels      (flash vs materialized attention)
  api      -> bench_api          (fluent front-end overhead vs raw executor)
  expr     -> bench_expr         (interpreted vs fused-numpy vs jitted-jax
                                  lambda stages; kernel-LRU hit counters)
  agg      -> bench_agg          (TPC-H Q1 grouped aggregation:
                                  group_by().agg() across expr backends,
                                  local vs workers, partial-map shuffle
                                  bytes)
  dist     -> bench_dist         (workers backend vs local sim; real
                                  page-serialized shuffle bytes vs N;
                                  median/p90/rows_per_s derived fields)
  analysis -> bench_analysis     (planlint wall-time vs compile budget;
                                  shuffle bytes with/without the
                                  redundant-exchange elision)
  service  -> bench_service      (persistent pool: cold vs warm latency,
                                  re-shipped SETUP bytes, queries/sec at
                                  K concurrent sessions vs the one-shot
                                  socket driver)
  §Roofline -> roofline          (from dry-run artifacts, if present)
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
import traceback


def _git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def _parse_derived(derived: str) -> dict:
    """Split a derived string into typed ``k=v`` fields; bare tokens
    (and error messages) land under ``"note"``."""
    fields, notes = {}, []
    for tok in str(derived).split():
        if "=" not in tok:
            notes.append(tok)
            continue
        k, v = tok.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        fields[k] = v
    if notes:
        fields["note"] = " ".join(notes)
    return fields


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", metavar="OUT", default=None,
                    help="also write a machine-readable JSON report")
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names to run (default: all)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks import (bench_agg, bench_analysis, bench_api,
                            bench_dist, bench_expr, bench_kernels,
                            bench_linalg, bench_ml, bench_oo,
                            bench_objectmodel, bench_service)
    suites = [
        ("linalg", bench_linalg.run),
        ("oo", bench_oo.run),
        ("ml", bench_ml.run),
        ("objectmodel", bench_objectmodel.run),
        ("kernels", bench_kernels.run),
        ("api", bench_api.run),
        ("expr", bench_expr.run),
        ("agg", bench_agg.run),
        ("dist", bench_dist.run),
        ("analysis", bench_analysis.run),
        ("service", bench_service.run),
    ]
    if args.only:
        keep = {s.strip() for s in args.only.split(",")}
        unknown = keep - {n for n, _ in suites}
        if unknown:
            ap.error(f"unknown suite(s): {', '.join(sorted(unknown))}")
        suites = [(n, fn) for n, fn in suites if n in keep]

    print("name,us_per_call,derived")
    results = []
    failures = 0
    for name, fn in suites:
        try:
            for row in fn():
                print(",".join(str(x) for x in row), flush=True)
                results.append({"suite": name, "name": row[0],
                                "us_per_call": float(row[1]),
                                **_parse_derived(row[2] if len(row) > 2
                                                 else "")})
        except Exception as e:
            failures += 1
            print(f"{name}_FAILED,0,{type(e).__name__}: {e}", flush=True)
            traceback.print_exc(file=sys.stderr)
    if not args.only:
        try:
            from benchmarks import roofline
            rows, _ = roofline.run()
            for row in rows:
                print(",".join(str(x) for x in row), flush=True)
                results.append({"suite": "roofline", "name": row[0],
                                "us_per_call": float(row[1]),
                                **_parse_derived(row[2] if len(row) > 2
                                                 else "")})
        except Exception as e:
            print(f"roofline_SKIPPED,0,{e}", flush=True)

    if args.json:
        report = {"schema": "repro-bench/1", "git_rev": _git_rev(),
                  "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                             time.gmtime()),
                  "failures": failures, "results": results}
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1)
        print(f"json report -> {args.json}", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
