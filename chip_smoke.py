#!/usr/bin/env python3
"""Smoke test of the relational query path on one TPU chip.

Runs TPC-H Q1 (SF 1 by default) and a co-partitioned group_by -> join ->
group_by chain with ``expr_backend="jax"``, through a local ``Session`` and
through a ``QueryService`` client session, each query cold and then warm.
Every answer is compared with the same query under ``expr_backend="numpy"``
on the host: keys and integer columns exactly, float columns within
``rtol=1e-9``. Then it checks that the device path really ran and that the
device is a TPU. The last line of standard output is one JSON object,
printed only when every phase passed::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Usage, from the root of a checkout::

    python3 chip_smoke.py            # on the chip, SF 1
    JAX_PLATFORMS=cpu REPRO_AGG_DEVICE=1 python3 chip_smoke.py --sf 0.01

``--sf`` lowers the scale for a rehearsal on the CPU; there every phase
runs and the script then fails at its platform check. On a host without a
TPU and without ``--sf`` it fails before generating any data. The wall
times it prints are smoke timings, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.apps.tpch import LineitemQ1, q1_pricing_summary  # noqa: E402
from repro.core import Session, agg, exprc, relops  # noqa: E402
from repro.data.synthetic import tpch_q1_lineitems  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.objectmodel.schema import Record, S, f64, i64  # noqa: E402
from repro.objectmodel.store import PagedStore  # noqa: E402
from repro.service import QueryService  # noqa: E402

SEED = 0
SF1_LINEITEMS = 6_001_215   # TPC-H lineitem rows at scale factor 1
CHAIN_FACTS = 1_000_000
CHAIN_DIMS = 64
RTOL = 1e-9


class FactRow(Record):
    key: i64
    val: f64


class DimRow(Record):
    dkey: i64
    tag: S(8)


class SmokeFailure(Exception):
    pass


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _timed(ds):
    t0 = time.perf_counter()
    out = ds.collect()
    return out, time.perf_counter() - t0


def compare(name: str, got, ref) -> dict:
    """Hold ``got`` to the host reference ``ref``: same columns, dtypes and
    lengths; keys, counts and integer columns exact; float columns within
    ``RTOL``. Returns the largest relative float difference, the column
    it is in, and whether every column is byte-identical."""
    _check(list(got) == list(ref),
           f"{name}: columns {list(got)} != reference {list(ref)}")
    max_rel, worst, identical = 0.0, None, True
    for col in ref:
        g, r = np.asarray(got[col]), np.asarray(ref[col])
        _check(g.dtype == r.dtype and g.shape == r.shape,
               f"{name}.{col}: {g.dtype}{g.shape} != reference "
               f"{r.dtype}{r.shape}")
        identical &= g.tobytes() == r.tobytes()
        if g.dtype.kind == "f":
            denom = np.maximum(np.abs(r), np.finfo(r.dtype).tiny)
            rel = np.abs(g - r) / denom
            if rel.size and float(rel.max()) > max_rel:
                max_rel, worst = float(rel.max()), col
            _check(np.allclose(g, r, rtol=RTOL, atol=0.0),
                   f"{name}.{col}: float values beyond rtol={RTOL}")
        else:
            _check(np.array_equal(g, r), f"{name}.{col}: values differ")
    return {"max_rel_diff": max_rel, "worst_col": worst,
            "byte_identical": identical}


def _chain(sess, facts, dims):
    """The co-partitioned group_by -> join -> group_by chain."""
    return (sess.load("facts", facts, FactRow)
                .group_by("key").agg(s=agg.sum("val"), c=agg.count())
                .join(sess.load("dims", dims, DimRow),
                      on=lambda a, b: a.key == b.dkey)
                .group_by("key").agg(t=agg.sum("s"), m=agg.count()))


def run(sf: float) -> None:
    cache_dir = use_compile_cache()
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} compile_cache={cache_dir}")

    n = max(1, round(SF1_LINEITEMS * sf))
    t0 = time.perf_counter()
    lines = tpch_q1_lineitems(n, seed=SEED)
    print(f"data: lineitems rows={n} sf={sf} packed_mb="
          f"{lines.nbytes / 1e6:.1f} gen_s={time.perf_counter() - t0:.3f}")

    rng = np.random.default_rng(SEED)
    n_facts = max(1, round(CHAIN_FACTS * min(sf, 1.0)))
    facts = FactRow.pack(key=rng.integers(0, CHAIN_DIMS, n_facts),
                         val=rng.normal(0, 1, n_facts))
    dims = DimRow.pack(dkey=np.arange(CHAIN_DIMS),
                       tag=np.array([b"d%d" % i for i in range(CHAIN_DIMS)]))

    store = PagedStore()
    exprc.reset_kernel_cache()
    results = []

    def report(name, cold, warm, ref_ds, t_cold, t_warm, extra=""):
        ref, t_ref = _timed(ref_ds)
        for tag, got in (("cold", cold), ("warm", warm)):
            c = compare(f"{name}/{tag}", got, ref)
            results.append(c)
            print(f"check: {name}/{tag} rows={len(next(iter(got.values())))}"
                  f" max_rel_diff={c['max_rel_diff']!r} "
                  f"in={c['worst_col']} "
                  f"byte_identical={c['byte_identical']}")
        print(f"smoke timing (not a benchmark metric): {name} "
              f"cold_s={t_cold:.3f} warm_s={t_warm:.3f} "
              f"host_numpy_s={t_ref:.3f}{extra}")

    # (a) local session: Q1, then the co-partitioned join chain
    local = Session(store=store, num_partitions=4, expr_backend="jax")
    set_name = local.load("lineitem", lines, LineitemQ1).set_name
    q1 = q1_pricing_summary(store, set_name, session=local)
    cold, t_cold = _timed(q1)
    warm, t_warm = _timed(q1)
    host = Session(store=store, num_partitions=4, expr_backend="numpy")
    report("local_q1", cold, warm,
           q1_pricing_summary(store, set_name, session=host), t_cold, t_warm)

    chain_dev = Session(store=store, num_partitions=4,
                        broadcast_threshold_bytes=0, expr_backend="jax")
    chain_host = Session(store=store, num_partitions=4,
                         broadcast_threshold_bytes=0, expr_backend="numpy")
    q = _chain(chain_dev, facts, dims)
    cold, t_cold = _timed(q)
    warm, t_warm = _timed(q)
    report("local_join_chain", cold, warm,
           _chain(chain_host, facts, dims), t_cold, t_warm,
           f" facts={n_facts} exchanges_elided="
           f"{chain_dev.last_stats.exchanges_elided}")

    # (b) the same Q1 through a resident service pool of thread workers;
    # its host reference is a local session with one partition per worker
    with QueryService(store=store, num_workers=2, launch="thread",
                      expr_backend="jax") as svc:
        svc.wait_ready(60)
        client = Session.connect(svc)
        q1 = q1_pricing_summary(store, set_name, session=client)
        cold, t_cold = _timed(q1)
        cold_setup = client.executor.last_setup_bytes
        warm, t_warm = _timed(q1)
        warm_setup = client.executor.last_setup_bytes
    _check(cold_setup > 0, "service cold query shipped no setup bytes")
    _check(warm_setup == 0,
           f"service warm repeat shipped {warm_setup} setup bytes, not 0")
    host2 = Session(store=store, num_partitions=2, expr_backend="numpy")
    report("service_q1", cold, warm,
           q1_pricing_summary(store, set_name, session=host2), t_cold, t_warm,
           f" setup_bytes_cold={cold_setup} setup_bytes_warm={warm_setup}")

    # device check: both device programs ran, and a jitted core's output
    # lives on the accelerator
    kinfo = exprc.kernel_cache_info()
    print(f"device check: segment_kernels={len(relops._SEG_KERNELS)} "
          f"exprc_kernel_misses={kinfo['misses']} "
          f"exprc_kernel_hits={kinfo['hits']}")
    _check(len(relops._SEG_KERNELS) > 0,
           "no segment-reduce kernel was built: pre-aggregation stayed on "
           "the host")
    _check(kinfo["misses"] > 0, "no exprc kernel was compiled")
    cores = [k for k in list(exprc._KCACHE.values())
             if getattr(k, "core", None) is not None]
    _check(bool(cores), "no fused stage has a jitted device core")
    with jax.enable_x64(True):
        outs = cores[0].core(*[np.zeros(8, d) for d in cores[0].core_dtypes])
    platforms = sorted({d.platform for o in outs for d in o.devices()})
    print(f"device check: jitted core output on {platforms}")
    _check(platforms == ["tpu"],
           f"jitted core output lives on {platforms}, not on a tpu")

    identical = all(r["byte_identical"] for r in results)
    worst = max(r["max_rel_diff"] for r in results)
    print(f"correctness: all answers within rtol={RTOL} of the host "
          f"reference; largest relative difference {worst!r}; "
          f"byte-identical to the host: {identical}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sf", type=float, default=None,
                    help="TPC-H scale factor (default 1; lower it only "
                         "for a rehearsal on the CPU)")
    args = ap.parse_args(argv)

    platform = jax.devices()[0].platform
    if platform != "tpu" and args.sf is None:
        print(f"FAIL: JAX finds no TPU (platform={platform!r}); a CPU "
              "rehearsal passes --sf", file=sys.stderr)
        return 1
    try:
        run(1.0 if args.sf is None else args.sf)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"FAIL: every phase ran, but on platform "
              f"{devices[0].platform!r}, not a tpu", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
