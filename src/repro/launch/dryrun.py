import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count on first init). REPRO_DRYRUN_DEVICES overrides for small-mesh tests.
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, record memory/cost analyses and the collective
schedule for §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun \
      --arch all --shape all --mesh single,multi --out artifacts/dryrun
"""
import argparse
import json
import re
import sys
import time
import traceback
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (ARCH_IDS, SHAPES, cell_is_runnable, get_arch,
                           get_shape)
from repro.core.planner import make_plan
from repro.engine import (TrainConfig, abstract_decode_state, input_shardings,
                          input_specs, make_serve_step, make_train_step)
from repro.launch.mesh import make_mesh, make_production_mesh, mesh_axis_sizes
from repro.models import Ctx, build_model
from repro.models.model_zoo import _batch_axis
from repro.optim import AdamWConfig, abstract_opt_state, opt_state_specs

_DT_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
             "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
             "pred": 1, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\])\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[-a-z]*\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(result: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(result):
        if dt not in _DT_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DT_BYTES[dt]
    return total


def parse_collectives(hlo: str, n_devices: int) -> List[Dict[str, Any]]:
    """Scan partitioned HLO for collectives; returns per-op records with
    result bytes and ring-model *moved* bytes per device."""
    out = []
    for line in hlo.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        result, kind = m.groups()
        nbytes = _shape_bytes(result)
        gm = _GROUPS_RE.search(line)
        if gm:
            n_groups, group_size = int(gm.group(1)), int(gm.group(2))
        else:
            # explicit groups {{0,1,...},{...}}: size = count in first group
            gb = re.search(r"replica_groups=\{\{([0-9,]+)\}", line)
            group_size = (len(gb.group(1).split(",")) if gb else n_devices)
        n = max(2, group_size)
        # ring cost of bytes leaving each device (result-shape based)
        if kind == "all-gather":
            moved = nbytes * (n - 1) / n
        elif kind == "all-reduce":
            moved = 2 * nbytes * (n - 1) / n
        elif kind == "reduce-scatter":
            moved = nbytes * (n - 1)  # result is 1/n of the input
        elif kind == "all-to-all":
            moved = nbytes * (n - 1) / n
        else:  # collective-permute
            moved = nbytes
        out.append({"kind": kind, "bytes": nbytes, "group_size": group_size,
                    "moved_bytes": moved})
    return out


def _shard_factor(spec: P, axis_sizes: Dict[str, int]) -> int:
    f = 1
    for entry in spec:
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        for a in axes:
            f *= axis_sizes.get(a, 1)
    return f


def analytic_bytes_per_device(abstract_tree, spec_tree,
                              axis_sizes: Dict[str, int]) -> int:
    total = 0
    flat_a = jax.tree.leaves(abstract_tree)
    flat_s = jax.tree.leaves(spec_tree,
                             is_leaf=lambda x: isinstance(x, P))
    for a, s in zip(flat_a, flat_s):
        nbytes = int(np.prod(a.shape)) * a.dtype.itemsize if a.shape else \
            a.dtype.itemsize
        total += nbytes // max(1, _shard_factor(s, axis_sizes))
    return total


def _named(mesh, tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        tree, is_leaf=lambda x: isinstance(x, P))


def build_cell(arch_name: str, shape_name: str, mesh, *,
               use_flash: bool = False, microbatches: int = 1,
               remat: Optional[str] = None,
               kv_strategy: Optional[str] = None,
               dp_only: bool = False, quantize_dispatch: bool = False,
               ep_shard_map: bool = False, kv_dtype: Optional[str] = None,
               compression: str = "none", capacity_factor: float = None,
               tag: str = ""):
    """Build (fn, args, in_shardings, out_shardings, donate, plan, model)."""
    cfg = get_arch(arch_name)
    import dataclasses
    if remat is not None:
        cfg = dataclasses.replace(cfg, remat=remat)
    if capacity_factor is not None:
        cfg = dataclasses.replace(cfg, capacity_factor=capacity_factor)
    shape = get_shape(shape_name)
    axes = mesh_axis_sizes(mesh)
    plan = make_plan(cfg, axes, shape, allow_dp_only=dp_only)
    if kv_strategy is not None:
        plan.kv_strategy = kv_strategy
    model = build_model(cfg)
    ctx = Ctx(plan=plan, use_flash=use_flash,
              quantize_dispatch=quantize_dispatch,
              ep_shard_map=ep_shard_map, mesh=mesh if ep_shard_map else None)

    p_abs = model.abstract_params()
    p_spec = model.param_specs(plan)
    batch_abs = input_specs(model, shape)
    batch_spec = input_shardings(model, shape, plan)

    if shape.kind == "train":
        from repro.engine.compression import CompressionConfig
        ocfg = AdamWConfig(moment_dtype=cfg.moment_dtype)
        tcfg = TrainConfig(microbatches=microbatches, opt=ocfg,
                           compression=CompressionConfig(scheme=compression))
        fn = make_train_step(model, ctx, tcfg)
        o_abs = abstract_opt_state(p_abs, ocfg)
        o_spec = opt_state_specs(p_spec)
        if compression != "none":  # error-feedback residuals, sharded as params
            e_abs = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), p_abs)
            e_sh = _named(mesh, p_spec)
        else:
            e_abs, e_sh = None, None
        args = (p_abs, o_abs, e_abs, batch_abs)
        in_sh = (_named(mesh, p_spec), _named(mesh, o_spec), e_sh,
                 _named(mesh, batch_spec))
        rep = NamedSharding(mesh, P())
        out_sh = (_named(mesh, p_spec), _named(mesh, o_spec), e_sh,
                  {"loss": rep, "aux_loss": rep, "z_loss": rep,
                   "tokens": rep, "grad_norm": rep, "lr": rep,
                   "total_loss": rep})
        donate = (0, 1) if compression == "none" else (0, 1, 2)
        state_abs, state_spec = (p_abs, o_abs), None
    elif shape.kind == "prefill":
        def fn(params, batch):
            logits, aux = model.forward(params, batch, ctx, last_only=True)
            return logits

        args = (p_abs, batch_abs)
        in_sh = (_named(mesh, p_spec), _named(mesh, batch_spec))
        b = _batch_axis(plan)
        out_sh = NamedSharding(mesh, P(b, None, "model"))
        donate = ()
        state_abs = None
    else:  # decode
        fn = make_serve_step(model, ctx)
        st_abs = abstract_decode_state(model, shape, kv_dtype=kv_dtype)
        st_spec = model.decode_state_specs(plan, kv_dtype=kv_dtype)
        rng_abs = jax.ShapeDtypeStruct((2,), jnp.uint32)
        args = (p_abs, batch_abs["token"], st_abs, rng_abs)
        b = _batch_axis(plan)
        tok_sh = NamedSharding(mesh, P(b, None))
        in_sh = (_named(mesh, p_spec), tok_sh, _named(mesh, st_spec),
                 NamedSharding(mesh, P()))
        out_sh = (tok_sh, NamedSharding(mesh, P(b, None, "model")),
                  _named(mesh, st_spec))
        donate = (2,)
        state_abs = st_abs
    return fn, args, in_sh, out_sh, donate, plan, model, state_abs


def run_cell(arch_name: str, shape_name: str, mesh_kind: str,
             out_dir: Optional[str] = None, mesh=None,
             **build_kwargs) -> Dict[str, Any]:
    cfg = get_arch(arch_name)
    shape = get_shape(shape_name)
    ok, why = cell_is_runnable(cfg, shape)
    rec: Dict[str, Any] = {"arch": arch_name, "shape": shape_name,
                           "mesh": mesh_kind}
    if not ok:
        rec.update({"status": "skipped", "reason": why})
        return rec
    if mesh is None:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = int(np.prod(mesh.devices.shape))
    t0 = time.time()
    fn, args, in_sh, out_sh, donate, plan, model, state_abs = build_cell(
        arch_name, shape_name, mesh, **build_kwargs)
    try:
        with mesh:
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=donate)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
    except Exception as e:
        rec.update({"status": "failed", "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-2000:]})
        return rec

    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_rec = {k: int(getattr(mem, k)) for k in
                   ("argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "generated_code_size_in_bytes")
                   if hasattr(mem, k)} if mem is not None else None
    except Exception:
        mem_rec = None
    colls = parse_collectives(compiled.as_text(), n_dev)

    axes = mesh_axis_sizes(mesh)
    # analytic per-device persistent state (params + opt + decode state)
    p_abs = model.abstract_params()
    p_spec = model.param_specs(plan)
    state_bytes = analytic_bytes_per_device(p_abs, p_spec, axes)
    if shape.kind == "train":
        ocfg = AdamWConfig(moment_dtype=cfg.moment_dtype)
        o_abs = abstract_opt_state(p_abs, ocfg)
        state_bytes += analytic_bytes_per_device(
            o_abs, opt_state_specs(p_spec), axes)
    elif shape.kind == "decode" and state_abs is not None:
        st_spec = model.decode_state_specs(
            plan, kv_dtype=build_kwargs.get("kv_dtype"))
        state_bytes += analytic_bytes_per_device(state_abs, st_spec, axes)

    by_kind: Dict[str, Dict[str, float]] = {}
    for c in colls:
        k = by_kind.setdefault(c["kind"], {"count": 0, "bytes": 0.0,
                                           "moved_bytes": 0.0})
        k["count"] += 1
        k["bytes"] += c["bytes"]
        k["moved_bytes"] += c["moved_bytes"]

    rec.update({
        "status": "ok",
        "devices": n_dev,
        "kind": shape.kind,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "flops_per_device": cost.get("flops"),
        "bytes_accessed_per_device": cost.get("bytes accessed"),
        "memory_analysis": mem_rec,
        "analytic_state_bytes_per_device": state_bytes,
        "collectives": by_kind,
        "collective_moved_bytes_per_device": sum(
            c["moved_bytes"] for c in colls),
        "plan": {"moe": plan.moe_strategy, "kv": plan.kv_strategy,
                 "fsdp": plan.fsdp, "remat": plan.remat,
                 "shard_batch": plan.shard_batch,
                 "decisions": plan.decisions},
        "params": model.param_count(),
    })
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = build_kwargs.get("tag", "")
        fname = f"{arch_name}__{shape_name}__{mesh_kind}{tag}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single,multi")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--use-flash", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args(argv)

    archs = ARCH_IDS if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = args.mesh.split(",")
    failures = 0
    n_dev = len(jax.devices())
    for mk in meshes:
        if n_dev >= 512 or (n_dev >= 256 and mk == "single"):
            mesh = make_production_mesh(multi_pod=(mk == "multi"))
        else:  # reduced mesh for CI/small-mesh tests
            if mk == "multi":
                mesh = make_mesh((2, n_dev // 8, 4),
                                 ("pod", "data", "model"))
            else:
                mesh = make_mesh((n_dev // 4, 4), ("data", "model"))
        for a in archs:
            for s in shapes:
                rec = run_cell(a, s, mk, out_dir=args.out, mesh=mesh,
                               use_flash=args.use_flash,
                               microbatches=args.microbatches)
                if rec["status"] == "ok":
                    print(f"[OK]   {a:18s} {s:12s} {mk:6s} "
                          f"flops/dev={rec['flops_per_device']:.3e} "
                          f"state/dev={rec['analytic_state_bytes_per_device']/2**30:.2f}GiB "
                          f"coll/dev={rec['collective_moved_bytes_per_device']/2**30:.3f}GiB "
                          f"compile={rec['compile_s']:.1f}s", flush=True)
                elif rec["status"] == "skipped":
                    print(f"[SKIP] {a:18s} {s:12s} {mk:6s} {rec['reason']}",
                          flush=True)
                else:
                    failures += 1
                    print(f"[FAIL] {a:18s} {s:12s} {mk:6s} {rec['error']}",
                          flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
