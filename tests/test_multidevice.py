"""Multi-device behaviour via subprocesses (the parent process must stay
single-device). Covers: small-mesh dry-run for every arch family, shard_map
two-stage aggregation / joins, pipeline parallelism, elastic re-mesh.

Each child runs on the CPU with virtual devices (``JAX_PLATFORMS=cpu`` and
``--xla_force_host_platform_device_count``), so no child ever reaches for an
accelerator the parent may hold. Meshes come from
:func:`repro.launch.mesh.make_mesh` (Auto axes). Under some sandboxed
runners a child spawned with piped stdio intermittently hangs inside bare
``jax.make_mesh``/XLA CPU client startup (no repro code on the stack, near
zero CPU); a one-shot canary probes that up front and skips the module
with a reason when the environment is in that state."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
SRC = os.path.join(ROOT, "src")

_ENV_SKIP = ("multi-device subprocess jax init hangs in this environment "
             "(sandbox-sensitive XLA CPU client startup with piped stdio — "
             "fails on bare jax.make_mesh, no repro code involved); "
             "see ROADMAP Open items")

_canary_ok = None


def _probe_canary(timeout: int = 90) -> bool:
    """One fresh probe: can a piped-stdio subprocess get through
    multi-device jax init right now?"""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    try:
        r = subprocess.run(
            [sys.executable, "-c",
             "import jax; jax.make_mesh((8,), ('d',)); print('ok')"],
            capture_output=True, text=True, timeout=timeout, env=env,
            cwd=ROOT)
        return r.returncode == 0
    except subprocess.TimeoutExpired:
        return False


def _multidevice_subprocess_ok() -> bool:
    global _canary_ok
    if _canary_ok is None:
        _canary_ok = _probe_canary()
    return _canary_ok


@pytest.fixture(autouse=True)
def _require_multidevice_subprocess():
    if not _multidevice_subprocess_ok():
        pytest.skip(_ENV_SKIP)


def _run(code: str, devices: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    try:
        r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                           capture_output=True, text=True, timeout=timeout,
                           env=env, cwd=ROOT)
    except subprocess.TimeoutExpired:
        # distinguish the intermittent environment init hang from a real
        # deadlock in the code under test: re-probe with a fresh canary —
        # if even bare jax.make_mesh hangs now, the environment flipped
        # into its broken state mid-run (skip); if the canary is fine,
        # the timeout is the test's own and must fail.
        global _canary_ok
        if not _probe_canary():
            _canary_ok = False
            pytest.skip(_ENV_SKIP)
        raise
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_dryrun_small_mesh_every_family(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["REPRO_DRYRUN_DEVICES"] = "16"
    env["JAX_PLATFORMS"] = "cpu"
    try:
        r = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun",
             "--arch",
             "gemma_7b,phi35_moe,xlstm_125m,jamba15_large,whisper_small",
             "--shape", "train_4k,decode_32k",
             "--mesh", "single", "--out", str(tmp_path)],
            capture_output=True, text=True, timeout=1800, env=env, cwd=ROOT)
    except subprocess.TimeoutExpired:
        if not _probe_canary():
            pytest.skip(_ENV_SKIP)
        raise
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("[OK]") == 10, r.stdout


def test_two_stage_aggregate_shard_map():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.engine.aggregation import two_stage_aggregate
    mesh = make_mesh((8,), ("data",))
    keys = jnp.arange(64) % 16
    vals = jnp.arange(64, dtype=jnp.float32)
    fn = jax.shard_map(
        lambda k, v: two_stage_aggregate(k, v, 16, "data"),
        mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P("data"))
    got = fn(keys, vals)
    want = np.zeros(16); np.add.at(want, np.asarray(keys), np.asarray(vals))
    np.testing.assert_allclose(np.asarray(got), want)
    print("two-stage OK")
    """)


def test_broadcast_and_hash_joins_shard_map():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.engine.aggregation import broadcast_join, hash_partition_join
    mesh = make_mesh((4,), ("data",))
    probe = jnp.arange(32) % 10
    build_k = jnp.arange(10)
    build_v = (jnp.arange(10) * 10.0)[:, None]
    # broadcast join: build side sharded, gathered inside
    fn = jax.shard_map(
        lambda p, bk, bv: broadcast_join(p, bk, bv, "data"),
        mesh=mesh, in_specs=(P("data"), P("data"), P("data")),
        out_specs=P("data"))
    m, v = fn(probe, jnp.pad(build_k, (0, 2)), jnp.pad(build_v, ((0,2),(0,0))))
    got = np.asarray(v)[np.asarray(m)]
    assert set(got.flatten().tolist()) <= set((build_v.flatten()).tolist())
    # hash-partition join: rows land on the shard owning their key bucket
    fn2 = jax.shard_map(
        lambda k, v: hash_partition_join(k, v, 4, "data"),
        mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P("data"))
    keys = jnp.arange(64) % 4
    vals = jnp.ones((64, 2))
    rk, rv = fn2(keys, vals)
    rk = np.asarray(rk).reshape(4, -1)
    for shard in range(4):
        kk = rk[shard]; kk = kk[kk >= 0]
        assert (kk == shard).all(), (shard, kk)
    print("joins OK")
    """)


def test_pipeline_parallel_matches_sequential():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.engine.pipeline_parallel import pipeline_forward
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("pipe",))
    S, B, D = 4, 8, 16
    rng = jax.random.PRNGKey(0)
    Ws = jax.random.normal(rng, (S, D, D)) / np.sqrt(D)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, D))
    stage = lambda W, h: jnp.tanh(h @ W)
    out = pipeline_forward(stage, Ws, x, n_micro=4, mesh=mesh)
    want = x
    for i in range(S):
        want = jnp.tanh(want @ Ws[i])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    print("pipeline OK")
    """)


def test_elastic_restore_to_new_mesh(tmp_path):
    _run(f"""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.checkpoint import Checkpointer
    from repro.launch.mesh import make_mesh
    ck = Checkpointer({str(tmp_path)!r})
    state = {{"w": jnp.arange(64.0).reshape(8, 8)}}
    ck.save(1, state)
    # restore onto a 2x4 mesh with w sharded over both axes
    mesh = make_mesh((2, 4), ("data", "model"))
    got, _ = ck.restore(state, specs={{"w": P("data", "model")}}, mesh=mesh)
    np.testing.assert_allclose(np.asarray(got["w"]), np.asarray(state["w"]))
    assert len(got["w"].sharding.device_set) == 8
    print("elastic OK")
    """)


def test_gradients_identical_with_and_without_compression_off():
    _run("""
    # dp-sharded train step == single-device train step (GSPMD correctness)
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.configs import get_arch, reduced_config
    from repro.core.planner import make_plan
    from repro.configs import get_shape
    from repro.models import build_model, Ctx
    from repro.engine import make_train_step, TrainConfig
    from repro.optim import init_opt_state, AdamWConfig
    from repro.launch.mesh import make_mesh
    cfg = reduced_config(get_arch("phi3_mini"))
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0), "float32")
    opt = init_opt_state(params, AdamWConfig())
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    ts = jax.jit(make_train_step(model, Ctx(), TrainConfig()))
    p1, _, _, m1 = ts(params, opt, None, batch)
    mesh = make_mesh((8,), ("data",))
    with mesh:
        sb = jax.device_put(batch, NamedSharding(mesh, P("data", None)))
        ts2 = jax.jit(make_train_step(model, Ctx(), TrainConfig()))
        p2, _, _, m2 = ts2(params, opt, None, sb)
    assert abs(float(m1["total_loss"]) - float(m2["total_loss"])) < 1e-4
    d = max(float(jnp.abs(a - b).max()) for a, b in
            zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    assert d < 1e-4, d
    print("dp-equivalence OK", d)
    """)


def test_ep_shard_map_matches_gspmd_baseline():
    _run("""
    import jax, jax.numpy as jnp, numpy as np, dataclasses
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.configs import get_arch, reduced_config, get_shape
    from repro.core.planner import make_plan
    from repro.models import build_model, Ctx
    from repro.launch.mesh import make_mesh
    cfg = dataclasses.replace(reduced_config(get_arch("phi35_moe")),
                              capacity_factor=4.0)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0), "float32")
    mesh = make_mesh((2, 4), ("data", "model"))
    plan = make_plan(cfg, {"data": 2, "model": 4}, get_shape("train_4k"))
    assert plan.moe_strategy == "ep"
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks}
    base, _ = model.forward(params, batch, Ctx())
    with mesh:
        ctx = Ctx(plan=plan, ep_shard_map=True, mesh=mesh)
        sb = jax.device_put(batch, NamedSharding(mesh, P("data", None)))
        ep, _ = jax.jit(lambda p, b: model.forward(p, b, ctx))(params, sb)
    err = float(jnp.abs(jax.nn.log_softmax(base)
                        - jax.nn.log_softmax(ep)).max())
    assert err < 2e-3, err
    print("EP shard_map equivalence OK", err)
    """)
