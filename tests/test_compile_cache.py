"""Where the persistent compilation cache lands
(:func:`repro.launch.compile_cache.use_compile_cache`). Each case runs in a
child process on the CPU, so the jax config it sets stays out of this
process."""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

_CHILD = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import use_compile_cache
    print(use_compile_cache())
    print(jax.config.jax_compilation_cache_dir)
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(16)).block_until_ready()
""")


def _child(env_extra):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra)
    r = subprocess.run([sys.executable, "-c", _CHILD], capture_output=True,
                       text=True, timeout=120, env=env, cwd=ROOT)
    assert r.returncode == 0, r.stderr
    return r.stdout.split()


def test_cache_dir_placed_from_outside_is_the_only_one(tmp_path):
    cache = tmp_path / "jaxcache"
    returned, configured = _child({
        "JAX_COMPILATION_CACHE_DIR": str(cache),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"})
    assert returned == configured == str(cache)
    assert any(cache.iterdir()), "the compile was not cached there"


def test_cache_dir_defaults_to_a_fixed_ignored_path_in_the_checkout():
    returned, configured = _child({})
    assert returned == configured == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
