"""The run command refuses a host with no TPU, and a checkout that holds
only the benchmark."""
import json
import os
import shutil
import subprocess
import sys

import harness

CMD = [sys.executable, "benchmarks/chip/run_cell.py", "--workload",
       "q1_sf1", "--seed", "5", "--seconds", "1"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(CMD, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def _no_result(p):
    for line in p.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def test_refuses_a_host_without_a_tpu():
    p = _run(harness.ROOT)
    assert p.returncode != 0
    assert _no_result(p)
    assert "no TPU" in p.stderr


def test_refuses_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p)
