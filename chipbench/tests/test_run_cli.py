"""``run.py`` stops with a non-zero code and no result line where JAX
finds no TPU."""
import os
import subprocess
import sys

from chipbench import harness


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = harness.load_benchmark()["workloads"][0]["name"]
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        cell, "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=harness.ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr
