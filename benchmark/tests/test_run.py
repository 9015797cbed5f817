"""``benchmark/run.py`` finds no TPU here: it exits nonzero before any
analysis and prints no result."""

import os
import subprocess
import sys

from conftest import BENCH_DIR, ROOT


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"),
         "--workload", "tpu_forced.tree16", "--seed", str(2**31 + 3),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert done.returncode != 0
    assert done.stdout == ""
    assert "TPU" in done.stderr and "analysis" not in done.stderr
