"""The yardstick's own tests, inside tier-1.

benchmark/tests pins the harness and the reduction of a trace to the
numbers every PR is judged by (PERF.md section 3); it runs on the CPU in
about a minute and a half. It is run here as one subprocess, so that its
``sys.path`` and its child processes stay its own.
"""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_the_benchmarks_tests_pass():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "benchmark/tests", "-q",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-2000:]
