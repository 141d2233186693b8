"""``bench/run.py`` prints no result and exits non-zero without a TPU, and
in a checkout that holds only BENCHMARK.json and the benchmark's files."""

import os
import shutil
import subprocess
import sys

from harness import spec


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, script, "--workload", "phi3-mini-3l.periodic",
         "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    r = _run(spec.ROOT, os.path.join(spec.BENCH, "run.py"))
    assert r.returncode != 0
    assert '"correct"' not in r.stdout


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, str(tmp_path / "bench" / "run.py"))
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
