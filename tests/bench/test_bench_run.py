"""``bench/run.py`` refuses to run off the TPU, and in a checkout that
holds only the benchmark's own files: a non-zero exit and no result."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, script, "--workload", "qwen2.5-14b.sweep",
         "--seed", str(2 ** 31 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    for line in proc.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_run_refuses_off_the_tpu():
    proc = _run(ROOT, os.path.join("bench", "run.py"))
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    _no_result(proc)


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        paths = json.load(fh)["paths"]
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in paths:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(str(tmp_path), os.path.join("bench", "run.py"))
    assert proc.returncode != 0
    _no_result(proc)
