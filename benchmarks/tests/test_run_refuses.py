"""``run.py`` prints no result and exits non-zero without a TPU, with a
forcing variable set, and in a directory that holds only the benchmark."""

import os
import shutil
import subprocess
import sys

from benchmarks import manifest

ARGS = ["--workload", "val175-commit-stream", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, **env):
    e = {**os.environ, "JAX_PLATFORMS": "cpu", **env}
    e.pop("BENCH_RUN", None)
    return subprocess.run(
        [sys.executable, "benchmarks/run.py", *ARGS], cwd=cwd, env=e,
        capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run(manifest.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_forcing_variable_no_result():
    p = _run(manifest.ROOT, COMETBFT_TPU_VERIFY_SCHED="0")
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "COMETBFT_TPU_VERIFY_SCHED" in p.stderr


def test_benchmark_alone_no_result(tmp_path):
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(manifest.ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
