"""``bench/run.py`` refuses to measure without a TPU, and without the
program beside it, and prints no result then."""

import os
import shutil
import subprocess
import sys

import _bench_path

ROOT = _bench_path.ROOT
ARGS = ["--workload", "packed448-gui-serial", "--seed", str(2**33 + 1),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=240)


def test_no_tpu_exits_nonzero_without_a_result():
    out = _run(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "NoDevice" in out.stderr


def test_benchmark_alone_exits_nonzero_without_a_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "BadSetup" in out.stderr
