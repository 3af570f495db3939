"""``bench/run.py`` refuses to measure where it cannot: without a TPU, and
in a checkout that holds only the benchmark. It exits non-zero and
prints no result line."""

import os
import shutil
import subprocess
import sys

import pytest

from bench.harness import BENCH_DIR, ROOT

ARGS = ["--workload", "mamba2-130m.ledger", "--seed", str(2 ** 31 + 5),
        "--seconds", "1", "--trace", "0"]


def run(script, cwd, **env):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    e.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script] + ARGS, cwd=cwd, env=e,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = run(os.path.join(BENCH_DIR, "run.py"), ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_only_the_benchmark_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "bench", ignore=shutil.ignore_patterns(
        ".work", ".jax_cache", "__pycache__"))
    p = run(str(tmp_path / "bench" / "run.py"), str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("bad", [["--workload", "no-such.cell"],
                                 ["--trace", "2"]])
def test_bad_arguments_no_result(bad):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH_DIR, "run.py")]
                       + ARGS + bad, cwd=ROOT, env=e, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
