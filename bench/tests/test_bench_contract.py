"""The benchmark refuses to measure without a chip, and refuses a device
that its table of peaks does not hold."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

BENCH = Path(__file__).parents[1]
ROOT = BENCH.parent


def test_unknown_device_kind_is_an_error():
    assert harness.peaks(BENCH, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        harness.peaks(BENCH, "TPU v99 imaginary")


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "xlstm125m-silo16",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_tpu_exits_nonzero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = _run(ROOT, env)
    assert r.returncode != 0
    assert r.stdout == ""
    assert "needs a TPU" in r.stderr


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = _run(tmp_path, env)
    assert r.returncode != 0
    assert r.stdout == ""
