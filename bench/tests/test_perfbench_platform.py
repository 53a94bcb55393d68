"""The benchmark refuses to run anywhere but on the chip."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import harness  # noqa: E402


def _run(cwd, *extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "products-convert",
         "--seed", "3", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_and_prints_no_result_without_a_tpu():
    r = _run(REPO)
    assert r.returncode == 2
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_run_fails_in_a_checkout_of_only_the_benchmark(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_devices_refuse_the_cpu_and_too_few_chips():
    with pytest.raises(harness.NoDevice, match="no TPU"):
        harness.devices(1)
    with pytest.raises(harness.NoDevice, match="asks for 4"):
        harness.devices(4, allow_cpu=True)
    assert len(harness.devices(1, allow_cpu=True)) == 1
