"""The measurement path refuses anything but a TPU, and prints no
result when it does."""

import os
import shutil
import subprocess
import sys

import pytest

from benchmark import harness
from benchmark.registry import CHECKOUT


def test_no_tpu_exits_nonzero_with_no_result(capsys):
    rc = harness.main(["--workload", "northstar-1k.fresh", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "needs a TPU" in out.err


def test_device_check_refuses_cpu_and_unknown_kinds(monkeypatch):
    with pytest.raises(harness.NoAccelerator):
        harness.device_info(1)
    # the check path that skips it reports the device as it is
    info = harness.device_info(1, require_tpu=False)
    assert info["platform"] == "cpu" and info["count"] >= 1


def test_benchmark_alone_exits_nonzero_with_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's
    files cannot run a cell."""
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(CHECKOUT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark", "--workload", "northstar-1k.fresh",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
