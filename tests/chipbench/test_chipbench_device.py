"""A run refuses a device that is not in the peaks table, a host with no
accelerator, and a checkout that holds only the benchmark's files."""
import os
import shutil
import subprocess
import sys

import pytest

from _tiny import ROOT, harness

ARGS = ["--workload", "femnist_paper_adjust", "--seed", "1", "--seconds",
        "1", "--trace", "0"]


def test_peaks_table_refuses_unknown_kind():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.Refused):
        harness.peaks_for("TPU v9 imaginary")
    with pytest.raises(harness.Refused):
        harness.peaks_for("cpu")


def test_no_accelerator_is_refused():
    with pytest.raises(harness.Refused):
        harness.accelerator(1)


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=120)


def test_cli_on_a_cpu_host_exits_nonzero_without_a_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_cli_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".data", ".jax_cache",
                                                  ".traces", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
