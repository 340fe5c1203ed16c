"""The entry point refuses to produce a result anywhere but on a TPU with
the program beside it."""
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, REPO
from lib import harness


def run(root, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", HOME=str(tmp_path),
               TMPDIR=str(tmp_path))
    return subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload",
         "lustre248x4096.online_filebench", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, cwd=root, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_exits_nonzero_and_prints_no_result(tmp_path):
    proc = run(REPO, tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = run(tmp_path, tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_missing_program_is_refused(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "REPO", tmp_path)
    with pytest.raises(harness.BenchError):
        harness.import_program()
