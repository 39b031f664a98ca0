"""``bench/run.py`` refuses to run anywhere but on a TPU, and without the
system under test beside it."""
import os
import shutil
import subprocess
import sys

import pytest

from bench import run as R

CELLS = [c["name"] for c in R.manifest()["workloads"]]


def _run(root, cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload", cell,
         "--seed", "4294967297", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("cell", CELLS)
def test_no_tpu_exits_nonzero_with_no_result(cell):
    proc = _run(R.ROOT, cell)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert proc.stdout.strip() == ""


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(R.MANIFEST, tmp_path / "BENCHMARK.json")
    shutil.copytree(R.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, CELLS[0])
    assert proc.returncode != 0
    assert "src/repro" in proc.stderr
    assert proc.stdout.strip() == ""

