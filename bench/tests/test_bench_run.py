"""``bench/run.py`` refuses to run where it cannot measure: no TPU, or a
directory holding only the benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]


def run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "olmo-1b.reason", "--seed", "3000000001",
         "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, stdin=subprocess.DEVNULL, timeout=300)


def no_result(proc):
    lines = proc.stdout.strip().splitlines()
    if not lines:
        return True
    try:
        json.loads(lines[-1])
    except ValueError:
        return True
    return False


def test_no_tpu_exits_nonzero_without_a_result():
    proc = run(CHECKOUT)
    assert proc.returncode != 0
    assert no_result(proc)
    assert "no accelerator" in proc.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHECKOUT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = run(tmp_path)
    assert proc.returncode != 0
    assert no_result(proc)
