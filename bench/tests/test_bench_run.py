"""``bench/run.py`` refuses to run, printing no result line, where it finds
no TPU: on this CPU, and from a directory that holds only
``BENCHMARK.json`` and the benchmark's own files."""
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
ARGS = ["--workload", "repl2.rw50", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(cwd: pathlib.Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


@pytest.mark.parametrize("where", ["checkout", "benchmark_files_only"])
def test_run_exits_nonzero_without_a_tpu(where, tmp_path):
    cwd = ROOT
    if where == "benchmark_files_only":
        cwd = tmp_path
        shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
        shutil.copytree(ROOT / "bench", tmp_path / "bench",
                        ignore=shutil.ignore_patterns(".out", ".dev",
                                                      "__pycache__"))
    p = _run(cwd)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "TPU" in p.stderr or "Error" in p.stderr
