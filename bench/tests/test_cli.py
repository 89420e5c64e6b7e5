"""The command refuses the CPU, and a directory without the program."""
import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT

ARGS = ["--workload", "fig8-2d-cold", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_the_cpu():
    p = _run(ROOT, ROOT / "bench" / "run.py")
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr and "'cpu'" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path, tmp_path / "bench" / "run.py")
    assert p.returncode != 0
    assert p.stdout == ""
