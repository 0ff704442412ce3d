"""The command line refuses to run where it cannot measure: with JAX held
to the CPU, and in a directory that holds only BENCHMARK.json and the
benchmark's own files (no program to serve)."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


def run(cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "mamba2_wc.fanout8", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


def test_cli_refuses_the_cpu():
    proc = run(CHECKOUT)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert no_result(proc.stdout)
    assert "accelerator" in proc.stderr


def test_cli_fails_without_the_program(tmp_path):
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    proc = run(tmp_path)
    assert proc.returncode != 0
    assert no_result(proc.stdout)
