"""The benchmark refuses, with a non-zero exit and no result line, where
JAX finds no TPU, and where the checkout holds only the benchmark."""

import os
import shutil
import subprocess
import sys

from chipbench.tests.tinyroot import REPO

ARGS = ["--workload", "composite-4096x16-zlib", "--seed", "3000000019",
        "--seconds", "1", "--trace", "0"]


def env(**extra):
    e = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    e.update(JAX_PLATFORMS="cpu", **extra)
    return e


def test_no_tpu_no_result():
    done = subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=REPO, env=env(), capture_output=True,
                          text=True, timeout=300)
    assert done.returncode != 0
    assert "{" not in done.stdout
    assert "TPU" in done.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    # past the look for a chip, the program itself is missing
    code = ("import sys, time; from pathlib import Path; "
            "sys.path[0] = str(Path.cwd()); from chipbench import harness; "
            f"harness.main({ARGS!r}, root=Path.cwd(), "
            "started=time.monotonic(), require_chip=False)")
    done = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                          env=env(), capture_output=True, text=True,
                          timeout=300)
    assert done.returncode != 0
    assert "{" not in done.stdout
    assert "repro" in done.stderr
