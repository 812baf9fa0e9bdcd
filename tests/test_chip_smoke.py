"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
campaign phases run end to end on a small configuration (CPU backend,
reference kernels), so a chip call is not spent finding a wrong path."""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs.festivus_imagery import SMOKE

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_refuses_without_a_tpu():
    run = subprocess.run([sys.executable, str(SCRIPT)],
                         env={**os.environ, "JAX_PLATFORMS": "cpu"},
                         capture_output=True, text=True, timeout=300)
    assert run.returncode != 0
    assert "no TPU found" in run.stderr
    assert '"ok"' not in run.stdout


def test_composite_phase_matches_reference(chip_smoke, capsys):
    chip_smoke.composite_phase(SMOKE, depth=4, seed=0, dev=jax.devices()[0])
    out = capsys.readouterr().out
    assert out.count("matches the f32 reference") == chip_smoke.TILES


def test_segmentation_phase_picks_depth_and_matches_reference(chip_smoke,
                                                              capsys):
    chip_smoke.segmentation_phase(SMOKE, seed=0, dev=jax.devices()[0],
                                  budget=1e12)
    out = capsys.readouterr().out
    # an unbounded budget takes the config's full depth
    assert f"depth {SMOKE.temporal_depth} of the paper's" in out
    assert out.count("grad_sum/count match the f32 reference") \
        == chip_smoke.TILES


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_goes_where_the_environment_says(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins and gets the entries; unset, the
    cache is the checkout's fixed .jax_cache."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    code = ("from repro.kernels.backend import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "import jax, jax.numpy as jnp\n"
            "jax.jit(lambda x: x + 1)(jnp.ones(3)).block_until_ready()\n")
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    want = tmp_path if from_env else ROOT / ".jax_cache"
    run = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True, timeout=300)
    assert run.stdout.strip().splitlines()[-1] == str(want)
    assert any(want.iterdir())
