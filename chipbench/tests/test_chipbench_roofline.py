"""The roofline's operation and byte counts on known shapes, and the share
they give against the peaks table."""

import importlib.util
import json

import pytest

from chipbench.tests.tinyroot import REPO

METRICS = REPO / "chipbench" / "metrics"


def metric(name):
    spec = importlib.util.spec_from_file_location(name, METRICS / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_composite_bytes_and_ops():
    flops, nbytes = metric("composite_roofline").cost(16, 4096, 4)
    pixels = 4096 * 4096
    # stack (16 x 4 bands) and weights (16) read once, 4 bands written, f32
    assert nbytes == 4 * pixels * (64 + 16 + 4) == 5_637_144_576
    assert flops == pixels * (2 * 64 + 16 + 8)


def test_grad_mag_bytes_and_ops():
    flops, nbytes = metric("grad_mag_roofline").cost(4, 6144, 4)
    pixels = 6144 * 6144
    # f32 stack and bool mask read once, two f32 sums written
    assert nbytes == pixels * (4 * 16 + 4 + 8) == 2_868_903_936
    assert flops == pixels * (8 * 16 + 8 * 4)


class FakeRun:
    device_kind = "TPU v5 lite"
    config = {"depth": 16, "tile_px": 4096, "bands": 4}

    def __init__(self, calls, seconds):
        self.found = (calls, seconds)
        self.peaks = json.loads((REPO / "chipbench" / "peaks.json").read_text())

    def module(self, prefix):
        return self.found if prefix == "jit_composite" else None

    def peak(self, key):
        return float(self.peaks["devices"][self.device_kind][key])


def test_share_is_least_time_over_measured_time():
    m = metric("composite_roofline")
    least = 5_637_144_576 / 819e9  # memory bound on a v5e
    assert m.read(FakeRun(2, 4 * least)) == pytest.approx(50.0)
    assert m.read(FakeRun(1, least)) == pytest.approx(100.0)


def test_a_program_that_did_not_run_gives_nothing():
    run = FakeRun(1, 1.0)
    run.module = lambda prefix: None
    assert metric("composite_roofline").read(run) is None


def test_unknown_device_is_an_error():
    from chipbench.harness import Run

    run = Run.__new__(Run)
    run.device_kind = "TPU v9"
    run.peaks = {"devices": {}}
    with pytest.raises(KeyError):
        run.peak("flops_per_s")
