"""A whole run, with the look for a chip skipped, sees ``correct`` come out
false when the timed path is broken underneath, once for each fault the
cells can have: a tile's output never written (the state left unchanged),
half of the stack left out, and an answer altered where it is produced.
One chip per cell: there is no exchange between chips to leave out."""

import pytest

from chipbench.tests import tinyroot


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinyroot.make(tmp_path_factory.mktemp("faults"))


def half_stack(fn):
    def broken(images, other, *args, **kwargs):
        half = images.shape[0] // 2
        return fn(images[:half], other[:half], *args, **kwargs)
    return broken


def altered(fn):
    def broken(*args, **kwargs):
        out = fn(*args, **kwargs)
        return out.at[0, 0].add(1e-3)
    return broken


def relabelled(fn):
    def broken(mask):
        labels = fn(mask)
        return labels.at[-1, -1].add(1)
    return broken


def test_sound_runs_are_correct(root):
    for cell in ("tiny-composite", "tiny-segment"):
        result = tinyroot.run(root, cell, seed=2**31 + 5)
        assert result["correct"] and result["failed"] == 0, result


@pytest.mark.parametrize("cell,target,fault", [
    ("tiny-composite", "repro.kernels.ops.composite", half_stack),
    ("tiny-composite", "repro.kernels.ops.composite", altered),
    ("tiny-segment", "repro.kernels.ops.grad_mag", half_stack),
    ("tiny-segment", "repro.apps.segmentation.connected_components",
     relabelled),
])
def test_broken_timed_path_is_not_correct(root, monkeypatch, cell, target,
                                          fault):
    import importlib

    module_name, attr = target.rsplit(".", 1)
    module = importlib.import_module(module_name)
    monkeypatch.setattr(module, attr, fault(getattr(module, attr)))
    result = tinyroot.run(root, cell, seed=9)
    assert not result["correct"]
    assert result["failed"] == result["attempted"] >= 1


@pytest.mark.parametrize("cell", ["tiny-composite", "tiny-segment"])
def test_output_never_written_is_not_correct(root, monkeypatch, cell):
    from repro.core import chunkstore

    write = chunkstore.ChunkedArray.write_region

    def skip_window_writes(self, start, data):
        if not self.spec.name.startswith("out/"):
            return write(self, start, data)

    monkeypatch.setattr(chunkstore.ChunkedArray, "write_region",
                        skip_window_writes)
    result = tinyroot.run(root, cell, seed=10)
    assert not result["correct"]
