"""Ahead-of-time compiles of the §V kernels for a v5e chip, at paper widths.

The TPU compiler compiles for a described (not attached) v5e, so these run
on a CPU-only host.  They catch what interpret mode cannot: a block that
overflows VMEM, a program that overflows the chip's 16 GB of HBM, and the
band axis landing on the 128 lanes of an HBM operand (which pads a 4-band
f32 stack 32x).  The topology is described inside a fixture, never while a
module is imported: only one process may load the TPU library at a time.
The block rows the kernels take for the chip's sublane tiling are checked
here too.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.backend import row_block
from repro.kernels.composite import composite_fwd
from repro.kernels.grad_mag import grad_mag_fwd

BANDS = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure: no compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh_2x2(topo):
    from jax.sharding import Mesh

    from repro.apps.segmentation import COLS, ROWS

    return Mesh(np.array(topo.devices).reshape(2, 2), (ROWS, COLS))


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.mark.parametrize("h,block_h,dtype,rows", [
    (4096, 8, jnp.float32, 8),
    (4096, 8, jnp.bfloat16, 16),   # 16-bit types pack 16 rows per tile
    (4096, 5, jnp.float32, 8),
    (36, 4, jnp.float32, 36),      # no whole-tile block divides 36
    (8, 8, jnp.bfloat16, 8),
])
def test_row_block_fills_whole_sublane_tiles(h, block_h, dtype, rows):
    assert row_block(h, block_h, dtype) == rows


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(functools.partial(fn, interpret=False)).lower(*args).compile()


def _check(compiled, shapes):
    assert "tpu_custom_call" in compiled.as_text()
    unpadded = sum(int(np.prod(s)) * jnp.dtype(d).itemsize for s, d in shapes)
    arg_bytes = compiled.memory_analysis().argument_size_in_bytes
    assert arg_bytes <= 1.3 * unpadded, (arg_bytes, unpadded)


@pytest.mark.parametrize("width,depth", [(4096, 16), (6144, 8)])
def test_composite_compiles_at_paper_width(width, depth, one_chip,
                                           no_compile_cache):
    shapes = [((depth, width, width, BANDS), jnp.float32),
              ((depth, width, width), jnp.float32)]
    _check(_compile(composite_fwd, one_chip, *shapes), shapes)


@pytest.mark.parametrize("program", ["cloud_score", "weights"])
def test_composite_band_math_compiles_at_paper_width(program, one_chip,
                                                     no_compile_cache):
    """The composite's score and weights programs read the stack as it
    lands on the device: no lane padding of the bands, output one f32
    plane per scene."""
    from repro.apps.composite import _device_cloud_score, composite_weights
    from repro.configs.festivus_imagery import DEFAULT

    depth, width = 16, 4096
    shapes = [((depth, width, width, BANDS), jnp.float32)]
    if program == "weights":
        shapes.append(((depth, width, width), jnp.float32))
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    if program == "cloud_score":
        lowered = _device_cloud_score.lower(
            *args, threshold=DEFAULT.cloud_reflectance_threshold)
    else:
        lowered = composite_weights.lower(*args)
    memory = lowered.compile().memory_analysis()
    unpadded = sum(int(np.prod(s)) * jnp.dtype(d).itemsize for s, d in shapes)
    assert memory.argument_size_in_bytes <= 1.3 * unpadded
    assert memory.output_size_in_bytes == 4 * depth * width * width


@pytest.mark.parametrize("width,depth", [(4096, 4), (6144, 4)])
def test_grad_mag_compiles_at_paper_width(width, depth, one_chip,
                                          no_compile_cache):
    shapes = [((depth, width, width, BANDS), jnp.float32),
              ((depth, width, width), jnp.bool_)]
    _check(_compile(grad_mag_fwd, one_chip, *shapes), shapes)


#: one v5e chip's HBM, as its compiler counts it (15.75 GiB)
HBM_BYTES = 16909336064


@pytest.mark.parametrize("program", ["split_grad_mag", "split_components"])
def test_split_segmentation_compiles_on_a_2x2_mesh(program, mesh_2x2,
                                                   no_compile_cache,
                                                   monkeypatch):
    """The §V.B tile at the paper's depth of 16, split 2x2: each chip's
    shard lands unpadded, and each chip's plan fits its memory."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.apps import segmentation as seg
    from repro.configs.festivus_imagery import DEFAULT
    from repro.kernels import grad_mag

    # the kernel as the chip compiles it, not the CPU's interpreter
    monkeypatch.setattr(grad_mag, "resolve_interpret", lambda _: False)

    depth, width = DEFAULT.temporal_depth, DEFAULT.segmentation_tile_px
    rows, cols = seg.ROWS, seg.COLS
    if program == "split_grad_mag":
        shapes = [((depth, width, width, BANDS), jnp.float32,
                   P(None, rows, cols, None)),
                  ((depth, width, width), jnp.bool_, P(None, rows, cols))]
        args = [jax.ShapeDtypeStruct(s, d,
                                     sharding=NamedSharding(mesh_2x2, spec))
                for s, d, spec in shapes]
        lowered = seg.split_grad_mag.lower(
            *args, mesh=mesh_2x2, threshold=DEFAULT.edge_threshold)
    else:
        shapes = [((width, width), jnp.bool_, P(rows, cols))]
        args = [jax.ShapeDtypeStruct(s, d,
                                     sharding=NamedSharding(mesh_2x2, spec))
                for s, d, spec in shapes]
        lowered = seg.split_components.lower(*args, mesh=mesh_2x2)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "collective-permute" in text
    assert ("tpu_custom_call" in text) == (program == "split_grad_mag")
    memory = compiled.memory_analysis()
    shard = sum(int(np.prod(s)) * jnp.dtype(d).itemsize
                for s, d, _ in shapes) // 4
    assert memory.argument_size_in_bytes <= 1.3 * shard
    planned = (memory.argument_size_in_bytes + memory.output_size_in_bytes
               + memory.temp_size_in_bytes - memory.alias_size_in_bytes)
    assert planned < HBM_BYTES, planned
