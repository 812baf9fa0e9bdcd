"""Weighted temporal composite (Pallas TPU) — paper §V.C.

"The output is a weighted average of this imagery, with higher weight given
to cloud-free, verdant input images."

The paper's CPU implementation fought NumPy intermediate copies and memory
ceilings (§V.A); the TPU-native formulation streams the time axis through
VMEM accumulators instead:

* Grid ``(H/block_h, T)`` — T is the trailing (sequential) axis, so the
  weighted-sum and weight-sum accumulators live in VMEM scratch across the
  whole time stack; the kernel reads each input strip from HBM exactly once.
* Block = a (C, block_h, W) bands-major image strip: W sits on the 128
  lanes and block_h on the sublanes.  With the 4 bands minor, as the public
  [T, H, W, C] layout has them, Mosaic pads the band axis to 128 lanes both
  in VMEM and in the HBM operand (32x the data for 4 bands), and a
  paper-width (4096 or 6144) tile compiles for neither.  The wrapper
  transposes to [T, C, H, W] and back, inside the caller's jit; that
  transpose is one stack-sized copy in HBM.
* Accumulation in f32 regardless of input dtype (bf16-safe over long
  stacks: Landsat revisits give T of O(100)).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret, row_block


def _composite_kernel(img_ref, w_ref, o_ref, num_scratch, den_scratch, *,
                      eps: float):
    t = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(t == 0)
    def _init():
        num_scratch[...] = jnp.zeros_like(num_scratch)
        den_scratch[...] = jnp.zeros_like(den_scratch)

    img = img_ref[0].astype(jnp.float32)      # [C, bh, W]
    w = w_ref[0].astype(jnp.float32)          # [bh, W]
    num_scratch[...] += img * w[None]
    den_scratch[...] += w

    @pl.when(t == nt - 1)
    def _finish():
        den = den_scratch[...][None] + eps
        o_ref[...] = (num_scratch[...] / den).astype(o_ref.dtype)


def composite_fwd(images: jax.Array, weights: jax.Array, *,
                  block_h: int = 8, eps: float = 1e-6,
                  interpret: bool | None = None) -> jax.Array:
    """images: [T, H, W, C]; weights: [T, H, W] -> [H, W, C].

    ``interpret=None`` detects the backend once (TPU -> compiled kernel,
    anything else -> Pallas interpreter); pass a bool to override.
    """
    interpret = resolve_interpret(interpret)
    T, H, W, C = images.shape
    if weights.shape != (T, H, W):
        raise ValueError(f"weights {weights.shape} != {(T, H, W)}")
    block_h = row_block(H, block_h, images.dtype, weights.dtype)
    grid = (H // block_h, T)
    out = pl.pallas_call(
        functools.partial(_composite_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, C, block_h, W), lambda i, t: (t, 0, i, 0)),
            pl.BlockSpec((1, block_h, W), lambda i, t: (t, i, 0)),
        ],
        out_specs=pl.BlockSpec((C, block_h, W), lambda i, t: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((C, H, W), images.dtype),
        scratch_shapes=[
            pltpu.VMEM((C, block_h, W), jnp.float32),
            pltpu.VMEM((block_h, W), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.transpose(images, (0, 3, 1, 2)), weights)
    return jnp.transpose(out, (1, 2, 0))
