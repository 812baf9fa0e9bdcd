"""Cloud-masked temporal gradient accumulation (Pallas TPU) — paper §V.B.

The field-segmentation front end: "we compute the spatial gradient
magnitude, ensuring that only changes across valid pixels produce nonzero
gradients ... accumulated over the bands of each image and over the images
available in the chosen time interval, along with a count of how many times
each pixel contained valid data."

TPU adaptation: spatial differencing needs each pixel's east and south
neighbours.  Pallas TPU BlockSpecs tile disjointly (no halo exchange), so
the wrapper materializes shifted views (x shifted one column / one row, and
likewise for the validity mask) and the kernel is then a pure streaming
map-accumulate over the time axis with VMEM accumulators — the same
sequential-T grid pattern as the composite kernel.  The shifted views cost
one extra HBM read per input; on TPU they would be produced by the XLA
fusion feeding the kernel.  Boundary semantics match the oracle: shifted
validity is zero outside the frame, so edge pixels contribute no gradient.
A shard of a split frame passes its east and south neighbours' first column
and row instead (``east``/``south``), so that its last column and row see
the pixels beyond the seam.

Layout: the kernel streams bands-major ``(C, block_h, W)`` strips, so W sits
on the 128 lanes (see :mod:`repro.kernels.composite` for why the public
band-minor layout cannot compile at paper widths).  The wrapper transposes
``[T, H, W, C]`` to ``[T, C, H, W]`` before shifting, inside the caller's jit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret, row_block


def _grad_kernel(x_ref, xe_ref, xs_ref, v_ref, ve_ref, vs_ref,
                 g_ref, c_ref, gs, cs, *, eps: float):
    t = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(t == 0)
    def _init():
        gs[...] = jnp.zeros_like(gs)
        cs[...] = jnp.zeros_like(cs)

    x = x_ref[0].astype(jnp.float32)    # [C, bh, W]
    xe = xe_ref[0].astype(jnp.float32)  # east-shifted
    xs = xs_ref[0].astype(jnp.float32)  # south-shifted
    v = v_ref[0].astype(jnp.float32)    # [bh, W]
    ve = ve_ref[0].astype(jnp.float32)
    vs = vs_ref[0].astype(jnp.float32)

    vx = (v * ve)[None]
    vy = (v * vs)[None]
    dx = (xe - x) * vx
    dy = (xs - x) * vy
    mag = jnp.sqrt(jnp.sum(dx * dx, axis=0) + jnp.sum(dy * dy, axis=0) + eps)
    gs[...] += mag * v
    cs[...] += v

    @pl.when(t == nt - 1)
    def _finish():
        g_ref[...] = gs[...].astype(g_ref.dtype)
        c_ref[...] = cs[...].astype(c_ref.dtype)


def grad_mag_fwd(images: jax.Array, valid: jax.Array, *, east=None,
                 south=None, block_h: int = 8, eps: float = 1e-6,
                 interpret: bool | None = None):
    """images: [T, H, W, C]; valid: [T, H, W] -> (grad_sum, count) [H, W].

    Matches kernels.ref.grad_mag exactly (same forward-difference, same
    both-pixels-valid gating, same sqrt(.+eps)).  ``interpret=None``
    detects the backend once (TPU -> compiled, else interpreter).

    ``east`` is the column just east of the frame, as (images [T, H, C],
    valid [T, H]), and ``south`` the row just south of it, as (images
    [T, W, C], valid [T, W]); each defaults to an invalid (zero) edge, the
    frame's own boundary.
    """
    interpret = resolve_interpret(interpret)
    T, H, W, C = images.shape
    if valid.shape != (T, H, W):
        raise ValueError(f"valid {valid.shape} != {(T, H, W)}")
    block_h = row_block(H, block_h, images.dtype)

    imf = jnp.transpose(images, (0, 3, 1, 2))  # [T, C, H, W]
    vf = valid.astype(images.dtype)
    # out of the frame, the shifted pixels are invalid (zero)
    if east is None:
        east = (jnp.zeros((T, H, C), images.dtype), jnp.zeros((T, H), bool))
    if south is None:
        south = (jnp.zeros((T, W, C), images.dtype), jnp.zeros((T, W), bool))
    # east neighbour (shift left along W)
    xe = jnp.concatenate(
        [imf[..., 1:], jnp.transpose(east[0], (0, 2, 1))[..., None]], axis=3)
    ve = jnp.concatenate(
        [vf[:, :, 1:], east[1].astype(images.dtype)[..., None]], axis=2)
    # south neighbour (shift up along H)
    xs = jnp.concatenate(
        [imf[:, :, 1:, :], jnp.transpose(south[0], (0, 2, 1))[:, :, None, :]],
        axis=2)
    vs = jnp.concatenate(
        [vf[:, 1:, :], south[1].astype(images.dtype)[:, None, :]], axis=1)

    grid = (H // block_h, T)
    img_spec = pl.BlockSpec((1, C, block_h, W), lambda i, t: (t, 0, i, 0))
    msk_spec = pl.BlockSpec((1, block_h, W), lambda i, t: (t, i, 0))
    out_spec = pl.BlockSpec((block_h, W), lambda i, t: (i, 0))
    return pl.pallas_call(
        functools.partial(_grad_kernel, eps=eps),
        grid=grid,
        in_specs=[img_spec, img_spec, img_spec, msk_spec, msk_spec, msk_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((H, W), jnp.float32),
                   jax.ShapeDtypeStruct((H, W), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_h, W), jnp.float32),
                        pltpu.VMEM((block_h, W), jnp.float32)],
        interpret=interpret,
    )(imf, xe, xs, vf, ve, vs)
