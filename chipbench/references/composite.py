"""Plain reference of the §V.C composite, written from the paper's recipe.

"The output is a weighted average of this imagery, with higher weight
given to cloud-free, verdant input images."  Per pixel and timestep:

    brightness = (red + nir + green) / 3
    flatness   = 1 - |red - green|
    cloud      = clip(4 (brightness - threshold), 0, 1) * clip(flatness, 0, 1)
    ndvi       = (nir - red) / (nir + red + 1e-6)
    weight     = (1 - cloud) * (0.25 + 0.75 clip(ndvi, 0, 1))
    composite  = sum_t weight x / (sum_t weight + 1e-6)

and the pyramid's level k is level k-1 pooled by 2x2 means.  The stack is
regenerated timestep by timestep from the seed, bands-major, so the device
holds two accumulators and one scene at a time.  ``dtype`` is the
precision of all arithmetic: float32 is the reference, bfloat16 the
control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6


def cloud_score(x, threshold):
    """Cloud score of bands-major reflectances [bands, ...]."""
    red, nir, green = x[0], x[1], x[2]
    brightness = (red + nir + green) / 3
    flatness = 1 - jnp.abs(red - green)
    return (jnp.clip((brightness - threshold) * 4, 0, 1)
            * jnp.clip(flatness, 0, 1))


@functools.partial(jax.jit, static_argnames=("threshold", "dtype"),
                   donate_argnums=(0, 1))
def _accumulate(num, den, img, *, threshold: float, dtype):
    x = img.astype(dtype)
    red, nir = x[0], x[1]
    ndvi = (nir - red) / (nir + red + EPS)
    w = ((1 - cloud_score(x, threshold))
         * (0.25 + 0.75 * jnp.clip(ndvi, 0, 1))).astype(dtype)
    return num + w[None] * x, den + w


@functools.partial(jax.jit, static_argnames=("levels",))
def _finish(num, den, *, levels: int):
    comp = (num / (den[None] + EPS)).astype(jnp.float32)
    top = comp
    for _ in range(levels):
        top = (top[:, 0::2, 0::2] + top[:, 1::2, 0::2]
               + top[:, 0::2, 1::2] + top[:, 1::2, 1::2]) / 4
    return comp, top


def composite(tile, threshold: float, levels: int, dtype=jnp.float32):
    """(composite [H, W, C], pyramid level ``levels`` [H/2^l, W/2^l, C]),
    float32 NumPy arrays, of the stack that ``tile`` (a ``scenes.Tile``)
    generates."""
    p = tile.params
    with jax.default_matmul_precision("highest"):
        num = jnp.zeros((p.bands, p.px, p.px), dtype)
        den = jnp.zeros((p.px, p.px), dtype)
        for t in range(p.depth):
            img, _ = tile[t]
            num, den = _accumulate(num, den, img, threshold=threshold,
                                   dtype=dtype)
        comp, top = _finish(num, den, levels=levels)
    # bands last, as the program stores them, on the host: the chip would
    # pad a 4-band minor axis to 128 lanes
    return (np.asarray(comp).transpose(1, 2, 0),
            np.asarray(top).transpose(1, 2, 0))
