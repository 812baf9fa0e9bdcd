"""Plain reference of the §V.B field segmentation, from the paper's chain.

1. a cloud mask (bright and flat pixels, the same score as the composite's)
   removes cloudy pixels from each scene's valid region;
2. the spatial gradient magnitude over the bands, where a forward
   difference counts only when both of its pixels are valid, is summed
   over the valid scenes, beside a count of valid scenes per pixel:

       mag = sqrt(sum_c dx^2 + sum_c dy^2 + 1e-6)

3. their ratio, thresholded, is the edge map;
4. a morphological closing (4-neighbour dilation, then erosion) cleans it;
5. the 4-connected components of the non-edge pixels are the fields, each
   labelled by its smallest row-major pixel index plus one, and each field
   of at least ``min_px`` pixels is a feature: pixel count, bounding box
   and centroid.

In float32 (the reference) the cloud score of step 1 is computed on the
host in NumPy, whose float32 division is correctly rounded, so the mask is
the exact function of the stored pixels; steps 2 to 4 run on the device.
In bfloat16 (the control) steps 1 to 4 all run on the device in bfloat16.
Step 5 runs on the host with SciPy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from scipy import ndimage

from chipbench.references.composite import cloud_score

EPS = 1e-6


def host_cloud_score(x: np.ndarray, threshold: float) -> np.ndarray:
    """The cloud score of bands-major float32 reflectances [C, H, W], in
    float32 on the host (the formula of ``references.composite``)."""
    red, nir, green = x[0], x[1], x[2]
    brightness = (red + nir + green) / np.float32(3)
    flatness = np.float32(1) - np.abs(red - green)
    return (np.clip((brightness - np.float32(threshold)) * np.float32(4), 0, 1)
            * np.clip(flatness, 0, 1))


@functools.partial(jax.jit, static_argnames=("threshold", "dtype"),
                   donate_argnums=(0, 1))
def _accumulate(gsum, count, img, v, *, threshold: float, dtype):
    x = img.astype(dtype)
    if threshold is not None:  # the control masks on the device
        v = v & (cloud_score(x, threshold) < 0.5)
    zero = jnp.zeros((), dtype)
    dx = jnp.where((v[:, 1:] & v[:, :-1])[None], x[:, :, 1:] - x[:, :, :-1],
                   zero)
    dy = jnp.where((v[1:] & v[:-1])[None], x[:, 1:] - x[:, :-1], zero)
    dx = jnp.pad(dx, ((0, 0), (0, 0), (0, 1)))
    dy = jnp.pad(dy, ((0, 0), (0, 1), (0, 0)))
    mag = jnp.sqrt(jnp.sum(dx * dx, axis=0) + jnp.sum(dy * dy, axis=0)
                   + EPS)
    vf = v.astype(dtype)
    return gsum + mag * vf, count + vf


def grad_sums(tile, cloud_threshold: float, dtype=jnp.float32):
    """(grad_sum, count) [H, W], as float32 NumPy arrays, of the stack
    that ``tile`` (a ``scenes.Tile``) generates."""
    p = tile.params
    on_host = dtype == jnp.float32
    with jax.default_matmul_precision("highest"):
        gsum = jnp.zeros((p.px, p.px), dtype)
        count = jnp.zeros((p.px, p.px), dtype)
        for t in range(p.depth):
            img, valid = tile[t]
            if on_host:
                x = np.asarray(img)
                valid = np.asarray(valid) & (
                    host_cloud_score(x, cloud_threshold) < 0.5)
            gsum, count = _accumulate(
                gsum, count, img, jnp.asarray(valid),
                threshold=None if on_host else cloud_threshold, dtype=dtype)
        return (np.asarray(gsum.astype(jnp.float32)),
                np.asarray(count.astype(jnp.float32)))


@functools.partial(jax.jit, static_argnames=("threshold", "steps"))
def _edges(gsum, count, *, threshold: float, steps: int):
    edges = gsum / jnp.maximum(count, 1) > threshold
    for _ in range(steps):
        p = jnp.pad(edges, 1)
        edges = (p[1:-1, 1:-1] | p[:-2, 1:-1] | p[2:, 1:-1]
                 | p[1:-1, :-2] | p[1:-1, 2:])
    for _ in range(steps):
        p = jnp.pad(edges, 1, constant_values=True)
        edges = (p[1:-1, 1:-1] & p[:-2, 1:-1] & p[2:, 1:-1]
                 & p[1:-1, :-2] & p[1:-1, 2:])
    return edges


def edges(gsum: np.ndarray, count: np.ndarray, edge_threshold: float,
          closing_steps: int, dtype=jnp.float32) -> np.ndarray:
    """The closed edge map [H, W] bool from the sums, in ``dtype``."""
    return np.asarray(_edges(jnp.asarray(gsum, dtype),
                             jnp.asarray(count, dtype),
                             threshold=edge_threshold, steps=closing_steps))


def fields(edge_map: np.ndarray, min_px: int):
    """(labels [H, W] int32, features) of the non-edge components.

    ``features`` maps each field's label to (pixels, (x0, y0, x1, y1),
    (centroid x, centroid y)), with the bounding box half-open."""
    raw, n = ndimage.label(~edge_map)  # 4-connected by default in 2-D
    h, w = edge_map.shape
    labels = np.zeros((h, w), np.int32)
    pixels = np.bincount(raw.ravel(), minlength=n + 1)
    ys, xs = np.indices((h, w), dtype=np.float64)
    index = np.arange(1, n + 1)
    sum_y = ndimage.sum_labels(ys, raw, index)
    sum_x = ndimage.sum_labels(xs, raw, index)
    features = {}
    for k, box in enumerate(ndimage.find_objects(raw), start=1):
        if box is None:
            continue
        rows, cols = box
        first = cols.start + int(np.argmax(raw[rows.start, cols] == k))
        label = rows.start * w + first + 1
        labels[box][raw[box] == k] = label
        count = int(pixels[k])
        if count >= min_px:
            features[label] = (count,
                               (cols.start, rows.start, cols.stop, rows.stop),
                               (sum_x[k - 1] / count, sum_y[k - 1] / count))
    return labels, features
