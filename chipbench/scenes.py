"""Seeded Landsat-like tile stacks, made on the device.

The same scene model as the program's own host generator
(``repro.data.imagery``), rebuilt with ``jax.random`` so that a tile's
stack is made on the chip in milliseconds per timestep:

* fields: a seeded Voronoi partition of the tile with a constant
  reflectance per field and band;
* seasonality: a per-timestep verdancy factor on the NIR band;
* sensor noise: N(0, 0.01) on every pixel and band;
* clouds: smooth blobs, bright (0.7 + N(0, 0.02)) and flat in all bands;
  ``valid`` is "not cloudy".

Departures that keep the work the same from seed to seed: every timestep
has the same number of blobs, and the cloudy share of each timestep is
held at the traffic's ``cloud_cover`` by a threshold found by bisection,
where the host generator draws a Poisson number of blobs.  Where a traffic
mix names a ``layout_seed``, the field layouts, their reflectances and the
clouds come from it, the same for every run seed; the run seed then draws
the sensor noise and the order of the layouts.  (The segmentation's
connected components take as many passes as a field is long, so layouts
drawn from the run seed change the work from seed to seed.)

Bands: 0 = red, 1 = nir, 2 = green, 3 = blue, reflectance in [0, 1].
Nothing here imports the program: the reference regenerates its inputs
from the same functions.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SceneParams:
    px: int
    bands: int
    depth: int
    fields: int
    cloud_cover: float
    blobs: int = 3
    noise: float = 0.01
    cloud_level: float = 0.7
    cloud_noise: float = 0.02
    #: seed of the field layouts and clouds, or None: the run seed's
    layout_seed: Optional[int] = None
    #: layouts drawn from ``layout_seed`` (one per stored tile)
    layouts: int = 1


def tile_key(seed: int, tile: int) -> jax.Array:
    """Key of stored tile ``tile`` under run seed ``seed`` (any size of
    non-negative int: the high word is folded in, not dropped)."""
    key = jax.random.key(seed % 2**32)
    key = jax.random.fold_in(key, (seed >> 32) % 2**32)
    return jax.random.fold_in(key, tile)


@functools.partial(jax.jit, static_argnames=("px", "bands", "fields"))
def field_map(key, *, px: int, bands: int, fields: int):
    """(labels [px, px] int32, base reflectance [fields, bands] f32)."""
    kp, kb = jax.random.split(jax.random.fold_in(key, 0x6669656c))
    pts = jax.random.uniform(kp, (fields, 2), minval=0.0, maxval=float(px))
    base = jax.random.uniform(kb, (fields, bands), minval=0.05, maxval=0.45)
    yy = jnp.arange(px, dtype=jnp.float32)[:, None]
    xx = jnp.arange(px, dtype=jnp.float32)[None, :]

    def nearest(i, carry):
        best, label = carry
        d = (yy - pts[i, 0]) ** 2 + (xx - pts[i, 1]) ** 2
        closer = d < best
        return jnp.where(closer, d, best), jnp.where(closer, i, label)

    init = (jnp.full((px, px), jnp.inf, jnp.float32),
            jnp.zeros((px, px), jnp.int32))
    _, labels = jax.lax.fori_loop(0, fields, nearest, init)
    return labels, base


def _cloud_mask(key, px: int, blobs: int, cover: float) -> jax.Array:
    kc, kr = jax.random.split(key)
    centres = jax.random.uniform(kc, (blobs, 2), minval=0.0, maxval=float(px))
    radii = jax.random.uniform(kr, (blobs, 2), minval=px / 12, maxval=px / 3)
    yy = jnp.arange(px, dtype=jnp.float32)[:, None]
    xx = jnp.arange(px, dtype=jnp.float32)[None, :]
    field = jnp.zeros((px, px), jnp.float32)
    for b in range(blobs):
        field += jnp.exp(-(((yy - centres[b, 0]) / radii[b, 0]) ** 2
                           + ((xx - centres[b, 1]) / radii[b, 1]) ** 2))

    # the threshold above which `cover` of the tile lies, by bisection
    def halve(_, bounds):
        lo, hi = bounds
        mid = 0.5 * (lo + hi)
        above = jnp.mean((field > mid).astype(jnp.float32)) > cover
        return jnp.where(above, mid, lo), jnp.where(above, hi, mid)

    _, hi = jax.lax.fori_loop(0, 40, halve, (jnp.float32(0.0), field.max()))
    return field > hi


@functools.partial(jax.jit, static_argnames=("params",))
def timestep(layout, key, t, *, params: SceneParams):
    """Timestep ``t`` of a tile, bands-major: (image [bands, px, px] f32,
    valid [px, px] bool): fields, reflectances and clouds from the key
    ``layout``, noise from ``key``.  (With the bands minor, the chip's
    layout would pad them to 128 lanes: 32 times the memory.)  The field
    map is made again for each timestep, so that nothing stays on the
    device between calls."""
    p = params
    labels, base = field_map(layout, px=p.px, bands=p.bands, fields=p.fields)
    _, kc, _ = jax.random.split(jax.random.fold_in(layout, t), 3)
    kn, _, kcn = jax.random.split(jax.random.fold_in(key, t), 3)
    img = jnp.stack([jnp.take(base[:, c], labels) for c in range(p.bands)])
    season = 0.5 + 0.5 * jnp.sin(2 * math.pi * t / max(2, p.depth))
    img = img.at[1].set(jnp.clip(img[1] * (0.6 + 0.8 * season), 0.0, 1.0))
    img = img + p.noise * jax.random.normal(kn, img.shape, jnp.float32)
    cloudy = _cloud_mask(kc, p.px, p.blobs, p.cloud_cover)
    cloud = p.cloud_level + p.cloud_noise * jax.random.normal(
        kcn, img.shape, jnp.float32)
    img = jnp.where(cloudy[None], cloud, img)
    return jnp.clip(img, 0.0, 1.0).astype(jnp.float32), ~cloudy


@jax.jit
def interleaved(img):
    """[bands, px, px] -> [px, px * bands]: the bands minor, as the program
    stores them, in a shape the chip lays out without padding; the host
    reshapes it to [px, px, bands] for free."""
    c, h, w = img.shape
    return jnp.transpose(img, (1, 2, 0)).reshape(h, w * c)


class Tile:
    """One stored tile's generator: ``tile[t]`` is timestep t on the
    device, bands-major; ``tile.host(t)`` is the same timestep as the
    program stores it, [px, px, bands] and [px, px] NumPy arrays.  It
    holds only its key: nothing of it stays on the device."""

    def __init__(self, params: SceneParams, seed: int, index: int):
        self.params = params
        self.key = jax.device_get(jax.random.key_data(tile_key(seed, index)))
        self.layout = self.key
        if params.layout_seed is not None:
            self.layout = jax.device_get(jax.random.key_data(tile_key(
                params.layout_seed, (index + seed) % params.layouts)))

    def field_map(self):
        """(labels [px, px] int32, base reflectance [fields, bands])."""
        p = self.params
        return field_map(jax.random.wrap_key_data(self.layout), px=p.px,
                         bands=p.bands, fields=p.fields)

    def __getitem__(self, t: int):
        wrap = jax.random.wrap_key_data
        return timestep(wrap(self.layout), wrap(self.key), t,
                        params=self.params)

    def __len__(self) -> int:
        return self.params.depth

    def dispatch(self, t: int):
        """Start timestep t on the device, laid out for the host."""
        img, valid = self[t]
        return interleaved(img), valid

    def fetch(self, dispatched):
        """A dispatched timestep as NumPy arrays [px, px, bands], [px, px]."""
        import numpy as np

        img, valid = dispatched
        p = self.params
        return (np.asarray(img).reshape(p.px, p.px, p.bands),
                np.asarray(valid))

    def host(self, t: int):
        return self.fetch(self.dispatch(t))
