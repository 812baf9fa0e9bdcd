"""Cloud-free composite (paper §V.C), tile-parallel over the task queue.

"The output is a weighted average of this imagery, with higher weight given
to cloud-free, verdant input images. ... The work was easily parallelized by
dividing the earth's surface into 43k square tiles; each tile was processed
independently."

Per-tile compute is the Pallas `composite` kernel (jnp oracle off-TPU);
weights combine the cloud mask with NDVI verdancy, exactly the paper's
recipe.  The campaign driver is the scatter/gather cluster engine
(`repro.launch.cluster`): each simulated node gets its own festivus mount
over the campaign's shared store + metadata KV and pulls tile tasks from
the worker-pull queue of §V.A.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.festivus_imagery import ImageryConfig
from repro.core.chunkstore import ChunkStore
from repro.core.spans import span, to_device, to_host
from repro.data import imagery
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.launch.cluster import (
    ClusterConfig,
    ClusterEngine,
    Worker,
    campaign_config,
)


def cloud_score(images: np.ndarray | jax.Array,
                cfg: ImageryConfig) -> np.ndarray | jax.Array:
    """Simple reflectance cloud mask ([12] Oreopoulos et al. in the paper):
    clouds are bright and spectrally flat.  images [T, H, W, C] -> [T, H, W]
    score in [0, 1].  A host array is scored in NumPy; a ``jax.Array`` by
    the same formula in a device program, returned without waiting."""
    with span("band_math"):
        if isinstance(images, jax.Array):
            return _device_cloud_score(images,
                                       cfg.cloud_reflectance_threshold)
        brightness = images[..., :3].mean(axis=-1)
        flatness = 1.0 - np.abs(images[..., 0] - images[..., 2])
        score = np.clip(
            (brightness - cfg.cloud_reflectance_threshold) * 4.0, 0.0, 1.0)
        return score * np.clip(flatness, 0.0, 1.0)


@functools.partial(jax.jit, static_argnames=("threshold",))
def _device_cloud_score(images: jax.Array, threshold: float) -> jax.Array:
    """:func:`cloud_score`'s NumPy formula, in f32 on the device."""
    brightness = images[..., :3].mean(axis=-1)
    flatness = 1.0 - jnp.abs(images[..., 0] - images[..., 2])
    score = jnp.clip((brightness - threshold) * 4.0, 0.0, 1.0)
    return score * jnp.clip(flatness, 0.0, 1.0)


@jax.jit
def composite_weights(images: jax.Array, score: jax.Array) -> jax.Array:
    """The paper's weights of a stack [T, H, W, C] and its cloud score, in
    one program that slices the stack's nir and red bands itself."""
    return kref.composite_weights(score, nir=images[..., 1],
                                  red=images[..., 0])


def composite_tile(images: np.ndarray, cfg: ImageryConfig,
                   impl: str = "auto") -> np.ndarray:
    """One tile: [T, H, W, C] stack -> [H, W, C] cloud-free composite.  The
    stack crosses to the device once; score, weights and composite are
    computed there."""
    (stack,) = to_device(images)
    score = cloud_score(stack, cfg)
    with span("dispatch"):
        weights = composite_weights(stack, score)
        del score  # freed once the weights are done, before the kernel runs
        out = kops.composite(stack, weights, impl=impl)
    del stack, weights
    return to_host(out)


def run_composite_campaign(cs: ChunkStore, tile_names: Sequence[str],
                           cfg: ImageryConfig, out_prefix: str = "composite",
                           num_workers: Optional[int] = None,
                           engine_config: Optional[ClusterConfig] = None) -> Dict:
    """Tile-per-task campaign through the scatter/gather cluster engine.

    Each simulated node (`num_workers` of them, default 4; or
    `engine_config.nodes` when a full config is supplied — passing both
    inconsistently raises) mounts the campaign bucket via its own Festivus
    instance over `cs`'s shared object store and metadata KV, so the
    caller's mount sees every output the fleet writes.  Returns the legacy
    summary dict plus the full :class:`ClusterReport` under ``"report"``
    (per-node stats, aggregate bandwidth, queue counters).
    """
    config = campaign_config(num_workers, engine_config)

    def handler(worker: Worker, tile_name: str):
        wcs = worker.chunkstore(cs.root)
        imgs, _ = imagery.read_scene_stack(wcs, tile_name)
        comp = composite_tile(imgs, cfg)
        arr = wcs.create(f"{out_prefix}/{tile_name}", comp.shape, comp.dtype,
                         (min(cfg.chunk_px, comp.shape[0]),
                          min(cfg.chunk_px, comp.shape[1]), comp.shape[2]),
                         codec="zlib", pyramid_levels=2)
        with span("write"):
            arr.write_region((0, 0, 0), comp)
        arr.build_pyramid()  # the JPX multi-resolution serving layer
        return {"tile": tile_name, "mean": float(comp.mean())}

    engine = ClusterEngine(cs.fs.store, meta=cs.fs.meta, config=config)
    report = engine.run({t: t for t in tile_names}, handler)
    report.raise_if_incomplete("composite")
    return {"tiles": len(tile_names), "stats": report.queue_stats,
            "report": report}
