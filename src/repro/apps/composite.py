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

from typing import Dict, Optional, Sequence

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


def cloud_score(images: np.ndarray, cfg: ImageryConfig) -> np.ndarray:
    """Simple reflectance cloud mask ([12] Oreopoulos et al. in the paper):
    clouds are bright and spectrally flat.  images [T, H, W, C] -> [T, H, W]
    score in [0, 1]."""
    with span("band_math"):
        brightness = images[..., :3].mean(axis=-1)
        flatness = 1.0 - np.abs(images[..., 0] - images[..., 2])
        score = np.clip(
            (brightness - cfg.cloud_reflectance_threshold) * 4.0, 0.0, 1.0)
        return score * np.clip(flatness, 0.0, 1.0)


def composite_tile(images: np.ndarray, cfg: ImageryConfig,
                   impl: str = "auto") -> np.ndarray:
    """One tile: [T, H, W, C] stack -> [H, W, C] cloud-free composite."""
    score = cloud_score(images, cfg)
    unread, score_d, nir, red = to_device(images, score, images[..., 1],
                                          images[..., 0])
    with span("dispatch"):
        weights = kref.composite_weights(unread, score_d, nir=nir, red=red)
    del unread, score_d, nir, red  # the device frees them after the call
    (stack,) = to_device(images)
    with span("dispatch"):
        out = kops.composite(stack, weights, impl=impl)
    del stack
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
