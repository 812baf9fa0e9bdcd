"""Field segmentation (paper §V.B): temporal edges -> fields -> polygons.

The paper's chain, stage by stage:

1. "for each image we apply a simple cloud mask ... and remove cloud pixels
   from the valid data region"                       -> cloud_score/valid
2. "compute the spatial gradient magnitude, ensuring that only changes
   across valid pixels produce nonzero gradients ... accumulated over the
   bands ... and over the images ... along with a count of how many times
   each pixel contained valid data"                  -> kernels grad_mag
3. "These quantities are divided pixelwise to produce a temporal-mean
   gradient image, which is then thresholded to produce a binary edge map"
4. "Morphological operations are used to clean up the edges"
5. "the non-edge pixels are separated into connected components ... labeled
   and polygonized, and the resulting polygons stored as a GeoJSON file"

Connected components run as an iterative min-label flood (jnp while_loop):
O(diameter) iterations of 4-neighbour min-pooling — the TPU-friendly
formulation of union-find.
"""

from __future__ import annotations

import json
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.festivus_imagery import ImageryConfig
from repro.apps.composite import cloud_score
from repro.core.spans import span, to_device, to_host
from repro.kernels import ops as kops


def temporal_edges(images: np.ndarray, valid: np.ndarray,
                   cfg: ImageryConfig, impl: str = "auto") -> np.ndarray:
    """Stages 1-3: temporal-mean gradient -> binary edge map [H, W] bool."""
    score = cloud_score(images, cfg)
    valid_d, score_d = to_device(valid, score)
    with span("dispatch"):
        valid_eff = valid_d & (score_d < 0.5)
    del valid_d, score_d  # the device frees them after the ops
    (stack,) = to_device(images)
    with span("dispatch"):
        gsum, count = kops.grad_mag(stack, valid_eff, impl=impl)
        del stack
        mean_grad = gsum / jnp.maximum(count, 1.0)
        edges = mean_grad > cfg.edge_threshold
    return to_host(edges)


def _binary_dilate(x: jnp.ndarray) -> jnp.ndarray:
    p = jnp.pad(x, 1)
    return (p[1:-1, 1:-1] | p[:-2, 1:-1] | p[2:, 1:-1]
            | p[1:-1, :-2] | p[1:-1, 2:])


def _binary_erode(x: jnp.ndarray) -> jnp.ndarray:
    p = jnp.pad(x, 1, constant_values=True)
    return (p[1:-1, 1:-1] & p[:-2, 1:-1] & p[2:, 1:-1]
            & p[1:-1, :-2] & p[1:-1, 2:])


def clean_edges(edges: np.ndarray, closing_steps: int = 1) -> np.ndarray:
    """Stage 4: morphological closing (dilate then erode) bridges one-pixel
    gaps in field boundaries without fattening them permanently."""
    (x,) = to_device(edges)
    with span("dispatch"):
        for _ in range(closing_steps):
            x = _binary_dilate(x)
        for _ in range(closing_steps):
            x = _binary_erode(x)
    return to_host(x)


@jax.jit
def connected_components(mask: jnp.ndarray) -> jnp.ndarray:
    """Label connected True regions of `mask` [H, W] -> int32 labels
    (0 = background).  Iterative min-label propagation to fixpoint."""
    h, w = mask.shape
    init = jnp.where(mask,
                     jnp.arange(1, h * w + 1, dtype=jnp.int32).reshape(h, w),
                     jnp.int32(0))
    big = jnp.int32(h * w + 2)

    def prop(labels):
        lab = jnp.where(mask, labels, big)
        p = jnp.pad(lab, 1, constant_values=big)
        neigh = jnp.minimum(
            jnp.minimum(p[:-2, 1:-1], p[2:, 1:-1]),
            jnp.minimum(p[1:-1, :-2], p[1:-1, 2:]))
        new = jnp.minimum(lab, neigh)
        return jnp.where(mask, new, 0)

    def cond(state):
        labels, changed = state
        return changed

    def body(state):
        labels, _ = state
        new = prop(labels)
        return new, jnp.any(new != labels)

    labels, _ = jax.lax.while_loop(cond, body, (init, jnp.bool_(True)))
    return labels


def polygonize(labels: np.ndarray, min_pixels: int = 8) -> Dict:
    """Stage 5: components -> GeoJSON-style feature collection.

    Each field becomes a feature with its bounding-box polygon, pixel count
    and centroid (the paper stores full boundary polygons; the bounding
    representation keeps this dependency-free while preserving the
    downstream contract: one feature per field, georeferencable geometry).
    """
    with span("polygonize"):
        labels = np.asarray(labels)
        ids, counts = np.unique(labels[labels > 0], return_counts=True)
        feats = []
        for lab, count in zip(ids, counts):
            if count < min_pixels:
                continue
            ys, xs = np.nonzero(labels == lab)
            y0, y1, x0, x1 = ys.min(), ys.max() + 1, xs.min(), xs.max() + 1
            feats.append({
                "type": "Feature",
                "properties": {"field_id": int(lab), "pixels": int(count),
                               "centroid": [float(xs.mean()),
                                            float(ys.mean())]},
                "geometry": {"type": "Polygon",
                             "coordinates": [[[int(x0), int(y0)],
                                              [int(x1), int(y0)],
                                              [int(x1), int(y1)],
                                              [int(x0), int(y1)],
                                              [int(x0), int(y0)]]]},
            })
        return {"type": "FeatureCollection", "features": feats}


def segment_tile(images: np.ndarray, valid: np.ndarray,
                 cfg: ImageryConfig, impl: str = "auto"
                 ) -> Tuple[np.ndarray, Dict]:
    """Full §V.B chain for one tile -> (labels [H, W], geojson dict)."""
    edges = temporal_edges(images, valid, cfg, impl=impl)
    edges = clean_edges(edges)
    (fields,) = to_device(~edges)
    with span("dispatch"):
        labels = connected_components(fields)
    del fields
    labels = to_host(labels)
    return labels, polygonize(labels)


def segment_to_store(cs, tile_name: str, cfg: ImageryConfig,
                     out_prefix: str = "fields") -> Dict:
    from repro.data import imagery

    imgs, valid = imagery.read_scene_stack(cs, tile_name)
    labels, geo = segment_tile(imgs, valid, cfg)
    arr = cs.create(f"{out_prefix}/{tile_name}/labels", labels.shape,
                    labels.dtype, labels.shape, codec="zlib")
    with span("write"):
        arr.write_region((0, 0), labels)
        cs.fs.write(f"{cs.root}/{out_prefix}/{tile_name}/fields.geojson",
                    json.dumps(geo).encode())
    return {"tile": tile_name, "fields": len(geo["features"])}


def run_segmentation_campaign(cs, tile_names, cfg: ImageryConfig,
                              out_prefix: str = "fields",
                              num_workers=None, engine_config=None) -> Dict:
    """Tile-per-task §V.B campaign through the scatter/gather cluster engine.

    Mirrors the composite campaign's contract: each simulated node mounts
    the campaign bucket via its own Festivus instance over `cs`'s shared
    object store + metadata KV, pulls tile tasks from the worker-pull
    queue, and writes the label array + GeoJSON for its tile (idempotent,
    disjoint outputs — safe under lease-expiry re-delivery and straggler
    speculation).  Returns the summary dict plus the full
    :class:`ClusterReport` under ``"report"``.
    """
    from repro.launch.cluster import ClusterEngine, campaign_config

    config = campaign_config(num_workers, engine_config)

    def handler(worker, tile_name: str):
        return segment_to_store(worker.chunkstore(cs.root), tile_name, cfg,
                                out_prefix)

    engine = ClusterEngine(cs.fs.store, meta=cs.fs.meta, config=config)
    report = engine.run({t: t for t in tile_names}, handler)
    report.raise_if_incomplete("segmentation")
    return {"tiles": len(tile_names), "stats": report.queue_stats,
            "report": report}
