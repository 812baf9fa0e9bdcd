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

A tile too deep for one chip is split by rows and columns over a mesh of
chips (``ImageryConfig.segmentation_mesh``).  Each chip holds one shard of
the stack; the gradient reads its neighbours' first row and column, the
closing a halo of its neighbours' edge map, and the components start from
global pixel indices and alternate a local flood with an exchange of edge
labels until no shard changes, so the labels are those of the whole tile.
"""

from __future__ import annotations

import functools
import json
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.festivus_imagery import ImageryConfig
from repro.apps.composite import cloud_score
from repro.core.spans import span, to_device, to_host
from repro.kernels import ops as kops
from repro.kernels.grad_mag import grad_mag_fwd


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


# -- one tile split over a mesh of chips --------------------------------------
#: mesh axes: rows of the frame over "y", columns over "x"
ROWS, COLS = "y", "x"


def segmentation_mesh(shape: Tuple[int, int]) -> Mesh:
    """The first rows x cols devices as a (rows, cols) mesh."""
    rows, cols = shape
    devices = jax.devices()
    if len(devices) < rows * cols:
        raise ValueError(f"segmentation mesh {shape} needs {rows * cols} "
                         f"devices; JAX found {len(devices)}")
    return Mesh(np.array(devices[:rows * cols]).reshape(rows, cols),
                (ROWS, COLS))


def _from_neighbour(x, axis: str, size: int, step: int, fill):
    """In each shard, ``x`` of the shard ``step`` (+1 or -1) further along
    mesh axis ``axis``; ``fill`` where that lies beyond the frame."""
    if size == 1:
        return jnp.full_like(x, fill)
    perm = [(k + step, k) for k in range(size) if 0 <= k + step < size]
    got = jax.lax.ppermute(x, axis, perm)
    k = jax.lax.axis_index(axis) + step
    return jnp.where((0 <= k) & (k < size), got, jnp.asarray(fill, x.dtype))


def _with_halo(x, k: int, rows: int, cols: int, fill):
    """A shard [h, w] with ``k`` rows and columns of its neighbours' on
    each side, corners included (rows first, then columns of the taller
    block): [h + 2k, w + 2k]; ``fill`` beyond the frame."""
    x = jnp.concatenate([_from_neighbour(x[-k:], ROWS, rows, -1, fill), x,
                         _from_neighbour(x[:k], ROWS, rows, 1, fill)], axis=0)
    return jnp.concatenate([_from_neighbour(x[:, -k:], COLS, cols, -1, fill),
                            x, _from_neighbour(x[:, :k], COLS, cols, 1, fill)],
                           axis=1)


def _split_grad_shard(x, v, *, rows, cols, threshold, steps):
    """One shard: the masked temporal gradient, its threshold and the
    closing -> (grad_sum, count, fields = not an edge)."""
    h, w = v.shape[1:]
    east = (_from_neighbour(x[:, :, 0], COLS, cols, 1, 0),
            _from_neighbour(v[:, :, 0], COLS, cols, 1, False))
    south = (_from_neighbour(x[:, 0], ROWS, rows, 1, 0),
             _from_neighbour(v[:, 0], ROWS, rows, 1, False))
    gsum, count = grad_mag_fwd(x, v, east=east, south=south)
    edges = gsum / jnp.maximum(count, 1.0) > threshold
    # the closing reads 2 * steps pixels around each pixel; beyond the
    # frame the dilation sees False and the erosion True, as with the
    # whole frame's padding
    k = 2 * steps
    if k:
        gy = (jax.lax.axis_index(ROWS) * h - k
              + jnp.arange(h + 2 * k))[:, None]
        gx = (jax.lax.axis_index(COLS) * w - k
              + jnp.arange(w + 2 * k))[None, :]
        inside = (gy >= 0) & (gy < rows * h) & (gx >= 0) & (gx < cols * w)
        x = _with_halo(edges, k, rows, cols, False)
        for _ in range(steps):
            x = _binary_dilate(x & inside)
        for _ in range(steps):
            x = _binary_erode(x | ~inside)
        edges = x[k:k + h, k:k + w]
    return gsum, count, ~edges


@functools.partial(jax.jit, static_argnames=("mesh", "threshold", "steps"))
def split_grad_mag(stack, mask, *, mesh: Mesh, threshold: float,
                   steps: int = 1):
    """Stages 2-4 on a stack [T, H, W, C] and effective mask [T, H, W]
    split over ``mesh`` -> (grad_sum, count, fields) [H, W], split alike:
    fields are the non-edge pixels after ``steps`` of closing."""
    rows, cols = mesh.shape[ROWS], mesh.shape[COLS]
    return jax.shard_map(
        functools.partial(_split_grad_shard, rows=rows, cols=cols,
                          threshold=threshold, steps=steps),
        mesh=mesh, in_specs=(P(None, ROWS, COLS, None), P(None, ROWS, COLS)),
        # the kernel's outputs carry no mesh-axis types
        out_specs=(P(ROWS, COLS),) * 3, check_vma=False)(stack, mask)


def _split_components_shard(fields, *, rows, cols):
    """One shard: labels from global row-major indices, a local flood to
    fixpoint, then an exchange of edge labels, in rounds until no shard
    changes -> (labels, rounds)."""
    h, w = fields.shape
    width = cols * w
    gy = jax.lax.axis_index(ROWS) * h + jnp.arange(h, dtype=jnp.int32)
    gx = jax.lax.axis_index(COLS) * w + jnp.arange(w, dtype=jnp.int32)
    init = jnp.where(fields, gy[:, None] * width + gx[None, :] + 1,
                     jnp.int32(0))
    big = jnp.int32(rows * h * width + 2)

    def flood(labels, north, south, west, east):
        def prop(lab):
            lab = jnp.where(fields, lab, big)
            up = jnp.concatenate([north[None], lab[:-1]], axis=0)
            down = jnp.concatenate([lab[1:], south[None]], axis=0)
            left = jnp.concatenate([west[:, None], lab[:, :-1]], axis=1)
            right = jnp.concatenate([lab[:, 1:], east[:, None]], axis=1)
            new = jnp.minimum(lab, jnp.minimum(jnp.minimum(up, down),
                                               jnp.minimum(left, right)))
            return jnp.where(fields, new, 0)

        def body(state):
            lab, _ = state
            new = prop(lab)
            return new, jnp.any(new != lab)

        # each shard floods until its own labels settle
        going = jax.lax.pcast(jnp.bool_(True), (ROWS, COLS), to="varying")
        return jax.lax.while_loop(lambda s: s[1], body, (labels, going))[0]

    def merge_round(state):
        labels, _, rounds = state
        lab = jnp.where(fields, labels, big)
        new = flood(labels,
                    _from_neighbour(lab[-1], ROWS, rows, -1, big),
                    _from_neighbour(lab[0], ROWS, rows, 1, big),
                    _from_neighbour(lab[:, -1], COLS, cols, -1, big),
                    _from_neighbour(lab[:, 0], COLS, cols, 1, big))
        changed = jax.lax.psum(jnp.any(new != labels).astype(jnp.int32),
                               (ROWS, COLS))
        return new, changed > 0, rounds + 1

    labels, _, rounds = jax.lax.while_loop(
        lambda s: s[1], merge_round, (init, jnp.bool_(True), jnp.int32(0)))
    return labels, rounds


@functools.partial(jax.jit, static_argnames=("mesh",))
def split_components(fields, *, mesh: Mesh):
    """Connected components of ``fields`` [H, W] split over ``mesh`` ->
    (labels [H, W] split alike, rounds): the labels of
    :func:`connected_components` on the whole frame; ``rounds`` counts the
    exchange-and-flood rounds, the last of which changed nothing."""
    return jax.shard_map(
        functools.partial(_split_components_shard, rows=mesh.shape[ROWS],
                          cols=mesh.shape[COLS]),
        mesh=mesh, in_specs=P(ROWS, COLS),
        out_specs=(P(ROWS, COLS), P()))(fields)


def split_segment_tile(images: np.ndarray, valid: np.ndarray,
                       cfg: ImageryConfig) -> Tuple[np.ndarray, Dict]:
    """The §V.B chain for one tile split over ``cfg.segmentation_mesh``
    -> (labels [H, W], geojson dict), as :func:`segment_tile` gives them.

    The cloud mask stays on the host in NumPy; the stack and the effective
    mask go to the chips split by rows and columns; the labels come back
    whole for the polygons.  The gradient always runs the kernel (compiled
    on TPU, interpreted elsewhere)."""
    mesh = segmentation_mesh(cfg.segmentation_mesh)
    rows, cols = cfg.segmentation_mesh
    h, w = valid.shape[1] // rows, valid.shape[2] // cols
    # the closing reads a halo of 2 pixels, which a shard must hold
    if (h * rows, w * cols) != valid.shape[1:] or min(h, w) < 2:
        raise ValueError(f"a {valid.shape[1:]} frame does not split into "
                         f"{cfg.segmentation_mesh} shards")
    score = cloud_score(images, cfg)
    with span("band_math"):
        mask = valid & (score < 0.5)
    del score
    stack, mask = to_device(images, mask, shardings=(
        NamedSharding(mesh, P(None, ROWS, COLS, None)),
        NamedSharding(mesh, P(None, ROWS, COLS))))
    with span("dispatch"):
        _, _, fields = split_grad_mag(stack, mask, mesh=mesh,
                                      threshold=cfg.edge_threshold)
        del stack, mask
        labels, rounds = split_components(fields, mesh=mesh)
        del fields
    with span("device_wait"):
        jax.block_until_ready((labels, rounds))
    rounds = int(rounds)
    # the merge ran on the chips, inside split_components; this span
    # carries its rounds and times the gather of the merged labels
    with span("cc_merge", rounds=rounds):
        labels = np.asarray(labels)
    return labels, polygonize(labels)


def segment_tile(images: np.ndarray, valid: np.ndarray,
                 cfg: ImageryConfig, impl: str = "auto"
                 ) -> Tuple[np.ndarray, Dict]:
    """Full §V.B chain for one tile -> (labels [H, W], geojson dict); over
    a mesh of chips where ``cfg.segmentation_mesh`` is not (1, 1)."""
    if tuple(cfg.segmentation_mesh) != (1, 1):
        return split_segment_tile(images, valid, cfg)
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
