"""§V.B segmentation: one tile through the program's campaign entry, and
the check of what it produced against the reference.

:func:`run_tile` goes through the program's whole path (object store,
Festivus, chunk store and codec, host cloud mask, ``grad_mag``, closing,
connected components, polygons, label and GeoJSON writes).  The campaign
stores no gradient sums, so a tap on the program's
``repro.kernels.ops.grad_mag`` keeps the ``(grad_sum, count)`` that the
tile's call returns, as the device arrays it returned: they are copied
to the host by the check, after the window has closed, so the window does
no work of the check's.  Held, they keep 2 x H x W x 4 bytes a tile on the
device (302 MB at 6144²) that the program would have freed.
:class:`Check` compares those, the label array read back through the
chunk store and the GeoJSON read back through Festivus with
``references/segmentation.py``.
"""

from __future__ import annotations

import json

import numpy as np

from chipbench.references import segmentation as reference

#: the numbers compared; their limits are in the configuration's file
NUMBERS = ("grad_sum_max_abs_err", "count_mismatch_px", "label_mismatch_px",
           "feature_mismatch")
#: centroids are float64 means, summed in another order by the reference
CENTROID_TOL_PX = 1e-6


def imagery_config(config):
    from repro.configs.festivus_imagery import ImageryConfig

    return ImageryConfig(
        bands=config["bands"], segmentation_tile_px=config["tile_px"],
        chunk_px=config["chunk_px"],
        cloud_reflectance_threshold=config["cloud_reflectance_threshold"],
        edge_threshold=config["edge_threshold"])


def run_tile(cs, name: str, out_prefix: str, icfg, engine_config):
    """One tile through the campaign entry: {"report": its ClusterReport,
    "produced": (grad_sum, count) of its grad_mag call, on the device, or
    None}."""
    from repro.apps.segmentation import run_segmentation_campaign
    from repro.kernels import ops

    program = ops.grad_mag
    seen = []

    def tap(*args, **kwargs):
        out = program(*args, **kwargs)
        seen.append(out)
        return out

    ops.grad_mag = tap
    try:
        result = run_segmentation_campaign(cs, [name], icfg,
                                           out_prefix=out_prefix,
                                           engine_config=engine_config)
    finally:
        ops.grad_mag = program
    produced = tuple(seen[0]) if len(seen) == 1 else None
    return {"report": result["report"], "produced": produced}


def stored(cs, name: str, out_prefix: str):
    """(labels, {label: feature}) as the campaign stored them."""
    labels = cs.open(f"{out_prefix}/{name}/labels").read_all()
    geo = json.loads(
        cs.fs.read(f"{cs.root}/{out_prefix}/{name}/fields.geojson"))
    features = {}
    for f in geo["features"]:
        ring = f["geometry"]["coordinates"][0]
        xs = [p[0] for p in ring]
        ys = [p[1] for p in ring]
        props = f["properties"]
        features[int(props["field_id"])] = (
            int(props["pixels"]), (min(xs), min(ys), max(xs), max(ys)),
            tuple(props["centroid"]))
    return labels, features


def _same(a, b) -> bool:
    return (a[0] == b[0] and tuple(a[1]) == tuple(b[1])
            and all(abs(x - y) <= CENTROID_TOL_PX for x, y in zip(a[2], b[2])))


class Check:
    """Compares a tile's outputs with the reference of its stack.

    ``dtype`` is the reference's precision: float32 for the benchmark's
    runs, bfloat16 for the control."""

    def __init__(self, config, dtype):
        self.config = config
        self.dtype = dtype
        self._refs = {}

    def reference(self, tile):
        if tile not in self._refs:
            c = self.config
            gsum, count = reference.grad_sums(
                tile, c["cloud_reflectance_threshold"], self.dtype)
            edges = reference.edges(gsum, count, c["edge_threshold"],
                                    c["closing_steps"], self.dtype)
            labels, features = reference.fields(edges, c["min_field_px"])
            self._refs[tile] = (gsum, count, labels, features)
        return self._refs[tile]

    def numbers(self, cs, name: str, out_prefix: str, tile, produced):
        """{number: value} for one tile of the window; ``produced`` is what
        :func:`run_tile` kept of it."""
        want_g, want_c, want, want_features = self.reference(tile)
        got, got_features = stored(cs, name, out_prefix)
        if produced is None or got.shape != want.shape:
            return {n: float("inf") for n in NUMBERS}
        got_g, got_c = (np.asarray(a) for a in produced)
        keys = set(got_features) | set(want_features)
        differ = sum(1 for k in keys
                     if k not in got_features or k not in want_features
                     or not _same(got_features[k], want_features[k]))
        return {
            "grad_sum_max_abs_err": float(np.max(np.abs(got_g - want_g))),
            "count_mismatch_px": int(np.count_nonzero(got_c != want_c)),
            "label_mismatch_px": int(np.count_nonzero(got != want)),
            "feature_mismatch": differ,
        }
