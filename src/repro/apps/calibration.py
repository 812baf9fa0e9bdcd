"""Initial processing pipeline (paper §V.A): the petabyte campaign, in shape.

Per-scene stages, exactly as the paper lists them: "retrieving it from
Cloud Storage, uncompressing it, parsing the metadata, identifying the
bounding rectangle that contains valid data, cleaning the edges of the
image, converting the raw pixel information into meaningful units
(calibrated top of atmosphere reflectance using the appropriate constants
for each satellite and accounting for solar distance and zenith angle),
tiling each image, ... compressing the data into JPEG 2000 format, and
storing the result back into Cloud Storage."

Scenes arrive as raw DN (digital number) uint16 rasters with per-band
gain/bias metadata; output is reflectance tiles in the chunk store.  The
whole campaign is driven by the scatter/gather cluster engine (one task per
scene over the worker-pull queue), matching the paper's Celery deployment —
workers are stateless, pre-emptible, and idempotent (tile writes are
whole-chunk PUTs), so elastic fleets and virtual-time scaling studies run
this campaign unchanged.
"""

from __future__ import annotations

import dataclasses
import json
import math
from typing import Dict, Optional, Tuple

import numpy as np

from repro.core.chunkstore import ChunkStore
from repro.launch.cluster import (
    ClusterConfig,
    ClusterEngine,
    Worker,
    campaign_config,
)


@dataclasses.dataclass(frozen=True)
class SceneMeta:
    """Per-scene calibration metadata (Landsat MTL-style)."""

    scene_id: str
    gains: Tuple[float, ...]  # per-band reflectance rescale gain
    biases: Tuple[float, ...]  # per-band additive bias
    sun_elevation_deg: float  # solar elevation
    earth_sun_au: float  # Earth-Sun distance in AU

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @staticmethod
    def from_json(s: str) -> "SceneMeta":
        d = json.loads(s)
        d["gains"] = tuple(d["gains"])
        d["biases"] = tuple(d["biases"])
        return SceneMeta(**d)


def toa_reflectance(dn: np.ndarray, meta: SceneMeta) -> np.ndarray:
    """DN -> top-of-atmosphere reflectance (USGS Landsat 8 handbook form):

        rho' = M_p * DN + A_p
        rho  = rho' * d^2 / sin(theta_se)

    dn: [H, W, C] uint16 -> f32 reflectance clipped to [0, 1.5].
    """
    gains = np.asarray(meta.gains, np.float32)
    biases = np.asarray(meta.biases, np.float32)
    rho = dn.astype(np.float32) * gains + biases
    d2 = np.float32(meta.earth_sun_au ** 2)
    sin_e = np.float32(math.sin(math.radians(meta.sun_elevation_deg)))
    return np.clip(rho * d2 / max(sin_e, 1e-3), 0.0, 1.5)


def valid_bounding_rect(dn: np.ndarray, fill_value: int = 0
                        ) -> Tuple[int, int, int, int]:
    """(y0, x0, y1, x1) of the valid-data rectangle (paper: "identifying the
    bounding rectangle that contains valid data")."""
    valid = np.any(dn != fill_value, axis=-1)
    rows = np.flatnonzero(valid.any(axis=1))
    cols = np.flatnonzero(valid.any(axis=0))
    if rows.size == 0:
        return (0, 0, 0, 0)
    return int(rows[0]), int(cols[0]), int(rows[-1]) + 1, int(cols[-1]) + 1


def clean_edges(img: np.ndarray, valid: np.ndarray,
                erode_px: int = 2) -> np.ndarray:
    """Erode the valid mask inward: scan-line / edge artifacts die here."""
    v = valid.copy()
    for _ in range(erode_px):
        shrunk = v.copy()
        shrunk[1:, :] &= v[:-1, :]
        shrunk[:-1, :] &= v[1:, :]
        shrunk[:, 1:] &= v[:, :-1]
        shrunk[:, :-1] &= v[:, 1:]
        v = shrunk
    return v


def process_scene(cs_in: ChunkStore, cs_out: ChunkStore,
                  scene_key: str, tile_px: int = 64) -> Dict:
    """One task: read raw scene -> calibrate -> clean -> tile -> store."""
    raw = cs_in.open(f"{scene_key}/dn").read_all()  # [H, W, C] uint16
    meta = SceneMeta.from_json(
        cs_in.fs.read(f"{cs_in.root}/{scene_key}/meta.json").decode())

    y0, x0, y1, x1 = valid_bounding_rect(raw)
    raw = raw[y0:y1, x0:x1]
    valid = np.any(raw != 0, axis=-1)
    valid = clean_edges(raw, valid)
    refl = toa_reflectance(raw, meta) * valid[..., None]

    h, w, c = refl.shape
    tiles = 0
    for ty in range(0, h, tile_px):
        for tx in range(0, w, tile_px):
            tile = refl[ty:ty + tile_px, tx:tx + tile_px]
            if not tile.any():
                continue  # all-invalid tile: don't store (paper's economics)
            name = f"{scene_key}/t{ty // tile_px}_{tx // tile_px}"
            arr = cs_out.create(name, tile.shape, np.float32,
                                (min(tile_px, tile.shape[0]),
                                 min(tile_px, tile.shape[1]), c),
                                codec="zlib")
            arr.write_region((0, 0, 0), tile)
            tiles += 1
    return {"scene": scene_key, "tiles": tiles,
            "rect": [y0, x0, y1, x1]}


def make_raw_scene(cs: ChunkStore, scene_key: str, height: int, width: int,
                   bands: int = 4, seed: int = 0) -> SceneMeta:
    """Synthesize a raw DN scene + metadata (the test/bench input side)."""
    rng = np.random.default_rng(seed)
    dn = rng.integers(1, 40000, size=(height, width, bands)).astype(np.uint16)
    # fill borders with nodata (the edge-cleaning target)
    pad = max(1, height // 16)
    dn[:pad], dn[-pad:], dn[:, :pad], dn[:, -pad:] = 0, 0, 0, 0
    meta = SceneMeta(scene_id=scene_key,
                     gains=tuple([2e-5] * bands),
                     biases=tuple([-0.1] * bands),
                     sun_elevation_deg=float(rng.uniform(25, 65)),
                     earth_sun_au=float(rng.uniform(0.98, 1.02)))
    arr = cs.create(f"{scene_key}/dn", dn.shape, np.uint16,
                    (min(256, height), min(256, width), bands), codec="zlib")
    arr.write_region((0, 0, 0), dn)
    cs.fs.write(f"{cs.root}/{scene_key}/meta.json", meta.to_json().encode())
    return meta


def run_campaign(cs_in: ChunkStore, cs_out: ChunkStore, scene_keys,
                 num_workers: Optional[int] = None, tile_px: int = 64,
                 engine_config: Optional[ClusterConfig] = None) -> Dict:
    """The §V.A pattern through the scatter/gather cluster engine.

    One task per scene over `num_workers` simulated nodes (default 4; or
    a full :class:`ClusterConfig` via `engine_config` — e.g. virtual-time
    with an elastic schedule).  Each node mounts the campaign bucket via
    its own Festivus instance over the *shared* object store and metadata
    KV, so the caller's mounts see every tile the fleet writes.  `cs_in`
    and `cs_out` must share one underlying store (they may use different
    roots); the per-worker mounts re-root onto both.  Returns the legacy
    summary dict plus the full :class:`ClusterReport` under ``"report"``.
    """
    if cs_in.fs.store is not cs_out.fs.store or cs_in.fs.meta is not cs_out.fs.meta:
        raise ValueError(
            "run_campaign needs cs_in and cs_out over one shared object "
            "store + metadata KV (the fleet mounts a single bucket)")
    config = campaign_config(num_workers, engine_config)

    def handler(worker: Worker, scene_key: str):
        return process_scene(worker.chunkstore(cs_in.root),
                             worker.chunkstore(cs_out.root),
                             scene_key, tile_px)

    engine = ClusterEngine(cs_in.fs.store, meta=cs_in.fs.meta, config=config)
    report = engine.run({k: k for k in scene_keys}, handler)
    report.raise_if_incomplete("calibration")
    return {"scenes": len(scene_keys), "stats": report.queue_stats,
            "results": report.results, "report": report}
