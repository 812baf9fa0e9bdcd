"""The paper's applications (§V): calibration, composite, segmentation."""

import json

import numpy as np
import pytest

from repro.apps import calibration, composite, segmentation
from repro.configs.festivus_imagery import SMOKE as IMG_CFG
from repro.core import ChunkStore, Festivus, FlakyObjectStore, InMemoryObjectStore
from repro.data import imagery


@pytest.fixture
def scene_store(chunkstore):
    spec = imagery.SceneSpec(tile_px=64, temporal_depth=6, seed=5)
    imagery.write_scene_stack(chunkstore, "tiles/t0", spec, chunk_px=32)
    return chunkstore, spec


# ---------------------------------------------------------------------------
# calibration (§V.A)
# ---------------------------------------------------------------------------
def test_toa_reflectance_formula():
    meta = calibration.SceneMeta("s", gains=(2e-5, 2e-5), biases=(-0.1, -0.1),
                                 sun_elevation_deg=30.0, earth_sun_au=1.0)
    dn = np.full((2, 2, 2), 10000, np.uint16)
    rho = calibration.toa_reflectance(dn, meta)
    expected = (10000 * 2e-5 - 0.1) / np.sin(np.radians(30.0))
    np.testing.assert_allclose(rho, expected, rtol=1e-5)


def test_valid_bounding_rect():
    dn = np.zeros((10, 12, 2), np.uint16)
    dn[2:7, 3:9] = 100
    assert calibration.valid_bounding_rect(dn) == (2, 3, 7, 9)


def test_campaign_processes_all_scenes(chunkstore):
    for i in range(3):
        calibration.make_raw_scene(chunkstore, f"scenes/s{i}", 96, 96, seed=i)
    out = calibration.run_campaign(chunkstore, chunkstore,
                                   [f"scenes/s{i}" for i in range(3)],
                                   num_workers=2, tile_px=48)
    assert out["scenes"] == 3
    assert all(r["tiles"] > 0 for r in out["results"].values())


def test_campaign_survives_flaky_store():
    """Pre-emptible-cloud realism: transient store failures must not kill
    the campaign (retry at the VFS layer + task retry above it)."""
    inner = InMemoryObjectStore()
    cs_in = ChunkStore(Festivus(inner), "raw")
    for i in range(2):
        calibration.make_raw_scene(cs_in, f"scenes/s{i}", 64, 64, seed=i)
    flaky = FlakyObjectStore(inner, failure_rate=0.5, seed=0)
    cs_flaky = ChunkStore(Festivus(flaky, meta=cs_in.fs.meta), "raw")
    out = calibration.run_campaign(cs_flaky, cs_flaky,
                                   ["scenes/s0", "scenes/s1"],
                                   num_workers=2)
    assert out["scenes"] == 2
    assert flaky.injected_failures > 0


def test_campaign_byte_identical_to_single_process(chunkstore):
    """The engine-run calibration campaign must write exactly the tiles the
    direct single-process path writes, byte for byte."""
    keys = [f"scenes/s{i}" for i in range(3)]
    for i, k in enumerate(keys):
        calibration.make_raw_scene(chunkstore, k, 96, 96, seed=10 + i)
    out = calibration.run_campaign(chunkstore, chunkstore, keys,
                                   num_workers=3, tile_px=48)
    assert out["report"].all_done
    ref_cs = ChunkStore(chunkstore.fs, "ref_out")
    for k in keys:
        calibration.process_scene(chunkstore, ref_cs, k, tile_px=48)
    got_tiles = [n for n in chunkstore.list_arrays() if "/t" in n]
    ref_tiles = ref_cs.list_arrays()
    assert sorted(got_tiles) == sorted(ref_tiles) and ref_tiles
    for name in ref_tiles:
        got = chunkstore.open(name).read_all()
        ref = ref_cs.open(name).read_all()
        assert got.tobytes() == ref.tobytes(), name


def test_campaign_through_virtual_time_engine(chunkstore):
    """§V.A runs unchanged on the DES: same outputs, virtual makespan."""
    from repro.launch.cluster import ClusterConfig

    keys = [f"scenes/v{i}" for i in range(2)]
    for i, k in enumerate(keys):
        calibration.make_raw_scene(chunkstore, k, 64, 64, seed=20 + i)
    out = calibration.run_campaign(
        chunkstore, chunkstore, keys, tile_px=32,
        engine_config=ClusterConfig(nodes=2, virtual_time=True))
    assert out["scenes"] == 2 and out["report"].all_done
    assert out["report"].makespan_s > 0
    assert out["report"].meta_ops > 0


def test_campaign_rejects_split_stores():
    a = ChunkStore(Festivus(InMemoryObjectStore()), "raw")
    b = ChunkStore(Festivus(InMemoryObjectStore()), "raw")
    with pytest.raises(ValueError):
        calibration.run_campaign(a, b, ["scenes/s0"])


# ---------------------------------------------------------------------------
# composite (§V.C)
# ---------------------------------------------------------------------------
def test_scene_stack_matches_per_timestep_scenes():
    """The stack builds the field map once and generates timesteps on
    threads; it must equal scene() run one timestep at a time, bit for bit."""
    spec = imagery.SceneSpec(tile_px=40, temporal_depth=5, seed=3)
    imgs, valid = imagery.scene_stack(spec)
    for t in range(spec.temporal_depth):
        img_t, valid_t = imagery.scene(spec, t)
        assert imgs[t].tobytes() == img_t.tobytes(), t
        assert (valid[t] == valid_t).all(), t


@pytest.mark.parametrize("campaign", [composite.run_composite_campaign,
                                      segmentation.run_segmentation_campaign])
def test_campaign_failure_carries_the_handler_error(chunkstore, campaign):
    """A failing tile raises with its handler's own error text, not only a
    count, so a device error reads at the end of a run's output."""
    from repro.launch.cluster import ClusterConfig

    with pytest.raises(RuntimeError,
                       match="tiles/missing failed with: FileNotFoundError"):
        campaign(chunkstore, ["tiles/missing"], IMG_CFG,
                 engine_config=ClusterConfig(nodes=1, max_retries=0))


def test_composite_prefers_cloud_free(scene_store):
    cs, spec = scene_store
    imgs, valid = imagery.read_scene_stack(cs, "tiles/t0")
    comp = composite.composite_tile(imgs, IMG_CFG, impl="ref")
    assert comp.shape == imgs.shape[1:]
    assert np.isfinite(comp).all()
    # composite should be darker than the cloudiest single frame (clouds
    # are bright flat ~0.7); compare mean brightness
    cloudiest = imgs.mean(axis=(1, 2, 3)).argmax()
    assert comp.mean() < imgs[cloudiest].mean()


def test_cloud_score_flags_bright_flat(scene_store):
    cs, spec = scene_store
    imgs, valid = imagery.read_scene_stack(cs, "tiles/t0")
    score = composite.cloud_score(imgs, IMG_CFG)
    # cloud pixels (invalid) should score higher than clear pixels
    assert score[~valid].mean() > score[valid].mean()


def smoke_stack(seed: int) -> np.ndarray:
    """A seeded [T, H, W, C] f32 stack at the smoke tile size."""
    spec = imagery.SceneSpec(tile_px=IMG_CFG.composite_tile_px,
                             temporal_depth=IMG_CFG.temporal_depth, seed=seed)
    return imagery.scene_stack(spec)[0]


STACK_SEEDS = [0, 7, 3000001401]


@pytest.mark.parametrize("seed", STACK_SEEDS)
def test_cloud_score_of_a_device_array_is_the_host_formula(seed):
    import jax
    import jax.numpy as jnp

    images = smoke_stack(seed)
    host = composite.cloud_score(images, IMG_CFG)
    device = composite.cloud_score(jnp.asarray(images), IMG_CFG)
    assert isinstance(host, np.ndarray) and isinstance(device, jax.Array)
    assert host.shape == device.shape == images.shape[:-1]
    assert host.dtype == device.dtype == np.float32
    np.testing.assert_allclose(np.asarray(device), host, rtol=0, atol=1e-6)


@pytest.mark.parametrize("seed", STACK_SEEDS)
def test_composite_tile_sends_the_stack_once(seed, monkeypatch):
    images = smoke_stack(seed)
    to_device = composite.to_device
    sent = []

    def recording(*arrays):
        sent.append([a.nbytes for a in arrays])
        return to_device(*arrays)

    monkeypatch.setattr(composite, "to_device", recording)
    for _ in range(2):
        composite.composite_tile(images, IMG_CFG, impl="ref")
    assert sent == [[images.nbytes]] * 2


@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("seed", STACK_SEEDS)
def test_composite_tile_is_the_host_weights_formula(seed, impl):
    """The paper's recipe written out on the host, in float64: cloud score,
    NDVI verdancy, weights, weighted mean over time."""
    images = smoke_stack(seed)
    x = images.astype(np.float64)
    red, nir, green = x[..., 0], x[..., 1], x[..., 2]
    cloud = (np.clip(((red + nir + green) / 3
                      - IMG_CFG.cloud_reflectance_threshold) * 4, 0, 1)
             * np.clip(1 - np.abs(red - green), 0, 1))
    ndvi = (nir - red) / (nir + red + 1e-6)
    w = (1 - cloud) * (0.25 + 0.75 * np.clip(ndvi, 0, 1))
    want = (w[..., None] * x).sum(0) / (w.sum(0)[..., None] + 1e-6)
    got = composite.composite_tile(images, IMG_CFG, impl=impl)
    assert got.shape == want.shape and got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


# ---------------------------------------------------------------------------
# segmentation (§V.B)
# ---------------------------------------------------------------------------
def test_connected_components_labels_regions():
    import jax.numpy as jnp

    mask = np.zeros((8, 8), bool)
    mask[1:3, 1:3] = True
    mask[5:7, 5:7] = True
    labels = np.asarray(segmentation.connected_components(jnp.asarray(mask)))
    ids = set(labels[mask])
    assert len(ids) == 2 and 0 not in ids
    assert (labels[~mask] == 0).all()


def test_segmentation_recovers_field_count(scene_store):
    cs, spec = scene_store
    imgs, valid = imagery.read_scene_stack(cs, "tiles/t0")
    labels, geo = segmentation.segment_tile(imgs, valid, IMG_CFG, impl="ref")
    n_found = len(geo["features"])
    # within 50% of the true Voronoi field count (edges can merge slivers)
    assert abs(n_found - spec.num_fields) <= spec.num_fields // 2, n_found


def test_segmentation_geojson_contract(scene_store):
    cs, spec = scene_store
    out = segmentation.segment_to_store(cs, "tiles/t0", IMG_CFG)
    raw = cs.fs.read(f"{cs.root}/fields/tiles/t0/fields.geojson")
    geo = json.loads(raw.decode())
    assert geo["type"] == "FeatureCollection"
    for feat in geo["features"]:
        assert feat["geometry"]["type"] == "Polygon"
        assert feat["properties"]["pixels"] >= 8


def test_segmentation_campaign_byte_identical_to_single_process(chunkstore):
    """run_segmentation_campaign == segment_to_store per tile, byte for
    byte (labels array and GeoJSON), with the fleet's writes visible to
    the caller's mount."""
    names = []
    for i in range(3):
        name = f"tiles/seg{i}"
        imagery.write_scene_stack(
            chunkstore, name,
            imagery.SceneSpec(tile_px=48, temporal_depth=4, seed=30 + i),
            chunk_px=16)
        names.append(name)
    out = segmentation.run_segmentation_campaign(chunkstore, names, IMG_CFG,
                                                 num_workers=3)
    assert out["tiles"] == 3 and out["report"].all_done
    for n in names:
        segmentation.segment_to_store(chunkstore, n, IMG_CFG,
                                      out_prefix="fields_ref")
        got = chunkstore.open(f"fields/{n}/labels").read_all()
        ref = chunkstore.open(f"fields_ref/{n}/labels").read_all()
        assert got.tobytes() == ref.tobytes(), n
        got_geo = chunkstore.fs.read(f"{chunkstore.root}/fields/{n}/fields.geojson")
        ref_geo = chunkstore.fs.read(
            f"{chunkstore.root}/fields_ref/{n}/fields.geojson")
        assert got_geo == ref_geo, n
    assert all(r["fields"] >= 0 for r in out["report"].results.values())
