"""The references against the program at the program's own SMOKE size,
and the control: the reference computed in bfloat16 fails the limits."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import composite as ref_composite
from chipbench.references import segmentation as ref_segmentation
from chipbench.scenes import SceneParams, Tile
from chipbench.tests.tinyroot import REPO


def limits(config: str):
    path = REPO / "chipbench" / "configs" / f"{config}.json"
    return json.loads(path.read_text())["limits"]


def stack(px: int, depth: int, seed: int):
    tile = Tile(SceneParams(px=px, bands=4, depth=depth, fields=12,
                            cloud_cover=0.3), seed, 0)
    steps = [tile.host(t) for t in range(depth)]
    return (tile, np.stack([s[0] for s in steps]),
            np.stack([s[1] for s in steps]))


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_composite_matches_the_program(impl):
    from repro.apps.composite import composite_tile
    from repro.configs.festivus_imagery import SMOKE

    tile, images, _ = stack(SMOKE.composite_tile_px, SMOKE.temporal_depth, 21)
    got = composite_tile(images, SMOKE, impl=impl)
    want, top = ref_composite.composite(
        tile, SMOKE.cloud_reflectance_threshold, 2)
    assert top.shape == (SMOKE.composite_tile_px // 4,) * 2 + (4,)
    err = np.max(np.abs(got - np.asarray(want)))
    assert err <= limits("composite_v5c")["composite_max_abs_err"]


def test_host_cloud_mask_is_the_programs_bit_for_bit():
    from repro.apps.composite import cloud_score
    from repro.configs.festivus_imagery import SMOKE

    _, images, _ = stack(96, 3, 5)
    for x in images:
        assert np.array_equal(
            ref_segmentation.host_cloud_score(
                x.transpose(2, 0, 1), SMOKE.cloud_reflectance_threshold),
            cloud_score(x[None], SMOKE)[0])


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_segmentation_matches_the_program(impl):
    from repro.apps import segmentation
    from repro.apps.composite import cloud_score
    from repro.configs.festivus_imagery import SMOKE
    from repro.kernels import ops

    px, depth = SMOKE.segmentation_tile_px, SMOKE.temporal_depth
    tile, images, valid = stack(px, depth, 33)
    limit = limits("segment_v5b")
    gsum, count = ref_segmentation.grad_sums(
        tile, SMOKE.cloud_reflectance_threshold)
    valid_eff = valid & (cloud_score(images, SMOKE) < 0.5)
    got_g, got_c = (np.asarray(a) for a in ops.grad_mag(
        jnp.asarray(images), jnp.asarray(valid_eff), impl=impl))
    assert np.max(np.abs(got_g - gsum)) <= limit["grad_sum_max_abs_err"]
    assert np.count_nonzero(got_c != count) <= limit["count_mismatch_px"]

    labels, geo = segmentation.segment_tile(images, valid, SMOKE, impl=impl)
    edges = ref_segmentation.edges(gsum, count, SMOKE.edge_threshold, 1)
    want, features = ref_segmentation.fields(edges, 8)
    assert np.array_equal(labels, want)
    assert len(features) == len(geo["features"]) >= 2
    for f in geo["features"]:
        pixels, box, centroid = features[f["properties"]["field_id"]]
        assert pixels == f["properties"]["pixels"]
        assert np.allclose(centroid, f["properties"]["centroid"], atol=1e-6)
        ring = np.array(f["geometry"]["coordinates"][0])
        assert tuple(box) == (ring[:, 0].min(), ring[:, 1].min(),
                              ring[:, 0].max(), ring[:, 1].max())


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_control_in_bfloat16_fails_the_composite(seed):
    from repro.apps.composite import composite_tile
    from repro.configs.festivus_imagery import SMOKE

    tile, images, _ = stack(128, 4, seed)
    got = composite_tile(images, SMOKE)
    control, _ = ref_composite.composite(
        tile, SMOKE.cloud_reflectance_threshold, 2, jnp.bfloat16)
    err = np.max(np.abs(got - np.asarray(control)))
    assert err > limits("composite_v5c")["composite_max_abs_err"]


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_control_in_bfloat16_fails_the_segmentation(seed):
    from repro.apps.composite import cloud_score
    from repro.configs.festivus_imagery import SMOKE
    from repro.kernels import ops

    tile, images, valid = stack(128, 4, seed)
    valid_eff = valid & (cloud_score(images, SMOKE) < 0.5)
    got_g, _ = ops.grad_mag(jnp.asarray(images), jnp.asarray(valid_eff))
    control, _ = ref_segmentation.grad_sums(
        tile, SMOKE.cloud_reflectance_threshold, jnp.bfloat16)
    err = np.max(np.abs(np.asarray(got_g) - control))
    assert err > limits("segment_v5b")["grad_sum_max_abs_err"]
