"""The device generator keeps the scene model of the program's host
generator (``repro.data.imagery.scene``): Voronoi fields, NIR seasonality,
sensor noise, and bright flat clouds that make pixels invalid."""

import jax
import numpy as np
import pytest

from chipbench.scenes import SceneParams, Tile, tile_key

PARAMS = SceneParams(px=96, bands=4, depth=6, fields=12, cloud_cover=0.3)


@pytest.fixture(scope="module")
def stack():
    tile = Tile(PARAMS, 2**31 + 3, 0)
    steps = [tile.host(t) for t in range(PARAMS.depth)]
    return tile, steps


def test_shapes_and_range(stack):
    _, steps = stack
    for img, valid in steps:
        assert img.shape == (96, 96, 4) and img.dtype == np.float32
        assert valid.shape == (96, 96) and valid.dtype == bool
        assert img.min() >= 0.0 and img.max() <= 1.0


def test_cloud_share_is_the_traffic_cover(stack):
    _, steps = stack
    for _, valid in steps:
        assert abs((~valid).mean() - PARAMS.cloud_cover) < 2 / 96


def test_clouds_are_bright_and_flat_like_the_host_generator(stack):
    from repro.data import imagery

    _, steps = stack
    host_img, host_valid = imagery.scene(imagery.SceneSpec(tile_px=96), 1)
    for img, valid in steps + [(host_img, host_valid)]:
        cloud = img[~valid]
        assert abs(cloud.mean() - 0.7) < 0.01
        assert cloud.std() < 0.03  # flat: N(0, 0.02) in every band


def test_fields_noise_and_seasonality(stack):
    tile, steps = stack
    labels, base = (np.asarray(a) for a in tile.field_map())
    assert set(np.unique(labels)) <= set(range(PARAMS.fields))
    assert ((base >= 0.05) & (base <= 0.45)).all()
    for t, (img, valid) in enumerate(steps):
        season = 0.5 + 0.5 * np.sin(2 * np.pi * t / PARAMS.depth)
        want = base[labels].copy()
        want[..., 1] = np.clip(want[..., 1] * (0.6 + 0.8 * season), 0, 1)
        noise = (img - want)[valid]
        assert abs(noise.mean()) < 2e-3
        assert abs(noise.std() - PARAMS.noise) < 2e-3


def test_seeds(stack):
    tile, steps = stack
    again = Tile(PARAMS, 2**31 + 3, 0)
    assert np.array_equal(again.host(2)[0], steps[2][0])
    other_tile = Tile(PARAMS, 2**31 + 3, 1).host(2)[0]
    assert not np.array_equal(other_tile, steps[2][0])
    # the seed's high word is folded in, not dropped
    data = jax.random.key_data
    assert not np.array_equal(data(tile_key(5, 0)),
                              data(tile_key(5 + 2**32, 0)))


def test_a_layout_seed_fixes_fields_and_clouds_not_noise():
    fixed = SceneParams(px=64, bands=4, depth=2, fields=12, cloud_cover=0.3,
                        layout_seed=77, layouts=2)
    a, b = Tile(fixed, 5, 0), Tile(fixed, 8, 1)  # layout (0 + 5) % 2 == 1
    c = Tile(fixed, 5, 1)                          # layout (1 + 5) % 2 == 0
    (img_a, valid_a), (img_b, valid_b) = a.host(1), b.host(1)
    assert np.array_equal(valid_a, valid_b)
    assert np.array_equal(*(np.asarray(t.field_map()[0]) for t in (a, b)))
    assert not np.array_equal(img_a, img_b)  # the noise is the run seed's
    assert abs(img_a - img_b).max() < 0.15
    assert not np.array_equal(valid_a, c.host(1)[1])
