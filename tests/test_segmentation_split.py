"""The §V.B chain with one tile split over a mesh of chips, against the
unsplit chain and the plain reference (``chipbench/references``), on four
virtual CPU devices.

The cases run in one subprocess (the device count must be set before JAX
starts), which this file is, run as a script; each test reads its case's
numbers.  Labels, counts and features must be exact, ``grad_sum`` within
1e-6."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
PX, DEPTH = 96, 3
SEEDS = (11, 12, 2**31 + 13)
MESHES = ((1, 2), (2, 1), (2, 2))
#: crafted walls, each on the mesh where it tests what it names
CRAFTED = {"u_shape": (1, 2), "seam_edges": (2, 2), "frame_corner": (2, 2)}
GRAD_TOL = 1e-6
CENTROID_TOL_PX = 1e-6


def _name(mesh) -> str:
    return f"{mesh[0]}x{mesh[1]}"


# -- the subprocess ------------------------------------------------------------
def _walls(case: str) -> np.ndarray:
    """Wall pixels [PX, PX] bool of a crafted case."""
    wall = np.zeros((PX, PX), bool)
    half = PX // 2
    if case == "u_shape":
        # a corridor that leaves the west shard along its top arm, turns in
        # the east shard and comes back along its bottom arm: the west
        # shard holds it in two pieces joined only through the east one
        corridor = np.zeros((PX, PX), bool)
        corridor[10:17, 20:71] = True
        corridor[10:41, 62:71] = True
        corridor[34:41, 20:71] = True
        p = np.pad(corridor, 1)
        ring = p[:-2, 1:-1] | p[2:, 1:-1] | p[1:-1, :-2] | p[1:-1, 2:]
        wall = ring & ~corridor
    elif case == "seam_edges":
        # walls on the first row and column past the seams, so that the
        # edges lie on both sides of each seam; a gap in two of the four
        # arms lets one field cross a seam through the edge band twice
        wall[half, :] = True
        wall[:, half] = True
        wall[half, 18:22] = wall[68:72, half] = False
    elif case == "frame_corner":
        # a field in the frame's last corner, walled off from the rest
        wall[PX - 5, PX - 5:] = True
        wall[PX - 5:, PX - 5] = True
    return wall


def _crafted(case: str):
    img = np.full((PX, PX, 4), 0.3, np.float32)
    img[..., 0] += 0.5 * _walls(case)
    return (np.stack([img] * DEPTH), np.ones((DEPTH, PX, PX), bool))


class _Stack:
    """A host stack in the form the reference reads (``tile[t]`` is a
    bands-major image and its valid mask)."""

    def __init__(self, images, valid):
        import types

        self.images, self.valid = images, valid
        self.params = types.SimpleNamespace(px=images.shape[1],
                                            depth=images.shape[0])

    def __getitem__(self, t):
        import jax.numpy as jnp

        return (jnp.asarray(self.images[t].transpose(2, 0, 1)),
                jnp.asarray(self.valid[t]))


def _features(geo):
    out = {}
    for f in geo["features"]:
        ring = f["geometry"]["coordinates"][0]
        xs, ys = [p[0] for p in ring], [p[1] for p in ring]
        props = f["properties"]
        out[int(props["field_id"])] = (
            int(props["pixels"]), (min(xs), min(ys), max(xs), max(ys)),
            tuple(props["centroid"]))
    return out


def _differ(got, want) -> int:
    def same(a, b):
        return (a[0] == b[0] and tuple(a[1]) == tuple(b[1])
                and all(abs(x - y) <= CENTROID_TOL_PX
                        for x, y in zip(a[2], b[2])))

    return sum(1 for k in set(got) | set(want)
               if k not in got or k not in want or not same(got[k], want[k]))


def _compare(images, valid, mesh, cfg):
    """Numbers of one stack: split against unsplit and reference."""
    import dataclasses

    from chipbench.references import segmentation as reference
    from repro.apps import segmentation as seg

    want, want_geo = seg.segment_tile(images, valid, cfg)
    gsum, count = reference.grad_sums(_Stack(images, valid),
                                      cfg.cloud_reflectance_threshold)
    edges = reference.edges(gsum, count, cfg.edge_threshold, 1)
    ref_labels, ref_features = reference.fields(edges, 8)

    programs = {name: getattr(seg, name)
                for name in ("split_grad_mag", "split_components")}
    taps = {}

    def tapped(name):
        def tap(*args, **kwargs):
            taps[name] = programs[name](*args, **kwargs)
            return taps[name]
        return tap

    for name in programs:
        setattr(seg, name, tapped(name))
    try:
        got, geo = seg.segment_tile(
            images, valid, dataclasses.replace(cfg, segmentation_mesh=mesh))
    finally:
        for name, program in programs.items():
            setattr(seg, name, program)
    got_g, got_c = (np.asarray(a) for a in taps["split_grad_mag"][:2])
    return got, edges, {
        "labels_vs_unsplit": int(np.count_nonzero(got != want)),
        "labels_vs_reference": int(np.count_nonzero(got != ref_labels)),
        "geo_vs_unsplit": geo == want_geo,
        "features_vs_reference": _differ(_features(geo), ref_features),
        "count_mismatch_px": int(np.count_nonzero(got_c != count)),
        "grad_sum_max_abs_err": float(np.max(np.abs(got_g - gsum))),
        "rounds": int(taps["split_components"][1]),
        "fields": len(geo["features"]),
    }


def _campaign(cfg):
    """A stored stack through ``run_segmentation_campaign`` on a 2x2 mesh
    and through the unsplit ``segment_to_store``: the stored outputs."""
    import dataclasses

    from repro.apps import segmentation as seg
    from repro.core import ChunkStore, Festivus, InMemoryObjectStore
    from repro.data import imagery
    from repro.launch.cluster import ClusterConfig

    cs = ChunkStore(Festivus(InMemoryObjectStore()), "bucket")
    imagery.write_scene_stack(
        cs, "tiles/t0", imagery.SceneSpec(tile_px=PX, temporal_depth=DEPTH,
                                          seed=5), chunk_px=PX // 3)
    split = dataclasses.replace(cfg, segmentation_mesh=(2, 2))
    out = seg.run_segmentation_campaign(
        cs, ["tiles/t0"], split,
        engine_config=ClusterConfig(nodes=1, max_retries=0))
    seg.segment_to_store(cs, "tiles/t0", cfg, out_prefix="unsplit")

    def read(prefix):
        labels = cs.open(f"{prefix}/tiles/t0/labels").read_all()
        geo = cs.fs.read(f"{cs.root}/{prefix}/tiles/t0/fields.geojson")
        return labels, geo

    (got, got_geo), (want, want_geo) = read("fields"), read("unsplit")
    return {"done": bool(out["report"].all_done),
            "labels_vs_unsplit": int(np.count_nonzero(got != want)),
            "geojson_vs_unsplit": got_geo == want_geo,
            "fields": len(json.loads(got_geo)["features"])}


def _harness(tmp: str):
    """A small split cell through the benchmark harness, untraced and
    traced: the results."""
    import time

    from chipbench import harness
    from chipbench.tests import tinyroot

    root = tinyroot.make(Path(tmp))
    bench = root / "chipbench"
    config = json.loads(
        (bench / "configs" / "segment_v5b_d16_2x2.json").read_text())
    config.update(name="tiny_split", tile_px=128, depth=4, chunk_px=64)
    (bench / "configs" / "tiny_split.json").write_text(json.dumps(config))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "tiny_split", "source": "https://arxiv.org/abs/1702.03935",
        "file": "chipbench/configs/tiny_split.json",
        "reduced": ["tile_px", "depth", "chunk_px"], "why": "test size"})
    manifest["workloads"].append({
        "name": "tiny-split", "config": "tiny_split",
        "traffic": "zlib-cloud30-layout1", "chips": 4, "why": "test size"})
    for metric in manifest["per_layer"]:
        if "segment-6144x16-2x2" in metric["workloads"]:
            metric["workloads"].append("tiny-split")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    out = {}
    for trace in (0, 1):
        result = harness.main(
            ["--workload", "tiny-split", "--seed", str(2**31 + 7),
             "--seconds", "0.2", "--trace", str(trace)],
            root=root, started=time.monotonic(), require_chip=False)
        out[f"trace{trace}"] = {
            "correct": result["correct"], "checks": result["checks"],
            "device_count": result["device"]["count"],
            "metrics": sorted(result["metrics"])}
    return out


def _cases(tmp: str) -> dict:
    import dataclasses

    from chipbench.scenes import SceneParams, Tile
    from repro.configs.festivus_imagery import SMOKE

    cfg = dataclasses.replace(SMOKE, segmentation_tile_px=PX)
    out = {}
    for seed in SEEDS:
        tile = Tile(SceneParams(px=PX, bands=4, depth=DEPTH, fields=12,
                                cloud_cover=0.3), seed, 0)
        steps = [tile.host(t) for t in range(DEPTH)]
        images = np.stack([img for img, _ in steps])
        valid = np.stack([ok for _, ok in steps])
        for mesh in MESHES:
            _, _, numbers = _compare(images, valid, mesh, cfg)
            out[f"seeded-{seed}-{_name(mesh)}"] = numbers
    half = PX // 2
    for case, mesh in CRAFTED.items():
        labels, edges, numbers = _compare(*_crafted(case), mesh, cfg)
        if case == "u_shape":
            top, bottom = labels[13, 30], labels[37, 30]
            numbers["one_field"] = bool(top == bottom != 0)
        elif case == "seam_edges":
            numbers["on_seams"] = bool(
                edges[half - 1:half + 1].mean() > 0.9
                and edges[:, half - 1:half + 1].mean() > 0.9)
            numbers["crossing"] = bool(labels[10, 10] == labels[80, 80] != 0
                                       and labels[10, 80] != labels[10, 10])
        else:
            ys, xs = np.nonzero(labels == labels[-1, -1])
            numbers["in_corner"] = bool(labels[-1, -1] != 0
                                        and ys.min() > PX - 5
                                        and xs.min() > PX - 5)
        out[case] = numbers
    out["campaign"] = _campaign(cfg)
    out["harness"] = _harness(tmp)
    return out


# -- the tests -----------------------------------------------------------------
@pytest.fixture(scope="module")
def results(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("split")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(REPO / "src"), str(REPO)]))
    proc = subprocess.run([sys.executable, __file__, str(tmp)],
                          capture_output=True, text=True, timeout=900,
                          env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _exact(numbers):
    assert numbers["labels_vs_unsplit"] == 0
    assert numbers["labels_vs_reference"] == 0
    assert numbers["geo_vs_unsplit"]
    assert numbers["features_vs_reference"] == 0
    assert numbers["count_mismatch_px"] == 0
    assert numbers["grad_sum_max_abs_err"] <= GRAD_TOL
    assert numbers["fields"] > 0


@pytest.mark.parametrize("mesh", MESHES, ids=_name)
@pytest.mark.parametrize("seed", SEEDS)
def test_split_equals_unsplit_and_reference(results, seed, mesh):
    numbers = results[f"seeded-{seed}-{_name(mesh)}"]
    _exact(numbers)
    assert numbers["rounds"] >= 2  # a flood, then a round that settles


def test_u_shaped_field_merges_over_several_rounds(results):
    """The west shard's two pieces of one field meet only after its label
    has crossed the seam twice: a local flood, two crossings and a round
    that changes nothing."""
    numbers = results["u_shape"]
    _exact(numbers)
    assert numbers["one_field"]
    assert numbers["rounds"] >= 4


def test_edges_on_the_seam_row_and_column(results):
    numbers = results["seam_edges"]
    _exact(numbers)
    assert numbers["on_seams"] and numbers["crossing"]


def test_field_in_the_frame_corner(results):
    numbers = results["frame_corner"]
    _exact(numbers)
    assert numbers["in_corner"]


def test_split_campaign_through_the_campaign_entry(results):
    numbers = results["campaign"]
    assert numbers["done"] and numbers["fields"] > 0
    assert numbers["labels_vs_unsplit"] == 0
    assert numbers["geojson_vs_unsplit"]


@pytest.mark.parametrize("trace", [0, 1])
def test_split_cell_through_the_benchmark_harness(results, trace):
    run = results["harness"][f"trace{trace}"]
    assert run["correct"], run
    assert run["device_count"] == 4
    assert all(c["value"] <= c["limit"] for c in run["checks"].values())
    # on the CPU a traced run reads no device: its device metrics are left
    # out, the span metrics are there
    if trace:
        assert "h2d_s_per_tile" in run["metrics"]
        assert "split_cc_ms_per_tile" not in run["metrics"]


def test_grad_mag_with_zero_edges_is_todays_call():
    import jax.numpy as jnp

    from repro.kernels.grad_mag import grad_mag_fwd

    rng = np.random.default_rng(3)
    t, h, w, c = 3, 16, 128, 4
    images = jnp.asarray(rng.random((t, h, w, c), np.float32))
    valid = jnp.asarray(rng.random((t, h, w)) > 0.2)
    today = grad_mag_fwd(images, valid, interpret=True)
    edged = grad_mag_fwd(
        images, valid, interpret=True,
        east=(jnp.zeros((t, h, c)), jnp.zeros((t, h), bool)),
        south=(jnp.zeros((t, w, c)), jnp.zeros((t, w), bool)))
    for a, b in zip(today, edged):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


if __name__ == "__main__":
    print(json.dumps(_cases(sys.argv[1])))
