"""Both §V campaigns, once, on one TPU chip at the paper's tile widths.

    python chip_smoke.py [--seed N]

The quickest proof that the system still starts on the chip.  One process
holds the chip for the whole run; nothing here starts a subprocess.

(a) device: a TPU is the default backend, the kernels resolve to compiled
    mode, and the lowered ``kops.composite``/``kops.grad_mag`` programs
    hold a Pallas TPU kernel (``ops.py`` picks the jnp reference off-TPU,
    and this is where that would show).
(b) §V.C composite: seeded 4096² x 4-band stacks (``ImageryConfig()``, 1024²
    zlib chunks) written through ``imagery.write_scene_stack`` into an
    in-memory object store, composited by ``run_composite_campaign`` (which
    writes the pyramid too), each output checked against the float32
    reference ``kernels.ref.composite`` run on the host's CPU backend.
(c) §V.B segmentation: 6144² stacks at the largest depth whose compiled
    ``grad_mag`` program fits the chip's memory, run by
    ``run_segmentation_campaign``; ``grad_sum``/``count`` are checked
    against ``kernels.ref.grad_mag`` on the host and every tile must yield
    at least one field.

Per-phase times (set-up, compile, run, check) and the chip's peak memory are
observations printed on the way.  The last line of standard output is
``{"ok": true, "device": {...}}`` only when every phase passed; with no TPU
the script exits non-zero before any phase.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

#: §V.C depth on the chip.  The paper's ~64 scenes are 17.2 GB as f32, more
#: than the chip's 16 GB, and the kernel holds the whole stack on the device.
COMPOSITE_DEPTH = 16
#: §V.B depth floor: ``grad_mag``'s shifted copies triple the image bytes
SEGMENTATION_MIN_DEPTH = 4
#: share of the chip's memory a campaign program may plan to use; the rest
#: is left for the other buffers a handler keeps alive
HBM_BUDGET = 0.9
#: tiles per campaign: more than one, so the queue hands out several tasks
TILES = 2
#: one worker: each worker holds a whole tile stack on the one chip
WORKERS = 1
#: the kernel tests' float32 tolerance (tests/test_kernels.py)
F32_TOL = dict(rtol=3e-5, atol=3e-5)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: {msg}")


def peak_hbm(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} B"


def stopwatch():
    t0 = time.perf_counter()
    return lambda: time.perf_counter() - t0


def write_tiles(cs, prefix: str, tile_px: int, depth: int, cfg, seed: int):
    from repro.data import imagery

    names = []
    for i in range(TILES):
        name = f"{prefix}/t{i}"
        spec = imagery.SceneSpec(tile_px=tile_px, bands=cfg.bands,
                                 temporal_depth=depth, seed=seed + i)
        imagery.write_scene_stack(cs, name, spec, chunk_px=cfg.chunk_px)
        names.append(name)
    return names


def campaign_config():
    from repro.launch.cluster import ClusterConfig

    # no retries: a handler's device error fails its task at once and is
    # raised with the campaign, instead of recompiling and failing again
    return ClusterConfig(nodes=WORKERS, max_retries=0)


def host_jit(fn):
    """``fn`` jitted for the host's CPU backend (the reference side)."""
    import jax

    cpu = jax.devices("cpu")[0]

    def run(*args):
        with jax.default_device(cpu):
            return jax.device_get(jax.jit(fn)(*args))
    return run


def new_store():
    from repro.core import ChunkStore, Festivus, InMemoryObjectStore

    return ChunkStore(Festivus(InMemoryObjectStore()), "bucket")


def composite_phase(cfg, depth: int, seed: int, dev) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.apps.composite import cloud_score, run_composite_campaign
    from repro.data import imagery
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref

    px, bands = cfg.composite_tile_px, cfg.bands
    print(f"[b] composite: {TILES} tiles of {px}x{px}x{bands} f32, depth "
          f"{depth} (cut from the paper's ~64 scenes: the whole f32 stack "
          f"sits on the device, {64 * px * px * bands * 4 / 1e9:.1f} GB at 64)",
          flush=True)
    clock = stopwatch()
    cs = new_store()
    names = write_tiles(cs, "composite-in", px, depth, cfg, seed)
    print(f"[b] set-up (generate + zlib-write): {clock():.2f} s", flush=True)

    clock = stopwatch()
    kops.composite(jnp.zeros((depth, px, px, bands), jnp.float32),
                   jnp.zeros((depth, px, px), jnp.float32),
                   impl="auto").block_until_ready()
    print(f"[b] compile (first call, on zeros): {clock():.2f} s", flush=True)

    clock = stopwatch()
    out = run_composite_campaign(cs, names, cfg, out_prefix="composite",
                                 engine_config=campaign_config())
    print(f"[b] run: {clock():.2f} s for {out['tiles']} tiles on "
          f"{out['report'].nodes} worker(s); queue {out['stats']}", flush=True)

    clock = stopwatch()
    reference = host_jit(kref.composite)
    weights = host_jit(lambda im, s: kref.composite_weights(
        im, s, nir=im[..., 1], red=im[..., 0]))
    for name in names:
        imgs, _ = imagery.read_scene_stack(cs, name)
        want = reference(imgs, weights(imgs, cloud_score(imgs, cfg)))
        arr = cs.open(f"composite/{name}")
        got = arr.read_all()
        if got.shape != (px, px, bands) or not np.isfinite(got).all():
            fail(f"composite {name}: shape {got.shape}, finite "
                 f"{np.isfinite(got).all()}")
        err = float(np.max(np.abs(got - want)))
        np.testing.assert_allclose(got, want, **F32_TOL,
                                   err_msg=f"composite {name} vs reference")
        top = arr.read_level(arr.spec.pyramid_levels)
        if not np.isfinite(top).all():
            fail(f"composite {name}: pyramid level not finite")
        print(f"[b] {name}: matches the f32 reference (max |diff| {err:.3g}); "
              f"pyramid top {top.shape}", flush=True)
    print(f"[b] check: {clock():.2f} s; peak HBM so far {peak_hbm(dev)}",
          flush=True)


def pick_depth(px: int, bands: int, lo: int, hi: int, budget: float) -> int:
    """Largest depth in [lo, hi] whose compiled ``grad_mag`` program (the
    §V.B stage that holds the stack) plans to use at most ``budget`` bytes."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    best = None
    for depth in range(lo, hi + 1):
        m = kops.grad_mag.lower(
            jax.ShapeDtypeStruct((depth, px, px, bands), jnp.float32),
            jax.ShapeDtypeStruct((depth, px, px), jnp.bool_),
        ).compile().memory_analysis()
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes)
        print(f"[c] depth {depth}: grad_mag plans {need} B of "
              f"{int(budget)} B", flush=True)
        if need > budget:
            break
        best = depth
    if best is None:
        fail(f"grad_mag at depth {lo} does not fit the chip")
    return best


def segmentation_phase(cfg, seed: int, dev, budget: float) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.apps import segmentation
    from repro.apps.composite import cloud_score
    from repro.data import imagery
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref

    px, bands = cfg.segmentation_tile_px, cfg.bands
    clock = stopwatch()
    depth = pick_depth(px, bands, SEGMENTATION_MIN_DEPTH, cfg.temporal_depth,
                       budget)
    print(f"[c] segmentation: {TILES} tiles of {px}x{px}x{bands} f32, depth "
          f"{depth} of the paper's {cfg.temporal_depth} (the largest whose "
          f"grad_mag program, shifted copies included, fits "
          f"{HBM_BUDGET:.0%} of the chip's memory); depth search "
          f"{clock():.2f} s", flush=True)
    clock = stopwatch()
    cs = new_store()
    names = write_tiles(cs, "segmentation-in", px, depth, cfg, seed + 100)
    print(f"[c] set-up (generate + zlib-write): {clock():.2f} s", flush=True)

    clock = stopwatch()
    kops.grad_mag(jnp.zeros((depth, px, px, bands), jnp.float32),
                  jnp.zeros((depth, px, px), bool),
                  impl="auto")[0].block_until_ready()
    segmentation.connected_components(
        jnp.zeros((px, px), bool)).block_until_ready()
    print(f"[c] compile (first calls, on zeros): {clock():.2f} s", flush=True)

    clock = stopwatch()
    out = segmentation.run_segmentation_campaign(
        cs, names, cfg, out_prefix="fields", engine_config=campaign_config())
    print(f"[c] run: {clock():.2f} s for {out['tiles']} tiles on "
          f"{out['report'].nodes} worker(s); queue {out['stats']}", flush=True)

    clock = stopwatch()
    reference = host_jit(kref.grad_mag)
    for name in names:
        fields = out["report"].results[name]["fields"]
        if fields < 1:
            fail(f"segmentation {name}: no field in the GeoJSON")
        imgs, valid = imagery.read_scene_stack(cs, name)
        valid &= cloud_score(imgs, cfg) < 0.5  # as temporal_edges masks it
        g, c = (np.asarray(a) for a in
                kops.grad_mag(jnp.asarray(imgs), jnp.asarray(valid)))
        g_ref, c_ref = reference(imgs, valid)
        np.testing.assert_allclose(g, g_ref, **F32_TOL,
                                   err_msg=f"grad_sum {name} vs reference")
        np.testing.assert_array_equal(c, c_ref,
                                      err_msg=f"count {name} vs reference")
        labels = cs.open(f"fields/{name}/labels")
        print(f"[c] {name}: grad_sum/count match the f32 reference (max "
              f"|diff| {float(np.max(np.abs(g - g_ref))):.3g}); {fields} "
              f"fields; labels {labels.spec.shape}", flush=True)
    print(f"[c] check: {clock():.2f} s; peak HBM so far {peak_hbm(dev)}",
          flush=True)


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=0,
                        help="seed of the generated scenes")
    args = parser.parse_args(argv)

    # the references run on the host's CPU backend next to the chip
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX's default device is {dev.platform!r}")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    import jax.numpy as jnp

    from repro.configs.festivus_imagery import ImageryConfig
    from repro.kernels import backend
    from repro.kernels import ops as kops

    print(f"[a] device: {dev.device_kind}, count {len(jax.devices())}; "
          f"compile cache {backend.enable_compile_cache()}", flush=True)
    if not backend.on_tpu() or backend.resolve_interpret(None):
        fail("kernels do not resolve to compiled mode on this device")
    cfg = ImageryConfig()
    stack = [jax.ShapeDtypeStruct(s, jnp.float32)
             for s in ((1, 8, 128, cfg.bands), (1, 8, 128))]
    for name, fn in (("composite", kops.composite),
                     ("grad_mag", kops.grad_mag)):
        if "tpu_custom_call" not in fn.lower(*stack).as_text():
            fail(f"kops.{name} lowers to no Pallas TPU kernel")
    print(f"[a] kernels compiled (not interpreted); kops.composite and "
          f"kops.grad_mag lower to tpu_custom_call; {WORKERS} campaign "
          f"worker(s)", flush=True)

    budget = HBM_BUDGET * dev.memory_stats()["bytes_limit"]
    composite_phase(cfg, COMPOSITE_DEPTH, args.seed, dev)
    segmentation_phase(cfg, args.seed, dev, budget)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
