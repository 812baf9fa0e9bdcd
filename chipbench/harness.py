"""One run of one cell: set-up, a measured window, the check, the result.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name, each in a file of its own:

* the cell in ``BENCHMARK.json`` (``workloads``), naming a configuration
  and a traffic mix;
* the configuration's file (``configs``' ``file``), which names its
  campaign kind and holds the limits of the check;
* the traffic mix, ``chipbench/traffic/<traffic>.json``;
* the campaign kind, ``chipbench/campaigns/<campaign>.py``: how one tile
  goes through the program, and how its stored outputs are checked;
* each per-layer metric, ``chipbench/metrics/<metric>.py``, with a
  ``read(run)`` that returns the metric or None.

A run: (1) refuses to go on without the chips the cell asks for; (2) makes
the cell's tile stacks on the device from ``--seed`` and stores them
through the program's chunk store; (3) runs one warm-up tile of the same
shapes through the campaign entry, so that every program is compiled or
loaded from the persistent cache; (4) measures a window of whole tiles,
one campaign call per tile, cycling through the stored stacks, which ends
at the first tile to finish at or after ``--seconds``; (5) reads back and
checks every tile of the window against the reference.  The last line of
standard output is the result; the numbers compared, each with its limit,
are the last lines of standard error.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import glob
import importlib.util
import json
import os
import resource
import shutil
import sys
import tempfile
import time
import traceback
from pathlib import Path
from typing import Dict, List, Optional

#: the benchmark's directory, found under the checkout's root by its name
BENCH_NAME = Path(__file__).resolve().parent.name
WINDOW_SPAN = "chipbench_window"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Refused(SystemExit):
    """The run cannot be made here; it prints no result."""


def log(*parts) -> None:
    print("[chipbench]", *parts, flush=True)


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    if not path.is_file():
        raise Refused(f"chipbench: no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """A workload of ``BENCHMARK.json`` with everything it names."""

    def __init__(self, root: Path, name: str):
        self.bench = bench = root / BENCH_NAME
        manifest = load_json(root / "BENCHMARK.json")
        entries = {w["name"]: w for w in manifest["workloads"]}
        if name not in entries:
            raise Refused(f"chipbench: no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.entry = entries[name]
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in manifest["configs"]}
        self.config = load_json(root / configs[self.entry["config"]]["file"])
        self.traffic = load_json(
            bench / "traffic" / f"{self.entry['traffic']}.json")
        self.campaign = load_module(
            bench / "campaigns" / f"{self.config['campaign']}.py",
            f"chipbench_campaign_{self.config['campaign']}")

        def here(metric) -> bool:
            return name in metric.get("workloads", [name])

        self.end_to_end = [m for m in manifest["end_to_end"] if here(m)]
        self.per_layer = [m for m in manifest["per_layer"] if here(m)]


class TileRecord:
    def __init__(self, index: int, stack: int, out_prefix: str):
        self.index = index
        self.stack = stack
        self.out_prefix = out_prefix
        self.start = self.end = 0.0
        self.error: Optional[str] = None
        self.produced = None  # what the campaign kept for the check


class Run:
    """What a per-layer metric's reader sees of one run."""

    def __init__(self, cell: Cell, device_kind: str, peaks: dict):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.device_kind = device_kind
        self.peaks = peaks
        self.tiles: List[TileRecord] = []
        self.window_s = 0.0
        self.memory_peak_bytes = 0
        self.trace = None  # tracereduce.Trace of a traced run

    @property
    def tiles_done(self) -> int:
        return sum(1 for t in self.tiles if t.error is None)

    def peak(self, key: str) -> float:
        """A peak of this run's device from ``peaks.json``; an unknown
        device is an error."""
        devices = self.peaks["devices"]
        if self.device_kind not in devices:
            raise KeyError(f"no peaks for device {self.device_kind!r} in "
                           f"chipbench/peaks.json")
        return float(devices[self.device_kind][key])

    def host_s_per_tile(self, qualnames) -> Optional[float]:
        """Seconds per tile inside the program functions ``qualnames``
        (outermost calls only), from the traced window."""
        from chipbench import tracereduce

        if self.trace is None or not self.tiles_done:
            return None
        names = [tracereduce.event_name(q) for q in qualnames]
        ns = tracereduce.outermost_ns(self.trace, names)
        return ns / 1e9 / self.tiles_done if ns else None

    def module(self, prefix: str):
        """(runs, device seconds) of the jitted programs named ``prefix``
        in the traced window, or None where none ran."""
        from chipbench import tracereduce

        if self.trace is None:
            return None
        count, ns = tracereduce.module_ns(self.trace, prefix)
        return (count, ns / 1e9) if count else None


def scene_params(cell: Cell):
    from chipbench.scenes import SceneParams

    c, t = cell.config, cell.traffic
    return SceneParams(px=c["tile_px"], bands=c["bands"], depth=c["depth"],
                       fields=t["fields"], cloud_cover=t["cloud_cover"],
                       blobs=t["cloud_blobs"],
                       layout_seed=t.get("layout_seed"),
                       layouts=t["stored_tiles"])


def store_stack(cs, name: str, timesteps, params, chunk_px: int,
                codec: str) -> None:
    """Write a stack, timestep by timestep, in the layout the program's
    ``imagery.read_scene_stack`` reads: ``<name>/images`` float32
    [T, H, W, C] and ``<name>/valid`` uint8 [T, H, W], one timestep and
    ``chunk_px``² pixels to a chunk.  The chunk store encodes a region's
    chunks on its own threads."""
    import numpy as np

    p = params
    images = cs.create(f"{name}/images", (p.depth, p.px, p.px, p.bands),
                       np.float32, (1, chunk_px, chunk_px, p.bands),
                       codec=codec)
    valid = cs.create(f"{name}/valid", (p.depth, p.px, p.px), np.uint8,
                      (1, chunk_px, chunk_px), codec=codec)
    for t, (img, ok) in enumerate(timesteps):
        img = np.asarray(img)
        ok = np.asarray(ok).astype(np.uint8)
        images.write_region((t, 0, 0, 0), img[None])
        valid.write_region((t, 0, 0), ok[None])


def generated(tile):
    """A tile's timesteps as NumPy arrays, each started on the device one
    step ahead of its copy to the host."""
    pending = tile.dispatch(0)
    for t in range(len(tile)):
        current = pending
        if t + 1 < len(tile):
            pending = tile.dispatch(t + 1)
        yield tile.fetch(current)


def warm_timesteps(params):
    """Timesteps of the warm-up stack: a one-pixel checkerboard in the red
    band, every pixel valid.  Every pixel is then an edge, so the
    segmentation's components converge in one pass and its polygons are
    none; the composite's work does not depend on content.  It compresses
    to almost nothing."""
    import numpy as np

    p = params
    img = np.zeros((p.px, p.px, p.bands), np.float32)
    img[..., 0] = (np.add.outer(np.arange(p.px), np.arange(p.px)) % 2)
    ok = np.ones((p.px, p.px), bool)
    return [(img, ok)] * p.depth


def cache_dir(root: Path) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where set, else ``<checkout>/.jax_cache`` (a fixed path: the path is
    part of each entry's key)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(root / ".jax_cache"))


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    return args


def main(argv=None, *, root: Path, started: float,
         require_chip: bool = True, keep_trace: Optional[Path] = None) -> dict:
    """Run one cell once; return the result line (also printed).
    ``keep_trace`` is where a traced run's ``.xplane.pb`` is copied."""
    args = parse(argv)
    cell = Cell(root, args.workload)

    import jax

    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        raise Refused(f"chipbench: {cell.name} needs {cell.chips} TPU "
                      f"chip(s); JAX found {len(devices)} "
                      f"{devices[0].platform!r} device(s)")
    devices = devices[:cell.chips]
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    jax.config.update("jax_compilation_cache_dir", cache_dir(root))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles: List[str] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(kw.get("fun_name", "?"))
        if event == COMPILE_EVENT else None)

    import jax.numpy as jnp

    from chipbench.scenes import Tile
    from repro.core import ChunkStore, Festivus, InMemoryObjectStore
    from repro.launch.cluster import ClusterConfig

    config, traffic, campaign = cell.config, cell.traffic, cell.campaign
    params = scene_params(cell)
    icfg = campaign.imagery_config(config)

    def engine():
        # one worker per chip; a handler's error fails its tile at once
        return ClusterConfig(nodes=config["workers"], max_retries=0)

    # -- set-up ---------------------------------------------------------------
    cs = ChunkStore(Festivus(InMemoryObjectStore()), "bucket")
    stacks = [Tile(params, args.seed, i)
              for i in range(traffic["stored_tiles"])]
    clock = time.perf_counter()
    for i, tile in enumerate(stacks):
        store_stack(cs, f"in/t{i}", generated(tile), params,
                    config["chunk_px"], traffic["codec"])
    stored = cs.fs.store.stats.bytes_written
    log(f"stored {len(stacks)} stacks of {params.depth}x{params.px}^2x"
        f"{params.bands} ({traffic['codec']}, {stored} B) in "
        f"{time.perf_counter() - clock:.2f} s")
    clock = time.perf_counter()
    store_stack(cs, "in/warm", warm_timesteps(params), params,
                config["chunk_px"], traffic["codec"])
    campaign.run_tile(cs, "in/warm", "warm", icfg, engine())
    gc.collect()
    log(f"warm-up tile in {time.perf_counter() - clock:.2f} s; "
        f"{len(compiles)} programs compiled or loaded so far")
    setup_s = time.monotonic() - started

    # -- window ---------------------------------------------------------------
    peaks = load_json(cell.bench / "peaks.json")
    run = Run(cell, devices[0].device_kind, peaks)
    trace_dir = (tempfile.mkdtemp(prefix="chipbench-trace-") if args.trace
                 else None)
    if trace_dir:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 1
        options.host_tracer_level = 1  # annotations, not runtime internals
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    compiled_before = len(compiles)
    span = (jax.profiler.TraceAnnotation(WINDOW_SPAN) if trace_dir
            else contextlib.nullcontext())
    hits = misses = 0
    with span:
        start = time.perf_counter()
        while True:
            rec = TileRecord(len(run.tiles), len(run.tiles) % len(stacks),
                             f"out/w{len(run.tiles)}")
            rec.start = time.perf_counter() - start
            try:
                out = campaign.run_tile(cs, f"in/t{rec.stack}", rec.out_prefix,
                                        icfg, engine())
                rec.produced = out["produced"]
                fs = out["report"].festivus_stats
                hits += fs.cache_hits
                misses += fs.cache_misses
            except Exception:  # noqa: BLE001 — a failed tile is counted
                rec.error = traceback.format_exc(limit=4)
            rec.end = time.perf_counter() - start
            run.tiles.append(rec)
            if rec.end >= args.seconds:
                break
    run.window_s = run.tiles[-1].end
    in_window = compiles[compiled_before:]
    if trace_dir:
        jax.profiler.stop_trace()
    run.memory_peak_bytes = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    for rec in run.tiles:
        log(f"tile {rec.index} (stack {rec.stack}): {rec.start:.3f} -> "
            f"{rec.end:.3f} s"
            + (f" FAILED\n{rec.error}" if rec.error else ""))
    log(f"window: {len(run.tiles)} tiles, {run.tiles_done} completed, "
        f"{run.window_s!r} s; festivus block cache {hits} hits, {misses} "
        f"misses; programs compiled or loaded in the window: {len(in_window)}"
        + (f" {in_window}" if in_window else ""))
    log(f"device peak {run.memory_peak_bytes} B; host peak resident "
        f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024} B")
    out = None
    gc.collect()

    # -- check ----------------------------------------------------------------
    clock = time.perf_counter()
    check = campaign.Check(config, jnp.float32)
    worst: Dict[str, float] = {n: 0.0 for n in campaign.NUMBERS}
    limits = config["limits"]
    failed = 0
    for rec in run.tiles:
        if rec.error is not None:
            failed += 1
            continue
        numbers = check.numbers(cs, f"in/t{rec.stack}", rec.out_prefix,
                                stacks[rec.stack], rec.produced)
        bad = [n for n in campaign.NUMBERS if not numbers[n] <= limits[n]]
        failed += bool(bad)
        for n in campaign.NUMBERS:
            worst[n] = max(worst[n], numbers[n])
        log(f"tile {rec.index} check: " + ", ".join(
            f"{n} {numbers[n]!r}" for n in campaign.NUMBERS))
    correct = failed == 0 and run.tiles_done > 0
    log(f"check: {time.perf_counter() - clock:.2f} s")

    # -- result ---------------------------------------------------------------
    result = {"correct": correct, "attempted": len(run.tiles),
              "failed": failed}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    if trace_dir:
        from chipbench import tracereduce

        files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
        run.trace = tracereduce.load(files[0])
        if keep_trace is not None:
            shutil.copy(files[0], keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        metrics = {}
        for m in cell.per_layer:
            reader = load_module(cell.bench / "metrics" / f"{m['name']}.py",
                                 f"chipbench_metric_{m['name']}")
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = tracereduce.busy_ns(run.trace) / 1e9
        device["window_s"] = run.trace.window_ns / 1e9
        program_files = {p.name
                         for p in (root / "src" / "repro").rglob("*.py")}
        breakdown = {
            "device_ops": [list(x) for x in tracereduce.top_ops(run.trace)],
            "idle_gaps": [list(x) for x in tracereduce.name_gaps(
                run.trace, tracereduce.idle_gaps(run.trace), program_files)],
        }
    else:
        scene_px = params.depth * params.px * params.px
        metrics = {
            "scene_mpx_per_s": {
                "value": run.tiles_done * scene_px / 1e6 / run.window_s,
                "unit": "Mpx/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        metrics = {m["name"]: metrics[m["name"]] for m in cell.end_to_end}
        breakdown = None
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {n: {"value": worst[n], "limit": limits[n]}
                        for n in campaign.NUMBERS}
    sys.stdout.flush()
    for n in campaign.NUMBERS:
        print(f"check {n} {worst[n]!r} limit {limits[n]!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return result
