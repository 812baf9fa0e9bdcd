"""A checkout of the benchmark with small cells added as new files only.

``make(tmp_path)`` copies ``BENCHMARK.json`` and ``chipbench/`` into
``tmp_path``, links the program's ``src/`` beside them, and adds, without
editing any file that was there: one small configuration per campaign kind
(``tiny_<kind>.json``), one traffic mix, one per-layer metric and their
entries in the copy's ``BENCHMARK.json``.  The cells are
``tiny-composite`` and ``tiny-segment``.
"""

from __future__ import annotations

import json
import os
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
PX = 128
DEPTH = 4

TRAFFIC = {"stored_tiles": 2, "codec": "zlib", "cloud_cover": 0.3,
           "fields": 12, "cloud_blobs": 3}

#: a metric that a later change could add: tiles completed in the window
METRIC = '''"""Tiles completed in the window."""


def read(run):
    return float(run.tiles_done)
'''


def make(tmp_path: Path) -> Path:
    root = Path(tmp_path) / "checkout"
    root.mkdir()
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(REPO / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "testdata"))
    os.symlink(REPO / "src", root / "src")
    bench = root / "chipbench"
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    for kind, base in (("composite", "composite_v5c"),
                       ("segment", "segment_v5b")):
        config = json.loads((bench / "configs" / f"{base}.json").read_text())
        config.update(name=f"tiny_{kind}", tile_px=PX, depth=DEPTH,
                      chunk_px=PX // 2)
        (bench / "configs" / f"tiny_{kind}.json").write_text(
            json.dumps(config))
        manifest["configs"].append({
            "name": f"tiny_{kind}",
            "source": "https://arxiv.org/abs/1702.03935",
            "file": f"chipbench/configs/tiny_{kind}.json",
            "reduced": ["tile_px", "depth", "chunk_px"], "why": "test size"})
        manifest["workloads"].append({
            "name": f"tiny-{kind}", "config": f"tiny_{kind}",
            "traffic": "tiny-mix", "chips": 1, "why": "test size"})
    (bench / "traffic" / "tiny-mix.json").write_text(json.dumps(TRAFFIC))
    (bench / "metrics" / "tiles_seen.py").write_text(METRIC)
    manifest["per_layer"].append({
        "name": "tiles_seen", "unit": "tiles", "better": "higher",
        "source": "host_clock", "layer": "device",
        "moves": "scene_mpx_per_s",
        "workloads": ["tiny-composite", "tiny-segment"]})
    for metric in manifest["per_layer"]:
        if "read_decode" in metric["name"] or "band_math" in metric["name"]:
            metric["workloads"] += ["tiny-composite", "tiny-segment"]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def run(root: Path, workload: str, seed: int = 7, seconds: float = 0.3,
        trace: int = 0) -> dict:
    """One run of ``workload`` in ``root``, on whatever JAX finds."""
    import time

    from chipbench import harness

    return harness.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", str(trace)],
                        root=root, started=time.monotonic(),
                        require_chip=False)
