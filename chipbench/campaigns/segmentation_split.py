"""§V.B segmentation of one tile split over a mesh of chips: one tile
through the program's campaign entry, and the check of what it produced
against the reference of the whole tile.

The same campaign entry, outputs and check as ``segmentation.py``, with
the configuration's ``mesh`` passed to the program as
``ImageryConfig.segmentation_mesh``.  A program without that field fails
here, before set-up.  The tap sits on the program's
``repro.apps.segmentation.split_grad_mag``, whose ``(grad_sum, count)``
are the whole tile's, split over the chips; they stay on the devices
until the check copies them, after the window (2 x H x W x 4 bytes a tile
over the mesh).  The reference runs on the whole tile, so the split is
held to the answer of the unsplit chain.
"""

from __future__ import annotations

import dataclasses

from chipbench.campaigns.segmentation import NUMBERS, Check, stored
from chipbench.campaigns.segmentation import imagery_config as _unsplit

__all__ = ["NUMBERS", "Check", "stored", "imagery_config", "run_tile"]


def imagery_config(config):
    return dataclasses.replace(_unsplit(config),
                               segmentation_mesh=tuple(config["mesh"]))


def run_tile(cs, name: str, out_prefix: str, icfg, engine_config):
    """One tile through the campaign entry: {"report": its ClusterReport,
    "produced": (grad_sum, count) of its split_grad_mag call, on the
    devices, or None}."""
    from repro.apps import segmentation

    program = segmentation.split_grad_mag
    seen = []

    def tap(*args, **kwargs):
        out = program(*args, **kwargs)
        seen.append(tuple(out[:2]))
        return out

    segmentation.split_grad_mag = tap
    try:
        result = segmentation.run_segmentation_campaign(
            cs, [name], icfg, out_prefix=out_prefix,
            engine_config=engine_config)
    finally:
        segmentation.split_grad_mag = program
    produced = seen[0] if len(seen) == 1 else None
    return {"report": result["report"], "produced": produced}
