"""§V.C composite: one tile through the program's campaign entry, and the
check of what it stored against the reference.

The window calls :func:`run_tile` once per tile; it goes through the
program's whole path (object store, Festivus, chunk store and codec, host
band math, device kernels, encode, store and pyramid).  :class:`Check`
reads back what each call stored, through the chunk store, and compares
it with ``references/composite.py`` on the same seeded stack.
"""

from __future__ import annotations

import numpy as np

from chipbench.references import composite as reference

#: the numbers compared; their limits are in the configuration's file
NUMBERS = ("composite_max_abs_err", "pyramid_max_abs_err")


def imagery_config(config):
    from repro.configs.festivus_imagery import ImageryConfig

    return ImageryConfig(
        bands=config["bands"], composite_tile_px=config["tile_px"],
        chunk_px=config["chunk_px"],
        cloud_reflectance_threshold=config["cloud_reflectance_threshold"],
        edge_threshold=config["edge_threshold"])


def run_tile(cs, name: str, out_prefix: str, icfg, engine_config):
    """One tile through the campaign entry: {"report": its ClusterReport,
    "produced": None} (every output is read back from the store)."""
    from repro.apps.composite import run_composite_campaign

    result = run_composite_campaign(cs, [name], icfg, out_prefix=out_prefix,
                                    engine_config=engine_config)
    return {"report": result["report"], "produced": None}


def stored(cs, name: str, out_prefix: str, levels: int):
    """(composite, pyramid top level) as the campaign stored them."""
    arr = cs.open(f"{out_prefix}/{name}")
    return arr.read_all(), arr.read_level(levels)


class Check:
    """Compares stored outputs with the reference of their stack.

    ``dtype`` is the reference's precision: float32 for the benchmark's
    runs, bfloat16 for the control."""

    def __init__(self, config, dtype):
        self.config = config
        self.dtype = dtype
        self._refs = {}

    def reference(self, tile):
        if tile not in self._refs:
            comp, top = reference.composite(
                tile, self.config["cloud_reflectance_threshold"],
                self.config["pyramid_levels"], self.dtype)
            self._refs[tile] = (comp, top)
        return self._refs[tile]

    def numbers(self, cs, name: str, out_prefix: str, tile, produced=None):
        """{number: value} for one stored output."""
        got, got_top = stored(cs, name, out_prefix,
                              self.config["pyramid_levels"])
        want, want_top = self.reference(tile)
        if got.shape != want.shape or got_top.shape != want_top.shape:
            return {n: float("inf") for n in NUMBERS}
        return {
            "composite_max_abs_err": float(np.max(np.abs(got - want))),
            "pyramid_max_abs_err": float(np.max(np.abs(got_top - want_top))),
        }
