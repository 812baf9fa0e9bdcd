"""Milliseconds of device time per tile in the jitted connected components
(the segmentation's device tail)."""

PROGRAM = "jit_connected_components"


def read(run):
    found = run.module(PROGRAM)
    if found is None or not run.tiles_done:
        return None
    _, seconds = found
    return 1e3 * seconds / run.tiles_done
