"""Milliseconds of device time per chip per tile in the jitted split
connected components (local floods and the merge across the shards)."""

PROGRAM = "jit_split_components"


def read(run):
    found = run.module(PROGRAM)
    if found is None or not run.tiles_done:
        return None
    _, seconds = found  # summed over the chips
    return 1e3 * seconds / run.cell.chips / run.tiles_done
