"""Seconds per tile reading the input stack: Festivus reads from the object
store, chunk decode and assembly (store + VFS + codec layer)."""

#: program functions whose outermost calls are counted
FUNCTIONS = ["repro.data.imagery.read_scene_stack"]


def read(run):
    return run.host_s_per_tile(FUNCTIONS)
