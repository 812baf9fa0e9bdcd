"""Seconds per tile in the host band math: the cloud score over the whole
stack in NumPy."""

#: program functions whose outermost calls are counted
FUNCTIONS = ["repro.apps.composite.cloud_score"]


def read(run):
    return run.host_s_per_tile(FUNCTIONS)
