"""Seconds per tile turning the label array into GeoJSON features on the
host."""

#: program functions whose outermost calls are counted
FUNCTIONS = ["repro.apps.segmentation.polygonize"]


def read(run):
    return run.host_s_per_tile(FUNCTIONS)
