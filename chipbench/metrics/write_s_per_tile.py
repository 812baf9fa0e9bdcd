"""Seconds per tile writing the composite: chunk encode and PUT, and the
float64 pyramid build."""

#: program functions whose outermost calls are counted
FUNCTIONS = ["repro.core.chunkstore.ChunkedArray.write_region",
             "repro.core.chunkstore.ChunkedArray.build_pyramid"]


def read(run):
    return run.host_s_per_tile(FUNCTIONS)
