"""Seconds per tile sending host arrays to the device until they are there
(``repro.h2d`` spans)."""

from chipbench import spanreduce


def read(run):
    return spanreduce.thread_s_per_tile(run, "h2d")
