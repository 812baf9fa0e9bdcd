"""Seconds per tile the host waits for device results before copying them
back (``repro.device_wait`` spans)."""

from chipbench import spanreduce


def read(run):
    return spanreduce.thread_s_per_tile(run, "device_wait")
