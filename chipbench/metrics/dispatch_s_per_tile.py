"""Seconds per tile in calls that enqueue device work without waiting for it
(``repro.dispatch`` spans): tracing, dispatch and eager operations."""

from chipbench import spanreduce


def read(run):
    return spanreduce.thread_s_per_tile(run, "dispatch")
