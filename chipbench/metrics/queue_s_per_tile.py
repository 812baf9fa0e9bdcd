"""Seconds per tile of the window outside every task span (``repro.task``):
the campaign call around its tasks, the engine and queue, and the step
between tiles."""

from chipbench import spanreduce


def read(run):
    return spanreduce.outside_task_s_per_tile(run)
