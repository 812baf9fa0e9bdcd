"""Seconds per tile inside a task span (``repro.task``) but inside none of
the layer spans opened within it on the task's thread: the work that no
layer names yet."""

from chipbench import spanreduce


def read(run):
    return spanreduce.task_self_s_per_tile(run)
