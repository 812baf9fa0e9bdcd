"""Seconds per tile building the output's pyramid (``repro.pyramid`` spans:
read back, float64 pooling, encode and PUT of every level)."""

from chipbench import spanreduce


def read(run):
    return spanreduce.thread_s_per_tile(run, "pyramid")
