"""Thread-seconds per tile fetching chunk objects through Festivus
(``repro.fetch`` spans: the stat lookup and the block reads), summed over
the chunk store's reader threads."""

from chipbench import spanreduce


def read(run):
    return spanreduce.thread_s_per_tile(run, "fetch")
