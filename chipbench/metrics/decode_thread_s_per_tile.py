"""Thread-seconds per tile decoding chunks (``repro.decode`` spans: the
codec and the copy into an owned array), summed over the chunk store's
reader threads."""

from chipbench import spanreduce


def read(run):
    return spanreduce.thread_s_per_tile(run, "decode")
