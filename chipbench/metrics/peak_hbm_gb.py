"""The fullest chip's peak device memory over the whole process, in GB
(``memory_stats()["peak_bytes_in_use"]`` after the window)."""


def read(run):
    return run.memory_peak_bytes / 1e9 if run.memory_peak_bytes else None
